"""admit.first_token_s: mean seconds from the start of a request's
admission to its first token on the host (`serve.request.first_token`,
recorded by the program): the prefill on the device plus the decode
block that emits the token. Over the requests `sched.queue_wait_s`
counts that got a first token inside the traced window. Prefill layer;
moves ttft_p90_s."""
from bench import program_spans


def read(ctx):
    w = program_spans.window(ctx)
    if w is None:
        return None
    lo, hi, recs = w
    waits = program_spans.admitted_waits(recs, lo, hi)
    return program_spans.mean([
        r.dur for r in recs
        if r.name == "serve.request.first_token" and r.rid in waits
        and r.attrs.get("tokens", 0) > 0 and lo <= r.t0 and r.t1 <= hi])
