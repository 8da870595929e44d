"""sched.queue_wait_s: mean seconds from a request's arrival to the start
of the admission that took it (`serve.request.queue`, recorded by the
program), over the requests that arrived in the traced window and were
admitted before it closed. Host scheduler layer (`ServeLoop.schedule`);
moves ttft_p90_s."""
from bench import program_spans


def read(ctx):
    w = program_spans.window(ctx)
    if w is None:
        return None
    lo, hi, recs = w
    return program_spans.mean(list(
        program_spans.admitted_waits(recs, lo, hi).values()))
