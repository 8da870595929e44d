"""host.round_self_ms: the host's own milliseconds per scheduler round,
when the device may sit idle: a `serve.round` span's duration minus the
time its `serve.wait` descendants (blocking device->host reads) cover,
averaged over the rounds that start in the traced window and dispatch
one of its decode blocks (`serve.block`). Spans recorded by the program.
Host scheduler layer (`ServeLoop.run`); moves tpot_p90_ms."""
from bench import program_spans


def read(ctx):
    w = program_spans.window(ctx)
    if w is None:
        return None
    lo, hi, recs = w
    by_id = {r.sid: r for r in recs}
    rounds = {b.parent for b in recs if b.name == "serve.block"
              and lo <= b.t0 and b.t1 <= hi}
    rounds = {sid for sid in rounds if sid in by_id
              and by_id[sid].name == "serve.round" and by_id[sid].t0 >= lo}
    waits = {sid: [] for sid in rounds}
    for r in recs:
        if r.name != "serve.wait":
            continue
        up = by_id.get(r.parent)
        while up is not None and up.sid not in rounds:
            up = by_id.get(up.parent)
        if up is not None:
            waits[up.sid].append((r.t0, r.t1))
    own = []
    for sid in rounds:
        covered, end = 0.0, float("-inf")
        for s, e in sorted(waits[sid]):
            s = max(s, end)
            if e > s:
                covered += e - s
                end = e
        own.append((by_id[sid].dur - covered) * 1e3)
    return program_spans.mean(own)
