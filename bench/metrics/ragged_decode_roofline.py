"""ragged_decode_roofline: the least time the pruned attention of the
window's decode steps needs (the larger of its FLOPs over the bf16 peak
and its bytes — int8 mirror over the live window, the winners' K and V
rows, the scales — over HBM bandwidth; `bench/flops.py`), over the
summed device time of the ragged decode kernel
(`kernels/ragged_decode.py`, ops named after it on the "XLA Ops" line).
Decode attention kernel layer; moves tpot_p90_ms. Silent where the
kernel is not on the path."""
from bench import flops, trace_reduce

KERNEL = ("ragged_decode",)


def read(ctx):
    secs = trace_reduce.time_by_name(ctx["events"], trace_reduce.OPS, KERNEL)
    m, p, peak = ctx["model"], ctx["prune"], ctx["peak"]
    slots = p["heavy_budget"] + p["reserve"]
    work, nbytes = 0, 0
    for fill in flops.block_fills(ctx["blocks"], p["heavy_budget"], slots):
        f, b = flops.decode_attention_work(m, p["select_k"], fill)
        work, nbytes = work + f, nbytes + b
    least = flops.roofline_seconds(work, nbytes, peak)
    if secs <= 0 or least == 0:
        return None
    return 100.0 * least / secs
