"""decode_step.mfu: the FLOPs the window's decode steps need (weight
matmuls and LM head per lane-step, the CAM pass over the live window and
exact attention over the select_k winners; `bench/flops.py`), over the
decode-block programs' device time (the "XLA Modules" executions
dispatched in `bench.step_block`) times the bf16 peak. Decode block
layer; moves tpot_p90_ms. It bounds every kernel of the step: a change
that takes a kernel off the path still shows here."""
from bench import flops, trace_reduce

# the decode block is the program the loop's decode phase dispatches
# (its jit is a functools.partial, named jit__unknown in the trace)
SPAN = "bench.step_block"
OTHERS = ("prefill", "admit")


def read(ctx):
    secs, _ = trace_reduce.time_in_spans(ctx["events"], SPAN, OTHERS)
    m, p = ctx["model"], ctx["prune"]
    slots = p["heavy_budget"] + p["reserve"]
    need = sum(sum(flops.decode_step_flops(m, p["select_k"], fill).values())
               for fill in flops.block_fills(ctx["blocks"],
                                             p["heavy_budget"], slots))
    if secs <= 0 or need == 0:
        return None
    return 100.0 * need / (secs * ctx["peak"]["bf16_flops_per_s"])
