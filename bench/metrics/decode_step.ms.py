"""decode_step.ms: device milliseconds of the decode-block programs
(`decode_block_lanes`: the "XLA Modules" executions that the loop's
decode phase, `bench.step_block`, dispatched) per decode step they ran
(the window's decode blocks x block). Decode block layer; moves
tpot_p90_ms."""
from bench import trace_reduce

# the decode block is the program the loop's decode phase dispatches
# (its jit is a functools.partial, named jit__unknown in the trace)
SPAN = "bench.step_block"
OTHERS = ("prefill", "admit")


def read(ctx):
    secs, _ = trace_reduce.time_in_spans(ctx["events"], SPAN, OTHERS)
    steps = len(ctx["blocks"]) * ctx["block"]
    if secs <= 0 or steps == 0:
        return None
    return secs * 1e3 / steps
