"""prefill.ms_per_ktok: device milliseconds of the prefill programs
(`Model.prefill_one` / `prefill_group` executions on the trace's "XLA
Modules" line) per thousand prompt tokens admitted in the window.
Prefill layer; moves ttft_p90_s."""
from bench import trace_reduce

PROGRAMS = ("prefill_one", "prefill_group")


def read(ctx):
    secs = trace_reduce.time_by_name(ctx["events"], trace_reduce.MODULES,
                                     PROGRAMS)
    toks = ctx["prompt_tokens"]
    if secs <= 0 or toks == 0:
        return None
    return secs * 1e3 / (toks / 1e3)
