"""sched.lane_occupancy: share of the window's decode lane-steps that
emitted a token — tokens the window's decode blocks emitted over
(decode blocks x block x lanes). Host scheduler layer (`ServeLoop.run` /
`schedule`); moves output_tok_s."""
from bench import requests


def read(ctx):
    if not ctx["blocks"]:
        return None
    return requests.lane_occupancy_pct(requests.block_tokens(ctx["blocks"]),
                                       len(ctx["blocks"]), ctx["block"],
                                       ctx["lanes"])
