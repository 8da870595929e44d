"""Request arithmetic: censored and missing times to first token, time
per output token, lane occupancy, nearest-rank percentiles."""
import math
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import requests  # noqa: E402


def stat(outcome="done", t_arrival=0.0, t_first=1.0, t_done=2.0, tokens=3):
    return SimpleNamespace(outcome=outcome, t_arrival=t_arrival,
                           t_first=t_first, t_done=t_done,
                           tokens=list(range(tokens)))


def test_ttft_counts_censored_and_missing():
    stats = [stat(t_arrival=0.5, t_first=1.25),
             # queued when the window closed: the loop stamps
             # t_first = t_done, the censored value
             stat("deadline", t_arrival=2.0, t_first=9.0, t_done=9.0,
                  tokens=0),
             stat("failed"), stat("rejected")]
    assert requests.ttft_s(stats) == [0.75, 7.0, math.inf, math.inf]


def test_tpot_needs_two_tokens():
    stats = [stat(t_first=1.0, t_done=2.0, tokens=5),
             stat(t_first=1.0, t_done=1.0, tokens=1),
             stat("deadline", t_first=0.5, t_done=0.8, tokens=2)]
    got = requests.tpot_ms(stats)
    assert len(got) == 2
    assert math.isclose(got[0], 250.0) and math.isclose(got[1], 300.0)


def test_percentile_nearest_rank():
    xs = list(range(1, 11))
    assert requests.percentile(xs, 90) == 9
    assert requests.percentile(xs, 91) == 10
    assert requests.percentile([3.0, math.inf], 90) == math.inf
    assert requests.percentile([5.0], 90) == 5.0
    assert math.isnan(requests.percentile([], 90))


def test_lane_occupancy():
    # 12 blocks of 8 steps over 16 lanes = 1536 lane-steps
    assert requests.lane_occupancy_pct(768, 12, 8, 16) == 50.0
    assert math.isnan(requests.lane_occupancy_pct(5, 0, 8, 16))


def test_output_tokens_and_outcomes():
    stats = [stat(tokens=4), stat("deadline", tokens=2), stat("failed",
                                                             tokens=0)]
    assert requests.output_tokens(stats) == 6
    assert requests.outcomes(stats) == {"done": 1, "deadline": 1,
                                        "failed": 1}
