"""A run end to end on the CPU at a tiny size, the chip check skipped:
correct with the served path intact; not correct with a token altered
where the decode block produces it, with one winner too few, or with the
wrong slot evicted; the int8 control, judged as a run judges, comes out
not correct; the plain reference agrees with the program run in f32
(Granite's multipliers folded into the program's weights); and the
command refuses to run without a TPU."""
import dataclasses
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, spec, weights  # noqa: E402
from bench.reference import Reference  # noqa: E402

MODEL = {"num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
         "vocab_size": 257, "hidden_act": "silu", "norm": "rmsnorm",
         "tie_word_embeddings": True, "rope_theta": 10000.0,
         "attention_bias": False}
PRUNE = {"policy": "unicaim", "heavy_budget": 48, "reserve": 16,
         "select_k": 16, "score_bits": 3, "query_bits": 4,
         "sink_tokens": 4, "recent_window": 8, "fused": "auto"}
# the tiny cell's limit on the first-token gap: bf16 against the f32
# reference reads 0 to 3e-3 at this size and up to 12 layers of width
# 512; a replaced token reads like a random one (~0.1 and up here)
TINY_LIMIT = 0.05
# and on the lockstep decode step: the composed engine on the CPU reads 0
# to float rounding against the reference; the int8 control 1e-2 to 3e-2
TINY_LOCKSTEP = 1e-3
# the lockstep prefill: bf16 attention probabilities read ~1.7e-3 here
# (the rows' root mean square), the int8 control ~1.4e-2
TINY_PREFILL = 5e-3
# the static eviction at prefill: the composed engine's column sums read
# 3e-4 to 7.4e-4 here, the int8 control 1.8e-3 to 5.7e-3; the lightest
# tokens kept read far above both
TINY_EVICT = 2e-3
GRANITE = {"embedding_multiplier": 12.0, "attention_multiplier": 0.125,
           "residual_multiplier": 0.22, "logits_scaling": 8.0}


def tiny_cell(model=MODEL, limit=TINY_LIMIT):
    cfg = {"name": "tiny", "model": dict(model), "prune": dict(PRUNE),
           "serve": {"lanes": 2, "block": 4, "window": "auto",
                     "buckets": "auto", "max_new": 8}}
    wl = {"name": "tiny", "config": "tiny",
          "arrivals": {"kind": "poisson", "rate_per_s": 2.0},
          "classes": [{"share": 1.0,
                       "prompt": {"dist": "loguniform", "min": 60, "max": 120},
                       "output": {"dist": "loguniform", "min": 14, "max": 24}}],
          "lead_s": 1.0,
          "check": {"first_token_gap_limit": limit,
                    "prefill_lockstep_limit": TINY_PREFILL,
                    "prefill_evict_limit": TINY_EVICT,
                    "decode_lockstep_limit": TINY_LOCKSTEP}}
    bj = spec.benchmark(ROOT)
    return {"entry": {"name": "tiny", "chips": 1}, "workload": wl,
            "config": cfg, "end_to_end": bj["end_to_end"],
            "per_layer": [m for m in bj["per_layer"]
                          if m["name"] == "sched.lane_occupancy"]}


def run(cell, trace=False):
    return harness.run_cell(cell, 2**33 + 11, 2.0, trace,
                            time.perf_counter(),
                            peak=harness.peak_table("TPU v5 lite"))


def test_tiny_run_is_correct():
    r = run(tiny_cell(), trace=True)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 4
    assert r["compared"]["first_token_gap"]["value"] <= TINY_LIMIT
    assert r["compared"]["prefill_lockstep_err"]["value"] <= TINY_PREFILL
    assert r["compared"]["prefill_evict_err"]["value"] <= TINY_EVICT
    assert r["compared"]["decode_lockstep_err"]["value"] <= TINY_LOCKSTEP
    assert list(r)[-1] == "compared"
    assert "sched.lane_occupancy" in r["metrics"]
    assert r["device"]["window_s"] > 0


def test_tiny_backlog_run_is_correct():
    """A backlog cell (granite-gen's arrivals and metrics, lead 0): the
    window opens at the run's start, every queued request counts, and
    the cell reports tokens/s and no time to first token."""
    cell = tiny_cell()
    cell["workload"].update(arrivals={"kind": "backlog", "per_lane": 16},
                            lead_s=0)
    gen = spec.cell("granite-gen", ROOT)
    cell.update(end_to_end=gen["end_to_end"], per_layer=gen["per_layer"])
    r = run(cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 32
    assert set(r["metrics"]) == {"output_tok_s", "tpot_p90_ms", "setup_s"}
    assert r["metrics"]["output_tok_s"]["value"] > 0


def test_altered_token_is_caught(monkeypatch):
    from repro.launch import serve
    made = serve._lanes_block_fn

    def broken(*a, **k):
        fn = made(*a, **k)

        def call(*args):
            out = list(fn(*args))
            toks, emitted = out[6], out[7]
            # the first token each lane emits in this block, replaced
            out[6] = jnp.where(jnp.arange(toks.shape[0])[:, None] == 0,
                               (toks + 1) % MODEL["vocab_size"], toks)
            del emitted
            return tuple(out)
        call._cache_size = fn._cache_size
        return call

    monkeypatch.setattr(serve, "_lanes_block_fn", broken)
    r = run(tiny_cell())
    assert not r["correct"]
    assert r["compared"]["first_token_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("fault", ["select_k-1", "evict-most",
                                   "keep-lightest"])
def test_fault_is_caught_in_lockstep(monkeypatch, fault):
    """Faults that the served tokens alone would hide: one winner too few
    per decode row, the most-scored slot evicted instead of the
    least-scored, the lightest prompt tokens kept at prefill."""
    from repro.core import cache, pruning
    from repro.models.transformer import Model
    number = "decode_lockstep_err"
    if fault == "keep-lightest":
        fill = pruning.prefill_fill

        def lightest(c, k, v, acc, prune, length=None):
            return fill(c, k, v, -acc, prune, length=length)
        monkeypatch.setattr(pruning, "prefill_fill", lightest)
        number = "prefill_evict_err"
    elif fault == "select_k-1":
        made = harness.program_model

        def planted(cfg):
            m = made(cfg)
            return Model(m.cfg, dataclasses.replace(
                m.prune, select_k=m.prune.select_k - 1))
        monkeypatch.setattr(harness, "program_model", planted)
    else:
        def evict_most(c, prune):
            score = jnp.where(cache.evictable_mask(c, prune), c.acc, -jnp.inf)
            full = c.fill[:, None] >= c.acc.shape[-1]
            return jnp.where(full, jnp.argmax(score, -1),
                             c.fill[:, None]).astype(jnp.int32)
        monkeypatch.setattr(cache, "_choose_slot", evict_most)
    r = run(tiny_cell())
    assert not r["correct"]
    v = r["compared"][number]
    assert v["value"] > v["limit"]


def test_control_reads_above_the_served_path():
    """The control procedure of bench/control.py at a size a test run
    holds: on each seed the program comes out correct and the int8
    reference in its place, judged against the same limits, does not."""
    rows = harness.readings(tiny_cell(), [1, 2, 3], 2.0)
    assert all(r["program"]["correct"] for r in rows), rows
    assert not any(r["control"]["correct"] for r in rows), rows


def test_reference_matches_program_in_f32():
    """The plain reference and the program, both in float32 at
    HIGHEST, on the same weights (the program's folded): the logits
    after the prompt, the prefill with static eviction in lockstep, and a
    lockstep decode step on the program's state after prefill and decode
    steps into eviction.
    They differ by float rounding order alone."""
    from repro.configs.base import PruneConfig
    from repro.models.transformer import Model
    for model in (MODEL, dict(MODEL, **GRANITE),
                  dict(MODEL, hidden_act="gelu_pytorch_tanh", norm="layernorm",
                       tie_word_embeddings=False, attention_bias=True)):
        prune = dict(PRUNE, fused=False)
        base = harness.program_model({"name": "t", "model": model,
                                      "prune": prune})
        prog = Model(dataclasses.replace(base.cfg, param_dtype="float32",
                                         compute_dtype="float32"),
                     PruneConfig(**prune))
        w = weights.make(model, 5, jnp.float32)
        params = weights.program_tree(w, model)
        prompt = np.random.default_rng(0).integers(0, 257, 100)
        ref = Reference(model, prune, "f32", prompt_pad=128)
        with jax.default_matmul_precision("highest"):
            lg, st = jax.jit(prog.prefill_one)(params, jnp.asarray(prompt))
            first = np.asarray(lg)
            step = jax.jit(prog.decode_step)
            tok = jnp.argmax(lg).reshape(1)
            for _ in range(30):                 # 16 appends, then evictions
                lg, st = step(params, st, tok)
                tok = jnp.argmax(lg, -1)
            lock = harness.Lockstep(prog, model, prune, False)
            dec = lock.decode(params, st, tok)
            pre = lock.prefill(params, prompt)
        want = ref.first_logits(w, prompt)
        assert np.abs(first - want).max() < 1e-4 * np.abs(want).max()
        assert dec["program"] < 1e-5, dec
        assert max(pre["program"].values()) < 1e-5, pre


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "granite-longdoc", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
