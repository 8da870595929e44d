#!/usr/bin/env python3
"""Chip smoke test: the serving path, once, on a TPU, at published width.

    python chip_smoke.py             # one chip  (phase "serve")
    python chip_smoke.py --chips 4   # four chips (phase "mesh" only)

Phase "serve" builds granite-3-2b at its published width (40 layers,
d_model 2048, 32/8 heads, bf16; random weights from --seed) with the
unicaim policy as the serving CLI builds it for 2048-token prompts
(heavy 1024 + reserve 64 slots, select_k 128) and `fused="auto"`. It
replays one seeded trace of staggered long-prompt requests through
`ServeLoop` twice: a warm-up pass that compiles every program, then the
served pass that is timed. It fails unless

  * JAX's platform is "tpu" (there is no CPU fallback);
  * `fused="auto"` resolved to the fused engine, and every compiled
    decode-block program that served the trace holds a `tpu_custom_call`;
  * every request ended "done" with no quarantined lane, failed or
    rejected request, and every token lies in [0, vocab);
  * the ragged kernel matches the pure-jnp oracle at the served width
    within KERNEL_ATOL;
  * the kernel engine and the composed engine (`fused=False`) agree on
    the served state with f32 activations: one decode step's logits on
    the median lane within LOGITS_RTOL, and, run in lockstep (both
    engines on the same cache view and query at every layer), every
    attention row within ATTN_RTOL; a planted selection fault must land
    above both bounds.

Phase "mesh" (--chips 4) replays the same trace through
`ServeLoop(mesh=4, lanes=8)` and through the unsharded engine on one chip
in the same process, and fails unless every outcome is "done", one
decode step on the unsharded side's final state gives the same logits
with the lanes sharded over the mesh (median lane within MESH_RTOL; a
planted lane mix-up must land above it), and the compiled sharded decode
block holds no collective. The two sides' token streams are printed:
they admit in different prefill groups, so they need not match. It runs
nothing else.

Earlier stdout lines report what was measured; the last line is the JSON
verdict. Everything runs in this one process: the chip belongs to it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "granite-3-2b"
LANES = 8
BLOCK = 8
N_REQUESTS = 16
PROMPT_MAX = 2048
# Kernel vs oracle at the served width, on seeded random operands: both
# compute the attention in f32 (the oracle at matmul precision HIGHEST),
# so they differ by f32 summation order and exp rounding only.
KERNEL_ATOL = 1e-4
# Kernel engine vs composed engine, one decode step, same state and
# weights, f32 activations, matmul precision HIGHEST. Both engines compute
# the attention in f32 and differ by f32 summation order (~2e-6 relative
# on a v5e). Two things amplify that difference on its way to the logits:
#   * bf16 activations round it up to bf16-ulp flips that 40 random
#     layers grow: as served, the engines read 6.8e-2 apart, and one
#     winner too few per row (select_k - 1) only 1.5e-1 — hence f32;
#   * the 4-bit query quantization is a step function: now and then the
#     2e-6 difference carries a query element across a rounding edge in
#     one engine's run and not the other's, that row's winners change,
#     and that lane's later layers diverge (on a v5e, one lane of 8 read
#     2.4e-2, the other seven 1.3e-6..1.8e-6).
# So the logits are checked per lane on the median lane, and the check
# that sees every row runs the two engines in lockstep: each layer's
# attention from both engines on the same cache view and query.
# LOGITS_RTOL: median lane's relative RMS. With f32 activations, 1e-5
# relative noise on every attention output moved the logits of a cut
# granite stack (8 and 24 layers, d_model 512) by 1.3e-5, select_k - 1 by
# 0.18..0.21. ATTN_RTOL: worst row's max |difference| over its max
# |composed| value, in lockstep; f32 order noise reads ~2e-6 there. Every
# run checks that a planted fault (the kernel engine at select_k - 1)
# exceeds both bounds.
LOGITS_RTOL = 1e-3
ATTN_RTOL = 1e-4
# Mesh phase: one decode step on the same state, lanes sharded vs not,
# both as served (bf16 activations): median lane's relative RMS. The
# per-shard program is the unsharded one on fewer lanes; if the chip's
# matmuls round a lane differently at another batch size, bf16 chaos
# stays near the 8.9e-2 that two whole engines read apart on a v5e. A
# lane mix-up (each lane against its neighbour) reads ~1.4, the RMS
# distance of unrelated logits. Every run checks that it exceeds the
# bound.
MESH_RTOL = 0.3
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "all-to-all", "reduce-scatter")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums JAX's backend-compile events (cache loads included)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def take(self):
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


def make_trace(vocab: int, n: int, seed: int):
    """n seeded requests: prompts of 513–2048 tokens (two prefill buckets,
    long enough that static pruning evicts), 32–64 new tokens each,
    arrivals staggered 0.1 s apart."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return [dict(prompt=rng.integers(0, vocab, int(rng.integers(513, 2049))),
                 max_new=int(rng.integers(32, 65)), arrival=0.1 * i)
            for i in range(n)]


def build(seed: int):
    import jax
    from repro.configs.base import get_config
    from repro.core import baselines
    from repro.models.transformer import Model

    cfg = get_config(ARCH)
    budget = PROMPT_MAX // 2                 # as launch/serve.py:main
    prune = baselines.unicaim(heavy=budget, reserve=64,
                              select_k=max(16, budget // 8), fused="auto")
    model = Model(cfg, prune)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return cfg, model, jax.block_until_ready(params)


def serve(model, params, trace, mesh=None):
    from repro.launch.serve import Request, ServeLoop

    loop = ServeLoop(model, params, lanes=LANES, max_new=64, block=BLOCK,
                     mesh=mesh)
    handles = [loop.submit(Request(**kw)) for kw in trace]
    t0 = time.perf_counter()
    loop.run()
    return loop, handles, time.perf_counter() - t0


def check_served(loop, handles, vocab: int, label: str):
    outcomes = {}
    for h in handles:
        outcomes[h.outcome] = outcomes.get(h.outcome, 0) + 1
    log(f"[{label}] outcomes {outcomes}; quarantined_lanes="
        f"{loop.counters['quarantined_lanes']} failed_requests="
        f"{loop.counters['failed_requests']} rejected_requests="
        f"{loop.counters['rejected_requests']}")
    check(outcomes == {"done": len(handles)},
          f"[{label}] not every request ended 'done': {outcomes}")
    for key in ("quarantined_lanes", "failed_requests", "rejected_requests"):
        check(loop.counters[key] == 0, f"[{label}] {key}="
              f"{loop.counters[key]}")
    for h in handles:
        toks = h.tokens
        check(len(toks) == h.stats.max_new,
              f"[{label}] request {h.rid} emitted {len(toks)} of "
              f"{h.stats.max_new} tokens")
        check(all(0 <= t < vocab for t in toks),
              f"[{label}] request {h.rid} emitted a token outside "
              f"[0, {vocab})")
    return sum(len(h.tokens) for h in handles)


def served_block_texts(loop):
    """Compiled HLO of every decode-block program the loop dispatched
    (one per slot window), lowered with the arguments `ServeLoop` hands
    it; the persistent cache makes these loads."""
    import numpy as np
    from repro.launch.serve import _lanes_block_fn, _model_key
    args = loop._block_args(np.zeros((loop.block, loop.lanes), bool))
    return {w: _lanes_block_fn(_model_key(loop.model), loop.block, w,
                               loop.mesh).lower(*args).compile().as_text()
            for w in sorted(loop._windows, key=lambda w: w or 1 << 30)}


def log_memory(when: str) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    log(f"device memory {when}: bytes_in_use "
        f"{stats.get('bytes_in_use', 'not reported')}, peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")


def rel_rms(a, b) -> float:
    import numpy as np
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def wall_ms(fn, *args, reps: int = 10) -> float:
    """Mean wall milliseconds of an already-compiled call (one-off)."""
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def check_kernel(model, seed: int):
    """The ragged kernel against `ref.fused_decode_ref` on seeded random
    operands at the served width: one layer's [lanes·kv_heads] rows,
    mixed fills (empty, partial, exactly one block, full)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.ragged_decode import ragged_decode

    cfg, prune = model.cfg, model.prune
    hk, d, s, k_sel = (cfg.n_kv_heads, cfg.head_dim, prune.slots,
                       prune.select_k)
    g, bh = cfg.n_heads // hk, LANES * hk
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 9)
    fills = jnp.asarray(np.resize([s, 700, 512, 0, 130, s - 1, 1000, 1],
                                  bh), jnp.int32)
    valid = (jnp.arange(s)[None] < fills[:, None]).astype(jnp.int8)
    args = (jax.random.normal(ks[0], (bh, g, d), jnp.bfloat16),
            jax.random.randint(ks[1], (bh, g, d), -7, 8, jnp.int8),
            jax.random.uniform(ks[2], (bh, g)) + 0.05,
            jax.random.randint(ks[3], (bh, s, d), -3, 4, jnp.int8),
            jax.random.uniform(ks[4], (bh, s)) + 0.05,
            jnp.ones((bh, s)), jnp.ones((bh, s)), valid,
            jax.random.bernoulli(ks[5], 0.05, (bh, s)).astype(jnp.int8)
            * valid,
            jax.random.normal(ks[6], (bh, s, d), jnp.bfloat16),
            jax.random.normal(ks[7], (bh, s, d), jnp.bfloat16))
    kernel = jax.jit(lambda f, *a: ragged_decode(f, *a, select_k=k_sel))
    oracle = jax.jit(lambda *a: ref.fused_decode_ref(*a, select_k=k_sel))
    out, probs = kernel(fills, *args)
    with jax.default_matmul_precision("highest"):
        out_r, probs_r = oracle(*args)
    err_out = float(jnp.abs(out - out_r).max())
    err_probs = float(jnp.abs(probs - probs_r).max())
    log(f"ragged kernel vs oracle at [{bh} rows, G={g}, d={d}, S={s}, "
        f"k={k_sel}]: max|d out|={err_out:.2e} max|d probs|="
        f"{err_probs:.2e} (tolerance {KERNEL_ATOL:.0e}); one call "
        f"{wall_ms(kernel, fills, *args) * 1e3:.0f} us wall (one-off)")
    check(err_out <= KERNEL_ATOL and err_probs <= KERNEL_ATOL,
          f"ragged kernel vs oracle: out {err_out:.2e}, probs "
          f"{err_probs:.2e} > {KERNEL_ATOL:.0e}")


def lockstep_attention(model, composed, params, state, tok):
    """One decode step of `model` (f32 activations, HIGHEST) in which each
    layer's attention also runs through the `composed` engine on the same
    cache view and query. Returns [layers, lanes, q heads]: each row's max
    |difference| over its max |composed| value."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import attention

    rows = []
    attend = attention._policy_attend

    def both(view, q, prune):
        _, ref = attend(view, q, composed)
        view, out = attend(view, q, prune)
        rel = (jnp.max(jnp.abs(out - ref), axis=-1)
               / jnp.maximum(jnp.max(jnp.abs(ref), axis=-1), 1e-30))
        jax.debug.callback(lambda r: rows.append(np.asarray(r)), rel,
                           ordered=True)
        return view, out

    # the decode step resolves the attention through this module global
    attention._policy_attend = both
    try:
        with jax.default_matmul_precision("highest"):
            jax.block_until_ready(jax.jit(model.decode_step)(params, state,
                                                             tok))
    finally:
        attention._policy_attend = attend
    check(len(rows) == model.cfg.num_layers,
          f"lockstep saw {len(rows)} of {model.cfg.num_layers} layers")
    return np.stack(rows)


def compare_engines(cfg, model, params, state, tok):
    """Kernel engine vs composed engine on the served state: one decode
    step's logits per lane and, in lockstep, every layer's attention rows
    (f32 activations, HIGHEST); a planted selection fault (the kernel
    engine at select_k - 1) must fail both. Also each engine's step as
    served (bf16 activations, default precision): logits difference and
    wall time."""
    import jax
    import numpy as np
    from repro.models.transformer import Model

    prune = model.prune
    composed = dataclasses.replace(prune, fused=False)
    planted = dataclasses.replace(prune, select_k=prune.select_k - 1)
    f32 = dataclasses.replace(cfg, compute_dtype="float32")
    runs = {
        ("kernel", "served"): model,
        ("composed", "served"): Model(cfg, composed),
        ("kernel", "f32"): Model(f32, prune),
        ("composed", "f32"): Model(f32, composed),
        ("planted", "f32"): Model(f32, planted),
    }
    logits, steps = {}, {}
    for (name, mode), m in runs.items():
        prec = "default" if mode == "served" else "highest"
        with jax.default_matmul_precision(prec):
            step = jax.jit(m.decode_step)
            out = step(params, state, tok)[0]   # the new state is dropped
            logits[name, mode] = np.asarray(out, np.float64)
            if mode == "served":
                steps[name] = wall_ms(step, params, state, tok)
    check(all(np.isfinite(v).all() for v in logits.values()),
          "non-finite logits")
    ref = logits["composed", "f32"]
    lanes = {name: [rel_rms(logits[name, "f32"][i], ref[i])
                    for i in range(len(ref))]
             for name in ("kernel", "planted")}
    median = {name: float(np.median(v)) for name, v in lanes.items()}
    served = rel_rms(logits["kernel", "served"], logits["composed", "served"])
    agree = int((logits["kernel", "f32"].argmax(-1) == ref.argmax(-1)).sum())
    log(f"logits kernel vs composed (one decode step, same state, f32 "
        f"activations, HIGHEST), rel_rms per lane "
        f"{[f'{x:.2e}' for x in lanes['kernel']]}: median "
        f"{median['kernel']:.3e} (bound {LOGITS_RTOL:.0e}); planted fault "
        f"select_k-1 per lane {[f'{x:.2e}' for x in lanes['planted']]}: "
        f"median {median['planted']:.3e} (must exceed the bound); argmax "
        f"agree {agree}/{len(ref)}")

    attn = lockstep_attention(Model(f32, prune), composed, params, state,
                              tok)
    attn_planted = lockstep_attention(Model(f32, planted), composed, params,
                                      state, tok)
    log(f"attention kernel vs composed in lockstep ({attn.shape[0]} layers "
        f"x {attn.shape[1]} lanes x {attn.shape[2]} heads, same view and "
        f"query): worst row {attn.max():.3e} (bound {ATTN_RTOL:.0e}); "
        f"planted fault select_k-1: worst row {attn_planted.max():.3e}, "
        f"{(attn_planted > ATTN_RTOL).mean():.1%} of rows above the bound")
    log(f"printed, not checked: kernel vs composed as served (bf16 "
        f"activations, default precision) rel_rms {served:.3e}")
    log(f"decode step wall ms (as served, this state, one-off): "
        f"kernel engine {steps['kernel']:.2f}, composed engine "
        f"{steps['composed']:.2f}")
    check(median["kernel"] <= LOGITS_RTOL, f"kernel vs composed logits: "
          f"median lane rel_rms {median['kernel']:.3e} > {LOGITS_RTOL:.0e}")
    check(median["planted"] > LOGITS_RTOL, f"planted select_k-1 fault: "
          f"median lane rel_rms {median['planted']:.3e} <= "
          f"{LOGITS_RTOL:.0e}: the logits bound cannot see it")
    check(attn.max() <= ATTN_RTOL, f"kernel vs composed attention in "
          f"lockstep: worst row {attn.max():.3e} > {ATTN_RTOL:.0e}")
    check(attn_planted.max() > ATTN_RTOL, f"planted select_k-1 fault: "
          f"worst lockstep row {attn_planted.max():.3e} <= "
          f"{ATTN_RTOL:.0e}: the attention bound cannot see it")


def phase_serve(n_requests: int, seed: int):
    import jax
    from repro.core.attention import fused_auto_decision
    from repro.launch.serve import donation_mode

    clock = CompileClock()
    t0 = time.perf_counter()
    cfg, model, params = build(seed)
    init_s = time.perf_counter() - t0
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"model {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
        f"vocab={cfg.vocab_size} params={n_params} ({cfg.param_dtype}); "
        f"slots={model.prune.slots} select_k={model.prune.select_k}")
    decision = fused_auto_decision()
    log(f"engine: fused='auto' resolved to {decision['engine']!r} "
        f"({decision['reason']})")
    check(decision["engine"] == "fused",
          f"fused='auto' resolved to {decision['engine']!r}")
    donation = donation_mode()
    log(f"donation_mode: {donation}")
    check(donation == "on", f"donation_mode() is {donation!r}")

    trace = make_trace(cfg.vocab_size, n_requests, seed)
    lens = [len(r["prompt"]) for r in trace]
    log(f"trace: {len(trace)} requests, prompt tokens {min(lens)}–"
        f"{max(lens)}, max_new {min(r['max_new'] for r in trace)}–"
        f"{max(r['max_new'] for r in trace)}, lanes={LANES} block={BLOCK}")
    setup_compile_s, _ = clock.take()

    loop, handles, warm_s = serve(model, params, trace)
    check_served(loop, handles, cfg.vocab_size, "warm-up")
    warm_compile_s, warm_compiles = clock.take()
    warm_tokens = [h.tokens for h in handles]

    loop, handles, served_s = serve(model, params, trace)
    served_compile_s, served_compiles = clock.take()
    toks = check_served(loop, handles, cfg.vocab_size, "served")
    # compiles slow the warm-up pass, so arrivals group into different
    # prefill programs than in the served pass; a report, not a check
    same = sum(a == h.tokens for a, h in zip(warm_tokens, handles))
    log(f"warm-up vs served greedy streams: {same}/{len(handles)} requests "
        f"token-identical (admission timing differs between the passes)")
    log(f"compile seconds: setup {setup_compile_s:.1f} (init wall "
        f"{init_s:.1f}), warm-up pass {warm_compile_s:.1f} over "
        f"{warm_compiles} programs (pass wall {warm_s:.1f}), served pass "
        f"{served_compile_s:.1f} over {served_compiles} programs")
    log(f"served seconds: {served_s:.3f} wall for {toks} tokens -> "
        f"{toks / served_s:.1f} tok/s (one-off wall-clock figure of a "
        f"smoke run, not a benchmark)")
    log_memory("after the served pass")

    for w, text in served_block_texts(loop).items():
        log(f"decode block window={w}: tpu_custom_call x"
            f"{text.count('tpu_custom_call')}")
        check("tpu_custom_call" in text,
              f"decode block (window={w}) has no tpu_custom_call")
    check_kernel(model, seed)
    compare_engines(cfg, model, params, loop.state, loop.tok)
    log_memory("at the end (engine comparison included)")


def serve_side(label, model, params, trace, vocab, clock, mesh=None):
    """One replay of the trace: its loop, tokens and compiled decode
    blocks."""
    loop, handles, wall = serve(model, params, trace, mesh=mesh)
    check_served(loop, handles, vocab, label)
    texts = served_block_texts(loop)
    fused = all("tpu_custom_call" in t for t in texts.values())
    compile_s, n = clock.take()
    log(f"[{label}] engine resolved: {'fused' if fused else 'composed'} "
        f"(decode block windows {sorted(texts, key=str)}); wall {wall:.1f}s "
        f"incl. {compile_s:.1f}s compiling {n} programs; tokens per shard "
        f"{[int(t) for t in loop._shard_tokens]}")
    return loop, [h.tokens for h in handles], texts


def sharded_step_logits(model, mesh, params, state, tok):
    """One decode step's logits with the lanes sharded over `mesh` as
    `ServeLoop(mesh=...)` shards them: the same per-shard program under
    `shard_map`, on `state` laid out P("data") on the lane axis."""
    import jax
    import numpy as np
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.runtime.sharding import lane_pspecs, lane_shardings

    step = jax.jit(shard_map(
        lambda p, s, t: model.decode_step(p, s, t)[0], mesh=mesh,
        in_specs=(P(), lane_pspecs(state, mesh), P("data")),
        out_specs=P("data"), check_vma=False))
    out = step(params, jax.device_put(state, lane_shardings(state, mesh)),
               jax.device_put(tok, NamedSharding(mesh, P("data"))))
    return np.asarray(out, np.float64)


def phase_mesh(n_requests: int, seed: int, chips: int):
    import jax
    import numpy as np
    from repro.launch.mesh import make_serve_mesh

    check(len(jax.devices()) >= chips,
          f"--chips {chips} but JAX sees {len(jax.devices())} devices")
    clock = CompileClock()
    cfg, model, params = build(seed)
    trace = make_trace(cfg.vocab_size, n_requests, seed)
    log(f"model {cfg.name} at published width; {len(trace)} requests, "
        f"lanes={LANES}, mesh data={chips}")
    loop, ref, _ = serve_side("unsharded", model, params, trace,
                              cfg.vocab_size, clock)
    state, tok = loop.state, loop.tok
    ref_logits = np.asarray(jax.jit(model.decode_step)(params, state,
                                                       tok)[0], np.float64)
    del loop
    # ServeLoop replicates the weights onto the mesh; chip 0 then holds
    # them twice (~10 GB of 16), as the unsharded copy stays referenced
    mesh = make_serve_mesh(chips)
    loop, toks, texts = serve_side("sharded", model, params, trace,
                                   cfg.vocab_size, clock, mesh=mesh)
    logits = sharded_step_logits(model, mesh, loop.params, state, tok)

    diverged = {}
    for i, (a, b) in enumerate(zip(toks, ref)):
        if a != b:
            diverged[i] = next(t for t, (x, y) in enumerate(zip(a, b))
                               if x != y)
    log(f"printed, not checked: greedy streams {len(toks) - len(diverged)}/"
        f"{len(toks)} requests token-identical sharded vs unsharded"
        + (f"; first divergent token per request {diverged}"
           if diverged else "")
        + " (the two sides admit in different prefill groups)")
    lanes = [rel_rms(logits[i], ref_logits[i])
             for i in range(len(ref_logits))]
    # planted lane mix-up: each lane's logits against its neighbour's
    mixed = [rel_rms(np.roll(logits, 1, axis=0)[i], ref_logits[i])
             for i in range(len(ref_logits))]
    median, median_mixed = float(np.median(lanes)), float(np.median(mixed))
    log(f"one decode step on the unsharded side's final state, sharded vs "
        f"unsharded (as served), rel_rms per lane "
        f"{[f'{x:.2e}' for x in lanes]}: median {median:.3e} (bound "
        f"{MESH_RTOL:.0e}); planted lane mix-up: median {median_mixed:.3e} "
        f"(must exceed the bound)")
    found = {w: {op: len(re.findall(op, text)) for op in COLLECTIVES}
             for w, text in texts.items()}
    for w, ops in found.items():
        log(f"sharded decode block window={w}: collectives {ops}")
    check(all(np.isfinite(x).all() for x in (logits, ref_logits)),
          "non-finite logits")
    check(median <= MESH_RTOL, f"sharded vs unsharded logits: median lane "
          f"rel_rms {median:.3e} > {MESH_RTOL:.0e}")
    check(median_mixed > MESH_RTOL, f"planted lane mix-up reads "
          f"{median_mixed:.3e} <= {MESH_RTOL:.0e}: the bound cannot see it")
    for w, ops in found.items():
        check(not any(ops.values()), f"sharded decode block (window={w}) "
              f"holds collectives {ops}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the data-sharded mesh phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and of the trace")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devices)} "
              f"{platform!r} device(s); this smoke test runs on a TPU only",
              file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: the repo's src/repro is not next to {__file__}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.runtime.compile_cache import configure_compile_cache

    log(f"jax {jax.__version__}; device_kind={devices[0].device_kind!r}; "
        f"devices={len(devices)}; compile cache {configure_compile_cache()}")
    try:
        if args.chips == 1:
            phase_serve(N_REQUESTS, args.seed)
        else:
            phase_mesh(N_REQUESTS, args.seed, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
