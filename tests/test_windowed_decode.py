"""Fill-aware ragged decode tests.

Two coordinated mechanisms under test:

  * windowed dispatch — the decode step runs over the `[:W]` slot prefix
    (W = pow2 cover of the live fills) and must be BIT-IDENTICAL to the
    full-slot step across fills, kv dtypes (bf16 + int8 mirror), policies,
    and the ring-buffer wrap boundary, with the window grid bounding the
    retrace count at log2(slots);
  * the ragged Pallas fused-decode kernel — per-lane live-block early
    exit must match `ref.fused_decode_ref` on mixed-fill batches.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import PruneConfig, get_config, reduced
from repro.core import baselines, quant
from repro.core.attention import decode_attention, windowed_decode_attention
from repro.core.cache import (decode_window, init_cache, slot_window,
                              slot_window_merge)
from repro.kernels import ops, ref
from repro.kernels.ragged_decode import ragged_decode
from repro.launch.serve import ServeLoop
from repro.models.transformer import Model

jax.config.update("jax_platform_name", "cpu")


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _filled_cache(fills, slots, prune, dtype=jnp.bfloat16, hk=2, d=16,
                  key=0):
    """Per-lane fill prefixes — the layout prefill + append decode make."""
    b = len(fills)
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    c = init_cache(b, hk, d, slots, prune, dtype)
    k = jax.random.normal(ks[0], (b, hk, slots, d))
    v = jax.random.normal(ks[1], (b, hk, slots, d))
    fills = jnp.asarray(fills, jnp.int32)
    live = jnp.arange(slots)[None, None, :] < fills[:, None, None]
    live = jnp.broadcast_to(live, (b, hk, slots))
    pos = jnp.broadcast_to(jnp.arange(slots)[None, None, :],
                           (b, hk, slots))
    acc = jax.random.uniform(ks[2], (b, hk, slots)) * live
    if c.quantized_kv:
        kq8, ksc = quant.quantize(k, 8)
        vq8, vsc = quant.quantize(v, 8)
        c = c._replace(k=jnp.where(live[..., None], kq8, 0),
                       v=jnp.where(live[..., None], vq8, 0),
                       kscale=jnp.where(live, ksc, 0.0),
                       vscale=jnp.where(live, vsc, 0.0))
    else:
        kq, ksc = quant.quantize(k, prune.score_bits)
        c = c._replace(k=jnp.where(live[..., None], k, 0).astype(c.k.dtype),
                       v=jnp.where(live[..., None], v, 0).astype(c.v.dtype),
                       kq=jnp.where(live[..., None], kq, 0),
                       kscale=jnp.where(live, ksc, 0.0))
    return c._replace(acc=acc, valid=live,
                      pos=jnp.where(live, pos, -1),
                      fill=fills, step=fills)


# -- decode_window grid -------------------------------------------------------


def test_decode_window_grid():
    prune = PruneConfig(policy="unicaim", heavy_budget=4032, reserve=64,
                        select_k=64, sink_tokens=2, recent_window=8)
    assert decode_window(128, 1, 4096, prune) == 256
    assert decode_window(100, 28, 4096, prune) == 128
    assert decode_window(0, 1, 4096, prune) == 64        # select_k floor
    assert decode_window(4000, 8, 4096, prune) is None   # full lane
    assert decode_window(2049, 1, 4096, prune) is None   # pow2 hits slots
    # non-pow2 block race can't partition a pow2 window → full width
    nb3 = dataclasses.replace(prune, select_blocks=3, select_k=63)
    assert decode_window(10, 1, 4096, nb3) is None
    nb2 = dataclasses.replace(prune, select_blocks=2)
    assert decode_window(128, 1, 4096, nb2) == 256


def test_slot_window_roundtrip_stacked():
    """slot_window/merge must be each other's inverse on layer-stacked
    caches (the DecodeState layout) for every field."""
    prune = baselines.unicaim(heavy=24, reserve=8, select_k=8,
                              sink_tokens=2, recent_window=4)
    c = _filled_cache([5, 12], prune.slots, prune, key=3)
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), c)
    win = slot_window(stacked, 16)
    assert win.k.shape[-2] == 16 and win.acc.shape[-1] == 16
    _assert_trees_equal(slot_window_merge(stacked, win), stacked)


# -- windowed step: bitwise parity --------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("policy,select_mode,fused", [
    ("unicaim", "topk", False),
    ("unicaim", "topk", True),
    ("unicaim", "threshold", False),
    ("h2o", "topk", False),
    ("dense", "topk", False),
])
def test_windowed_step_bitwise_parity(kv_dtype, policy, select_mode, fused):
    """Windowed decode == full-slot decode, bit for bit, across fills and
    multiple steps (each step appends into the window)."""
    if policy != "unicaim" and kv_dtype == "int8":
        pytest.skip("int8 KV is a unicaim-mode knob")
    prune = PruneConfig(policy=policy, heavy_budget=48, reserve=16,
                        sink_tokens=2, recent_window=4, select_k=8,
                        select_mode=select_mode, kv_dtype=kv_dtype,
                        fused=fused, fused_backend="xla",
                        accumulate="exact" if policy == "h2o" else "approx")
    for fills in ([3, 9], [0, 20], [16, 28]):
        cw = cf = _filled_cache(fills, prune.slots, prune,
                                dtype=jnp.bfloat16, key=sum(fills))
        w = decode_window(max(fills), 3, prune.slots, prune)
        assert w is not None and w < prune.slots
        step_w = jax.jit(lambda c, q, k, v: windowed_decode_attention(
            c, q, k, v, prune, w))
        step_f = jax.jit(lambda c, q, k, v: decode_attention(
            c, q, k, v, prune))
        for i in range(3):
            ks = jax.random.split(jax.random.PRNGKey(100 + i), 3)
            q = jax.random.normal(ks[0], (2, 4, 16))
            kn = jax.random.normal(ks[1], (2, 2, 16))
            vn = jax.random.normal(ks[2], (2, 2, 16))
            cw, ow = step_w(cw, q, kn, vn)
            cf, of = step_f(cf, q, kn, vn)
            np.testing.assert_array_equal(np.asarray(ow), np.asarray(of))
            _assert_trees_equal(cw, cf)


@pytest.mark.parametrize("policy", ["unicaim", "streaming"])
def test_ring_wrap_boundary_forces_full_width(policy):
    """At the wrap/eviction boundary the window must be the full slot
    array: decode_window refuses a window there, and steps that overwrite
    slots (ring wrap for streaming, argmin eviction for unicaim) stay
    bit-identical between the windowed entry point (window=None) and the
    plain full step."""
    prune = (baselines.streaming(28, sinks=2) if policy == "streaming"
             else baselines.unicaim(heavy=24, reserve=8, select_k=8,
                                    sink_tokens=2, recent_window=4))
    slots = prune.slots
    # one step before full: any window would have to cover slots → None
    assert decode_window(slots - 1, 1, slots, prune) is None
    assert decode_window(slots, 4, slots, prune) is None
    cw = cf = _filled_cache([slots, slots - 1], slots, prune,
                            dtype=jnp.float32, key=7)
    step_w = jax.jit(lambda c, q, k, v: windowed_decode_attention(
        c, q, k, v, prune, None))
    step_f = jax.jit(lambda c, q, k, v: decode_attention(c, q, k, v, prune))
    for i in range(4):                      # crosses full → wraps/evicts
        ks = jax.random.split(jax.random.PRNGKey(i), 3)
        q = jax.random.normal(ks[0], (2, 2, 16))
        kn = jax.random.normal(ks[1], (2, 2, 16))
        vn = jax.random.normal(ks[2], (2, 2, 16))
        cw, ow = step_w(cw, q, kn, vn)
        cf, of = step_f(cf, q, kn, vn)
        np.testing.assert_array_equal(np.asarray(ow), np.asarray(of))
        _assert_trees_equal(cw, cf)
    assert int(np.asarray(cw.fill).max()) == slots


# -- model + serving level ----------------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_model_windowed_decode_step_parity(kv_dtype):
    """Model.decode_step(window=W) — slicing + layer scan + merge — is
    bitwise the full-width step: logits and every DecodeState leaf."""
    cfg = reduced(get_config("longchat-7b"))
    prune = dataclasses.replace(
        baselines.unicaim(heavy=48, reserve=16, select_k=16,
                          sink_tokens=2, recent_window=8),
        kv_dtype=kv_dtype)
    model = Model(cfg, prune)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32))),
             "length": jnp.asarray([9, 26], jnp.int32)}
    logits, state_w = jax.jit(model.prefill)(params, batch)
    state_f = state_w
    tw = tf = jnp.argmax(logits, -1)
    step = jax.jit(model.decode_step, static_argnames=("window",))
    for _ in range(4):
        lw, state_w = step(params, state_w, tw, window=32)
        lf, state_f = step(params, state_f, tf, window=None)
        np.testing.assert_array_equal(np.asarray(lw), np.asarray(lf))
        tw, tf = jnp.argmax(lw, -1), jnp.argmax(lf, -1)
    _assert_trees_equal(state_w, state_f)


def test_serve_windowed_parity_and_retrace_bound():
    """ServeLoop(window='auto') emits the exact tokens of window=None and
    compiles at most log2(slots) + 1 distinct windowed block programs
    (the pow2 grid is the retrace bound)."""
    cfg = reduced(get_config("longchat-7b"))
    prune = baselines.unicaim(heavy=48, reserve=16, select_k=16,
                              sink_tokens=2, recent_window=8)
    model = Model(cfg, prune)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, t) for t in (9, 25, 12, 40)]

    def run(window):
        loop = ServeLoop(model, params, lanes=2, eos=-1, block=4,
                         window=window)
        rids = [loop.submit(p, max_new=12) for p in prompts]
        done = {s.rid: s for s in loop.run()}
        return [done[r].tokens for r in rids], loop

    toks_w, loop_w = run("auto")
    toks_f, loop_f = run(None)
    assert toks_w == toks_f
    assert loop_w.counters["decode_windows"] >= 1
    assert (loop_w.counters["decode_windows"]
            <= math.ceil(math.log2(prune.slots)) + 1)
    assert loop_f.counters["decode_windows"] <= 1     # {None}


# -- ragged fused-decode kernel ----------------------------------------------


def _ragged_args(bh, g, d, dv, s, fills, key=0, quantized=False,
                 prot_frac=0.1):
    ks = jax.random.split(jax.random.PRNGKey(key), 10)
    q = jax.random.normal(ks[0], (bh, g, d))
    qq = jax.random.randint(ks[1], (bh, g, d), -7, 8, jnp.int8)
    qs = jax.random.uniform(ks[2], (bh, g)) + 0.05
    mirror = jax.random.randint(ks[3], (bh, s, d), -7, 8, jnp.int8)
    ms = jax.random.uniform(ks[4], (bh, s)) + 0.05
    if quantized:
        k = jax.random.randint(ks[5], (bh, s, d), -127, 128, jnp.int8)
        v = jax.random.randint(ks[6], (bh, s, dv), -127, 128, jnp.int8)
        kscale = jax.random.uniform(ks[7], (bh, s)) * 0.02 + 0.001
        vscale = jax.random.uniform(ks[8], (bh, s)) * 0.02 + 0.001
    else:
        k = jax.random.normal(ks[5], (bh, s, d))
        v = jax.random.normal(ks[6], (bh, s, dv))
        kscale = jnp.ones((bh, s))
        vscale = jnp.ones((bh, s))
    fills = jnp.asarray(fills, jnp.int32)
    valid = (jnp.arange(s)[None, :] < fills[:, None]).astype(jnp.int8)
    prot = (jax.random.bernoulli(ks[9], prot_frac,
                                 (bh, s)).astype(jnp.int8)) * valid
    return fills, (q, qq, qs, mirror, ms, kscale, vscale, valid, prot, k, v)


@pytest.mark.parametrize("bh,g,d,dv,s,sk,fills,quantized", [
    (4, 2, 32, 32, 64, 16, [5, 30, 64, 0], False),   # mixed + empty + full
    (3, 4, 16, 24, 100, 8, [100, 17, 42], True),     # int8, ragged S
    (2, 1, 16, 16, 48, 48, [10, 48], False),         # select_k == S
])
def test_ragged_kernel_matches_ref(bh, g, d, dv, s, sk, fills, quantized):
    """Dead-block early exit must not change a bit of the math: parity
    with the global-selection oracle on per-lane fill prefixes."""
    fl, args = _ragged_args(bh, g, d, dv, s, fills, key=s + sk,
                            quantized=quantized)
    out_k, probs_k = ragged_decode(fl, *args, select_k=sk, block_s=16,
                                   interpret=True)
    out_r, probs_r = ref.fused_decode_ref(*args, select_k=sk, num_blocks=1)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(probs_k), np.asarray(probs_r),
                               atol=1e-6)
    # probs at dead slots are exactly zero (they feed the acc table)
    dead = np.arange(s)[None, :] >= np.asarray(fl)[:, None]
    assert not np.asarray(probs_k)[dead].any()


def test_ops_fused_decode_dispatches_ragged():
    """ops.fused_decode(fills=..., backend='pallas') must route through
    the ragged kernel (global selection) and match the XLA fallback."""
    fl, args = _ragged_args(3, 2, 16, 16, 40, [7, 22, 40], key=11)
    out_r, probs_r = ops.fused_decode(*args, select_k=8, num_blocks=1,
                                      backend="pallas", fills=fl)
    out_x, probs_x = ops.fused_decode(*args, select_k=8, num_blocks=1,
                                      backend="xla", fills=fl)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_x),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(probs_r), np.asarray(probs_x),
                               atol=1e-6)


def test_fused_auto_resolves_per_backend():
    """fused='auto' must resolve to the composed path off-TPU (the XLA
    fallback was measured at parity-to-slower) and stay a valid
    PruneConfig value."""
    from repro.core.attention import decode_engine, fused_auto_decision
    prune = dataclasses.replace(
        baselines.unicaim(heavy=24, reserve=8, select_k=8, sink_tokens=2,
                          recent_window=4), fused="auto")
    prune.validate()
    decision = fused_auto_decision()
    assert decision["engine"] in ("fused", "composed")
    assert decision["reason"]
    on_tpu = jax.default_backend() == "tpu"
    assert decode_engine(prune) == ("fused" if on_tpu else "composed")
    assert decode_engine(dataclasses.replace(prune, fused=True)) == "fused"
    assert decode_engine(dataclasses.replace(prune, fused=False)) == "composed"
