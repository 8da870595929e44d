"""UniCAIM attention — the three computation modes composed (§III-B).

Decode step:  CAM mode (approximate scoring over the quantized mirror)
              → top-k selection → current-domain mode (exact attention over
              the gathered k tokens) → charge-domain mode (accumulated-score
              update) → static eviction on the next write.

Prefill:      chunked causal attention (flash-style online softmax in XLA,
              Pallas kernel on TPU) that produces per-token accumulated
              attention column sums "for free" → one-shot static pruning.

All paths are pure functions: (cache, inputs) → (cache, outputs), so the
decode loop is a lax.scan and the whole model jits/shards with pjit.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.runtime.flags import xscan

from repro.configs.base import PruneConfig
from repro.core import quant, scoring, topk
from repro.core.cache import (KVCache, _token_writes, layer_window,
                              protected_mask, slot_window,
                              slot_window_merge, write_token,
                              write_token_stacked)
from repro.core.topk import NEG_INF
from repro.runtime.sharding import shard


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------


def _dense_attend(cache: KVCache, q: jax.Array, head_dim_scale: int,
                  mask: Optional[jax.Array] = None):
    """Exact attention over all (valid) cache slots.

    q: [B, Hq, d] → out [B, Hq, dv]; also returns probs [B, Hq, S].
    """
    s_exact = scoring.exact_scores(q, cache.k_values(), cache.valid)
    if mask is not None:
        s_exact = jnp.where(mask, s_exact, NEG_INF)
    probs = scoring.score_probs(s_exact, head_dim_scale)          # [B,Hq,S]
    b, hq, s = probs.shape
    hk = cache.k.shape[1]
    g = hq // hk
    p = probs.reshape(b, hk, g, s)
    out = jnp.einsum("bhgs,bhsd->bhgd", p,
                     cache.v_values().astype(jnp.float32))
    return out.reshape(b, hq, -1), probs


def _gathered_attend_blocked(cache: KVCache, q: jax.Array, idx: jax.Array,
                             head_dim_scale: int):
    """Exact attention over block-local top-k slots (distributed CAM race).

    idx: [B, Hk, nb, k_loc] — per-block winners. All gathers index the
    UNSHARDED intra-block axis, so with slots sharded over `model` and
    blocks aligned to shards, no cache bytes cross the interconnect; only
    the [B, Hq] softmax stats and the [B, Hq, dv] partial outputs reduce.
    """
    b, hq, d = q.shape
    _, hk, nb, k_loc = idx.shape
    g = hq // hk
    s = cache.k.shape[2]
    # re-pin shardings: reshape splits the sharded slot axis into
    # (blocks, slots/blocks) — the constraint keeps blocks on `model` so
    # the gathers below stay shard-local (no cache all-gather)
    kb = shard(cache.k.reshape(b, hk, nb, s // nb, d),
               "batch", "kv_heads", "slots", None, None)
    vb = shard(cache.v.reshape(b, hk, nb, s // nb, -1),
               "batch", "kv_heads", "slots", None, None)
    validb = shard(cache.valid.reshape(b, hk, nb, s // nb),
                   "batch", "kv_heads", "slots", None)
    k_sel = jnp.take_along_axis(kb, idx[..., None], axis=3)   # [B,Hk,nb,kl,d]
    v_sel = jnp.take_along_axis(vb, idx[..., None], axis=3)
    valid_sel = jnp.take_along_axis(validb, idx, axis=3)
    if cache.quantized_kv:
        ks_b = cache.kscale.reshape(b, hk, nb, s // nb)
        vs_b = cache.vscale.reshape(b, hk, nb, s // nb)
        k_sel = (k_sel.astype(jnp.float32)
                 * jnp.take_along_axis(ks_b, idx, axis=3)[..., None])
        v_sel = (v_sel.astype(jnp.float32)
                 * jnp.take_along_axis(vs_b, idx, axis=3)[..., None])
    q_g = q.reshape(b, hk, g, d).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bhnkd->bhgnk", q_g,
                        k_sel.astype(jnp.float32))
    logits = logits / jnp.sqrt(jnp.float32(head_dim_scale))
    logits = jnp.where(valid_sel[:, :, None, :, :], logits, NEG_INF)
    m = jnp.max(logits, axis=(-2, -1), keepdims=True)         # cross-block
    e = jnp.exp(logits - jax.lax.stop_gradient(m))
    e = e * (logits > NEG_INF / 2)
    z = jnp.sum(e, axis=(-2, -1), keepdims=True)
    p = e / jnp.maximum(z, 1e-30)
    out = jnp.einsum("bhgnk,bhnkd->bhgd", p, v_sel.astype(jnp.float32))
    return out.reshape(b, hq, -1)


def _gathered_attend(cache: KVCache, q: jax.Array, idx: jax.Array,
                     head_dim_scale: int):
    """Exact attention over gathered top-k slots (current-domain CIM).

    q: [B, Hq, d]; idx: [B, Hk, k] slot indices → out [B, Hq, dv].
    """
    b, hq, d = q.shape
    _, hk, k = idx.shape
    g = hq // hk
    k_sel = jnp.take_along_axis(cache.k, idx[..., None], axis=2)   # [B,Hk,k,d]
    v_sel = jnp.take_along_axis(cache.v, idx[..., None], axis=2)
    valid_sel = jnp.take_along_axis(cache.valid, idx, axis=2)      # [B,Hk,k]
    if cache.quantized_kv:
        k_sel = (k_sel.astype(jnp.float32)
                 * jnp.take_along_axis(cache.kscale, idx, axis=2)[..., None])
        v_sel = (v_sel.astype(jnp.float32)
                 * jnp.take_along_axis(cache.vscale, idx, axis=2)[..., None])
    q_g = q.reshape(b, hk, g, d).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bhkd->bhgk", q_g, k_sel.astype(jnp.float32))
    logits = jnp.where(valid_sel[:, :, None, :], logits, NEG_INF)
    probs = scoring.score_probs(logits.reshape(b, hq, k), head_dim_scale)
    out = jnp.einsum("bhgk,bhkd->bhgd", probs.reshape(b, hk, g, k),
                     v_sel.astype(jnp.float32))
    return out.reshape(b, hq, -1), probs, valid_sel


def _slot_axes(mesh, nb: int):
    """Greedy prefix of (model, data, pod) whose sizes multiply to nb."""
    axes, prod = [], 1
    for a in ("model", "data", "pod"):
        if a in mesh.shape and prod < nb:
            axes.append(a)
            prod *= mesh.shape[a]
    return tuple(axes) if prod == nb else ()


def _blocked_attend_shardmap(cache: KVCache, q: jax.Array,
                             biased: jax.Array, prune: PruneConfig,
                             mesh) -> jax.Array:
    """Shard-local top-k + gather + flash-decode combine via shard_map.

    The production path for slot-sharded caches: each model-shard races its
    LOCAL slots (k/select_blocks winners), gathers locally, and only the
    softmax stats + [B,Hq,dv] partial outputs cross the interconnect — the
    distributed form of the paper's per-array CAM race. Requires
    select_blocks == mesh model-axis size.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    b, hq, d = q.shape
    hk = cache.k.shape[1]
    g = hq // hk
    nb = prune.select_blocks
    k_loc = prune.select_k // nb
    slot_axes = _slot_axes(mesh, nb)
    assert slot_axes, (dict(mesh.shape), nb)
    red = slot_axes if len(slot_axes) > 1 else slot_axes[0]
    batch_axes = tuple(a for a in ("pod", "data")
                       if a in mesh.shape and a not in slot_axes
                       and b % mesh.shape[a] == 0)
    bspec = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    sspec = slot_axes if len(slot_axes) > 1 else slot_axes[0]
    quantized = cache.quantized_kv

    def local_fn(q_l, k_l, v_l, ks_l, vs_l, valid_l, sc_l):
        _, idx = jax.lax.top_k(sc_l, k_loc)
        k_sel = jnp.take_along_axis(k_l, idx[..., None], axis=2)
        v_sel = jnp.take_along_axis(v_l, idx[..., None], axis=2)
        if quantized:
            k_sel = (k_sel.astype(jnp.float32)
                     * jnp.take_along_axis(ks_l, idx, axis=2)[..., None])
            v_sel = (v_sel.astype(jnp.float32)
                     * jnp.take_along_axis(vs_l, idx, axis=2)[..., None])
        valid_sel = jnp.take_along_axis(valid_l, idx, axis=2)
        q_g = q_l.reshape(-1, hk, g, d).astype(jnp.float32)
        logits = jnp.einsum("bhgd,bhkd->bhgk", q_g,
                            k_sel.astype(jnp.float32))
        logits = logits / jnp.sqrt(jnp.float32(d))
        logits = jnp.where(valid_sel[:, :, None, :], logits, NEG_INF)
        m = jax.lax.pmax(jnp.max(logits, -1, keepdims=True), red)
        e = jnp.exp(logits - m) * (logits > NEG_INF / 2)  # [b,Hk,g,k_loc]
        z = jax.lax.psum(jnp.sum(e, axis=-1), red)        # [b,Hk,g]
        o = jnp.einsum("bhgk,bhkd->bhgd", e, v_sel.astype(jnp.float32))
        o = jax.lax.psum(o, red)
        return o / jnp.maximum(z, 1e-30)[..., None]

    dummy = jnp.zeros((), jnp.float32)
    ks_in = cache.kscale if quantized else dummy
    vs_in = cache.vscale if quantized else dummy
    scalar = P()
    out = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, None, None),                    # q
                  P(bspec, None, sspec, None),             # k
                  P(bspec, None, sspec, None),             # v
                  P(bspec, None, sspec) if quantized else scalar,
                  P(bspec, None, sspec) if quantized else scalar,
                  P(bspec, None, sspec),                   # valid
                  P(bspec, None, sspec)),                  # scores
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )(q, cache.k, cache.v, ks_in, vs_in, cache.valid, biased)
    return out.reshape(b, hq, -1)


def fused_auto_decision() -> dict:
    """How `PruneConfig(fused="auto")` resolves on this backend, and why
    (benches record this into BENCH_latency.json).

    On TPU auto picks the fused engine: the Pallas kernels keep the
    [B,Hq,S] scores and the index tensors of the composed path in VMEM
    and never write them to HBM. They do NOT read only the winners' K/V
    rows: they stream every live K/V block and attend through the winner
    mask, so per step they read the mirror plus all live K/V, where the
    composed path gathers only select_k rows. Whether the fused engine is
    the faster one on the chip is therefore open until a chip benchmark
    times the two engines (ROADMAP Queue 1 item 3); that race decides
    whether auto keeps resolving to fused there. Off-TPU the kernel would
    lower to the XLA fallback (`ref.fused_decode_ref`), which CPU
    profiling at ctx512 put at parity-to-~6%-slower than the composed
    path, so auto runs the composed path there."""
    on_tpu = jax.default_backend() == "tpu"
    return {
        "engine": "fused" if on_tpu else "composed",
        "backend": jax.default_backend(),
        "reason": ("pallas kernels: scores and winners stay in VMEM; all "
                   "live K/V streamed (speed vs composed not yet measured)"
                   if on_tpu else
                   "xla fallback measured at parity-to-slower vs the "
                   "composed path off-TPU"),
    }


def decode_engine(prune: PruneConfig, latent_cache: bool = False) -> str:
    """The decode engine `prune` resolves to outside a mesh context:
    "fused" or "composed". Only unicaim has a fused engine, and it covers
    the paper-default decode configuration; anything it doesn't
    (threshold race, exact accumulation, MLA latent caches) runs the
    composed oracle path."""
    if prune.fused == "auto":
        enabled = fused_auto_decision()["engine"] == "fused"
    else:
        enabled = bool(prune.fused)
    if (enabled and prune.policy == "unicaim"
            and prune.select_mode == "topk"
            and prune.accumulate == "approx" and not latent_cache
            and prune.select_k % max(1, prune.select_blocks) == 0):
        return "fused"
    return "composed"


def _fused_eligible(cache: KVCache, prune: PruneConfig) -> bool:
    from repro.runtime.sharding import active_mesh
    # under a mesh context the composed path owns distribution (shard
    # constraint re-pinning / the shard_map race); the fused kernel is
    # unsharded and would force GSPMD to all-gather the cache around the
    # pallas_call. (ServeLoop(mesh=...) sets no such context: its
    # shard_map body runs the kernel per shard.)
    return (decode_engine(prune, latent_cache=cache.v is None) == "fused"
            and active_mesh() is None)


def _fused_decode_attend(cache: KVCache, q: jax.Array, prune: PruneConfig
                         ) -> Tuple[KVCache, jax.Array]:
    """Single-pass fused engine: one kernel (or one fused XLA region) does
    CAM scoring over the mirror, top-k selection, exact attention over
    the winners, AND emits the charge-domain accumulation probabilities
    — no [B,Hq,S] scores or index tensors between passes."""
    from repro.kernels import ops

    b, hq, d = q.shape
    hk = cache.k.shape[1]
    g = hq // hk
    s = cache.slots
    dv = cache.v.shape[-1]
    qq, qs = quant.quantize_query(q, prune.query_bits)
    mirror = cache.kq if cache.kq is not None else cache.k
    if cache.quantized_kv:
        kscale, vscale = cache.kscale, cache.vscale
    else:
        kscale = jnp.ones((b, hk, s), jnp.float32)
        vscale = kscale
    prot = protected_mask(cache, prune)

    def bhf(x):                               # [B, Hk, ...] → [B·Hk, ...]
        return x.reshape((b * hk,) + x.shape[2:])

    nb = max(1, prune.select_blocks)
    # per-lane live counts drive the ragged kernel's early exit (global
    # selection only — a block race would change per-block winner counts)
    fills = jnp.repeat(cache.fill, hk) if nb == 1 else None
    out, probs = ops.fused_decode(
        q.reshape(b, hk, g, d).reshape(b * hk, g, d),
        qq.reshape(b, hk, g, d).reshape(b * hk, g, d),
        qs.reshape(b * hk, g),
        bhf(mirror), bhf(cache.kscale), bhf(kscale), bhf(vscale),
        bhf(cache.valid.astype(jnp.int8)), bhf(prot.astype(jnp.int8)),
        bhf(cache.k), bhf(cache.v),
        select_k=prune.select_k, num_blocks=nb,
        backend=prune.fused_backend, fills=fills)
    out = out.reshape(b, hk, g, dv).reshape(b, hq, dv)
    acc = cache.acc * prune.acc_decay + probs.reshape(b, hk, s)
    return cache._replace(acc=acc), out


def windowed_decode_attention(cache: KVCache, q: jax.Array,
                              k_new: jax.Array, v_new: jax.Array,
                              prune: PruneConfig, window: Optional[int],
                              ) -> Tuple[KVCache, jax.Array]:
    """One decode step over the `[:window]` slot prefix of the cache.

    `window` is a STATIC width (the caller picks it on the host from the
    lane fills — see `cache.decode_window`); None or >= slots runs the
    full-width step. Because every live slot sits in the fill prefix and
    slots >= fill are invalid (NEG_INF-scored, zero-probability,
    zero-accumulation), the windowed step is bit-identical to the
    full-width one while touching O(window) instead of O(slots) bytes —
    the decode-cost-tracks-live-context contract of the paper."""
    if window is None or window >= cache.slots:
        return decode_attention(cache, q, k_new, v_new, prune)
    win, out = decode_attention(slot_window(cache, window), q, k_new,
                                v_new, prune)
    return slot_window_merge(cache, win), out


def decode_attention(cache: KVCache, q: jax.Array, k_new: jax.Array,
                     v_new: jax.Array, prune: PruneConfig,
                     ) -> Tuple[KVCache, jax.Array]:
    """One decode step of UniCAIM (or baseline policy) attention.

    q:     [B, Hq, d] current query (post-RoPE)
    k_new: [B, Hk, d], v_new: [B, Hk, dv] current token (post-RoPE)
    returns (updated cache, attention output [B, Hq, dv] f32).
    """
    cache = write_token(cache, k_new, v_new, prune)
    return _policy_attend(cache, q, prune)


def _policy_attend(cache: KVCache, q: jax.Array, prune: PruneConfig,
                   ) -> Tuple[KVCache, jax.Array]:
    """Post-write half of a decode step: policy dispatch (dense / h2o /
    unicaim score→select→attend, fused or composed) + charge-domain
    accumulation. Shared verbatim by the functional `decode_attention`
    and the in-place `decode_attention_stacked` (which hands it a
    windowed read VIEW of the stacked cache), so both paths are the same
    arithmetic — the basis of their bitwise parity."""
    head_dim = q.shape[-1]

    if prune.policy in ("dense", "streaming"):
        out, _ = _dense_attend(cache, q, head_dim)
        return cache, out

    if prune.policy == "h2o":
        out, probs = _dense_attend(cache, q, head_dim)
        acc = scoring.accumulate(cache.acc, probs, cache.k.shape[1],
                                 prune.acc_decay)
        return cache._replace(acc=acc), out

    # ---- unicaim ----
    if _fused_eligible(cache, prune):
        return _fused_decode_attend(cache, q, prune)

    b, hq, _ = q.shape
    hk = cache.k.shape[1]
    # CAM mode: approximate scores over the quantized mirror (in int8-KV
    # mode the stored K itself is the mirror — no second copy)
    qq, qs = quant.quantize_query(q, prune.query_bits)
    mirror = cache.kq if cache.kq is not None else cache.k
    s_approx = scoring.approx_scores(qq, qs, mirror, cache.kscale,
                                     cache.valid)                  # [B,Hq,S]
    grouped = topk.gqa_group_scores(s_approx, hk)                  # [B,Hk,S]
    prot = protected_mask(cache, prune)

    if prune.select_mode == "threshold":
        # CAM race semantics: masked exact attention, no gather. The race
        # runs over finite *evictable* scores only — protected slots (the
        # ±1e30 sentinels of apply_selection_bias) would blow the binary
        # search's resolution out to ~1e27 — and the protected mask is
        # unioned back in, with the per-row target shrunk accordingly.
        evictable = cache.valid & ~prot
        k_dyn = jnp.maximum(
            prune.select_k - jnp.sum(prot, axis=-1, keepdims=True), 1)
        mask = topk.threshold_race(grouped, k_dyn, prune.threshold_iters,
                                   eligible=evictable) | prot      # [B,Hk,S]
        g = hq // hk
        mask_q = jnp.repeat(mask, g, axis=1) if g > 1 else mask
        out, _ = _dense_attend(cache, q, head_dim, mask=mask_q)
    elif prune.select_blocks > 1:
        biased = topk.apply_selection_bias(grouped, prot, ~cache.valid)
        nb = prune.select_blocks
        s = biased.shape[-1]
        assert s % nb == 0 and prune.select_k % nb == 0, (s, prune.select_k)
        from repro.runtime.sharding import active_mesh
        mesh = active_mesh()
        if mesh is not None and _slot_axes(mesh, nb):
            # production path: shard_map keeps select+gather+attend local
            out = _blocked_attend_shardmap(cache, q, biased, prune, mesh)
        else:
            k_loc = prune.select_k // nb
            biased_b = shard(biased.reshape(b, hk, nb, s // nb),
                             "batch", "kv_heads", "slots", None)
            _, idx = topk.exact_topk(biased_b, k_loc)    # [B,Hk,nb,k_loc]
            out = _gathered_attend_blocked(cache, q, idx, head_dim)
    else:
        biased = topk.apply_selection_bias(grouped, prot, ~cache.valid)
        _, idx = topk.exact_topk(biased, prune.select_k)           # [B,Hk,k]
        out, _, _ = _gathered_attend(cache, q, idx, head_dim)

    # charge-domain mode: same-cycle accumulation of approximate probs
    if prune.accumulate == "approx":
        probs_acc = scoring.score_probs(s_approx, head_dim)
    else:  # 'exact' — full-precision probabilities (ablation)
        s_exact = scoring.exact_scores(q, cache.k, cache.valid)
        probs_acc = scoring.score_probs(s_exact, head_dim)
    acc = scoring.accumulate(cache.acc, probs_acc, hk, prune.acc_decay)
    return cache._replace(acc=acc), out


def decode_attention_stacked(kv: KVCache, li, q: jax.Array,
                             k_new: jax.Array, v_new: jax.Array,
                             prune: PruneConfig, window: Optional[int],
                             active: Optional[jax.Array],
                             ) -> Tuple[KVCache, jax.Array]:
    """One IN-PLACE decode step at layer `li` of a layer-stacked cache.

    The zero-copy split of `windowed_decode_attention`: reads go through
    a `dynamic_slice` window VIEW of layer `li` (`layer_window` — pure
    reads, aliasing-safe), writes go straight into the full-width stacked
    buffers as O(B·Hk·dh) scatters plus one O(window) `dynamic_update_
    slice` for the accumulated-score row — never the per-field
    slice-copy + merge round-trip that defeats `donate_argnums`. `kv`
    threads through the caller's layer scan as a CARRY, so under jit the
    whole DecodeState stays input-output aliased across the decode block.

    `active` ([B] bool, optional) freezes finished lanes at the source
    (dropped scatters + kept acc rows) — replacing the full-width
    `state_lane_select` merge of the masked decode block. Active-lane
    arithmetic is `_policy_attend` over the same windowed values the
    functional path sees, hence bitwise-identical outputs; inactive
    lanes' out rows are garbage the caller already masks.

    q: [B, Hq, d]; k_new/v_new: [B, Hk, ·]; window as in
    `windowed_decode_attention` (None = full width — eviction/ring-wrap
    lanes included, since `layer_window` then views every slot).
    Returns (updated stacked cache, out [B, Hq, dv])."""
    w = kv.slots if window is None or window >= kv.slots else window
    view = layer_window(kv, li, w)
    slot, vals = _token_writes(view, k_new, v_new, prune)
    # mirror the token write into the view (all lanes, matching the
    # functional path — inactive lanes' results never land anywhere)
    b, hk = slot.shape
    bi = jnp.arange(b)[:, None]
    hi = jnp.arange(hk)[None, :]
    acc0 = view.acc
    view = view._replace(
        **{f: getattr(view, f).at[bi, hi, slot].set(v)
           for f, v in vals.items()},
        fill=jnp.minimum(view.fill + 1, w), step=view.step + 1)
    view, out = _policy_attend(view, q, prune)
    acc_row = view.acc
    if active is not None:
        acc_row = jnp.where(active[:, None, None], acc_row, acc0)
    # Storage writes LAST, with the scatter index carrying a zero-valued
    # data dependency on the attend output. This is load-bearing for the
    # in-place guarantee: the attend's window reads of the stacked
    # buffers are dataflow-independent of the scatters, and XLA's
    # scheduler is free to place an in-place-aspiring scatter BEFORE a
    # read of the same buffer — copy-insertion then preserves the old
    # value with a full O(slots) carry copy per step, silently
    # resurrecting the copy floor (measured: ~8 MB/step temp at
    # slots=4096; `lax.optimization_barrier` does NOT fix the schedule).
    # Routing `dep == 0` (guaranteed: nan_to_num maps the NaN/Inf edge
    # of 0.0*x to 0.0, and the runtime dependency keeps the product from
    # constant-folding) through the scatter index forces every read to
    # complete first, keeping compiled temp bytes flat in `slots`.
    dep = jnp.nan_to_num(0.0 * (jnp.sum(out) + jnp.sum(acc_row))
                         ).astype(jnp.int32)
    kv = write_token_stacked(kv, li, slot + dep,
                             {f: v for f, v in vals.items() if f != "acc"},
                             active)
    li = jnp.asarray(li, jnp.int32) + dep
    acc = jax.lax.dynamic_update_slice(kv.acc, acc_row[None],
                                       (li, 0, 0, 0))
    return kv._replace(acc=acc), out


# ---------------------------------------------------------------------------
# Prefill: chunked causal attention + accumulated column scores
# ---------------------------------------------------------------------------


def chunked_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             chunk: int = 512, obs_window: int = 0,
                             scale: Optional[float] = None,
                             length: Optional[jax.Array] = None,
                             ) -> Tuple[jax.Array, jax.Array]:
    """Causal attention over the full prompt, scanned over query chunks.

    q: [B, Hq, N, d], k/v: [B, Hk, N, d] → (out [B, Hq, N, dv],
    acc [B, Hk, N] column sums of attention probabilities).

    obs_window > 0 restricts accumulation to the last `obs_window` query rows
    (SnapKV-style); 0 accumulates over all rows (H2O-style, paper default).
    Never materialises the N×N matrix — one [*, chunk, N] tile at a time.

    `length` ([B] int32, optional) marks the true per-lane prompt length
    when the input is right-padded to a shape-stable bucket: rows at or
    beyond `length` never accumulate into the column sums (so pad tokens
    add zero charge-domain mass), the observation window anchors at the
    true length, and pad *columns* are already unreachable for every real
    row via the causal mask (pads sit at the end). Outputs at pad rows are
    garbage and must be ignored by the caller. With `length=None` the full
    width is live — bit-identical to the unbucketed behaviour.
    """
    b, hq, n, d = q.shape
    hk = k.shape[1]
    g = hq // hk
    chunk = min(chunk, n)
    n_real = n
    pad = (-n) % chunk
    if pad:
        # pad rows/cols at the END: causal masking kills pad columns for
        # every real row; pad-row outputs are sliced off below
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        n = n + pad
    n_chunks = n // chunk
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if length is None:
        length = jnp.full((b,), n_real, jnp.int32)
    length = jnp.minimum(length.astype(jnp.int32), n_real)
    # K/V stay in their storage dtype (bf16 in production) — the MXU
    # accumulates in f32 via preferred_element_type; re-reading full K/V per
    # chunk at 2 bytes instead of 4 halves the dominant HBM term (§Perf)
    q = q.astype(k.dtype)
    q_chunks = q.reshape(b, hq, n_chunks, chunk, d).transpose(2, 0, 1, 3, 4)
    col = jnp.arange(n)

    def body(acc, inp):
        ci, q_c = inp                                              # [B,Hq,T,d]
        row = ci * chunk + jnp.arange(chunk)
        q_g = q_c.reshape(b, hk, g, chunk, d)
        logits = jax.lax.dot_general(
            q_g, k, dimension_numbers=(((4,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32)                    # [B,Hk,g,T,N]
        logits = logits.reshape(b, hq, chunk, n)
        causal = row[:, None] >= col[None, :]
        logits = jnp.where(causal[None, None], logits * scale, NEG_INF)
        m = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.exp(logits - m)
        z = jnp.sum(e, axis=-1, keepdims=True)
        probs = e / jnp.maximum(z, 1e-30)                          # [B,Hq,T,N]
        p_g = probs.reshape(b, hk, g, chunk, n).astype(v.dtype)
        out_c = jax.lax.dot_general(
            p_g, v, dimension_numbers=(((4,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32)                    # [B,Hk,g,T,dv]
        live = row[None, :] < length[:, None]   # pad rows add no mass
        if obs_window > 0:
            live = live & (row[None, :] >= (length[:, None] - obs_window))
        w = jnp.where(live, 1.0, 0.0)[:, None, None, :, None]
        acc = acc + jnp.sum(p_g.astype(jnp.float32) * w, axis=(2, 3))
        return acc, out_c.reshape(b, hq, chunk, -1)

    acc0 = jnp.zeros((b, hk, n), jnp.float32)
    acc, outs = xscan(body, acc0, (jnp.arange(n_chunks), q_chunks))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(b, hq, n, -1)
    return out[:, :, :n_real], acc[:, :, :n_real]


def prefill_chunk_attend(q_c: jax.Array, k_buf: jax.Array, v_buf: jax.Array,
                         row0: jax.Array, length: jax.Array,
                         scale: Optional[float] = None,
                         obs_window: int = 0,
                         ) -> Tuple[jax.Array, jax.Array]:
    """One prompt chunk attending into the streamed prefill K/V buffer.

    The chunked-prefill (Sarathi-style admission) analogue of one `body`
    pass of `chunked_causal_attention`: queries for absolute rows
    [row0, row0+C) attend causally over the whole fixed-size buffer
    [B, Hk, N, ·] whose first row0+C rows have been written. Unwritten
    columns sit strictly in the causal future of every chunk row, so the
    causal mask alone keeps them out — the computation is bit-identical to
    the same rows of a whole-prompt `chunked_causal_attention` with
    chunk=C over the same bucket N (same reduction widths, same masked
    exponentials), which is what makes time-sliced admission numerically
    invisible.

    q_c: [B, Hq, C, d]; k_buf/v_buf: [B, Hk, N, ·]; row0: scalar int32
    (may be traced — one compiled program per (C, N) pair); length: [B]
    true prompt lengths. Returns (out [B, Hq, C, dv], col_acc [B, Hk, N]
    — this chunk's contribution to the accumulated column sums).
    """
    b, hq, c, d = q_c.shape
    hk, n = k_buf.shape[1], k_buf.shape[2]
    g = hq // hk
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    q_c = q_c.astype(k_buf.dtype)
    row = row0 + jnp.arange(c)
    col = jnp.arange(n)
    q_g = q_c.reshape(b, hk, g, c, d)
    logits = jax.lax.dot_general(
        q_g, k_buf, dimension_numbers=(((4,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)                        # [B,Hk,g,C,N]
    logits = logits.reshape(b, hq, c, n)
    causal = row[:, None] >= col[None, :]
    logits = jnp.where(causal[None, None], logits * scale, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    z = jnp.sum(e, axis=-1, keepdims=True)
    probs = e / jnp.maximum(z, 1e-30)                              # [B,Hq,C,N]
    p_g = probs.reshape(b, hk, g, c, n).astype(v_buf.dtype)
    out_c = jax.lax.dot_general(
        p_g, v_buf, dimension_numbers=(((4,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)                        # [B,Hk,g,C,dv]
    live = row[None, :] < length[:, None]
    if obs_window > 0:
        live = live & (row[None, :] >= (length[:, None] - obs_window))
    w = jnp.where(live, 1.0, 0.0)[:, None, None, :, None]
    col_acc = jnp.sum(p_g.astype(jnp.float32) * w, axis=(2, 3))
    return out_c.reshape(b, hq, c, -1), col_acc
