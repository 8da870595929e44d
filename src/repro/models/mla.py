"""DeepSeek-V3 Multi-head Latent Attention (MLA) with UniCAIM pruning in
LATENT space — a beyond-paper extension (DESIGN.md §5).

The decode cache holds the compressed per-token latent u = [c_kv ⊕ k_rope]
(kv_lora + rope dims). Scores are computed with the absorbed query
q_abs = [W_ukᵀ q_nope ⊕ q_rope], so both the CAM-mode approximate pass and
the exact pass run directly on the latent mirror:

    q·k  ==  q_nope·(W_uk c) + q_rope·k_rope  ==  q_abs·u

Values are never materialised per token: attention contracts probabilities
against the latents and up-projects once (ctx @ W_uv).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, PruneConfig
from repro.core import quant, scoring, topk
from repro.core.cache import (KVCache, _token_writes, layer_window,
                              prefill_fill, protected_mask, write_token,
                              write_token_stacked)
from repro.core.topk import NEG_INF
from repro.models.layers import dense_init, rope
from repro.runtime.sharding import shard


def init_mla(key, cfg: ModelConfig, dtype):
    m = cfg.mla
    ks = jax.random.split(key, 6)
    h = cfg.n_heads
    return {
        "wq_a": dense_init(ks[0], cfg.d_model, m.q_lora_rank, dtype),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "wq_b": dense_init(ks[1], m.q_lora_rank,
                           h * (m.qk_nope_dim + m.qk_rope_dim), dtype),
        "wkv_a": dense_init(ks[2], cfg.d_model,
                            m.kv_lora_rank + m.qk_rope_dim, dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wkv_b": dense_init(ks[3], m.kv_lora_rank,
                            h * (m.qk_nope_dim + m.v_dim), dtype),
        "wo": dense_init(ks[4], h * m.v_dim, cfg.d_model, dtype),
    }


def _rms(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _split_wkv_b(p, cfg: ModelConfig):
    m = cfg.mla
    w = p["wkv_b"].reshape(m.kv_lora_rank, cfg.n_heads,
                           m.qk_nope_dim + m.v_dim)
    return w[..., :m.qk_nope_dim], w[..., m.qk_nope_dim:]   # W_uk, W_uv


def _queries(p, x, cfg: ModelConfig, positions):
    """x [B,T,d] → q_nope [B,T,H,nope], q_rope [B,T,H,rope] (RoPE'd)."""
    m = cfg.mla
    b, t, _ = x.shape
    cq = _rms(x @ p["wq_a"], p["q_norm"])
    q = (cq @ p["wq_b"]).reshape(b, t, cfg.n_heads,
                                 m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _latents(p, x, cfg: ModelConfig, positions):
    """x [B,T,d] → u [B,T,kv_lora+rope] (c_kv normed, k_rope RoPE'd)."""
    m = cfg.mla
    kv = x @ p["wkv_a"]
    c_kv = _rms(kv[..., :m.kv_lora_rank], p["kv_norm"])
    k_rope = rope(kv[..., m.kv_lora_rank:][..., None, :], positions,
                  cfg.rope_theta)[..., 0, :]
    return jnp.concatenate([c_kv, k_rope], axis=-1)


def _mla_attend(p, x, cfg: ModelConfig, positions, chunk: int,
                obs_window: int = 0, length=None):
    """Absorbed-form chunked causal MLA.

    Returns (out [B,T,d], u [B,T,latent], acc [B,1,T]). Never materialises
    the T×T matrix or per-head K/V: scores and context both contract against
    the shared latent (one "kv head"), then a single per-head up-projection.
    """
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope = _queries(p, x, cfg, positions)
    u = _latents(p, x, cfg, positions)
    w_uk, w_uv = _split_wkv_b(p, cfg)
    q_abs = jnp.einsum("bthn,khn->bthk", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))            # [B,T,H,kv_lora]
    q_full = jnp.concatenate([q_abs, q_rope.astype(jnp.float32)], -1)
    q_full = shard(q_full.transpose(0, 2, 1, 3), "batch", "heads", "seq",
                   None)                                    # [B,H,T,latent]
    scale = 1.0 / jnp.sqrt(float(m.qk_nope_dim + m.qk_rope_dim))
    from repro.core.attention import chunked_causal_attention
    ctx, acc = chunked_causal_attention(
        q_full.astype(jnp.float32), u[:, None],             # Hk = 1
        u[:, None, :, :m.kv_lora_rank], chunk=min(chunk, t), scale=scale,
        obs_window=obs_window, length=length)               # ctx [B,H,T,kvr]
    out = jnp.einsum("bhtk,khv->bthv", ctx, w_uv.astype(jnp.float32))
    out = out.reshape(b, t, h * m.v_dim).astype(x.dtype)
    return out @ p["wo"], u, acc


def mla_train(p, x, cfg: ModelConfig, positions, chunk: int = 0):
    """Chunked causal MLA for training. [B,T,d]→[B,T,d]."""
    out, _, _ = _mla_attend(p, x, cfg, positions, chunk or cfg.attn_chunk)
    return out


def mla_prefill(p, x, cfg: ModelConfig, positions, prune: PruneConfig,
                cache: KVCache, chunk: int = 0, length=None):
    """Prefill with one-shot static pruning of the LATENT cache.

    `length` ([B] int32, optional): true per-lane lengths for bucketed
    (right-padded) prompts."""
    out, u, acc = _mla_attend(p, x, cfg, positions, chunk or cfg.attn_chunk,
                              obs_window=prune.prefill_obs_window,
                              length=length)
    cache = prefill_fill(cache, u[:, None, :, :], None, acc, prune,
                         length=length)
    return out, cache


def _mla_blocked_shardmap(cache: KVCache, q_full: jax.Array,
                          biased: jax.Array, prune: PruneConfig, mesh,
                          kv_lora: int, scale_dim: int) -> jax.Array:
    """Shard-local latent selection for MLA decode (distributed CAM race
    over the latent mirror). Returns ctx [B, H, kv_lora]."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.core.attention import _slot_axes

    b, h, lat = q_full.shape
    nb = prune.select_blocks
    k_loc = prune.select_k // nb
    slot_axes = _slot_axes(mesh, nb)
    red = slot_axes if len(slot_axes) > 1 else slot_axes[0]
    batch_axes = tuple(a for a in ("pod", "data")
                       if a in mesh.shape and a not in slot_axes
                       and b % mesh.shape[a] == 0)
    bspec = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    sspec = slot_axes if len(slot_axes) > 1 else slot_axes[0]
    quantized = cache.quantized_kv

    def local_fn(q_l, u_l, ks_l, valid_l, sc_l):
        _, idx = jax.lax.top_k(sc_l, k_loc)                # [b,1,k_loc]
        u_sel = jnp.take_along_axis(u_l, idx[..., None], axis=2)[:, 0]
        if quantized:
            us = jnp.take_along_axis(ks_l, idx, axis=2)[:, 0]
            u_sel = u_sel.astype(jnp.float32) * us[..., None]
        valid_sel = jnp.take_along_axis(valid_l, idx, axis=2)[:, 0]
        logits = jnp.einsum("bhl,bkl->bhk", q_l.astype(jnp.float32),
                            u_sel.astype(jnp.float32))
        logits = logits / jnp.sqrt(jnp.float32(scale_dim))
        logits = jnp.where(valid_sel[:, None, :], logits, NEG_INF)
        mx = jax.lax.pmax(jnp.max(logits, -1, keepdims=True), red)
        e = jnp.exp(logits - mx) * (logits > NEG_INF / 2)
        z = jax.lax.psum(jnp.sum(e, axis=-1), red)         # [b,H]
        ctx = jnp.einsum("bhk,bkl->bhl", e,
                         u_sel[..., :kv_lora].astype(jnp.float32))
        ctx = jax.lax.psum(ctx, red)
        return ctx / jnp.maximum(z, 1e-30)[..., None]

    scalar = P()
    ks_in = cache.kscale if quantized else jnp.zeros((), jnp.float32)
    return shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, None, None),
                  P(bspec, None, sspec, None),
                  P(bspec, None, sspec) if quantized else scalar,
                  P(bspec, None, sspec),
                  P(bspec, None, sspec)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(q_full.astype(jnp.float32), cache.k, ks_in, cache.valid, biased)


def mla_decode(p, x, cfg: ModelConfig, cache: KVCache, prune: PruneConfig
               ) -> Tuple[jax.Array, KVCache]:
    """One decode step with UniCAIM selection in latent space.

    x: [B,d] → (y [B,d], cache). Cache holds latents (Hk=1, v=None).
    """
    m = cfg.mla
    b, _ = x.shape
    h = cfg.n_heads
    pos = cache.step[:, None]                               # [B,1]
    q_nope, q_rope = _queries(p, x[:, None, :], cfg, pos)
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]             # [B,H,*]
    u_new = _latents(p, x[:, None, :], cfg, pos)[:, 0]      # [B,latent]
    cache = write_token(cache, u_new[:, None, :], None, prune)

    w_uk, w_uv = _split_wkv_b(p, cfg)
    q_abs = jnp.einsum("bhn,khn->bhk", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    q_full = jnp.concatenate([q_abs, q_rope.astype(jnp.float32)], -1)
    ctx, cache = _latent_attend(cache, q_full, cfg, prune)
    out = jnp.einsum("bhk,khv->bhv", ctx, w_uv.astype(jnp.float32))
    y = out.reshape(b, h * m.v_dim).astype(x.dtype) @ p["wo"]
    return y, cache


def mla_decode_stacked(p, x, cfg: ModelConfig, kv: KVCache, li,
                       prune: PruneConfig, window, active
                       ) -> Tuple[jax.Array, KVCache]:
    """One IN-PLACE decode step at layer `li` of a layer-stacked LATENT
    cache — the MLA twin of `core.attention.decode_attention_stacked`.

    Same zero-copy split: reads go through a `dynamic_slice` window view
    of layer `li` (`layer_window`), the token write mirrors into the view
    for the attend and then lands in the full-width stacked buffers as
    O(B·latent) scatters (Hk = 1) plus one O(window) accumulator-row
    update, with the zero-valued `dep` index trick pinning the schedule
    so XLA keeps the scan carry aliased (see decode_attention_stacked for
    why that is load-bearing). `active` freezes finished lanes at the
    source exactly as in the GQA path. x: [B,d] post-norm hidden.
    Returns (y [B,d], stacked cache)."""
    m = cfg.mla
    b, _ = x.shape
    h = cfg.n_heads
    w = kv.slots if window is None or window >= kv.slots else window
    view = layer_window(kv, li, w)
    pos = view.step[:, None]                                # [B,1]
    q_nope, q_rope = _queries(p, x[:, None, :], cfg, pos)
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]             # [B,H,*]
    u_new = _latents(p, x[:, None, :], cfg, pos)[:, 0]      # [B,latent]
    slot, vals = _token_writes(view, u_new[:, None, :], None, prune)
    # mirror the token write into the view (all lanes, matching the
    # functional path — inactive lanes' results never land anywhere)
    bi = jnp.arange(b)[:, None]
    hi = jnp.arange(1)[None, :]
    acc0 = view.acc
    view = view._replace(
        **{f: getattr(view, f).at[bi, hi, slot].set(v)
           for f, v in vals.items()},
        fill=jnp.minimum(view.fill + 1, w), step=view.step + 1)

    w_uk, w_uv = _split_wkv_b(p, cfg)
    q_abs = jnp.einsum("bhn,khn->bhk", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    q_full = jnp.concatenate([q_abs, q_rope.astype(jnp.float32)], -1)
    ctx, view = _latent_attend(view, q_full, cfg, prune)
    acc_row = view.acc
    if active is not None:
        acc_row = jnp.where(active[:, None, None], acc_row, acc0)
    out = jnp.einsum("bhk,khv->bhv", ctx, w_uv.astype(jnp.float32))
    y = out.reshape(b, h * m.v_dim).astype(x.dtype) @ p["wo"]
    # storage writes LAST, index-dependent on the attend output (ctx
    # covers every latent-buffer read, acc_row the accumulator reads —
    # see decode_attention_stacked for why this pin is load-bearing)
    dep = jnp.nan_to_num(0.0 * (jnp.sum(ctx) + jnp.sum(acc_row))
                         ).astype(jnp.int32)
    kv = write_token_stacked(kv, li, slot + dep,
                             {f: v for f, v in vals.items() if f != "acc"},
                             active)
    li = jnp.asarray(li, jnp.int32) + dep
    acc = jax.lax.dynamic_update_slice(kv.acc, acc_row[None],
                                       (li, 0, 0, 0))
    return y, kv._replace(acc=acc)


def _latent_attend(cache: KVCache, q_full: jax.Array, cfg: ModelConfig,
                   prune: PruneConfig) -> Tuple[jax.Array, KVCache]:
    """Policy attend over a latent cache that already holds the new token.

    q_full: [B,H,latent] absorbed query. Returns (ctx [B,H,kv_lora],
    cache with the charge-domain accumulator updated). Shared verbatim by
    the functional `mla_decode` and the in-place `mla_decode_stacked`
    (which hands it a windowed read VIEW of the stacked cache), so both
    paths are the same arithmetic — the basis of their bitwise parity."""
    m = cfg.mla
    scale_dim = m.qk_nope_dim + m.qk_rope_dim

    if prune.policy == "unicaim":
        qq, qs = quant.quantize_query(q_full, prune.query_bits)
        mirror = cache.kq if cache.kq is not None else cache.k
        s_apx = scoring.approx_scores(qq, qs, mirror, cache.kscale,
                                      cache.valid)          # [B,H,S]
        grouped = topk.gqa_group_scores(s_apx, 1)           # [B,1,S]
        biased = topk.apply_selection_bias(
            grouped, protected_mask(cache, prune), ~cache.valid)
        from repro.core.attention import _slot_axes
        from repro.runtime.sharding import active_mesh
        mesh = active_mesh()
        if (prune.select_blocks > 1 and mesh is not None
                and _slot_axes(mesh, prune.select_blocks)):
            ctx = _mla_blocked_shardmap(cache, q_full, biased, prune,
                                        mesh, m.kv_lora_rank, scale_dim)
        else:
            _, idx = topk.exact_topk(biased, prune.select_k)  # [B,1,k]
            u_sel = jnp.take_along_axis(cache.k, idx[..., None],
                                        axis=2)[:, 0]
            if cache.quantized_kv:
                u_scale = jnp.take_along_axis(cache.kscale, idx,
                                              axis=2)[:, 0]
                u_sel = u_sel.astype(jnp.float32) * u_scale[..., None]
            valid_sel = jnp.take_along_axis(cache.valid, idx, axis=2)[:, 0]
            logits = jnp.einsum("bhk,bsk->bhs", q_full,
                                u_sel.astype(jnp.float32)) / jnp.sqrt(
                                    float(scale_dim))
            logits = jnp.where(valid_sel[:, None, :], logits, NEG_INF)
            pr = jax.nn.softmax(logits, axis=-1)            # [B,H,k]
            ctx = jnp.einsum("bhs,bsk->bhk", pr,
                             u_sel[..., :m.kv_lora_rank]
                             .astype(jnp.float32))
        probs_acc = scoring.score_probs(s_apx, scale_dim)
        acc = scoring.accumulate(cache.acc, probs_acc, 1, prune.acc_decay)
        cache = cache._replace(acc=acc)
    else:  # dense / h2o / streaming over the latent cache
        u_all = cache.k_values()[:, 0].astype(jnp.float32)
        logits = jnp.einsum("bhk,bsk->bhs", q_full, u_all) / jnp.sqrt(
            float(scale_dim))
        logits = jnp.where(cache.valid[:, 0][:, None, :], logits, NEG_INF)
        pr = jax.nn.softmax(logits, axis=-1)
        ctx = jnp.einsum("bhs,bsk->bhk", pr,
                         u_all[:, :, :m.kv_lora_rank])
        if prune.policy == "h2o":
            acc = scoring.accumulate(cache.acc, pr, 1, prune.acc_decay)
            cache = cache._replace(acc=acc)
    return ctx, cache
