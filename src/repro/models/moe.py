"""Mixture-of-Experts layer: top-k routing, sort-based capacity dispatch,
EP sharding.

Dispatch is SORT-based (MaxText/MegaBlocks style): tokens are ordered by
destination expert, placed into a [E, capacity, d] buffer (overflow slots
drop), expert FFNs run as batched einsums with the expert axis sharded over
`model` (EP — XLA inserts the all-to-alls), and outputs are gathered back by
inverse permutation. This is O(T·k·log) routing + O(E·C·d·ff) compute —
the naive one-hot dispatch tensor [T, E, C] would be O(T·E·C) and is
intractable at deepseek-v3 scale (1M tokens × 256 experts × 40k capacity).

Supports shared (always-on) experts and sigmoid gating (DeepSeek-V3 style).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import apply_mlp, init_mlp
from repro.runtime.sharding import shard


def init_moe(key, cfg: ModelConfig, dtype):
    mo = cfg.moe
    keys = jax.random.split(key, 4)
    d, ff, e = cfg.d_model, mo.d_ff_expert, mo.n_experts
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    fscale = 1.0 / jnp.sqrt(jnp.float32(ff))
    p = {
        "router": (jax.random.normal(keys[0], (d, e), jnp.float32)
                   * scale).astype(jnp.float32),          # router in f32
        "wi": (jax.random.normal(keys[1], (e, d, ff), jnp.float32)
               * scale).astype(dtype),
        "wo": (jax.random.normal(keys[2], (e, ff, d), jnp.float32)
               * fscale).astype(dtype),
    }
    if cfg.act == "swiglu":
        p["wg"] = (jax.random.normal(keys[3], (e, d, ff), jnp.float32)
                   * scale).astype(dtype)
    if mo.n_shared > 0:
        skeys = jax.random.split(jax.random.fold_in(key, 7), mo.n_shared)
        p["shared"] = [init_mlp(sk, cfg, ff, dtype) for sk in skeys]
    return p


def _expert_ffn(p, x, act: str):
    """x: [E, C, d] → [E, C, d] with per-expert weights."""
    h = jnp.einsum("ecd,edf->ecf", x, p["wi"])
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, p["wg"])) * h
    elif act == "gelu":
        h = jax.nn.gelu(h)
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    h = shard(h, "experts", None, "ff")
    return jnp.einsum("ecf,efd->ecd", h, p["wo"])


def apply_moe_ep_shardmap(p, x, cfg: ModelConfig, mesh,
                          gating_override: str = None
                          ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map + all_to_all (§Perf beyond-paper).

    The XLA-propagated sort-based dispatch all-gathers the [T·k, d] update
    payload across shards (the dominant collective of the 671B train cell).
    Here each data shard routes its LOCAL tokens, packs per-expert-shard
    send buffers, and a single all_to_all over `model` moves exactly the
    token payloads — the textbook EP schedule. Requires n_experts and
    tokens divisible by the model-axis size.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mo: MoEConfig = cfg.moe
    b, t, d = x.shape
    e, k = mo.n_experts, mo.top_k
    ep = mesh.shape["model"]
    e_loc = e // ep
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape
                       and b % mesh.shape[a] == 0)
    bspec = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    n_loc = (b // dp) * t                       # local tokens per data shard
    # per-(src shard → dst shard) capacity, multiple of 8
    cap = max(-(-int(n_loc * k * mo.capacity_factor) // ep), 8)
    cap = -(-cap // 8) * 8
    gating = gating_override or ("sigmoid" if mo.n_shared else "softmax")

    def local_fn(x_l, router, wi, wg, wo):
        xt = x_l.reshape(-1, d)                               # [n_loc, d]
        logits = xt.astype(jnp.float32) @ router
        scores = (jax.nn.sigmoid(logits) if gating == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        topv, topi = jax.lax.top_k(scores, k)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
        flat_e = topi.reshape(-1)                             # [n_loc·k]
        dest_shard = flat_e // e_loc
        # position within the (dest shard) send queue
        sort_idx = jnp.argsort(dest_shard, stable=True)
        sorted_dst = dest_shard[sort_idx]
        counts = jnp.bincount(dest_shard, length=ep)
        offs = jnp.cumsum(counts) - counts
        pos_in = jnp.arange(n_loc * k, dtype=jnp.int32) - offs[sorted_dst]
        keep = pos_in < cap
        send_slot = jnp.where(keep, sorted_dst * cap + pos_in, ep * cap)
        tok_of = sort_idx // k
        send = jnp.zeros((ep * cap + 8, d), x_l.dtype)
        send = send.at[send_slot].set(xt[tok_of])
        send_eid = jnp.full((ep * cap + 8,), -1, jnp.int32)
        send_eid = send_eid.at[send_slot].set(flat_e[sort_idx])
        # all_to_all: [ep, cap, d] send → [ep, cap, d] recv (per dst shard)
        recv = jax.lax.all_to_all(send[:ep * cap].reshape(ep, cap, d),
                                  "model", 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(
            send_eid[:ep * cap].reshape(ep, cap), "model", 0, 0,
            tiled=False)
        rx = recv.reshape(ep * cap, d)                        # [R, d]
        re_id = recv_eid.reshape(ep * cap)
        # second-stage LOCAL sort-dispatch: each local expert computes only
        # its own rows (a one-hot dense dispatch would multiply the FFN
        # flops by E_loc — measured 29× on deepseek-v3 before this fix)
        r_tot = ep * cap
        le = jnp.where(re_id >= 0, re_id % e_loc, e_loc)      # e_loc = trash
        s_idx = jnp.argsort(le, stable=True)
        s_le = le[s_idx]
        cnts = jnp.bincount(le, length=e_loc + 1)
        offs = jnp.cumsum(cnts) - cnts
        cap2 = -(-int(r_tot // max(e_loc, 1) * 1.25) // 8) * 8
        pos2 = jnp.arange(r_tot, dtype=jnp.int32) - offs[s_le]
        ok2 = (pos2 < cap2) & (s_le < e_loc)
        dest2 = jnp.where(ok2, s_le * cap2 + pos2, e_loc * cap2)
        buf = jnp.zeros((e_loc * cap2 + 1, d), rx.dtype)
        buf = buf.at[dest2].set(rx[s_idx])
        ex_in = buf[:-1].reshape(e_loc, cap2, d)              # [E_loc,C2,d]
        h = jnp.einsum("ecd,edf->ecf", ex_in, wi)
        if cfg.act == "swiglu":
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ex_in, wg)) * h
        elif cfg.act == "gelu":
            h = jax.nn.gelu(h)
        else:
            h = jnp.square(jax.nn.relu(h))
        ex_out = jnp.einsum("ecf,efd->ecd", h, wo)
        out_buf = jnp.concatenate(
            [ex_out.reshape(e_loc * cap2, d),
             jnp.zeros((1, d), ex_out.dtype)], 0)
        inv2 = jnp.zeros((r_tot,), jnp.int32).at[s_idx].set(
            dest2.astype(jnp.int32))
        y_rx = out_buf[inv2]                                  # [R, d]
        # return payloads to source shards
        back = jax.lax.all_to_all(y_rx.reshape(ep, cap, d), "model", 0, 0,
                                  tiled=False).reshape(ep * cap, d)
        back = jnp.concatenate([back, jnp.zeros((8, d), back.dtype)], 0)
        y_sorted = back[send_slot]                            # [n_loc·k, d]
        gate_sorted = (topv.reshape(-1)[sort_idx] * keep)[:, None]
        contrib = y_sorted.astype(jnp.float32) * gate_sorted
        y = jnp.zeros((n_loc, d), jnp.float32).at[tok_of].add(contrib)
        # aux loss (local fractions; mean over shards via psum)
        probs = jax.nn.softmax(logits, axis=-1)
        f_e = jnp.bincount(flat_e, length=e).astype(jnp.float32) / (n_loc * k)
        aux = e * jnp.sum(f_e * jnp.mean(probs, axis=0)) * mo.aux_loss_weight
        aux = jax.lax.pmean(aux, batch_axes) if batch_axes else aux
        return y.reshape(x_l.shape).astype(x_l.dtype), aux

    scalarP = P()
    y, aux = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, None, None), scalarP,
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(bspec, None, None), scalarP),
        check_vma=False,
    )(x, p["router"], p["wi"], p.get("wg", p["wi"]), p["wo"])

    if mo.n_shared > 0:
        for sp in p["shared"]:
            y = y + apply_mlp(sp, x, cfg.act)
    return y, aux


def apply_moe(p, x, cfg: ModelConfig, gating_override: str = None
              ) -> Tuple[jax.Array, jax.Array]:
    """x: [B,T,d] → (y [B,T,d], aux_loss scalar)."""
    mo: MoEConfig = cfg.moe
    b, t, d = x.shape
    n_tok = b * t
    e, k = mo.n_experts, mo.top_k
    cap = max(-(-int(n_tok * k * mo.capacity_factor) // e), 8)
    cap = -(-cap // 8) * 8                                 # round up to 8

    xt = x.reshape(n_tok, d)
    logits = xt.astype(jnp.float32) @ p["router"]          # [T, E]
    gating = gating_override or ("sigmoid" if mo.n_shared else "softmax")
    if gating == "sigmoid":                                # deepseek-v3 style
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(scores, k)                  # [T, k]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch ----
    flat_e = topi.reshape(-1)                              # [T·k]
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    counts = jnp.bincount(flat_e, length=e)                # [E]
    offsets = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(n_tok * k, dtype=jnp.int32) - offsets[sorted_e]
    keep_sorted = pos_in_e < cap
    trash = e * cap                                        # overflow slot
    dest_sorted = jnp.where(keep_sorted, sorted_e * cap + pos_in_e, trash)
    token_of = sort_idx // k                               # source token

    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[dest_sorted].set(xt[token_of])
    ex_in = shard(buf[:-1].reshape(e, cap, d), "experts", None, None)
    ex_out = _expert_ffn(p, ex_in, cfg.act)
    out_buf = jnp.concatenate(
        [ex_out.reshape(e * cap, d), jnp.zeros((1, d), ex_out.dtype)], 0)

    # inverse permutation → per-(token, choice) output rows
    dest = jnp.zeros((n_tok * k,), jnp.int32).at[sort_idx].set(
        dest_sorted.astype(jnp.int32))
    y = (out_buf[dest].reshape(n_tok, k, d).astype(jnp.float32)
         * topv[..., None]).sum(axis=1).astype(x.dtype)

    # load-balancing aux loss (Switch): E * Σ_e f_e · P_e
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = counts.astype(jnp.float32) / (n_tok * k)
    frac_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_prob) * mo.aux_loss_weight

    if mo.n_shared > 0:
        ys = xt.reshape(b, t, d)
        for sp in p["shared"]:
            y = y + apply_mlp(sp, ys, cfg.act).reshape(n_tok, d)
    return y.reshape(b, t, d), aux
