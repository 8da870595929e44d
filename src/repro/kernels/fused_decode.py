"""Pallas TPU kernel — the fused single-pass pruned-decode engine.

One kernel per (batch·kv-head, slot-block) grid cell runs the whole UniCAIM
decode pipeline that `core/attention.py` otherwise composes from three
passes (approx_score → top-k → gather_attention):

  1. CAM mode     — int8 scores for the block's slots from the quantized
                    key mirror,
  2. CAM race     — block-local top-k selection entirely in VMEM
                    (iterative max race; protected slots always win),
  3. current mode — exact online-softmax attention over the block's
                    winners, accumulated across blocks,
  4. charge mode  — per-slot approximate probabilities (the accumulated-
                    score update) emitted from the score scratch at the
                    last block.

Nothing round-trips HBM between the stages: the [B,Hq,S] score tensor and
[B,Hk,nb,k] index tensor of the composed path never materialize. The
race yields a winner MASK, not an index list, so the current-mode pass is
a masked attention over the K/V block the pipeline already streamed into
VMEM. A per-row winner DMA is not an option on the TPU: the HBM layout
tiles K/V rows (16 bf16 rows per tile, d=64 padded to 128 lanes), and
Mosaic refuses a one-row slice of a tile.

Selection semantics match the composed path: with num_blocks == 1 this is
the global `exact_topk`; with num_blocks == nb it is the hierarchical
per-block race of `select_blocks = nb` (`_gathered_attend_blocked`).

  q      [BH, G, d]   storage dtype   exact queries (GQA group per kv head)
  qq     [BH, G, d]   int8            quantized queries (CAM drive lines)
  qscale [BH, G]      f32
  mirror [BH, S, d]   int8            key mirror (int8-KV mode: K itself)
  mscale [BH, S]      f32             mirror dequant scale
  kscale [BH, S]      f32             K-row dequant scale (ones for bf16)
  vscale [BH, S]      f32             V-row dequant scale (ones for bf16)
  valid  [BH, S]      int8
  prot   [BH, S]      int8            protected (sinks + recent): race bias
  k      [BH, S, d]   storage dtype   exact keys
  v      [BH, S, dv]  storage dtype   exact values
  out    [BH, G, dv]  f32
  probs  [BH, S]      f32            Σ_g softmax_g(scores/√d) — acc update

Inside the kernel every per-slot vector is a lane row ([1, S] or [8, bs]
blocks of `meta`) and every per-query scalar a column ([G, 1]), so no
value ever changes between the lane and sublane axes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Selection-score sentinels. Invalid slots carry G·NEG_INF after the group
# sum, so the "already picked" marker must sit strictly below any of them
# for the race to pick distinct slots exactly like lax.top_k.
PROT_WIN = 1e30
PICKED = -1e35

# Rows of the packed per-slot metadata operand ([BH, META_ROWS, S] f32).
MS, KS, VS, VALID, PROT = range(5)
META_ROWS = 8                  # one f32 sublane tile
_HI = jax.lax.Precision.HIGHEST


def pack_meta(mscale, kscale, vscale, valid, prot, s_pad: int) -> jax.Array:
    """[BH, S] per-slot vectors → one [BH, 8, s_pad] f32 operand (rows MS,
    KS, VS, VALID, PROT, zeros). Slots past S are invalid and unprotected."""
    rows = [x.astype(jnp.float32) for x in (mscale, kscale, vscale, valid,
                                            prot)]
    meta = jnp.stack(rows, axis=1)
    bh, r, s = meta.shape
    return jnp.pad(meta, ((0, 0), (0, META_ROWS - r), (0, s_pad - s)))


def cam_scores(qq, qs, mir, meta):
    """CAM mode for one slot block: [G, d] int8 · [bs, d] int8 → masked
    scores [G, bs]. The integer operands are exact in bf16 and their
    products sum exactly in f32, so this equals the oracle's f32 einsum.
    The precision is pinned: under `default_matmul_precision("highest")`
    Mosaic would be asked for an fp32 contraction of bf16 operands, which
    it refuses."""
    raw = jax.lax.dot_general(
        qq.astype(jnp.bfloat16), mir.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)                # [G, bs]
    raw = raw * qs * meta[MS:MS + 1]
    return jnp.where(meta[VALID:VALID + 1] != 0, raw, NEG_INF)


def race(ssel, n_pick: int):
    """CAM race: mask of the `n_pick` largest entries of ssel [1, n], ties
    to the lower slot (lax.top_k's order). Each round takes the row max,
    then the first lane holding it — two lane reductions, no scalar."""
    n = ssel.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, ssel.shape, 1)

    def pick(_, sc):
        m = jnp.max(sc, axis=-1, keepdims=True)
        idx = jnp.min(jnp.where(sc == m, iota, n), axis=-1, keepdims=True)
        return jnp.where(iota == idx, PICKED, sc)

    return jax.lax.fori_loop(0, n_pick, pick, ssel) == PICKED


def attend_block(q, k, v, meta, sel, scale, m_sc, l_sc, o_sc, n_valid=None):
    """Current mode for one slot block: online-softmax attention of q
    [G, d] over the block's winners (`sel` [1, bs] & valid), folding the
    per-slot K/V dequant scales into the logits and the probabilities.
    `n_valid` (static) marks rows past the array end in a partial last
    block: their V rows are zeroed so 0·garbage cannot poison p @ V."""
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if n_valid is not None:
        rows = jax.lax.broadcasted_iota(jnp.int32, vf.shape, 0)
        vf = jnp.where(rows < n_valid, vf, 0.0)
    logits = jax.lax.dot_general(
        q.astype(jnp.float32), kf,
        dimension_numbers=(((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)                # [G, bs]
    logits = logits * meta[KS:KS + 1] * scale
    live = sel & (meta[VALID:VALID + 1] != 0)
    logits = jnp.where(live, logits, NEG_INF)
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(logits > NEG_INF / 2, jnp.exp(logits - m_new), 0.0)
    l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    o_sc[...] = o_sc[...] * corr + jax.lax.dot(
        p * meta[VS:VS + 1], vf, precision=_HI,
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new


def charge_probs(buf, scale):
    """Charge mode: Σ_g softmax_g(buf · scale) over the slots → [1, S]."""
    lg = buf * scale
    mg = jnp.max(lg, axis=-1, keepdims=True)
    e = jnp.where(buf > NEG_INF / 2, jnp.exp(lg - mg), 0.0)
    z = jnp.sum(e, axis=-1, keepdims=True)
    return jnp.sum(e / jnp.maximum(z, 1e-30), axis=0, keepdims=True)


def init_softmax(m_sc, l_sc, o_sc):
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    o_sc[...] = jnp.zeros_like(o_sc)


def lane_offset(j, bs: int):
    """Start of slot block j in a lane row, with its alignment stated."""
    return pl.multiple_of(j * bs, bs)


def _fused_decode_kernel(q_ref, qq_ref, qs_ref, mir_ref, meta_ref, k_ref,
                         v_ref, out_ref, probs_ref,
                         score_buf, m_sc, l_sc, o_sc,
                         *, nb, bs, k_loc, scale):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_softmax(m_sc, l_sc, o_sc)

    meta = meta_ref[0]                                     # [8, bs]
    raw = cam_scores(qq_ref[0], qs_ref[0], mir_ref[0], meta)
    score_buf[:, pl.ds(lane_offset(j, bs), bs)] = raw
    ssel = jnp.sum(raw, axis=0, keepdims=True)             # [1, bs]
    ssel = jnp.where(meta[PROT:PROT + 1] != 0, PROT_WIN, ssel)
    attend_block(q_ref[0], k_ref[0], v_ref[0], meta, race(ssel, k_loc),
                 scale, m_sc, l_sc, o_sc)

    @pl.when(j == nb - 1)
    def _flush():
        out_ref[0] = o_sc[...] / jnp.maximum(l_sc[...], 1e-30)
        probs_ref[0] = charge_probs(score_buf[...], scale)


def _block_pad(x: jax.Array, nb: int, bs0: int, bs: int) -> jax.Array:
    """Pad each of the nb slot blocks from bs0 to bs rows IN PLACE.

    Interleaved (per-block) padding keeps the selection partition identical
    to the unpadded layout — block j still covers original slots
    [j·bs0, (j+1)·bs0) — unlike trailing padding, which would shift block
    boundaries and change which slots race each other."""
    bh = x.shape[0]
    tail = x.shape[2:]
    xb = x.reshape((bh, nb, bs0) + tail)
    widths = [(0, 0), (0, 0), (0, bs - bs0)] + [(0, 0)] * len(tail)
    return jnp.pad(xb, widths).reshape((bh, nb * bs) + tail)


@functools.partial(jax.jit,
                   static_argnames=("select_k", "num_blocks", "interpret",
                                    "block_align"))
def fused_decode(q: jax.Array, qq: jax.Array, qscale: jax.Array,
                 mirror: jax.Array, mscale: jax.Array, kscale: jax.Array,
                 vscale: jax.Array, valid: jax.Array, prot: jax.Array,
                 k: jax.Array, v: jax.Array, *, select_k: int,
                 num_blocks: int = 1, interpret: bool = False,
                 block_align: int = 0):
    """Single-pass pruned decode. Returns (out [BH,G,dv], probs [BH,S]).

    S must divide into num_blocks equal selection blocks (callers pad a
    ragged tail — see ops.fused_decode). block_align=0 picks the backend
    default: no alignment in interpret mode, 128-lane alignment on TPU
    (applied per block, preserving the selection partition)."""
    bh, g, d = q.shape
    _, s, _ = mirror.shape
    dv = v.shape[-1]
    nb = num_blocks
    assert s % nb == 0, (s, nb)
    assert select_k % nb == 0, (select_k, nb)
    k_loc = select_k // nb
    bs0 = s // nb
    assert k_loc <= bs0, (k_loc, bs0)
    align = block_align or (1 if interpret else 128)
    bs = -(-bs0 // align) * align
    s_pad = bs * nb
    if bs != bs0:
        mirror, k, v, mscale, kscale, vscale, valid, prot = (
            _block_pad(x, nb, bs0, bs)
            for x in (mirror, k, v, mscale, kscale, vscale, valid, prot))
    meta = pack_meta(mscale, kscale, vscale, valid, prot, s_pad)
    kernel = functools.partial(_fused_decode_kernel, nb=nb, bs=bs,
                               k_loc=k_loc, scale=1.0 / (d ** 0.5))
    out, probs = pl.pallas_call(
        kernel,
        grid=(bh, nb),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda i, j: (i, 0, 0)),     # q
            pl.BlockSpec((1, g, d), lambda i, j: (i, 0, 0)),     # qq
            pl.BlockSpec((1, g, 1), lambda i, j: (i, 0, 0)),     # qscale
            pl.BlockSpec((1, bs, d), lambda i, j: (i, j, 0)),    # mirror
            pl.BlockSpec((1, META_ROWS, bs),
                         lambda i, j: (i, 0, j)),                # meta
            pl.BlockSpec((1, bs, d), lambda i, j: (i, j, 0)),    # k
            pl.BlockSpec((1, bs, dv), lambda i, j: (i, j, 0)),   # v
        ],
        out_specs=[
            pl.BlockSpec((1, g, dv), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, s_pad), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, g, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, s_pad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, s_pad), jnp.float32),     # score buffer
            pltpu.VMEM((g, 1), jnp.float32),         # running max
            pltpu.VMEM((g, 1), jnp.float32),         # running denom
            pltpu.VMEM((g, dv), jnp.float32),        # running output
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, qq, qscale.astype(jnp.float32)[..., None], mirror, meta, k, v)
    probs = probs[:, 0]
    if bs != bs0:   # drop the per-block alignment padding
        probs = probs.reshape(bh, nb, bs)[:, :, :bs0].reshape(bh, s)
    return out, probs
