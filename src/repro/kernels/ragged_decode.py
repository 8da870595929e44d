"""Pallas TPU kernel — ragged fused decode: per-lane, not max-lane, cost.

The fused single-pass engine (kernels/fused_decode.py) prices every lane at
the allocated slot count: its grid walks all S/bs mirror blocks per
(batch·kv-head) row even when a lane has filled 128 of 4096 slots. This
kernel is the fill-aware variant — the software analogue of the paper's
O(1) per-array CAM race for a *mixed* batch:

  * the per-group live-block count (below) is SCALAR-PREFETCHED,
    so it is available to the index maps before the kernel body runs;
  * dead blocks (block index >= live count) remap their DMAs to a block
    the pipeline already holds — Pallas elides the copy when consecutive
    grid steps fetch the same block, so a dead block moves no bytes;
  * ``pl.when`` skips the dead block's work entirely — a short lane pays
    O(fill) compute + bandwidth while a long lane in the same batch pays
    its own O(fill), instead of everyone paying O(max over lanes).

Selection is GLOBAL top-k (the ``num_blocks == 1`` semantics of the fused
kernel / ``ref.fused_decode_ref``). A grid step takes R rows, R the
largest divisor of BH that is <= 8 (`group_rows`): at granite-3-2b's
BH = lanes x 8 kv heads, a group is one lane's kv heads. Each group walks
its blocks twice — grid (BH/R, 2·nb):

  steps [0, nb)    CAM pass: score the live mirror blocks of the group's R
                   rows into a VMEM buffer initialised to NEG_INF (dead
                   regions therefore rank exactly like invalid slots);
                   the last step runs ONE race over the [R, s_pad]
                   selection buffer — row-wise, so R rows fill the vreg's
                   sublanes in the rounds one row would take — and keeps
                   the winner mask;
  steps [nb, 2nb)  current pass: stream the live K/V blocks and attend
                   each row over its winners (online softmax); the last
                   step emits the output and the per-slot charge-domain
                   probabilities.

A group's live-block count is the max over its rows' ``ceil(fill / bs)``.
That is exact for any fills: slots at or past a row's fill are invalid,
so a block scored past it gives NEG_INF and races like a dead region.
Where a group's rows share one fill (one lane's kv heads) nothing extra
is scored.

  fills  [BH]        int32           live slot count per row (lane fill)
  q      [BH, G, d]  storage dtype   exact queries
  qq     [BH, G, d]  int8            quantized queries (CAM drive lines)
  qscale [BH, G]     f32
  mirror [BH, S, d]  int8            key mirror (int8-KV mode: K itself)
  mscale [BH, S]     f32
  kscale [BH, S]     f32             K-row dequant scale (ones for bf16)
  vscale [BH, S]     f32
  valid  [BH, S]     int8
  prot   [BH, S]     int8            protected slots always win the race
  k      [BH, S, d]  storage dtype   exact keys
  v      [BH, S, dv] storage dtype   exact values
  out    [BH, G, dv] f32
  probs  [BH, S]     f32             Σ_g softmax_g(scores/√d)

S need not be a multiple of the block: the last block is partial, its
out-of-range slots are invalid in the packed metadata and its V rows are
zeroed in-kernel, so mirror/K/V are read in place with no padded copy.

Composition with the in-place decode path: this kernel is a pure READ of
the cache arrays (its fill-aware block skipping is the kernel-level
analogue of `core/cache.layer_window`'s read window), so it slots into
`decode_attention_stacked`'s read-window/storage-write split without
breaking buffer donation — the token's scatter writes
(`write_token_stacked`) land in the full-width stacked buffers after the
kernel's reads, and the cache pytree stays input-output aliased through
the decode block.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_decode import (META_ROWS, NEG_INF, PROT, PROT_WIN,
                                        attend_block, cam_scores,
                                        charge_probs, init_softmax,
                                        lane_offset, pack_meta, race)

log = logging.getLogger("repro.kernels")


def _ragged_decode_kernel(nblk_ref, q_ref, qq_ref, qs_ref, mir_ref,
                          meta_ref, k_ref, v_ref, out_ref, probs_ref,
                          score_buf, sel_buf, m_sc, l_sc, o_sc,
                          *, nb, bs, s, k_sel_n, scale):
    i = pl.program_id(0)
    j = pl.program_id(1)
    live_blocks = nblk_ref[i]
    rows = sel_buf.shape[0]
    g = score_buf.shape[0] // rows

    @pl.when(j == 0)
    def _init():
        # dead regions keep NEG_INF: they race exactly like invalid slots
        score_buf[...] = jnp.full_like(score_buf, NEG_INF)
        sel_buf[...] = jnp.full_like(sel_buf, g * NEG_INF)
        init_softmax(m_sc, l_sc, o_sc)

    # -- CAM pass: score this block iff it holds any row's live slot --
    @pl.when(j < live_blocks)
    def _score():
        off = lane_offset(j, bs)
        for r in range(rows):
            meta = meta_ref[r]                             # [8, bs]
            raw = cam_scores(qq_ref[r], qs_ref[r], mir_ref[r], meta)
            score_buf[r * g:(r + 1) * g, pl.ds(off, bs)] = raw
            ssel = jnp.sum(raw, axis=0, keepdims=True)     # [1, bs]
            sel_buf[r:r + 1, pl.ds(off, bs)] = jnp.where(
                meta[PROT:PROT + 1] != 0, PROT_WIN, ssel)

    # -- one global CAM race over the group's rows; keep the winner mask --
    @pl.when(j == nb - 1)
    def _race():
        sel_buf[...] = jnp.where(race(sel_buf[...], k_sel_n), 1.0, 0.0)

    # -- current pass: exact attention over each live block's winners --
    jj = j - nb

    @pl.when((jj >= 0) & (jj < live_blocks))
    def _attend():
        off = lane_offset(jj, bs)
        for r in range(rows):
            sel = sel_buf[r:r + 1, pl.ds(off, bs)] > 0.5
            attend_block(q_ref[r], k_ref[r], v_ref[r], meta_ref[r], sel,
                         scale, m_sc.at[r], l_sc.at[r], o_sc.at[r],
                         n_valid=None if s % bs == 0 else s - jj * bs)

    @pl.when(j == 2 * nb - 1)
    def _flush():
        out_ref[...] = o_sc[...] / jnp.maximum(l_sc[...], 1e-30)
        # -- charge-domain mode: per-slot approximate probabilities --
        for r in range(rows):
            probs_ref[0, r:r + 1, :] = charge_probs(
                score_buf[r * g:(r + 1) * g], scale)


def group_rows(bh: int) -> int:
    """Rows a grid step takes: the largest divisor of BH that is <= 8, so
    the race fills the vreg's 8 sublanes (a lane's 8 kv heads for
    granite-3-2b)."""
    return max(r for r in range(1, min(bh, 8) + 1) if bh % r == 0)


@functools.partial(jax.jit,
                   static_argnames=("select_k", "block_s", "interpret",
                                    "block_align"))
def ragged_decode(fills: jax.Array, q: jax.Array, qq: jax.Array,
                  qscale: jax.Array, mirror: jax.Array, mscale: jax.Array,
                  kscale: jax.Array, vscale: jax.Array, valid: jax.Array,
                  prot: jax.Array, k: jax.Array, v: jax.Array, *,
                  select_k: int, block_s: int = 512,
                  interpret: bool = False, block_align: int = 0):
    """Fill-aware fused decode. Returns (out [BH,G,dv], probs [BH,S]).

    Global (num_blocks == 1) selection semantics — matches
    ``ref.fused_decode_ref(..., num_blocks=1)`` whenever slots at and
    beyond ``fills[i]`` are invalid (the cache write discipline).
    block_align=0 picks the backend default (none in interpret mode,
    128 lanes on TPU)."""
    bh, g, d = q.shape
    s = mirror.shape[1]
    dv = v.shape[-1]
    assert select_k <= s, (select_k, s)
    align = block_align or (1 if interpret else 128)
    bs = -(-min(block_s, s) // align) * align
    s_pad = -(-s // bs) * bs
    nb = s_pad // bs
    rows = group_rows(bh)
    log.info("ragged_decode: %d rows a grid step, grid (%d, %d) for BH %d, "
             "G %d, d %d, S %d", rows, bh // rows, 2 * nb, bh, g, d, s)
    meta = pack_meta(mscale, kscale, vscale, valid, prot, s_pad)
    nblk = jnp.clip(-(-jnp.minimum(fills.astype(jnp.int32), s) // bs),
                    0, nb)
    # a group walks the blocks of its longest row: slots past a row's fill
    # are invalid, so its extra blocks score NEG_INF and race as dead
    nblk = jnp.max(nblk.reshape(bh // rows, rows), axis=1)

    def blk(j, cnt):
        # dead blocks re-fetch the last live block: the pipeline sees an
        # unchanged block index and elides the copy entirely
        return jnp.maximum(jnp.minimum(j, cnt - 1), 0)

    def score_blk(i, j, c):        # CAM pass walks 0..live-1, then holds
        return blk(jnp.minimum(j, nb - 1), c[i])

    def attend_blk(i, j, c):       # held at block 0 until the current pass
        return blk(jnp.maximum(j - nb, 0), c[i])

    def meta_blk(i, j, c):
        return blk(jnp.where(j < nb, j, j - nb), c[i])

    kernel = functools.partial(_ragged_decode_kernel, nb=nb, bs=bs, s=s,
                               k_sel_n=select_k, scale=1.0 / (d ** 0.5))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh // rows, 2 * nb),
        in_specs=[
            pl.BlockSpec((rows, g, d), lambda i, j, c: (i, 0, 0)),  # q
            pl.BlockSpec((rows, g, d), lambda i, j, c: (i, 0, 0)),  # qq
            pl.BlockSpec((rows, g, 1), lambda i, j, c: (i, 0, 0)),  # qscale
            pl.BlockSpec((rows, bs, d),
                         lambda i, j, c: (i, score_blk(i, j, c), 0)),
            pl.BlockSpec((rows, META_ROWS, bs),
                         lambda i, j, c: (i, 0, meta_blk(i, j, c))),
            pl.BlockSpec((rows, bs, d),
                         lambda i, j, c: (i, attend_blk(i, j, c), 0)),
            pl.BlockSpec((rows, bs, dv),
                         lambda i, j, c: (i, attend_blk(i, j, c), 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, g, dv), lambda i, j, c: (i, 0, 0)),
            pl.BlockSpec((1, rows, s_pad), lambda i, j, c: (i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows * g, s_pad), jnp.float32),  # score buffer
            pltpu.VMEM((rows, s_pad), jnp.float32),     # race input → winners
            pltpu.VMEM((rows, g, 1), jnp.float32),      # running max
            pltpu.VMEM((rows, g, 1), jnp.float32),      # running denom
            pltpu.VMEM((rows, g, dv), jnp.float32),     # running output
        ],
    )
    out, probs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, g, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh // rows, rows, s_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(nblk, q, qq, qscale.astype(jnp.float32)[..., None], mirror, meta,
      k, v)
    return out, probs.reshape(bh, s_pad)[:, :s]
