"""Public jit'd wrappers for the Pallas kernels.

Backend selection: Pallas-TPU when running on TPU, interpret mode (Python
execution of the kernel body) for CPU validation, and the pure-XLA reference
path for the multi-pod dry-run (the dry-run lowers SPMD HLO that the
roofline parser consumes — see launch/dryrun.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.approx_score import approx_score as _approx_pallas
from repro.kernels.flash_prefill import flash_prefill as _flash_pallas
from repro.kernels.fused_decode import fused_decode as _fused_pallas
from repro.kernels.gather_attention import gather_attention as _gather_pallas
from repro.kernels.ragged_decode import ragged_decode as _ragged_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_slots(x, mult, axis, value=0):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x, s
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), s


def approx_score(qq, qscale, kq, kscale, valid, block_s: int = 512,
                 backend: str = "auto"):
    """CAM-mode scoring. Shapes as in kernels/approx_score.py."""
    if backend == "xla" or (backend == "auto" and not _on_tpu()
                            and kq.shape[1] > 4096):
        # interpret mode is slow for very long S on CPU; use the oracle
        return ref.approx_score_ref(qq, qscale, kq, kscale, valid)
    kq_p, s = _pad_slots(kq, block_s, 1)
    ks_p, _ = _pad_slots(kscale, block_s, 1)
    va_p, _ = _pad_slots(valid.astype(jnp.int8), block_s, 1)
    out = _approx_pallas(qq, qscale, kq_p, ks_p, va_p, block_s=block_s,
                         interpret=not _on_tpu())
    return out[:, :, :s]


def gather_attention(q, k, v, valid, block_k: int = 512,
                     backend: str = "auto"):
    """Current-domain exact attention over gathered slots."""
    if backend == "xla":
        return ref.gather_attention_ref(q, k, v, valid)
    k_p, kk = _pad_slots(k, block_k, 1)
    v_p, _ = _pad_slots(v, block_k, 1)
    va_p, _ = _pad_slots(valid.astype(jnp.int8), block_k, 1)
    return _gather_pallas(q, k_p, v_p, va_p, block_k=block_k,
                          interpret=not _on_tpu())


def fused_decode(q, qq, qscale, mirror, mscale, kscale, vscale, valid,
                 prot, k, v, select_k: int, num_blocks: int = 1,
                 backend: str = "auto", fills=None):
    """Fused single-pass pruned decode (score → select → gather → attend).

    Shapes as in kernels/fused_decode.py. The XLA fallback is one fused
    region whose gather touches only the selected rows; the Pallas kernel
    keeps scores and the winner mask in VMEM and attends over the K/V
    blocks it streams. Returns (out [BH, G, dv], probs [BH, S]).

    `fills` ([BH] int32, optional): per-row live slot counts. With global
    selection (num_blocks == 1) on the Pallas backend this dispatches the
    RAGGED kernel (kernels/ragged_decode.py), which scalar-prefetches the
    live-block counts and early-exits dead k-blocks — each lane pays its
    own O(fill) instead of O(S). The XLA fallback needs no fills: slots
    beyond fill are invalid and already masked, so its result is
    identical either way.
    """
    s = mirror.shape[1]
    if backend == "xla" or (backend == "auto" and not _on_tpu()):
        pass                       # the reference path masks, not skips
    elif fills is not None and num_blocks == 1:
        return _ragged_pallas(
            fills, q, qq, qscale, mirror, mscale, kscale, vscale, valid,
            prot, k, v, select_k=select_k, interpret=not _on_tpu())
    if s % num_blocks:
        # ragged tail: pad to equal selection blocks (both backends see the
        # same partition; pad slots are invalid so they never win the race)
        mirror, k, v = (_pad_slots(x, num_blocks, 1)[0]
                        for x in (mirror, k, v))
        mscale, kscale, vscale, valid, prot = (
            _pad_slots(x, num_blocks, 1)[0]
            for x in (mscale, kscale, vscale, valid, prot))
    if backend == "xla" or (backend == "auto" and not _on_tpu()):
        out, probs = ref.fused_decode_ref(
            q, qq, qscale, mirror, mscale, kscale, vscale, valid, prot,
            k, v, select_k=select_k, num_blocks=num_blocks)
    else:
        out, probs = _fused_pallas(
            q, qq, qscale, mirror, mscale, kscale, vscale, valid, prot,
            k, v, select_k=select_k, num_blocks=num_blocks,
            interpret=not _on_tpu())
    return out, probs[:, :s]


def flash_prefill(q, k, v, group: int = 1, block_q: int = 256,
                  block_k: int = 256, backend: str = "auto", lengths=None):
    """Prefill flash attention + accumulated column scores.

    `lengths` ([BH] int32, optional): true row counts when N is a shape
    bucket and the tail is right-padding."""
    if backend == "xla":
        return ref.flash_prefill_ref(q, k, v, group, lengths=lengths)
    return _flash_pallas(q, k, v, group=group, block_q=block_q,
                         block_k=block_k, interpret=not _on_tpu(),
                         lengths=lengths)
