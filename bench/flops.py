"""Operations and bytes the algorithm needs, from published shapes.

Counted from what pruned decode must do, not from what a kernel
happens to stream: a kernel that reads every live K/V block reads more
than `decode_attention_work` counts, and one that gathers only the
winners reads what it counts.

Per decode step of one lane at cache fill `fill` (live slots), for each
layer:
  * weight matmuls: 2 * (d*q + 2*d*kv + q*d + n_mlp*d*ff)
  * CAM score pass: 2 * Hq * fill * dh   (low-bit dot over the live window)
  * exact attention over the select_k winners: 4 * Hq * k * dh
and once per step the LM head, 2 * d * vocab.
Bytes the attention needs per layer: the int8 K mirror over the live
window (Hk * fill * dh), the winners' K and V rows in the cache dtype
(2 * Hk * k * dh * 2), and the mirror scales (Hk * fill * 4).
"""
from __future__ import annotations

from typing import Dict

KV_BYTES = 2          # bf16 cache rows
MIRROR_BYTES = 1      # int8 container per mirror element
SCALE_BYTES = 4       # f32 scale per (slot, kv head)


def mlp_matrices(act: str) -> int:
    return 3 if act in ("silu", "swiglu") else 2


def decode_step_flops(m: Dict, select_k: int, fill: int) -> Dict[str, int]:
    """FLOPs of one lane's decode step. `m` holds the published sizes
    (HF names: hidden_size, num_hidden_layers, num_attention_heads,
    num_key_value_heads, head_dim, intermediate_size, vocab_size,
    hidden_act)."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    hq, hk, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    ff, v = m["intermediate_size"], m["vocab_size"]
    q, kv = hq * dh, hk * dh
    k = min(select_k, fill)
    return {
        "matmul": L * 2 * (d * q + 2 * d * kv + q * d
                           + mlp_matrices(m["hidden_act"]) * d * ff),
        "lm_head": 2 * d * v,
        "cam": L * 2 * hq * fill * dh,
        "exact": L * 4 * hq * k * dh,
    }


def decode_attention_work(m: Dict, select_k: int, fill: int):
    """(flops, bytes) the pruned attention needs in one lane's decode
    step, summed over layers."""
    L = m["num_hidden_layers"]
    hk, dh = m["num_key_value_heads"], m["head_dim"]
    f = decode_step_flops(m, select_k, fill)
    k = min(select_k, fill)
    nbytes = L * (hk * fill * dh * MIRROR_BYTES
                  + 2 * hk * k * dh * KV_BYTES
                  + hk * fill * SCALE_BYTES)
    return f["cam"] + f["exact"], nbytes


def decode_fills(prompt_len: int, tokens: int, heavy: int, slots: int):
    """Live-slot count at each decode step of a request that emitted
    `tokens` tokens: the first comes from prefill, which keeps
    min(prompt, heavy) slots; step i then attends over i more, up to
    the slot count."""
    kept = min(prompt_len, heavy)
    return [min(kept + i, slots) for i in range(1, tokens)]


def block_fills(blocks, heavy: int, slots: int):
    """Live-slot counts of the decode steps that the recorded blocks ran
    for their lanes: per lane, the steps that emitted tokens
    [before, before + emitted), where token i >= 1 takes one step at
    `decode_fills`'s fill."""
    for b in blocks:
        for prompt_len, before, emitted in b["lanes"]:
            kept = min(prompt_len, heavy)
            for i in range(max(before, 1), before + emitted):
                yield min(kept + i, slots)


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """Least time for the work on the chip: the larger of compute and
    memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
