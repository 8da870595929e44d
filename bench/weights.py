"""Seeded random weights, made on the device in one jitted call.

The benchmark owns the weights: it makes them here from `--seed`, in
its own naming, in the dtype they are served in. The plain reference
reads them as made, with the published model's multipliers applied as
the configuration states them. The program's dense `Model` has no
multipliers, so `program_tree` folds them into the arrays it hands the
program (re-nested into the program's parameter tree):

  embedding_multiplier e   the embedding table times e;
  attention_multiplier a   W_q (and its bias) times a * sqrt(head_dim),
                           since the program scales scores by
                           1/sqrt(head_dim) (exact where that is a power
                           of two);
  residual_multiplier r    W_o and W_down (and their biases) times r;
  logits_scaling s         the LM head divided by s; a tied head is the
                           embedding table, already times e, so the
                           final norm's gain is divided by e * s.

Each fold is exact in real arithmetic; the folded bf16 arrays round
once more. The program then computes the published model's function.

Scales: embeddings 0.02, matrices 1/sqrt(fan_in), norm gains 1 + 0.05 N,
biases 0.02 N (so every published path, biases and gains included, does
work on the numbers).

The block the configuration's `model` section describes (HF names):

  hidden_act               silu / swiglu: gated MLP (W_gate); gelu_pytorch_tanh
                           / gelu_new: tanh-GELU; gelu: exact erf-GELU;
                           any other name is refused
  norm                     rmsnorm, or layernorm (gains and biases)
  rms_norm_eps /           the norms' epsilon (one of the two, default
  norm_epsilon             1e-6)
  attention_bias           biases on q, k and v (leaves bq, bk, bv)
  attention_out_bias       a bias on the o-projection (leaf bo)
  mlp_bias                 biases on the MLP's up and down projections, and
                           the gate's when gated (b_up, b_down, b_gate)
  use_bias                 the default of the three bias groups above
                           (StarCoder2's published key)
  sliding_window           each query sees the last W positions, itself
                           included (absent or null: no window); see
                           bench/reference.py

A bias leaf exists only where the file asks for its group, so a file
that asks for none gets the same leaves, in the same order, from the
same seed as before the groups existed. In the program's tree a `w*`
weight's bias is the matching `b*`: attn.bo, mlp.bi (up), mlp.bo
(down), mlp.bg (gate).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import math

import jax
import jax.numpy as jnp


ACTIVATIONS = {"silu": "gated", "swiglu": "gated",
               "gelu_pytorch_tanh": "gelu_tanh", "gelu_new": "gelu_tanh",
               "gelu": "gelu_erf"}


def activation(m: Dict) -> str:
    """The MLP's form by the published activation name: "gated",
    "gelu_tanh" or "gelu_erf"."""
    act = m["hidden_act"]
    if act not in ACTIVATIONS:
        raise ValueError(f"hidden_act {act!r} is not one of "
                         f"{sorted(ACTIVATIONS)}")
    return ACTIVATIONS[act]


def gated(m: Dict) -> bool:
    return activation(m) == "gated"


def layernorm(m: Dict) -> bool:
    if m["norm"] not in ("rmsnorm", "layernorm"):
        raise ValueError(f"norm {m['norm']!r} is neither rmsnorm nor "
                         f"layernorm")
    return m["norm"] == "layernorm"


def norm_eps(m: Dict) -> float:
    """The norms' epsilon, under either published key."""
    if "rms_norm_eps" in m and "norm_epsilon" in m:
        raise ValueError("the configuration states both rms_norm_eps and "
                         "norm_epsilon")
    return float(m.get("rms_norm_eps", m.get("norm_epsilon", 1e-6)))


def sliding_window(m: Dict) -> Optional[int]:
    w = m.get("sliding_window")
    if w is not None and int(w) < 1:
        raise ValueError(f"sliding_window {w!r} sees no position")
    return None if w is None else int(w)


def _bias(m: Dict, key: str) -> bool:
    return bool(m.get(key, m.get("use_bias", False)))


def qkv_bias(m: Dict) -> bool:
    return _bias(m, "attention_bias")


def out_bias(m: Dict) -> bool:
    return _bias(m, "attention_out_bias")


def mlp_bias(m: Dict) -> bool:
    return _bias(m, "mlp_bias")


def shapes(m: Dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind). Per-layer leaves carry a leading layer axis."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    hq, hk, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    ff, v = m["intermediate_size"], m["vocab_size"]
    s = {"embed": ((v, d), "embed"), "final_norm_w": ((d,), "gain")}
    if layernorm(m):
        s["final_norm_b"] = ((d,), "bias")
    if not m["tie_word_embeddings"]:
        s["lm_head"] = ((d, v), "matrix")
    per = {"ln1_w": ((d,), "gain"), "ln2_w": ((d,), "gain"),
           "wq": ((d, hq * dh), "matrix"), "wk": ((d, hk * dh), "matrix"),
           "wv": ((d, hk * dh), "matrix"), "wo": ((hq * dh, d), "matrix"),
           "w_up": ((d, ff), "matrix"), "w_down": ((ff, d), "matrix")}
    if layernorm(m):
        per.update(ln1_b=((d,), "bias"), ln2_b=((d,), "bias"))
    if qkv_bias(m):
        per.update(bq=((hq * dh,), "bias"), bk=((hk * dh,), "bias"),
                   bv=((hk * dh,), "bias"))
    if out_bias(m):
        per["bo"] = ((d,), "bias")
    if mlp_bias(m):
        per.update(b_up=((ff,), "bias"), b_down=((d,), "bias"))
    if gated(m):
        per["w_gate"] = ((d, ff), "matrix")
        if mlp_bias(m):
            per["b_gate"] = ((ff,), "bias")
    for k, (shape, kind) in per.items():
        s[k] = ((L,) + shape, kind)
    return s


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any seed up to 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _build(m: Dict, key, dtype) -> Dict[str, jax.Array]:
    """The published arrays from a key (see `shapes` and the scales
    above)."""
    spec = shapes(m)
    names = sorted(spec)
    out = {}
    for i, name in enumerate(names):
        shape, kind = spec[name]
        z = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32)
        if kind == "embed":
            x = 0.02 * z
        elif kind == "matrix":
            x = z / jnp.sqrt(jnp.float32(shape[-2]))
        elif kind == "gain":
            x = 1.0 + 0.05 * z
        else:
            x = 0.02 * z
        out[name] = x.astype(dtype)
    return out


def make(m: Dict, seed: int, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """The weights as published (the reference's), from the seed."""
    return jax.jit(lambda key: _build(m, key, dtype))(seed_key(seed))


def program_params(m: Dict, seed: int, dtype=jnp.bfloat16) -> Dict:
    """The program's parameter tree for the same seed, made and folded in
    one jitted call (the published arrays never sit beside it)."""
    return jax.jit(lambda key: program_tree(_build(m, key, dtype), m))(
        seed_key(seed))


def folds(m: Dict) -> Dict[str, float]:
    """Factor each array is multiplied by for the program (see above)."""
    e = float(m.get("embedding_multiplier", 1.0))
    a = float(m.get("attention_multiplier", 0.0)) * math.sqrt(m["head_dim"])
    r = float(m.get("residual_multiplier", 1.0))
    s = float(m.get("logits_scaling", 1.0))
    out = {"embed": e, "wo": r, "bo": r, "w_down": r, "b_down": r}
    if a:
        out.update(wq=a, bq=a)
    if m["tie_word_embeddings"]:
        out["final_norm_w"] = 1.0 / (e * s)
    else:
        out["lm_head"] = 1.0 / s
    return {k: f for k, f in out.items() if abs(f - 1.0) > 1e-12}


def program_tree(w: Dict[str, jax.Array], m: Dict) -> Dict:
    """The arrays, folded, in the program's parameter layout (a dense
    attention stack scanned as one segment)."""
    w = dict(w)
    for k, f in folds(m).items():
        if k in w:
            w[k] = (w[k].astype(jnp.float32) * f).astype(w[k].dtype)

    def norm(prefix):
        p = {"w": w[prefix + "_w"]}
        if layernorm(m):
            p["b"] = w[prefix + "_b"]
        return p

    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    if qkv_bias(m):
        attn.update(bq=w["bq"], bk=w["bk"], bv=w["bv"])
    if out_bias(m):
        attn["bo"] = w["bo"]
    mlp = {"wi": w["w_up"], "wo": w["w_down"]}
    if mlp_bias(m):
        mlp.update(bi=w["b_up"], bo=w["b_down"])
    if gated(m):
        mlp["wg"] = w["w_gate"]
        if mlp_bias(m):
            mlp["bg"] = w["b_gate"]
    tree = {"embed": w["embed"], "final_norm": norm("final_norm"),
            "seg0_dense": {"ln1": norm("ln1"), "ln2": norm("ln2"),
                           "attn": attn, "mlp": mlp}}
    if not m["tie_word_embeddings"]:
        tree["lm_head"] = w["lm_head"]
    return tree
