"""Plain reference of the served computation, one request at a time.

What a served model computes under the configuration's prune settings,
written out in straightforward `jax.numpy` in float32 at matmul
precision HIGHEST, with no kernels, no batching across requests and
nothing imported from the program. It follows the published
configuration: the embedding, attention, residual and logit multipliers
where the configuration states them (Granite), else the plain
transformer's 1, 1/sqrt(head_dim), 1 and 1; the biases of each group the
file switches on, the file's norm epsilon and the MLP's activation by
its published name (bench/weights.py).

  prefill   causal attention over the prompt; each kv head's
            accumulated attention column sums (summed over its query
            group and the real prompt rows) rank the prompt tokens, and
            the `heavy` best are kept (the first `sink` and last
            `recent` tokens always);
  attend    one decode step of one layer, on a cache as it stands: the
            new token is written to the next free slot, or once the
            slots are full over the evictable slot (neither a sink nor
            among the last `recent`) with the least accumulated score;
            its score starts at the mean of the live slots' scores. The
            query, quantized to `query_bits`, scores every live slot on
            the mirror (the CAM pass); the protected slots and the best
            of the rest fill `select_k` winners per kv head; exact
            attention runs over the winners; the softmax of the CAM
            scores, summed over the query group, adds to the
            accumulated scores;
  logits    final norm, then the LM head.

A sliding window W (`sliding_window`) is served so, a query at position
t seeing the key at s iff t - W < s <= t:

  prefill   the query at t attends to the keys at t - W < s <= t, and
            the column sums are of that attention;
  keep      a position that no later query can see (s <= n - W, for a
            prompt of n) is never kept or protected, sinks included: it
            ranks below every live position and fills no slot while
            live positions remain;
  attend    at query position `step`, a slot holding pos <= step - W is
            dead: it is never protected, it is evicted before any live
            slot (the oldest dead slot first), and it is left out of the
            CAM scores, the winners (which are drawn from live slots
            only), exact attention and the accumulation (its score stays
            as it was). It still counts, as every occupied slot does, in
            the mean that starts the new token's score.

Without a window every path is the windowless one, bit for bit.

`precision="int8"` is the control: every matmul (the weights', the LM
head's and exact attention's) takes int8 operands, each scaled per row
along the contracted axis: the step below the served bfloat16 that would
tempt a change.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30


class Layer(NamedTuple):
    """One lane's cache of one layer, as it stands before a decode step."""
    k: jax.Array        # [Hk, S, dh]
    v: jax.Array        # [Hk, S, dh]
    kq: jax.Array       # [Hk, S, dh] integer-valued mirror
    ks: jax.Array       # [Hk, S] mirror scale
    acc: jax.Array      # [Hk, S] accumulated scores
    valid: jax.Array    # [Hk, S] bool
    pos: jax.Array      # [Hk, S] int32, -1 empty
    fill: jax.Array     # [] int32 live slots
    step: jax.Array     # [] int32 tokens seen


def quantize(x, bits):
    """Symmetric quantization along the last axis: (codes, scale)."""
    qm = 2 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(x), axis=-1) / qm
    safe = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / safe[..., None]), -qm, qm), scale


def _int8_rows(x, axis):
    """int8 codes of x scaled per slice along `axis`, and the scales."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    q = jnp.round(x / jnp.where(s > 0, s, 1.0)).astype(jnp.int8)
    return q, s


def einsum(spec, a, b, precision):
    """f32 einsum at HIGHEST, or (control) with int8 operands scaled per
    row along the contracted axes, accumulated in int32."""
    if precision == "f32":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    contract = [c for c in sa if c in sb and c not in out]
    qa, fa = _int8_rows(a, tuple(sa.index(c) for c in contract))
    qb, fb = _int8_rows(b, tuple(sb.index(c) for c in contract))
    acc = jnp.einsum(spec, qa, qb, preferred_element_type=jnp.int32)
    keep = ",".join([sa, sb]) + "->" + out
    scale = jnp.einsum(keep, fa, fb)      # contracted axes have size 1
    return acc.astype(jnp.float32) * scale


def matmul(x, w, precision):
    """x [..., din] f32 @ w [din, dout] (bf16 storage)."""
    wf = w.astype(jnp.float32)
    if precision == "f32":
        return jnp.dot(x, wf, precision=HIGHEST)
    xq, sx = _int8_rows(x, -1)
    wq, sw = _int8_rows(wf, 0)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def norm(x, w, b, kind, eps):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * w.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def rope(x, pos, theta):
    """Rotary embedding on halves of the last axis. x [..., H, dh],
    pos broadcastable to x.shape[:-2]."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.asarray(pos, jnp.float32)[..., None, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def multipliers(m: Dict) -> Dict[str, float]:
    """The published scalar multipliers, or the plain transformer's."""
    return {"embedding": float(m.get("embedding_multiplier", 1.0)),
            "attention": float(m.get("attention_multiplier",
                                     1.0 / math.sqrt(m["head_dim"]))),
            "residual": float(m.get("residual_multiplier", 1.0)),
            "logits": float(m.get("logits_scaling", 1.0))}


class Reference:
    """m: published sizes (HF names, plus "norm"); p: prune settings."""

    def __init__(self, m: Dict, p: Dict, precision: str = "f32",
                 prompt_pad: int = 4096, q_block: int = 512):
        self.m, self.p, self.precision = m, p, precision
        self.mult = multipliers(m)
        self.eps = weights.norm_eps(m)
        self.act = weights.activation(m)
        self.window = weights.sliding_window(m)
        self.hq, self.hk = m["num_attention_heads"], m["num_key_value_heads"]
        self.dh = m["head_dim"]
        self.g = self.hq // self.hk
        self.heavy = p["heavy_budget"]
        self.slots = p["heavy_budget"] + p["reserve"]
        self.P = max(prompt_pad, self.heavy)
        self.qb = q_block
        self.prefill = jax.jit(self._prefill)

    # -- layer pieces ---------------------------------------------------------

    def _mm(self, x, w):
        return matmul(x, w, self.precision)

    def _linear(self, x, lw, w, b):
        """x @ lw[w], plus lw[b] where the layer has that bias."""
        y = self._mm(x, lw[w])
        return y + lw[b].astype(jnp.float32) if b in lw else y

    def _norm(self, x, lw, which):
        return norm(x, lw[which + "_w"], lw.get(which + "_b"), self.m["norm"],
                    self.eps)

    def _qkv(self, h, lw, pos):
        q = self._linear(h, lw, "wq", "bq")
        k = self._linear(h, lw, "wk", "bk")
        v = self._linear(h, lw, "wv", "bv")
        lead = h.shape[:-1]
        q = q.reshape(lead + (self.hq, self.dh))
        k = k.reshape(lead + (self.hk, self.dh))
        v = v.reshape(lead + (self.hk, self.dh))
        theta = self.m["rope_theta"]
        return rope(q, pos, theta), rope(k, pos, theta), v

    def _mlp(self, h, lw):
        up = self._linear(h, lw, "w_up", "b_up")
        if self.act == "gated":
            a = jax.nn.silu(self._linear(h, lw, "w_gate", "b_gate")) * up
        else:
            a = jax.nn.gelu(up, approximate=self.act == "gelu_tanh")
        return self._linear(a, lw, "w_down", "b_down")

    def _logits(self, w, x):
        h = norm(x, w["final_norm_w"], w.get("final_norm_b"), self.m["norm"],
                 self.eps)
        head = w["lm_head"] if "lm_head" in w else w["embed"].T
        return self._mm(h, head) / self.mult["logits"]

    @staticmethod
    def _layers(w):
        return {k: v for k, v in w.items()
                if k not in ("embed", "lm_head", "final_norm_w",
                             "final_norm_b")}

    # -- prefill --------------------------------------------------------------

    def prefill_attend(self, q, k, v, n):
        """Causal attention of one prompt at one layer: q [P, Hq, dh],
        k / v [P, Hk, dh], n real rows -> (output [P, Hq, dh], each kv
        head's attention column sums over its query group and the real
        rows [Hk, P])."""
        P, hk, g, dh = q.shape[0], self.hk, self.g, self.dh
        qb = math.gcd(P, self.qb)
        pos = jnp.arange(P)
        qg = q.reshape(P // qb, qb, hk, g, dh)

        def block(acc, inp):
            bi, qbk = inp
            rows = bi * qb + jnp.arange(qb)
            s = einsum("tkgd,skd->kgts", qbk, k, self.precision) \
                * self.mult["attention"]
            see = rows[:, None] >= pos[None, :]
            if self.window is not None:
                see = see & (rows[:, None] - pos[None, :] < self.window)
            s = jnp.where(see, s, NEG)
            pr = jax.nn.softmax(s, axis=-1)
            o = einsum("kgts,skd->tkgd", pr, v, self.precision)
            live = (rows < n).astype(jnp.float32)
            acc = acc + jnp.einsum("kgts,t->ks", pr, live, precision=HIGHEST)
            return acc, o.reshape(qb, hk * g, dh)

        acc, o = jax.lax.scan(block, jnp.zeros((hk, P), jnp.float32),
                              (jnp.arange(P // qb), qg))
        return o.reshape(P, hk * g, dh), acc

    def rank(self, acc, n):
        """What static eviction ranks the prompt's positions by [Hk, P]:
        their column sums; +inf for the protected (the first `sink` and
        last `recent`); -inf past the prompt and, under a window, where
        no later query can see (s <= n - W), sinks included."""
        p = self.p
        pos = jnp.arange(acc.shape[-1])
        protect = (pos < p["sink_tokens"]) | (pos >= n - p["recent_window"])
        ranked = jnp.where(protect, jnp.inf, acc)
        seen = pos < n
        if self.window is not None:
            seen = seen & (pos > n - self.window)
        return jnp.where(seen, ranked, -jnp.inf)

    def keep(self, acc, n):
        """Static eviction at prefill: the positions [Hk, heavy] that
        rank highest, and the least rank a kept position needs [Hk]."""
        top, idx = jax.lax.top_k(self.rank(acc, n), self.heavy)
        return idx, top[:, -1]

    def _prefill(self, w, tokens, n):
        """tokens [P] (right-padded), n real length -> logits [V] after
        the prompt."""
        P = tokens.shape[0]
        pos = jnp.arange(P)
        res = self.mult["residual"]
        x = w["embed"][tokens].astype(jnp.float32) * self.mult["embedding"]

        def layer(x, lw):
            q, k, v = self._qkv(self._norm(x, lw, "ln1"), lw, pos)
            o, _ = self.prefill_attend(q, k, v, n)
            x = x + res * self._linear(o.reshape(P, -1), lw, "wo", "bo")
            x = x + res * self._mlp(self._norm(x, lw, "ln2"), lw)
            return x, None

        x, _ = jax.lax.scan(layer, x, self._layers(w))
        return self._logits(w, x[n - 1])

    # -- one decode step of one layer -------------------------------------------

    def write(self, c: Layer, k_new, v_new) -> Layer:
        """The new token's write, the first part of `attend`: into the
        next free slot, or once the slots are full over the slot evicted
        (a dead one, the oldest first; else the evictable one with the
        least accumulated score), its score starting at the mean of the
        occupied slots' (as they stand before the write, dead ones
        included). Returns the cache after it, `step` one on."""
        p = self.p
        S = c.acc.shape[-1]
        acc, valid, posn, step = c.acc, c.valid, c.pos, c.step
        prot = valid & (((posn >= 0) & (posn < p["sink_tokens"]))
                        | (posn >= step - p["recent_window"]))
        evict = jnp.argmin(jnp.where(valid & ~prot, acc, jnp.inf), -1)
        if self.window is not None:
            dead = valid & (posn <= step - self.window)
            oldest = jnp.argmin(jnp.where(dead, posn, step), -1)
            evict = jnp.where(dead.any(-1), oldest, evict)
        slot = jnp.where(c.fill >= S, evict, c.fill)      # [Hk]
        occupied = jnp.maximum(jnp.sum(valid, -1), 1)
        init = jnp.sum(jnp.where(valid, acc, 0.0), -1) / occupied
        kqn, ksn = quantize(k_new, p["score_bits"])
        at = jnp.arange(S)[None, :] == slot[:, None]      # [Hk, S]

        def put(new, old):
            return jnp.where(at[..., None], new[:, None],
                             old.astype(jnp.float32))

        return Layer(
            k=put(k_new, c.k), v=put(v_new, c.v), kq=put(kqn, c.kq),
            ks=jnp.where(at, ksn[:, None], c.ks),
            acc=jnp.where(at, init[:, None], acc),
            valid=valid | at, pos=jnp.where(at, step, posn),
            fill=jnp.minimum(c.fill + 1, S), step=step + 1)

    def select(self, c: Layer, q):
        """The CAM pass of `attend` on the cache after the write:
        (approximate scores [Hk, g, S], the live slots [Hk, S], the
        `select_k` winners [Hk, k])."""
        p, hk, g, dh = self.p, self.hk, self.g, self.dh
        valid, posn = c.valid, c.pos
        qq, qs = quantize(q, p["query_bits"])
        raw = jnp.einsum("kgd,ksd->kgs", qq.reshape(hk, g, dh), c.kq,
                         precision=HIGHEST)
        prot = valid & (((posn >= 0) & (posn < p["sink_tokens"]))
                        | (posn >= c.step - p["recent_window"]))
        if self.window is not None:         # the query is at c.step - 1
            valid = valid & (posn >= c.step - self.window)
        approx = raw * qs.reshape(hk, g)[..., None] * c.ks[:, None, :]
        approx = jnp.where(valid[:, None, :], approx, NEG)
        sel = jnp.where(prot, 1e30, jnp.sum(approx, 1))
        sel = jnp.where(valid, sel, NEG)
        _, idx = jax.lax.top_k(sel, p["select_k"])       # [Hk, k]
        return approx, valid, idx

    def attend(self, c: Layer, q, k_new, v_new):
        """One lane's decode step at one layer, on the cache `c` as it
        stands before the step: q [Hq, dh], k_new / v_new [Hk, dh].
        Returns (attention output [Hq, dh], accumulated scores after the
        step [Hk, S])."""
        hk, g, dh = self.hk, self.g, self.dh
        scale = self.mult["attention"]
        c = self.write(c, k_new, v_new)
        approx, valid, idx = self.select(c, q)
        kw = jnp.take_along_axis(c.k, idx[..., None], 1)
        vw = jnp.take_along_axis(c.v, idx[..., None], 1)
        vok = jnp.take_along_axis(valid, idx, 1)
        lg = einsum("kgd,kjd->kgj", q.reshape(hk, g, dh), kw,
                    self.precision) * scale
        lg = jnp.where(vok[:, None, :], lg, NEG)
        pw = jax.nn.softmax(lg, -1) * vok[:, None, :]
        o = einsum("kgj,kjd->kgd", pw, vw, self.precision)
        # charge-domain accumulation of the CAM softmax
        z = approx * scale
        e = jnp.exp(z - jnp.max(z, -1, keepdims=True)) * valid[:, None, :]
        acc = c.acc + jnp.sum(e / jnp.maximum(jnp.sum(e, -1, keepdims=True),
                                              1e-30), 1)
        return o.reshape(hk * g, dh), acc

    # -- one request ------------------------------------------------------------

    def _padded(self, prompt: np.ndarray) -> np.ndarray:
        """The prompt right-padded to a power of two (at least `heavy` and
        a whole number of query blocks), so that a few programs serve
        every length up to the pad limit."""
        n = len(prompt)
        if n > self.P:
            raise ValueError(f"prompt of {n} exceeds the reference pad {self.P}")
        width = max(self.heavy, 1 << max(n - 1, 0).bit_length())
        toks = np.zeros(min(width, self.P), np.int32)
        toks[:n] = prompt
        return toks

    def first_logits(self, w, prompt: np.ndarray) -> np.ndarray:
        """Logits [V] after the prompt (what the first served token comes
        from)."""
        return np.asarray(self.prefill(w, jnp.asarray(self._padded(prompt)),
                                       jnp.int32(len(prompt))))
