"""The configuration's block as the benchmark reads it: bias groups, the
norm epsilon, the activation by its published name and a sliding window,
in the weights, the plain reference and `harness.program_model`.

Granite's weights are pinned by digest; a tiny StarCoder2-shaped block
(every bias, LayerNorm at 1e-5, tanh-GELU, a window) is held to a
float64 numpy forward written here; a window that never binds changes no
bit; a hand-built cache with dead slots is served as the window's rules
say; and `program_model` refuses what the program cannot serve, by
name."""
import dataclasses
import hashlib
import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, weights  # noqa: E402
from bench.reference import Layer, Reference, quantize  # noqa: E402

MODEL = {"num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
         "vocab_size": 257, "hidden_act": "silu", "norm": "rmsnorm",
         "tie_word_embeddings": True, "rope_theta": 10000.0,
         "attention_bias": False}
GRANITE = {"embedding_multiplier": 12.0, "attention_multiplier": 0.125,
           "residual_multiplier": 0.22, "logits_scaling": 8.0}
# StarCoder2-3B's block at a tiny width: a bias on every linear layer
# (its published `use_bias`), LayerNorm at 1e-5, tanh-GELU, a window
STARCODER2 = {"num_hidden_layers": 3, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "intermediate_size": 128, "vocab_size": 257,
              "hidden_act": "gelu_pytorch_tanh", "norm": "layernorm",
              "norm_epsilon": 1e-5, "use_bias": True,
              "tie_word_embeddings": True, "rope_theta": 999999.4420358813,
              "sliding_window": 32}
PRUNE = {"policy": "unicaim", "heavy_budget": 128, "reserve": 16,
         "select_k": 16, "score_bits": 3, "query_bits": 4,
         "sink_tokens": 4, "recent_window": 8, "fused": "auto"}

# sha256 of the arrays (name, dtype, shape, bytes, in name order) as the
# harness made them before the bias groups, the epsilon keys, the
# activation names and the window existed
PINNED = {
    ("make", 5): "6bf4c49c720a0c433fe76ae73ab7224d"
                 "dc990c2b4be0ba9abcb26193921b98b1",
    ("make", 2**33 + 11): "ea3f457121b41553c7092380721bff3a"
                          "6b1b9a60ceef6c5fc9ba4f9611634f96",
    ("plain", 5): "3463f8ceb00ddb7f3a910f1a6d615efc"
                  "69a9efede12e370928e1b14a6fc1d9a7",
    ("plain", 2**33 + 11): "5bda1b901f3f2d6dcdc58ac7d8a17006"
                           "25b285a78b97926e28d5cc35f7b9251f",
    ("granite", 5): "5f37db64c3e15583878618b45434fed0"
                    "69524cde7555db229ca713608144f445",
    ("granite", 2**33 + 11): "015b1b9f83d35bc9f099e9aa925c9c4b"
                             "47fcf185d69f671fc5497cf2ff562292",
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, a in sorted(jax.tree_util.tree_flatten_with_path(tree)[0],
                          key=lambda x: jax.tree_util.keystr(x[0])):
        a = np.asarray(a)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", [5, 2**33 + 11])
def test_granite_weights_unchanged(seed):
    """The published arrays (the same for both files: the multipliers
    are folded only into the program's tree) and the program's folded
    tree, bit for bit, from each seed."""
    for name, m in (("plain", MODEL), ("granite", dict(MODEL, **GRANITE))):
        assert digest(weights.make(m, seed)) == PINNED[("make", seed)]
        assert digest(weights.program_params(m, seed)) == PINNED[(name, seed)]


@pytest.mark.parametrize("gate", [False, True])
def test_bias_groups(gate):
    """Each group switches on by its own key (`use_bias` is their
    default), lands in the program's tree under its `b*` name, and the
    residual multiplier folds into the o and down biases with W_o and
    W_down."""
    base = {k: v for k, v in MODEL.items() if k != "attention_bias"}
    base["hidden_act"] = "silu" if gate else "gelu_pytorch_tanh"
    per = {k for k in weights.shapes(base) if k.startswith("b")}
    assert per == set()
    groups = {"attention_bias": {"bq", "bk", "bv"},
              "attention_out_bias": {"bo"},
              "mlp_bias": {"b_up", "b_down"} | ({"b_gate"} if gate else set())}
    for key, leaves in groups.items():
        got = {k for k in weights.shapes(dict(base, **{key: True}))
               if k.startswith("b")}
        assert got == leaves, key
    every = {k for k in weights.shapes(dict(base, use_bias=True))
             if k.startswith("b")}
    assert every == set().union(*groups.values())
    assert {k for k in weights.shapes(dict(base, use_bias=True,
                                           mlp_bias=False))
            if k.startswith("b")} == {"bq", "bk", "bv", "bo"}
    m = dict(base, use_bias=True, **GRANITE)
    w = weights.make(m, 3, jnp.float32)
    tree = weights.program_tree(w, m)["seg0_dense"]
    r = GRANITE["residual_multiplier"]
    np.testing.assert_array_equal(tree["attn"]["bo"], w["bo"] * np.float32(r))
    np.testing.assert_array_equal(tree["mlp"]["bo"],
                                  w["b_down"] * np.float32(r))
    np.testing.assert_array_equal(tree["mlp"]["bi"], w["b_up"])
    assert ("bg" in tree["mlp"]) == gate
    if gate:
        np.testing.assert_array_equal(tree["mlp"]["bg"], w["b_gate"])


def numpy_logits(w, m, tokens) -> np.ndarray:
    """The published block in float64: full masked attention (causal,
    within the window), no pruning; the logits after the last token."""
    f = {k: np.asarray(v).astype(np.float64) for k, v in w.items()}
    n, hq, hk, dh = (len(tokens), m["num_attention_heads"],
                     m["num_key_value_heads"], m["head_dim"])
    eps = m.get("norm_epsilon", m.get("rms_norm_eps", 1e-6))

    def norm(x, g, b):
        if m["norm"] == "rmsnorm":
            return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / np.sqrt(var + eps) * g + b

    def act(x, gate):
        name = m["hidden_act"]
        if name == "silu":
            return gate / (1 + np.exp(-gate)) * x
        if name == "gelu":
            return 0.5 * x * (1 + np.vectorize(math.erf)(x / math.sqrt(2)))
        return 0.5 * x * (1 + np.tanh(math.sqrt(2 / math.pi)
                                      * (x + 0.044715 * x ** 3)))

    half = dh // 2
    ang = np.arange(n)[:, None] * m["rope_theta"] ** (-np.arange(half) / half)
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    t, s = np.arange(n)[:, None], np.arange(n)[None, :]
    sees = s <= t
    if m.get("sliding_window"):
        sees &= s > t - m["sliding_window"]
    x = f["embed"][tokens]
    for i in range(m["num_hidden_layers"]):
        lw = {k: v[i] for k, v in f.items()
              if k not in ("embed", "lm_head", "final_norm_w", "final_norm_b")}
        h = norm(x, lw["ln1_w"], lw.get("ln1_b"))
        q = rope((h @ lw["wq"] + lw["bq"]).reshape(n, hq, dh))
        k = rope((h @ lw["wk"] + lw["bk"]).reshape(n, hk, dh))
        v = (h @ lw["wv"] + lw["bv"]).reshape(n, hk, dh)
        k, v = np.repeat(k, hq // hk, 1), np.repeat(v, hq // hk, 1)
        sc = np.einsum("thd,shd->hts", q, k) / math.sqrt(dh)
        sc = np.where(sees, sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = np.einsum("hts,shd->thd", p, v).reshape(n, hq * dh)
        x = x + o @ lw["wo"] + lw["bo"]
        h = norm(x, lw["ln2_w"], lw.get("ln2_b"))
        gate = h @ lw["w_gate"] + lw["b_gate"] if "w_gate" in lw else None
        x = x + act(h @ lw["w_up"] + lw["b_up"], gate) @ lw["w_down"] \
            + lw["b_down"]
    h = norm(x[-1], f["final_norm_w"], f.get("final_norm_b"))
    return h @ (f["lm_head"] if "lm_head" in f else f["embed"].T)


@pytest.mark.parametrize("n", [100, 20])
@pytest.mark.parametrize("act", ["gelu_pytorch_tanh", "gelu", "silu"])
def test_reference_matches_numpy_forward(act, n):
    """A 32-token window over a 100-token prompt (it binds) and over a
    20-token one (it does not), prompts no longer than `heavy`."""
    m = dict(STARCODER2, hidden_act=act)
    w = weights.make(m, 7)
    prompt = np.random.default_rng(n).integers(0, m["vocab_size"], n)
    got = Reference(m, PRUNE, "f32", prompt_pad=128).first_logits(w, prompt)
    want = numpy_logits(w, m, prompt)
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def cache(rng, hk=2, S=32, dh=16, fill=32, step=120, bits=3):
    """A lane's cache of one layer: `fill` slots holding sorted distinct
    positions below `step`, random keys, values and scores."""
    pos = np.full((hk, S), -1, np.int32)
    for h in range(hk):
        pos[h, :fill] = np.sort(rng.permutation(step)[:fill])
    valid = pos >= 0
    k = rng.standard_normal((hk, S, dh)).astype(np.float32)
    kq, ks = quantize(jnp.asarray(k), bits)
    return Layer(k=jnp.asarray(k, jnp.bfloat16),
                 v=jnp.asarray(rng.standard_normal((hk, S, dh)), jnp.bfloat16),
                 kq=kq.astype(jnp.int8), ks=ks,
                 acc=jnp.asarray(np.where(valid, rng.random((hk, S)), 0),
                                 jnp.float32),
                 valid=jnp.asarray(valid), pos=jnp.asarray(pos),
                 fill=jnp.int32(fill), step=jnp.int32(step))


@pytest.mark.parametrize("base", ["granite", "starcoder2"])
@pytest.mark.parametrize("window", [None, 121])
def test_unbound_window_changes_nothing(base, window):
    """`sliding_window` null, or no shorter than prompt plus steps (a
    100-token prompt; a decode step at position 120), gives every path
    the windowless file's output bit for bit (the prompt's rows; the
    rows that pad it are no one's)."""
    m0 = dict(MODEL, **GRANITE) if base == "granite" else dict(STARCODER2)
    m0.pop("sliding_window", None)
    m = dict(m0, sliding_window=window)
    p = dict(PRUNE, heavy_budget=48)
    w = weights.make(m0, 11)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, m0["vocab_size"], 100)
    q, k, v = (jnp.asarray(rng.standard_normal((128, h, 16)), jnp.float32)
               for h in (4, 2, 2))
    c = cache(rng, fill=24), cache(rng)
    qd, kd, vd = (jnp.asarray(rng.standard_normal((h, 16)), jnp.float32)
                  for h in (4, 2, 2))

    def outputs(model):
        ref = Reference(model, p, "f32", prompt_pad=128)
        o, acc = jax.jit(ref.prefill_attend)(q, k, v, jnp.int32(100))
        out = [ref.first_logits(w, prompt), o[:100], acc,
               *jax.jit(ref.keep)(acc, jnp.int32(100))]
        for ci in c:
            out += [*jax.jit(ref.attend)(ci, qd, kd, vd),
                    *jax.jit(ref.write)(ci, kd, vd)]
        return [np.asarray(x) for x in out]

    for a, b in zip(outputs(m0), outputs(m)):
        np.testing.assert_array_equal(a, b)


def test_dead_slots():
    """A full cache under a window of 8 at query position 20: the slots
    holding positions <= 12 are dead. The oldest, a sink with the top
    score, is evicted first; the new token's score starts at the mean of
    every occupied slot's; the winners are live slots; a dead slot's keys
    and values weigh nothing and its score gains nothing."""
    m = dict(STARCODER2, sliding_window=8)
    p = dict(PRUNE, heavy_budget=12, reserve=4, select_k=4, sink_tokens=2,
             recent_window=2)
    ref = Reference(m, p, "f32")
    rng = np.random.default_rng(3)
    positions = np.array([0, 1, 3, 6, 7, 9, 10, 11, 12, 13, 14, 15, 16, 17,
                          18, 19], np.int32)
    dead = positions <= 12
    c = cache(rng, S=16, fill=16, step=20)
    pos = np.tile(positions, (2, 1))
    acc = np.where(dead, 0.1, 0.5).astype(np.float32)
    acc[0], acc[5] = 9.0, 0.0           # the sink ranks top; pos 9 least
    # dead keys that a query along them would pick over every live one
    k = np.asarray(c.k, np.float32)
    qd = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
    k[:, dead] = 8 * np.asarray(qd).reshape(2, 2, 16).sum(1)[:, None]
    kq, ks = quantize(jnp.asarray(k), p["score_bits"])
    c = c._replace(pos=jnp.asarray(pos), acc=jnp.asarray(np.tile(acc, (2, 1))),
                   k=jnp.asarray(k, jnp.bfloat16), kq=kq.astype(jnp.int8),
                   ks=ks)
    kd, vd = (jnp.asarray(rng.standard_normal((2, 16)), jnp.float32)
              for _ in range(2))
    after = ref.write(c, kd, vd)
    assert (np.asarray(after.pos)[:, 0] == 20).all()          # sink evicted
    np.testing.assert_array_equal(np.asarray(after.pos)[:, 1:], pos[:, 1:])
    np.testing.assert_allclose(np.asarray(after.acc)[:, 0], acc.mean(),
                               rtol=1e-6)
    _, live, idx = ref.select(after, qd)
    assert (np.asarray(after.pos)[np.arange(2)[:, None], np.asarray(idx)]
            > 12).all()
    np.testing.assert_array_equal(np.asarray(live),
                                  np.asarray(after.pos) > 12)
    o, acc_after = ref.attend(c, qd, kd, vd)
    gone = np.tile(dead, (2, 1)) & (np.arange(16) > 0)        # still dead
    np.testing.assert_array_equal(np.asarray(acc_after)[gone],
                                  np.tile(acc, (2, 1))[gone])
    assert (np.asarray(acc_after)[~gone] > np.asarray(after.acc)[~gone]).all()
    noise = jnp.asarray(gone[..., None] * rng.standard_normal((2, 16, 16)),
                        jnp.float32)
    kq2, ks2 = quantize(c.k.astype(jnp.float32) + noise, p["score_bits"])
    moved = c._replace(k=(c.k + noise).astype(jnp.bfloat16),
                       v=(c.v + noise).astype(jnp.bfloat16),
                       kq=kq2.astype(jnp.int8), ks=ks2)
    o2, acc2 = ref.attend(moved, qd, kd, vd)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(acc_after), np.asarray(acc2))


@pytest.mark.parametrize("change,names", [
    ({}, ["out_bias", "mlp_bias", "norm_eps", "sliding_window"]),
    ({"hidden_act": "relu2"}, ["relu2"]),
    ({"hidden_act": "gelu"}, ["'gelu'", "tanh"]),
    ({"rms_norm_eps": 1e-5}, ["rms_norm_eps", "norm_epsilon"]),
    ({"norm": "layer_norm"}, ["layer_norm"]),
    ({"sliding_window": 0}, ["sliding_window"]),
])
def test_program_model_refuses_by_name(change, names):
    """The StarCoder2-shaped file is refused in one error naming the four
    ModelConfig fields the program lacks; an activation the program has
    no form of, a file stating both epsilon keys, a norm of another name
    and an empty window, by name too."""
    m = dict(STARCODER2, **change)
    with pytest.raises(ValueError) as e:
        harness.program_model({"name": "t", "model": m, "prune": PRUNE})
    assert all(name in str(e.value) for name in names), str(e.value)
    if change.get("hidden_act") == "relu2":
        for make in (weights.shapes, lambda m: Reference(m, PRUNE)):
            with pytest.raises(ValueError, match="relu2"):
                make(m)


def test_program_model_takes_what_it_can_serve(monkeypatch):
    """Defaults stated outright pass (the epsilon under either key);
    where ModelConfig has the four fields, the file's values are set."""
    from repro.configs import base
    ok = dict(MODEL, norm_epsilon=1e-6, attention_out_bias=False,
              mlp_bias=False, sliding_window=None)
    model = harness.program_model({"name": "t", "model": ok, "prune": PRUNE})
    assert model.cfg.act == "swiglu"

    @dataclasses.dataclass(frozen=True)
    class Widened(base.ModelConfig):
        out_bias: bool = False
        mlp_bias: bool = False
        norm_eps: float = 1e-6
        sliding_window: object = None

    monkeypatch.setattr(base, "ModelConfig", Widened)
    cfg = harness.program_model({"name": "t", "model": STARCODER2,
                                 "prune": PRUNE}).cfg
    assert (cfg.out_bias, cfg.mlp_bias, cfg.norm_eps, cfg.sliding_window,
            cfg.act, cfg.norm, cfg.qkv_bias) == (True, True, 1e-5, 32,
                                                 "gelu", "ln", True)


def test_window_at_prefill():
    """A 32-token window over a 100-token prompt: the positions no later
    query sees (s <= 68), sinks included, rank below every live one and
    are never kept; the last `recent` are protected; the lockstep check
    reads a kept dead position as inf and the reference's own choice as
    0."""
    m = dict(STARCODER2, sliding_window=32)
    p = dict(PRUNE, heavy_budget=16, reserve=16)
    ref = Reference(m, p, "f32")
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((128, h, 16)), jnp.float32)
               for h in (4, 2, 2))
    o, acc = ref.prefill_attend(q, k, v, jnp.int32(100))
    rank = np.asarray(ref.rank(acc, 100))
    s = np.arange(128)
    assert np.isneginf(rank[:, (s <= 68) | (s >= 100)]).all()
    assert np.isposinf(rank[:, (s >= 92) & (s < 100)]).all()
    assert np.isfinite(rank[:, (s > 68) & (s < 92)]).all()
    idx, _ = ref.keep(acc, 100)
    assert (np.asarray(idx) > 68).all() and (np.asarray(idx) < 100).all()
    lock = harness.Lockstep(None, m, p, False)

    def keep_err(pos):
        kk = jnp.take_along_axis(jnp.swapaxes(k, 0, 1), pos[..., None], 1)
        return lock._prefill_err(o, acc, k, jnp.int32(100), o, pos,
                                 jnp.ones(pos.shape, bool),
                                 jnp.take_along_axis(acc, pos, 1), kk)[4]

    np.testing.assert_array_equal(keep_err(idx), 0.0)
    assert np.isposinf(keep_err(idx.at[:, 0].set(2))).all()
