"""Sharding rules: divisibility fallbacks, combined axes, cache specs, and
a tiny-mesh pjit end-to-end check (runs on however many host devices exist)."""
import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import PruneConfig, get_config, reduced
from repro.core import baselines
from repro.launch.mesh import make_mesh
from repro.models.transformer import Model
from repro.runtime.sharding import (decode_state_pspecs, logical_to_spec,
                                    params_pspecs, use_mesh)

jax.config.update("jax_platform_name", "cpu")


# abstract meshes over fake devices, for spec computation only
MESH = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
MESH3 = jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_divisibility_fallback_replicates():
    with use_mesh(MESH):
        # 24 heads don't divide 16 → replicated
        assert logical_to_spec(("heads",), (24,)) == P()
        assert logical_to_spec(("heads",), (32,)) == P("model")


def test_combined_axes_batch():
    with use_mesh(MESH3):
        spec = logical_to_spec(("batch", None), (256, 10))
        assert spec == P(("pod", "data"))
    with use_mesh(MESH):
        assert logical_to_spec(("batch", None), (256, 10)) == P("data")


def test_param_rules_attention_and_moe():
    cfg = reduced(get_config("grok-1-314b"),
                  d_model=64, n_heads=16, n_kv_heads=16, head_dim=16)
    model = Model(cfg, baselines.unicaim(48, 16, 16))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    with use_mesh(jax.sharding.AbstractMesh((2, 2), ("data", "model"))):
        specs = params_pspecs(shapes)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    by_name = {"/".join(str(getattr(k, "key", getattr(k, "name", k)))
                        for k in path): s for path, s in flat}
    wq = [v for k, v in by_name.items() if k.endswith("attn/wq")][0]
    assert wq == P(None, "data", "model")        # stack, fsdp, qdim
    wi = [v for k, v in by_name.items() if "moe/wi" in k][0]
    # [stack, experts→model, d→fsdp(data), ff replicated]
    assert wi[1] == "model" and wi[2] == "data"
    router = [v for k, v in by_name.items() if "moe/router" in k][0]
    assert router == P()                         # replicated


def test_decode_state_specs_kv_heads_vs_slots():
    prune = PruneConfig(policy="unicaim", heavy_budget=1984, reserve=64,
                        select_k=64)
    with use_mesh(MESH):
        # kv_heads=32 divides 16 → heads sharded, slots unsharded
        cfg = reduced(get_config("zamba2-7b"), n_kv_heads=32, n_heads=32,
                      num_layers=12, attn_period=6)
        m = Model(cfg, prune, decode_slots=2048)
        st = jax.eval_shape(lambda: m.init_decode_state(16))
        specs = decode_state_pspecs(st)
        assert specs.kv.k[2] == "model"
        # kv_heads=2 → slots take the model axis
        cfg2 = reduced(get_config("starcoder2-3b"), n_kv_heads=2)
        m2 = Model(cfg2, prune, decode_slots=2048)
        st2 = jax.eval_shape(lambda: m2.init_decode_state(16))
        specs2 = decode_state_pspecs(st2)
        assert specs2.kv.k[2] is None
        assert specs2.kv.k[3] == "model"


def test_decode_state_specs_long_context_combines_axes():
    prune = PruneConfig(policy="unicaim", heavy_budget=524224, reserve=64,
                        select_k=2048)
    with use_mesh(MESH):
        cfg = reduced(get_config("llava-next-mistral-7b"), n_kv_heads=2)
        m = Model(cfg, prune, decode_slots=524288)
        st = jax.eval_shape(lambda: m.init_decode_state(1))  # batch 1
        specs = decode_state_pspecs(st)
        # batch can't shard; slots fold model AND the idle data axis
        assert specs.kv.k[3] == ("model", "data")


def test_pjit_end_to_end_tiny_mesh():
    """Real pjit run on the host's devices (1 on CI — still exercises the
    NamedSharding path)."""
    n = len(jax.devices())
    mesh = make_mesh((1, n), ("data", "model"))
    cfg = reduced(get_config("granite-3-2b"), n_heads=4, n_kv_heads=2)
    prune = baselines.unicaim(heavy=48, reserve=16, select_k=16,
                              sink_tokens=2, recent_window=8)
    model = Model(cfg, prune)
    with use_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        shardings = jax.tree.map(
            lambda s: jax.NamedSharding(mesh, s), params_pspecs(params),
            is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(params, shardings)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (2, 32), 0, cfg.vocab_size)}
        logits, aux = jax.jit(model.train_logits)(params, batch)
        assert not np.isnan(np.asarray(logits)).any()
