"""Runtime correctness of the shard_map blocked-decode path: on a real
(2 data × 4 model) mesh, the shard-local CAM race must produce the same
outputs as the single-device blocked reference."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import PruneConfig
    from repro.core.attention import decode_attention
    from repro.core.cache import init_cache
    from repro.runtime.sharding import use_mesh, decode_state_pspecs
    import jax.tree_util as jtu

    B, Hq, Hk, d, S = 4, 8, 4, 32, 64
    prune = PruneConfig(policy="unicaim", heavy_budget=56, reserve=8,
                        sink_tokens=2, recent_window=4, select_k=16,
                        select_blocks=4, score_bits=8, query_bits=8)

    def run(mesh):
        cache = init_cache(B, Hk, d, S, prune, jnp.float32)
        if mesh is not None:
            ctx = use_mesh(mesh)
            ctx.__enter__()
        outs = []
        step = jax.jit(lambda c, q, k, v: decode_attention(c, q, k, v,
                                                           prune))
        for i in range(24):
            ks = jax.random.split(jax.random.PRNGKey(i), 3)
            q = jax.random.normal(ks[0], (B, Hq, d))
            kn = jax.random.normal(ks[1], (B, Hk, d))
            vn = jax.random.normal(ks[2], (B, Hk, d))
            cache, o = step(cache, q, kn, vn)
            outs.append(np.asarray(o))
        if mesh is not None:
            ctx.__exit__(None, None, None)
        return np.stack(outs)

    ref = run(None)                       # pure blocked path, 1 device
    mesh = make_mesh((2, 4), ("data", "model"))
    got = run(mesh)                       # shard_map path (blocks=model=4)
    np.testing.assert_allclose(got, ref, atol=2e-4)
    print("SHARDMAP_OK")
""")


def test_shardmap_decode_matches_reference():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "SHARDMAP_OK" in out.stdout, (out.stdout[-2000:],
                                         out.stderr[-3000:])


MLA_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs.base import PruneConfig
    from repro.core import quant, scoring, topk
    from repro.core.cache import init_cache, protected_mask, write_token
    from repro.core.topk import NEG_INF
    from repro.models.mla import _mla_blocked_shardmap
    from repro.runtime.sharding import use_mesh

    # FULL budget (select_k == slots): every block keeps everything, so the
    # shard-local MLA race must equal dense latent attention exactly. The
    # comparison is at the latent-attention component level — full-model
    # logits go through MoE top-k routing, whose near-tie flips between two
    # differently-compiled (mesh vs no-mesh) programs are O(1) fp noise and
    # would mask a real shardmap bug.
    B, H, S, LAT, KVR = 4, 8, 64, 40, 32
    prune = PruneConfig(policy="unicaim", heavy_budget=S - 8, reserve=8,
                        sink_tokens=2, recent_window=4, select_k=S,
                        select_blocks=4, score_bits=8, query_bits=8)
    cache = init_cache(B, 1, LAT, S, prune, jnp.float32, latent=True)
    for i in range(50):
        u = jax.random.normal(jax.random.PRNGKey(i), (B, 1, LAT))
        cache = write_token(cache, u, None, prune)

    q_full = jax.random.normal(jax.random.PRNGKey(99), (B, H, LAT))
    qq, qs = quant.quantize_query(q_full, prune.query_bits)
    s_apx = scoring.approx_scores(qq, qs, cache.kq, cache.kscale,
                                  cache.valid)
    grouped = topk.gqa_group_scores(s_apx, 1)
    biased = topk.apply_selection_bias(
        grouped, protected_mask(cache, prune), ~cache.valid)
    scale_dim = 48

    u_all = cache.k[:, 0].astype(jnp.float32)
    logits = jnp.einsum("bhk,bsk->bhs", q_full, u_all) \\
        / jnp.sqrt(float(scale_dim))
    logits = jnp.where(cache.valid[:, 0][:, None, :], logits, NEG_INF)
    pr = jax.nn.softmax(logits, axis=-1)
    ref = jnp.einsum("bhs,bsk->bhk", pr, u_all[:, :, :KVR])

    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        got = _mla_blocked_shardmap(cache, q_full, biased, prune, mesh,
                                    KVR, scale_dim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4)
    print("MLA_SHARDMAP_OK")
""")


def test_mla_shardmap_decode_matches_reference():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", MLA_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "MLA_SHARDMAP_OK" in out.stdout, (out.stdout[-2000:],
                                             out.stderr[-3000:])


MOE_EP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from repro.launch.mesh import make_mesh
    from repro.configs.base import get_config, reduced
    from repro.models.moe import apply_moe, apply_moe_ep_shardmap, init_moe
    from repro.runtime.sharding import use_mesh

    cfg = reduced(get_config("grok-1-314b"))
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_experts=8, top_k=2, capacity_factor=8.0))
    params = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
    y_ref, _ = apply_moe(params, x, cfg)
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        y_ep, _ = jax.jit(lambda p, x: apply_moe_ep_shardmap(
            p, x, cfg, mesh))(params, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               atol=2e-4)
    print("MOE_EP_OK")
""")


def test_moe_ep_shardmap_matches_baseline_dispatch():
    """Expert-parallel all_to_all dispatch == sort-based dispatch when
    nothing drops (high capacity factor)."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", MOE_EP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "MOE_EP_OK" in out.stdout, (out.stdout[-2000:],
                                       out.stderr[-3000:])
