"""Elastic scaling: a checkpoint saved under one mesh restores onto a
DIFFERENT topology with shardings recomputed from logical rules.

Runs in a subprocess so it can claim 8 host devices without polluting the
single-device test session.
"""
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
    from repro.checkpoint.manager import CheckpointManager
    from repro.configs.base import get_config, reduced
    from repro.core import baselines
    from repro.models.transformer import Model
    from repro.runtime.sharding import params_pspecs, use_mesh
    from repro.runtime.fault import elastic_restore

    cfg = reduced(get_config("granite-3-2b"), n_heads=4, n_kv_heads=2)
    model = Model(cfg, baselines.unicaim(48, 16, 16))

    mesh_a = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh_a):
        params = model.init(jax.random.PRNGKey(0))
        sh = jax.tree.map(lambda s: NamedSharding(mesh_a, s),
                          params_pspecs(params),
                          is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(params, sh)
    mgr = CheckpointManager("/tmp/elastic_ckpt_test", keep=1,
                            async_save=False)
    mgr.save(7, params, block=True)
    flat_a = [np.asarray(x) for x in jax.tree.leaves(params)]

    # "cluster shrinks": restore onto a 4x2 mesh with recomputed shardings
    mesh_b = make_mesh((4, 2), ("data", "model"))
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    with use_mesh(mesh_b):
        def make_sh():
            return jax.tree.map(lambda s: NamedSharding(mesh_b, s),
                                params_pspecs(template),
                                is_leaf=lambda x: isinstance(x, P))
        restored = elastic_restore(mgr, template, make_sh)
    flat_b = [np.asarray(x) for x in jax.tree.leaves(restored)]
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(a, b)
    # the restored tree really lives on the new mesh
    leaf = jax.tree.leaves(restored)[0]
    assert leaf.sharding.mesh.shape == {"data": 4, "model": 2}, \
        leaf.sharding
    print("ELASTIC_OK")
""")


def test_elastic_restore_across_meshes():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "ELASTIC_OK" in out.stdout, (out.stdout[-2000:],
                                        out.stderr[-2000:])
