"""Compile-only checks of the decode kernels for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached, so each
case compiles a kernel for a described (not attached) v5e at the widths
the serving path runs — the tiling and layout refusals that interpret
mode cannot see fail here. Nothing executes. Every case must lower to a
Mosaic kernel (`tpu_custom_call`), not to an interpreted fallback.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the workers of a parallel test
run all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.fused_decode import fused_decode
from repro.kernels.ragged_decode import group_rows, ragged_decode

LANES = 8
SLOTS = 1088           # heavy 1024 + reserve 64, as chip_smoke.py serves
SELECT_K = 128
# (kv heads, query group, head dim): granite-3-2b GQA, longchat-7b MHA,
# phi3-medium-14b GQA with 10 kv heads (ragged_decode's 8-row groups
# straddle lanes)
WIDTHS = {"granite-3-2b": (8, 4, 64), "longchat-7b": (32, 1, 128),
          "phi3-medium-14b": (10, 4, 128)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _operands(sharding, model, kv_dtype, q_dtype, lanes=LANES):
    hk, g, d = WIDTHS[model]
    bh = lanes * hk

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    kv = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    fills = sds((bh,), jnp.int32)
    return fills, (
        sds((bh, g, d), q_dtype),                      # q
        sds((bh, g, d), jnp.int8),                     # qq
        sds((bh, g), jnp.float32),                     # qscale
        sds((bh, SLOTS, d), jnp.int8),                 # mirror
        sds((bh, SLOTS), jnp.float32),                 # mscale
        sds((bh, SLOTS), jnp.float32),                 # kscale
        sds((bh, SLOTS), jnp.float32),                 # vscale
        sds((bh, SLOTS), jnp.int8),                    # valid
        sds((bh, SLOTS), jnp.int8),                    # prot
        sds((bh, SLOTS, d), kv),                       # k
        sds((bh, SLOTS, d), kv))                       # v


# (matmul precision, query dtype): "served" as the serving path runs;
# "compare" as chip_smoke.py compares the engines (f32 activations at
# HIGHEST), where a kernel dot that leaves its precision to the context
# would ask Mosaic for an fp32 contraction of bf16 operands
MODES = {"served": ("default", jnp.bfloat16),
         "compare": ("highest", jnp.float32)}


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("model", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", ["ragged_decode", "fused_decode"])
def test_decode_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                        kernel, model, kv_dtype, mode):
    precision, q_dtype = MODES[mode]
    fills, args = _operands(one_chip, model, kv_dtype, q_dtype)
    with jax.default_matmul_precision(precision):
        if kernel == "ragged_decode":
            fn = jax.jit(lambda f, *a: ragged_decode(f, *a,
                                                     select_k=SELECT_K))
            lowered = fn.lower(fills, *args)
        else:
            # two selection blocks: the block-local race of select_blocks=2
            fn = jax.jit(lambda *a: fused_decode(*a, select_k=SELECT_K,
                                                 num_blocks=2))
            lowered = fn.lower(*args)
        hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo, f"{kernel} did not lower to Mosaic"


def _pallas_grid(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn.params["grid_mapping"].grid
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grid = _pallas_grid(sub)
            if grid is not None:
                return grid
    return None


def test_ragged_decode_groups_a_lanes_kv_heads(one_chip, caplog):
    """granite-3-2b at 16 lanes: BH = 128 rows, 8 a grid step (one lane's
    kv heads), so the grid is (16, 2·nb) and the CAM race runs once per
    lane per layer, not once per kv head."""
    hk, g, d = WIDTHS["granite-3-2b"]
    bh = 2 * LANES * hk
    nb = -(-SLOTS // 512)                       # block_s 512, 128-aligned
    fills, args = _operands(one_chip, "granite-3-2b", "bf16",
                            jnp.bfloat16, lanes=2 * LANES)
    with caplog.at_level("INFO", logger="repro.kernels"):
        jaxpr = jax.make_jaxpr(lambda f, *a: ragged_decode(
            f, *a, select_k=SELECT_K))(fills, *args)
    assert group_rows(bh) == 8
    assert _pallas_grid(jaxpr.jaxpr) == (bh // 8, 2 * nb) == (16, 6)
    assert f"8 rows a grid step, grid (16, {2 * nb})" in caplog.text
    assert [group_rows(n) for n in (80, 160, 12, 7, 13, 1)] == \
        [8, 8, 6, 7, 1, 1]
