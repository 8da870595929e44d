"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with Auto axes: the repo's sharding rules rely on
    GSPMD propagation, while `make_mesh` defaults to Explicit axes."""
    types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=types, devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist right now (tests / smoke runs)."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def make_serve_mesh(n_shards: int | None = None):
    """1-D lane-parallel serving mesh: the first `n_shards` devices on a
    single ``data`` axis. `ServeLoop(mesh=...)` shards its lane batch
    over it — decode lanes are independent, so the decode block lowers
    to a collective-free per-shard program (`tests/test_sharded_serve`).
    On CPU, force devices first: XLA_FLAGS=--xla_force_host_platform_device_count=8.
    """
    devs = jax.devices()
    n = n_shards or len(devs)
    return make_mesh((n,), ("data",), devices=devs[:n])


def make_elastic_mesh(n_devices: int | None = None):
    """Rebuild a (data, model) mesh for the CURRENT device count — the
    elastic-scaling entry point after a topology change."""
    devs = jax.devices()
    n = n_devices or len(devs)
    model = 1
    for cand in (16, 8, 4, 2, 1):
        if n % cand == 0:
            model = cand
            break
    return make_mesh((n // model, model), ("data", "model"), devices=devs[:n])
