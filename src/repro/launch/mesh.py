"""Production mesh construction.

`make_production_mesh` is a FUNCTION (never a module-level constant) so that
importing this module touches no jax device state — the dry-run sets
XLA_FLAGS before any jax initialization and only then calls this.

Mesh shapes:
    single pod : (16, 16)    axes ("data", "model")   = 256 chips (v5e pod)
    multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

The "pod" axis composes with "data" everywhere batch/FSDP sharding appears
(compound ("pod","data") axis), so adding pods scales batch and ZeRO shards
without touching any model-parallel dimension — the recipe extends to N pods
by changing one integer (elastic scaling: launch/elastic.py re-derives the
mesh from the live host set).
"""
from __future__ import annotations


import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with every axis `Auto`. The sharding rules
    (distributed/sharding.py) annotate arrays and leave propagation to
    GSPMD; `make_mesh`'s default Explicit axes would instead type every
    array's sharding, and ops mixing differently-sharded operands (the static
    decode path's `dynamic_update_slice` into its cache) then fail to trace."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a (data, model) mesh with model=1.
    Used by smoke tests and the CPU end-to-end drivers."""
    n = len(jax.devices())
    return auto_mesh((n, 1), ("data", "model"))


def make_elastic_mesh(n_chips: int, *, model_parallel: int = 16,
                      chips_per_pod: int = 256):
    """Derive a mesh from a live chip count (straggler-exclusion restarts).
    Keeps the model axis fixed and gives the remainder to (pod, data)."""
    if model_parallel < 1 or n_chips < 1 or n_chips % model_parallel:
        # ValueError, not assert: the check must survive `python -O` — a
        # silently mis-factored serving mesh is a deployment outage
        # (message pinned by tests/test_sharded_serving.py)
        raise ValueError(
            f"make_elastic_mesh: n_chips ({n_chips}) must be a positive "
            f"multiple of model_parallel ({model_parallel})")
    rows = n_chips // model_parallel
    pods = max(n_chips // chips_per_pod, 1)
    while rows % pods:
        pods -= 1
    data = rows // pods
    if pods > 1:
        return auto_mesh((pods, data, model_parallel), ("pod", "data", "model"))
    return auto_mesh((data, model_parallel), ("data", "model"))
