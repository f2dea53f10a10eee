"""Production mesh: one v5e pod = (data=16, model=16) = 256 chips;
multi-pod adds a leading DCN 'pod' axis (2 pods = 512 chips).

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import). Chip peaks
live in launch/chips.py."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis ``Auto``.

    The sharding code here places arrays with ``NamedSharding`` /
    ``with_sharding_constraint`` and leaves propagation to the compiler;
    ``Explicit`` axes (``make_mesh``'s default since jax 0.7) would instead
    type every array by its sharding and reject those programs.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dev_mesh(n_devices: int | None = None, *, prefer: str = "model"):
    """Small mesh over whatever devices exist (tests / examples).

    prefer="model" (default, train/dry-run): give the model axis the largest
    factor of n in (4, 2, 1) — a 4-device host becomes (data=1, model=4).
    prefer="data" (serving): all devices on the batch axis, (data=n, model=1)
    — the shape the batch-parallel cloud tier (serve.mesh_executor) wants.
    """
    n = n_devices or len(jax.devices())
    if prefer == "data":
        return make_mesh((n, 1), ("data", "model"))
    if prefer != "model":
        raise ValueError(f"prefer must be 'data' or 'model', got {prefer!r}")
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return make_mesh((n // model, model), ("data", "model"))
