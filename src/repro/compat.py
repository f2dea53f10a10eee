"""Single import site for the jax APIs that have moved between releases
(lint rule RA03; Pallas goes through kernels/compat.py).

``shard_map`` left ``jax.experimental`` and ``set_mesh`` replaced entering a
``Mesh``; call sites import both from here, as they do the described (not
attached) TPU topologies the chip-compile tests build, so the next move is
absorbed in this one file. The repo targets the installed jax only.
"""
from __future__ import annotations

import jax

__all__ = ["shard_map", "set_mesh", "tpu_topology"]

shard_map = jax.shard_map
set_mesh = jax.set_mesh


def tpu_topology(name: str):
    """Describe a TPU topology (e.g. ``"v5e:2x2"``) without a chip attached;
    its ``devices`` accept compiles, not data. Loads the TPU library."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu", topology_name=name)
