"""Tier-C example: the paper's scheme on a multi-pod pipeline boundary.

    PYTHONPATH=src python examples/pod_boundary_compression.py

Runs on 8 fake devices arranged as (pod=2, data=2, model=2). The hidden
stream crossing the pod axis is (a) full-tensor-quantized (eq. 4) or
(b) subset-transmitted + BaF-restored (§3.3), and we report wire bytes and
restoration error vs the uncompressed bf16 transfer.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import set_mesh
from repro.launch.mesh import make_mesh
from repro.core.baf import BaFStreamConfig, init_baf_stream
from repro.distributed.pipeline import (compressed_pod_transfer,
                                        subset_pod_transfer, wire_bytes)

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
B, S, D, C = 4, 64, 256, 64

key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (B, S, D), jnp.float32)
with set_mesh(mesh):
    xs = jax.device_put(x, NamedSharding(mesh, P()))

    # (a) full-tensor n-bit transfer
    for bits in (8, 4):
        y = jax.jit(lambda t: compressed_pod_transfer(
            t, mesh, bits=bits, dtype=jnp.float32))(xs)
        comp, raw = wire_bytes(x, bits)
        err = float(jnp.max(jnp.abs(y - x)))  # both pods hold the same x here
        print(f"[full  n={bits}] wire {comp:>8,} B vs bf16 {raw:>8,} B "
              f"({raw/comp:.1f}x less)  max dequant err {err:.4f}")

    # (b) the paper's subset + BaF restore: transmit C of D channels
    sel = jnp.arange(C)                      # offline order (eqs. 2-3)
    baf = init_baf_stream(jax.random.PRNGKey(1),
                          BaFStreamConfig(c=C, d_in=D, hidden=128))
    w_block = jax.random.normal(jax.random.PRNGKey(2), (D, D)) * 0.05
    frozen_block = lambda t: t @ w_block     # receiver's boundary block

    y = jax.jit(lambda t: subset_pod_transfer(
        t, mesh, sel_idx=sel, baf_params=baf, forward_fn=frozen_block,
        bits=8, dtype=jnp.float32))(xs)
    comp, raw = wire_bytes(x[..., :C], 8)
    print(f"[subset C={C}/{D} n=8] wire {comp:>8,} B vs bf16 full "
          f"{x.size*2:>8,} B ({x.size*2/comp:.1f}x less); restored "
          f"{y.shape} (predictor untrained here; Tier-A trains it)")
print("wire-byte accounting matches the paper's: payload + C*32-bit side info")
