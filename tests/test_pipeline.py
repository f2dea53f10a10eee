"""Pod-boundary activation compression (Tier C) — multi-device subprocess."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np

from repro.distributed.pipeline import (_dequantize_stream, _quantize_stream,
                                        wire_bytes)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
       "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}


def test_stream_quant_roundtrip(rng):
    x = jnp.asarray(rng.normal(size=(2, 16, 32)).astype(np.float32)) * 3
    codes, mn, mx = _quantize_stream(x, 8)
    y = _dequantize_stream(codes, mn, mx, 8, jnp.float32)
    step = (np.asarray(mx, np.float32) - np.asarray(mn, np.float32)) / 255
    assert (np.abs(np.asarray(y - x)) <= 0.51 * step + 1e-4).all()


def test_wire_bytes_accounting():
    x = jnp.zeros((4, 64, 256))
    comp8, raw = wire_bytes(x, 8)
    comp4, _ = wire_bytes(x, 4)
    assert raw == x.size * 2
    assert comp8 == x.size + 256 * 4      # uint8 codes + fp16 min/max
    assert comp4 == x.size // 2 + 256 * 4


def test_pod_transfer_multidevice():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.baf import BaFStreamConfig, init_baf_stream
from repro.compat import set_mesh
from repro.launch.mesh import make_mesh
from repro.distributed.pipeline import compressed_pod_transfer, subset_pod_transfer
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 32), jnp.float32)
with set_mesh(mesh):
    xs = jax.device_put(x, NamedSharding(mesh, P()))
    y = jax.jit(lambda t: compressed_pod_transfer(t, mesh, bits=8,
                                                  dtype=jnp.float32))(xs)
    # both pods hold identical x, so the received tensor ~= x
    err = float(jnp.max(jnp.abs(y - x)))
    assert err < 0.05, err
    baf = init_baf_stream(jax.random.PRNGKey(1),
                          BaFStreamConfig(c=8, d_in=32, hidden=16))
    w = jax.random.normal(jax.random.PRNGKey(2), (32, 32)) * 0.05
    z = jax.jit(lambda t: subset_pod_transfer(
        t, mesh, sel_idx=jnp.arange(8), baf_params=baf,
        forward_fn=lambda h: h @ w, bits=8, dtype=jnp.float32))(xs)
    assert z.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(z)))
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], env=ENV,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
