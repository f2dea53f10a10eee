"""The serving path's Pallas kernels compile for a TPU v5e chip.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached, so these tests need no accelerator. They catch
what interpret mode cannot: blocks not aligned to the (8, 128) tiling,
casts Mosaic lacks, and kernels that overrun VMEM. Shapes are the paper's
geometry (configs/yolo_baf.full_config: a 64x64 split tensor, so R = 4096
rows, Q = 128) at the smallest and largest C and both served bucket sizes.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import pipeline
from repro.configs.yolo_baf import full_config
from repro.core.baf import BaFConvConfig, init_baf_conv
from repro.core.split import restore_codes_fused
from repro.kernels.consolidate import consolidate_pallas
from repro.kernels.histogram import histogram_pallas
from repro.kernels.quantize import quantize_pallas
from repro.models.cnn import init_cnn
from repro.serve import LinearCostModel, MeshExecutor

R = 4096
BITS = 8
BATCHES = [1, 8]
CHANNELS = [8, 128]


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from repro.compat import tpu_topology
        try:
            desc = tpu_topology("v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_backend(monkeypatch):
    """The kernels pick interpret mode from the default backend, which is
    still the CPU here; the compile is for the TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **kwargs) -> str:
    return fn.lower(*args, **kwargs).compile().as_text()


def _param_specs(c, sharding):
    """Shapes of the full-width CNN and a BaF predictor for C, placed on
    ``sharding`` (nothing is allocated)."""
    cfg = full_config()

    def place(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, sharding), tree)

    params = place(jax.eval_shape(
        lambda: init_cnn(jax.random.PRNGKey(0), cfg)))
    baf = place(jax.eval_shape(lambda: init_baf_conv(
        jax.random.PRNGKey(1), BaFConvConfig(c=c, q=cfg.split_q))))
    return params, baf


@pytest.mark.parametrize("c", CHANNELS)
@pytest.mark.parametrize("b", BATCHES)
def test_quantize_compiles(one_chip, tpu_backend, b, c):
    x = _spec((b, R, c), jnp.float32, one_chip)
    hlo = _compiled_text(jax.jit(lambda v: quantize_pallas(v, BITS)), x)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("c", CHANNELS)
@pytest.mark.parametrize("b", BATCHES)
def test_consolidate_compiles(one_chip, tpu_backend, b, c):
    args = (_spec((b, R, c), jnp.float32, one_chip),
            _spec((b, R, c), jnp.uint8, one_chip),
            _spec((b, c), jnp.float16, one_chip),
            _spec((b, c), jnp.float16, one_chip))
    hlo = _compiled_text(
        jax.jit(lambda *a: consolidate_pallas(*a, BITS)), *args)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("c", CHANNELS)
@pytest.mark.parametrize("b", BATCHES)
def test_histogram_compiles(one_chip, tpu_backend, b, c):
    codes = _spec((b * R, c), jnp.int32, one_chip)
    hlo = _compiled_text(
        jax.jit(lambda v: histogram_pallas(v, 1 << BITS)), codes)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("c", CHANNELS)
@pytest.mark.parametrize("b", BATCHES)
def test_fused_restore_compiles(one_chip, tpu_backend, b, c):
    """The jitted restore the gateway serves: BaF predictor at full width
    plus the consolidation kernel, as one TPU program."""
    hw = full_config().split_hw
    params, baf = _param_specs(c, one_chip)
    hlo = _compiled_text(
        restore_codes_fused, baf, params["split"],
        _spec((c,), jnp.int32, one_chip),
        _spec((b, hw, hw, c), jnp.uint8, one_chip),
        _spec((b, 1, 1, c), jnp.float16, one_chip),
        _spec((b, 1, 1, c), jnp.float16, one_chip), bits=BITS)
    assert "tpu_custom_call" in hlo


def test_mesh_restore_forward_compiles_on_four_chips(topo, tpu_backend):
    """The sharded cloud tier's program over a 2x2 host: every mesh axis
    must be manual, or the consolidation kernel cannot be partitioned."""
    c, rows = 8, 64
    hw = full_config().split_hw
    mesh = Mesh(np.asarray(topo.devices).reshape(len(topo.devices), 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    params, baf = _param_specs(c, NamedSharding(mesh, P()))
    spec = pipeline.ModelSpec(sel_idx=np.arange(c), params=params,
                              baf_params=baf)
    plan = pipeline.compile(pipeline.OperatingPoint(c=c, bits=BITS), spec)
    fn = MeshExecutor(mesh, cost=LinearCostModel())._sharded_fn(
        plan, (rows, hw, hw, c))
    rows_on_data = NamedSharding(mesh, P("data"))
    hlo = _compiled_text(
        fn, baf, params, _spec((rows, hw, hw, c), jnp.uint8, rows_on_data),
        _spec((rows, 1, 1, c), jnp.float16, rows_on_data),
        _spec((rows, 1, 1, c), jnp.float16, rows_on_data))
    assert "tpu_custom_call" in hlo
