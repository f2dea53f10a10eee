"""Distribution layer: sharding rules, logical-axis shim, compressed all-reduce
and a multi-device dry-run smoke cell (subprocess — jax device count is locked
at first init, so fake-device tests cannot run in the main test process)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.api import AxisRules, axis_ctx, logical_axes
from repro.distributed.sharding import batch_pspec, param_pspec, params_pspecs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
       "JAX_PLATFORMS": "cpu"}


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_param_pspec_rules():
    mesh = _FakeMesh({"data": 16, "model": 16})
    leaf = jnp.zeros((8192, 4096))

    class K:  # tree path key stub
        def __init__(self, key):
            self.key = key

    spec = param_pspec((K("layers"), K("attn"), K("wq")), leaf, mesh)
    assert spec == P("data", "model")
    spec = param_pspec((K("attn"), K("wo")), leaf, mesh)
    assert spec == P("model", "data")
    # indivisible dim stays unsharded (whisper vocab 51865)
    # indivisible vocab dim stays unsharded (whisper 51865); d_model -> data
    spec = param_pspec((K("embed"),), jnp.zeros((51865, 384)), mesh)
    assert spec == P(None, "data")   # template (M, D): 51865 % 16 != 0
    # stacked MoE expert dim -> model axis
    spec = param_pspec((K("moe"), K("wup")), jnp.zeros((64, 2048, 1024)), mesh)
    assert spec == P("model", "data", None)
    # unknown leaves replicated
    assert param_pspec((K("ln1"), K("scale")), jnp.zeros((64,)), mesh) == P()


def test_batch_pspec_divisibility():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert batch_pspec(256, mesh, multi_pod=False) == "data"
    assert batch_pspec(256, mesh, multi_pod=True) == ("pod", "data")
    assert batch_pspec(1, mesh, multi_pod=True) is None   # long_500k b=1
    assert batch_pspec(2, mesh, multi_pod=True) == "pod"


def test_logical_axes_noop_outside_context(rng):
    assert logical_axes("batch", None, "ffn") is None
    with axis_ctx(AxisRules(rules={"batch": "data", "ffn": "model"})):
        assert logical_axes("batch", None, "ffn") == P("data", None, "model")
    assert logical_axes("batch") is None


def test_params_pspecs_cover_every_arch():
    """Every large (>=1M elem) param leaf of every full config is sharded on
    at least one axis — catches rule-table gaps that would replicate a 72B
    matrix onto every chip."""
    from repro.configs import ARCH_IDS, get_config
    from repro.launch.specs import abstract_params
    from repro.models.encdec import init_encdec
    from repro.models.lm import init_lm
    mesh = _FakeMesh({"data": 16, "model": 16})
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        init = init_encdec if cfg.family == "audio" else init_lm
        a_params = abstract_params(cfg, init)
        specs = params_pspecs(a_params, mesh)
        flat = jax.tree_util.tree_flatten_with_path(a_params)[0]
        sflat = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
        for (path, leaf), spec in zip(flat, sflat):
            n = int(np.prod(leaf.shape))
            if n >= 1_000_000:
                assert any(a is not None for a in spec), \
                    f"{arch}: {jax.tree_util.keystr(path)} {leaf.shape} replicated"


def test_cache_pspecs_cover_namedtuple_fields():
    """Regression for §Perf HC0: NamedTuple field names (GetAttrKey) must
    reach the rule matcher — a silent miss replicates every KV cache across
    the model axis. Every large cache leaf must get a non-trivial spec."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.distributed.sharding import cache_pspecs
    from repro.models.lm import init_decode_cache
    mesh = _FakeMesh({"data": 16, "model": 16})
    for arch in ("qwen2_7b", "rwkv6_3b", "zamba2_1p2b"):
        cfg = get_config(arch)
        cache = jax.eval_shape(lambda: init_decode_cache(cfg, 128, 4096))
        specs = cache_pspecs(cache, mesh, "data")
        flat = jax.tree_util.tree_flatten_with_path(cache)[0]
        sflat = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
        for (path, leaf), spec in zip(flat, sflat):
            if int(np.prod(leaf.shape)) >= 1_000_000:
                assert any(a is not None for a in spec), \
                    f"{arch}: {jax.tree_util.keystr(path)} {leaf.shape} replicated"


def _run(code: str, devices: int = 8):
    env = {**ENV, "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_grad_compress_all_reduce_multidevice():
    """On a (pod=2, data=2, model=2) fake mesh: quantized cross-pod mean is
    close to the exact mean, residual = g - dequant(local codes)."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.compat import set_mesh
from repro.launch.mesh import make_mesh
from repro.optim.grad_compress import quantized_pod_mean
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
g = {"w": jnp.linspace(-1, 1, 64).reshape(8, 8)}
with set_mesh(mesh):
    gp = jax.device_put(g, NamedSharding(mesh, P()))
    # pod-varying input: add pod index so the mean is non-trivial
    def f(x):
        return quantized_pod_mean(x, mesh, bits=8)
    mean, resid = jax.jit(f)(gp)
exact = g["w"]  # both pods hold the same tensor -> mean == tensor
err = float(jnp.max(jnp.abs(mean["w"] - exact)))
print("ERR", err)
assert err < 2e-2, err
rez = float(jnp.max(jnp.abs(resid["w"])))
assert rez < 2e-2, rez
print("OK")
""")
    assert "OK" in out


def test_dryrun_smoke_cell_multidevice():
    """A reduced-config cell lowers + compiles on a (2,2,2) fake-device mesh —
    the same code path as the production dry-run, at test-friendly scale."""
    out = _run("""
import os
os.environ["DRYRUN_XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.compat import set_mesh
from repro.launch.mesh import make_mesh
from repro.launch.specs import build_cell
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
for arch, shape in [("qwen2_7b", "train_4k"), ("rwkv6_3b", "decode_32k")]:
    cell = build_cell(arch, shape, mesh, multi_pod=True, smoke=True)
    with set_mesh(mesh):
        c = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                    out_shardings=cell.out_shardings,
                    donate_argnums=cell.donate).lower(*cell.args).compile()
    assert c.memory_analysis() is not None
    print("OK", arch, shape)
""")
    assert out.count("OK") == 2
