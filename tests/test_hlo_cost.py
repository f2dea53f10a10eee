"""Trip-count-aware HLO cost model vs hand counts (DESIGN.md §4.1)."""
import jax
import jax.numpy as jnp

from repro.launch.hlo_cost import analyze_compiled, analyze_hlo_text


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_flops_scaled_by_trip_count():
    def f(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=12)
        return y
    c = _compile(f, jnp.zeros((128, 128)))
    r = analyze_compiled(c)
    assert r["flops"] == 12 * 2 * 128 ** 3
    # XLA's own analysis counts the body once — ours must exceed it
    assert r["flops"] > (c.cost_analysis().get("flops") or 0)


def test_nested_scan():
    def g(x):
        def outer(c, _):
            c2, _ = jax.lax.scan(lambda a, _: (a @ a, None), c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y
    r = analyze_compiled(_compile(g, jnp.zeros((64, 64))))
    assert r["flops"] == 4 * 3 * 2 * 64 ** 3


def test_dot_general_batched_flops():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)
    a = jnp.zeros((4, 32, 16))
    b = jnp.zeros((4, 16, 8))
    r = analyze_compiled(_compile(f, a, b))
    assert r["flops"] == 2 * 4 * 32 * 8 * 16


def test_collective_bytes_trip_scaled():
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    if len(jax.devices()) < 2:        # single real device: parse a synthetic HLO
        txt = """
%body (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %gte = f32[64]{0} get-tuple-element(%p), index=1
  %ar = f32[64]{0} all-reduce(%gte), replica_groups={}
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[64]{0}) tuple(%i, %ar)
}
%cond (p.1: (s32[], f32[64])) -> pred[] {
  ROOT %lt = pred[] compare(%x, %y), direction=LT
}
ENTRY %main (a: f32[64]) -> f32[64] {
  %w = (s32[], f32[64]{0}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %out = f32[64]{0} get-tuple-element(%w), index=1
}
"""
        r = analyze_hlo_text(txt)
        assert r["collective_bytes"].get("all-reduce") == 7 * 64 * 4
        return


def test_bytes_written_buffer_model():
    def f(a, b):
        return a @ b
    a = jnp.zeros((128, 64))
    b = jnp.zeros((64, 32))
    r = analyze_compiled(_compile(f, a, b))
    # at least write+read of the (128, 32) result through the dot
    assert r["bytes"] >= 2 * 128 * 32 * 4
    assert "dot" in r["bytes_by_op"]
