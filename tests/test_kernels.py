"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models.attention import repeat_kv
from repro.models.linear_attention import LOG_DECAY_MIN


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 4, 6, 8])
@pytest.mark.parametrize("shape", [(1, 64, 8), (2, 256, 128), (3, 100, 16)])
def test_quantize_kernel_matches_ref(rng, bits, shape):
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32)) * 7
    bc = min(128, shape[-1])
    codes, qp = ops.quantize_fused(x, bits, block_c=bc)
    rc, rm, rM = ref.quantize_fused_ref(x, bits)
    assert codes.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(qp.mins).reshape(shape[0], -1),
                                  np.asarray(rm))
    np.testing.assert_array_equal(np.asarray(qp.maxs).reshape(shape[0], -1),
                                  np.asarray(rM))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_kernel_dtypes(rng, dtype):
    x = jnp.asarray(rng.normal(size=(2, 32, 16)).astype(np.float32)).astype(dtype)
    codes, qp = ops.quantize_fused(x, 8, block_c=16)
    rc, _, _ = ref.quantize_fused_ref(x.astype(jnp.float32), 8)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(rc))


def test_quantize_kernel_4d_layout(rng):
    x = jnp.asarray(rng.normal(size=(2, 8, 8, 16)).astype(np.float32))
    codes, qp = ops.quantize_fused(x, 8, block_c=16)
    assert codes.shape == x.shape
    assert qp.mins.shape == (2, 1, 1, 16)   # per-example broadcastable


def _f16_probe_values() -> np.ndarray:
    """Every finite fp16 value, the midpoints between neighbours (ties) and
    the f32 values either side of each midpoint, plus overflow cases."""
    h = np.arange(1 << 16, dtype=np.uint16).view(np.float16)
    h = np.sort(h[np.isfinite(h)])
    base = h.astype(np.float32)
    mids = (base[:-1] + base[1:]) / 2
    return np.concatenate([
        base, mids, np.nextafter(mids, np.float32(np.inf)),
        np.nextafter(mids, np.float32(-np.inf)),
        np.asarray([65519.99, 65520.0, -65520.0, 7e4, -7e4, 1e-9, -1e-9,
                    np.inf, -np.inf], np.float32)])


def test_kernel_f16_rounding_matches_astype():
    """The quantize kernel rounds side info to fp16 with integer ops on the
    f32 bits (Mosaic on v5e has no f16); it must equal astype(float16)."""
    from repro.kernels.quantize import _round_f16
    x = _f16_probe_values()
    got = np.asarray(_round_f16(jnp.asarray(x)))
    with np.errstate(over="ignore"):
        want = x.astype(np.float16).astype(np.float32)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.signbit(got), np.signbit(want))


def test_kernel_f16_next_up_matches_nextafter():
    from repro.kernels.quantize import _next_f16_up
    h = np.arange(1 << 16, dtype=np.uint16).view(np.float16)
    h = h[~np.isnan(h)]
    got = np.asarray(_next_f16_up(jnp.asarray(h.astype(np.float32))))
    with np.errstate(over="ignore"):
        want = np.nextafter(h, np.float16(np.inf)).astype(np.float32)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# consolidate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [3, 6, 8])
@pytest.mark.parametrize("shape", [(1, 64, 8), (2, 512, 32), (2, 100, 64)])
def test_consolidate_kernel_matches_ref(rng, bits, shape):
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    codes, qp = ops.quantize_fused(x, min(bits, 8))
    est = x + jnp.asarray(rng.normal(size=shape).astype(np.float32)) * 0.3
    b, c = shape[0], shape[-1]
    out = ops.consolidate_fused(est, codes, qp.mins, qp.maxs, bits)
    rout = ref.consolidate_ref(est, codes, qp.mins.reshape(b, c),
                               qp.maxs.reshape(b, c), bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rout), atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,h,kh,hd", [(128, 4, 4, 32), (256, 4, 2, 64),
                                       (64, 2, 1, 128)])
def test_flash_attention_matches_ref(rng, causal, s, h, kh, hd):
    q = jnp.asarray(rng.normal(size=(2, s, h, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, s, kh, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, s, kh, hd)).astype(np.float32))
    o = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64)
    ro = ref.flash_attention_ref(q, repeat_kv(k, h), repeat_kv(v, h),
                                 causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ro),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_windowed(rng):
    s, h, hd, w = 256, 2, 32, 64
    q = jnp.asarray(rng.normal(size=(1, s, h, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, s, h, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, s, h, hd)).astype(np.float32))
    o = ops.flash_attention(q, k, v, causal=True, window=w,
                            block_q=64, block_kv=64)
    ro = ref.flash_attention_ref(q, k, v, causal=True, window=w)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ro),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16(rng):
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 64))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 64))).astype(jnp.bfloat16)
    o = ops.flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
    ro = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ro, np.float32), atol=3e-2)


def test_flash_attention_uneven_blocks(rng):
    # Sq != Sk (q_offset causal alignment, chunked prefill case)
    sq, sk = 64, 192
    q = jnp.asarray(rng.normal(size=(1, sq, 2, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, sk, 2, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, sk, 2, 32)).astype(np.float32))
    o = ops.flash_attention(q, k, v, causal=True, block_q=32, block_kv=32)
    ro = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ro),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# linear scan
# ---------------------------------------------------------------------------

def _ld(rng, shape):
    return -jnp.abs(jnp.asarray(rng.normal(size=shape).astype(np.float32)))


@pytest.mark.parametrize("mode", ["rwkv", "ssm"])
@pytest.mark.parametrize("s,chunk,dk,dv", [(64, 16, 16, 16), (128, 32, 32, 64),
                                           (96, 8, 64, 32)])
def test_linear_scan_matches_recurrent_ref(rng, mode, s, chunk, dk, dv):
    b, h = 2, 2
    q = jnp.asarray(rng.normal(size=(b, s, h, dk)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h, dk)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h, dv)).astype(np.float32))
    ld = _ld(rng, (b, s, h, dk)) if mode == "rwkv" else _ld(rng, (b, s, h, 1))
    bonus = (jnp.asarray(rng.normal(size=(h, dk)).astype(np.float32))
             if mode == "rwkv" else None)
    y, st = ops.linear_scan(q, k, v, ld, bonus=bonus, chunk=chunk, mode=mode)
    ld_full = jnp.clip(jnp.broadcast_to(ld, (b, s, h, dk)), LOG_DECAY_MIN, -1e-9)
    ry, rst = ref.linear_scan_ref(q, k, v, ld_full, bonus=bonus, mode=mode)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st), np.asarray(rst), atol=1e-3, rtol=1e-3)


def test_linear_scan_initial_state_chaining(rng):
    """Scanning two halves with carried state == one full scan."""
    b, s, h, dk, dv, chunk = 1, 64, 2, 16, 16, 16
    q = jnp.asarray(rng.normal(size=(b, s, h, dk)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h, dk)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h, dv)).astype(np.float32))
    ld = _ld(rng, (b, s, h, dk))
    y_full, st_full = ops.linear_scan(q, k, v, ld, chunk=chunk, mode="ssm")
    m = s // 2
    y1, st1 = ops.linear_scan(q[:, :m], k[:, :m], v[:, :m], ld[:, :m],
                              chunk=chunk, mode="ssm")
    y2, st2 = ops.linear_scan(q[:, m:], k[:, m:], v[:, m:], ld[:, m:],
                              chunk=chunk, mode="ssm", initial_state=st1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full),
                               atol=1e-4, rtol=1e-4)


def test_linear_scan_matches_library_chunked_engine(rng):
    """Kernel == models.linear_attention.chunked_linear_attention (the jnp
    path the models actually run) — same clamping, same chunk math."""
    from repro.models.linear_attention import chunked_linear_attention
    b, s, h, dk, dv, chunk = 2, 64, 2, 16, 16, 16
    q = jnp.asarray(rng.normal(size=(b, s, h, dk)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h, dk)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h, dv)).astype(np.float32))
    ld = _ld(rng, (b, s, h, dk))
    u = jnp.asarray(rng.normal(size=(h, dk)).astype(np.float32))
    y_k, st_k = ops.linear_scan(q, k, v, ld, bonus=u, chunk=chunk, mode="rwkv")
    y_j, st_j = chunked_linear_attention(q, k, v, ld, bonus=u, chunk=chunk,
                                         mode="rwkv")
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_j),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_j),
                               atol=1e-4, rtol=1e-4)
