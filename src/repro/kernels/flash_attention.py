"""Flash attention Pallas kernel — (block_q, block_kv) VMEM tiling.

TPU-native formulation of the attention hot path: softmax statistics (running
max m, normalizer l) and the output accumulator live in VMEM scratch across
the sequential kv-block grid dimension; the (S, S) score matrix is never
materialized in HBM. Matmul operands are (block_q, hd) x (hd, block_kv) —
128-aligned on both MXU dims for hd ∈ {64, 128} with the default blocks.

Grid: (B·H, S/block_q, S/block_kv) with the kv dimension sequential
("arbitrary" semantics): scratch persists across it, and fully-masked kv
blocks are skipped via pl.when (causal ⇒ ~half the blocks do no work;
windowed ⇒ only ~2W/S of them do).

Numerics: scores and the accumulator are fp32 regardless of input dtype;
masked lanes use a -1e30 fill (finite, so exp() underflows to exactly 0
without NaN edge cases at all-masked blocks — those are skipped anyway).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.kernels.compat import CompilerParams, pl, pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window, bq: int, bk: int,
                  nk_total: int, q_offset: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block skip predicate (trace-time grid indices -> cheap scalar compare)
    run = True
    if causal:
        # kv block strictly after the last query of this q block: fully masked
        run = ik * bk <= (iq + 1) * bq - 1 + q_offset
    if window is not None:
        run = jnp.logical_and(run, (ik + 1) * bk - 1 > iq * bq + q_offset - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale

        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                             # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                          # (bq, bk)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(ik == nk_total - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           block_q: int = 128, block_kv: int = 128,
                           interpret: bool | None = None) -> jax.Array:
    """q,k,v: (BH, S, hd), kv heads already repeated to BH. Returns (BH, Sq, hd).

    Supports Sq != Sk (q_offset-aligned causal masking for chunked prefill).
    """
    bh, sq, hd = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_kv, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    nk = sk // bk
    scale = 1.0 / (hd ** 0.5)

    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, nk_total=nk, q_offset=sk - sq)
    return pl.pallas_call(
        kern,
        grid=(bh, sq // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)
