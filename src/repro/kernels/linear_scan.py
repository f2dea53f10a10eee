"""Chunked linear-attention / SSD scan Pallas kernel (RWKV-6 & Mamba-2).

TPU adaptation of the CUDA per-thread recurrences in the RWKV-6 / Mamba-2
papers (DESIGN.md §4): instead of per-element sequential state updates, the
sequence is chunked so that

  * intra-chunk interactions are (L, dk) x (dk, L) / (L, L) x (L, dv) MXU
    matmuls (matmul form of the recurrence),
  * the inter-chunk state S ∈ (dk, dv) is carried in VMEM scratch across the
    sequential chunk grid dimension — it never round-trips to HBM.

Recurrence (per head):  S_t = diag(w_t) S_{t-1} + k_t ⊗ v_t
  rwkv mode:  y_t = q_t·S_{t-1} + (q_t ⊙ u ⊙ k_t)·v_t     (bonus u, strict)
  ssm  mode:  y_t = q_t·S_t                                (inclusive)

Numerics: identical to models/linear_attention.py — fp32 throughout, log-decay
clamped to [LOG_DECAY_MIN, -1e-9] by the ops wrapper so exp(±cum log decay)
stays finite within a chunk.

Grid: (B·H, S/L) with the chunk dimension sequential ("arbitrary").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.kernels.compat import CompilerParams, pl, pltpu


def _scan_kernel(q_ref, k_ref, v_ref, ld_ref, u_ref, s0_ref,
                 y_ref, sfinal_ref, state_ref, *,
                 mode: str, nc_total: int, use_bonus: bool):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    q = q_ref[0].astype(jnp.float32)                    # (L, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)                    # (L, dv)
    ld = ld_ref[0].astype(jnp.float32)                  # (L, dk)
    L = q.shape[0]

    la = jnp.cumsum(ld, axis=0)                         # inclusive cum log-decay
    la_prev = la - ld                                   # exclusive
    la_end = la[-1:, :]                                 # (1, dk)

    la_q = la_prev if mode == "rwkv" else la
    qd = q * jnp.exp(la_q)
    kd = k * jnp.exp(-la)
    k_rem = k * jnp.exp(la_end - la)

    # intra-chunk: strict lower-triangular (rwkv) / inclusive (ssm)
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tri = (col < row) if mode == "rwkv" else (col <= row)
    scores = jax.lax.dot_general(qd, kd, (((1,), (1,)), ((), ())))
    scores = jnp.where(tri, scores, 0.0)
    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())))

    if use_bonus:
        u = u_ref[0].astype(jnp.float32)                # (1, dk)
        bq = jnp.sum(q * u * k, axis=-1, keepdims=True)  # (L, 1)
        y = y + bq * v

    # inter-chunk: contribution of the carried state, then state update
    state = state_ref[...]                              # (dk, dv)
    y = y + jax.lax.dot_general(qd, state, (((1,), (0,)), ((), ())))
    state_ref[...] = jnp.exp(la_end[0])[:, None] * state + jax.lax.dot_general(
        k_rem, v, (((0,), (0,)), ((), ())))
    y_ref[0] = y

    @pl.when(ic == nc_total - 1)
    def _finalize():
        sfinal_ref[0] = state_ref[...]


def linear_scan_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                       log_decay: jax.Array, *, bonus: jax.Array | None = None,
                       initial_state: jax.Array | None = None,
                       chunk: int = 16, mode: str = "rwkv",
                       interpret: bool | None = None):
    """q,k,ld: (BH, S, dk); v: (BH, S, dv); bonus: (BH, dk) or None;
    initial_state: (BH, dk, dv) or None. Returns (y (BH,S,dv), state).

    Head flattening / decay clamping / bonus broadcasting live in ops.py.
    """
    bh, s, dk = q.shape
    dv = v.shape[-1]
    assert s % chunk == 0, (s, chunk)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    nc = s // chunk

    if bonus is None:
        bonus = jnp.zeros((bh, dk), jnp.float32)
        use_bonus = False
    else:
        use_bonus = mode == "rwkv"
    if initial_state is None:
        initial_state = jnp.zeros((bh, dk, dv), jnp.float32)

    kern = functools.partial(_scan_kernel, mode=mode, nc_total=nc,
                             use_bonus=use_bonus)
    y, sfinal = pl.pallas_call(
        kern,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dk), lambda b, c: (b, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(q, k, v, log_decay, bonus, initial_state)
    return y, sfinal
