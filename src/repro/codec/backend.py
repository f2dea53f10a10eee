"""Tensor-level static rANS coding behind the wire codec (core/codec.py).

Maps a channel-last code tensor onto the container's per-channel chunks:
chunk i = channel i's symbols in raster order over the leading axes (for a
(B, H, W, C) BaF residual tensor: all of channel i, batch-major). The tensor
is coded as it is; no tiled 2D image is built.

Tables are static and per channel. Symbol statistics come from the
on-device histogram kernel (kernels/histogram.py); tables travel in the
container's zlib'd table blob. The encoder picks per-channel tables or one
shared pooled table, whichever yields fewer wire bytes (small tensors
can't amortize C tables) — the choice is recorded per container by simply
repeating the pooled table, so the decoder never special-cases it.
"""
from __future__ import annotations

import zlib

import numpy as np

from repro.codec import container as box
from repro.obs import hooks
from repro.codec.rans import (MAX_PROB_BITS, RANS_L, CorruptStream,
                              RansTable, encode_static_rows, normalize_freqs)

MAX_BITS = 12                # slot tables are 2^prob_bits; keep them sane
STATIC_LANES = 32
PROB_BITS_STATIC = 14


def _chunk_layout(shape: tuple) -> tuple[int, int]:
    """(n_chunks, symbols per chunk) for a shape.

    Channel-last for >= 2-D tensors; a 1-D/0-D stream is a SINGLE chunk
    (treating each element of a flat array as its own channel would emit a
    chunk header + lane states per element — a 14x blowup).
    """
    if len(shape) >= 2:
        c = shape[-1]
        return c, int(np.prod(shape[:-1]))
    k = shape[0] if shape else 1
    return (1 if k else 0), k


def _as_symbol_matrix(codes: np.ndarray, bits: int) -> np.ndarray:
    """(..., C) -> (C, K) uint32 symbol streams."""
    arr = np.asarray(codes)
    if not 1 <= bits <= MAX_BITS:
        raise ValueError(f"the rans coder supports 1..{MAX_BITS} bits, "
                         f"got {bits}")
    if arr.size:
        amin, amax = int(arr.min()), int(arr.max())
        if amin < 0:
            raise ValueError(f"rans coder: negative code {amin}")
        if amax >= 1 << bits:
            raise ValueError(f"rans coder: code {amax} does not fit "
                             f"{bits} bits")
    c, _k = _chunk_layout(arr.shape)
    mat = arr.reshape(-1, c).T.astype(np.uint32) if c else \
        np.empty((0, 0), np.uint32)
    return np.ascontiguousarray(mat)


def _expected_payload_bits(counts: np.ndarray, tables: list[RansTable],
                           prob_bits: int) -> float:
    """Cross-entropy estimate of the coded size of each chunk under its
    table: sum_s counts[s] * (prob_bits - log2(freq[s])). rANS realizes
    this within ~1%, which is plenty to pick a table layout without coding."""
    total = 0.0
    for i, t in enumerate(tables):
        f = t.freqs.astype(np.float64)
        total += float(np.sum(counts[i] * (prob_bits - np.log2(f))))
    return total


def encode_static_tensor(codes: np.ndarray, bits: int) -> bytes:
    """Code a channel-last tensor with per-channel (or pooled) static
    tables -> container bytes."""
    with hooks.timed("codec.encode"):
        return _encode_static_tensor(codes, bits)


def _encode_static_tensor(codes: np.ndarray, bits: int) -> bytes:
    from repro.kernels.histogram import channel_histogram

    mat = _as_symbol_matrix(codes, bits)
    n_ch, k = mat.shape
    # the device round trip inside encode: codes up, counts back
    with hooks.timed("codec.histogram"):
        counts = channel_histogram(mat.T, bits)   # (K, C): chunk layout

    # scale lanes with the chunk's expected *compressed* size: each lane
    # costs 4 bytes of state on the wire, so a heavily skewed (low-entropy)
    # chunk takes fewer lanes — target <= ~6% state overhead — while long
    # high-entropy chunks take the full vector width
    total = counts.sum(axis=1, keepdims=True)
    with np.errstate(divide="ignore", invalid="ignore"):
        p = counts / np.maximum(total, 1)
        ent_bits = float(-(counts * np.where(p > 0, np.log2(p, where=p > 0),
                                             0.0)).sum())
    payload_guess = max(1, int(ent_bits / 8) // max(n_ch, 1))
    lanes = max(1, min(STATIC_LANES, k // 32 or 1, payload_guess // 64 or 1))
    prob_bits = min(MAX_PROB_BITS, max(PROB_BITS_STATIC, bits + 2))
    if n_ch == 0 or k == 0:
        chunks = [(0, np.full(lanes, RANS_L, "<u4"), b"")] * n_ch
        tables = [normalize_freqs(np.ones(1 << bits), prob_bits)] * n_ch
        return box.pack_container(bits=bits, prob_bits=prob_bits,
                                  lanes=lanes, tables=tables, chunks=chunks)

    def build(tables: list[RansTable]):
        # every chunk of the container in one interleaved loop
        hooks.observe("codec_encode_rows", n_ch)
        states, words = encode_static_rows(mat, tables, lanes)
        chunks = [(k, states[i], words[i]) for i in range(n_ch)]
        return box.pack_container(bits=bits, prob_bits=prob_bits,
                                  lanes=lanes,
                                  tables=[t.freqs for t in tables],
                                  chunks=chunks)

    per_channel = [RansTable.from_counts(counts[i], prob_bits)
                   for i in range(n_ch)]
    tables = per_channel
    if n_ch > 1:
        # pick the table layout BEFORE coding: compare the cross-entropy
        # payload estimate plus the zlib'd table blob each layout transmits
        # (small tiles cannot amortize C tables), then code once
        pooled = RansTable.from_counts(counts.sum(axis=0), prob_bits)
        pooled_tables = [pooled] * n_ch

        def table_blob_bits(ts):
            raw = np.concatenate([t.freqs.astype("<u2") for t in ts])
            return 8 * len(zlib.compress(raw.tobytes(), 9))

        cost_per = (_expected_payload_bits(counts, per_channel, prob_bits)
                    + table_blob_bits(per_channel))
        cost_pool = (_expected_payload_bits(counts, pooled_tables, prob_bits)
                     + table_blob_bits(pooled_tables))
        if cost_pool < cost_per:
            tables = pooled_tables
    return build(tables)


def decode_tensor(payload: bytes, shape: tuple, bits: int) -> np.ndarray:
    """Decode a container back to the channel-last code tensor ``shape``."""
    with hooks.timed("codec.decode"):
        return _decode_tensor(payload, shape, bits)


def _decode_tensor(payload: bytes, shape: tuple, bits: int) -> np.ndarray:
    cont = box.RansContainer.parse(payload)
    h = cont.header
    if h.bits != bits:
        raise CorruptStream(
            f"container codes {h.bits} bits, wire header says {bits}")
    n_ch, k = _chunk_layout(tuple(shape))
    if h.n_chunks != n_ch:
        raise CorruptStream(
            f"container has {h.n_chunks} tile chunks, shape {shape} "
            f"needs {n_ch}")
    for i in range(n_ch):
        if cont.chunk_count(i) != k:
            raise CorruptStream(
                f"chunk {i} holds {cont.chunk_count(i)} symbols, shape "
                f"{shape} needs {k}")
    if n_ch == 0 or k == 0:
        return np.zeros(shape, np.uint32)
    mat = cont.decode_all()                        # (C, K)
    return mat.T.reshape(shape)


def decode_channels(payload: bytes, indices, count: int | None = None
                    ) -> np.ndarray:
    """Partial decode of selected channel chunks -> (len(indices), K)."""
    cont = box.RansContainer.parse(payload)
    out = cont.decode_channels(indices)
    if count is not None and out.size and out.shape[1] != count:
        raise CorruptStream(
            f"chunks hold {out.shape[1]} symbols, expected {count}")
    return out
