"""Cross-container batched rANS decode — chunk-level interleave.

Decoding a micro-batch bucket one container at a time runs each chunk's
``steps = count / lanes`` interleave steps as tiny numpy dispatches at a
vector width of only ``lanes`` (often 2-8 on small BaF tensors): ``N * C *
steps`` dispatches for N same-shape containers of C channels.

This module coalesces the interleave across *all* chunks of *all*
containers in the batch: chunks with identical coding geometry (lanes,
probability resolution) stack into one ``(M, lanes)`` state matrix and the
decode loop runs ``steps`` iterations total at vector width ``M * lanes``
— each chunk still consumes its own word stream through a per-row pointer,
so outputs are bit-identical to the per-blob decoder (the batched
pipeline's hard invariant).

Each group builds one slot -> symbol table per row, once, in a single
vectorized ``np.repeat`` (``M x 2^prob_bits`` entries, one byte each up to
8 bits and two above: 16 MiB for 1,024 chunks at ``prob_bits`` 14, freed
when the group returns), so each step decodes every lane with one gather.

All integrity checks of the scalar path run here too: container/chunk CRCs
(via ``RansContainer.chunk_parts``), word-stream exhaustion, and the
lane-state return-to-initial check, each raising :class:`CorruptStream`.
"""
from __future__ import annotations

import numpy as np

from repro.codec import container as box
from repro.codec.backend import _chunk_layout
from repro.codec.rans import MAX_PROB_BITS, RANS_L, WORD_BITS, CorruptStream
from repro.obs import hooks

_U64 = np.uint64


def _pad_words(jobs_words: "list[bytes]") -> tuple[np.ndarray, np.ndarray]:
    """Stack ragged word streams -> (padded (M, W) uint16, lengths (M,))."""
    rows = [np.frombuffer(w, "<u2") for w in jobs_words]
    wlen = np.array([r.size for r in rows], np.int64)
    out = np.zeros((len(rows), int(wlen.max()) if len(rows) else 0),
                   np.uint16)
    for r, row in enumerate(rows):
        out[r, :row.size] = row
    return out, wlen


def _renorm(x, need, words, ptr, wlen):
    """One shared renormalization step: rows gather their own next words."""
    nneed = need.sum(axis=1)
    if nneed.any():
        if np.any(ptr + nneed > wlen):
            bad = int(np.argmax(ptr + nneed > wlen))
            raise CorruptStream(
                f"rANS word stream truncated in batch row {bad}: needed "
                f"{int(ptr[bad] + nneed[bad])} words, have {int(wlen[bad])}")
        idx = ptr[:, None] + np.cumsum(need, axis=1) - 1
        rowi = np.arange(x.shape[0])[:, None]
        w = words[rowi, np.where(need, idx, 0)]
        x = np.where(need, (x << _U64(WORD_BITS)) | w.astype(_U64), x)
        ptr += nneed
    return x, ptr


def _finish_checks(x, ptr, wlen):
    if np.any(ptr != wlen):
        bad = int(np.argmax(ptr != wlen))
        raise CorruptStream(
            f"rANS word stream has {int(wlen[bad] - ptr[bad])} unread "
            f"trailing words in batch row {bad}")
    if not bool(np.all(x == _U64(RANS_L))):
        raise CorruptStream(
            "rANS lane states did not return to initial value "
            "(corrupt payload)")


def _static_tables(tables: "list[np.ndarray]",
                   prob_bits: int) -> np.ndarray:
    """Stack M static frequency tables -> (M, S) uint64, with the checks
    :class:`RansTable` makes on one, raising :class:`CorruptStream` that
    names the first bad batch row."""
    freqs = np.stack([np.asarray(t) for t in tables]).astype(_U64)
    if prob_bits > MAX_PROB_BITS:
        # no encoder writes it, and its slot tables would be 2^prob_bits
        # entries per row
        raise CorruptStream(
            f"container prob_bits {prob_bits} exceeds {MAX_PROB_BITS}")
    sums = freqs.sum(axis=1)
    off = sums != _U64(1 << prob_bits)
    if off.any():
        bad = int(np.argmax(off))
        raise CorruptStream(
            f"frequency table of batch row {bad} sums to {int(sums[bad])}, "
            f"expected {1 << prob_bits}")
    zero = (freqs == 0).any(axis=1)
    if zero.any():
        raise CorruptStream(
            f"frequency table of batch row {int(np.argmax(zero))} has "
            f"zero-frequency symbols")
    return freqs


def _slot_tables(freqs: np.ndarray, prob_bits: int) -> np.ndarray:
    """(M, S) checked frequency tables -> (M, 2^prob_bits) slot -> symbol
    tables, row m what ``RansTable.slot_symbols`` gives for table m, built
    in one ``np.repeat``."""
    m, nsym = freqs.shape
    syms = np.arange(nsym, dtype=np.uint8 if nsym <= 256 else np.uint16)
    reps = freqs.ravel().astype(np.int64)
    return np.repeat(np.tile(syms, m), reps).reshape(m, 1 << prob_bits)


def _decode_static_group(jobs, count: int, prob_bits: int,
                         lanes: int) -> np.ndarray:
    """jobs: [(states, words bytes, freq table (S,) array)] -> (M, count)."""
    m = len(jobs)
    steps = -(-count // lanes)
    freqs = _static_tables([t for _, _, t in jobs], prob_bits)
    cums = np.cumsum(freqs, axis=1) - freqs
    lut = _slot_tables(freqs, prob_bits)
    hooks.observe("codec_decode_rows", m)
    x = np.stack([np.asarray(s) for s, _, _ in jobs]).astype(_U64)
    words, wlen = _pad_words([w for _, w, _ in jobs])
    mask = _U64((1 << prob_bits) - 1)
    pb = _U64(prob_bits)
    ptr = np.zeros(m, np.int64)
    rowi = np.arange(m)[:, None]
    out = np.empty((m, steps * lanes), np.uint32)
    for t in range(steps):
        slot = x & mask
        s = lut[rowi, slot]
        out[:, t * lanes:(t + 1) * lanes] = s
        x = freqs[rowi, s] * (x >> pb) + slot - cums[rowi, s]
        x, ptr = _renorm(x, x < _U64(RANS_L), words, ptr, wlen)
    _finish_checks(x, ptr, wlen)
    return out[:, :count]


def decode_tensor_batch(payloads: "list[bytes]", shape: tuple,
                        bits: int) -> np.ndarray:
    """Decode N same-shape containers -> (N, prod(shape)) channel-last rows.

    The decode behind ``core/codec.py``'s ``decode_many``: output row i
    equals ``decode_tensor(payloads[i], shape, bits).ravel()`` bit for
    bit, but all compatible chunks across the whole batch share one
    interleaved decode loop."""
    with hooks.timed("codec.decode_batch"):
        return _decode_tensor_batch(payloads, shape, bits)


def _decode_tensor_batch(payloads: "list[bytes]", shape: tuple,
                         bits: int) -> np.ndarray:
    shape = tuple(shape)
    n_ch, k = _chunk_layout(shape)
    count_total = int(np.prod(shape)) if shape else 1
    conts = [box.RansContainer.parse(p) for p in payloads]
    for cont in conts:
        h = cont.header
        if h.bits != bits:
            raise CorruptStream(
                f"container codes {h.bits} bits, wire header says {bits}")
        if h.n_chunks != n_ch:
            raise CorruptStream(
                f"container has {h.n_chunks} tile chunks, shape {shape} "
                f"needs {n_ch}")
        # symbol-count validation runs before the zero-size shortcut, like
        # the scalar decoder — a chunk claiming symbols for an empty shape
        # is corrupt, not ignorable
        for j in range(h.n_chunks):
            if cont.chunk_count(j) != k:
                raise CorruptStream(
                    f"chunk {j} holds {cont.chunk_count(j)} symbols, "
                    f"shape {shape} needs {k}")
    n = len(conts)
    if n_ch == 0 or k == 0:
        return np.zeros((n, count_total), np.uint32)
    mats = np.empty((n, n_ch, k), np.uint32)
    # group chunks by coding geometry; each group shares one decode loop
    groups: dict = {}
    for i, cont in enumerate(conts):
        h = cont.header
        for j in range(h.n_chunks):
            _count, states, words = cont.chunk_parts(j)   # CRC-verified
            groups.setdefault((h.prob_bits, h.lanes), []).append(
                ((i, j), (states, words, cont.chunk_table(j))))
    # all grouped chunks' lanes decode in one vector pass
    for (prob_bits, lanes), entries in groups.items():
        rows = _decode_static_group([job for _, job in entries], k,
                                    prob_bits, lanes)
        for (i, j), row in zip((pos for pos, _ in entries), rows):
            mats[i, j] = row
    # channel-last reassembly, one transpose over the whole stack
    return np.ascontiguousarray(
        mats.transpose(0, 2, 1)).reshape(n, count_total)
