"""Interleaved multi-stream rANS — the repo's real entropy coder.

Range asymmetric numeral systems (Duda 2013) in the interleaved formulation
of Giesen's ryg_rans: N independent lane states share one 16-bit word stream
with a fixed, deterministic interleaving, so encode/decode vectorize over
lanes with numpy while remaining bit-exact.

Construction (all little-endian):

  * state x ∈ [L, L·2^16) with L = 2^16; renormalization emits/reads one
    16-bit word. ``x_max = f << (32 - prob_bits)`` ≥ 2^16 whenever
    ``prob_bits <= 16``, so at most ONE renormalization per symbol — the
    per-step emit is a single masked operation, no data-dependent loops.
  * lane l owns symbols l, l+N, l+2N, …; encoding walks the symbols in
    reverse and lays its renorm words out forward, step by step and in
    increasing lane order within a step, so the decoder (walking forward)
    reads them with a single pointer.
  * the encoder takes *per-symbol* (freq, cumfreq) arrays — a static table
    (``RansTable``) reduces to a gather before the coding loop, so the loop
    itself knows nothing of tables.
  * the encoder codes M streams of one length at once (``rans_encode_rows``):
    M stacked rows x N lanes of state advance through one reverse loop of
    K / N steps, and each row's renorm words are picked out afterwards, so a
    container's C chunks cost one Python-level loop, not C. Row m's states
    and words are exactly what coding stream m alone gives; one stream is
    the M = 1 case.
  * decoding a full stream must return every lane to the initial state L;
    ``rans_decode`` checks this, which catches most payload corruption that
    happens to keep slots in range.

Frequencies are normalized to sum exactly to ``1 << prob_bits`` with every
alphabet symbol kept ≥ 1 (``normalize_freqs``), so any symbol — including
lane padding — is always codable.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

RANS_L = 1 << 16            # lower bound of the normalization interval
WORD_BITS = 16              # renormalization word size
MAX_PROB_BITS = 15          # freqs must fit uint16 in the table blob

_U64 = np.uint64


class CorruptStream(ValueError):
    """A bitstream failed structural or arithmetic validation."""


def normalize_freqs_rows(counts: np.ndarray, prob_bits: int) -> np.ndarray:
    """Scale each row of histogram ``counts`` (M, S) to sum exactly to
    ``1 << prob_bits`` -> (M, S) uint32.

    Every symbol of the alphabet gets frequency >= 1 (even zero-count ones),
    so each table can code *any* symbol — required for lane padding. An
    all-zero row scales as if every count were 1. Deterministic: ties break
    by symbol index, so encoder and decoder derive identical tables from
    identical counts. Row m is exactly what normalizing it alone gives; all
    rows go through one set of array operations.
    """
    if not 1 <= prob_bits <= MAX_PROB_BITS:
        raise ValueError(f"prob_bits must be in [1, {MAX_PROB_BITS}], "
                         f"got {prob_bits}")
    c = np.maximum(np.asarray(counts, dtype=np.int64), 0)
    if c.ndim != 2:
        raise ValueError(f"counts must be (M, S), got shape {c.shape}")
    n = c.shape[1]
    target = 1 << prob_bits
    if n == 0:
        raise ValueError("empty alphabet")
    if n > target:
        raise ValueError(f"alphabet of {n} symbols does not fit "
                         f"prob_bits={prob_bits}")
    total = c.sum(axis=1, keepdims=True)
    c = np.where(total == 0, 1, c)
    total = np.where(total == 0, n, total)
    scaled = (c * target) // total
    freqs = np.maximum(scaled, 1)
    diff = target - freqs.sum(axis=1, keepdims=True)
    # a shortfall goes to the largest fractional remainders, one each; the
    # ranks sort remainders down, ties by symbol index
    rem = c * target - scaled * total
    rank = np.empty_like(freqs)
    np.put_along_axis(rank, np.argsort(-rem, axis=1, kind="stable"),
                      np.broadcast_to(np.arange(n), freqs.shape), axis=1)
    freqs += rank < diff
    # the min-1 bumps oversubscribed the budget: reclaim it from the largest
    # frequencies first (they lose the least precision), each down to 1,
    # until the excess is paid
    need = np.maximum(-diff, 0)
    order = np.argsort(-freqs, axis=1, kind="stable")
    spare = np.take_along_axis(freqs, order, axis=1) - 1
    before = np.cumsum(spare, axis=1) - spare
    take = np.clip(need - before, 0, spare)
    assert np.array_equal(take.sum(axis=1, keepdims=True), need), \
        "cannot normalize: alphabet too large"
    np.put_along_axis(freqs, order,
                      np.take_along_axis(freqs, order, axis=1) - take, axis=1)
    return freqs.astype(np.uint32)


def normalize_freqs(counts: np.ndarray, prob_bits: int) -> np.ndarray:
    """Scale one histogram: the M = 1 case of :func:`normalize_freqs_rows`."""
    return normalize_freqs_rows(np.reshape(counts, (1, -1)), prob_bits)[0]


def cum_freqs(freqs: np.ndarray) -> np.ndarray:
    """Exclusive prefix sums of frequency tables along the last axis."""
    return (np.cumsum(freqs, axis=-1, dtype=np.uint64)
            - freqs).astype(np.uint32)


@dataclass
class RansTable:
    """Static frequency table: freqs + exclusive cumulative + slot lookup."""
    freqs: np.ndarray               # (S,) uint32, sums to 1 << prob_bits
    prob_bits: int
    cum: np.ndarray = field(init=False)           # (S,) exclusive prefix sum
    _slots: np.ndarray | None = field(init=False, default=None, repr=False)

    def __post_init__(self):
        self.freqs = np.asarray(self.freqs, np.uint32)
        if int(self.freqs.sum()) != 1 << self.prob_bits:
            raise CorruptStream(
                f"frequency table sums to {int(self.freqs.sum())}, "
                f"expected {1 << self.prob_bits}")
        if self.freqs.size and int(self.freqs.min()) < 1:
            raise CorruptStream("frequency table has zero-frequency symbols")
        self.cum = cum_freqs(self.freqs)

    @classmethod
    def from_counts(cls, counts, prob_bits: int) -> "RansTable":
        return cls(freqs=normalize_freqs(counts, prob_bits),
                   prob_bits=prob_bits)

    def slot_symbols(self) -> np.ndarray:
        """(1 << prob_bits,) slot -> symbol decode lookup (lazily built)."""
        if self._slots is None:
            self._slots = np.repeat(
                np.arange(self.freqs.size, dtype=np.uint32),
                self.freqs).astype(np.uint32)
        return self._slots


def rans_encode_rows(freqs: np.ndarray, cums: np.ndarray, prob_bits: int,
                     lanes: int) -> tuple[np.ndarray, list[bytes]]:
    """Encode M symbol streams at once given their (freq, cumfreq) gathers.

    freqs/cums: (M, K) with K a multiple of ``lanes`` (callers pad, as
    :func:`encode_static_rows` does); row m holds stream m, entry (m, i)
    its symbol i. One reverse interleave loop of K / lanes steps codes all
    M rows side by side, M x lanes states wide; each row keeps its own lane
    states and its own word stream. Returns ``(final lane states (M, lanes) uint32, [word
    stream bytes of row m])``, row m exactly what coding it alone gives.
    """
    f = np.asarray(freqs)
    c = np.asarray(cums)
    if f.ndim != 2 or c.shape != f.shape:
        raise ValueError(f"freqs {f.shape} and cums {c.shape} must be one "
                         f"(M, K) shape")
    m, k = f.shape
    if lanes < 1 or k % lanes:
        raise ValueError(f"{k} symbols do not fill {lanes} lanes")
    steps = k // lanes
    shift = _U64(32 - prob_bits)
    pb = _U64(prob_bits)
    # step-major so each step reads one contiguous (M, lanes) block
    f = np.ascontiguousarray(f.reshape(m, steps, lanes).transpose(1, 0, 2),
                             _U64)
    c = np.ascontiguousarray(c.reshape(m, steps, lanes).transpose(1, 0, 2),
                             _U64)
    x_max = f << shift
    x = np.full((m, lanes), RANS_L, _U64)
    # each step's renorm mask and the low word of every state; the words
    # are picked out per row once the loop is done
    need = np.empty((steps, m, lanes), bool)
    low = np.empty((steps, m, lanes), "<u2")
    for t in range(steps - 1, -1, -1):
        nt = np.greater_equal(x, x_max[t], out=need[t])
        low[t] = x                                # keeps the low 16 bits
        x = np.where(nt, x >> _U64(WORD_BITS), x)
        q, r = np.divmod(x, f[t])
        x = (q << pb) + r + c[t]
    # row-major over (M, steps, lanes) is the decoder's order: step by step
    # forward, lanes increasing within a step, one row after another
    sel = need.transpose(1, 0, 2)
    words = low.transpose(1, 0, 2)[sel]
    ends = np.cumsum(need.sum(axis=(0, 2)))
    rows = np.split(words, ends[:-1]) if m else []
    return x.astype("<u4"), [w.tobytes() for w in rows]


def rans_encode(freqs: np.ndarray, cums: np.ndarray, prob_bits: int,
                lanes: int) -> tuple[np.ndarray, bytes]:
    """Encode one symbol stream: the M = 1 case of :func:`rans_encode_rows`.

    freqs/cums: (K,) with K a multiple of ``lanes``; entry i belongs to
    symbol i of the stream. Returns ``(final lane states (lanes,) uint32,
    word stream bytes)``.
    """
    states, words = rans_encode_rows(np.reshape(freqs, (1, -1)),
                                     np.reshape(cums, (1, -1)),
                                     prob_bits, lanes)
    return states[0], words[0]


def rans_decode(states: np.ndarray, words: bytes, count: int,
                table: RansTable, lanes: int) -> np.ndarray:
    """Decode ``count`` symbols coded with one static table.

    Raises :class:`CorruptStream` on a short/overlong word stream or when
    the lane states fail to return to the initial value (bit corruption).
    """
    if lanes < 1 or states.size != lanes:
        raise CorruptStream(
            f"expected {lanes} lane states, got {states.size}")
    steps = -(-count // lanes) if count else 0
    slot_syms = table.slot_symbols()
    freqs = table.freqs.astype(_U64)
    cums = table.cum.astype(_U64)
    mask = _U64((1 << table.prob_bits) - 1)
    pb = _U64(table.prob_bits)
    w = np.frombuffer(words, "<u2")
    x = states.astype(_U64)
    out = np.empty((steps, lanes), np.uint32)
    ptr = 0
    for t in range(steps):
        slot = x & mask
        s = slot_syms[slot]
        out[t] = s
        x = freqs[s] * (x >> pb) + slot - cums[s]
        need = x < _U64(RANS_L)
        nneed = int(np.count_nonzero(need))
        if nneed:
            if ptr + nneed > w.size:
                raise CorruptStream(
                    f"rANS word stream truncated: needed {ptr + nneed} "
                    f"words, have {w.size}")
            x[need] = (x[need] << _U64(WORD_BITS)) | w[ptr:ptr + nneed]
            ptr += nneed
    if ptr != w.size:
        raise CorruptStream(
            f"rANS word stream has {w.size - ptr} unread trailing words")
    if steps and not bool(np.all(x == _U64(RANS_L))):
        raise CorruptStream(
            "rANS lane states did not return to initial value "
            "(corrupt payload)")
    return out.reshape(-1)[:count]


def encode_static_rows(symbols: np.ndarray, freqs: np.ndarray,
                       cums: np.ndarray, prob_bits: int,
                       lanes: int) -> tuple[np.ndarray, list[bytes]]:
    """Static-table coder for M streams of one length: pad, gather (f, c),
    and code all rows in one :func:`rans_encode_rows` loop.

    symbols: (M, K); row m is coded with the table ``freqs[m]`` and its
    exclusive prefix sums ``cums[m]``, both (M, S). Padding uses each row's
    most probable symbol (cheapest per pad symbol); the decoder truncates by
    count, so only the wire cost is affected. Returns ``(lane states (M,
    lanes), [word bytes of row m])``.
    """
    symbols = np.asarray(symbols, np.uint32)
    pad = (-symbols.shape[1]) % lanes
    if pad:
        fill = np.argmax(freqs, axis=1).astype(np.uint32)
        symbols = np.concatenate(
            [symbols, np.repeat(fill[:, None], pad, axis=1)], axis=1)
    return rans_encode_rows(np.take_along_axis(freqs, symbols, axis=1),
                            np.take_along_axis(cums, symbols, axis=1),
                            prob_bits, lanes)


def encode_static(symbols: np.ndarray, table: RansTable,
                  lanes: int) -> tuple[np.ndarray, bytes]:
    """One stream under one static table: the M = 1 case of
    :func:`encode_static_rows`."""
    states, words = encode_static_rows(np.reshape(symbols, (1, -1)),
                                       table.freqs[None], table.cum[None],
                                       table.prob_bits, lanes)
    return states[0], words[0]
