"""Entropy-coding subsystem: rANS core, container, tensor coding, batched
decode, wire integration.

Round-trip properties run under hypothesis when installed (via the
hypothesis_compat shim) and as seeded spot checks otherwise.
"""
import hashlib
import zlib

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.codec import (CorruptStream, RansContainer, RansTable,
                         decode_channels, decode_tensor, encode_static_tensor,
                         normalize_freqs, rans_decode)
from repro.codec import container as box
from repro.codec.backend import _expected_payload_bits
from repro.codec.batch import decode_tensor_batch
from repro.codec.rans import (RANS_L, encode_static, encode_static_rows,
                              normalize_freqs_rows, rans_encode_rows)
from repro.obs import hooks
from repro.obs.metrics import MetricsRegistry
from repro.core import codec as wire
from repro.core.quant import QuantParams


def _qp(c, bits, rng):
    mins = rng.normal(size=(c,)).astype(np.float16)
    return QuantParams(mins=mins, maxs=(mins + 1).astype(np.float16),
                       bits=bits)


# ---------------------------------------------------------------------------
# normalize_freqs
# ---------------------------------------------------------------------------

@given(n=st.integers(1, 300), prob_bits=st.integers(9, 14),
       seed=st.integers(0, 2**16))
@settings(max_examples=50, deadline=None)
def test_property_normalize_freqs_exact_sum_min_one(n, prob_bits, seed):
    r = np.random.default_rng(seed)
    # arbitrary code distribution, including many zero-count symbols
    counts = (r.integers(0, 50, size=n)
              * (r.random(n) < 0.4)).astype(np.int64)
    f = normalize_freqs(counts, prob_bits)
    assert int(f.sum()) == 1 << prob_bits
    assert int(f.min()) >= 1


def test_normalize_freqs_all_zero_counts():
    f = normalize_freqs(np.zeros(16, np.int64), 12)
    assert int(f.sum()) == 4096 and int(f.min()) >= 1


def test_normalize_freqs_rejects_oversized_alphabet():
    with pytest.raises(ValueError, match="does not fit"):
        normalize_freqs(np.ones(1 << 13), 12)


def _scalar_normalize_freqs(counts, prob_bits):
    """The one-row normalization written out symbol by symbol: scale, bump
    zeros to 1, then hand a shortfall to the largest remainders (ties by
    index) or reclaim an excess from the largest frequencies down. Returns
    (freqs, the branch taken)."""
    c = [max(int(v), 0) for v in counts]
    n, target = len(c), 1 << prob_bits
    total = sum(c)
    if total == 0:
        c, total = [1] * n, n
    scaled = [v * target // total for v in c]
    freqs = [max(v, 1) for v in scaled]
    diff = target - sum(freqs)
    if diff > 0:
        rem = [c[i] * target - scaled[i] * total for i in range(n)]
        for i in sorted(range(n), key=lambda i: (-rem[i], i))[:diff]:
            freqs[i] += 1
        return np.array(freqs, np.uint32), "shortfall"
    need = -diff
    for i in sorted(range(n), key=lambda i: (-freqs[i], i)):
        take = min(freqs[i] - 1, need)
        freqs[i] -= take
        need -= take
    assert need == 0
    return np.array(freqs, np.uint32), ("oversubscribed" if diff else "exact")


def _norm_counts(kind, m, n, r):
    """(m, n) histograms that drive normalization down one branch each."""
    if kind == "shortfall":                 # no zeros: floors fall short
        return r.integers(1, 1000, size=(m, n))
    if kind == "oversubscribed":            # real codes: a peak, many zeros
        counts = np.zeros((m, n), np.int64)
        for row in counts:
            hot = r.choice(n, size=max(2, n // 64), replace=False)
            row[hot] = np.floor(1e5 * 0.6 ** np.arange(hot.size))
        return counts
    if kind == "all_zero":
        return np.zeros((m, n), np.int64)
    counts = np.zeros((m, n), np.int64)     # "single_symbol"
    counts[np.arange(m), r.integers(n, size=m)] = r.integers(1, 10**6, m)
    return counts


@pytest.mark.parametrize("kind", ["shortfall", "oversubscribed", "all_zero",
                                  "single_symbol", "mixed"])
@pytest.mark.parametrize("prob_bits", [9, 14, 15])
@pytest.mark.parametrize("m", [1, 8, 128])
def test_normalize_freqs_rows_matches_each_row_alone(m, prob_bits, kind):
    r = np.random.default_rng([m, prob_bits, len(kind)])
    # 4096 symbols at prob_bits 14 scale exactly, so any zero count bumped
    # to 1 oversubscribes the budget
    n = 4096 if prob_bits >= 12 else 256
    kinds = (["shortfall", "oversubscribed", "all_zero", "single_symbol"]
             if kind == "mixed" else [kind])
    counts = np.concatenate([_norm_counts(kinds[i % len(kinds)], 1, n, r)
                             for i in range(m)])
    rows = normalize_freqs_rows(counts, prob_bits)
    assert rows.shape == (m, n) and rows.dtype == np.uint32
    branches = set()
    for i in range(m):
        ref, branch = _scalar_normalize_freqs(counts[i], prob_bits)
        branches.add(branch)
        assert np.array_equal(rows[i], ref), i
        assert np.array_equal(normalize_freqs(counts[i], prob_bits), ref), i
    want = {"shortfall": {"shortfall"}, "oversubscribed": {"oversubscribed"}}
    assert branches >= want.get(kind, set())


@pytest.mark.parametrize("counts,prob_bits,msg", [
    (np.ones((3, 1 << 13)), 12, "does not fit"),
    (np.ones((2, 16)), 0, "prob_bits must be in"),
    (np.ones((2, 16)), 16, "prob_bits must be in"),
    (np.ones((2, 0)), 12, "empty alphabet"),
    (np.ones(16), 12, "must be"),
])
def test_normalize_freqs_rows_rejects(counts, prob_bits, msg):
    with pytest.raises(ValueError, match=msg):
        normalize_freqs_rows(counts, prob_bits)


# ---------------------------------------------------------------------------
# core coder round-trips
# ---------------------------------------------------------------------------

@given(bits=st.integers(1, 12), n=st.integers(0, 600),
       lanes=st.integers(1, 32), alpha=st.floats(0.05, 5.0),
       seed=st.integers(0, 2**16))
@settings(max_examples=60, deadline=None)
def test_property_static_roundtrip_arbitrary_distributions(bits, n, lanes,
                                                           alpha, seed):
    r = np.random.default_rng(seed)
    nsym = 1 << bits
    p = r.dirichlet(np.full(nsym, alpha))        # arbitrary code distribution
    syms = r.choice(nsym, size=n, p=p).astype(np.uint32)
    table = RansTable.from_counts(np.bincount(syms, minlength=nsym),
                                  max(12, bits + 2))
    states, words = encode_static(syms, table, lanes)
    dec = rans_decode(states, words, n, table, lanes)
    assert np.array_equal(dec, syms)


@pytest.mark.parametrize("encode_fn", [encode_static_tensor])
@pytest.mark.parametrize("shape", [(1, 1), (1,), (3, 1, 1), (2, 5, 3, 4),
                                   (0, 4), (4, 0), (6, 6, 8)])
def test_tensor_roundtrip_edge_shapes(rng, encode_fn, shape):
    codes = rng.integers(0, 32, size=shape).astype(np.uint32)
    blob = encode_fn(codes, 5)
    assert np.array_equal(decode_tensor(blob, shape, 5), codes)


@pytest.mark.parametrize("bits", [1, 2, 3, 5, 7, 9, 11, 12])
def test_odd_bit_widths_both_modes(rng, bits):
    codes = rng.integers(0, 1 << bits, size=(2, 7, 5, 3)).astype(np.uint32)
    assert np.array_equal(
        decode_tensor(encode_static_tensor(codes, bits), codes.shape, bits),
        codes)


# ---------------------------------------------------------------------------
# the row-stacked coder against a scalar reference
# ---------------------------------------------------------------------------

def _scalar_rans_encode(syms, freqs, cums, prob_bits, lanes):
    """ryg_rans rules, one symbol at a time: 32-bit states in [2^16, 2^32),
    16-bit renorm words, symbol i on lane i % lanes. Symbols are coded last
    to first, each pushing its renorm word; the decoder pops them, so the
    stream is the pushes reversed."""
    x = [1 << 16] * lanes
    pushed = []
    for i in range(len(syms) - 1, -1, -1):
        f, c, lane = int(freqs[syms[i]]), int(cums[syms[i]]), i % lanes
        if x[lane] >= f << (32 - prob_bits):
            pushed.append(x[lane] & 0xFFFF)
            x[lane] >>= 16
        x[lane] = ((x[lane] // f) << prob_bits) + x[lane] % f + c
    return (np.array(x, "<u4"),
            np.array(pushed[::-1], "<u2").tobytes())


_ROW_BITS = 6                                   # 64-symbol alphabet


def _row_table(kind, prob_bits, rng):
    nsym = 1 << _ROW_BITS
    if kind == "uniform":
        counts = np.ones(nsym)
    elif kind == "skewed":
        counts = np.floor(1e6 * 0.35 ** np.arange(nsym))
    else:                                       # one symbol holds all mass
        counts = np.zeros(nsym)
        counts[rng.integers(nsym)] = 1
    return RansTable.from_counts(counts, prob_bits)


def _check_rows(syms, tables, prob_bits, lanes):
    """Encode ``syms`` (M, K) stacked and prove every row against the
    scalar reference, ``rans_decode`` and the batched container decoder."""
    m, k = syms.shape
    pad = (-k) % lanes
    fill = [int(np.argmax(t.freqs)) for t in tables]
    padded = np.concatenate(
        [syms, np.repeat(np.array(fill, np.uint32)[:, None], pad, 1)], 1)
    freqs = np.stack([t.freqs for t in tables])
    cums = np.stack([t.cum for t in tables])
    states, words = rans_encode_rows(np.take_along_axis(freqs, padded, 1),
                                     np.take_along_axis(cums, padded, 1),
                                     prob_bits, lanes)
    assert states.shape == (m, lanes) and len(words) == m
    for i in range(m):
        ref_states, ref_words = _scalar_rans_encode(
            padded[i], tables[i].freqs, tables[i].cum, prob_bits, lanes)
        assert np.array_equal(states[i], ref_states), i
        assert words[i] == ref_words, i
        assert np.array_equal(
            rans_decode(states[i], words[i], k, tables[i], lanes), syms[i])
    blob = box.pack_container(
        bits=_ROW_BITS, prob_bits=prob_bits,
        lanes=lanes, tables=[t.freqs for t in tables],
        chunks=[(k, states[i], words[i]) for i in range(m)])
    # shape (K, M): chunk i is column i, so row i comes back as column i
    out = decode_tensor_batch([blob, blob], (k, m), _ROW_BITS)
    assert np.array_equal(out[0].reshape(k, m).T, syms)
    assert np.array_equal(out[1], out[0])
    return words


@pytest.mark.parametrize("table_kind", ["uniform", "skewed", "single"])
@pytest.mark.parametrize("prob_bits", [9, 14, 15])
@pytest.mark.parametrize("k_kind", ["padded", "one_step"])
@pytest.mark.parametrize("lanes", [1, 3, 32])
@pytest.mark.parametrize("m", [1, 2, 8, 130])
def test_rans_encode_rows_matches_scalar_reference(m, lanes, k_kind,
                                                   prob_bits, table_kind):
    r = np.random.default_rng([m, lanes, prob_bits, len(table_kind)])
    # "padded": K leaves the last step part-filled (lanes > 1); "one_step":
    # K = lanes, a single interleave step
    k = lanes if k_kind == "one_step" else 2 * lanes + 1 + (lanes > 1)
    tables = [_row_table(table_kind, prob_bits, r) for _ in range(m)]
    syms = np.stack([
        r.choice(1 << _ROW_BITS, size=k,
                 p=t.freqs / t.freqs.sum()).astype(np.uint32)
        for t in tables])
    _check_rows(syms, tables, prob_bits, lanes)


def test_rans_encode_rows_silent_row_beside_busy_rows():
    # a few steps of the dominant symbol never push a state past x_max, so
    # row 1 emits no renorm word while its uniform neighbours emit many
    r = np.random.default_rng(14)
    prob_bits, lanes, k = 14, 3, 7
    single = _row_table("single", prob_bits, r)
    uniform = _row_table("uniform", prob_bits, r)
    syms = np.stack([
        r.integers(0, 1 << _ROW_BITS, size=k),
        np.full(k, int(np.argmax(single.freqs))),
        r.integers(0, 1 << _ROW_BITS, size=k)]).astype(np.uint32)
    words = _check_rows(syms, [uniform, single, uniform], prob_bits, lanes)
    assert words[1] == b"" and words[0] and words[2]


def test_rans_encode_rows_rejects_partial_steps():
    with pytest.raises(ValueError, match="do not fill"):
        rans_encode_rows(np.ones((2, 5)), np.zeros((2, 5)), 9, 3)
    with pytest.raises(ValueError, match="one"):
        rans_encode_rows(np.ones((2, 6)), np.zeros((2, 3)), 9, 3)


# ---------------------------------------------------------------------------
# static container bytes: pinned, and the stacked loop's counter
# ---------------------------------------------------------------------------

def _laplace_codes(seed, scales):
    """(1, 64, 64, C) 8-bit codes around 128, channel c of width scales[c]:
    the paper's split tensor at the least and most compressed points."""
    r = np.random.default_rng(seed)
    z = r.laplace(0.0, 1.0, size=(1, 64, 64, len(scales))) * scales
    return np.clip(np.round(128 + z), 0, 255).astype(np.uint32)


# sha256 of the containers as the per-channel coding loop wrote them; the
# stacked loop must reproduce every byte (so wire sizes cannot move)
_GOLDEN = {
    "c128_per_channel": (
        1401, np.geomspace(0.5, 40, 128), False,
        "5611bee3f1aacd731bcf676ff9fb12f293760d06805b617b9c9ca75cbbd11261"),
    "c128_pooled": (
        1403, np.full(128, 6.0), True,
        "5fbdd670c9b935248346a2ed27ef76fbdc8319f30f3d7fab01a4c26d452f014e"),
    "c8_per_channel": (
        1402, np.geomspace(1, 30, 8), False,
        "8c1381dba0ee770c1d44c353fed9ec1eda53a4e775466b64bd4adf375fc6f078"),
    "c8_pooled": (
        1404, np.full(8, 6.0), True,
        "bbab62a4ccb4103dac81af9061f5fdb8c1df219a19040168e941615db5678293"),
    # a narrow field takes 15 lanes, so every chunk ends in a padded step
    "c8_pooled_15_lanes": (
        1404, np.full(8, 0.7), True,
        "ba4c208f6d336805e1e86b753cbd8e77f49b06683dd4d6838e9ecb04fec36142"),
}


@pytest.mark.parametrize("case", sorted(_GOLDEN))
def test_static_container_bytes_are_pinned(case):
    seed, scales, pooled, digest = _GOLDEN[case]
    codes = _laplace_codes(seed, scales)
    blob = encode_static_tensor(codes, 8)
    cont = RansContainer.parse(blob)
    tabs = [cont.chunk_table(j) for j in range(cont.header.n_chunks)]
    assert all(np.array_equal(t, tabs[0]) for t in tabs) == pooled
    assert hashlib.sha256(blob).hexdigest() == digest
    assert np.array_equal(decode_tensor(blob, codes.shape, 8), codes)


@pytest.mark.parametrize("c", [1, 8, 128])
def test_static_encode_observes_rows_once(rng, c):
    codes = rng.integers(0, 256, size=(1, 16, 16, c)).astype(np.uint32)
    m = MetricsRegistry()
    with hooks.active(m):
        blob = encode_static_tensor(codes, 8)
    rows = m.get("codec_encode_rows")
    assert rows is not None and rows.count == 1 and rows.total == c
    assert encode_static_tensor(codes, 8) == blob    # nothing installed
    assert not hooks.enabled() and m.get("codec_encode_rows").count == 1


# ---------------------------------------------------------------------------
# the table stage: layout choice and table section against the formula
# written out row by row
# ---------------------------------------------------------------------------

def _reference_layout(codes, bits):
    """Tables, layout, both costs and the counts, as the row-by-row formula
    picks them: each channel's table normalized alone, the cross-entropy
    payload summed row by row in row order, and each layout's table blob
    zlib'd at level 9."""
    c = codes.shape[-1]
    flat = codes.reshape(-1, c)
    counts = np.stack([np.bincount(flat[:, i], minlength=1 << bits)
                       for i in range(c)])
    prob_bits = max(14, bits + 2)
    per = [_scalar_normalize_freqs(row, prob_bits)[0] for row in counts]
    pooled = [_scalar_normalize_freqs(counts.sum(axis=0), prob_bits)[0]] * c

    def cost(tables):
        total = 0.0
        for i, t in enumerate(tables):
            total += float(np.sum(counts[i] * (prob_bits - np.log2(
                t.astype(np.float64)))))
        raw = np.concatenate([t.astype("<u2") for t in tables]).tobytes()
        return total + 8 * len(zlib.compress(raw, 9))

    cost_per, cost_pool = cost(per), cost(pooled)
    if c > 1 and cost_pool < cost_per:
        return pooled, "pooled", cost_per, cost_pool, counts
    return per, "per_channel", cost_per, cost_pool, counts


def _near_tie_codes(seed, h):
    """(1, h, 64, 8) codes whose channel widths span 2..8: near h = 60 the
    two layouts cost the same to a few bits in about 150,000."""
    r = np.random.default_rng([seed, h])
    z = r.laplace(0.0, 1.0, size=(1, h, 64, 8)) * np.geomspace(2, 8, 8)
    return np.clip(np.round(128 + z), 0, 255).astype(np.uint32)


def _delta_codes(seed):
    """A P-frame-like residual: (1, 64, 64, 128) codes at the zero point
    128 but for 2% of them, within +-8 of it."""
    r = np.random.default_rng(seed)
    codes = np.full((1, 64, 64, 128), 128, np.uint32)
    hit = r.random(codes.shape) < 0.02
    codes[hit] = r.integers(120, 137, size=int(hit.sum()))
    return codes


_LAYOUT_CASES = {
    # (seed, h) where the winner wins by under 2 bits
    "tie_pooled_24_64": (lambda: _near_tie_codes(24, 64), "pooled", 2),
    "tie_pooled_25_59": (lambda: _near_tie_codes(25, 59), "pooled", 2),
    "tie_per_channel_39_64": (lambda: _near_tie_codes(39, 64),
                              "per_channel", 2),
    "tie_per_channel_37_63": (lambda: _near_tie_codes(37, 63),
                              "per_channel", 2),
    "delta_c128": (lambda: _delta_codes(5), "pooled", None),
    "c1": (lambda: _laplace_codes(9, np.full(1, 3.0)), "per_channel", None),
    **{f"golden_{name}": ((lambda g=g: _laplace_codes(g[0], g[1])),
                          "pooled" if g[2] else "per_channel", None)
       for name, g in _GOLDEN.items()},
}


@pytest.mark.parametrize("case", sorted(_LAYOUT_CASES))
def test_table_layout_and_container_match_row_formula(case):
    make, layout, tie = _LAYOUT_CASES[case]
    codes = make()
    tables, ref_layout, cost_per, cost_pool, counts = _reference_layout(
        codes, 8)
    assert ref_layout == layout
    if tie is not None:                       # a near-tie, in bits
        assert abs(cost_pool - cost_per) < tie
    # the stacked costs are the row formula's floats, bit for bit
    per = normalize_freqs_rows(counts, 14)
    pooled = normalize_freqs_rows(counts.sum(axis=0, keepdims=True), 14)
    assert (_expected_payload_bits(counts, per, 14)
            + 8 * len(box.table_section(per))) == cost_per
    assert (_expected_payload_bits(counts, pooled, 14) + 8 * len(
        box.table_section(np.repeat(pooled, len(per), axis=0)))) == cost_pool
    m = MetricsRegistry()
    with hooks.active(m):
        blob = encode_static_tensor(codes, 8)
    cont = RansContainer.parse(blob)
    got = [cont.chunk_table(j) for j in range(cont.header.n_chunks)]
    assert all(np.array_equal(g, t) for g, t in zip(got, tables))
    n_ch = codes.shape[-1]
    assert (m.get("codec_table_layout", layout=layout) is not None) == \
        (n_ch > 1)
    # the whole container, rebuilt from the formula's tables with each row
    # coded alone
    syms = codes.reshape(-1, n_ch).T
    lanes = cont.header.lanes
    chunks = []
    for i in range(n_ch):
        table = RansTable(freqs=tables[i], prob_bits=cont.header.prob_bits)
        states, words = encode_static(syms[i], table, lanes)
        chunks.append((syms.shape[1], states, words))
    assert blob == box.pack_container(
        bits=8, prob_bits=cont.header.prob_bits, lanes=lanes,
        tables=tables, chunks=chunks)


@pytest.mark.parametrize("c,pooled", [(1, False), (8, False), (8, True),
                                      (128, False), (128, True)])
def test_table_section_compressed_once_per_candidate(monkeypatch, c, pooled):
    scales = np.full(c, 6.0) if pooled else np.geomspace(0.5, 40, c)
    codes = _laplace_codes(1400 + c, scales)
    want = encode_static_tensor(codes, 8)
    per, layout, _, _, _ = _reference_layout(codes, 8)
    assert (layout == "pooled") == pooled
    per_raw = np.concatenate([t.astype("<u2") for t in per]).tobytes()
    seen = []
    real = zlib.compress

    def spy(data, *args):
        seen.append(bytes(data))
        return real(data, *args)

    monkeypatch.setattr(zlib, "compress", spy)
    assert encode_static_tensor(codes, 8) == want
    # the per-channel section once, and the pooled one once beside it when
    # there is a choice to make; never the chosen one again
    assert seen.count(per_raw) == 1
    assert len(seen) == (1 if c == 1 else 2)


@pytest.mark.parametrize("given", ["tables", "section", "both", "neither"])
def test_pack_container_takes_tables_or_their_section(given):
    tables = [normalize_freqs(np.arange(1, 17), 12)] * 2
    chunks = [(0, np.full(1, RANS_L, "<u4"), b"")] * 2
    args = {"tables": tables, "section": box.table_section(tables)}
    if given in args:
        want = box.pack_container(bits=4, prob_bits=12, lanes=1,
                                  tables=tables, chunks=chunks)
        assert box.pack_container(bits=4, prob_bits=12, lanes=1,
                                  chunks=chunks,
                                  **{given: args[given]}) == want
        return
    with pytest.raises(ValueError, match="exactly one"):
        box.pack_container(bits=4, prob_bits=12, lanes=1, chunks=chunks,
                           **(args if given == "both" else {}))


@pytest.mark.parametrize("c", [1, 8, 128])
def test_table_layout_counter_once_per_container(rng, c):
    codes = rng.integers(0, 256, size=(1, 16, 16, c)).astype(np.uint32)
    m = MetricsRegistry()
    with hooks.active(m):
        for _ in range(3):
            encode_static_tensor(codes, 8)
    # 256 symbols a channel cannot amortize a table each: pooled wins
    assert m.get("codec_table_layout", layout="per_channel") is None
    pooled = m.get("codec_table_layout", layout="pooled")
    assert (pooled.value if c > 1 else pooled) == (3 if c > 1 else None)
    encode_static_tensor(codes, 8)             # nothing installed
    assert not hooks.enabled()
    assert c == 1 or pooled.value == 3


# ---------------------------------------------------------------------------
# batched decode: per-row slot tables against the scalar decoder
# ---------------------------------------------------------------------------

_C_BATCH = 2                                    # chunks per container
_LANES_FOR = {1: 1, 4: 3, 64: 5, 4096: 32}      # 3 and 5 leave a part step


def _batch_table(kind, bits, prob_bits, r):
    nsym = 1 << bits
    if kind == "laplace":
        width = r.uniform(0.5, 4) * max(1, nsym // 16)
        counts = np.floor(1e6 * np.exp(-np.abs(np.arange(nsym) - nsym / 2)
                                       / width))
    else:       # "spike": every symbol at frequency 1 beside a dominant one
        counts = np.zeros(nsym)
        counts[r.integers(nsym)] = 1
    return RansTable.from_counts(counts, prob_bits)


def _encode_rows(syms, tables, lanes):
    """Code row m of ``syms`` under ``tables[m]`` in one stacked loop."""
    return encode_static_rows(syms, np.stack([t.freqs for t in tables]),
                              np.stack([t.cum for t in tables]),
                              tables[0].prob_bits, lanes)


def _batch_container(tables, k, lanes, bits, r):
    """Symbols drawn from each chunk's table, with some of the rarest mixed
    in, coded into one static container -> (blob, symbols (C, k))."""
    syms = np.stack([
        r.choice(1 << bits, size=k, p=t.freqs / t.freqs.sum())
        for t in tables]).astype(np.uint32)
    for row, t in zip(syms, tables):                # freq-1 symbols too
        rare = np.flatnonzero(t.freqs == t.freqs.min())
        pos = r.choice(k, size=max(1, k // 64), replace=False)
        row[pos] = r.choice(rare, size=pos.size)
    states, words = _encode_rows(syms, tables, lanes)
    blob = box.pack_container(
        bits=bits, prob_bits=tables[0].prob_bits,
        lanes=lanes, tables=[t.freqs for t in tables],
        chunks=[(k, states[i], words[i]) for i in range(len(tables))])
    return blob, syms


@pytest.mark.parametrize("table_kind", ["laplace", "spike"])
@pytest.mark.parametrize("n", [1, 8])
@pytest.mark.parametrize("k", [1, 4, 64, 4096])
@pytest.mark.parametrize("bits,prob_bits", [
    (2, 9), (2, 14), (2, 15), (8, 9), (8, 14), (8, 15), (12, 14), (12, 15)])
@pytest.mark.parametrize("layout", ["per_channel", "pooled"])
def test_batched_decode_matches_scalar_decoders(layout, bits, prob_bits, k,
                                                n, table_kind):
    r = np.random.default_rng([bits, prob_bits, k, n, len(table_kind),
                               len(layout)])
    lanes = _LANES_FOR[k]
    blobs, syms = [], []
    for _ in range(n):
        tables = [_batch_table(table_kind, bits, prob_bits, r)
                  for _ in range(1 if layout == "pooled" else _C_BATCH)]
        tables = tables * (_C_BATCH // len(tables))
        blob, s = _batch_container(tables, k, lanes, bits, r)
        blobs.append(blob)
        syms.append(s)
    shape = (1, k, _C_BATCH)
    out = decode_tensor_batch(blobs, shape, bits)
    assert out.shape == (n, k * _C_BATCH)
    for i, blob in enumerate(blobs):
        cont = RansContainer.parse(blob)
        # the scalar decoder looks slots up in RansTable.slot_symbols()
        for j in range(_C_BATCH):
            _, states, words = cont.chunk_parts(j)
            table = RansTable(freqs=cont.chunk_table(j).astype(np.uint32),
                              prob_bits=prob_bits)
            ref = rans_decode(states, words, k, table, lanes)
            assert np.array_equal(ref, syms[i][j]), (i, j)
            assert np.array_equal(out[i].reshape(k, _C_BATCH)[:, j], ref)
        assert np.array_equal(out[i].reshape(shape),
                              decode_tensor(blob, shape, bits))


def _corrupt_static(kind, r):
    """Two good containers and one made bad in ``kind``'s way, its chunk
    CRCs valid, so the fault reaches the batched decode loop. The fault
    sits in container 1, chunk 1: batch row 3. Returns (blobs, shape,
    bits)."""
    bits, prob_bits, k, lanes = 4, 10, 96, 4
    tables = [_batch_table("laplace", bits, prob_bits, r) for _ in range(2)]
    blobs = [_batch_container(tables, k, lanes, bits, r)[0]
             for _ in range(3)]
    syms = r.integers(0, 1 << bits, size=(2, k)).astype(np.uint32)
    states, words = _encode_rows(syms, tables, lanes)
    states, words = states.copy(), list(words)
    freqs = [t.freqs.copy() for t in tables]
    if kind == "table_sum":
        freqs[1][0] += 1
    elif kind == "zero_freq":
        freqs[1][3] += freqs[1][5]
        freqs[1][5] = 0
    elif kind == "truncated_words":
        words[1] = words[1][:-2]
    elif kind == "trailing_words":
        words[1] = words[1] + b"\x01\x00"
    elif kind == "flipped_word":
        # the low bit of the last word read: the renorms stay in step, so
        # only the lane states tell
        last = np.frombuffer(words[1], "<u2").copy()
        last[-1] ^= 1
        words[1] = last.tobytes()
    blobs[1] = box.pack_container(
        bits=bits, prob_bits=prob_bits, lanes=lanes,
        tables=freqs,
        chunks=[(k, states[i], words[i]) for i in range(2)])
    return blobs, (1, k, 2), bits


@pytest.mark.parametrize("kind,msg", [
    ("table_sum", "frequency table of batch row 3 sums to"),
    ("zero_freq", "frequency table of batch row 3 has zero-frequency"),
    ("truncated_words", "truncated in batch row 3"),
    ("trailing_words", "trailing words in batch row 3"),
    ("flipped_word", "did not return to initial value"),
])
def test_batched_decode_corruption_raises_corrupt_stream(kind, msg):
    blobs, shape, bits = _corrupt_static(kind, np.random.default_rng(17))
    with pytest.raises(CorruptStream, match=msg):
        decode_tensor_batch(blobs, shape, bits)
    with pytest.raises(CorruptStream):               # the scalar path too
        decode_tensor(blobs[1], shape, bits)
    decode_tensor_batch([blobs[0], blobs[2]], shape, bits)


def test_batched_decode_rejects_prob_bits_beyond_format():
    # sums right and has no zero, but no encoder writes prob_bits 16, and
    # its slot tables would take 2^16 entries per row
    blob = box.pack_container(
        bits=1, prob_bits=16, lanes=1,
        tables=[np.array([1 << 15, 1 << 15])],
        chunks=[(1, np.array([RANS_L], "<u4"), b"")])
    with pytest.raises(CorruptStream, match="prob_bits 16 exceeds 15"):
        decode_tensor_batch([blob], (1, 1), 1)


@pytest.mark.parametrize("n,c", [(8, 128), (1, 8)])
def test_batched_decode_observes_rows_once(n, c):
    r = np.random.default_rng([n, c])
    codes = [r.integers(0, 256, size=(1, 64, 64, c)).astype(np.uint32)
             for _ in range(n)]
    blobs = [encode_static_tensor(x, 8) for x in codes]
    m = MetricsRegistry()
    with hooks.active(m):
        out = decode_tensor_batch(blobs, codes[0].shape, 8)
    rows = m.get("codec_decode_rows")
    assert rows is not None and rows.count == 1 and rows.total == n * c
    assert np.array_equal(out, np.stack([x.ravel() for x in codes]))
    decode_tensor_batch(blobs, codes[0].shape, 8)   # nothing installed
    assert not hooks.enabled() and m.get("codec_decode_rows").count == 1


def test_rans_rejects_out_of_range_codes(rng):
    with pytest.raises(ValueError, match="does not fit"):
        encode_static_tensor(np.full((4, 4), 300), 8)
    with pytest.raises(ValueError, match="negative"):
        encode_static_tensor(np.full((4, 4), -1), 8)
    with pytest.raises(ValueError, match="1..12"):
        encode_static_tensor(np.zeros((4, 4), np.uint32), 16)


# ---------------------------------------------------------------------------
# container: partial decode + corruption
# ---------------------------------------------------------------------------

def test_partial_decode_matches_full(rng):
    codes = rng.integers(0, 256, size=(2, 8, 8, 6)).astype(np.uint32)
    blob = encode_static_tensor(codes, 8)
    full = decode_tensor(blob, codes.shape, 8)
    part = decode_channels(blob, [5, 0, 2])
    for row, ch in zip(part, [5, 0, 2]):
        assert np.array_equal(row, full[..., ch].reshape(-1))


def test_partial_decode_skips_corrupt_other_chunks(rng):
    """Corruption in chunk j must not prevent decoding chunk i != j."""
    codes = rng.integers(0, 64, size=(1, 16, 16, 4)).astype(np.uint32)
    blob = bytearray(encode_static_tensor(codes, 6))
    blob[-3] ^= 0x55                       # flip bits inside the LAST chunk
    got = decode_channels(bytes(blob), [0])
    assert np.array_equal(got[0], codes[..., 0].reshape(-1))
    with pytest.raises(CorruptStream):
        decode_channels(bytes(blob), [3])


@pytest.mark.parametrize("mutate,msg", [
    (lambda b: b"XXXX" + b[4:], "bad container magic"),
    (lambda b: b[:1], "truncated container header"),
    (lambda b: b[:4] + bytes([9]) + b[5:], "unsupported container version"),
    (lambda b: b[:5] + bytes([7]) + b[6:], "header CRC mismatch"),
    (lambda b: b + b"zz", "trailing garbage"),
    (lambda b: b[:-5], "truncated chunk"),
])
def test_container_corruption_distinct_errors(rng, mutate, msg):
    codes = rng.integers(0, 16, size=(4, 4, 2)).astype(np.uint32)
    blob = encode_static_tensor(codes, 4)
    with pytest.raises(CorruptStream, match=msg):
        RansContainer.parse(mutate(blob)).decode_all()


# mode 1 was the retired adaptive-context coder
@pytest.mark.parametrize("mode", [7, 1])
def test_container_rejects_unknown_mode_with_valid_crc(mode):
    import struct
    import zlib as _z

    from repro.codec import container as box
    hdr = box._HEADER.pack(box.MAGIC, box.VERSION, mode, 4, 12, 1, 0, 0, 0)
    blob = hdr + struct.pack("<I", _z.crc32(hdr))
    with pytest.raises(CorruptStream, match="unknown container mode"):
        RansContainer.parse(blob)


def test_decode_tensor_shape_bits_crosschecks(rng):
    codes = rng.integers(0, 16, size=(4, 4, 2)).astype(np.uint32)
    blob = encode_static_tensor(codes, 4)
    with pytest.raises(CorruptStream, match="wire header says"):
        decode_tensor(blob, codes.shape, 6)
    with pytest.raises(CorruptStream, match="tile chunks"):
        decode_tensor(blob, (4, 4, 3), 4)
    with pytest.raises(CorruptStream, match="symbols"):
        decode_tensor(blob, (2, 4, 2), 4)


@given(seed=st.integers(0, 2**12))
@settings(max_examples=25, deadline=None)
def test_property_bit_flips_never_serve_wrong_data(seed):
    """Defense in depth (header CRC, table adler32, per-chunk CRC, lane-state
    check): any single-bit flip in a container either raises CorruptStream
    or decodes to exactly the original codes (flips in semantically-neutral
    zlib metadata bits of the table blob) — wrong tensors are never served."""
    r = np.random.default_rng(seed)
    codes = r.integers(0, 256, size=(1, 8, 8, 3)).astype(np.uint32)
    blob = bytearray(encode_static_tensor(codes, 8))
    pos = int(r.integers(0, len(blob)))
    blob[pos] ^= 1 << int(r.integers(0, 8))
    try:
        out = decode_tensor(bytes(blob), codes.shape, 8)
    except CorruptStream:
        return
    assert np.array_equal(out, codes)


# ---------------------------------------------------------------------------
# wire-codec integration (core/codec.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", range(2, 9))
def test_wire_roundtrip_all_c_bits(rng, bits):
    for c in (1, 4, 8):
        codes = rng.integers(0, 1 << bits, size=(2, 6, 6, c)).astype(np.uint8)
        qp = _qp(c, bits, rng)
        enc = wire.encode(codes, qp)
        dec, dec_qp = wire.decode(
            wire.EncodedTensor.from_bytes(enc.to_bytes()))
        assert np.array_equal(dec, codes)
        assert dec_qp.bits == bits


def test_wire_bits_counts_whole_container(rng):
    codes = rng.integers(0, 256, size=(1, 4, 4, 4)).astype(np.uint8)
    qp = _qp(4, 8, rng)
    enc = wire.encode(codes, qp)
    assert enc.wire_bits() == 8 * len(enc.to_bytes())
    assert enc.total_bits() == enc.wire_bits() - 8 * enc.header_bytes()


def test_static_close_to_floor_on_skewed_stream(rng):
    """Static tables on an iid skewed stream sit near the order-0 entropy."""
    p = np.asarray([0.6, 0.2, 0.1, 0.05, 0.02, 0.01, 0.01, 0.01])
    codes = rng.choice(8, size=(1, 64, 64, 4), p=p).astype(np.uint32)
    qp = _qp(4, 3, rng)
    enc = wire.encode(codes, qp)
    floor = wire.empirical_entropy_bits(codes, 3)
    assert 8 * len(enc.payload) <= 1.10 * floor


# ---------------------------------------------------------------------------
# Pallas histogram kernel vs bincount
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,bits", [((4, 16, 16, 8), 8), ((37, 5), 4),
                                        ((1, 1), 1), ((3, 7, 3), 6)])
def test_histogram_kernel_matches_bincount(rng, shape, bits):
    from repro.kernels.histogram import channel_histogram
    codes = rng.integers(0, 1 << bits, size=shape)
    counts = channel_histogram(codes, bits)
    c = shape[-1]
    ref = np.stack([np.bincount(codes.reshape(-1, c)[:, i],
                                minlength=1 << bits) for i in range(c)])
    assert np.array_equal(counts, ref)


def test_histogram_kernel_empty():
    from repro.kernels.histogram import channel_histogram
    counts = channel_histogram(np.empty((0, 4), np.int32), 8)
    assert counts.shape == (4, 256) and not counts.any()
