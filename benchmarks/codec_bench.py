"""Entropy-codec benchmark: bits/element + throughput on BaF residuals.

    PYTHONPATH=src python benchmarks/codec_bench.py [--smoke]

Sweeps the wire-codec backends (raw / zlib / rans / rans-ctx) over a
(C, bits) grid of synthetic BaF residual tiles and reports, per point:

  * bits per element of the entropy-coded payload (the quantity RD tables
    and channel budgets are computed from),
  * the order-0 empirical-entropy floor (``core/codec.py``) as the target —
    a context coder may go *below* it by exploiting spatial correlation,
  * encode / decode throughput in MB/s of raw code bytes.

The residual generator mirrors what BaF prediction leaves behind: a small,
spatially smooth error field plus sparse heavy-tailed spikes whose per-
channel amplitude sets the quantizer range (exactly why near-lossless
residual coding pays off — the bulk of the mass lands in a few codes).
Tiles are encoded at deployment granularity (one example per container,
matching the gateway's one-request-per-transmission accounting).

``--smoke`` (CI) shrinks the sweep to < 60 s and **gates** on the paper-
motivated acceptance: rANS payload <= 0.95x zlib payload on 8-bit
residuals, exiting nonzero on failure.

Prints ``name,us_per_call,derived`` CSV rows like benchmarks/run.py and
writes benchmarks/BENCH_codec.json — a schema'd ``repro-bench/1`` record
(repro.obs.bench) that ``benchmarks/compare.py`` gates against the committed
baseline: payload bits/element are deterministic (tight tolerances), MB/s
throughputs are informational (shared CI runners).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

import jax.numpy as jnp

from repro.core import codec as wire
from repro.launch.compile_cache import enable_compile_cache
from repro.obs.bench import bench_record, metric, write_bench
from repro.core.quant import compute_quant_params, quantize
from repro.core.tiling import tile_batch

_ROWS: list[str] = []


def _row(name: str, us: float, derived: str):
    line = f"{name},{us:.1f},{derived}"
    _ROWS.append(line)
    print(line, flush=True)


def synthetic_baf_residuals(rng: np.random.Generator, b: int, h: int, w: int,
                            c: int, *, outlier_p: float = 0.003,
                            outlier_scale=(8.0, 40.0)) -> np.ndarray:
    """BaF-like residual field: smooth low-amplitude error + sparse spikes."""
    r = rng.normal(size=(b, h, w, c))
    for _ in range(2):                       # cheap separable smoothing
        r = (r + np.roll(r, 1, axis=1) + np.roll(r, 1, axis=2)) / 3.0
    r /= r.std(axis=(0, 1, 2), keepdims=True)
    amp = rng.uniform(*outlier_scale, size=(1, 1, 1, c))
    spikes = ((rng.random((b, h, w, c)) < outlier_p)
              * rng.normal(size=(b, h, w, c)) * amp)
    return (r + spikes).astype(np.float32)


def quantize_tile(z: np.ndarray, bits: int) -> np.ndarray:
    qp = compute_quant_params(jnp.asarray(z), bits, per_example=True)
    return np.asarray(quantize(jnp.asarray(z), qp)), qp


def bench_point(rng, *, h: int, w: int, c: int, bits: int,
                backends: tuple[str, ...], repeats: int = 1) -> dict:
    z = synthetic_baf_residuals(rng, 1, h, w, c)
    codes, qp = quantize_tile(z, bits)
    tiled = np.asarray(tile_batch(jnp.asarray(codes)))
    stream = tiled.reshape(-1, tiled.shape[-1])
    n = codes.size
    floor_bits = wire.empirical_entropy_bits(codes, bits)
    out = {"h": h, "w": w, "c": c, "bits": bits, "elements": n,
           "entropy_floor_bpe": floor_bits / n}
    for backend in backends:
        data = codes if not wire.backend_wants_tiling(backend) else stream
        t0 = time.perf_counter()
        for _ in range(repeats):
            enc = wire.encode(data, qp, backend=backend)
        enc_s = (time.perf_counter() - t0) / repeats
        blob = enc.to_bytes()
        t0 = time.perf_counter()
        for _ in range(repeats):
            dec, _ = wire.decode(wire.EncodedTensor.from_bytes(blob))
        dec_s = (time.perf_counter() - t0) / repeats
        assert np.array_equal(np.asarray(dec).ravel(), data.ravel()), \
            f"{backend} round-trip mismatch at C={c} bits={bits}"
        mb = n / 1e6                          # one code byte per element
        out[backend] = {
            "payload_bpe": 8 * len(enc.payload) / n,
            "wire_bpe": enc.wire_bits() / n,
            "encode_mb_s": mb / max(enc_s, 1e-9),
            "decode_mb_s": mb / max(dec_s, 1e-9),
        }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="CI gate, < 60 s")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    enable_compile_cache()
    rng = np.random.default_rng(args.seed)
    backends = ("raw", "zlib", "rans", "rans-ctx")

    if args.smoke:
        grid = [(32, 32, 8, 2), (32, 32, 8, 4), (32, 32, 8, 8),
                (64, 64, 8, 8), (32, 32, 16, 8)]
    else:
        grid = [(32, 32, c, bits) for c in (4, 8, 16)
                for bits in (2, 4, 6, 8)]
        grid += [(64, 64, c, 8) for c in (4, 8, 16)]

    results = {"seed": args.seed, "points": []}
    for h, w, c, bits in grid:
        r = bench_point(rng, h=h, w=w, c=c, bits=bits, backends=backends)
        results["points"].append(r)
        _row(f"codec_{h}x{w}x{c}_{bits}b", 0.0,
             f"floor={r['entropy_floor_bpe']:.2f}bpe "
             + " ".join(f"{b}={r[b]['payload_bpe']:.2f}" for b in backends)
             + f" rans_enc={r['rans']['encode_mb_s']:.2f}MB/s"
               f" rans_dec={r['rans']['decode_mb_s']:.2f}MB/s")

    # -- acceptance gate: rANS must beat zlib by >= 5% on 8-bit residuals --
    pts8 = [p for p in results["points"] if p["bits"] == 8]
    rans8 = sum(p["rans"]["payload_bpe"] * p["elements"] for p in pts8)
    zlib8 = sum(p["zlib"]["payload_bpe"] * p["elements"] for p in pts8)
    ratio = rans8 / zlib8
    results["rans_vs_zlib_8bit"] = ratio
    ok = ratio <= 0.95
    results["acceptance_rans_payload"] = ok
    _row("codec_gate", 0.0,
         f"rans/zlib payload @8bit = {ratio:.3f} "
         f"({'OK' if ok else 'FAIL'} <= 0.95)")

    # context coder vs the order-0 floor on the biggest 8-bit tiles
    big = [p for p in results["points"] if p["bits"] == 8
           and p["h"] * p["w"] * p["c"] >= 16384]
    if big:
        ctx = sum(p["rans-ctx"]["payload_bpe"] * p["elements"] for p in big)
        floor = sum(p["entropy_floor_bpe"] * p["elements"] for p in big)
        results["ctx_vs_floor_8bit"] = ctx / floor
        _row("codec_ctx_floor", 0.0,
             f"rans-ctx/entropy-floor @8bit = {ctx / floor:.3f}")

    # -- schema'd trajectory record (compare.py gates on the baseline's
    # tolerances). Payload sizes are seeded-deterministic: rANS realizes the
    # same stream byte for byte every run, zlib is looser across library
    # versions. Throughputs vary with the host -> informational.
    metrics = {
        "rans_vs_zlib_8bit": metric(ratio, tolerance=0.05),
    }
    if "ctx_vs_floor_8bit" in results:
        metrics["ctx_vs_floor_8bit"] = metric(results["ctx_vs_floor_8bit"],
                                              tolerance=0.05)
    _PAYLOAD_TOL = {"rans": 0.02, "rans-ctx": 0.02, "zlib": 0.05, "raw": 0.0}
    for p in results["points"]:
        point = f"{p['h']}x{p['w']}x{p['c']}_{p['bits']}b"
        metrics[f"entropy_floor_bpe.{point}"] = metric(
            p["entropy_floor_bpe"], tolerance=0.01)
        for b in backends:
            metrics[f"payload_bpe.{b}.{point}"] = metric(
                p[b]["payload_bpe"], tolerance=_PAYLOAD_TOL[b])
            metrics[f"decode_mb_s.{b}.{point}"] = metric(
                p[b]["decode_mb_s"], better="higher", tolerance=None)
    rec = bench_record(
        "codec",
        config={"seed": args.seed, "smoke": bool(args.smoke),
                "grid": [list(g) for g in grid]},
        metrics=metrics, raw=results)
    out = os.path.join(os.path.dirname(__file__), "BENCH_codec.json")
    write_bench(out, rec)
    print(f"wrote {out}")
    if args.smoke and not ok:
        print("ERROR: rANS payload gate failed", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
