"""Roofline analysis — derive the three terms per (arch × shape) cell from the
dry-run's compiled artifact (EXPERIMENTS.md §Roofline).

    compute    = HLO_FLOPs_per_device / peak bf16 FLOP/s
    memory     = HLO_bytes_per_device / HBM bandwidth
    collective = collective_bytes_per_device / chip-to-chip bandwidth

Peaks are the v5e entry of repro/launch/chips.py (the dry-run compiles for a
v5e pod); the collective term uses the chip's whole ICI bandwidth.

Note on "per chips": XLA's cost_analysis runs on the SPMD-*partitioned*
module, i.e. what ONE chip executes — so dividing by per-chip peaks is the
same as the brief's HLO_total/(chips × peak) under perfect balance. The
collective term uses summed collective operand bytes from the partitioned HLO
(dryrun.collective_bytes); it is an upper-ish bound that ignores ring-step
overlap, good for *ranking* bottlenecks and tracking deltas.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --single-pod-only --json dryrun.json
    PYTHONPATH=src python -m benchmarks.roofline --json dryrun.json --md roofline.md

Also writes benchmarks/BENCH_roofline.json — a schema'd ``repro-bench/1``
record with one informational metric per (arch, shape, kind) cell, so
``benchmarks/compare.py`` can report roofline trajectory across commits
(cost-model quantities, never gated).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, "src"))

from repro.launch.chips import chip_peaks  # noqa: E402
from repro.obs.bench import bench_record, metric, write_bench  # noqa: E402

PEAKS = chip_peaks("TPU v5 lite")      # the dry-run's production mesh

# steps per "unit of work" for MODEL_FLOPS accounting
_FWD_BWD = {"train": 6.0, "prefill": 2.0, "decode": 2.0, "long": 2.0}


def model_flops(arch: str, shape: str, kind: str, chips: int) -> float:
    """Analytic useful FLOPs per device: k·N_active·D_tokens / chips."""
    from repro.configs import get_config
    from repro.configs.base import SHAPES, active_param_count
    cfg = get_config(arch)
    sh = SHAPES[shape]
    n = active_param_count(cfg)
    if kind in ("train", "prefill", "long"):
        tokens = sh["global_batch"] * sh["seq_len"]
    else:                      # decode: one new token per sequence
        tokens = sh["global_batch"]
    return _FWD_BWD[kind] * n * tokens / chips


def analyse(rec: dict, chips: int = 256) -> dict:
    """rec: one dry-run record (repro.launch.dryrun.run_cell output).

    Prefers the trip-count-aware *_scaled fields (repro.launch.hlo_cost);
    falls back to raw cost_analysis values for old records."""
    flops = rec.get("flops_scaled") or rec.get("flops") or 0.0
    nbytes = rec.get("bytes_scaled") or rec.get("bytes_accessed") or 0.0
    coll = sum((rec.get("collective_bytes_scaled")
                or rec.get("collective_bytes") or {}).values())
    t_c = flops / PEAKS.bf16_flops
    t_m = nbytes / PEAKS.hbm_bytes_per_s
    t_x = coll / PEAKS.ici_bytes_per_s
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"], rec.get("kind", "train"), chips)
    bound = max(terms.values())
    return {
        **{f"t_{k}": v for k, v in terms.items()},
        "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": (mf / flops) if flops else 0.0,
        "roofline_frac": (mf / PEAKS.bf16_flops) / bound if bound else 0.0,
        # fraction of the bound step time that is useful model math at peak:
        # = (what an ideal implementation would take) / (this one's bound)
    }


_SUGGEST = {
    "compute": "reduce recompute (remat policy) / raise useful_ratio toward 1",
    "memory": "fuse elementwise chains, widen microbatch to raise arithmetic "
              "intensity, keep weights resident (serve: tp sharding)",
    "collective": "reshard to cut per-layer all-gathers, overlap collectives "
                  "with compute, compress cross-pod traffic (grad_compress)",
}


def to_markdown(records: list[dict], chips: int = 256) -> str:
    lines = [
        "| arch | shape | kind | compute s | memory s | collective s | "
        "dominant | useful ratio | roofline frac | next lever |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for rec in records:
        if rec.get("status") == "skip":
            lines.append(f"| {rec['arch']} | {rec['shape']} | — | — | — | — | "
                         f"N/A (quadratic attn @500k) | — | — | — |")
            continue
        if rec.get("status") != "ok":
            lines.append(f"| {rec['arch']} | {rec['shape']} | — | FAIL | | | "
                         f"| | | {rec.get('error','')[:60]} |")
            continue
        a = analyse(rec, chips)
        lines.append(
            f"| {rec['arch']} | {rec['shape']} | {rec.get('kind','')} "
            f"| {a['t_compute']:.3e} | {a['t_memory']:.3e} | {a['t_collective']:.3e} "
            f"| **{a['dominant']}** | {a['useful_ratio']:.2f} "
            f"| {a['roofline_frac']:.3f} | {_SUGGEST[a['dominant']]} |")
    return "\n".join(lines)


def bench_metrics(records: list[dict], chips: int = 256) -> dict:
    """Informational trajectory metrics: the cost model ranks bottlenecks,
    it does not gate (tolerance None everywhere)."""
    out: dict = {}
    for rec in records:
        if rec.get("status") != "ok":
            continue
        a = analyse(rec, chips)
        cell = f"{rec['arch']}.{rec['shape']}.{rec.get('kind', 'train')}"
        out[f"{cell}.bound_s"] = metric(
            max(a["t_compute"], a["t_memory"], a["t_collective"]),
            tolerance=None)
        out[f"{cell}.useful_ratio"] = metric(a["useful_ratio"],
                                             better="higher", tolerance=None)
        out[f"{cell}.roofline_frac"] = metric(a["roofline_frac"],
                                              better="higher", tolerance=None)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", required=True, help="dry-run records")
    ap.add_argument("--md", default=None, help="write markdown table here")
    ap.add_argument("--chips", type=int, default=256)
    args = ap.parse_args(argv)
    records = json.load(open(args.json))
    records = [r for r in records if r.get("mesh") != "pod2x16x16"
               or r.get("status") == "skip"]
    md = to_markdown(records, args.chips)
    if args.md:
        with open(args.md, "w") as f:
            f.write(md + "\n")
        print(f"wrote {args.md}")
    else:
        print(md)
    rec = bench_record(
        "roofline",
        config={"chips": args.chips, "cells": len(records)},
        metrics=bench_metrics(records, args.chips))
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "BENCH_roofline.json")
    write_bench(out, rec)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
