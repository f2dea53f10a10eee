"""Benchmark-trajectory gate: compare a BENCH_*.json against a baseline.

    PYTHONPATH=src python benchmarks/compare.py \
        --current benchmarks/BENCH_session.json \
        --baseline /tmp/BENCH_session.baseline.json [--check] [--verbose]

Loads two ``repro-bench/1`` records (see ``repro.obs.bench``) and prints a
per-metric trajectory report. Exit code 1 when any gated metric regressed
beyond the **baseline's** tolerance, a gated metric disappeared, the names
differ, or the configs drifted (``--allow-config-drift`` downgrades drift to
informational — e.g. intentionally comparing across request counts).

``--check FILE`` just validates a record against the schema and exits.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, "src"))

from repro.obs.bench import compare, format_report, load_bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="gate a benchmark record against a baseline")
    ap.add_argument("--current", help="BENCH_*.json from this run")
    ap.add_argument("--baseline", help="BENCH_*.json to gate against")
    ap.add_argument("--check", metavar="FILE",
                    help="only validate FILE against the schema")
    ap.add_argument("--allow-config-drift", action="store_true",
                    help="report config differences instead of failing")
    ap.add_argument("--quiet", action="store_true",
                    help="print only non-passing lines + the summary")
    args = ap.parse_args(argv)

    if args.check:
        rec = load_bench(args.check)          # raises on schema violations
        n_gated = sum(m.get("tolerance") is not None
                      for m in rec["metrics"].values())
        print(f"{args.check}: valid {rec['schema']} record "
              f"'{rec['name']}' ({len(rec['metrics'])} metrics, "
              f"{n_gated} gated)")
        return 0

    if not (args.current and args.baseline):
        ap.error("--current and --baseline are required (or use --check)")
    current = load_bench(args.current)
    baseline = load_bench(args.baseline)
    ok, deltas = compare(current, baseline,
                         allow_config_drift=args.allow_config_drift)
    print(f"comparing '{current['name']}' "
          f"{baseline['git_sha'][:12]} -> {current['git_sha'][:12]}")
    print(format_report(deltas, verbose=not args.quiet))
    print("PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
