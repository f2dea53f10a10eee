"""Knee sweep of an open-loop cell: one process, one rate after another.

    python3 bench/sweep.py --workload yolo3-c8.steady --seed 5 \
        --seconds 15 --rates 12,16,20,24

Serves the cell's traffic mix at each offered rate in turn (the mix's own
rate replaced) and prints, per rate, the latency percentiles, the mean
queue wait in the first and last quarter of the window and how long the
drain ran past the window's end. The knee is the highest rate at which the
queue does not grow through the window. Not part of a benchmark run: it
fixes the rate that is then written into the mix's file.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    from benchlib import cell, manifest, stats
    man = manifest.load()
    c = manifest.cell(man, args.workload)
    devices = cell.require_chips(c["chips"])
    cell.enable_compile_cache(HERE / ".jax_cache")
    cfg, mix = manifest.config(man, c), dict(manifest.traffic(c))
    for r in (float(x) for x in args.rates.split(",")):
        mix["rate_rps"] = r
        result, checks, extra = cell.execute(
            man, c, cfg, mix, seed=args.seed, seconds=args.seconds,
            trace_on=False, t_start=time.perf_counter(), devices=devices)
        d = extra["drained"]
        offs = d.due - d.t0
        wait = d.queue_wait_s
        q = args.seconds / 4
        row = {"rate_rps": r, "requests": d.n, "calls": len(d.calls),
               "p50_ms": stats.percentile(d.latency_s, 50) * 1e3,
               "p95_ms": stats.percentile(d.latency_s, 95) * 1e3,
               "wait_first_quarter_ms": float(np.mean(wait[offs < q])) * 1e3,
               "wait_last_quarter_ms":
                   float(np.mean(wait[offs >= 3 * q])) * 1e3,
               "overrun_s": d.last_end - (d.t0 + args.seconds),
               "completed_rps": stats.rate(d.n, d.t0, d.last_end),
               "correct": result["correct"],
               "logits_rel_err": checks["logits_rel_err"]["value"]}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
