"""Readings that the limits of the check are set from.

    python3 bench/calibrate.py --workload yolo3-c8.steady --seconds 50 \
        --seeds 11,12,13

Runs the cell once per seed in one process, as a benchmark run does (the
same window, the same check), and prints per seed the numbers the check
compares, and the control's: the plain reference computed in the
configuration's ``control_dtype`` put in the program's place, on the same
requests, compared by the same rule. Not part of a benchmark run;
``PERF.md`` records what it read and the limits set from it.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from benchlib import cell, manifest
    man = manifest.load()
    c = manifest.cell(man, args.workload)
    devices = cell.require_chips(c["chips"])
    cell.enable_compile_cache(HERE / ".jax_cache")
    cfg, mix = manifest.config(man, c), manifest.traffic(c)
    for seed in (int(s) for s in args.seeds.split(",")):
        result, checks, extra = cell.execute(
            man, c, cfg, mix, seed=seed, seconds=args.seconds,
            trace_on=False, t_start=time.perf_counter(), devices=devices,
            control=True)
        row = {"seed": seed, "correct": result["correct"],
               "attempted": result["attempted"],
               **{k: v["value"] for k, v in checks.items()},
               **{k: v for k, v in extra.items() if k.startswith("control")},
               "metrics": {k: v["value"]
                           for k, v in result["metrics"].items()}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
