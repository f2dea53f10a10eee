"""Chip benchmark of the BaF split-inference serving path.

    python3 bench/run.py --workload yolo3-c8.steady --seed 7 --seconds 20 \
        --trace 0

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: set-up,
a measured window of ``--seconds``, then the check against the plain
reference. The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` the per-layer metrics and ``breakdown``); the last lines of
standard error give each number compared beside its limit. Exits 2 without
a TPU or with fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

# JAX's persistent compile cache: one fixed path inside the checkout
CACHE_DIR = HERE / ".jax_cache"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from benchlib import cell
    return cell.main(args, t_start=T_START, cache_dir=CACHE_DIR)


if __name__ == "__main__":
    sys.exit(main())
