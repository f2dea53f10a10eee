"""Finds every piece of a cell by its name in ``BENCHMARK.json``.

    configuration  bench/configs/<file named in the manifest>.json
    model code     bench/models/<cfg["model"]>/{inputs,reference,system}.py
    traffic mix    bench/traffic/<traffic>.json
    metric reader  bench/metrics/<name>.py, or <stem>.py for a name
                   <stem>.<qualifier> (one reader, metrics split by the
                   end-to-end metric they move)

Adding a configuration, traffic mix or metric is adding its file and its
manifest entry; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                     f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def config(manifest: dict, cell_entry: dict, root: Path = ROOT) -> dict:
    entry = config_entry(manifest, cell_entry["config"])
    return json.loads((root / entry["file"]).read_text())


def traffic(cell_entry: dict, bench: Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{cell_entry['traffic']}.json")
                      .read_text())


def metrics_for(manifest: dict, cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    that list it under ``workloads``, and those with no such key."""
    return [m for m in manifest[kind]
            if cell_name in m.get("workloads", [cell_name])]


def _load_module(path: Path, name: str):
    """Import the file at ``path`` once per process, as module ``name``."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def reader_path(metric: str, bench: Path = BENCH) -> Path:
    own = bench / "metrics" / f"{metric}.py"
    if own.is_file():
        return own
    return bench / "metrics" / f"{metric.split('.', 1)[0]}.py"


def reader_module(metric: str, bench: Path = BENCH):
    path = reader_path(metric, bench)
    return _load_module(path, "bench_metric_" + path.stem.replace(".", "_"))


def reader(metric: str, bench: Path = BENCH):
    """The ``read(run) -> float | None`` function of one metric."""
    return reader_module(metric, bench).read


def model(cfg: dict, part: str, bench: Path = BENCH):
    """One module of the configuration's model: ``inputs`` (seeded weights
    and images), ``reference`` (plain jnp, imports nothing of the program)
    or ``system`` (the program under test, built from those weights)."""
    path = bench / "models" / cfg["model"] / f"{part}.py"
    return _load_module(path, f"bench_model_{cfg['model']}_{part}")
