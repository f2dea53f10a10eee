"""``BENCHMARK.json`` against its contract, and every piece it names found
by its name: configurations, traffic mixes, model code, metric readers."""
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import pytest  # noqa: E402

from benchlib import manifest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
MAN = manifest.load(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MAN["workloads"]]


def test_top_level_keys_and_paths():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(MAN["paths"]) <= 16 and len(MAN["command"]) <= 32
    for p in MAN["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
    for word in MAN["command"][1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in MAN["paths"])
            assert (ROOT / word).is_file()
    assert 1 <= MAN["run_seconds"] <= 51


def test_a_full_check_fits_its_budget_at_24_cells():
    runs = 2 + 14 * 24
    spent = runs * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert spent <= 43200


def test_entries_have_exactly_their_keys_and_valid_names():
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    for kind in ("end_to_end", "per_layer"):
        for m in MAN[kind]:
            extra = set(m) - {"workloads"}
            want = ({"name", "unit", "better", "bound", "source"}
                    if kind == "end_to_end" else
                    {"name", "unit", "better", "source", "layer", "moves"})
            assert extra == want, m["name"]
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
    names = [m["name"] for k in ("end_to_end", "per_layer") for m in MAN[k]]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))


def test_end_to_end_bounds_and_sources():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for cell in CELLS:
        e2e = [m["name"] for m in manifest.metrics_for(MAN, cell,
                                                       "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert manifest.metrics_for(MAN, cell, "per_layer"), cell


def test_each_layer_metric_moves_what_its_cells_report():
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            e2e = [e["name"] for e in manifest.metrics_for(MAN, cell,
                                                           "end_to_end")]
            assert m["moves"] in e2e, (m["name"], cell)


def test_every_named_file_exists_under_paths():
    bench = ROOT / MAN["paths"][0]
    used = {w["config"] for w in MAN["workloads"]}
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    for c in MAN["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(MAN["paths"][0] + "/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        for key in c["reduced"]:
            assert key in cfg["reduced"] and key in cfg
        for part in ("inputs", "reference", "system", "flops"):
            assert (bench / "models" / cfg["model"] / f"{part}.py").is_file()
    for w in MAN["workloads"]:
        assert (bench / "traffic" / f"{w['traffic']}.json").is_file()
    for kind in ("end_to_end", "per_layer"):
        for m in MAN[kind]:
            assert callable(manifest.reader(m["name"], bench)), m["name"]


def test_the_reference_imports_nothing_of_the_program():
    for c in MAN["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        for part in ("reference", "inputs", "flops"):
            src = (ROOT / MAN["paths"][0] / "models" / cfg["model"]
                   / f"{part}.py").read_text()
            assert "repro" not in re.findall(r"^\s*(?:from|import)\s+(\w+)",
                                             src, re.M)


def test_a_new_metric_file_is_found_by_name(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "fresh_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    assert manifest.reader("fresh_metric", tmp_path)(None) == 42.0
    # a qualified name falls back to its stem's reader
    assert manifest.reader("fresh_metric.steady", tmp_path)(None) == 42.0
    # an own file for the qualified name wins
    (tmp_path / "metrics" / "fresh_metric.backlog.py").write_text(
        "def read(run):\n    return 7.0\n")
    assert manifest.reader("fresh_metric.backlog", tmp_path)(None) == 7.0
    with pytest.raises(FileNotFoundError):
        manifest.reader("absent_metric", tmp_path)


def test_a_new_mix_and_configuration_are_found_by_name(tmp_path):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "bursty.json").write_text('{"pool": 3}')
    assert manifest.traffic({"traffic": "bursty"}, tmp_path) == {"pool": 3}
    man = {"configs": [{"name": "x", "file": "cfg/x.json"}]}
    (tmp_path / "cfg").mkdir()
    (tmp_path / "cfg" / "x.json").write_text('{"name": "x"}')
    assert manifest.config(man, {"config": "x"}, tmp_path) == {"name": "x"}
