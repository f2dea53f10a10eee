"""A CPU rehearsal of whole runs at ``smoke_config()`` size: the window
loop, the check and the result line. The harness's look for a chip is
steered here, in the test; the command itself refuses to run without one.

With the timed path broken underneath (an answer altered where it is
produced, the entropy round trip broken, half of each micro-batch left
out) and with the control in the program's place (the plain reference
computed in bfloat16), ``correct`` must come out false.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchlib import cell, manifest  # noqa: E402

# configs/yolo_baf.py::smoke_config(): width 0.25, 128 px, 8 classes, one
# residual block in the cloud tail; the BaF net narrowed to match
SMOKE = {"width_mult": 0.25, "input_size": 128, "num_classes": 8,
         "tail_res_blocks": 1, "baf_hidden": 16}


def smoke(workload, rate_rps=4.0):
    man = manifest.load()
    c = manifest.cell(man, workload)
    cfg = {**manifest.config(man, c), **SMOKE}
    cfg["c"] = min(cfg["c"], 16)
    mix = {**manifest.traffic(c), "pool": 5}
    if mix["arrival"] == "poisson":
        mix["rate_rps"] = rate_rps
    return man, c, cfg, mix


def run(workload, seconds=2.0, trace_on=False, seed=2**31 + 3,
        rate_rps=4.0):
    import jax
    man, c, cfg, mix = smoke(workload, rate_rps)
    return cell.execute(man, c, cfg, mix, seed=seed, seconds=seconds,
                        trace_on=trace_on, t_start=time.perf_counter(),
                        devices=jax.devices())


def test_main_prints_the_result_line_last(monkeypatch, capsys):
    man, c, cfg, mix = smoke("yolo3-c8.steady")
    monkeypatch.setattr(manifest, "config", lambda m, w: cfg)
    monkeypatch.setattr(manifest, "traffic", lambda w: mix)
    import jax
    monkeypatch.setattr(cell, "require_chips",
                        lambda n: jax.devices()[:n])
    args = SimpleNamespace(workload="yolo3-c8.steady", seed=12345,
                           seconds=2.0, trace=0)
    assert cell.main(args, t_start=time.perf_counter(), cache_dir=None,
                     require=cell.require_chips) == 0
    out, err = capsys.readouterr()
    lines = out.strip().splitlines()
    assert lines[-2].startswith("compiles_in_window=0 ")
    result = json.loads(lines[-1])
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "checks"}
    assert list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 8
    assert set(result["metrics"]) == {"p95_latency_ms", "p50_latency_ms",
                                      "wire_kbit_per_req", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    dev = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert dev["platform"] == "cpu" and dev["count"] == 1
    tail = err.strip().splitlines()[-2:]
    assert tail[0].startswith("check logits_rel_err: ")
    assert tail[1].startswith("check unanswered: 0")


def test_traced_backlog_run_reports_its_layers():
    result, checks, _ = run("yolo3-c128.backlog", trace_on=True)
    assert result["correct"] is True
    assert result["attempted"] % 8 == 0 and result["attempted"] >= 8
    # on the CPU there is no device trace or peak: those readers stay silent
    assert set(result["metrics"]) == {"encode_ms_per_req.backlog",
                                      "decode_ms_per_req.backlog"}
    assert result["device"]["window_s"] > 0
    assert result["breakdown"] == {"device_ops": [], "idle_gaps": []}


@pytest.fixture
def broken_answer(monkeypatch):
    """One answer altered where the gateway produces it."""
    from repro.serve import gateway
    orig = gateway.ServingGateway._response_for

    def altered(self, req, ticket, row, op, stats):
        out = orig(self, req, ticket, row, op, stats)
        if req.req_id == 0:
            out.logits = out.logits.copy()
            out.logits[0] += 0.05 * np.max(np.abs(out.logits))
        return out
    monkeypatch.setattr(gateway.ServingGateway, "_response_for", altered)


@pytest.fixture
def broken_round_trip(monkeypatch):
    """The entropy decode hands back codes one step off in one channel."""
    from repro.pipeline import plan
    orig = plan.CompressionPlan.decode_batch

    def off_by_one(self, blobs):
        out = orig(self, blobs)
        codes = out.codes.copy()
        codes[..., 0] = (codes[..., 0].astype(np.int64) + 1) % (
            1 << self.op.bits)
        return plan.DecodedBatch(codes=codes.astype(out.codes.dtype),
                                 mins=out.mins, maxs=out.maxs)
    monkeypatch.setattr(plan.CompressionPlan, "decode_batch", off_by_one)


@pytest.fixture
def half_batch_left_out(monkeypatch):
    """Each micro-batch runs only its first half through restore and the
    cloud; the rows of the other half get answers of the first half."""
    from repro.serve import gateway
    orig = gateway.ServingGateway._run_batch

    def half(self, batch):
        reqs = batch.requests
        kept = reqs[:(len(reqs) + 1) // 2]
        return orig(self, dataclasses.replace(
            batch, requests=kept + kept[:len(reqs) - len(kept)]))
    monkeypatch.setattr(gateway.ServingGateway, "_run_batch", half)


@pytest.mark.parametrize("fault", ["broken_answer", "broken_round_trip",
                                   "half_batch_left_out"])
@pytest.mark.parametrize("workload", ["yolo3-c8.steady",
                                      "yolo3-c128.backlog"])
def test_a_broken_timed_path_is_not_correct(fault, workload, request):
    request.getfixturevalue(fault)
    # a rate at which requests queue, so that micro-batches hold several
    result, checks, extra = run(workload, rate_rps=40.0)
    assert max(n for n, _ in extra["batches"]) >= 2
    assert result["correct"] is False
    assert checks["logits_rel_err"]["value"] > \
        checks["logits_rel_err"]["limit"]


@pytest.mark.parametrize("workload", ["yolo3-c8.steady",
                                      "yolo3-c128.backlog"])
def test_the_bfloat16_control_is_not_correct(workload, monkeypatch):
    man, c, cfg, mix = smoke(workload)
    system = manifest.model(cfg, "system")
    reference = manifest.model(cfg, "reference")

    class Control:
        """The plain reference in bfloat16, in the program's place."""

        def __init__(self, cfg, weights):
            self.cfg, self.weights = cfg, weights
            self.meter = system.WireMeter()
            self.meter.bytes = 1

        def serve(self, images):
            logits = reference.logits(self.cfg, self.weights, images,
                                      dtype=cfg["control_dtype"])
            return system.Served(logits=logits,
                                 batches=[(len(images), len(images))],
                                 padded=np.full(len(images), len(images)))
    monkeypatch.setattr(system, "System", Control)
    result, checks, _ = run(workload)
    assert result["correct"] is False
    assert checks["logits_rel_err"]["value"] > \
        checks["logits_rel_err"]["limit"]


def test_the_command_refuses_to_run_without_a_chip(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "yolo3-c8.steady", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
