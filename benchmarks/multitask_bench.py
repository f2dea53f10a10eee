"""Multi-task serving benchmark: one encoded stream, N downstream heads.

    PYTHONPATH=src python benchmarks/multitask_bench.py [--smoke]

Part 1 sweeps per-task RD tables (tasks/distortion.py): every operating
point is encoded/decoded/restored once and every registered head prices it
by its own output divergence. The sweep is disk-cached
(rd_cache_tasks_seed5.json, keyed on the ops grid + codec revision + head
set + weight vector) so CI reruns are cheap.

Part 2 (the headline gate) compares ONE shared stream against per-task
independent streams at matched per-task distortion: floors are anchored at
a common operating point (quality at the anchor minus a margin), so the
shared selection meets every floor without degradation, and every
independent single-task selection meets the same floor. Gates:

  * >= 3 heads served from the single stream, no floor degraded,
  * independent-streams total wire bits >= 1.5x the shared stream's.

Part 3 drives the MultiTaskGateway end to end with a mixed tenant
population (one full-set tenant, one classify-only tenant) on a
deterministic LinearCostModel. Gates:

  * single-decode fan-out: no head runs more often than batches are
    decoded, and all >= 3 heads are served,
  * the declared-subset tenant pays measurably fewer wire bits than the
    full-stream tenant at equal request counts (<= 0.8x),
  * a second run of the same workload replays bit-identically.

Prints ``name,us_per_call,derived`` CSV rows like the other benchmarks and
writes a schema'd BENCH_multitask.json (repro.obs.bench) for compare.py.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.configs.yolo_baf import smoke_config, smoke_data_config
from repro.core.baf import BaFConvConfig, init_baf_conv
from repro.data.synthetic import shapes_batch_iterator
from repro.launch.compile_cache import enable_compile_cache
from repro.models.cnn import init_cnn
from repro.obs.bench import bench_record, metric, write_bench
from repro.pipeline import OperatingPoint
from repro.serve import LinearCostModel, SerialExecutor, TenantRequest, TenantSpec
from repro.tasks import (BitAllocationController, HeadConfig,
                         MultiTaskGateway, build_task_rd_tables,
                         init_head_bank, load_or_build_task_tables,
                         task_set_key)

SIZE = 32
CALIB_N = 4
SEED = 5
OPS = tuple(OperatingPoint(c=c, bits=b, backend="rans")
            for c in (4, 8) for b in (2, 4, 6, 8))
# weight = how much a tenant cares; detect is the premium task, embed is
# best-effort — also the degrade order under pressure (lowest first)
WEIGHTS = {"classify": 1.0, "detect": 3.0, "embed": 0.5}
# floors anchor: every task's floor is its measured quality at this op
# minus a margin, so the anchor op provably meets every floor and the
# shared-vs-independent comparison runs in the non-degraded regime
ANCHOR = OperatingPoint(c=8, bits=6, backend="rans")
FLOOR_MARGIN_DB = 0.5

_ROWS: list[str] = []


def _row(name: str, us: float, derived: str):
    line = f"{name},{us:.1f},{derived}"
    _ROWS.append(line)
    print(line, flush=True)


def build_system():
    cnn_cfg = smoke_config()._replace(input_size=SIZE)
    data_cfg = smoke_data_config()._replace(image_size=SIZE,
                                            batch_size=max(CALIB_N, 8))
    params = init_cnn(jax.random.PRNGKey(0), cnn_cfg)
    bank = {c: (init_baf_conv(jax.random.PRNGKey(c),
                              BaFConvConfig(c=c, q=cnn_cfg.split_q,
                                            hidden=8)),
                np.arange(c)) for c in (4, 8)}
    imgs, _ = next(shapes_batch_iterator(data_cfg, seed=SEED))
    head_cfg = HeadConfig(split_p=cnn_cfg.split_p,
                          num_classes=cnn_cfg.num_classes)
    head_bank = init_head_bank(jax.random.PRNGKey(99), head_cfg)
    return params, bank, np.asarray(imgs), head_cfg, head_bank


# ---------------------------------------------------------------------------
# Part 1: per-task RD tables (cached sweep)
# ---------------------------------------------------------------------------

def sweep_tables(params, bank, imgs, head_cfg, head_bank) -> dict:
    cache = os.path.join(os.path.dirname(__file__),
                         f"rd_cache_tasks_seed{SEED}.json")
    t0 = time.perf_counter()
    tables = load_or_build_task_tables(
        cache,
        {"seed": SEED, "image_size": SIZE, "n_calib": CALIB_N,
         "head_seed": 99, "anchor": str(ANCHOR)},
        lambda: build_task_rd_tables(params, bank, imgs[:CALIB_N],
                                     head_bank=head_bank, head_cfg=head_cfg,
                                     ops=OPS),
        ops=OPS, tasks=task_set_key(head_bank, WEIGHTS))
    wall = time.perf_counter() - t0
    _row("multitask_tables", 1e6 * wall / (len(OPS) * len(tables)),
         f"tasks={sorted(tables)} ops={len(OPS)} wall={wall:.2f}s")
    return tables


def anchored_floors(tables: dict) -> dict:
    floors = {}
    for task, pts in tables.items():
        at = next(p for p in pts if p.op == ANCHOR)
        floors[task] = at.psnr_db - FLOOR_MARGIN_DB
    return floors


# ---------------------------------------------------------------------------
# Part 2: shared stream vs independent per-task streams
# ---------------------------------------------------------------------------

def bench_shared_vs_independent(alloc: BitAllocationController) -> dict:
    tasks = alloc.tasks
    shared = alloc.select(tasks)
    independent = alloc.independent_bits(tasks)
    ratio = independent / shared.bits_per_example
    _row("multitask_allocation", 0.0,
         f"heads={len(tasks)} shared_bits={shared.bits_per_example:.0f} "
         f"independent_bits={independent:.0f} ratio={ratio:.2f}x "
         f"op={shared.op.c}c{shared.op.bits}b degraded={shared.degraded}")
    assert len(tasks) >= 3, (
        f"ACCEPTANCE FAIL: only {len(tasks)} heads priced, need >= 3")
    assert shared.degraded == (), (
        f"ACCEPTANCE FAIL: anchored floors must not degrade, got "
        f"{shared.degraded}")
    for task in tasks:                  # matched per-task distortion
        assert shared.quality_db(task) >= alloc.floor(task), task
    assert ratio >= 1.5, (
        f"ACCEPTANCE FAIL: independent streams only {ratio:.2f}x the shared "
        f"stream's bits, below the 1.5x gate")
    return {"heads": list(tasks),
            "shared_bits_per_example": shared.bits_per_example,
            "independent_bits_total": independent,
            "independent_over_shared": ratio,
            "shared_op": f"c{shared.op.c}_b{shared.op.bits}",
            "per_task_quality_db": dict(shared.per_task_quality_db),
            "floors_db": {t: alloc.floor(t) for t in tasks}}


# ---------------------------------------------------------------------------
# Part 3: gateway fan-out, subset billing, replay
# ---------------------------------------------------------------------------

def bench_gateway_fanout(params, bank, imgs, head_cfg, head_bank,
                         alloc: BitAllocationController,
                         *, n_requests: int) -> dict:
    def run():
        gw = MultiTaskGateway(
            params, bank,
            tenants=[TenantSpec("full"),
                     TenantSpec("lite", tasks=("classify",))],
            head_bank=head_bank, head_cfg=head_cfg, allocator=alloc,
            executor=SerialExecutor(cost=LinearCostModel(0.004, 0.001)),
            max_batch=4, batch_window_s=0.01)
        work = [TenantRequest(("full", "lite")[i % 2], imgs[i % len(imgs)],
                              t_submit=0.002 * i) for i in range(n_requests)]
        t0 = time.perf_counter()
        responses, tel = gw.serve_tenants(work)
        return gw, responses, tel, time.perf_counter() - t0

    gw, responses, tel, wall = run()
    per = tel.per_tenant()
    subset_fraction = (per["lite"]["bits_on_wire"]
                       / per["full"]["bits_on_wire"])
    heads_served = sorted(gw.head_calls)
    max_head_over_decode = max(gw.head_calls.values()) / gw.decode_calls
    _row("multitask_gateway", 1e6 * wall / n_requests,
         f"requests={n_requests} decodes={gw.decode_calls} "
         f"head_calls={gw.head_calls} subset_bits={subset_fraction:.2f}x")
    assert len(heads_served) >= 3, (
        f"ACCEPTANCE FAIL: only heads {heads_served} served")
    assert max_head_over_decode <= 1.0, (
        f"ACCEPTANCE FAIL: a head ran {max_head_over_decode:.2f}x per "
        f"decoded batch — single-decode fan-out violated")
    assert per["full"]["count"] == per["lite"]["count"]
    assert subset_fraction <= 0.8, (
        f"ACCEPTANCE FAIL: classify-only tenant pays {subset_fraction:.2f}x "
        f"of the full tenant's wire bits, above the 0.8x gate")

    gw2, responses2, tel2, _ = run()
    replay_ok = tel2.per_tenant() == per
    for tenant in responses:
        for a, b in zip(responses[tenant], responses2[tenant]):
            replay_ok &= a.tasks == b.tasks and all(
                np.array_equal(a.outputs[t], b.outputs[t])
                for t in a.outputs)
    _row("multitask_replay", 0.0, f"replay={replay_ok}")
    assert replay_ok, "ACCEPTANCE FAIL: multi-task replay diverged"
    return {"requests": n_requests, "decode_calls": gw.decode_calls,
            "head_calls": dict(sorted(gw.head_calls.items())),
            "heads_served": heads_served,
            "subset_bits_fraction": subset_fraction,
            "full_bits_on_wire": per["full"]["bits_on_wire"],
            "lite_bits_on_wire": per["lite"]["bits_on_wire"],
            "replay_bit_identical": replay_ok, "wall_s": wall}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (< 60 s)")
    args = ap.parse_args()
    enable_compile_cache()
    n_requests = 16 if args.smoke else 48

    params, bank, imgs, head_cfg, head_bank = build_system()
    tables = sweep_tables(params, bank, imgs, head_cfg, head_bank)
    alloc = BitAllocationController(tables, weights=WEIGHTS,
                                    floors=anchored_floors(tables))
    shared = bench_shared_vs_independent(alloc)
    fanout = bench_gateway_fanout(params, bank, imgs, head_cfg, head_bank,
                                  alloc, n_requests=n_requests)

    rec = bench_record(
        "multitask",
        config={"smoke": bool(args.smoke), "image_size": SIZE,
                "n_calib": CALIB_N, "seed": SEED, "ops": len(OPS),
                "weights": WEIGHTS, "anchor": str(ANCHOR),
                "floor_margin_db": FLOOR_MARGIN_DB,
                "n_requests": n_requests},
        metrics={
            # deterministic: seeded data, virtual-clock gateway, cached
            # (or deterministically rebuilt) RD sweep
            "independent_over_shared_bits": metric(
                shared["independent_over_shared"], better="higher",
                tolerance=0.05),
            "shared_bits_per_example": metric(
                shared["shared_bits_per_example"], better="lower",
                tolerance=0.05),
            "subset_bits_fraction": metric(
                fanout["subset_bits_fraction"], better="lower",
                tolerance=0.05),
            "heads_per_decode": metric(
                sum(fanout["head_calls"].values())
                / fanout["decode_calls"], better="higher", tolerance=0.1),
            # wall time is runner-dependent: informational only
            "gateway_wall_s": metric(fanout["wall_s"], better="lower",
                                     tolerance=None),
        },
        raw={"shared_vs_independent": shared, "gateway": fanout})
    out = os.path.join(os.path.dirname(__file__), "BENCH_multitask.json")
    write_bench(out, rec)
    print(f"wrote {out}")
    print("multitask gates OK")


if __name__ == "__main__":
    main()
