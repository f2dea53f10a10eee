"""Multi-task serving: head registry, per-task distortion, bit allocation,
task negotiation, and the MultiTaskGateway end to end."""
import json
import math

import jax
import numpy as np
import pytest

from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.configs.yolo_baf import smoke_config, smoke_data_config
from repro.core.baf import BaFConvConfig, init_baf_conv
from repro.data.synthetic import shapes_batch_iterator
from repro.models.cnn import init_cnn
from repro.pipeline import (Capabilities, NegotiationError, negotiate,
                            negotiate_tasks)
from repro.serve import (LinearCostModel, OperatingPoint, RDPoint,
                         SerialExecutor, TenantRequest, TenantSpec,
                         load_or_build_rd_table, rd_table_from_json,
                         rd_table_to_json)
from repro.tasks import (BitAllocationController, HeadConfig,
                         MultiTaskGateway, MultiTaskResponse,
                         available_heads, build_task_rd_tables,
                         divergence_to_db, get_head, init_head_bank,
                         load_or_build_task_tables, register_head, run_heads,
                         task_divergences, task_set_key)
from repro.tasks.heads import TaskHead

# ---------------------------------------------------------------------------
# Hand-written allocation tables: four ops, shared wire bits, documented
# per-task quality so every policy branch is checkable by eye
# ---------------------------------------------------------------------------

OP_A = OperatingPoint(c=4, bits=2, backend="rans")     # 1000 bits
OP_B = OperatingPoint(c=4, bits=4, backend="rans")     # 2000 bits
OP_C = OperatingPoint(c=8, bits=4, backend="rans")     # 4000 bits
OP_D = OperatingPoint(c=8, bits=8, backend="rans")     # 8000 bits

_QUAL = {  # task -> quality dB at (A, B, C, D)
    "a": (10.0, 20.0, 30.0, 40.0),
    "b": (5.0, 12.0, 25.0, 35.0),
    "c": (2.0, 8.0, 15.0, 30.0),
}
_BITS = {OP_A: 1000.0, OP_B: 2000.0, OP_C: 4000.0, OP_D: 8000.0}


def _tables(tasks=("a", "b", "c")):
    out = {}
    for t in tasks:
        out[t] = [RDPoint(op, bits_per_example=_BITS[op], psnr_db=q)
                  for op, q in zip((OP_A, OP_B, OP_C, OP_D), _QUAL[t])]
    return out


def test_alloc_picks_cheapest_point_meeting_every_floor():
    ctl = BitAllocationController(_tables(), floors={"a": 18.0, "b": 10.0})
    d = ctl.select(("a", "b"))
    assert d.op == OP_B and d.bits_per_example == 2000.0
    assert d.degraded == ()
    assert d.quality_db("a") == 20.0 and d.quality_db("b") == 12.0


def test_alloc_no_floors_means_cheapest_overall():
    ctl = BitAllocationController(_tables())
    assert ctl.select(("a", "b", "c")).op == OP_A


def test_alloc_declared_subset_never_costs_more():
    ctl = BitAllocationController(
        _tables(), floors={"a": 18.0, "b": 24.0, "c": 7.0})
    full = ctl.select(("a", "b", "c"))          # b's floor forces OP_C
    sub = ctl.select(("a",))                    # a alone is happy at OP_B
    assert full.op == OP_C
    assert sub.bits_per_example <= full.bits_per_example
    assert sub.op == OP_B


def test_alloc_degrades_lowest_weight_first_under_budget_pressure():
    ctl = BitAllocationController(
        _tables(), weights={"a": 3.0, "b": 1.0, "c": 0.5},
        floors={"a": 18.0, "b": 10.0, "c": 28.0})
    # c's floor needs OP_D (8000 bits); budget only admits A/B/C -> c is
    # the lightest task, so it alone is degraded and OP_B still serves a+b
    d = ctl.select(("a", "b", "c"), bit_budget=4000.0)
    assert d.degraded == ("c",)
    assert d.op == OP_B


def test_alloc_best_effort_when_every_floor_relaxed():
    ctl = BitAllocationController(
        _tables(), weights={"a": 3.0, "b": 1.0, "c": 0.5},
        floors={"a": 50.0, "b": 50.0, "c": 50.0})
    d = ctl.select(("a", "b", "c"), bit_budget=4000.0)
    # relaxation order is ascending weight; best-effort picks the fitting
    # point with the highest weighted quality (OP_C here)
    assert d.degraded == ("c", "b", "a")
    assert d.op == OP_C


def test_alloc_nothing_fits_serves_cheapest_never_drops():
    ctl = BitAllocationController(_tables(), floors={"a": 18.0})
    d = ctl.select(("a", "b"), bit_budget=500.0)
    assert d.op == OP_A                        # cheapest overall
    assert "a" in d.degraded                   # floor unmet, recorded


def test_alloc_is_declaration_order_independent():
    ctl = BitAllocationController(_tables(), floors={"a": 18.0, "b": 10.0})
    assert ctl.select(("b", "a")) == ctl.select(("a", "b"))
    assert ctl.select(("a", "a", "b")) == ctl.select(("a", "b"))


def test_alloc_per_task_bits_are_weight_proportional_and_sum():
    ctl = BitAllocationController(_tables(), weights={"a": 3.0, "b": 1.0})
    d = ctl.select(("a", "b"))
    bits = dict(d.per_task_bits)
    assert bits["a"] == pytest.approx(3 * bits["b"])
    assert sum(bits.values()) == pytest.approx(d.bits_per_example)


def test_alloc_independent_streams_cost_at_least_the_shared_stream():
    ctl = BitAllocationController(
        _tables(), floors={"a": 18.0, "b": 10.0, "c": 7.0})
    shared = ctl.select(("a", "b", "c")).bits_per_example
    independent = ctl.independent_bits(("a", "b", "c"))
    assert independent >= shared
    # and here strictly: three floors each need >= OP_B independently
    assert independent > shared


def test_alloc_validation_errors():
    with pytest.raises(ValueError, match="empty task table"):
        BitAllocationController({})
    with pytest.raises(ValueError, match="empty RD table"):
        BitAllocationController({"a": []})
    with pytest.raises(ValueError, match="weight"):
        BitAllocationController(_tables(), weights={"a": 0.0})
    ctl = BitAllocationController(_tables(("a", "b")))
    with pytest.raises(KeyError, match="no RD table"):
        ctl.select(("a", "zz"))
    with pytest.raises(ValueError, match="empty declared"):
        ctl.select(())


@given(data=st.data() if HAVE_HYPOTHESIS else None)
@settings(max_examples=40, deadline=None)
def test_alloc_monotone_in_declared_set_when_no_degradation(data):
    """Fewer declared tasks never cost more bits — the billing property —
    whenever every floor is servable within budget (floors anchored at a
    common op guarantee the non-degraded regime)."""
    names = ("a", "b", "c", "d")
    n_ops = data.draw(st.integers(2, 5), label="n_ops")
    ops = [OperatingPoint(c=8, bits=i + 1, backend="rans")
           for i in range(n_ops)]
    wire = data.draw(st.lists(st.integers(100, 10_000), min_size=n_ops,
                              max_size=n_ops, unique=True), label="wire")
    qual = {t: data.draw(st.lists(st.integers(0, 400), min_size=n_ops,
                                  max_size=n_ops), label=f"q_{t}")
            for t in names}
    tables = {t: [RDPoint(op, bits_per_example=float(w), psnr_db=q / 10.0)
                  for op, w, q in zip(ops, wire, qual[t])]
              for t in names}
    anchor = data.draw(st.integers(0, n_ops - 1), label="anchor")
    floors = {t: qual[t][anchor] / 10.0 - 0.05 for t in names}
    weights = {t: data.draw(st.floats(0.1, 10.0, allow_nan=False),
                            label=f"w_{t}") for t in names}
    ctl = BitAllocationController(tables, weights=weights, floors=floors)
    declared = tuple(data.draw(
        st.lists(st.sampled_from(names), min_size=2, max_size=4,
                 unique=True), label="declared"))
    subset = tuple(data.draw(
        st.lists(st.sampled_from(declared), min_size=1,
                 max_size=len(declared), unique=True), label="subset"))
    full = ctl.select(declared)
    sub = ctl.select(subset)
    assert full.degraded == () and sub.degraded == ()
    assert sub.bits_per_example <= full.bits_per_example


# ---------------------------------------------------------------------------
# Task negotiation (pipeline.negotiate_tasks)
# ---------------------------------------------------------------------------

def test_negotiate_tasks_passthrough_and_dedupe():
    assert negotiate_tasks(("b", "a", "b"), None) == ("b", "a")
    caps = Capabilities()                      # task_heads None = serves all
    assert negotiate_tasks(("x", "y"), caps) == ("x", "y")


def test_negotiate_tasks_drops_unsupported_when_downgrade_allowed():
    caps = Capabilities(task_heads=("classify", "embed"), downgrade=True)
    assert negotiate_tasks(("classify", "detect", "embed"), caps) == \
        ("classify", "embed")


def test_negotiate_tasks_refuses_without_downgrade():
    caps = Capabilities(task_heads=("classify",), downgrade=False)
    with pytest.raises(NegotiationError, match="downgrade is disabled"):
        negotiate_tasks(("classify", "detect"), caps)


def test_negotiate_tasks_refuses_when_nothing_survives():
    caps = Capabilities(task_heads=("classify",), downgrade=True)
    with pytest.raises(NegotiationError, match="none of the declared"):
        negotiate_tasks(("detect", "embed"), caps)


def test_negotiate_tasks_empty_declaration_is_an_error():
    with pytest.raises(ValueError, match="empty task declaration"):
        negotiate_tasks((), None)


def test_foreign_wire_profile_refused_regardless_of_task_subset():
    """Task negotiation never bypasses wire-profile refusal: however few
    heads a tenant declares, a foreign container profile still refuses."""
    caps = Capabilities(profiles=(99,), task_heads=("classify",),
                        downgrade=True)
    assert negotiate_tasks(("classify",), caps) == ("classify",)
    with pytest.raises(NegotiationError, match="wire profile"):
        negotiate(OperatingPoint(c=8, bits=4, backend="rans"), caps)


@given(declared=(st.lists(st.sampled_from(("w", "x", "y", "z")), min_size=1,
                          max_size=4, unique=True)
                 if HAVE_HYPOTHESIS else None),
       served=(st.lists(st.sampled_from(("w", "x", "y", "z")), min_size=0,
                        max_size=4, unique=True)
               if HAVE_HYPOTHESIS else None))
@settings(max_examples=60, deadline=None)
def test_negotiate_tasks_result_is_served_subsequence_or_refusal(declared,
                                                                 served):
    caps = Capabilities(task_heads=tuple(served), downgrade=True)
    try:
        out = negotiate_tasks(tuple(declared), caps)
    except NegotiationError:
        assert not (set(declared) & set(served))
        return
    assert out == tuple(t for t in declared if t in served)
    assert set(out) <= set(served)


# ---------------------------------------------------------------------------
# Head registry + forwards (tiny real system)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_task_system():
    cnn_cfg = smoke_config()._replace(input_size=32)
    data_cfg = smoke_data_config()._replace(image_size=32, batch_size=8)
    params = init_cnn(jax.random.PRNGKey(0), cnn_cfg)
    bank = {c: (init_baf_conv(jax.random.PRNGKey(c),
                              BaFConvConfig(c=c, q=cnn_cfg.split_q,
                                            hidden=8)),
                np.arange(c)) for c in (4, 8)}
    imgs, _ = next(shapes_batch_iterator(data_cfg, seed=5))
    head_cfg = HeadConfig(split_p=cnn_cfg.split_p,
                          num_classes=cnn_cfg.num_classes)
    head_bank = init_head_bank(jax.random.PRNGKey(99), head_cfg)
    from repro.models.cnn import cnn_edge
    z = jax.jit(lambda p, i: cnn_edge(p, i)[1])(params, np.asarray(imgs))
    return params, bank, np.asarray(imgs), head_cfg, head_bank, np.asarray(z)


def test_registry_serves_the_three_builtin_heads():
    assert set(available_heads()) >= {"classify", "detect", "embed"}
    with pytest.raises(KeyError, match="registered"):
        get_head("nope")
    with pytest.raises(ValueError, match="already registered"):
        register_head(TaskHead(name="classify", init=None, forward=None,
                               divergence=None))


def test_head_config_validates_head_dim():
    with pytest.raises(ValueError, match="not divisible"):
        _ = HeadConfig(split_p=64, d_model=30, n_heads=4).head_dim


def test_head_output_shapes_and_determinism(tiny_task_system):
    params, _, _, head_cfg, head_bank, z = tiny_task_system
    out = run_heads(params, head_bank, z, available_heads(), head_cfg)
    b, h, w, _ = z.shape
    assert out["classify"].shape == (b, head_cfg.num_classes)
    assert out["detect"].shape == (b, h, w,
                                   head_cfg.box_fields + head_cfg.num_classes)
    assert out["embed"].shape == (b, head_cfg.embed_dim)
    # embeddings are L2-normalized rows
    assert np.allclose(np.linalg.norm(out["embed"], axis=-1), 1.0, atol=1e-4)
    again = run_heads(params, head_bank, z, available_heads(), head_cfg)
    for t in out:
        assert np.array_equal(out[t], again[t])


def test_head_divergence_zero_on_identical_outputs(tiny_task_system):
    params, _, _, head_cfg, head_bank, z = tiny_task_system
    out = run_heads(params, head_bank, z, available_heads(), head_cfg)
    for t, y in out.items():
        assert get_head(t).divergence(y, y) == pytest.approx(0.0, abs=1e-9)


# ---------------------------------------------------------------------------
# Per-task distortion tables
# ---------------------------------------------------------------------------

def test_divergence_to_db_monotone_and_capped():
    assert divergence_to_db(0.1) < divergence_to_db(0.01)
    assert divergence_to_db(0.0) == divergence_to_db(1e-30) == 120.0


def test_task_divergences_intersects_task_sets(tiny_task_system):
    params, _, _, head_cfg, head_bank, z = tiny_task_system
    ref = run_heads(params, head_bank, z, ("classify", "embed"), head_cfg)
    out = run_heads(params, head_bank, z, ("classify",), head_cfg)
    d = task_divergences(ref, out)
    assert set(d) == {"classify"}
    assert d["classify"] == pytest.approx(0.0, abs=1e-9)


def test_build_task_rd_tables_shares_bits_across_tasks(tiny_task_system):
    params, bank, imgs, head_cfg, head_bank, _ = tiny_task_system
    ops = [OperatingPoint(c=4, bits=4, backend="rans"),
           OperatingPoint(c=8, bits=8, backend="rans")]
    tables = build_task_rd_tables(params, bank, imgs[:4],
                                  head_bank=head_bank, head_cfg=head_cfg,
                                  ops=ops)
    assert set(tables) == set(head_bank)
    for t, pts in tables.items():
        assert [p.op for p in pts] == ops
        assert all(math.isfinite(p.psnr_db) for p in pts)
        assert all(p.kl >= 0.0 for p in pts)
    # one shared stream: wire bits identical across tasks at each op
    for i in range(len(ops)):
        bits = {t: tables[t][i].bits_per_example for t in tables}
        assert len(set(bits.values())) == 1
        assert min(bits.values()) > 0


def test_build_task_rd_tables_rejects_op_outside_bank(tiny_task_system):
    params, bank, imgs, head_cfg, head_bank, _ = tiny_task_system
    with pytest.raises(ValueError, match="bank"):
        build_task_rd_tables(params, bank, imgs[:2], head_bank=head_bank,
                             head_cfg=head_cfg,
                             ops=[OperatingPoint(c=16, bits=4,
                                                 backend="rans")])


# ---------------------------------------------------------------------------
# Disk caches: task identity must be part of the key (the staleness fix)
# ---------------------------------------------------------------------------

def _counting_build(table):
    calls = {"n": 0}

    def build():
        calls["n"] += 1
        return table
    return calls, build


def test_task_table_cache_hits_only_on_identical_task_identity(tmp_path):
    path = tmp_path / "cache.json"
    ops = [OP_A, OP_B]
    tables = {t: pts[:2] for t, pts in _tables(("a", "b")).items()}
    key = task_set_key(("a", "b"), {"a": 2.0})
    calls, build = _counting_build(tables)

    first = load_or_build_task_tables(path, {"seed": 1}, build,
                                      ops=ops, tasks=key)
    assert calls["n"] == 1
    again = load_or_build_task_tables(path, {"seed": 1}, build,
                                      ops=ops, tasks=key)
    assert calls["n"] == 1                      # cache hit
    for t in tables:                            # NaN fields defeat ==
        for x, y, z in zip(again[t], first[t], tables[t]):
            assert (x.op, x.bits_per_example, x.psnr_db) == \
                (y.op, y.bits_per_example, y.psnr_db) == \
                (z.op, z.bits_per_example, z.psnr_db)

    # different weight vector -> stale -> rebuild
    load_or_build_task_tables(path, {"seed": 1}, build, ops=ops,
                              tasks=task_set_key(("a", "b"), {"a": 3.0}))
    assert calls["n"] == 2
    # different head set -> stale -> rebuild
    load_or_build_task_tables(path, {"seed": 1}, build, ops=ops,
                              tasks=task_set_key(("a",)))
    assert calls["n"] == 3
    # corrupt file -> rebuild, never crash
    path.write_text("{not json")
    load_or_build_task_tables(path, {"seed": 1}, build, ops=ops, tasks=key)
    assert calls["n"] == 4


def test_rd_table_cache_distinguishes_task_aware_sweeps(tmp_path):
    """The staleness fix on the *existing* single-table cache: a cache
    written without task identity must rebuild for a task-aware caller,
    and vice versa."""
    path = tmp_path / "rd.json"
    table = _tables(("a",))["a"]
    ops = [OP_A, OP_B, OP_C, OP_D]
    calls, build = _counting_build(table)

    load_or_build_rd_table(path, {"seed": 1}, build, ops=ops)
    assert calls["n"] == 1
    load_or_build_rd_table(path, {"seed": 1}, build, ops=ops)
    assert calls["n"] == 1                      # plain caller hits
    tkey = task_set_key(("classify", "detect"), {"detect": 3.0})
    load_or_build_rd_table(path, {"seed": 1}, build, ops=ops, tasks=tkey)
    assert calls["n"] == 2                      # task-aware caller rebuilds
    load_or_build_rd_table(path, {"seed": 1}, build, ops=ops, tasks=tkey)
    assert calls["n"] == 2                      # then hits
    load_or_build_rd_table(path, {"seed": 1}, build, ops=ops,
                           tasks=task_set_key(("classify",)))
    assert calls["n"] == 3                      # different head set rebuilds
    load_or_build_rd_table(path, {"seed": 1}, build, ops=ops)
    assert calls["n"] == 4                      # plain caller is stale again


def test_rd_point_p_over_i_round_trips_and_legacy_rows_parse():
    table = [RDPoint(OP_A, bits_per_example=1000.0, psnr_db=20.0,
                     p_over_i=0.25),
             RDPoint(OP_B, bits_per_example=2000.0, psnr_db=25.0)]
    back = rd_table_from_json(rd_table_to_json(table))
    assert back[0].p_over_i == 0.25
    assert math.isnan(back[1].p_over_i)
    legacy = rd_table_to_json(table)
    for row in legacy:
        row.pop("p_over_i", None)               # pre-p_over_i cache rows
    old = rd_table_from_json(legacy)
    assert all(math.isnan(p.p_over_i) for p in old)


# ---------------------------------------------------------------------------
# MultiTaskGateway end to end
# ---------------------------------------------------------------------------

OP_LO = OperatingPoint(c=4, bits=2, backend="rans")
OP_HI = OperatingPoint(c=8, bits=6, backend="rans")

# hand-written allocation tables over REAL ops: classify alone is happy at
# the cheap point, detect's floor forces the expensive one
GW_TABLES = {
    "classify": [RDPoint(OP_LO, 1000.0, 20.0), RDPoint(OP_HI, 4000.0, 30.0)],
    "detect":   [RDPoint(OP_LO, 1000.0, 8.0),  RDPoint(OP_HI, 4000.0, 25.0)],
    "embed":    [RDPoint(OP_LO, 1000.0, 15.0), RDPoint(OP_HI, 4000.0, 28.0)],
}
GW_FLOORS = {"classify": 15.0, "detect": 20.0, "embed": 10.0}


def _task_gateway(parts, *, tenants, allocator="default", **kw):
    params, bank, _, head_cfg, head_bank, _ = parts
    if allocator == "default":
        allocator = BitAllocationController(GW_TABLES, floors=GW_FLOORS)
    kw.setdefault("executor",
                  SerialExecutor(cost=LinearCostModel(0.004, 0.001)))
    kw.setdefault("max_batch", 4)
    kw.setdefault("batch_window_s", 0.01)
    return MultiTaskGateway(params, bank, tenants=tenants,
                            head_bank=head_bank, head_cfg=head_cfg,
                            allocator=allocator, **kw)


def _mixed_workload(imgs, n=8):
    return [TenantRequest(("full", "lite")[i % 2], imgs[i % len(imgs)],
                          t_submit=0.001 * i) for i in range(n)]


@pytest.fixture(scope="module")
def task_gateway_run(tiny_task_system):
    gw = _task_gateway(tiny_task_system, tenants=[
        TenantSpec("full"),                     # undeclared -> all heads
        TenantSpec("lite", tasks=("classify",))])
    work = _mixed_workload(tiny_task_system[2])
    responses, tel = gw.serve_tenants(work)
    return gw, work, responses, tel


def test_gateway_fans_out_declared_task_sets(task_gateway_run):
    gw, _, responses, _ = task_gateway_run
    assert gw.task_sets["full"] == ("classify", "detect", "embed")
    assert gw.task_sets["lite"] == ("classify",)
    for r in responses["full"]:
        assert isinstance(r, MultiTaskResponse)
        assert set(r.outputs) == {"classify", "detect", "embed"}
        assert all(np.isfinite(v).all() for v in r.outputs.values())
        assert np.array_equal(r.logits, r.outputs["classify"])
    for r in responses["lite"]:
        assert set(r.outputs) == {"classify"}
        assert r.op == OP_LO
    for r in responses["full"]:
        assert r.op == OP_HI


def test_gateway_runs_each_head_once_per_decoded_batch(task_gateway_run):
    gw, work, _, _ = task_gateway_run
    assert gw.decode_calls >= 1
    assert set(gw.head_calls) == {"classify", "detect", "embed"}
    # one decode + one restore per batch serves every subscribed head: no
    # head ever runs more often than the batches themselves
    for t, n in gw.head_calls.items():
        assert 1 <= n <= gw.decode_calls
    assert gw.head_calls["classify"] == gw.decode_calls


def test_gateway_declared_subset_tenant_pays_fewer_bits(task_gateway_run):
    _, _, _, tel = task_gateway_run
    per = tel.per_tenant()
    assert per["full"]["count"] == per["lite"]["count"] == 4
    assert per["lite"]["bits_on_wire"] < per["full"]["bits_on_wire"]


def test_gateway_mixed_population_replays_bit_identically(tiny_task_system):
    outs = []
    for _ in range(2):
        gw = _task_gateway(tiny_task_system, tenants=[
            TenantSpec("full"),
            TenantSpec("lite", tasks=("classify",))])
        responses, tel = gw.serve_tenants(
            _mixed_workload(tiny_task_system[2]))
        outs.append((responses, tel.per_tenant()))
    (r1, t1), (r2, t2) = outs
    assert t1 == t2
    for tenant in r1:
        for a, b in zip(r1[tenant], r2[tenant]):
            assert a.tasks == b.tasks and set(a.outputs) == set(b.outputs)
            for task in a.outputs:
                assert np.array_equal(a.outputs[task], b.outputs[task])


def test_gateway_negotiates_task_sets_at_construction(tiny_task_system):
    caps = Capabilities(task_heads=("classify", "embed"), downgrade=True)
    gw = _task_gateway(
        tiny_task_system, capabilities=caps,
        tenants=[TenantSpec("t", tasks=("classify", "detect"))])
    assert gw.task_sets["t"] == ("classify",)   # detect dropped up front
    with pytest.raises(NegotiationError):
        _task_gateway(
            tiny_task_system,
            capabilities=Capabilities(task_heads=("classify",),
                                      downgrade=False),
            tenants=[TenantSpec("t", tasks=("classify", "detect"))])
    with pytest.raises(ValueError, match="no head in the bank"):
        _task_gateway(tiny_task_system,
                      tenants=[TenantSpec("t", tasks=("nope",))])


def test_gateway_requires_allocator_tables_for_every_head(tiny_task_system):
    partial = {t: pts for t, pts in GW_TABLES.items() if t != "embed"}
    with pytest.raises(ValueError, match="no RD table"):
        _task_gateway(
            tiny_task_system, tenants=[TenantSpec("t")],
            allocator=BitAllocationController(partial))


def test_gateway_without_allocator_still_bounds_outputs(tiny_task_system):
    gw = _task_gateway(tiny_task_system, allocator=None,
                       default_op=OP_LO,
                       tenants=[TenantSpec("lite", tasks=("classify",))])
    responses, _ = gw.serve_tenants(
        [TenantRequest("lite", tiny_task_system[2][0])])
    assert set(responses["lite"][0].outputs) == {"classify"}
    assert responses["lite"][0].op == OP_LO


def test_gateway_single_tenant_serve_returns_full_fanout(tiny_task_system):
    gw = _task_gateway(tiny_task_system, default_op=OP_HI,
                       tenants=[TenantSpec("t")])
    responses, _ = gw.serve(tiny_task_system[2][:4])
    assert [r.req_id for r in responses] == [0, 1, 2, 3]
    for r in responses:
        assert isinstance(r, MultiTaskResponse)
        assert set(r.outputs) == {"classify", "detect", "embed"}


def test_gateway_counts_task_requests_in_metrics(task_gateway_run):
    _, _, _, tel = task_gateway_run
    counts = {}
    for name, labels, metric in tel.metrics.collect():
        if name == "task_requests_total":
            counts[(labels["tenant"], labels["task"])] = metric.value
    assert counts[("full", "detect")] == 4
    assert counts[("lite", "classify")] == 4
    assert ("lite", "detect") not in counts
