import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
                                         "--xla_force_host_platform_device_count=512")
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on the
production meshes and report memory/cost analysis.

  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells, both meshes
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod-only --json out.json

A cell "passes" when jit(...).lower(...).compile() succeeds under the mesh —
i.e. every collective the sharding implies is supported and the per-device
memory analysis is available. Output feeds EXPERIMENTS.md §Dry-run and the
roofline benchmarks (benchmarks/roofline.py re-uses lower_cell)."""
import argparse
import json
import re
import sys
import time
import traceback

import jax

from repro.compat import set_mesh

from repro.configs import ARCH_IDS, canonical
from repro.configs.base import SHAPES


def lower_cell(arch: str, shape: str, *, multi_pod: bool, smoke: bool = False,
               tcfg_overrides=None, overrides=None):
    """Returns (lowered, compiled, meta dict)."""
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = build_cell(arch, shape, mesh, multi_pod=multi_pod, smoke=smoke,
                      tcfg_overrides=tcfg_overrides, overrides=overrides)
    with set_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
    return lowered, compiled, {"kind": cell.kind, "mesh": mesh.shape}


_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")


_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<shape>[^=]*?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<suffix>-start|-done)?\(")


def collective_bytes(compiled) -> dict:
    """Sum result bytes of every collective in the compiled (SPMD-partitioned)
    HLO, by op kind. Async pairs (-start/-done) are counted once (the -start).
    Parses compiled.as_text()."""
    out: dict[str, float] = {}
    for line in compiled.as_text().splitlines():
        m = _OP_RE.match(line)
        if not m or m.group("suffix") == "-done":
            continue
        nbytes = _shape_bytes(m.group("shape"))
        if nbytes:
            op = m.group("op")
            out[op] = out.get(op, 0.0) + nbytes
    return out


_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "s64": 8,
                "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred|f8e4m3fn|f8e5m2)\[([\d,]*)\]")


def _shape_bytes(lhs: str) -> float:
    """Bytes of all array shapes on the lhs of an HLO instruction."""
    total = 0.0
    for m in _SHAPE_RE.finditer(lhs):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def run_cell(arch: str, shape: str, *, multi_pod: bool, verbose=True,
             tcfg_overrides=None, overrides=None) -> dict:
    t0 = time.time()
    rec = {"arch": arch, "shape": shape,
           "mesh": "pod2x16x16" if multi_pod else "16x16", "status": "ok"}
    if overrides:
        rec["overrides"] = overrides
    try:
        lowered, compiled, meta = lower_cell(arch, shape, multi_pod=multi_pod,
                                             tcfg_overrides=tcfg_overrides,
                                             overrides=overrides)
        rec["kind"] = meta["kind"]
        ma = compiled.memory_analysis()
        if ma is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes"):
                rec[k] = getattr(ma, k, None)
        ca = compiled.cost_analysis()
        if ca:
            rec["flops"] = ca.get("flops")
            rec["bytes_accessed"] = ca.get("bytes accessed",
                                           ca.get("bytes_accessed"))
        rec["collective_bytes"] = collective_bytes(compiled)
        # trip-count-aware accounting (XLA counts while bodies once; the
        # scanned-layer models need body x trips — repro.launch.hlo_cost)
        from repro.launch.hlo_cost import analyze_compiled
        scaled = analyze_compiled(compiled)
        rec["flops_scaled"] = scaled["flops"]
        rec["bytes_scaled"] = scaled["bytes"]
        rec["collective_bytes_scaled"] = scaled["collective_bytes"]
        rec["compile_s"] = round(time.time() - t0, 1)
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=8)
        rec["compile_s"] = round(time.time() - t0, 1)
    if verbose:
        flops = rec.get("flops")
        print(f"[{rec['mesh']}] {arch:15s} {shape:12s} {rec['status']:4s} "
              f"flops={flops:.3e}" if flops else
              f"[{rec['mesh']}] {arch:15s} {shape:12s} {rec['status']}"
              + (f"  ({rec.get('error','')[:120]})" if rec["status"] != "ok" else ""),
              flush=True)
    return rec


def iter_cells():
    from repro.configs import get_config
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES:
            yield arch, shape, shape in cfg.supported_shapes


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--json", default=None)
    ap.add_argument("--grad-compress-bits", type=int, default=None)
    ap.add_argument("--override", action="append", default=[],
                    help="perf levers, key=value (seq_parallel=0, "
                         "remat_policy=dots, microbatches=4, flash_decode=1)")
    args = ap.parse_args(argv)
    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        overrides[k] = (int(v) if v.lstrip("-").isdigit() else
                        {"true": True, "false": False}.get(v.lower(), v))
    for bkey in ("seq_parallel", "decode_seq_shard", "flash_decode"):
        if bkey in overrides:
            overrides[bkey] = bool(overrides[bkey])
    overrides = overrides or None

    meshes = []
    if not args.multi_pod_only:
        meshes.append(False)
    if not args.single_pod_only:
        meshes.append(True)
    over = ({"grad_compress_bits": args.grad_compress_bits}
            if args.grad_compress_bits else None)

    records = []
    n_fail = 0
    for arch, shape, supported in iter_cells():
        if args.arch and canonical(args.arch) != arch:
            continue
        if args.shape and args.shape != shape:
            continue
        if not supported:
            records.append({"arch": arch, "shape": shape, "status": "skip",
                            "reason": "full attention is O(S^2) at 500k; "
                                      "see DESIGN.md §5"})
            print(f"[ ---- ] {arch:15s} {shape:12s} SKIP (quadratic attn)",
                  flush=True)
            continue
        for mp in meshes:
            rec = run_cell(arch, shape, multi_pod=mp, tcfg_overrides=over,
                           overrides=overrides)
            records.append(rec)
            n_fail += rec["status"] == "FAIL"

    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {args.json}")
    print(f"\n{sum(r['status']=='ok' for r in records)} ok, "
          f"{n_fail} failed, "
          f"{sum(r['status']=='skip' for r in records)} skipped")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
