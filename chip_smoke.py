"""Bring-up smoke test: the BaF split-inference serving path on a TPU.

    python chip_smoke.py               # one chip, the paper's geometry
    python chip_smoke.py --four-chips  # four chips: MeshExecutor vs serial

One chip: builds ``configs/yolo_baf.full_config()`` (512x512 input, a
64x64x256 split tensor, Q=128) with seeded random weights and BaF predictors
for C=8 and C=128, then serves 17 synthetic 512 px requests per C through
``ServingGateway`` with rANS coding at 8 bits and ``max_batch=8`` — buckets
of 8 and 1. It checks the responses, the rANS round trip, the fused restore
against the float32 reference, each Pallas kernel against its reference,
and that the compiled restore holds the Pallas kernel.

Four chips: serves 64 rows through ``MeshExecutor`` on
``make_dev_mesh(prefer="data")`` and through ``SerialExecutor`` and checks
that the logits match and that the sharded output spans the four chips.

Every result line names the device it ran on. The last line is a JSON
object with ``"ok": true`` and the device only when every check passed;
without a TPU the script exits non-zero before doing anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.yolo_baf import full_config  # noqa: E402
from repro.core.baf import BaFConvConfig, consolidate, init_baf_conv  # noqa: E402
from repro.core.quant import compute_quant_params, quantize  # noqa: E402
from repro.core.split import (_jitted_cnn_fns, restore_codes,  # noqa: E402
                              restore_codes_fused)
from repro.data.synthetic import ShapesDatasetConfig, shapes_batch_iterator  # noqa: E402
from repro.kernels.consolidate import consolidate_pallas  # noqa: E402
from repro.kernels.histogram import histogram_pallas  # noqa: E402
from repro.kernels.quantize import quantize_pallas  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_dev_mesh  # noqa: E402
from repro.models.cnn import init_cnn  # noqa: E402
from repro.serve import MeshExecutor, OperatingPoint, ServingGateway  # noqa: E402

SEED = 0
BITS = 8

# Served logits (default matmul precision: one bf16 pass per f32 matmul or
# conv on TPU) against the reference at highest precision. Each pass rounds
# both operands to bf16 (relative error 2^-9 each, so <= 2^-8 per product,
# accumulated in f32); the codes pass through 10 such layers before the
# logits (BaF: 4 convs, split conv; cloud: 4 convs, head). Adding the
# per-layer worst case gives 10 * 2^-8 = 0.039 of the logits' scale; the
# clip, PReLU and leaky ReLU between them are 1-Lipschitz and amplify
# nothing.
SERVED_VS_REF_RTOL = 0.05
# Programs that do the same f32 arithmetic in another order or fusion may
# differ only by f32 reassociation: fused vs reference restore at highest
# precision (eq. (6)'s clip in the Pallas kernel vs in XLA), the kernel vs
# core/baf.consolidate, and the mesh's per-device batches vs one serial
# batch. 1e-4 of the output's scale is hundreds of f32 ulps.
REASSOC_RTOL = 1e-4
# quantize_pallas vs core/quant: the same f32 formula, but Mosaic's and
# XLA's f32 division may differ in the last ulp, which moves a code by one
# only when x lands within an ulp of a rounding boundary (probability about
# 2^-23 per element).
QUANT_MAX_MISMATCH_FRAC = 1e-5


class Checks:
    """Prints each check with the device label and remembers failures."""

    def __init__(self, label: str):
        self.label = label
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"[{self.label}] {'PASS' if ok else 'FAIL'} {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def info(self, text: str) -> None:
        print(f"[{self.label}] {text}", flush=True)


class CompileCounter:
    """Compile seconds and persistent-cache hits, from jax.monitoring."""

    def __init__(self):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def build_system(cfg, cs, seed: int):
    """Seeded CNN weights and one BaF predictor + channel subset per C."""
    params = init_cnn(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    bank = {}
    for c in cs:
        sel = np.sort(rng.permutation(cfg.split_p)[:c])
        baf = init_baf_conv(jax.random.PRNGKey(seed + 1 + c),
                            BaFConvConfig(c=c, q=cfg.split_q))
        bank[c] = (baf, sel)
    return params, bank


def make_images(cfg, n: int, seed: int) -> np.ndarray:
    data = ShapesDatasetConfig(image_size=cfg.input_size,
                               num_classes=cfg.num_classes, batch_size=n)
    imgs, _ = next(shapes_batch_iterator(data, seed=seed))
    return np.asarray(imgs)


def check_kernels(check: Checks, z_sel, z_tilde_sel, bits: int, tag: str):
    """Each Pallas kernel against its reference on (B, R, C) real data."""
    b, r, c = z_sel.shape
    qp = compute_quant_params(z_sel, bits, per_example=True)
    ref_codes = np.asarray(quantize(z_sel, qp))
    codes, mins, maxs = quantize_pallas(z_sel, bits)
    codes = np.asarray(codes)
    diff = np.abs(codes.astype(np.int32) - ref_codes.astype(np.int32))
    frac = float(np.mean(diff != 0))
    side_ok = (np.array_equal(np.asarray(mins), np.asarray(qp.mins).reshape(b, c))
               and np.array_equal(np.asarray(maxs),
                                  np.asarray(qp.maxs).reshape(b, c)))
    check(f"quantize_pallas == core/quant {tag}",
          side_ok and int(diff.max()) <= 1 and frac <= QUANT_MAX_MISMATCH_FRAC,
          f"side info exact={side_ok}, codes differing {frac:.2e} "
          f"(max |diff| {int(diff.max())})")

    codes = jnp.asarray(ref_codes)
    got = consolidate_pallas(z_tilde_sel, codes, qp.mins.reshape(b, c),
                             qp.maxs.reshape(b, c), bits)
    want = consolidate(z_tilde_sel, codes, qp)
    err = rel_err(got, want)
    check(f"consolidate_pallas == core/baf.consolidate {tag}",
          err <= REASSOC_RTOL, f"max rel err {err:.2e}")

    flat = ref_codes.reshape(-1, c)
    counts = np.asarray(histogram_pallas(jnp.asarray(flat, jnp.int32),
                                         1 << bits))
    bincounts = np.stack([np.bincount(flat[:, i], minlength=1 << bits)
                          for i in range(c)], axis=1)
    check(f"histogram_pallas == np.bincount {tag}",
          np.array_equal(counts, bincounts))


def serve_one_c(check: Checks, counter: CompileCounter, params, bank, c: int,
                imgs: np.ndarray, *, max_batch: int, bits: int):
    """Serve ``imgs`` at one C through the gateway, then check it."""
    n = imgs.shape[0]
    baf, sel = bank[c]
    op = OperatingPoint(c=c, bits=bits, backend="rans")
    gw = ServingGateway(params, {c: bank[c]}, default_op=op,
                        max_batch=max_batch)
    num_classes = params["head"]["w"].shape[-1]

    compile0 = counter.compile_s
    t0 = time.perf_counter()
    resps, _ = gw.serve(imgs)
    cold_s = time.perf_counter() - t0
    compile_s = counter.compile_s - compile0
    t0 = time.perf_counter()
    warm, _ = gw.serve(imgs)
    warm_s = time.perf_counter() - t0
    wire = [r.stats.wire_bits for r in resps]
    check.info(f"C={c}: {n} requests, cold serve {cold_s:.3f} s "
               f"(backend compile {compile_s:.3f} s), warm serve "
               f"{warm_s:.3f} s = {warm_s / n:.4f} s per request, "
               f"wire bits per request mean {np.mean(wire):.0f} "
               f"(min {min(wire)}, max {max(wire)})")

    logits = np.stack([r.logits for r in resps])
    check(f"C={c} logits finite, shape ({num_classes},)",
          logits.shape == (n, num_classes) and bool(np.all(np.isfinite(logits))))
    check(f"C={c} warm serve repeats the cold logits",
          np.array_equal(logits, np.stack([r.logits for r in warm])))

    # rANS round trip, and the fused path against the reference, on the
    # rows of each served bucket (0..max_batch-1 and the last, alone)
    plan = gw.plan_for(op)
    edge_fn, cloud_fn = _jitted_cnn_fns()
    split = params["split"]
    sel_j = jnp.asarray(sel, jnp.int32)
    n_full = (n // max_batch) * max_batch
    for rows in (list(range(max_batch)), list(range(n_full, n))):
        if not rows:
            continue
        tag = f"C={c} B={len(rows)}"
        zs = [edge_fn(params, jnp.asarray(imgs[i:i + 1])) for i in rows]
        quant = [plan.quantize(z) for z in zs]
        dec = plan.decode_batch([plan.encode(z) for z in zs])
        check(f"rANS decode == quantized codes, bit for bit {tag}",
              np.array_equal(dec.codes, np.concatenate([q[0] for q in quant]))
              and np.array_equal(dec.mins, np.concatenate([q[1] for q in quant]))
              and np.array_equal(dec.maxs,
                                 np.concatenate([q[2] for q in quant])))
        args = (baf, split, sel_j, jnp.asarray(dec.codes),
                jnp.asarray(dec.mins), jnp.asarray(dec.maxs))
        with jax.default_matmul_precision("highest"):
            z_ref = restore_codes(*args, bits=bits, consolidation=True)
            ref = np.asarray(cloud_fn(params, z_ref))
            z_fused = restore_codes_fused(*args, bits=bits)
            fused = np.asarray(cloud_fn(params, z_fused))
        err_z = rel_err(z_fused, z_ref)
        err = rel_err(fused, ref)
        check(f"fused restore + cloud == reference, both highest precision "
              f"{tag}", err_z <= REASSOC_RTOL and err <= REASSOC_RTOL,
              f"restore max rel err {err_z:.2e}, logits {err:.2e} "
              f"(limit {REASSOC_RTOL:g})")
        err = rel_err(logits[rows], ref)
        check(f"served logits == reference (highest precision) {tag}",
              err <= SERVED_VS_REF_RTOL,
              f"max rel err {err:.2e} (limit {SERVED_VS_REF_RTOL:g})")

        if len(rows) == max_batch:
            hlo = restore_codes_fused.lower(*args, bits=bits).compile().as_text()
            check(f"compiled fused restore holds a Pallas kernel {tag}",
                  "tpu_custom_call" in hlo)
        z = jnp.concatenate(zs)
        b, h, w, _ = z.shape
        z_sel = z[..., sel_j].reshape(b, h * w, c)
        z_tilde = restore_codes(*args, bits=bits, consolidation=False)
        check_kernels(check, z_sel, z_tilde[..., sel_j].reshape(b, h * w, c),
                      bits, tag)


def run_one_chip(check: Checks, counter: CompileCounter, cfg, *, cs=(8, 128),
                 n_requests: int = 17, max_batch: int = 8, bits: int = BITS,
                 seed: int = SEED) -> None:
    params, bank = build_system(cfg, cs, seed)
    imgs = make_images(cfg, n_requests, seed)
    check.info(f"config: input {cfg.input_size} px, split tensor "
               f"{cfg.split_hw}x{cfg.split_hw}x{cfg.split_p}, Q={cfg.split_q}, "
               f"C in {tuple(cs)}, {bits} bits, rans, max_batch {max_batch}")
    for c in cs:
        serve_one_c(check, counter, params, bank, c, imgs,
                    max_batch=max_batch, bits=bits)


def run_four_chips(check: Checks, cfg, *, c: int = 8, rows: int = 64,
                   bits: int = BITS, seed: int = SEED) -> None:
    """MeshExecutor over all devices vs SerialExecutor on the same rows."""
    params, bank = build_system(cfg, (c,), seed)
    imgs = make_images(cfg, rows, seed)
    op = OperatingPoint(c=c, bits=bits, backend="rans")
    mesh = make_dev_mesh(prefer="data")
    n_dev = len(jax.devices())
    check(f"mesh puts all {n_dev} devices on the data axis",
          mesh.shape["data"] == n_dev, str(dict(mesh.shape)))

    out = {}
    for name, executor in (("serial", None), ("mesh", MeshExecutor(mesh))):
        gw = ServingGateway(params, bank, default_op=op, max_batch=rows,
                            executor=executor)
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            resps, _ = gw.serve(imgs)
            times.append(time.perf_counter() - t0)
        out[name] = (gw, np.stack([r.logits for r in resps]))
        check.info(f"{name}: {rows} requests at C={c}, cold serve "
                   f"{times[0]:.3f} s, warm serve {times[1]:.3f} s")
    serial, mesh_logits = out["serial"][1], out["mesh"][1]
    err = rel_err(mesh_logits, serial)
    check(f"mesh logits == serial logits on {rows} rows",
          bool(np.all(np.isfinite(mesh_logits))) and err <= REASSOC_RTOL,
          f"bit-identical={np.array_equal(mesh_logits, serial)}, "
          f"max rel err {err:.2e} (limit {REASSOC_RTOL:g})")

    gw, ex = out["mesh"][0], out["mesh"][0].executor
    plan = gw.plan_for(op)
    dec = plan.decode_batch([gw.encode_request(imgs[i:i + 1])[1]
                             for i in range(rows)])
    sharded = ex._sharded_fn(plan, dec.codes.shape)(
        plan.spec.baf_params, plan.spec.params, dec.codes, dec.mins, dec.maxs)
    spanned = {d.id for d in sharded.sharding.device_set}
    check(f"sharded logits span {n_dev} devices",
          len(spanned) == n_dev and np.array_equal(np.asarray(sharded),
                                                   mesh_logits),
          f"device ids {sorted(spanned)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the MeshExecutor path over 4 chips and "
                         "compare it with SerialExecutor")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    check = Checks(f"{dev.platform} {dev.device_kind} x{len(devices)}")
    check.info(f"jax {jax.__version__}, compile cache {cache_dir}")

    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips(check, full_config())
    else:
        run_one_chip(check, counter, full_config())
    check.info(f"total {time.perf_counter() - t0:.1f} s, backend compile "
               f"{counter.compile_s:.1f} s, persistent cache hits "
               f"{counter.hits}, misses {counter.misses}")
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
