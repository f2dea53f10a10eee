"""MeshExecutor — the sharded cloud tier.

Every executor so far modeled the cloud as virtual queues in front of one
device; this one puts the real compute on a **device mesh**. The bound
``run_fn`` (the gateway's ``_run_batch_mesh``) still does the batched host
decode, but restore + cloud forward run under ``shard_map`` with batch-axis
data parallelism: a padded micro-batch of N rows is split into
``N / mesh.shape['data']`` rows per device, each device runs the *same*
restore→forward program on its shard, and the logits come back sharded on
the batch axis. Model and BaF weights are replicated via
``distributed.sharding.params_pspecs`` (serve mode: ``data_axis=None``, the
"weights stay resident" layout — on the serving mesh the model axis is 1, so
every rule resolves to a full copy per device).

Bit-identity contract: per-row restore+forward is independent of its
batch-mates, so sharding the batch axis changes only the *shape* each device
computes at. The regression tests pin that a full bucket served by this
executor is bit-identical to :class:`~repro.serve.executor.SerialExecutor`
serving the same rows (XLA is free to pick different instruction schedules
at different batch shapes; the tests are the fence that it has not).

Virtual-clock planning: the per-batch service duration is the cost model
evaluated at the **per-shard** row count (``ceil(padded / n_data)``) — a
mesh that splits a 64-row bucket 8 ways charges the time of an 8-row batch.
With a frozen :class:`~repro.serve.executor.CalibratedCostModel` (fit on the
serial tier's measured samples, then ``freeze()``-d) the clock is a pure
function of the workload, so federated runs replay bit-for-bit. An unfrozen
calibrating model is refused at construction: it would record per-shard
sizes against whole-batch wall times and poison its own fit.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.split import restore_codes, restore_codes_fused
from repro.distributed.sharding import params_pspecs
from repro.launch.hlo_cost import analyze_compiled
from repro.launch.chips import ChipPeaks, chip_peaks
from repro.launch.mesh import make_dev_mesh
from repro.models.cnn import cnn_cloud
from repro.serve.executor import (CalibratedCostModel, CloudExecutor,
                                  CostModel, _Queue)


@dataclass(frozen=True)
class _ShardCost:
    """One batch as a single mesh device sees it — what the cost model is
    evaluated at (``padded_size`` = rows per shard, not rows per batch)."""
    padded_size: int
    key: Any = None


class MeshExecutor(CloudExecutor):
    """Cloud tier serving batched restore+forward from a device mesh.

    Parameters
    ----------
    mesh : jax Mesh with a batch-parallel axis (default:
        ``launch.mesh.make_dev_mesh(prefer="data")`` — all local devices on
        the data axis, the serving shape)
    cost : CostModel for virtual service times, evaluated per shard. Pass a
        **frozen** :class:`CalibratedCostModel` for bit-identical replay;
        an unfrozen one is rejected.
    data_axis : mesh axis name the batch is sharded over
    overhead_s : fixed per-batch virtual overhead added on top of the
        per-shard cost (dispatch / collective headroom); 0 by default
    """

    def __init__(self, mesh=None, *, cost: CostModel | None = None,
                 data_axis: str = "data", overhead_s: float = 0.0):
        if isinstance(cost, CalibratedCostModel) and not cost.frozen:
            raise ValueError(
                "MeshExecutor needs a frozen CalibratedCostModel: calibrate "
                "on the serial tier, freeze(), then hand it over — a "
                "calibrating model would record per-shard sizes against "
                "whole-batch wall times and poison its own fit")
        super().__init__(queues=[_Queue(rate=1.0)], cost=cost)
        self.mesh = mesh if mesh is not None else make_dev_mesh(prefer="data")
        if data_axis not in self.mesh.shape:
            raise ValueError(f"mesh has no {data_axis!r} axis: "
                             f"{dict(self.mesh.shape)}")
        self.data_axis = data_axis
        self.n_data = int(self.mesh.shape[data_axis])
        self.overhead_s = float(overhead_s)
        # (id(plan), codes shape) -> (plan, jitted shard_map program). The
        # plan ref is kept so id() stays valid for the cache's lifetime.
        self._fns: dict = {}
        self._pspecs: dict = {}      # id(params tree) -> (tree, specs)

    # -- virtual clock -------------------------------------------------------
    def shard_rows(self, padded_size: int) -> int:
        """Rows each device computes for a batch of ``padded_size``."""
        return -(-int(padded_size) // self.n_data)

    def _plan_duration(self, batch, wall_s: float) -> float:
        view = _ShardCost(padded_size=self.shard_rows(batch.padded_size),
                          key=getattr(batch, "key", None))
        return self.overhead_s + self.cost.duration_s(view, wall_s)

    # -- sharded compute -----------------------------------------------------
    def _params_specs(self, tree):
        hit = self._pspecs.get(id(tree))
        if hit is None:
            # serve layout: no data-axis (ZeRO) factor — inside a manual
            # shard_map region a data-sharded weight would arrive as a slice
            # with nothing to all-gather it; the model axis is size 1 on the
            # serving mesh, so every rule degenerates to a full per-device copy
            hit = (tree, params_pspecs(tree, self.mesh, data_axis=None))
            self._pspecs[id(tree)] = hit
        return hit[1]

    def _sharded_fn(self, plan, shape: tuple):
        key = (id(plan), tuple(shape))
        hit = self._fns.get(key)
        if hit is not None:
            return hit[1]
        bits = plan.op.bits
        sel = plan._sel
        fused = plan.fused
        consolidation = plan.consolidation

        def body(bafp, params, codes, mins, maxs):
            split = params["split"]
            if fused:
                z = restore_codes_fused(bafp, split, sel, codes, mins, maxs,
                                        bits=bits)
            else:
                z = restore_codes(bafp, split, sel, codes, mins, maxs,
                                  bits=bits, consolidation=consolidation)
            return cnn_cloud(params, z)

        d = self.data_axis
        # manual over every mesh axis: a Pallas kernel (the fused restore's
        # consolidation) cannot sit in a region the compiler still
        # partitions, even over the size-1 model axis
        fn = jax.jit(shard_map(
            body, mesh=self.mesh,
            in_specs=(self._params_specs(plan.spec.baf_params),
                      self._params_specs(plan.spec.params),
                      P(d), P(d), P(d)),
            out_specs=P(d), check_vma=False))
        self._fns[key] = (plan, fn)
        return fn

    def run_sharded(self, plan, decoded, target: int) -> np.ndarray:
        """Restore + cloud forward ``decoded`` across the mesh.

        Rows are padded (repeat-last, same as the serial path's bucket
        padding) to a multiple of the data-axis size so every device gets an
        equal shard; returns host logits for the first ``target`` rows.
        One jitted shard_map program per (plan, padded codes shape).
        """
        if plan.spec.params is None or plan.spec.baf_params is None:
            raise ValueError("plan was compiled without model weights; "
                             "MeshExecutor cannot restore")
        dec = decoded.pad_to(self.shard_rows(target) * self.n_data)
        fn = self._sharded_fn(plan, dec.codes.shape)
        out = fn(plan.spec.baf_params, plan.spec.params,
                 dec.codes, dec.mins, dec.maxs)
        return np.asarray(jax.block_until_ready(out))[:target]


def seed_cost_from_hlo(plan, sample_shape: tuple, *,
                       peaks: ChipPeaks | None = None) -> CalibratedCostModel:
    """Roofline-seeded :class:`CalibratedCostModel` for a plan's cloud body.

    Compiles the (serial) restore+forward program for one ``(N, H, W, C)``
    codes shape, runs the trip-count-aware ``launch/hlo_cost`` analysis over
    the compiled HLO, and seeds ``per_item_s`` with the roofline time
    ``max(flops/peak flops, bytes/peak HBM bandwidth) / N`` of ``peaks``
    (default: the first device's entry in launch/chips.py, an error on a
    device with no published peaks). Measured calibration samples override
    the seed at ``fit()``; the seed carries fits that would otherwise be
    degenerate (a single batch size in the samples).
    """
    if peaks is None:
        peaks = chip_peaks()
    n = int(sample_shape[0])
    c = int(sample_shape[-1])
    bits, sel = plan.op.bits, plan._sel
    fused, consolidation = plan.fused, plan.consolidation

    def body(bafp, params, codes, mins, maxs):
        split = params["split"]
        if fused:
            z = restore_codes_fused(bafp, split, sel, codes, mins, maxs,
                                    bits=bits)
        else:
            z = restore_codes(bafp, split, sel, codes, mins, maxs,
                              bits=bits, consolidation=consolidation)
        return cnn_cloud(params, z)

    code_dtype = np.uint8 if bits <= 8 else np.uint16
    codes = np.zeros(sample_shape, code_dtype)
    mins = np.zeros((n, 1, 1, c), np.float16)
    maxs = np.ones((n, 1, 1, c), np.float16)
    compiled = jax.jit(body).lower(plan.spec.baf_params, plan.spec.params,
                                   codes, mins, maxs).compile()
    est = analyze_compiled(compiled)
    roof_s = max(est["flops"] / peaks.bf16_flops,
                 est["bytes"] / peaks.hbm_bytes_per_s)
    return CalibratedCostModel(seed_per_item_s=roof_s / n)
