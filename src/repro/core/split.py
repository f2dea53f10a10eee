"""Split-inference engine: edge -> (quantize/entropy-code) -> channel ->
(decode/dequantize) -> BaF restore -> cloud.  Paper Fig. 1, end to end.

Device-side math (quantize, BaF, consolidation) is jit-able JAX; the entropy
codec is host code (DESIGN.md §4). The engine measures real bits on the wire,
including the C*32 side-info bits, matching the paper's accounting.

Coding configuration now lives in ``repro.pipeline``: build an
``OperatingPoint``, ``compile`` it against a ``ModelSpec``, and run the plan's
``encode`` / ``decode_batch`` / ``restore``. This module keeps the jitted
device-side restore functions (one trace per ``(C, bits, batch-bucket)``,
shared process-wide) plus ``SplitInferenceEngine``, the single-operating-point
wrapper, which itself executes a plan. The loose-tuple entry points
``encode_activation`` / ``decode_stream`` served their one deprecation
release and are gone — see docs/MIGRATION.md for the mapping.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.baf import baf_conv_predict, scatter_consolidated
from repro.core.quant import QuantParams, compute_quant_params, dequantize, quantize


@dataclass(frozen=True)
class ActivationStats:
    """Cheap per-request content descriptor of the selected split channels.

    The quantizer step scales with the content's dynamic range and the PSNR
    peak follows the content's peak, so these two numbers are enough for the
    rate controller to shift calibration-time RD-table PSNRs toward *this*
    request (serve/rate_control.py ContentKeyedController).
    """
    peak: float          # max |z_sel| over the example
    dyn_range: float     # mean over channels of per-channel (max - min)


def activation_stats(z, sel_idx) -> ActivationStats:
    """O(HWC) statistics of ``z[..., sel_idx]`` — no quantize/codec work.

    z: (B, H, W, P) split activation (any leading batch shape); stats are
    aggregated over the whole array (callers pass one request at a time).
    """
    z_sel = np.asarray(z)[..., np.asarray(sel_idx)]
    flat = z_sel.reshape(-1, z_sel.shape[-1]).astype(np.float32)
    peak = float(np.max(np.abs(flat))) if flat.size else 0.0
    rng = float(np.mean(np.max(flat, 0) - np.min(flat, 0))) if flat.size else 0.0
    return ActivationStats(peak=peak, dyn_range=rng)


@dataclass
class SplitStats:
    total_bits: int
    payload_bits: int
    side_info_bits: int
    raw_bits: int            # uncompressed fp32 full-tensor bits (reference)
    entropy_bits: float      # order-0 entropy floor of the code stream
    wire_bits: int = 0       # actual container bytes * 8 (header included) —
                             # what the channel/scheduler meter

    @property
    def reduction_vs_raw(self) -> float:
        return 1.0 - self.total_bits / self.raw_bits


@partial(jax.jit, static_argnames=("bits", "consolidation"))
def restore_codes(baf_params, split, sel_idx, codes, mins, maxs, *,
                  bits: int, consolidation: bool = True):
    """Dequantize + BaF restore at one operating point (reference path).

    One compile per distinct (C, bits, consolidation, batch-bucket shape);
    callers that bucket their batches (serve/batcher.py) never re-trace.
    """
    qp = QuantParams(mins, maxs, bits)
    z_hat_sel = dequantize(codes, qp)
    return baf_conv_predict(
        baf_params, split["conv"], split["bn"], sel_idx, z_hat_sel,
        codes=codes if consolidation else None,
        qp=qp if consolidation else None)


@partial(jax.jit, static_argnames=("bits",))
def restore_codes_fused(baf_params, split, sel_idx, codes, mins, maxs, *,
                        bits: int):
    """Batched restore with the fused Pallas consolidation kernel.

    Same math as ``restore_codes(consolidation=True)`` but eq. (6) runs through
    kernels/consolidate.py: bounds are rebuilt from codes + side info in VMEM
    instead of materializing (lo, hi) in HBM — the hot path for micro-batched
    gateway serving.
    """
    from repro.kernels.consolidate import consolidate_pallas
    qp = QuantParams(mins, maxs, bits)
    z_hat_sel = dequantize(codes, qp)
    z_tilde = baf_conv_predict(baf_params, split["conv"], split["bn"],
                               sel_idx, z_hat_sel)
    b, h, w, c = codes.shape
    r = h * w
    cons = consolidate_pallas(
        z_tilde[..., sel_idx].reshape(b, r, c),
        codes.reshape(b, r, c),
        mins.reshape(b, c), maxs.reshape(b, c), bits)
    return scatter_consolidated(z_tilde, cons.reshape(b, h, w, c), sel_idx)


def edge_forward(p, img):
    """The edge half of the CNN: ``img`` -> split activation (B, H, W, P).

    A named function, not a lambda, so its jitted program reads
    ``jit_edge_forward`` in a profile, beside ``jit_cnn_cloud`` and the
    restore's ``jit_restore_codes_fused``."""
    from repro.models.cnn import cnn_edge      # lazy, as in the callers
    return cnn_edge(p, img)[1]


@lru_cache(maxsize=1)
def _jitted_cnn_fns():
    # lazy: models.cnn is imported on first use (mirrors the engine's local
    # import), but the jit wrappers are cached so repeated fidelity sweeps
    # (build_rd_table) trace each network once per shape, not once per call
    from repro.models.cnn import cnn_cloud
    return jax.jit(edge_forward), jax.jit(cnn_cloud)


def fidelity_metrics(params, baf_params, sel_idx, img, *, bits: int,
                     consolidation: bool = True, z=None):
    """Continuous restoration metrics at one (C, bits) operating point.

    The mAP proxy saturates on the synthetic task; these expose the C/n
    degradation trends: (psnr_db of sigma(Z_tilde) vs sigma(Z), mean
    KL(cloud || split) of the downstream logits). Pass a precomputed split
    activation ``z`` to skip the edge forward (rate-controller sweeps).
    """
    import jax.nn as jnn

    from repro import nn as _nn

    edge_fn, cloud_fn = _jitted_cnn_fns()
    sel_idx = jnp.asarray(np.asarray(sel_idx), jnp.int32)
    if z is None:
        z = edge_fn(params, img)
    z_sel = z[..., sel_idx]
    qp = compute_quant_params(z_sel, bits, per_example=True)
    codes = quantize(z_sel, qp)
    z_tilde = restore_codes(baf_params, params["split"], sel_idx, codes,
                            qp.mins, qp.maxs, bits=bits,
                            consolidation=consolidation)
    y_true = _nn.leaky_relu(z).astype(jnp.float32)
    y_rest = _nn.leaky_relu(z_tilde).astype(jnp.float32)
    mse = float(jnp.mean(jnp.square(y_true - y_rest)))
    peak = float(jnp.max(jnp.abs(y_true))) or 1.0
    psnr = 10.0 * np.log10(peak * peak / max(mse, 1e-12))
    logits_split = cloud_fn(params, z_tilde)
    logits_cloud = cloud_fn(params, z)
    p_cloud = jnn.log_softmax(logits_cloud.astype(jnp.float32))
    p_split = jnn.log_softmax(logits_split.astype(jnp.float32))
    kl = float(jnp.mean(jnp.sum(jnp.exp(p_cloud) * (p_cloud - p_split), -1)))
    return psnr, kl


# ---------------------------------------------------------------------------
# Single-operating-point engine (thin wrapper over the pure paths)
# ---------------------------------------------------------------------------

class SplitInferenceEngine:
    """Orchestrates the paper's mobile/cloud pipeline for the Tier-A CNN.

    A thin wrapper that compiles one :class:`repro.pipeline.CompressionPlan`
    at construction and executes it end to end (the plan is exposed as
    ``self.plan`` for callers that want the staged API).

    Parameters
    ----------
    params : CNN params (see models/cnn.py)
    baf_params : trained BaF predictor params (core/baf.py)
    sel_idx : ordered selected-channel indices (core/selection.py), length C
    bits : quantizer depth n
    """

    def __init__(self, params, baf_params, sel_idx, *, bits: int = 8,
                 consolidation: bool = True):
        from repro import pipeline                     # lazy: avoid cycle
        from repro.models.cnn import cnn_cloud
        self._edge_fn = jax.jit(edge_forward)
        self._cloud_fn = jax.jit(cnn_cloud)
        self.params = params
        self.baf_params = baf_params
        self.sel_idx = jnp.asarray(np.asarray(sel_idx), jnp.int32)
        self.bits = bits
        self.consolidation = consolidation
        self.op = pipeline.OperatingPoint(c=int(self.sel_idx.shape[0]),
                                          bits=bits)
        self.spec = pipeline.ModelSpec(sel_idx=np.asarray(sel_idx),
                                       params=params, baf_params=baf_params)
        self.plan = pipeline.compile(self.op, self.spec, fused=False,
                                     consolidation=consolidation)

    # -- mobile side --------------------------------------------------------
    def encode(self, img):
        """Edge forward + plan encode -> (WireBlob, SplitStats)."""
        z = self._edge_fn(self.params, img)            # (B, H, W, P)
        blob = self.plan.encode(z)
        return blob, blob.stats

    # -- cloud side ----------------------------------------------------------
    def decode_and_infer(self, blob):
        """Decode a plan ``WireBlob`` + BaF restore + cloud forward."""
        z_tilde = self.plan.restore(self.plan.decode(blob))
        return self._cloud_fn(self.params, z_tilde)

    # -- fidelity metrics ------------------------------------------------------
    def fidelity(self, img):
        """Continuous restoration metrics — see :func:`fidelity_metrics`."""
        return fidelity_metrics(self.params, self.baf_params, self.sel_idx,
                                img, bits=self.bits,
                                consolidation=self.consolidation)

    # -- end to end ----------------------------------------------------------
    def __call__(self, img):
        blob, stats = self.encode(img)
        # decode parses blob.data through EncodedTensor.from_bytes — the
        # actual wire round-trip, header validation included
        logits = self.decode_and_infer(blob)
        return logits, stats
