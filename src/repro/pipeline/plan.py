"""Plan/execute split for the BaF compression pipeline.

``compile(op, model_spec)`` turns a declarative :class:`OperatingPoint` plus
model weights into a :class:`CompressionPlan` — a jit-like executable object
owning one request's coding configuration end to end:

    plan.encode(z)            -> WireBlob         (quantize/entropy-code)
    plan.decode_batch(blobs)  -> DecodedBatch     (vectorized host decode)
    plan.restore(decoded)     -> z_tilde          (jitted BaF restore)

Compilation is cached per ``(operating point, model spec, flags)`` and the
device-side restore reuses one jitted trace per distinct
``(C, bits, batch-bucket)`` — callers that bucket their batches
(serve/batcher.py) never re-trace, no matter how many plans they hold.

``decode_batch`` is the batched/vectorized host decode path: N same-bucket
wire blobs are parsed once and the rANS chunks of all their payloads share
one interleaved decode loop (core/codec.py ``decode_many``). Outputs are
bit-identical to per-request decode.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax.numpy as jnp
import numpy as np

from repro.core import codec as wire
from repro.core.quant import compute_quant_params, quantize
from repro.core.split import (SplitStats, restore_codes, restore_codes_fused)
from repro.obs import hooks
from repro.pipeline.op import OperatingPoint


@dataclass(frozen=True, eq=False)
class ModelSpec:
    """Model-side inputs a plan binds to.

    eq/hash are object identity: two specs are "the same model" only when
    they are literally the same object, which is what the compile cache keys
    on (params pytrees are not hashable, and value-comparing them per encode
    would defeat the point of a cached plan).

    ``params``/``baf_params`` may be None for an encode/decode-only plan
    (e.g. the edge side of a split deployment); ``restore`` then refuses.

    Compiled plans cache *on the spec itself* (``_plans``), so dropping the
    spec (e.g. on a model reload) releases its plans and weights — nothing
    is pinned in a process-wide cache.
    """
    sel_idx: Any                 # (C,) ordered selected-channel indices
    params: Any = None           # CNN params (models/cnn.py); needs ["split"]
    baf_params: Any = None       # trained BaF predictor for this C
    _plans: dict = field(default_factory=dict, init=False, repr=False)


@dataclass(frozen=True)
class WireBlob:
    """One request's serialized container plus the plan-level metadata the
    cloud side needs before it decodes a single payload byte: the operating
    point and the codes shape (the micro-batcher buckets on these)."""
    data: bytes
    op: OperatingPoint
    shape: tuple                 # codes shape, (B, H, W, C)
    stats: SplitStats | None = None

    @property
    def nbytes(self) -> int:
        return len(self.data)

    def to_tensor(self) -> wire.EncodedTensor:
        """Parse back to the wire-format view (header validation included)."""
        return wire.EncodedTensor.from_bytes(self.data)


@dataclass
class DecodedBatch:
    """Stacked decode output, restore-ready."""
    codes: np.ndarray            # (N, H, W, C) integer codes
    mins: np.ndarray             # (N, 1, 1, C) fp16
    maxs: np.ndarray             # (N, 1, 1, C) fp16

    def __len__(self) -> int:
        return self.codes.shape[0]

    def pad_to(self, target: int) -> "DecodedBatch":
        """Pad to a bucket size by repeating the last row (dropped after
        restore); the device never sees a shape outside the bucket set."""
        n = len(self)
        if target < n:
            raise ValueError(f"cannot pad {n} rows down to {target}")
        if target == n:
            return self
        reps = [1] * n
        reps[-1] += target - n
        rep = np.repeat
        return DecodedBatch(codes=rep(self.codes, reps, axis=0),
                            mins=rep(self.mins, reps, axis=0),
                            maxs=rep(self.maxs, reps, axis=0))


class CompressionPlan:
    """Executable coding pipeline for one operating point.

    Build via :func:`compile` (cached), not directly. The plan owns the
    operating point; every stage reads configuration from it, so there is
    no loose ``(C, bits)`` plumbing between stages.
    """

    def __init__(self, op: OperatingPoint, spec: ModelSpec, *,
                 fused: bool = True, consolidation: bool = True):
        self.op = op
        self.spec = spec
        self.fused = fused
        self.consolidation = consolidation
        sel = np.asarray(spec.sel_idx)
        if sel.shape[0] != self.op.c:
            raise ValueError(
                f"operating point transmits C={self.op.c} channels but the "
                f"model spec selects {sel.shape[0]}")
        self._sel = jnp.asarray(sel, jnp.int32)

    # -- keys ---------------------------------------------------------------
    @property
    def trace_key(self) -> tuple:
        """What the jitted restore actually specializes on (plus the batch
        bucket shape supplied at call time)."""
        return (self.op.c, self.op.bits, self.fused, self.consolidation)

    # -- encode (edge side) -------------------------------------------------
    def _quantize(self, z) -> tuple[np.ndarray, "object"]:
        """Shared quantize stage -> (codes (B,H,W,C), QuantParams).

        Eager, not jitted: a jitted quantizer moves some codes by one on
        the TPU. The codes' copy to the host also waits for the edge
        forward, so the ``pipeline.quantize`` timer holds that wait."""
        with hooks.timed("pipeline.quantize"):
            z_sel = z[..., self._sel]
            qp = compute_quant_params(z_sel, self.op.bits, per_example=True)
            return np.asarray(quantize(z_sel, qp)), qp

    def quantize(self, z) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Quantize the split activation -> (codes, mins, maxs), no coding.

        The reference the round-trip property tests compare decode against —
        it shares the quantize stage with :meth:`encode` by construction.
        """
        codes, qp = self._quantize(z)
        b, c = codes.shape[0], codes.shape[-1]
        mins = np.asarray(qp.mins, np.float16).reshape(b, 1, 1, c)
        maxs = np.asarray(qp.maxs, np.float16).reshape(b, 1, 1, c)
        return codes, mins, maxs

    def encode_codes(self, codes: np.ndarray, qp,
                     raw_bits: int | None = None) -> WireBlob:
        """Entropy-code an already-quantized code tensor (B, H, W, C).

        The coding half of :meth:`encode`, exposed so stateful callers can
        feed *derived* code tensors — the streaming session codec codes the
        temporal delta of two frames' codes through exactly this path, so
        P-frames ride the same coder, container format, and wire
        accounting as I-frames. ``qp`` carries the side info serialized with
        the stream (the current frame's quant params, not the reference's).
        """
        with hooks.timed("pipeline.encode"):
            enc = wire.encode(codes, qp)
            if raw_bits is None:
                raw_bits = int(np.prod(codes.shape)) * 32
            stats = SplitStats(
                total_bits=enc.total_bits(),
                payload_bits=8 * len(enc.payload),
                side_info_bits=8 * len(enc.side_info),
                raw_bits=raw_bits,
                entropy_bits=wire.empirical_entropy_bits(codes, self.op.bits),
                wire_bits=enc.wire_bits(),
            )
            return WireBlob(data=enc.to_bytes(), op=self.op,
                            shape=tuple(codes.shape), stats=stats)

    def encode(self, z) -> WireBlob:
        """Quantize/entropy-code the split activation ``z`` (B, H, W, P)
        and serialize the container; returns the blob with wire accounting."""
        codes, qp = self._quantize(z)
        return self.encode_codes(codes, qp,
                                 raw_bits=int(np.prod(z.shape)) * 32)

    # -- decode (cloud side, host) ------------------------------------------
    def _check_blob(self, blob: WireBlob, shape: tuple) -> None:
        if blob.op != self.op:
            raise ValueError(
                f"blob was encoded at {blob.op}, this plan executes "
                f"{self.op}")
        if tuple(blob.shape) != shape:
            raise ValueError(
                f"mixed shapes in one decode batch: {blob.shape} vs {shape}")

    def decode(self, blob: WireBlob) -> DecodedBatch:
        """Single-blob decode (= ``decode_batch([blob])``)."""
        return self.decode_batch([blob])

    def decode_batch(self, blobs: "list[WireBlob]") -> DecodedBatch:
        """Vectorized host decode across N same-bucket requests.

        All blobs must share this plan's operating point and one codes shape
        (the micro-batcher's bucket invariant). The entropy decode of all
        payloads shares one interleaved rANS loop; output rows are bit-exact
        with per-request decode, in input order.
        """
        if not blobs:
            raise ValueError("decode_batch needs at least one blob")
        with hooks.timed("pipeline.decode_batch"):
            shape = tuple(blobs[0].shape)
            for blob in blobs:
                self._check_blob(blob, shape)
            encs = [wire.EncodedTensor.from_bytes(b.data) for b in blobs]
            streams, qps = wire.decode_many(encs)
            n = len(blobs)
            b, h, w, c = shape
            codes = streams.reshape(n * b, h, w, c)
            mins = np.stack([np.asarray(qp.mins, np.float16) for qp in qps])
            maxs = np.stack([np.asarray(qp.maxs, np.float16) for qp in qps])
            return DecodedBatch(codes=codes,
                                mins=mins.reshape(n * b, 1, 1, c),
                                maxs=maxs.reshape(n * b, 1, 1, c))

    # -- restore (cloud side, device) ---------------------------------------
    def restore(self, decoded: DecodedBatch):
        """Dequantize + BaF restore; returns the full-width split activation,
        dispatched and not waited for (the ``pipeline.restore`` timer reads
        host dispatch time, not device time).

        One jitted trace per ``(C, bits, bucket shape)`` — shared process-wide
        across plans and gateways via the module-level jit caches in
        core/split.py.
        """
        if self.spec.params is None or self.spec.baf_params is None:
            raise ValueError(
                "plan was compiled without model weights (encode/decode "
                "only); supply params and baf_params in the ModelSpec "
                "to restore")
        # the timer covers host dispatch only (and the trace, on a first
        # call): the device runs on after it returns, and its completion
        # belongs to the caller, which blocks on it (gateway.cloud)
        with hooks.timed("pipeline.restore", fused=self.fused):
            split = self.spec.params["split"]
            codes = jnp.asarray(decoded.codes)
            mins = jnp.asarray(decoded.mins)
            maxs = jnp.asarray(decoded.maxs)
            if self.fused:
                return restore_codes_fused(self.spec.baf_params, split,
                                           self._sel, codes, mins, maxs,
                                           bits=self.op.bits)
            return restore_codes(self.spec.baf_params, split, self._sel,
                                 codes, mins, maxs, bits=self.op.bits,
                                 consolidation=self.consolidation)

    def __repr__(self) -> str:
        return (f"CompressionPlan(op={self.op}, fused={self.fused}, "
                f"consolidation={self.consolidation})")


def compile(op: OperatingPoint, model_spec: ModelSpec, *,   # noqa: A001
            fused: bool = True,
            consolidation: bool = True) -> CompressionPlan:
    """Build (or fetch the cached) plan for ``op`` against ``model_spec``.

    Plans cache on the spec object per ``(op, flags)`` — the cache lives
    exactly as long as the spec does, so dropped specs free their weights.
    The underlying jit traces are cached independently per
    ``(C, bits, bucket)``, so even a fresh plan object re-traces nothing
    the process has already compiled.
    """
    key = (op, fused, consolidation)
    plan = model_spec._plans.get(key)
    if plan is None:
        plan = CompressionPlan(op, model_spec, fused=fused,
                               consolidation=consolidation)
        model_spec._plans[key] = plan
    return plan
