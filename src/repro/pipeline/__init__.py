"""Unified compression-pipeline API: declarative operating points compiled
into executable plans.

    from repro import pipeline

    op   = pipeline.OperatingPoint(c=8, bits=6)
    plan = pipeline.compile(op, pipeline.ModelSpec(sel_idx=sel,
                                                   params=params,
                                                   baf_params=baf))
    blob    = plan.encode(z)                 # quantize/entropy-code
    decoded = plan.decode_batch([blob, ...]) # vectorized host decode
    z_tilde = plan.restore(decoded)          # jitted BaF restore

One plan owns a request's coding configuration end to end; serve/ and the
benchmarks construct all coding state through this package.
"""
from repro.pipeline.op import (SESSION_WIRE_VERSION, WIRE_PROFILE_VERSION,
                               Capabilities, NegotiationError, OperatingPoint,
                               negotiate, negotiate_session, negotiate_tasks)
from repro.pipeline.plan import (CompressionPlan, DecodedBatch, ModelSpec,
                                 WireBlob, compile)

__all__ = [
    "SESSION_WIRE_VERSION", "WIRE_PROFILE_VERSION", "Capabilities",
    "NegotiationError", "OperatingPoint", "negotiate", "negotiate_session",
    "negotiate_tasks",
    "CompressionPlan", "DecodedBatch", "ModelSpec", "WireBlob", "compile",
]
