"""Entropy-coding subsystem: context-adaptive interleaved rANS.

The real coder behind the wire codec's ``rans`` / ``rans-ctx`` backends
(core/codec.py). Layers, bottom to top:

  * ``rans.py``      — interleaved multi-stream rANS core (numpy-vectorized
                       over lanes, bit-exact round-trip, normalized tables)
  * ``context.py``   — adaptive quantized-up-neighbor/channel context model
                       (nothing transmitted; decoder mirrors adaptation)
  * ``container.py`` — versioned bitstream container with per-tile chunks,
                       partial decode, and distinct corruption errors
  * ``backend.py``   — tensor-level adapters registered with core/codec.py
  * ``batch.py``     — cross-container batched decode: chunks of a whole
                       micro-batch share one interleaved decode loop
                       (bit-identical to the per-blob path)

Symbol statistics for static tables are computed on device by the Pallas
histogram kernel (repro.kernels.histogram).
"""
from repro.codec.backend import (decode_channels, decode_tensor,
                                 encode_adaptive_tensor, encode_static_tensor)
from repro.codec.batch import decode_tensor_batch
from repro.codec.container import RansContainer
from repro.codec.context import decode_ctx, encode_ctx, plan_lanes
from repro.codec.rans import (CorruptStream, RansTable, normalize_freqs,
                              rans_decode, rans_encode)

__all__ = [
    "CorruptStream", "RansContainer", "RansTable",
    "decode_channels", "decode_ctx", "decode_tensor", "decode_tensor_batch",
    "encode_adaptive_tensor", "encode_ctx", "encode_static_tensor",
    "normalize_freqs", "plan_lanes", "rans_decode", "rans_encode",
]
