"""Entropy-coding subsystem: interleaved static-table rANS.

The one coder behind the wire codec (core/codec.py). Layers, bottom to top:

  * ``rans.py``      — interleaved multi-stream rANS core (numpy-vectorized
                       over lanes, bit-exact round-trip, normalized tables)
  * ``container.py`` — versioned bitstream container with per-channel
                       chunks, partial decode, and distinct corruption errors
  * ``backend.py``   — channel-last tensor <-> container, per-channel or
                       pooled static tables
  * ``batch.py``     — cross-container batched decode: chunks of a whole
                       micro-batch share one interleaved decode loop
                       (bit-identical to the per-blob path)

Symbol statistics for the static tables are computed on device by the
Pallas histogram kernel (repro.kernels.histogram).
"""
from repro.codec.backend import (decode_channels, decode_tensor,
                                 encode_static_tensor)
from repro.codec.batch import decode_tensor_batch
from repro.codec.container import RansContainer
from repro.codec.rans import (CorruptStream, RansTable, normalize_freqs,
                              rans_decode, rans_encode)

__all__ = [
    "CorruptStream", "RansContainer", "RansTable",
    "decode_channels", "decode_tensor", "decode_tensor_batch",
    "encode_static_tensor", "normalize_freqs", "rans_decode", "rans_encode",
]
