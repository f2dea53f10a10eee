"""Lossless wire codec for quantized channel codes — host-side by design.

The paper compresses the tiled image with FLIF (or the lossless tool of [5],
or HEVC). This repo codes the channel-last code tensor directly with its own
entropy coder: interleaved multi-stream rANS with static per-channel
frequency tables (``repro.codec``; on-device Pallas histogram -> host coding
pass; one chunk per channel, partial decode). ``encode``/``decode``/
``decode_many`` wrap its containers in the wire header, plus
:func:`empirical_entropy_bits` as a codec-independent order-0 floor.

Wire format (``EncodedTensor.to_bytes``): ``BaF2`` magic, backend id
(always :data:`RANS_WIRE_ID`), bit depth, shape, explicit side-info and
payload lengths. ``from_bytes`` validates structurally — bad magic, unknown
backend id, every truncation, and trailing garbage each raise a distinct
``ValueError`` — so corrupt blobs fail at the header, not deep inside the
rANS container.

Bit accounting follows the paper: ``total_bits`` counts payload + C*32 bits
of fp16 min/max side info; ``wire_bits`` additionally counts the container
header — the number the serving channel/scheduler actually meter.
"""
from __future__ import annotations

import struct
from dataclasses import dataclass

import numpy as np

from repro.codec import (decode_tensor, decode_tensor_batch,
                         encode_static_tensor)
from repro.core.quant import QuantParams

MAGIC = b"BaF2"
_OLD_MAGICS = (b"BaF1",)
# the header's backend id: the static rANS container. Ids 0-2 (zlib, png,
# raw) and 4 (the adaptive-context coder) belonged to retired codecs and are
# refused as unknown.
RANS_WIRE_ID = 3


def _codes_dtype(bits: int):
    return (np.uint8 if bits <= 8
            else (np.uint16 if bits <= 16 else np.uint32))


# ---------------------------------------------------------------------------
# Wire format
# ---------------------------------------------------------------------------

@dataclass
class EncodedTensor:
    payload: bytes          # rANS container (repro/codec/container.py)
    bits: int
    shape: tuple            # original codes shape, channel-last
    side_info: bytes        # fp16 mins/maxs

    def total_bits(self) -> int:
        """Paper-style accounting: payload + C*32 side-info bits."""
        return 8 * (len(self.payload) + len(self.side_info))

    def header_bytes(self) -> int:
        return 7 + 4 * len(self.shape) + 8

    def wire_bits(self) -> int:
        """Everything that crosses the channel: header + side info + payload.

        This is what the serving channel meters and the scheduler budgets;
        ``total_bits`` stays the paper's (header-free) reporting quantity.
        """
        return 8 * (self.header_bytes() + len(self.side_info)
                    + len(self.payload))

    def to_bytes(self) -> bytes:
        hdr = struct.pack("<4sB B B", MAGIC, RANS_WIRE_ID,
                          self.bits, len(self.shape))
        hdr += struct.pack(f"<{len(self.shape)}I", *self.shape)
        hdr += struct.pack("<II", len(self.side_info), len(self.payload))
        return hdr + self.side_info + self.payload

    @classmethod
    def from_bytes(cls, data: bytes) -> "EncodedTensor":
        if len(data) < 7:
            raise ValueError(
                f"truncated wire header: {len(data)} bytes, need >= 7")
        magic, backend_id, bits, ndim = struct.unpack_from("<4sB B B", data, 0)
        if magic in _OLD_MAGICS:
            raise ValueError(
                f"unsupported wire-format version {magic.decode('ascii', 'replace')} "
                f"(this build writes {MAGIC.decode('ascii')}; re-encode)")
        if magic != MAGIC:
            raise ValueError(f"bad magic {magic!r}")
        if backend_id != RANS_WIRE_ID:
            raise ValueError(f"unknown backend id {backend_id}")
        off = 7
        if off + 4 * ndim + 8 > len(data):
            raise ValueError(
                f"truncated wire header: {ndim}-d shape + lengths need "
                f"{off + 4 * ndim + 8} bytes, have {len(data)}")
        shape = struct.unpack_from(f"<{ndim}I", data, off)
        off += 4 * ndim
        silen, plen = struct.unpack_from("<II", data, off)
        off += 8
        if off + silen > len(data):
            raise ValueError(
                f"truncated side info: header claims {silen} bytes, "
                f"{len(data) - off} remain")
        side_info = data[off:off + silen]
        off += silen
        if off + plen > len(data):
            raise ValueError(
                f"truncated payload: header claims {plen} bytes, "
                f"{len(data) - off} remain")
        payload = data[off:off + plen]
        off += plen
        if off != len(data):
            raise ValueError(
                f"{len(data) - off} bytes of trailing garbage after payload")
        return cls(payload=payload, bits=bits, shape=tuple(shape),
                   side_info=side_info)


def _pack_side_info(qp: QuantParams) -> bytes:
    mins = np.asarray(qp.mins, dtype=np.float16)
    maxs = np.asarray(qp.maxs, dtype=np.float16)
    return mins.tobytes() + maxs.tobytes()


def _unpack_side_info(data: bytes, bits: int) -> QuantParams:
    half = len(data) // 2
    mins = np.frombuffer(data[:half], dtype=np.float16)
    maxs = np.frombuffer(data[half:], dtype=np.float16)
    return QuantParams(mins=mins, maxs=maxs, bits=bits)


def encode(codes: np.ndarray, qp: QuantParams) -> EncodedTensor:
    """Entropy-code quantized channel codes (any shape, channel-last)."""
    codes = np.asarray(codes)
    return EncodedTensor(payload=encode_static_tensor(codes, qp.bits),
                         bits=qp.bits, shape=tuple(codes.shape),
                         side_info=_pack_side_info(qp))


def decode(enc: EncodedTensor) -> tuple[np.ndarray, QuantParams]:
    qp = _unpack_side_info(enc.side_info, enc.bits)
    codes = decode_tensor(enc.payload, enc.shape, enc.bits)
    return codes.astype(_codes_dtype(enc.bits)).reshape(enc.shape), qp


def decode_many(encs: "list[EncodedTensor]") -> tuple[np.ndarray,
                                                      list[QuantParams]]:
    """Decode N same-(bits, shape) tensors -> ((N, *shape), qps).

    The batched host-decode primitive behind ``repro.pipeline``'s
    ``CompressionPlan.decode_batch``: the chunks of all N containers share
    one interleaved rANS decode loop (``repro.codec.batch``), bit-identical
    to decoding each alone.
    """
    if not encs:
        raise ValueError("decode_many needs at least one tensor")
    first = encs[0]
    for e in encs[1:]:
        if (e.bits, e.shape) != (first.bits, first.shape):
            raise ValueError(
                f"decode_many requires a homogeneous batch; got "
                f"({e.bits}, {e.shape}) vs ({first.bits}, {first.shape})")
    codes = decode_tensor_batch([e.payload for e in encs], first.shape,
                                first.bits)
    codes = codes.astype(_codes_dtype(first.bits), copy=False).reshape(
        (len(encs),) + tuple(first.shape))
    qps = [_unpack_side_info(e.side_info, e.bits) for e in encs]
    return codes, qps


def empirical_entropy_bits(codes: np.ndarray, bits: int) -> float:
    """Order-0 empirical entropy of the code stream, in total bits.

    Codec-independent floor used in benchmarks to separate "what the
    quantizer achieved" from "what the entropy coder realized".
    """
    flat = np.asarray(codes).ravel()
    if flat.size == 0:
        return 0.0
    counts = np.bincount(flat.astype(np.int64), minlength=1 << bits)
    p = counts[counts > 0] / flat.size
    return float(-np.sum(p * np.log2(p)) * flat.size)
