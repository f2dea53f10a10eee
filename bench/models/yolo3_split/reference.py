"""Plain reference of the served path: image -> logits, in ``jax.numpy``.

Written from the paper (arXiv 2002.07036: the YOLOv3 Darknet-53 front
through layer l=12, eqs. (4)-(6) and Fig. 2 for quantization, BaF
prediction and consolidation) and imports nothing of the program. It reads
only the benchmark's own seeded weights and images.

    edge      stem (conv, BN, leaky ReLU 0.1, residual pairs), split conv
              (3x3, stride 2) and its BN: Z
    quantize  the C transmitted channels of each image, per channel:
              m, M = min, max over the image, stored at fp16; M is raised
              to the next fp16 value, so that fp16 rounding of the max can
              never push a code past 2^n - 1; code = round((Z - m) /
              (M - m) * (2^n - 1)), clipped to [0, 2^n - 1]       (eq. 4);
              on the host in numpy, so that each operation rounds once as
              IEEE arithmetic does (a TPU fusion of the same division can
              be off by more than an ulp, which moves codes at bin edges)
    entropy   the rANS round trip is lossless: the codes come back as sent
    restore   dequantize (eq. 5), inverse BN of the transmitted channels,
              transposed 3x3 conv (x2), PReLU, two 3x3 convs with PReLU,
              3x3 conv to Q channels, then the split conv and BN forward;
              consolidation clips the C transmitted channels into their
              received bins [m + (k - 1/2) s, m + (k + 1/2) s], s = (M - m)
              / (2^n - 1)                                      (eq. 6)
    cloud     leaky ReLU, residual blocks (1x1 conv, 3x3 conv, each with BN
              and leaky ReLU), global average pool, dense head

Arithmetic is in ``dtype`` throughout (float32 for the reference, the
configuration's type; bfloat16 for the control), with matmuls and convs at
the configuration's stated precision. The side information stays fp16: it
is the wire format, not a compute type.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LEAK = 0.1
BN_EPS = 1e-5
DIMS = ("NHWC", "HWIO", "NHWC")
PRECISION = {"default": lax.Precision.DEFAULT, "high": lax.Precision.HIGH,
             "highest": lax.Precision.HIGHEST}
STEM_STRIDES = (1, 2, 1, 1, 2, 1, 1, 1, 1)
RES_IN = (2, 5, 7)
RES_OUT = (3, 6, 8)


def _conv(x, p, stride, prec):
    y = lax.conv_general_dilated(x, p["w"], (stride, stride), "SAME",
                                 dimension_numbers=DIMS, precision=prec)
    return y + p["b"] if "b" in p else y


def _bn(x, p):
    return (x - p["mean"]) * lax.rsqrt(p["var"] + BN_EPS) * p["scale"] \
        + p["bias"]


def _bn_inverse(z, p):
    return (z - p["bias"]) / p["scale"] * jnp.sqrt(p["var"] + BN_EPS) \
        + p["mean"]


def _leaky(x):
    return jnp.where(x >= 0, x, LEAK * x)


def _prelu(x, p):
    return jnp.where(x >= 0, x, p["alpha"] * x)


def edge(params, img, prec):
    x = img
    for i, (p, s) in enumerate(zip(params["stem"], STEM_STRIDES)):
        if i in RES_IN:
            shortcut = x
        x = _leaky(_bn(_conv(x, p["conv"], s, prec), p["bn"]))
        if i in RES_OUT:
            x = x + shortcut
    split = params["split"]
    return _bn(_conv(x, split["conv"], 2, prec), split["bn"])


def quantize(z_sel: np.ndarray, bits: int, dtype):
    """(codes uint8/16, m, M) with m, M (B, 1, 1, C) fp16 per image, from
    ``z_sel`` (B, H, W, C) on the host, with arithmetic in ``dtype``."""
    levels = (1 << bits) - 1
    dt = np.dtype(dtype)
    f16_max = np.float16(65504.0)
    m = np.maximum(z_sel.min(axis=(1, 2), keepdims=True).astype(np.float16),
                   -f16_max)
    M = z_sel.max(axis=(1, 2), keepdims=True).astype(np.float16)
    M = np.minimum(np.nextafter(M, np.float16(np.inf)), f16_max)
    mm, MM = m.astype(dt), M.astype(dt)
    scaled = ((z_sel.astype(dt) - mm) / np.maximum(MM - mm, dt.type(1e-12))
              * dt.type(levels))
    codes = np.clip(np.rint(scaled.astype(np.float32)), 0, levels)
    return codes.astype(np.uint8 if bits <= 8 else np.uint16), m, M


def restore(params, baf, sel, codes, m, M, bits: int, prec, dtype):
    levels = (1 << bits) - 1
    mm, MM = m.astype(dtype), M.astype(dtype)
    k = codes.astype(dtype)
    z_hat = k / levels * (MM - mm) + mm                              # eq. (5)
    split = params["split"]
    bn_sel = {name: v[sel] for name, v in split["bn"].items()}
    x = _bn_inverse(z_hat, bn_sel)
    x = lax.conv_transpose(x, baf["up"]["w"], (2, 2), "SAME",
                           dimension_numbers=DIMS, precision=prec) \
        + baf["up"]["b"]
    x = _prelu(x, baf["up_act"])
    x = _prelu(_conv(x, baf["c2"], 1, prec), baf["c2_act"])
    x = _prelu(_conv(x, baf["c3"], 1, prec), baf["c3_act"])
    x = _conv(x, baf["c4"], 1, prec)
    z_tilde = _bn(_conv(x, split["conv"], 2, prec), split["bn"])
    step = (MM - mm) / levels
    lo = mm + (k - 0.5) * step
    hi = mm + (k + 0.5) * step
    kept = jnp.clip(z_tilde[..., sel], lo, hi)                      # eq. (6)
    return z_tilde.at[..., sel].set(kept)


def cloud(params, z, prec):
    x = _leaky(z)
    tail = params["tail"]
    for i in range(0, len(tail), 2):
        shortcut = x
        x = _leaky(_bn(_conv(x, tail[i]["conv"], 1, prec), tail[i]["bn"]))
        x = _leaky(_bn(_conv(x, tail[i + 1]["conv"], 1, prec),
                       tail[i + 1]["bn"]))
        x = x + shortcut
    feat = jnp.mean(x, axis=(1, 2))
    head = params["head"]
    return jnp.dot(feat, head["w"], precision=prec) + head["b"]


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


@lru_cache(maxsize=None)
def _edge_jit(precision: str, dtype: str):
    """One image's edge half, up to the split tensor Z: an edge device's
    step."""
    prec, dt = PRECISION[precision], jnp.dtype(dtype)

    def fn(params, img):
        return edge(params, img.astype(dt), prec)
    return jax.jit(fn)


@lru_cache(maxsize=None)
def _cloud_jit(precision: str, dtype: str):
    """A block of images' cloud half, from codes and side info to logits."""
    prec, dt = PRECISION[precision], jnp.dtype(dtype)

    def fn(params, baf, sel, codes, m, M, *, bits):
        z_tilde = restore(params, baf, sel, codes, m, M, bits, prec, dt)
        return cloud(params, z_tilde, prec).astype(jnp.float32)
    return jax.jit(fn, static_argnames="bits")


def logits(cfg: dict, weights, images: np.ndarray, *, dtype: str = "float32",
           block: int = 8) -> np.ndarray:
    """Reference logits (N, classes) float32 of ``images``.

    The edge half runs one image at a time, as each edge device runs it;
    the cloud half runs ``block`` images at a time (the last block padded
    by repeating its last image), so that it fits beside nothing else on
    the chip.
    """
    params, baf, sel = weights
    params, baf = _cast(params, dtype), _cast(baf, dtype)
    bits, precision = cfg["bits"], cfg["matmul_precision"]
    edge_fn = _edge_jit(precision, dtype)
    cloud_fn = _cloud_jit(precision, dtype)
    sel_np = np.asarray(sel)
    sent = [quantize(np.asarray(edge_fn(params, jnp.asarray(img[None])))
                     [..., sel_np], bits, jnp.dtype(dtype))
            for img in images]
    out = []
    for i in range(0, len(sent), block):
        part = sent[i:i + block]
        n = len(part)
        part = part + [part[-1]] * (block - n)
        codes, m, M = (jnp.asarray(np.concatenate(x)) for x in zip(*part))
        out.append(np.asarray(cloud_fn(params, baf, sel, codes, m, M,
                                       bits=bits))[:n])
    return np.concatenate(out)
