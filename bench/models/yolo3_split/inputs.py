"""Seeded weights and input images of the split YOLOv3-front model.

Both are made on the device in one jitted call each, from ``--seed``. The
weights come out in the layout the serving program takes (a CNN pytree, one
BaF predictor and the transmitted channel subset); the plain reference
reads the same arrays. BN statistics and biases are random too, so that
every BN, inverse BN and bias term does work that a comparison can see.

The images are the shapes task (class k = a ring of k + 3 Gaussian blobs in
one colour, plus noise), a copy of the program's synthetic generator.
"""
from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

# (in, out, kernel, stride) of the nine stem convs at width 1; residual
# pairs are (2, 3), (5, 6), (7, 8): the pair's input is added after it
STEM = ((3, 32, 3, 1), (32, 64, 3, 2), (64, 32, 1, 1), (32, 64, 3, 1),
        (64, 128, 3, 2), (128, 64, 1, 1), (64, 128, 3, 1), (128, 64, 1, 1),
        (64, 128, 3, 1))
RES_IN = (2, 5, 7)
RES_OUT = (3, 6, 8)


def ch(cfg: dict, c: int) -> int:
    return max(4, int(round(c * cfg["width_mult"])))


def key(seed: int, stream: int):
    """A JAX key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def _conv(k, cin, cout, ksize, bias):
    kw, kb = jax.random.split(k)
    p = {"w": jax.random.normal(kw, (ksize, ksize, cin, cout))
         * math.sqrt(2.0 / (cin * ksize * ksize))}
    if bias:
        p["b"] = 0.05 * jax.random.normal(kb, (cout,))
    return p


def _bn(k, c):
    k1, k2, k3, k4 = jax.random.split(k, 4)
    return {"scale": jax.random.uniform(k1, (c,), minval=0.5, maxval=1.5),
            "bias": 0.1 * jax.random.normal(k2, (c,)),
            "mean": 0.1 * jax.random.normal(k3, (c,)),
            "var": jax.random.uniform(k4, (c,), minval=0.5, maxval=1.5)}


def _conv_bn(k, cin, cout, ksize):
    k1, k2 = jax.random.split(k)
    return {"conv": _conv(k1, cin, cout, ksize, bias=False),
            "bn": _bn(k2, cout)}


def _make(k, *, frozen: tuple):
    cfg = dict(frozen)
    c = cfg["c"]
    p_ch, q_ch, hid = ch(cfg, 256), ch(cfg, 128), cfg["baf_hidden"]
    ks = iter(jax.random.split(k, 64))
    params = {
        "stem": [_conv_bn(next(ks), 3 if i == 0 else ch(cfg, cin),
                          ch(cfg, cout), ksize)
                 for i, (cin, cout, ksize, _) in enumerate(STEM)],
        "split": _conv_bn(next(ks), q_ch, p_ch, 3),
        "tail": [],
    }
    for _ in range(cfg["tail_res_blocks"]):
        params["tail"].append(_conv_bn(next(ks), p_ch, ch(cfg, 128), 1))
        params["tail"].append(_conv_bn(next(ks), ch(cfg, 128), p_ch, 3))
    kw, kb = jax.random.split(next(ks))
    params["head"] = {
        "w": jax.random.normal(kw, (p_ch, cfg["num_classes"]))
        / math.sqrt(p_ch),
        "b": 0.05 * jax.random.normal(kb, (cfg["num_classes"],))}

    def prelu(kk):
        return {"alpha": jax.random.uniform(kk, (hid,), minval=0.1,
                                            maxval=0.3)}
    baf = {"up": _conv(next(ks), c, hid, 3, bias=True),
           "up_act": prelu(next(ks)),
           "c2": _conv(next(ks), hid, hid, 3, bias=True),
           "c2_act": prelu(next(ks)),
           "c3": _conv(next(ks), hid, hid, 3, bias=True),
           "c3_act": prelu(next(ks)),
           "c4": _conv(next(ks), hid, q_ch, 3, bias=True)}
    sel = jnp.sort(jax.random.permutation(next(ks), p_ch)[:c]).astype(
        jnp.int32)
    return params, baf, sel


def _freeze(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@lru_cache(maxsize=None)
def _make_jit(frozen: tuple):
    return jax.jit(partial(_make, frozen=frozen))


def make_weights(cfg: dict, seed: int):
    """(cnn params, BaF params, selected channels (C,) int32), float32, on
    the device, from one jitted call."""
    return jax.block_until_ready(_make_jit(_freeze(cfg))(key(seed, 0)))


def _render(k, *, size: int, n: int, num_classes: int, noise: float = 0.15):
    k_lbl, k_pos, k_rad, k_noise, k_col = jax.random.split(k, 5)
    labels = jax.random.randint(k_lbl, (n,), 0, num_classes)
    cx = jax.random.uniform(k_pos, (n, 2), minval=0.3, maxval=0.7) * size
    radius = jax.random.uniform(k_rad, (n,), minval=0.15, maxval=0.3) * size
    colors = jax.random.uniform(k_col, (n, 3), minval=0.4, maxval=1.0)
    yy, xx = jnp.mgrid[0:size, 0:size]

    def one(args):
        label, c, r, col = args
        n_blobs = label + 3
        ang = jnp.arange(12) * (2 * jnp.pi / jnp.maximum(n_blobs, 1))
        active = jnp.arange(12) < n_blobs
        bx = c[0] + r * jnp.cos(ang)
        by = c[1] + r * jnp.sin(ang)
        d2 = ((xx[None] - bx[:, None, None]) ** 2
              + (yy[None] - by[:, None, None]) ** 2)
        blob = jnp.exp(-d2 / (2 * (0.06 * size) ** 2)) * active[:, None, None]
        return jnp.max(blob, axis=0)[..., None] * col[None, None, :]

    imgs = jax.lax.map(one, (labels, cx, radius, colors))
    imgs = imgs + noise * jax.random.normal(k_noise, imgs.shape)
    return imgs.astype(jnp.float32)


def make_images(cfg: dict, seed: int, n: int) -> np.ndarray:
    """A pool of ``n`` images (n, S, S, 3) float32, rendered on the device
    in one jitted call and handed to the host as a client would send them."""
    return np.asarray(_render_jit(cfg["input_size"], n,
                                  cfg["num_classes"])(key(seed, 1)))


@lru_cache(maxsize=None)
def _render_jit(size: int, n: int, num_classes: int):
    return jax.jit(partial(_render, size=size, n=n, num_classes=num_classes))
