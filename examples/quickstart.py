"""Quickstart: the paper's pipeline on one tensor, in ~60 lines.

    PYTHONPATH=src python examples/quickstart.py

Takes a feature tensor, selects the most-correlated channel subset (eqs. 2-3),
quantizes + entropy-codes it (eqs. 4-5; rANS on the channel-last codes, where
the paper tiles them into an image for FLIF, §3.2), restores the full
tensor with an (untrained) BaF predictor (§3.3) and consolidates the
transmitted channels (eq. 6), printing real wire bits at every stage.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import nn
from repro.core import codec as wire
from repro.core.baf import BaFConvConfig, baf_conv_predict, init_baf_conv
from repro.core.quant import QuantParams, compute_quant_params, dequantize, quantize
from repro.core.selection import correlation_matrix_conv, select_channels

B, H, W, P, Q, C, BITS = 2, 16, 16, 64, 32, 16, 8

key = jax.random.PRNGKey(0)
# a stand-in split layer: X (B, 2H, 2W, Q) --conv s2 + BN--> Z (B, H, W, P)
x = jax.random.normal(key, (B, 2 * H, 2 * W, Q))
conv = nn.init_conv(jax.random.PRNGKey(1), Q, P, 3, bias=False)
bn = nn.init_batchnorm(P)
z = nn.batchnorm_apply(bn, nn.conv_apply(conv, x, stride=2))
print(f"split tensor Z: {z.shape}, raw fp32 = {z.size * 32:,} bits")

# 1. channel selection (offline, eqs. 2-3)
rho = correlation_matrix_conv(z, x)
order = select_channels(rho).order
sel = jnp.asarray(order[:C])
print(f"selected C={C} of P={P} channels: {np.asarray(sel)[:8]}...")

# 2. quantize (eq. 4) + entropy-code
z_sel = z[..., sel]
qp = compute_quant_params(z_sel, BITS, per_example=True)
codes = quantize(z_sel, qp)
enc = wire.encode(np.asarray(codes), qp)
blob = enc.to_bytes()
print(f"wire: {enc.total_bits():,} bits "
      f"({8 * len(enc.side_info):,} side info) -> "
      f"{1 - enc.total_bits() / (z.size * 32):.1%} smaller than raw fp32")

# 3. cloud: decode (eq. 5) + BaF restore (§3.3) + consolidation (eq. 6)
dec = wire.EncodedTensor.from_bytes(blob)
codes_rx, qp_rx = wire.decode(dec)
codes_rx = jnp.asarray(codes_rx)
qp_rx = QuantParams(mins=jnp.asarray(qp_rx.mins).reshape(B, 1, 1, C),
                    maxs=jnp.asarray(qp_rx.maxs).reshape(B, 1, 1, C),
                    bits=BITS)
z_hat_sel = dequantize(codes_rx, qp_rx)
print(f"decode exact: {bool(jnp.all(codes_rx == codes))}, "
      f"dequant err <= step/2: "
      f"{float(jnp.max(jnp.abs(z_hat_sel - z_sel))):.4f}")

baf = init_baf_conv(jax.random.PRNGKey(2), BaFConvConfig(c=C, q=Q, hidden=32))
z_tilde = baf_conv_predict(baf, conv, bn, sel, z_hat_sel,
                           codes=codes_rx, qp=qp_rx)
print(f"restored all-P tensor: {z_tilde.shape} (untrained predictor; "
      f"examples/split_inference.py trains it end to end)")
# the transmitted channels are consolidated: they sit inside their bins
from repro.core.quant import bin_bounds
lo, hi = bin_bounds(codes_rx, qp_rx)
inside = bool(jnp.all((z_tilde[..., sel] >= lo - 1e-4)
                      & (z_tilde[..., sel] <= hi + 1e-4)))
print(f"eq. (6) consolidation holds on transmitted channels: {inside}")
