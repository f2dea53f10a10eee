"""Single import site for the Pallas modules (lint rule RA03).

``jax.experimental`` is an unstable namespace — pallas has already moved
once, and its TPU compiler-params class was renamed — so kernels spell

    from repro.kernels.compat import CompilerParams, pl, pltpu

and a future move is absorbed here, in one place, instead of in every
kernel. The repo targets the installed jax only.
"""
from __future__ import annotations

# the import shim boundary: raw jax.experimental is allowed here and in
# repro/compat.py only (both files are RA03-exempt by config)
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["CompilerParams", "pl", "pltpu"]

CompilerParams = pltpu.CompilerParams
