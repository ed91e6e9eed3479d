"""Rounding to a narrower type that XLA may not optimise away.

A convert to a narrower type followed by one back is dropped by XLA's
simplifier ("excess precision"): a reference that stores bfloat16 through a
pair of ``astype`` never rounds, and a control quantised that way is the
reference itself (both were seen on the chip, PERF.md section 2).
``lax.reduce_precision`` is the operation XLA has to honour.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def store(x, dtype):
    """``x`` rounded to the stored type ``dtype``."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        x = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x.astype(dtype)


def quantiser(quant):
    """The rounding a reference applies to what the configuration holds in
    bfloat16: ``None`` is the reference itself (no rounding); ``"fp8"`` is
    the control, the nearest precision below: float8_e4m3's 4 exponent and
    3 mantissa bits, the tensor scaled to 224 (inside that format's range)
    first and back after, with a straight-through gradient."""
    if quant is None:
        return lambda x: x
    if quant != "fp8":
        raise ValueError("unknown precision %r" % (quant,))
    top = 224.0

    def q(x):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        rounded = lax.reduce_precision(x / scale, exponent_bits=4,
                                       mantissa_bits=3) * scale
        return x + lax.stop_gradient(rounded - x)

    return q
