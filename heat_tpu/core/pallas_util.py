"""Shared Pallas-kernel helpers.

``dot_f32`` is the precision dispatcher for in-kernel f32 contractions.
Besides the ``jax.lax.Precision`` tiers it accepts ``"bf16x3"``: an
explicit three-pass bf16 split-product — ``a·b ≈ hi(a)·hi(b) +
hi(a)·lo(b) + lo(a)·hi(b)`` with ``hi(x) = bf16(x)`` and
``lo(x) = bf16(x − hi(x))`` — which is numerically the classical bf16x3
compensation (the same error class as ``Precision.HIGH``) but built from
three DEFAULT-tier dots that Mosaic provably lowers onto the MXU. The
round-5 builder-side on-chip capture (figures in ROADMAP.md) measured
the HIGH-tier in-kernel dot at ~36× below the cdist write roofline —
consistent with an off-MXU (VPU-loop) lowering — so guaranteed-MXU
multi-pass form matters independently of the enum tiers.
"""

from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp

__all__ = ["dot_f32", "DotPrecision"]

DotPrecision = Union[jax.lax.Precision, str]


def dot_f32(a, b, dimension_numbers, precision: DotPrecision):
    """f32-accumulated dot_general with a sweepable precision strategy.

    ``precision`` is a ``jax.lax.Precision`` tier (the enum or its name as
    a string, e.g. ``"HIGHEST"``) passed through to one ``dot_general``,
    or the string ``"bf16x3"`` for the explicit MXU-guaranteed three-pass
    split product.
    """
    if isinstance(precision, str) and precision != "bf16x3":
        precision = getattr(jax.lax.Precision, precision)
    if precision == "bf16x3":
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        a_hi = a.astype(jnp.bfloat16)
        b_hi = b.astype(jnp.bfloat16)
        a_lo = (a - a_hi.astype(jnp.float32)).astype(jnp.bfloat16)
        b_lo = (b - b_hi.astype(jnp.float32)).astype(jnp.bfloat16)

        def _d(x, y):
            return jax.lax.dot_general(
                x, y, dimension_numbers,
                preferred_element_type=jnp.float32,
            )

        # hi·lo + lo·hi first: the small terms accumulate before the
        # dominant hi·hi lands (marginally better rounding, same passes)
        return (_d(a_hi, b_lo) + _d(a_lo, b_hi)) + _d(a_hi, b_hi)
    return jax.lax.dot_general(
        a, b, dimension_numbers,
        precision=precision,
        preferred_element_type=jnp.float32,
    )
