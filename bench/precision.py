"""Matrix products of the plain references, at a stated precision.

Every product of a reference goes through :func:`einsum`, which rounds its
operands as the precision says and then multiplies them exactly in float32
(``precision="highest"``), so the same precision reads the same on a TPU and
on a CPU. The rounding is ``lax.reduce_precision``, which the compiler keeps:
a float32 → bfloat16 → float32 round trip of ``astype`` may be removed as
excess precision where it feeds a product, and on a TPU it was. Elementwise
arithmetic stays float32 throughout.

``highest``  float32 operands: the reference itself.
``high``     each operand split into two bfloat16 parts and three of the four
             cross products kept — the TPU's three-pass ``bf16_3x``; the
             control for a configuration that states float32 at highest.
``bf16``     operands rounded to bfloat16, one product: the control for other
             float32.
``fp8``      operands scaled per tensor into 4 exponent and 3 mantissa
             bits (e4m3) and rounded: the control for a configuration that
             states bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

KINDS = ("highest", "high", "bf16", "fp8")
FP8_MAX = 240.0     # largest finite value of 4 exponent and 3 mantissa bits


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return jax.lax.reduce_precision(a / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def einsum(spec: str, a, b, kind: str = "highest"):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    ex = lambda x, y: jnp.einsum(spec, x, y, precision="highest",
                                 preferred_element_type=jnp.float32)
    if kind == "highest":
        return ex(a, b)
    if kind == "high":
        a_hi, b_hi = _bf16(a), _bf16(b)
        a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
        return ex(a_hi, b_hi) + (ex(a_hi, b_lo) + ex(a_lo, b_hi))
    if kind == "bf16":
        return ex(_bf16(a), _bf16(b))
    if kind == "fp8":
        return ex(_fp8(a), _fp8(b))
    raise ValueError(f"unknown precision {kind!r}; one of {KINDS}")


def matmul(a, b, kind: str = "highest"):
    """``a @ b`` over the last axis of ``a``."""
    return einsum("...i,ij->...j", a, b, kind)


def control(cfg: dict) -> str:
    """The precision of a configuration's control: the next below the one it
    states (``dtype`` of the weights and ``matmul_precision``)."""
    if cfg["dtype"] == "bfloat16":
        return "fp8"
    if cfg["dtype"] == "float32":
        return "high" if cfg["matmul_precision"] == "highest" else "bf16"
    raise ValueError(f"no control precision below {cfg['dtype']!r}")
