"""Pallas TPU kernel: fused Gram-statistics update  G = XᵀX,  Q = XᵀY.

This is the AFL-specific compute hot spot: every analytic train step folds a
batch of backbone embeddings ``X (N, d)`` and one-hot targets ``Y (N, C)``
into the sufficient statistics. d is the model width (up to 6144 here), so G
is up to 6144² and the update is a rank-N outer-product accumulation — an MXU
matmul with a long reduction dim.

TPU mapping:
  grid = (d/bi, d/bj, N/bn); the reduction dim (N) is the innermost,
  sequential ("arbitrary") grid axis, so the f32 VMEM scratch accumulator for
  an output tile survives across its reduction steps and is flushed once.
  X tiles arrive in VMEM twice per (i, j) step — once row-blocked for the i
  side, once for the j side — with 128-aligned (bn, bi/bj) blocks feeding the
  MXU via dot_general on the transposed left operand. Q = XᵀY is fused into
  the j == 0 column of the grid so X's i-side tile is reused from VMEM instead
  of re-streamed from HBM.

Validated on CPU in interpret mode against ``repro.kernels.ref.gram_ref``
(the pure-jnp oracle) over a shape/dtype sweep in tests/test_kernels_gram.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import scopes

DEFAULT_BLOCK_D = 128   # output tile side (MXU lane-aligned)
DEFAULT_BLOCK_N = 512   # reduction chunk (sublane multiple)


def _gram_kernel(xi_ref, xj_ref, y_ref, g_ref, q_ref, g_acc, q_acc, *,
                 precision):
    """One (i, j, n) grid step.

    xi_ref: (bn, bi)  rows of X for the output-row block i
    xj_ref: (bn, bj)  rows of X for the output-col block j
    y_ref:  (bn, C)   targets (same row chunk)
    g_ref:  (bi, bj)  output tile of G
    q_ref:  (bi, C)   output tile of Q (written by the j==0 column only)
    g_acc/q_acc: f32 VMEM scratch accumulators
    """
    j = pl.program_id(1)
    n = pl.program_id(2)
    n_steps = pl.num_programs(2)

    @pl.when(n == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)

    @pl.when(jnp.logical_and(n == 0, j == 0))
    def _init_q():
        q_acc[...] = jnp.zeros_like(q_acc)

    xi = xi_ref[...].astype(jnp.float32)
    xj = xj_ref[...].astype(jnp.float32)
    # (bi, bn) @ (bn, bj) on the MXU; contraction over the row chunk.
    g_acc[...] += jax.lax.dot_general(
        xi, xj, (((0,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32
    )

    @pl.when(j == 0)
    def _q_update():
        y = y_ref[...].astype(jnp.float32)
        q_acc[...] += jax.lax.dot_general(
            xi, y, (((0,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32
        )

    @pl.when(n == n_steps - 1)
    def _flush():
        g_ref[...] = g_acc[...].astype(g_ref.dtype)

    @pl.when(jnp.logical_and(n == n_steps - 1, j == 0))
    def _flush_q():
        q_ref[...] = q_acc[...].astype(q_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_d", "block_n", "interpret", "out_dtype")
)
@jax.named_scope(scopes.GRAM_FOLD)
def gram_update(
    x: jax.Array,
    y: jax.Array,
    *,
    block_d: int = DEFAULT_BLOCK_D,
    block_n: int = DEFAULT_BLOCK_N,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Compute (XᵀX, XᵀY) with the fused Pallas kernel.

    x: (N, d) embeddings (any float dtype; accumulation is f32).
    y: (N, C) targets.
    Shapes are padded up to block multiples here in the wrapper; zero rows
    contribute nothing to either product so padding is exact.
    """
    n, d = x.shape
    n2, c = y.shape
    assert n == n2, (n, n2)
    bd = min(block_d, _ceil_mult(d, 128))
    bn = min(block_n, _ceil_mult(n, 8))
    d_p = _ceil_mult(d, bd)
    n_p = _ceil_mult(n, bn)
    c_p = _ceil_mult(c, 128)
    if (d_p, n_p) != (d, n):
        x = jnp.pad(x, ((0, n_p - n), (0, d_p - d)))
    if (n_p, c_p) != (n, c):
        y = jnp.pad(y, ((0, n_p - n), (0, c_p - c)))

    # The MXU multiplies bf16 operands exactly in one pass; any other dtype
    # (f32 embeddings) needs its full-f32 passes, or each product keeps only
    # ~8 mantissa bits (measured 4.5e-4 max relative Gram error on a v5e).
    bf16 = x.dtype == jnp.bfloat16 and y.dtype == jnp.bfloat16
    precision = None if bf16 else jax.lax.Precision.HIGHEST

    grid = (d_p // bd, d_p // bd, n_p // bn)
    g, q = pl.pallas_call(
        functools.partial(_gram_kernel, precision=precision),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, n: (n, i)),  # X rows, i-side
            pl.BlockSpec((bn, bd), lambda i, j, n: (n, j)),  # X rows, j-side
            pl.BlockSpec((bn, c_p), lambda i, j, n: (n, 0)),  # Y rows
        ],
        out_specs=[
            pl.BlockSpec((bd, bd), lambda i, j, n: (i, j)),
            pl.BlockSpec((bd, c_p), lambda i, j, n: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d_p, d_p), out_dtype),
            jax.ShapeDtypeStruct((d_p, c_p), out_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bd, bd), jnp.float32),
            pltpu.VMEM((bd, c_p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="gram_update",
    )(x, x, y)
    return g[:d, :d], q[:d, :c]


def _ceil_mult(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m
