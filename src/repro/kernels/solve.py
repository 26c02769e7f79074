"""Pallas TPU kernels for the AFL aggregate solve: blocked Cholesky,
batched triangular solves, and the fused multi-γ sweep.

AFL's single round ends in ONE linear solve, ``(C_agg + γI) W = Q_agg``, plus
the RI-ablation γ-sweep that repeats it over a ridge grid — at d=2048 the
PR-3 sweep spent ~40% of wall time in the per-γ host loop (interpreter +
per-call BLAS dispatch + a fresh ``C + γI`` materialization each iteration).
These kernels move the whole factor→sweep pipeline into ``pallas_call``s:

  * :func:`blocked_cholesky` — a right-looking blocked Cholesky over a batch
    of SPD systems. Panels are unrolled at trace time so every trsm/syrk
    tile update is a static-shape MXU matmul at the true d³/3 flop count;
    only the ``block``-column micro-factorizations run as ``fori_loop``
    column sweeps (O(d) cheap sequential steps total, each touching one
    ``block``² tile batched over the whole system batch).
  * :func:`cholesky_solve` — the batched forward/backward substitution
    against those factors, blocked the same way (per-panel inverse diagonal
    blocks turn the substitution recurrences into matmuls).
  * :func:`multi_gamma_solve` — the fused sweep: ONE ``pallas_call`` whose
    grid walks γ-blocks; each step materializes ``C + γ_j I`` for its block
    of γs in registers/VMEM, factors all of them batched, and solves for
    ``W(γ_j)`` — no host loop, no per-γ dispatch, one ``C`` fetch per block.

Precision variants (the ``precision`` argument):

  * ``"native"`` — compute in the input dtype: f32 by default, or **native
    f64** end-to-end under ``jax_enable_x64`` (the 1e-10-vs-numpy parity
    configuration locked down by ``tests/test_solve_kernels.py``).
  * ``"f32_x2"`` — f32 storage with **emulated-f64 products**: every
    trsm/syrk/substitution matmul splits its operands into exact high/low
    12-bit-mantissa halves (Dekker splitting) and accumulates the three
    significant cross products, so the MXU contractions carry ~2× the f32
    mantissa. Remaining error is f32 accumulation + the scalar
    sqrt/reciprocal path — measured ~1 decade better than plain f32 on the
    d=2048 sweep (see ``benchmarks/solve_kernels_bench.py``).

On TPU the calls compile through Mosaic with the whole batched system
resident in VMEM — which bounds native occupancy to roughly d ≤ 1024 at f32
per core (the kernels raise the scoped VMEM limit and step one system at a
time at that width). Wider single systems stream panels through VMEM
(:func:`streamed_cholesky`); the serving path at d=6144 shards the Gram
itself (``repro.fl.api.ShardedCoordinator(tiled_gram=True)``). Off-TPU the
kernels execute in interpret mode (``repro.kernels.ops`` defaults) — which
is how this repo's CI exercises them, and fast enough to beat the host
per-γ loop ~3× at d=2048 (measured, ``results/bench/solve_kernels_bench.json``).

Rank-deficient systems (the γ=0 ablations) are NOT special-cased here: a
singular system yields NaNs, which callers (``AnalyticEngine``) detect and
route to the eigendecomposition/pinv host path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "blocked_cholesky",
    "cholesky_solve",
    "multi_gamma_solve",
    "panel_factor",
    "panel_tri_inv",
    "panel_trsm",
    "panel_update",
    "tile_cholesky_factor",
    "tile_cholesky_solve",
    "streamed_cholesky",
    "streamed_cholesky_solve",
    "chol_rank_update",
    "panel_width",
    "DEFAULT_BLOCK",
    "DEFAULT_GAMMA_BLOCK",
    "DEFAULT_STREAM_BLOCK",
    "STREAM_MIN_DIM",
]

DEFAULT_BLOCK = 128        # panel width: MXU-lane multiple, 2·d fori steps
DEFAULT_GAMMA_BLOCK = 8    # γs factored together per fused-sweep grid step
DEFAULT_BATCH_BLOCK = 8    # systems per grid step for the batched kernels
DEFAULT_STREAM_BLOCK = 256   # panel width for the HBM-streamed single-system path
DEFAULT_UPDATE_BLOCK = 256   # row/col tile edge for the streamed syrk grid
STREAM_MIN_DIM = 2048      # engine routes single systems this wide to streaming

_SPLIT = 4097.0            # 2^12 + 1: Dekker split constant for f32


# ---------------------------------------------------------------------------
# In-kernel building blocks (trace-time helpers on (batch, ·, ·) values)
# ---------------------------------------------------------------------------


def _split(a):
    """Dekker split: a == hi + lo with 12-bit-mantissa halves (exact in f32,
    so every pairwise product of halves is exact in f32)."""
    t = a * _SPLIT
    hi = t - (t - a)
    return hi, (a - hi)


# dot_general dimension numbers: batched (b, ·, ·) tiles and plain 2-D tiles
_BDIMS_NN = (((2,), (1,)), ((0,), (0,)))   # a @ b
_BDIMS_NT = (((2,), (2,)), ((0,), (0,)))   # a @ bᵀ
_BDIMS_TN = (((1,), (1,)), ((0,), (0,)))   # aᵀ @ b
_DIMS_NN = (((1,), (0,)), ((), ()))       # a @ b
_DIMS_NT = (((1,), (1,)), ((), ()))       # a @ bᵀ
_DIMS_TN = (((0,), (0,)), ((), ()))       # aᵀ @ b


def _make_mm(precision: str, dims):
    """Tile matmul with dimension numbers ``dims`` at the requested
    precision: native dtype, or the 3-product emulated-f64 split.

    Every product asks for the MXU's full-f32 passes: a TPU's default for
    f32 operands is one bf16 pass, which left the d=1024 solves ~5e-3 off
    the f64 reference on a v5e (``chip_smoke.py``)."""

    def mm(a, b):
        return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                               preferred_element_type=a.dtype)

    if precision != "f32_x2":
        return mm

    def mm_x2(a, b):
        ah, al = _split(a)
        bh, bl = _split(b)
        return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))

    return mm_x2


# Mosaic lowers no value indexed by a traced loop counter (``s[:, j, j]``,
# ``s.at[:, :, j].set``), so the column sweeps below pick and place rows and
# columns with iota masks: a masked reduction reads one, a select writes one.
# Each is O(m²) VPU work per step on a tile the step touches in full anyway.


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _pick_col(a, j):
    """``a[..., :, j]`` as a ``(..., m, 1)`` column."""
    hit = _iota(a.shape, a.ndim - 1) == j
    return jnp.sum(jnp.where(hit, a, jnp.zeros_like(a)), axis=-1,
                   keepdims=True)


def _pick_row(a, i):
    """``a[..., i, :]`` as a ``(..., 1, n)`` row."""
    hit = _iota(a.shape, a.ndim - 2) == i
    return jnp.sum(jnp.where(hit, a, jnp.zeros_like(a)), axis=-2,
                   keepdims=True)


def _factor_tile(tile):
    """Unblocked Cholesky of SPD tiles ``(..., m, m)`` → lower L.

    A ``fori_loop`` column sweep with masked full-width updates, so every
    iteration has static shapes (VPU work on one tile, batched); the upper
    triangle is written as zeros. The trailing block stays symmetric through
    the sweep, so row j past the pivot is column j transposed. A non-PD tile
    yields NaNs (sqrt of a non-positive pivot) that propagate to the
    caller's fallback check.
    """
    rows = _iota(tile.shape[:-1] + (1,), tile.ndim - 2)
    cols = _iota(tile.shape[:-2] + (1, tile.shape[-1]), tile.ndim - 1)
    lanes = _iota(tile.shape, tile.ndim - 1)

    def body(j, s):
        col = _pick_col(s, j)                        # (..., m, 1)
        pv = jnp.sqrt(_pick_row(col, j))             # (..., 1, 1)
        colm = jnp.where(rows > j, col / pv, jnp.zeros_like(col))
        rowm = jnp.where(cols > j, _pick_row(s, j) / pv,
                         jnp.zeros_like(pv))         # (..., 1, m)
        s = s - colm * rowm
        cj = jnp.where(rows == j, pv, colm)
        return jnp.where(lanes == j, cj, s)

    return lax.fori_loop(0, tile.shape[-1], body, tile)


def _tri_inv_tile(l):
    """Inverse of lower-triangular tiles ``(..., m, m)`` by column-oriented
    forward substitution on the identity — turns panel trsm into one
    matmul."""
    rows = _iota(l.shape[:-1] + (1,), l.ndim - 2)
    sub = _iota(l.shape, l.ndim - 2)

    def body(j, z):
        lcol = _pick_col(l, j)                       # (..., m, 1)
        zj = _pick_row(z, j) / _pick_row(lcol, j)    # (..., 1, m)
        below = jnp.where(rows > j, lcol, jnp.zeros_like(lcol))
        return jnp.where(sub == j, zj, z - below * zj)

    eye = (_iota(l.shape, l.ndim - 2) == _iota(l.shape, l.ndim - 1))
    return lax.fori_loop(0, l.shape[-1], body, eye.astype(l.dtype))


def _factor_panels(s_ref, block, precision):
    """Right-looking blocked Cholesky, in place on a ``(b, d, d)`` VMEM ref.

    Panels are unrolled at trace time (static, tile-aligned slices; true
    d³/3 flops). Leaves the clean lower factor in ``s_ref`` and returns the
    per-panel inverse diagonal blocks (reused by the solve phase so
    substitution needs no extra column sweeps)."""
    d = s_ref.shape[-1]
    mm_nt = _make_mm(precision, _BDIMS_NT)
    inv_blocks = []
    for o in range(0, d, block):
        e = o + block
        l11 = _factor_tile(s_ref[:, o:e, o:e])
        zinv = _tri_inv_tile(l11)
        inv_blocks.append(zinv)
        s_ref[:, o:e, o:e] = l11
        if e < d:
            l21 = mm_nt(s_ref[:, e:, o:e], zinv)
            s_ref[:, e:, o:e] = l21
            s_ref[:, e:, e:] = s_ref[:, e:, e:] - mm_nt(l21, l21)
    # zero the (garbage) strict upper triangle so the output is a clean L
    shape = s_ref.shape
    lower = _iota(shape, 1) >= _iota(shape, 2)
    s_ref[...] = jnp.where(lower, s_ref[...], jnp.zeros(shape, s_ref.dtype))
    return inv_blocks


def _solve_panels(l, b, block, precision, inv_blocks):
    """Batched ``L Lᵀ x = b`` by blocked forward + backward substitution.

    ``l`` (b, d, d) and ``b`` (b, d, c) are refs or values (only static
    slices are read). Returns the solution as a list of (b, block, c)
    panels, so the caller writes each one into its output ref."""
    mm_nn = _make_mm(precision, _BDIMS_NN)
    mm_tn = _make_mm(precision, _BDIMS_TN)
    n = l.shape[-1] // block
    sl = [slice(k * block, (k + 1) * block) for k in range(n)]
    ys = []
    for k in range(n):
        rhs = b[:, sl[k]]
        for j in range(k):
            rhs = rhs - mm_nn(l[:, sl[k], sl[j]], ys[j])
        ys.append(mm_nn(inv_blocks[k], rhs))
    xs = [None] * n
    for k in reversed(range(n)):
        rhs = ys[k]
        for j in range(k + 1, n):
            rhs = rhs - mm_tn(l[:, sl[j], sl[k]], xs[j])
        xs[k] = mm_tn(inv_blocks[k], rhs)
    return xs


def _ceil_mult(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def _pad_spd(a, d_p):
    """Pad a batch of (d, d) systems to (d_p, d_p) with an identity tail —
    the padded block factors to I and never couples back (block diagonal)."""
    d = a.shape[-1]
    if d_p == d:
        return a
    pad = d_p - d
    a = jnp.pad(a, ((0, 0), (0, pad), (0, pad)))
    tail = jnp.arange(d_p) >= d
    eye_tail = jnp.where(tail[:, None] & tail[None, :] &
                         (jnp.arange(d_p)[:, None] == jnp.arange(d_p)[None, :]),
                         jnp.ones((d_p, d_p), a.dtype),
                         jnp.zeros((d_p, d_p), a.dtype))
    return a + eye_tail[None]


# ---------------------------------------------------------------------------
# pallas_call entry points
# ---------------------------------------------------------------------------

# A v5e core has 128 MiB of VMEM, but Mosaic's default scoped limit is
# 16 MiB — less than one whole-resident d=1024 f32 system with its input and
# output double-buffered. The whole-resident kernels raise the limit, and
# size their per-step system batch so its bytes stay under _STEP_BYTES.
_VMEM_LIMIT = 100 * 2**20
_STEP_BYTES = 4 * 2**20


def _whole_resident_params():
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _systems_per_step(cap: int, count: int, d_p: int, dtype) -> int:
    per_system = d_p * d_p * jnp.dtype(dtype).itemsize
    return max(1, min(cap, count, _STEP_BYTES // per_system))


@functools.partial(jax.jit,
                   static_argnames=("block", "precision", "interpret",
                                    "batch_block"))
def blocked_cholesky(a: jax.Array, *, block: int = DEFAULT_BLOCK,
                     precision: str = "native", interpret: bool = False,
                     batch_block: int = DEFAULT_BATCH_BLOCK) -> jax.Array:
    """Batched lower-Cholesky ``a (m, d, d) SPD → L`` via the blocked kernel.

    The grid walks batch blocks; each step factors up to ``batch_block``
    systems together (one trace of the unrolled panel pipeline serves the
    whole batch; wide systems go one per step to fit VMEM). Returns clean
    lower factors; non-PD inputs yield NaNs.
    """
    m, d, _ = a.shape
    if m == 0:
        return jnp.zeros((0, d, d), a.dtype)
    bs = min(block, _ceil_mult(d, 8))
    d_p = _ceil_mult(d, bs)
    bb = _systems_per_step(batch_block, m, d_p, a.dtype)
    m_p = _ceil_mult(m, bb)
    a = _pad_spd(a, d_p)
    if m_p != m:
        # pad the batch with identity systems (factor = I, discarded)
        pad = jnp.broadcast_to(jnp.eye(d_p, dtype=a.dtype)[None],
                               (m_p - m, d_p, d_p))
        a = jnp.concatenate([a, pad], 0)

    def kernel(a_ref, l_ref):
        l_ref[...] = a_ref[...]
        _factor_panels(l_ref, bs, precision)

    out = pl.pallas_call(
        kernel,
        grid=(m_p // bb,),
        in_specs=[pl.BlockSpec((bb, d_p, d_p), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((bb, d_p, d_p), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m_p, d_p, d_p), a.dtype),
        compiler_params=_whole_resident_params(),
        interpret=interpret,
        name="blocked_cholesky",
    )(a)
    return out[:m, :d, :d]


@functools.partial(jax.jit,
                   static_argnames=("block", "precision", "interpret",
                                    "batch_block"))
def cholesky_solve(l: jax.Array, b: jax.Array, *, block: int = DEFAULT_BLOCK,
                   precision: str = "native", interpret: bool = False,
                   batch_block: int = DEFAULT_BATCH_BLOCK) -> jax.Array:
    """Batched triangular solve ``L Lᵀ x = b`` for lower factors from
    :func:`blocked_cholesky` — ``l (m, d, d)``, ``b (m, d, c)`` → ``x``.

    Blocked forward/backward substitution: the per-panel diagonal blocks are
    inverted once (``fori`` column sweeps), after which both sweeps are pure
    tile matmuls — the repeated-solve hot path costs d²·c, not d³.
    """
    m, d, _ = l.shape
    c = b.shape[-1]
    if m == 0:
        return jnp.zeros((0, d, c), b.dtype)
    bs = min(block, _ceil_mult(d, 8))
    d_p = _ceil_mult(d, bs)
    c_p = _ceil_mult(c, 8)
    bb = _systems_per_step(batch_block, m, d_p, l.dtype)
    m_p = _ceil_mult(m, bb)
    if d_p != d:
        l = _pad_spd(l, d_p)       # identity tail: triangular and invertible
    if (d_p, c_p) != (d, c):
        b = jnp.pad(b, ((0, 0), (0, d_p - d), (0, c_p - c)))
    if m_p != m:
        pad_l = jnp.broadcast_to(jnp.eye(d_p, dtype=l.dtype)[None],
                                 (m_p - m, d_p, d_p))
        l = jnp.concatenate([l, pad_l], 0)
        b = jnp.concatenate(
            [b, jnp.zeros((m_p - m, d_p, c_p), b.dtype)], 0)

    def kernel(l_ref, b_ref, x_ref):
        inv_blocks = [_tri_inv_tile(l_ref[:, o:o + bs, o:o + bs])
                      for o in range(0, d_p, bs)]
        xs = _solve_panels(l_ref, b_ref, bs, precision, inv_blocks)
        for k, xk in enumerate(xs):
            x_ref[:, k * bs:(k + 1) * bs] = xk

    out = pl.pallas_call(
        kernel,
        grid=(m_p // bb,),
        in_specs=[
            pl.BlockSpec((bb, d_p, d_p), lambda i: (i, 0, 0)),
            pl.BlockSpec((bb, d_p, c_p), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, d_p, c_p), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m_p, d_p, c_p), b.dtype),
        compiler_params=_whole_resident_params(),
        interpret=interpret,
        name="cholesky_solve",
    )(l, b)
    return out[:m, :d, :c]


@functools.partial(jax.jit,
                   static_argnames=("block", "gamma_block", "precision",
                                    "interpret"))
def multi_gamma_solve(c: jax.Array, q: jax.Array, gammas: jax.Array, *,
                      block: int = DEFAULT_BLOCK,
                      gamma_block: int = DEFAULT_GAMMA_BLOCK,
                      precision: str = "native",
                      interpret: bool = False) -> jax.Array:
    """The fused γ-sweep: solve ``(C + γ_j I) W_j = Q`` for a whole γ grid.

    One ``pallas_call`` whose grid walks γ-blocks: each step shifts the
    diagonal of C by its block of γs (up to ``gamma_block``; wide systems go
    one per step to fit VMEM), factors all of them as one batched blocked
    Cholesky, and runs the batched substitution — replacing the per-γ host
    loop (allocate ``C + γI`` → LAPACK → dispatch, per γ) with a single
    device program. Returns ``(n_gammas, d, c)``; γs whose system is
    singular come back as NaNs (caller falls back to the eigendecomposition
    path).
    """
    d = c.shape[-1]
    n_cls = q.shape[-1]
    n_g = gammas.shape[0]
    if n_g == 0:
        return jnp.zeros((0, d, n_cls), c.dtype)
    bs = min(block, _ceil_mult(d, 8))
    d_p = _ceil_mult(d, bs)
    c_p = _ceil_mult(n_cls, 8)
    bg = _systems_per_step(gamma_block, n_g, d_p, c.dtype)
    n_gp = _ceil_mult(n_g, bg)
    if d_p != d:
        c = _pad_spd(c[None], d_p)[0]
    if (d_p, c_p) != (d, n_cls):
        q = jnp.pad(q, ((0, d_p - d), (0, c_p - n_cls)))
    if n_gp != n_g:
        gammas = jnp.concatenate(
            [gammas, jnp.broadcast_to(gammas[-1], (n_gp - n_g,))])
    gammas = gammas.astype(c.dtype)

    def kernel(c_ref, q_ref, g_ref, w_ref, s_ref):
        eye = (_iota((d_p, d_p), 0) == _iota((d_p, d_p), 1)).astype(c.dtype)
        base = pl.program_id(0) * bg
        for t in range(bg):
            s_ref[t] = c_ref[...] + g_ref[base + t] * eye
        inv_blocks = _factor_panels(s_ref, bs, precision)
        qb = jnp.broadcast_to(q_ref[...][None], (bg, d_p, c_p))
        xs = _solve_panels(s_ref, qb, bs, precision, inv_blocks)
        for k, xk in enumerate(xs):
            w_ref[:, k * bs:(k + 1) * bs] = xk

    out = pl.pallas_call(
        kernel,
        grid=(n_gp // bg,),
        in_specs=[
            pl.BlockSpec((d_p, d_p), lambda i: (0, 0)),
            pl.BlockSpec((d_p, c_p), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bg, d_p, c_p), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_gp, d_p, c_p), c.dtype),
        scratch_shapes=[pltpu.VMEM((bg, d_p, d_p), c.dtype)],
        compiler_params=_whole_resident_params(),
        interpret=interpret,
        name="multi_gamma_solve",
    )(c, q, gammas)
    return out[:n_g, :d, :n_cls]


# ---------------------------------------------------------------------------
# Tile-parallel / HBM-streamed single-system path
#
# The kernels above keep the whole batched system resident in VMEM, which
# caps Mosaic-native occupancy near d≈1024 at f32. The path below factors a
# SINGLE wide system as a sequence of panel-sized pallas_calls: the (b, b)
# diagonal micro-factorization, the (r, b) panel trsm, and the streamed
# trailing syrk whose 2-D grid walks (row, col) tiles of the trailing
# submatrix — each grid step touches one VMEM-sized tile, so pallas's
# automatic grid pipelining double-buffers the HBM→VMEM panel traffic and a
# d≥2048 system factors Mosaic-native.
#
# The same trace-time routine also runs tile-PARALLEL: each mesh shard holds
# one (r, d) row tile of the global Gram, and the per-panel communication is
# abstracted behind two callbacks (``gather`` and ``psum``). The panel owner
# is a *static* shard index (panel width divides the tile rows), so the
# schedule per panel is: every shard offers its candidate diagonal block,
# one all-gather-of-a-panel replicates the true block, every shard factors
# it redundantly (b³ — cheap) and applies trsm/syrk to its own rows. No
# device ever materializes the full (d, d) system — peak per-device live
# bytes stay at the (r, d) tile plus one (d, b) panel column. With ONE shard
# and identity callbacks the very same trace is the local streamed kernel,
# which is what makes the distributed path bit-for-bit testable against
# :func:`streamed_cholesky`.
# ---------------------------------------------------------------------------

def panel_width(rows: int, cap: int = DEFAULT_STREAM_BLOCK) -> int:
    """Largest panel width ≤ ``cap`` that divides ``rows`` — panels must tile
    the shard rows exactly so every panel has a single static owner shard."""
    b = min(cap, rows)
    while rows % b:
        b -= 1
    return b


@functools.partial(jax.jit, static_argnames=("interpret",))
def panel_factor(diag: jax.Array, *, interpret: bool = False):
    """Factor one (b, b) SPD diagonal block → ``(L, inv(L))`` in VMEM.

    Both outputs come from one pallas_call so the trsm-ready inverse rides
    along with the factor; a non-PD block yields NaNs (caller fallback).
    """
    b = diag.shape[-1]

    def kernel(d_ref, l_ref, z_ref):
        l = _factor_tile(d_ref[...])
        l_ref[...] = l
        z_ref[...] = _tri_inv_tile(l)

    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b, b), diag.dtype),
                   jax.ShapeDtypeStruct((b, b), diag.dtype)),
        interpret=interpret,
        name="panel_factor",
    )(diag)


@functools.partial(jax.jit, static_argnames=("interpret",))
def panel_tri_inv(l: jax.Array, *, interpret: bool = False) -> jax.Array:
    """inv(L) of one (b, b) lower-triangular block (solve-only callers that
    hold a factor but not the inverses from :func:`panel_factor`)."""
    b = l.shape[-1]

    def kernel(l_ref, z_ref):
        z_ref[...] = _tri_inv_tile(l_ref[...])

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, b), l.dtype),
        interpret=interpret,
        name="panel_tri_inv",
    )(l)


@functools.partial(jax.jit,
                   static_argnames=("precision", "interpret", "row_block"))
def panel_trsm(raw: jax.Array, zinv: jax.Array, *, precision: str = "native",
               interpret: bool = False,
               row_block: int = DEFAULT_UPDATE_BLOCK) -> jax.Array:
    """Panel trsm ``raw (r, b) @ inv(L_D)ᵀ`` — the grid streams row blocks of
    the local column slab through VMEM against the replicated (b, b) inverse."""
    r, b = raw.shape
    rb = panel_width(r, row_block)
    mm = _make_mm(precision, _DIMS_NT)

    def kernel(a_ref, z_ref, o_ref):
        o_ref[...] = mm(a_ref[...], z_ref[...])

    return pl.pallas_call(
        kernel,
        grid=(r // rb,),
        in_specs=[
            pl.BlockSpec((rb, b), lambda i: (i, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rb, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, b), raw.dtype),
        interpret=interpret,
        name="panel_trsm",
    )(raw, zinv)


@functools.partial(jax.jit,
                   static_argnames=("precision", "interpret", "row_block",
                                    "col_block"))
def panel_update(trail: jax.Array, lp: jax.Array, pt: jax.Array, *,
                 precision: str = "native", interpret: bool = False,
                 row_block: int = DEFAULT_UPDATE_BLOCK,
                 col_block: int = DEFAULT_UPDATE_BLOCK) -> jax.Array:
    """Streamed trailing syrk ``trail (r, w) − lp (r, b) @ pt (w, b)ᵀ``.

    The 2-D grid walks (row, col) VMEM tiles of the trailing submatrix, so
    per-step residency is rb·cb + (rb + cb)·b elements regardless of d —
    this is the kernel that keeps the right-looking update HBM-streamed.
    """
    r, w = trail.shape
    b = lp.shape[-1]
    rb = panel_width(r, row_block)
    cb = panel_width(w, col_block)
    mm = _make_mm(precision, _DIMS_NT)

    def kernel(t_ref, l_ref, p_ref, o_ref):
        o_ref[...] = t_ref[...] - mm(l_ref[...], p_ref[...])

    return pl.pallas_call(
        kernel,
        grid=(r // rb, w // cb),
        in_specs=[
            pl.BlockSpec((rb, cb), lambda i, j: (i, j)),
            pl.BlockSpec((rb, b), lambda i, j: (i, 0)),
            pl.BlockSpec((cb, b), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((rb, cb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, w), trail.dtype),
        interpret=interpret,
        name="panel_update",
    )(trail, lp, pt)


def tile_cholesky_factor(tile, *, shard, n_shards: int, gather, block: int,
                         precision: str = "native", interpret: bool = False,
                         use_kernel: bool = True):
    """Blocked right-looking Cholesky of a row-tiled global system.

    ``tile`` is this shard's ``(r, d)`` row slab of the global SPD system
    (``d = n_shards · r``); ``shard`` is the shard's linear index (a traced
    ``axis_index`` under shard_map, or a plain 0 for the local streamed
    path) and ``gather(x) → (n_shards, …)`` stacks a per-shard value in
    shard order (``lax.all_gather`` on the mesh; ``x[None]`` locally).
    ``block`` must divide ``r`` (see :func:`panel_width`) so each panel has
    one static owner shard. Returns this shard's rows of the clean lower
    factor plus the replicated per-panel inverse diagonal blocks.

    Per panel: every shard offers its candidate (b, b) diagonal slice, the
    gather replicates the owner's true one, every shard factors it
    redundantly (b³ flops — far below the gather latency it would trade
    against) and applies trsm to its local column slab; one more panel
    gather assembles the (d, b) L-column every shard needs for its streamed
    trailing syrk. Peak live bytes per shard: the (r, d) tile + one (d, b)
    panel — never the (d, d) system.
    """
    r, d_p = tile.shape
    b = block
    mm_nt = _make_mm(precision, _DIMS_NT)
    rows_g = shard * r + jnp.arange(r)          # global row ids of this tile
    work = tile
    zs = []
    for p in range(d_p // b):
        o = p * b
        own = o // r                    # static: panel lives on one shard
        lo = o - own * r                # static owner-local row offset
        diag = gather(work[lo:lo + b, o:o + b])[own]
        if use_kernel:
            l_d, z = panel_factor(diag, interpret=interpret)
        else:
            l_d = _factor_tile(diag)
            z = _tri_inv_tile(l_d)
        zs.append(z)
        if use_kernel:
            colv = panel_trsm(work[:, o:o + b], z, precision=precision,
                              interpret=interpret)
        else:
            colv = mm_nt(work[:, o:o + b], z)
        below = rows_g >= o + b
        in_diag = (rows_g >= o) & (rows_g < o + b)
        ld_full = jnp.zeros((r, b), work.dtype).at[lo:lo + b].set(l_d)
        col = jnp.where(below[:, None], colv,
                        jnp.where(in_diag[:, None], ld_full,
                                  jnp.zeros_like(colv)))
        work = work.at[:, o:o + b].set(col)
        w_tr = d_p - o - b
        if w_tr:
            lcol = gather(col).reshape(n_shards * r, b)
            pt = lcol[o + b:]
            lp = jnp.where(below[:, None], col, jnp.zeros_like(col))
            if use_kernel:
                trail = panel_update(work[:, o + b:], lp, pt,
                                     precision=precision, interpret=interpret)
            else:
                trail = work[:, o + b:] - mm_nt(lp, pt)
            work = work.at[:, o + b:].set(trail)
    return work, zs


def tile_cholesky_solve(tile_l, q_tile, zs=None, *, shard, n_shards: int,
                        gather, psum, block: int, precision: str = "native",
                        interpret: bool = False, use_kernel: bool = True):
    """``L Lᵀ x = q`` against a row-tiled factor from
    :func:`tile_cholesky_factor`; returns the replicated ``(d, C)`` solution.

    ``q_tile`` is this shard's rows of the right-hand side; ``psum`` reduces
    a per-shard value over the mesh (identity locally). Forward sweep: the
    panel owner forms its (b, C) block from its own L rows and the psum
    broadcasts it; backward sweep: every shard contributes its local rows'
    partial product and the psum assembles the replicated update. Per-panel
    traffic is (b, C) — never the system.
    """
    r, d_p = tile_l.shape
    cdim = q_tile.shape[-1]
    b = block
    mm_nn = _make_mm(precision, _DIMS_NN)
    mm_tn = _make_mm(precision, _DIMS_TN)
    rows_g = shard * r + jnp.arange(r)
    panels = list(range(d_p // b))
    if zs is None:
        zs = []
        for p in panels:
            o = p * b
            own, lo = o // r, o - (o // r) * r
            diagl = gather(tile_l[lo:lo + b, o:o + b])[own]
            if use_kernel:
                zs.append(panel_tri_inv(diagl, interpret=interpret))
            else:
                zs.append(_tri_inv_tile(diagl))
    y = jnp.zeros((d_p, cdim), q_tile.dtype)
    for p in panels:
        o = p * b
        own, lo = o // r, o - (o // r) * r
        rhs = q_tile[lo:lo + b]
        if o:
            rhs = rhs - mm_nn(tile_l[lo:lo + b, :o], y[:o])
        y_p = mm_nn(zs[p], rhs)
        y_p = jnp.where(jnp.asarray(shard == own), y_p, jnp.zeros_like(y_p))
        y = y.at[o:o + b].set(psum(y_p))
    x = jnp.zeros((d_p, cdim), q_tile.dtype)
    for p in reversed(panels):
        o = p * b
        below = rows_g >= o + b
        lp = jnp.where(below[:, None], tile_l[:, o:o + b],
                       jnp.zeros((r, b), tile_l.dtype))
        start = jnp.asarray(shard * r)
        xs_local = lax.dynamic_slice(
            x, (start, jnp.zeros_like(start)), (r, cdim))
        total = psum(mm_tn(lp, xs_local))
        x = x.at[o:o + b].set(mm_tn(zs[p], y[o:o + b] - total))
    return x


@functools.partial(jax.jit,
                   static_argnames=("block", "precision", "interpret"))
def streamed_cholesky(a: jax.Array, *, block: int = DEFAULT_STREAM_BLOCK,
                      precision: str = "native",
                      interpret: bool = False) -> jax.Array:
    """Single-system lower Cholesky ``a (d, d) SPD → L`` via panel streaming.

    The degenerate one-shard instance of :func:`tile_cholesky_factor`: the
    whole system stays in HBM and only panel-sized tiles transit VMEM, so a
    d≥2048 system factors Mosaic-native where :func:`blocked_cholesky`'s
    whole-resident batch kernel cannot. Non-divisible d is padded with an
    identity tail and sliced back.
    """
    d = a.shape[-1]
    bs = min(block, _ceil_mult(d, 8))
    d_p = _ceil_mult(d, bs)
    ap = _pad_spd(a[None], d_p)[0]
    l, _ = tile_cholesky_factor(
        ap, shard=0, n_shards=1, gather=lambda v: v[None], block=bs,
        precision=precision, interpret=interpret)
    return l[:d, :d]


@functools.partial(jax.jit,
                   static_argnames=("block", "precision", "interpret"))
def streamed_cholesky_solve(l: jax.Array, b: jax.Array, *,
                            block: int = DEFAULT_STREAM_BLOCK,
                            precision: str = "native",
                            interpret: bool = False) -> jax.Array:
    """``L Lᵀ x = b`` against a :func:`streamed_cholesky` factor —
    ``l (d, d)`` lower, ``b (d, c)`` → ``x (d, c)``."""
    d = l.shape[-1]
    bs = min(block, _ceil_mult(d, 8))
    d_p = _ceil_mult(d, bs)
    lp = _pad_spd(l[None], d_p)[0]
    bp = jnp.pad(b, ((0, d_p - d), (0, 0))) if d_p != d else b
    x = tile_cholesky_solve(
        lp, bp, None, shard=0, n_shards=1, gather=lambda v: v[None],
        psum=lambda v: v, block=bs, precision=precision, interpret=interpret)
    return x[:d]


# ---------------------------------------------------------------------------
# Fused rank-k Cholesky update (the batched-ingest fold)
# ---------------------------------------------------------------------------


def _rank_update_kernel(l_ref, xt_ref, o_ref, x_ref):
    """Householder column sweep folding ``xtᵀ`` rows into a lower factor.

    Whole-resident: L (d_p, d_p), swept in place in ``o_ref``, and the
    stacked update tail xt (d_p, k_p), in the ``x_ref`` scratch, live in
    VMEM for the entire sweep — one kernel launch for the whole rank-k
    update instead of k rank-1 sweeps (or a host-driven loop). Each column
    step annihilates all k update entries with a single (k+1)-reflection;
    masked full-width updates keep every iteration static-shape under
    ``fori_loop``. Zero update rows (s == 0 — including every identity-tail
    padding column) reduce to r = |a| with vanishing corrections, so padding
    needs no masking of its own.
    """
    dp = l_ref.shape[-1]
    rows = _iota((dp, 1), 0)
    lanes = _iota((dp, dp), 1)
    o_ref[...] = l_ref[...]
    x_ref[...] = xt_ref[...]

    def body(i, carry):
        l, xt = o_ref[...], x_ref[...]
        w = _pick_row(xt, i)                         # (1, k_p)
        s = jnp.sum(w * w, axis=-1, keepdims=True)   # (1, 1)
        s_ = jnp.where(s > 0, s, 1.0)      # w == 0 ⇒ t == 0, updates vanish
        col = _pick_col(l, i)                        # (d_p, 1)
        a = _pick_row(col, i)                        # (1, 1)
        r = jnp.sqrt(a * a + s)
        amr = -s / (r + a)                 # a − r without cancellation
        beta = (r + a) / (r * s_)          # 2 / uᵀu for u = [a−r; w]
        below = rows > i
        t = amr * col + jnp.sum(xt * w, axis=-1, keepdims=True)
        new_col = jnp.where(below, col - (beta * amr) * t, col)
        new_col = jnp.where(rows == i, r, new_col)
        o_ref[...] = jnp.where(lanes == i, new_col, l)
        x_ref[...] = jnp.where(below, xt - (beta * t) * w, xt)
        return carry

    lax.fori_loop(0, dp, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def chol_rank_update(l: jax.Array, xs: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """Fused rank-k Cholesky update: ``L (d, d)`` lower with ``A = LLᵀ``,
    update rows ``xs (k, d)`` → ``chol(A + xsᵀxs)`` in ONE ``pallas_call``.

    This is the micro-batch ingest fold's device path: a whole batch of
    client roots, stacked, folds into the cached factor in a single kernel
    launch — versus the non-kernel jax path's per-column ``fori_loop``
    dispatched from ``jit`` (same flops, k× the launch/carry overhead when
    applied per report). The update is positive (a Gram delta), so the
    sweep cannot break down; non-finite inputs surface as NaNs, which
    ``AnalyticEngine.factor_update`` detects and routes to a full refactor.
    Whole-resident in VMEM like :func:`blocked_cholesky` — same d ≲ 1024
    f32 bound; wider serving systems refactor via the streamed path anyway.
    """
    d = l.shape[-1]
    k = xs.shape[0]
    if k == 0:
        return l
    bs = min(DEFAULT_BLOCK, _ceil_mult(d, 8))
    d_p = _ceil_mult(d, bs)
    k_p = _ceil_mult(k, 8)
    lp = _pad_spd(l[None], d_p)[0]
    xt = jnp.pad(xs.T.astype(l.dtype), ((0, d_p - d), (0, k_p - k)))
    out = pl.pallas_call(
        _rank_update_kernel,
        out_shape=jax.ShapeDtypeStruct((d_p, d_p), l.dtype),
        scratch_shapes=[pltpu.VMEM((d_p, k_p), l.dtype)],
        compiler_params=_whole_resident_params(),
        interpret=interpret,
        name="chol_rank_update",
    )(lp, xt)
    return out[:d, :d]
