"""Public jit'd entry points for the Pallas kernels.

On a TPU backend the calls compile through Mosaic; on any other backend (the
CPU test runs, ``JAX_PLATFORMS=cpu``) they execute in the Pallas interpreter.
The choice is made at each call, never at import, so importing this module
does not initialize a backend. ``repro.kernels.ref`` holds the pure-jnp
oracles used by the tests and by the models' default (portable) path.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import scopes
from repro.kernels import flash_attention as _fa
from repro.kernels import gram as _gram
from repro.kernels import solve as _solve


def interpret_default() -> bool:
    """Whether Pallas calls run interpreted: on every backend but a TPU."""
    return jax.default_backend() != "tpu"


def gram_update(x: jax.Array, y: jax.Array, **kw) -> tuple[jax.Array, jax.Array]:
    """Fused (XᵀX, XᵀY). Interpreted off-TPU, Mosaic-compiled on TPU.

    XLA cannot partition a Mosaic kernel, so rows sharded over a mesh (the
    embeddings of a data-parallel forward) are folded where they live: each
    device runs the kernel on its own rows and one psum adds the partial
    statistics — the AA law at device scale. The result is replicated.
    """
    kw.setdefault("interpret", interpret_default())
    sharding = getattr(x, "sharding", None)   # None for tracers under jit
    if (isinstance(sharding, NamedSharding) and len(sharding.device_set) > 1
            and sharding.spec and sharding.spec[0] is not None):
        fold = _sharded_gram(sharding.mesh, sharding.spec[0],
                             tuple(sorted(kw.items())))
        return fold(x, y)
    return _gram.gram_update(x, y, **kw)


@functools.lru_cache(maxsize=None)
def _sharded_gram(mesh, rows, kw_items):
    """The jitted per-device fold + psum, built once per mesh and options so
    a stream of batches compiles it once."""
    kw = dict(kw_items)

    def local(xs, ys):
        stats = _gram.gram_update(xs, ys, **kw)
        with jax.named_scope(scopes.GRAM_PSUM):
            return jax.lax.psum(stats, rows)

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(rows), P(rows)), out_specs=P(),
                                 check_vma=False))


def blocked_cholesky(a: jax.Array, **kw) -> jax.Array:
    """Batched blocked lower-Cholesky of SPD systems (m, d, d) → L."""
    kw.setdefault("interpret", interpret_default())
    return _solve.blocked_cholesky(a, **kw)


def cholesky_solve(l: jax.Array, b: jax.Array, **kw) -> jax.Array:
    """Batched L·Lᵀ·x = b substitution against blocked_cholesky factors."""
    kw.setdefault("interpret", interpret_default())
    return _solve.cholesky_solve(l, b, **kw)


def multi_gamma_solve(c: jax.Array, q: jax.Array, gammas: jax.Array,
                      **kw) -> jax.Array:
    """Fused γ-sweep: (C + γ_j I) W_j = Q for the whole grid in one call."""
    kw.setdefault("interpret", interpret_default())
    return _solve.multi_gamma_solve(c, q, gammas, **kw)


STREAM_MIN_DIM = _solve.STREAM_MIN_DIM


def chol_rank_update(l: jax.Array, xs: jax.Array, **kw) -> jax.Array:
    """Fused rank-k Cholesky factor update L → chol(LLᵀ + xsᵀxs)."""
    kw.setdefault("interpret", interpret_default())
    return _solve.chol_rank_update(l, xs, **kw)


def streamed_cholesky(a: jax.Array, **kw) -> jax.Array:
    """Single-system (d, d) lower Cholesky via HBM→VMEM panel streaming."""
    kw.setdefault("interpret", interpret_default())
    return _solve.streamed_cholesky(a, **kw)


def streamed_cholesky_solve(l: jax.Array, b: jax.Array, **kw) -> jax.Array:
    """L·Lᵀ·x = b substitution against a streamed_cholesky factor."""
    kw.setdefault("interpret", interpret_default())
    return _solve.streamed_cholesky_solve(l, b, **kw)


def flash_attention(q, k, v, **kw) -> jax.Array:
    """Causal/GQA/sliding-window flash attention."""
    kw.setdefault("interpret", interpret_default())
    return _fa.flash_attention(q, k, v, **kw)
