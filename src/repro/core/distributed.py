"""Distributed AFL aggregation: the single round as a single collective.

On the TPU mesh each shard along the federation axes (``('data',)`` or
``('pod', 'data')``) plays one client cohort. Each shard holds a local
:class:`~repro.core.engine.SuffStats` (C_k^r implicit: raw Gram + a client
count, adding γ per-client lazily — the engine's shared bookkeeping,
algebraically identical to the paper's C_k^r = C_k + γI per client, see
eq (15): Σ C_i^r = Σ C_i + kγI).

``federated_solve`` then performs the paper's entire aggregation stage as:

    psum(SuffStats)  →  RI restore  →  Cholesky solve (engine, jax backend)

i.e. ONE all-reduce round — the communication pattern the AA law licenses.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.engine import AnalyticEngine, SuffStats
from repro.core.streaming import AnalyticState, to_stats
from repro.kernels.ops import interpret_default
from repro.launch.mesh import auto_mesh

__all__ = [
    "psum_stats",
    "psum_state",
    "federation_mesh",
    "federated_solve",
    "federated_solve_no_ri",
    "make_federated_solve",
    "make_tiled_federated_solve",
]

_ENGINE = AnalyticEngine("jax")


def federation_mesh(n_shards: int, axis_names: Sequence[str] = ("data",),
                    *, devices=None) -> Mesh:
    """A 1-axis federation mesh over the first ``n_shards`` devices.

    The elastic coordinator (``ShardedCoordinator.grow/shrink`` and the
    shard-count-changing ``from_state``) admits and retires mesh devices
    through this single constructor, so "which devices back n shards" has
    one answer everywhere. More shards than devices is a caller error —
    the tiled-Gram layout is one row tile per device.
    """
    devices = list(jax.devices() if devices is None else devices)
    n = int(n_shards)
    if n < 1:
        raise ValueError(f"a federation mesh needs ≥1 shard, got {n}")
    if n > len(devices):
        raise ValueError(
            f"{n} shards need {n} devices, only {len(devices)} available")
    if len(tuple(axis_names)) != 1:
        raise ValueError(
            f"federation_mesh builds 1-axis meshes, got {tuple(axis_names)}")
    return auto_mesh((n,), axis_names, devices=devices[:n])


def psum_stats(stats: SuffStats, axis_names: Sequence[str]) -> SuffStats:
    """All-reduce the sufficient statistics over the federation axes.

    The AA law (Thm 1) makes this one psum *the whole aggregation stage*:
    statistics (and the lazy client count) simply add.
    """
    ax = tuple(axis_names)
    return jax.tree.map(lambda x: jax.lax.psum(x, ax), stats)


def psum_state(state: AnalyticState, axis_names: Sequence[str]) -> AnalyticState:
    """Back-compat: all-reduce a bare 3-leaf AnalyticState."""
    ax = tuple(axis_names)
    return jax.tree.map(lambda x: jax.lax.psum(x, ax), state)


def federated_solve(
    state: AnalyticState,
    *,
    axis_names: Sequence[str],
    num_clients: int,
    gamma: float,
    target_gamma: float = 0.0,
) -> jax.Array:
    """AFL aggregation stage inside shard_map: one psum + RI + solve.

    ``state`` holds this shard's *raw* Gram/moment (no γ added). Per the RI
    process (Thm 2), the regularized aggregate would be C_agg + KγI; restoring
    (eq 16) means solving with C_agg + target_γ·I directly — the engine's
    lazy-γ semantics, so the KγI term is never materialized. The
    γ/num_clients arguments are kept so callers can instead request the
    *biased* (no-RI) solution for the Table-3 ablation.
    """
    agg = psum_stats(to_stats(state, clients=1.0), axis_names)
    return _ENGINE.solve(agg, use_ri=True, target_gamma=target_gamma)


def federated_solve_no_ri(
    state: AnalyticState,
    *,
    axis_names: Sequence[str],
    num_clients: int,
    gamma: float,
) -> jax.Array:
    """Biased aggregate w/o RI: solves with C_agg + KγI (Table 3 left columns).

    ``num_clients`` is authoritative for K — a shard cohort may stand in for
    more than one client, so the per-shard clients tags are overridden.
    """
    agg = psum_stats(to_stats(state, clients=1.0), axis_names)
    agg = agg._replace(clients=jnp.asarray(num_clients, agg.gram.dtype))
    eng = AnalyticEngine("jax", gamma=gamma)
    return eng.solve(agg, use_ri=False)


def make_federated_solve(
    mesh: Mesh,
    *,
    axis_names: Sequence[str] = ("data",),
    gamma: float = 1.0,
    target_gamma: float = 0.0,
    use_ri: bool = True,
):
    """Build a jitted shard-mapped aggregation: AnalyticState-per-shard → W.

    The returned function consumes an ``AnalyticState`` whose leaves carry a
    leading federation-shard dimension laid out over ``axis_names`` and
    returns the replicated global weight — the whole FL round in one XLA
    program containing exactly one all-reduce family per statistic.
    """
    ax = tuple(axis_names)
    num_clients = 1
    for a in ax:
        num_clients *= mesh.shape[a]
    in_spec = AnalyticState(P(ax), P(ax), P(ax))
    solver = federated_solve if use_ri else federated_solve_no_ri

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(in_spec,), out_specs=P()
    )
    def _agg(stacked: AnalyticState) -> jax.Array:
        local = jax.tree.map(lambda x: jnp.sum(x, axis=0), stacked)
        return solver(
            local, axis_names=ax, num_clients=num_clients, gamma=gamma,
            **({"target_gamma": target_gamma} if use_ri else {}),
        )

    return jax.jit(_agg)


def make_tiled_federated_solve(
    mesh: Mesh,
    *,
    axis_names: Sequence[str] = ("data",),
    target_gamma: float = 0.0,
    use_kernel: bool = False,
    distributed_factor: bool = False,
    dim: int | None = None,
    block: int | None = None,
):
    """Build a jitted aggregation over a row-TILED Gram: tiles-per-shard → W.

    ``make_federated_solve`` psums whole (d, d) leaves — every shard holds a
    full-size partial aggregate, so per-device resident memory is d²
    regardless of the mesh. At d=6144 that is ~302 MB of f64 per device just
    for the Gram partials, which is what capped the PR-3 sharded backend.
    Here each shard instead holds ONE ``(d/shards, d)`` row tile of the one
    global Gram (``ShardedCoordinator(tiled_gram=True)`` scatters every
    arrival across the tiles at ingest, so the tiles already ARE the
    aggregate — d²/shards resident per device). The returned function takes
    the stacked tiles ``(shards, d/shards, d)`` and the matching moment
    tiles ``(shards, d/shards, C)``, and in one XLA program:

      1. each shard scatters its tile into an otherwise-zero full system at
         its own row offset (``axis_index`` → ``dynamic_update_slice``),
      2. ONE psum assembles the replicated global (d, d) system — the same
         collective family as the leaf psum, but each shard contributes
         every Gram entry exactly once instead of a full-size partial
         (the full matrix is a transient of the solve, not resident state),
      3. RI restore is a diagonal shift (raw tiles + ``target_gamma``·I —
         the engine's lazy-γ semantics), and the replicated system is
         factored and solved in-graph (``use_kernel=True`` routes this
         through the blocked Pallas Cholesky of ``repro.kernels.solve``).

    With ``distributed_factor=True`` step 2 never happens: instead of
    gathering the system, the factorization itself runs tile-parallel
    (:func:`repro.kernels.solve.tile_cholesky_factor`): each panel's owner
    shard is static, one all-gather-of-a-panel replicates its (b, b)
    diagonal block and its (d, b) L-column, and every shard applies
    trsm/syrk to its own rows through the streamed Pallas panel kernels —
    peak per-device live bytes stay at the (r, d) tile plus one panel
    column, never the (d, d) transient. ``dim`` gives the TRUE head width
    when the tiles are padded (``ShardedCoordinator`` pads indivisible dims
    with zero rows); pad rows get a unit diagonal so the padded block
    factors to I and decouples, and the returned weight is sliced back to
    ``dim`` rows.

    Device arithmetic follows jax's global precision; under
    ``jax_enable_x64`` the result matches the sync host path ≤1e-10 at
    d=2048 on an 8-way mesh (``tests/test_distributed_cholesky.py``).
    """
    ax = tuple(axis_names)
    engine = AnalyticEngine("jax", use_kernel=use_kernel)
    n_shards = 1
    for a in ax:
        n_shards *= mesh.shape[a]
    interpret = interpret_default()

    if distributed_factor:
        from repro.kernels.solve import (
            DEFAULT_STREAM_BLOCK, panel_width,
            tile_cholesky_factor, tile_cholesky_solve)

        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=(P(ax), P(ax)), out_specs=P(),
            check_vma=False,   # gathers + dynamic slices defeat vma inference
        )
        def _agg_dist(gram_tiles: jax.Array,
                      moment_tiles: jax.Array) -> jax.Array:
            idx = jnp.asarray(0)
            for a in ax:
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
            gt = gram_tiles[0]                 # (rows, d_p) — this shard's tile
            mt = moment_tiles[0]               # (rows, C)
            rows, d_p = gt.shape
            d_true = d_p if dim is None else dim
            # RI restore on the true diagonal (lazy-γ: raw tiles + γ·I) and a
            # unit diagonal on pad rows so the pad block factors to I and
            # never couples back. Selects, not adds, so off-diagonal entries
            # pass through bit-identically.
            cols = jnp.arange(d_p)
            gr = idx * rows + jnp.arange(rows)
            on_diag = gr[:, None] == cols[None, :]
            a_tile = jnp.where(
                on_diag & (gr[:, None] < d_true),
                gt + jnp.asarray(target_gamma, gt.dtype), gt)
            a_tile = jnp.where(on_diag & (gr[:, None] >= d_true),
                               jnp.ones((), gt.dtype), a_tile)
            b = panel_width(rows, block or DEFAULT_STREAM_BLOCK)
            gather = lambda v: jax.lax.all_gather(v, ax)
            tile_l, zs = tile_cholesky_factor(
                a_tile, shard=idx, n_shards=n_shards, gather=gather,
                block=b, interpret=interpret)
            w = tile_cholesky_solve(
                tile_l, mt, zs, shard=idx, n_shards=n_shards, gather=gather,
                psum=lambda v: jax.lax.psum(v, ax), block=b,
                interpret=interpret)
            return w[:d_true]

        return jax.jit(_agg_dist)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P(ax), P(ax)), out_specs=P()
    )
    def _agg(gram_tiles: jax.Array, moment_tiles: jax.Array) -> jax.Array:
        # linear shard index over the (possibly multi-axis) federation mesh
        idx = jnp.asarray(0)
        for a in ax:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        gt = gram_tiles[0]                     # (rows, d) — this shard's tile
        mt = moment_tiles[0]                   # (rows, C)
        rows, d = gt.shape
        offset = (idx * rows).astype(jnp.int32)
        zero = jnp.zeros((), jnp.int32)
        full_g = jax.lax.dynamic_update_slice(
            jnp.zeros((d, d), gt.dtype), gt, (offset, zero))
        full_m = jax.lax.dynamic_update_slice(
            jnp.zeros((d, mt.shape[1]), mt.dtype), mt, (offset, zero))
        full_g = jax.lax.psum(full_g, ax)
        full_m = jax.lax.psum(full_m, ax)
        d_true = d if dim is None else dim
        a_sys = full_g + jnp.asarray(target_gamma, gt.dtype) * jnp.eye(
            d, dtype=gt.dtype)
        if d_true != d:
            # padded system: unit diagonal on the pad block, then slice back
            tail = jnp.arange(d) >= d_true
            a_sys = jnp.where(
                (jnp.arange(d)[:, None] == jnp.arange(d)[None, :])
                & tail[:, None], jnp.ones((), gt.dtype), a_sys)
            return engine.backend.solve_sym(a_sys, full_m)[:d_true]
        return engine.backend.solve_sym(a_sys, full_m)

    return jax.jit(_agg)
