"""The sufficient-statistics engine: ONE implementation of AFL's math.

Every path in this repo that touches the paper's statistics→solve pipeline —
the host f64 reference (`core.analytic`), the device streaming accumulator
(`core.streaming`), the one-collective federated solve (`core.distributed`),
and the serving coordinators (`fl.api`) — routes through this module.
The math appears exactly once:

  * ``SuffStats``: the sufficient statistics of a (partial) analytic
    regression, in *raw-Gram* form — ``gram = Σ XᵀX`` with NO γ baked in,
    plus a ``clients`` counter so the per-client γI of the paper's
    C_k^r = X_kᵀX_k + γI is applied *lazily* at solve time
    (Σ C_k^r = Σ C_k + kγI, eq (15); `core.distributed` already used this
    bookkeeping — it is now the shared semantics).
  * ``AnalyticEngine``: update / merge / ri_restore / solve /
    solve_multi_gamma over a pluggable backend.

Backends:
  * ``numpy_f64`` — host numpy in float64, Cholesky with pseudo-inverse
    fallback for the rank-deficient γ=0 ablations (paper Table 3 / A.1).
  * ``jax`` — device f32 (or f64 where enabled), jit-able, with an optional
    Kahan-compensated accumulator for long streaming reductions and the
    Pallas Gram kernel (`repro.kernels.gram`) as the update path
    (``use_kernel=True``).

The engine also exposes an explicit factorization handle
(:meth:`AnalyticEngine.factor` / :meth:`AnalyticEngine.factor_solve`) so hot
serving paths (``fl.api.AFLServer``) can cache the d³ Cholesky across
repeated ``solve()`` polls and pay only the d²·C triangular solves. The
handle is *rank-updatable* (:meth:`Factorization.rank_update` /
:meth:`AnalyticEngine.factor_update`): a low-rank client arrival folds into
the cached factor in O(k·d²), which is what makes event-loop serving
(``fl.async_server``) refactor-free on the straggler hot path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Sequence

import numpy as np

try:  # d²·C triangular solves for cached factors (vs np.linalg.solve's LU)
    from scipy.linalg import solve_triangular as _solve_triangular
except ImportError:  # pragma: no cover - scipy ships with jax, but stay soft
    _solve_triangular = None

__all__ = [
    "SuffStats",
    "Factorization",
    "SweepFactorization",
    "SweepRefreshNeeded",
    "AnalyticEngine",
    "NumpyF64Backend",
    "JaxBackend",
    "get_backend",
]


class SuffStats(NamedTuple):
    """Sufficient statistics of a (partial) analytic regression (a pytree).

    gram:    ``Σ XᵀX``  (d, d) — RAW, no regularization baked in.
    moment:  ``Σ XᵀY``  (d, C).
    count:   number of samples folded in (scalar).
    clients: number of client contributions merged in (scalar). The paper's
             per-client +γI is applied lazily as ``clients·γ·I`` wherever a
             regularized aggregate is needed; the RI restore (Thm 2) then
             amounts to *not* adding it back (eq 16).
    gram_c / moment_c: optional Kahan compensation carries (same shapes as
             gram/moment; ``None`` unless the engine runs compensated
             accumulation). ``None`` leaves vanish from the pytree, so the
             plain 4-leaf layout is unchanged for psum/sharding.
    """

    gram: Any
    moment: Any
    count: Any
    clients: Any
    gram_c: Any = None
    moment_c: Any = None

    @property
    def dim(self) -> int:
        return self.gram.shape[0]

    @property
    def num_classes(self) -> int:
        return self.moment.shape[1]


@dataclasses.dataclass(frozen=True)
class Factorization:
    """Opaque reusable factorization of a regularized Gram matrix.

    ``handle`` is backend-specific (host Cholesky factor or jax cho_factor
    output; ``None`` marks the numpy pinv fallback for singular systems, in
    which case ``matrix`` holds the system for the per-solve pseudo-inverse —
    on the successful-factor path ``matrix`` is ``None`` so cached entries
    carry only the factor).

    ``backend`` is the backend that produced the factor; it makes the handle
    *updatable*: :meth:`rank_update` folds a positive rank-k perturbation
    ``XᵀX`` into the factor in O(k·d²) instead of the O(d³) refactorization.
    """

    handle: Any
    matrix: Any = None
    backend: Any = None

    @property
    def updatable(self) -> bool:
        """True when :meth:`rank_update` is available (a real triangular
        factor from a backend; the pinv fallback has nothing to rotate)."""
        return self.backend is not None and self.handle is not None

    def rank_update(self, xs) -> "Factorization":
        """chol(A) → chol(A + xsᵀ·xs) for update rows ``xs`` of shape (k, d).

        k sequential rank-1 Cholesky updates fused into one Householder
        column sweep — O(k·d²) versus the d³ refactor, numerically exact for
        a *positive* update (which a Gram delta always is, so no hyperbolic
        downdates are ever needed on the serving path).
        """
        if not self.updatable:
            raise ValueError(
                "factorization is not rank-updatable (pinv fallback for a "
                "singular system, or constructed without a backend)")
        return self.backend.rank_update(self, xs)

    def rank_update_many(self, roots) -> "Factorization":
        """Fold a *sequence* of update roots in one pass — the micro-batch
        twin of :meth:`rank_update`.

        Semantically ``functools.reduce(Factorization.rank_update, roots)``,
        but executed as ONE column sweep interleaving each group's
        reflections in arrival order. On the host backend that interleaving
        performs the *identical* scalar operation schedule as the sequential
        folds (row i of the factor is only touched at column step i, and
        each group couples to the others solely through those rows), so the
        result is bit-for-bit equal to sequential updates — the property the
        batched ingest fold is pinned to.
        """
        if not self.updatable:
            raise ValueError(
                "factorization is not rank-updatable (pinv fallback for a "
                "singular system, or constructed without a backend)")
        return self.backend.rank_update_many(self, roots)


class SweepRefreshNeeded(RuntimeError):
    """A rank-updated sweep handle cannot answer this γ grid exactly (the
    base spectrum hits the pinv cutoff with pending low-rank corrections) —
    re-eigendecompose the current statistics and retry."""


@dataclasses.dataclass(frozen=True)
class SweepFactorization:
    """Rank-updatable eigendecomposition handle for repeated multi-γ sweeps.

    ``vals/vecs`` are the eigendecomposition ``base = V Λ Vᵀ`` of the raw
    (RI) — or regularized (no-RI) — aggregate Gram at the time the handle
    was built; the d³ ``eigh`` is the whole cost of a γ sweep, so a serving
    coordinator wants to pay it once and keep sweeping as the federation
    evolves. ``u`` accumulates the low-rank roots of every Gram delta merged
    since (``uᵀu`` = the raw update), with ``vu = Vᵀuᵀ`` cached so each
    sweep works entirely in the fixed eigenbasis:

        (B(γ) + uᵀu)⁻¹ Q  =  B⁻¹Q − B⁻¹uᵀ (I + u B⁻¹ uᵀ)⁻¹ u B⁻¹ Q,
        B(γ) = V (Λ+γ) Vᵀ

    — exact Woodbury algebra, O(d²·(C+k) + k³) per γ instead of a fresh d³
    eigendecomposition. The update itself (:meth:`rank_update`) is O(d²·k):
    one projection of the new roots into the eigenbasis. Past
    ``AFLServer.sweep_rank_budget`` accumulated rows (default d/8; see
    ``benchmarks/solve_kernels_bench.py`` for the measured crossover) a
    fresh handle is cheaper per sweep again and callers rebuild.

    With no pending update (``rank == 0``) the solve path is the plain
    spectral sweep — bit-identical to :meth:`AnalyticEngine.
    solve_multi_gamma`'s historical output, including the pinv-style
    truncation for rank-deficient γ=0 systems. With pending updates the
    truncation would no longer equal the pseudo-inverse of the *updated*
    system, so that combination raises :class:`SweepRefreshNeeded` instead
    of silently answering a subtly different question.
    """

    vals: Any
    vecs: Any
    backend: Any
    u: np.ndarray                 # (k, d) pending raw-Gram update roots
    vu: np.ndarray                # (d, k) = vecsᵀ · uᵀ, cached projection

    @property
    def rank(self) -> int:
        return int(self.u.shape[0])

    @property
    def dim(self) -> int:
        return int(self.u.shape[1])

    def rank_update(self, xs) -> "SweepFactorization":
        """Fold update rows ``xs (k, d)`` (``xsᵀxs`` = the merged raw-Gram
        delta) into the handle: append to ``u`` and project once."""
        xs = np.asarray(xs, np.float64).reshape(-1, self.dim)
        if not xs.shape[0]:
            return self
        proj = np.asarray(self.vecs, np.float64).T @ xs.T
        return dataclasses.replace(
            self, u=np.concatenate([self.u, xs], 0),
            vu=np.concatenate([self.vu, proj], 1))


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------


class NumpyF64Backend:
    """Host numpy, float64 — the paper-faithful reference arithmetic."""

    name = "numpy_f64"

    def asarray(self, a):
        return np.asarray(a, np.float64)

    def eye(self, d, like=None):
        return np.eye(d)

    def zeros(self, shape):
        return np.zeros(shape, np.float64)

    def scalar(self, v):
        return float(v)

    def gram_update(self, x, y):
        x = self.asarray(x)
        y = self.asarray(y)
        return x.T @ x, x.T @ y, float(x.shape[0])

    def factor(self, a) -> Factorization:
        """Cholesky when PD; ``handle=None`` → pinv fallback per solve, so the
        γ=0 rank-deficient ablations (paper Table 3 / A.1) run instead of
        raising. The handle is the UPPER factor R (A = RᵀR), C-contiguous:
        the rank-update sweep then walks contiguous rows instead of strided
        columns (~3× faster at d=2048)."""
        try:
            return Factorization(
                np.ascontiguousarray(np.linalg.cholesky(a).T), backend=self)
        except np.linalg.LinAlgError:
            return Factorization(None, a, backend=self)

    def rank_update(self, f: Factorization, xs) -> Factorization:
        """Rank-k Cholesky update: R → chol(RᵀR + xsᵀxs)."""
        xs = self.asarray(xs).reshape(-1, f.handle.shape[0])
        return Factorization(_chol_rank_update(f.handle, xs), backend=self)

    def rank_update_many(self, f: Factorization, roots) -> Factorization:
        """One grouped column sweep over a sequence of update roots —
        bit-for-bit equal to folding them with :meth:`rank_update` one at a
        time (see :func:`_chol_rank_update_grouped`)."""
        d = f.handle.shape[0]
        roots = [self.asarray(x).reshape(-1, d) for x in roots]
        return Factorization(
            _chol_rank_update_grouped(f.handle, roots), backend=self)

    def factor_solve(self, f: Factorization, b):
        if f.handle is None:
            return np.linalg.pinv(f.matrix) @ b
        if _solve_triangular is not None:
            y = _solve_triangular(f.handle, b, trans="T", lower=False)
            return _solve_triangular(f.handle, y, lower=False)
        y = np.linalg.solve(f.handle.T, b)
        return np.linalg.solve(f.handle, y)

    def solve_sym(self, a, b):
        return self.factor_solve(self.factor(a), b)

    def eigh(self, a):
        return np.linalg.eigh(a)

    def safe_reciprocal(self, v, cutoff):
        """1/v where |v| > cutoff, else 0 — pinv-style spectral truncation."""
        return np.where(np.abs(v) > cutoff, 1.0 / np.where(v == 0, 1.0, v), 0.0)


class JaxBackend:
    """Device jax arrays, jit-able; f32 by default (f64 where x64 is on).

    ``use_kernel=True`` routes the Gram update through the fused Pallas
    kernel (`repro.kernels.ops.gram_update`) AND the factor/solve/γ-sweep
    through the blocked Pallas solve kernels (`repro.kernels.solve`:
    blocked Cholesky, batched substitution, fused multi-γ sweep — Mosaic on
    TPU, interpreter elsewhere). The solve path assumes PD systems (γ>0 or
    full-rank statistics); a singular system surfaces as NaNs, which
    :meth:`AnalyticEngine.solve_multi_gamma` detects and reroutes to the
    eigendecomposition/pinv path (direct ``solve`` callers needing γ=0
    rank-deficient semantics use the ``numpy_f64`` backend).
    """

    name = "jax"

    def __init__(self, dtype=None, use_kernel: bool = False):
        import jax.numpy as jnp

        self._jnp = jnp
        self.dtype = dtype or jnp.float32
        self.use_kernel = use_kernel
        self._rank_update_fn = None

    def asarray(self, a):
        return self._jnp.asarray(a, self.dtype)

    def eye(self, d, like=None):
        return self._jnp.eye(d, dtype=self.dtype)

    def zeros(self, shape):
        return self._jnp.zeros(shape, self.dtype)

    def scalar(self, v):
        return self._jnp.asarray(v, self.dtype)

    def gram_update(self, x, y):
        jnp = self._jnp
        x = x.reshape(-1, x.shape[-1]).astype(self.dtype)
        y = y.reshape(-1, y.shape[-1]).astype(self.dtype)
        if self.use_kernel:
            from repro.kernels import ops as _kops

            g, q = _kops.gram_update(x, y)
            g = g.astype(self.dtype)
            q = q.astype(self.dtype)
        else:
            # full-f32 MXU passes: a TPU's default for f32 is one bf16 pass
            g = jnp.dot(x.T, x, precision="highest")
            q = jnp.dot(x.T, y, precision="highest")
        return g, q, jnp.asarray(x.shape[0], self.dtype)

    def factor(self, a) -> Factorization:
        if self.use_kernel:
            from repro.kernels import ops as _kops

            # blocked Pallas Cholesky; handle shape matches cho_factor's
            # (tri, lower) convention so rank_update works unchanged. Wide
            # single systems go through the HBM-streamed panel path — the
            # whole-resident batch kernel exceeds VMEM past d≈1024 f32.
            if a.shape[-1] >= _kops.STREAM_MIN_DIM:
                return Factorization(
                    (_kops.streamed_cholesky(a), True), backend=self)
            return Factorization(
                (_kops.blocked_cholesky(a[None])[0], True), backend=self)
        import jax.scipy.linalg as jsl

        return Factorization(jsl.cho_factor(a), backend=self)

    def rank_update(self, f: Factorization, xs) -> Factorization:
        """Rank-k update of a cho_factor handle. Kernel path: the whole
        stacked update in ONE fused Pallas sweep (`repro.kernels.ops.
        chol_rank_update`); otherwise a jit-compiled fori_loop column
        sweep."""
        import jax

        c, lower = f.handle
        xs = self.asarray(xs).reshape(-1, c.shape[0])
        # cho_factor leaves garbage in the untouched triangle — extract a
        # clean lower factor, sweep, and hand back a (lower, True) handle.
        tri = self._jnp.tril(c) if lower else self._jnp.triu(c).T
        if self.use_kernel:
            from repro.kernels import ops as _kops

            return Factorization(
                (_kops.chol_rank_update(tri, xs), True), backend=self)
        if self._rank_update_fn is None:
            self._rank_update_fn = jax.jit(_chol_rank_update_jax)
        return Factorization((self._rank_update_fn(tri, xs), True), backend=self)

    def rank_update_many(self, f: Factorization, roots) -> Factorization:
        """Batched fold on the device backend: the concatenated roots go
        through one rank-(Σk) sweep. Exact in exact arithmetic (a sum of
        Gram deltas is a Gram delta); the bit-for-bit-vs-sequential
        guarantee is the host backend's."""
        c, _ = f.handle
        d = c.shape[0]
        xs = [self.asarray(x).reshape(-1, d) for x in roots]
        stacked = xs[0] if len(xs) == 1 else self._jnp.concatenate(xs, 0)
        return self.rank_update(f, stacked)

    def factor_solve(self, f: Factorization, b):
        if self.use_kernel:
            from repro.kernels import ops as _kops

            tri, lower = f.handle
            l = tri if lower else tri.T
            if l.shape[-1] >= _kops.STREAM_MIN_DIM:
                return _kops.streamed_cholesky_solve(l, b)
            return _kops.cholesky_solve(l[None], b[None])[0]
        import jax.scipy.linalg as jsl

        return jsl.cho_solve(f.handle, b)

    def solve_sym(self, a, b):
        return self.factor_solve(self.factor(a), b)

    def fused_sweep(self, a, b, gammas):
        """Whole-γ-grid solve ``(a + γ_j I) W_j = b`` via the fused Pallas
        sweep kernel (kernel path only); singular γs come back as NaNs."""
        from repro.kernels import ops as _kops

        return _kops.multi_gamma_solve(
            a, b, self._jnp.asarray(gammas, self.dtype))

    def eigh(self, a):
        return self._jnp.linalg.eigh(a)

    def safe_reciprocal(self, v, cutoff):
        """1/v where |v| > cutoff, else 0 — pinv-style spectral truncation."""
        jnp = self._jnp
        return jnp.where(jnp.abs(v) > cutoff, 1.0 / jnp.where(v == 0, 1.0, v), 0.0)


def _chol_rank_update(R, xs):
    """Host rank-k Cholesky update: R upper with A = RᵀR → chol(A + xsᵀxs).

    One Householder column sweep over the implicit QR of ``[R; xs]``: at
    column i a single (k+1)-reflection annihilates all k update entries at
    once, so the work is k fused rank-1 updates — O(k·d²) flops in d
    vectorized iterations (not d·k scalar ones). Everything the inner loop
    touches (a row of R, the tail of xsᵀ) is contiguous in the C layout.
    The update is positive (a Gram delta), so the sweep cannot break down.
    """
    d = R.shape[0]
    R = np.array(R, np.float64, copy=True, order="C")
    xt = np.array(xs.T, np.float64, copy=True, order="C")  # (d, k) rows contiguous
    for i in range(d):
        w = xt[i]
        s = w @ w
        if s == 0.0:
            continue
        a = R[i, i]
        r = np.sqrt(a * a + s)
        amr = -s / (r + a)                 # a − r without cancellation
        beta = (r + a) / (r * s)           # 2 / uᵀu for u = [a−r; w]
        row = R[i, i + 1:]
        t = amr * row + xt[i + 1:] @ w     # uᵀ · [row; xs-tail]
        R[i, i] = r
        R[i, i + 1:] = row - (beta * amr) * t
        xt[i + 1:] -= (beta * t)[:, None] * w[None, :]
    return R


def _chol_rank_update_grouped(R, roots):
    """Grouped rank-(Σk) update: one column sweep folding a *sequence* of
    update-row groups, bit-for-bit equal to sequential per-group
    :func:`_chol_rank_update` calls.

    Why interleaving is exact, not just exact-in-exact-arithmetic: the
    sequential sweep reads and writes row i of R only at column step i, and
    a group's reflections couple to later groups solely through those rows —
    each group's own ``xt`` tail is private. So running column i for group
    1, then group 2, … performs the *same scalar operations in the same
    order* as finishing group 1's whole sweep before starting group 2's.
    Each group keeps its own contiguous (d, k_g) ``xt`` buffer so every
    BLAS call sees the exact shapes/strides of the sequential path.
    """
    d = R.shape[0]
    R = np.array(R, np.float64, copy=True, order="C")
    xts = [np.array(x.T, np.float64, copy=True, order="C") for x in roots]
    for i in range(d):
        for xt in xts:
            w = xt[i]
            s = w @ w
            if s == 0.0:
                continue
            a = R[i, i]
            r = np.sqrt(a * a + s)
            amr = -s / (r + a)
            beta = (r + a) / (r * s)
            row = R[i, i + 1:]
            t = amr * row + xt[i + 1:] @ w
            R[i, i] = r
            R[i, i + 1:] = row - (beta * amr) * t
            xt[i + 1:] -= (beta * t)[:, None] * w[None, :]
    return R


def _chol_rank_update_jax(L, xs):
    """Device twin of :func:`_chol_rank_update`: masked full-width columns so
    every iteration has static shapes under ``lax.fori_loop`` + ``jit``."""
    import jax
    import jax.numpy as jnp

    d = L.shape[0]
    idx = jnp.arange(d)

    def body(i, carry):
        L, xt = carry
        w = xt[i]
        s = w @ w
        s_ = jnp.where(s > 0, s, 1.0)      # w == 0 ⇒ t == 0, updates vanish
        a = L[i, i]
        r = jnp.sqrt(a * a + s)
        amr = -s / (r + a)
        beta = (r + a) / (r * s_)
        below = idx > i
        col = L[:, i]
        t = amr * col + xt @ w
        new_col = jnp.where(below, col - (beta * amr) * t, col).at[i].set(r)
        L = L.at[:, i].set(new_col)
        xt = jnp.where(below[:, None], xt - (beta * t)[:, None] * w[None, :], xt)
        return L, xt

    L, _ = jax.lax.fori_loop(0, d, body, (L, xs.T))
    return L


def _factor_has_nan(f: Factorization) -> bool:
    """True when a factor handle carries NaNs (host upper R, or a device
    ``(tri, lower)`` handle — reading the latter materializes it, which the
    host-driven serving path does anyway before solving)."""
    h = f.handle
    tri = h[0] if isinstance(h, tuple) else h
    return bool(np.any(np.isnan(np.asarray(tri))))


def get_backend(name: str, **kwargs):
    """Backend registry: ``numpy_f64`` | ``jax`` (+ dtype / use_kernel)."""
    if name == "numpy_f64":
        if kwargs.get("use_kernel"):
            raise ValueError("the Pallas kernel path requires the jax backend")
        return NumpyF64Backend()
    if name == "jax":
        return JaxBackend(dtype=kwargs.get("dtype"), use_kernel=bool(kwargs.get("use_kernel")))
    raise ValueError(f"unknown engine backend {name!r}")


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class AnalyticEngine:
    """Backend-agnostic AFL statistics→solve pipeline.

    One instance carries the protocol-level configuration (backend, the γ
    every client uses locally, accumulation policy); the statistics
    themselves travel as explicit :class:`SuffStats` values, so the engine is
    stateless and its methods are safe inside ``jit``/``shard_map`` for the
    jax backend.

    >>> eng = AnalyticEngine("numpy_f64", gamma=1.0)
    >>> stats = eng.merge(eng.client_stats(x1, y1), eng.client_stats(x2, y2))
    >>> w = eng.solve(stats)          # RI-restored joint solution (Thm 1+2)
    """

    def __init__(
        self,
        backend: str = "numpy_f64",
        *,
        gamma: float = 1.0,
        dtype=None,
        use_kernel: bool = False,
        kahan: bool = False,
    ):
        self.backend = get_backend(backend, dtype=dtype, use_kernel=use_kernel)
        self.gamma = float(gamma)
        if kahan and backend != "jax":
            raise ValueError("Kahan accumulation targets the f32 jax backend")
        self.kahan = bool(kahan)

    # -- accumulation -------------------------------------------------------

    def init(self, dim: int, num_classes: int) -> SuffStats:
        """Empty statistics (0 samples, 0 clients)."""
        b = self.backend
        comp_g = b.zeros((dim, dim)) if self.kahan else None
        comp_q = b.zeros((dim, num_classes)) if self.kahan else None
        return SuffStats(
            gram=b.zeros((dim, dim)),
            moment=b.zeros((dim, num_classes)),
            count=b.scalar(0.0),
            clients=b.scalar(0.0),
            gram_c=comp_g,
            moment_c=comp_q,
        )

    def update(self, stats: SuffStats, x, y) -> SuffStats:
        """Fold a batch of (embeddings, one-hot targets) into the statistics.

        Pure accumulation: ``clients`` is untouched — a participant marks
        itself with :meth:`finalize_client` (or arrives via
        :meth:`client_stats`) once its local stage is complete.
        """
        g_upd, q_upd, n = self.backend.gram_update(x, y)
        if self.kahan and stats.gram_c is not None:
            gram, gram_c = _kahan_add(stats.gram, stats.gram_c, g_upd)
            moment, moment_c = _kahan_add(stats.moment, stats.moment_c, q_upd)
        else:
            gram, gram_c = stats.gram + g_upd, stats.gram_c
            moment, moment_c = stats.moment + q_upd, stats.moment_c
        return SuffStats(gram, moment, stats.count + n, stats.clients,
                         gram_c, moment_c)

    def finalize_client(self, stats: SuffStats) -> SuffStats:
        """Mark accumulated statistics as ONE client's upload (clients=1)."""
        return stats._replace(clients=self.backend.scalar(1.0))

    def client_stats(self, x, y) -> SuffStats:
        """One client's local stage in a single call: raw stats, clients=1."""
        x = self.backend.asarray(x)
        y = self.backend.asarray(y)
        return self.finalize_client(
            self.update(self.init(x.shape[-1], y.shape[-1]), x, y))

    def merge(self, a: SuffStats, b: SuffStats) -> SuffStats:
        """The AA law in sufficient-statistics form: everything adds
        (Thm 1 / eq (11): C_agg = ΣC_k, Q_agg = ΣQ_k; client counts add for
        the lazy-γ bookkeeping of eq (15))."""
        return SuffStats(
            gram=a.gram + b.gram,
            moment=a.moment + b.moment,
            count=a.count + b.count,
            clients=a.clients + b.clients,
            gram_c=_maybe_add(a.gram_c, b.gram_c),
            moment_c=_maybe_add(a.moment_c, b.moment_c),
        )

    def merge_many(self, stats: SuffStats, uploads) -> SuffStats:
        """Left-fold a whole micro-batch of uploads in ONE stacked reduction.

        ``np.add.reduce`` over the leading axis of a stacked array
        accumulates strictly in index order (pairwise re-association only
        kicks in when reducing a contiguous *inner* axis), so the gram and
        moment come out bit-for-bit equal to sequential :meth:`merge` calls
        — the AA law is order-free in exact arithmetic, but the batched
        ingest fold is pinned to the sequential schedule exactly. The scalar
        ``count``/``clients`` fields fold in an explicit Python loop for the
        same reason. Kahan-compensated statistics (and non-host backends)
        keep the sequential path: compensation is intrinsically pairwise.
        """
        uploads = list(uploads)
        if not uploads:
            return stats
        if (not isinstance(self.backend, NumpyF64Backend)
                or stats.gram_c is not None
                or any(u.gram_c is not None for u in uploads)):
            for u in uploads:
                stats = self.merge(stats, u)
            return stats
        gram = np.add.reduce(
            np.stack([np.asarray(stats.gram)]
                     + [np.asarray(u.gram) for u in uploads]), axis=0)
        moment = np.add.reduce(
            np.stack([np.asarray(stats.moment)]
                     + [np.asarray(u.moment) for u in uploads]), axis=0)
        count, clients = stats.count, stats.clients
        for u in uploads:
            count = count + u.count
            clients = clients + u.clients
        return SuffStats(gram, moment, count, clients,
                         stats.gram_c, stats.moment_c)

    # -- regularization bookkeeping -----------------------------------------

    def regularized_gram(self, stats: SuffStats, gamma: Optional[float] = None):
        """``C_agg^r = Σ XᵀX + kγI`` — the regularized aggregate the paper's
        Algorithm 1 materializes (here derived lazily from raw stats)."""
        g = self.gamma if gamma is None else float(gamma)
        d = stats.gram.shape[0]
        return stats.gram + (stats.clients * g) * self.backend.eye(d)

    def _system(self, stats: SuffStats, use_ri: bool, target_gamma: float):
        d = stats.gram.shape[0]
        eye = self.backend.eye(d)
        if use_ri:
            # RI restore (Thm 2 / eq 16) on raw stats: the kγI term would be
            # added (eq 15) and removed (eq 16) analytically — so it is never
            # materialized; only the final target ridge remains.
            return stats.gram + self.backend.scalar(target_gamma) * eye
        return stats.gram + stats.clients * self.backend.scalar(self.gamma) * eye

    # -- solves -------------------------------------------------------------

    def solve(
        self,
        stats: SuffStats,
        *,
        use_ri: bool = True,
        target_gamma: float = 0.0,
    ):
        """Joint weight over everything merged into ``stats``.

        use_ri=True  → the paper's full pipeline (exact joint solution,
                       restored to ``target_gamma`` ridge; 0 = eq 16).
        use_ri=False → the biased no-RI aggregate carrying the accumulated
                       ``kγI`` (paper Table 3 ablation).
        """
        return self.backend.solve_sym(
            self._system(stats, use_ri, target_gamma), stats.moment)

    def factor(
        self,
        stats: SuffStats,
        *,
        use_ri: bool = True,
        target_gamma: float = 0.0,
    ) -> Factorization:
        """Factor the regularized system once; reuse via :meth:`factor_solve`.

        This is the serving hot path: one d³ factorization amortized over
        every straggler-poll ``solve()`` until new statistics arrive.
        """
        return self.backend.factor(self._system(stats, use_ri, target_gamma))

    def factor_solve(self, factorization: Factorization, b):
        """Solve against a cached factorization (d²·C instead of d³)."""
        return self.backend.factor_solve(factorization, b)

    def factor_update(
        self,
        factorization: Factorization,
        stats: SuffStats,
        root=None,
        *,
        use_ri: bool = True,
        target_gamma: float = 0.0,
        max_rank: Optional[int] = None,
    ) -> Factorization:
        """Fold a newly-merged low-rank delta into an existing factor.

        ``stats`` is the POST-merge aggregate (used only for the fallback);
        ``root`` is a (k, d) square root of the raw-Gram delta that was
        merged — ``rootᵀ·root == ΔGram`` — e.g. the client batch X_k itself
        or its QR ``R`` factor (same information as C_k, no raw features).

        When the delta is genuinely low-rank (k ≤ ``max_rank``; the default
        d//16 is the measured update-vs-refactor crossover at d=2048, see
        benchmarks/async_server_bench.py) and the factor is updatable, this
        is the O(k·d²) rank-k Cholesky update. Otherwise it falls back to a
        full refactor from ``stats``: dense delta (``root=None``), rank past
        the crossover, a pinv-fallback factor (the γ=0 rank-deficient path),
        or ``use_ri=False`` — whose per-client +γI delta is full-rank by
        construction.

        ``root`` may also be a list/tuple of (k_i, d) roots — a micro-batch
        of deltas folded in one grouped sweep (:meth:`Factorization.
        rank_update_many`); the budget then applies to Σk_i. Either way the
        updated factor is checked for NaNs (a breakdown can only come from
        non-finite inputs — the update itself is positive) and a poisoned
        sweep falls back to the full refactor instead of caching NaNs.
        """
        if root is not None and use_ri and factorization.updatable:
            roots = list(root) if isinstance(root, (list, tuple)) else [root]
            roots = [self.backend.asarray(r).reshape(-1, stats.dim)
                     for r in roots]
            total = sum(int(r.shape[0]) for r in roots)
            budget = max(1, stats.dim // 16) if max_rank is None else int(max_rank)
            if total <= budget:
                updated = (factorization.rank_update(roots[0])
                           if len(roots) == 1
                           else factorization.rank_update_many(roots))
                if not _factor_has_nan(updated):
                    return updated
        return self.factor(stats, use_ri=use_ri, target_gamma=target_gamma)

    def ri_restore(
        self,
        w_agg_r,
        c_agg_r,
        num_clients: int,
        gamma: Optional[float] = None,
        target_gamma: float = 0.0,
    ):
        """Theorem 2 / eq (16) in its explicit form, for *regularized*
        aggregates (Ŵ_agg^r, C_agg^r) as produced by the paper-literal
        Algorithm 1: ``Ŵ_agg = (C_agg^r − KγI)^{-1} C_agg^r Ŵ_agg^r``."""
        b = self.backend
        g = self.gamma if gamma is None else float(gamma)
        d = c_agg_r.shape[0]
        shift = b.scalar(num_clients * g - target_gamma) * b.eye(d)
        return b.solve_sym(c_agg_r - shift, c_agg_r @ w_agg_r)

    def solve_multi_gamma(
        self,
        stats: SuffStats,
        gammas: Sequence[float],
        *,
        use_ri: bool = True,
        rcond: float = 1e-12,
    ):
        """Solve the same statistics under several target ridges at once.

        One eigendecomposition ``C = VΛVᵀ`` (d³) serves every γ:
        ``W(γ) = V (Λ+γ)^{-1} Vᵀ Q`` is then d²·C per γ — the γ model sweep
        costs barely more than a single solve. Eigenvalues with
        ``λ+γ <= rcond·λ_max`` are treated as zero (pinv semantics), so the
        γ=0 rank-deficient case matches the fallback of the direct solve.

        Returns a list of weights, one per γ, each the RI-restored
        (``use_ri=True``) or biased (``use_ri=False``, γ then *adds* the
        lazy kγ term per eq (15)) solution.

        Backends route differently: the Pallas-kernel jax backend runs the
        whole grid through ONE fused factor+solve kernel call
        (:func:`repro.kernels.solve.multi_gamma_solve`), falling back to the
        eigendecomposition below only when a system in the grid is singular
        (the γ=0 rank-deficient ablations — NaNs trip the fallback, so pinv
        semantics match the numpy_f64 oracle). Everything else goes through
        a fresh :class:`SweepFactorization` — one eigendecomposition, every
        γ; serving coordinators keep that handle and rank-update it instead
        (see :meth:`sweep_factor` / :meth:`sweep_solve`).
        """
        gammas = [float(g) for g in gammas]
        if getattr(self.backend, "use_kernel", False) and gammas:
            base = stats.gram if use_ri else self.regularized_gram(stats)
            ws = self.backend.fused_sweep(base, stats.moment, gammas)
            ws_host = np.asarray(ws)
            if (bool(np.isfinite(ws_host).all())
                    and _cholesky_sweep_trustworthy(
                        base, stats.moment, ws_host, rcond)):
                return [ws[i] for i in range(len(gammas))]
            # singular or ≈singular system in the grid (NaNs, or a solution
            # blown up past what the pinv truncation would allow):
            # eigendecomposition/pinv fallback with the caller's rcond
        return self.sweep_solve(self.sweep_factor(stats, use_ri=use_ri),
                                stats.moment, gammas, rcond=rcond)

    def sweep_factor(self, stats: SuffStats, *,
                     use_ri: bool = True) -> SweepFactorization:
        """Eigendecompose the aggregate once for repeated γ sweeps.

        The returned handle is rank-updatable: as low-rank arrivals merge
        into an evolving federation, :meth:`SweepFactorization.rank_update`
        folds their roots in O(d²·k) and :meth:`sweep_solve` stays exact via
        Woodbury in the fixed eigenbasis — no per-sweep d³ re-factorization.
        """
        base = stats.gram if use_ri else self.regularized_gram(stats)
        vals, vecs = self.backend.eigh(base)
        d = stats.dim
        return SweepFactorization(vals, vecs, self.backend,
                                  u=np.zeros((0, d)), vu=np.zeros((d, 0)))

    def sweep_solve(
        self,
        handle: SweepFactorization,
        moment,
        gammas: Sequence[float],
        *,
        rcond: float = 1e-12,
    ):
        """Solve the γ grid against a (possibly rank-updated) sweep handle.

        rank == 0 reproduces the plain spectral sweep bit-for-bit; with
        pending updates each γ costs one extra k×k solve (exact Woodbury).
        Raises :class:`SweepRefreshNeeded` when pending updates meet the
        pinv truncation cutoff (rank-deficient base at γ≈0) — the caller
        rebuilds the handle from current statistics, which always succeeds.
        """
        b = handle.backend
        vals, vecs = handle.vals, handle.vecs
        vq = vecs.T @ moment
        scale = abs(float(np.max(np.asarray(vals)))) if np.asarray(vals).size else 1.0
        cutoff = rcond * max(scale, np.finfo(np.float32).tiny)
        k = handle.rank
        eye_k = np.eye(k)
        out = []
        for g in gammas:
            inv = b.safe_reciprocal(vals + b.scalar(float(g)), cutoff)
            if k == 0:
                out.append(vecs @ (inv[:, None] * vq))
                continue
            inv_h = np.asarray(inv, np.float64)
            if np.any(inv_h == 0.0):
                raise SweepRefreshNeeded(
                    f"spectral truncation at γ={g} with {k} pending update "
                    "rows — rebuild the sweep handle from current stats")
            su = inv_h[:, None] * np.asarray(handle.vu, np.float64)  # (d, k)
            cap = eye_k + handle.vu.T @ su                           # (k, k)
            rhs = su.T @ np.asarray(vq, np.float64)                  # (k, C)
            coeff = inv_h[:, None] * np.asarray(vq, np.float64) \
                - su @ np.linalg.solve(cap, rhs)
            out.append(np.asarray(vecs, np.float64) @ coeff)
        return out


def _cholesky_sweep_trustworthy(base, moment, ws_host, rcond) -> bool:
    """Should a finite fused-Cholesky sweep result be trusted, or does the
    grid need the eigendecomposition/pinv path?

    NaN catches exactly-singular pivots, but roundoff can leave a
    rank-deficient system's smallest pivots tiny-*positive*: the factor
    then succeeds and returns finite weights with norms ~1/λ_noise — where
    the documented pinv semantics (eigenvalues ≤ rcond·λ_max treated as
    zero) would have truncated. For any γ the pinv solution satisfies
    ``‖W‖ ≤ ‖Q‖ / (rcond·λ_max)``, and trace(base) ≥ λ_max for PSD base —
    so a solution with ``‖W‖·rcond·trace > ‖Q‖`` can only come from
    inverting spectrum the truncation would have zeroed. Conservative by at
    most the d× gap between trace and λ_max (extra fallbacks are merely
    slower, never wrong)."""
    scale = float(np.trace(np.asarray(base, np.float64)))
    q_norm = float(np.linalg.norm(np.asarray(moment, np.float64)))
    w_norm = float(max(np.linalg.norm(w) for w in ws_host))
    return w_norm * float(rcond) * max(scale, np.finfo(np.float32).tiny) \
        <= q_norm


def _kahan_add(total, comp, upd):
    """One compensated-summation step: returns (new_total, new_comp)."""
    y = upd - comp
    t = total + y
    comp = (t - total) - y
    return t, comp


def _maybe_add(a, b):
    if a is None or b is None:
        return None
    return a + b
