"""The comparisons that decide ``correct``, each one number against its
limit (``limits/<workload>.json``).

``emb_gap``    forward and pooling: the largest relative L2 gap of an
               embedding row, over the checked batches, from the plain
               reference at the precision the configuration states.
``fold_gap``   the folded statistics: the report's XᵀX and XᵀY against the
               float64 fold of every row the client folded, the larger of
               the two normwise (Frobenius) gaps, each relative to the
               float64 statistic's norm. Normwise, because float32
               accumulation over hundreds of batches puts a few entries'
               rounding near the control's; summed over every entry, the
               control's product error stands out.
``count_gap``  rows the report says it folded, against the rows sent (exact).
``head_berr``  the solved head W: its normwise backward error on the
               report's own normal equations, ‖GW − Q‖ / (‖G‖‖W‖ + ‖Q‖).

In the control (``--control``) each stage is replaced by the reference one
precision lower (``bench.precision``): the forward at the configuration's
control precision, the fold at ``high``, the solve one floating type down.
"""

from __future__ import annotations

import numpy as np


def row_gap(x, ref) -> float:
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    num = np.linalg.norm(x - ref, axis=-1)
    den = np.maximum(np.linalg.norm(ref, axis=-1), np.finfo(np.float64).tiny)
    return float(np.max(num / den))


def stats_gap(gram, moment, gram_ref, moment_ref) -> float:
    def rel(a, b):
        return float(np.linalg.norm(np.asarray(a, np.float64) - b)
                     / max(np.linalg.norm(b), np.finfo(np.float64).tiny))
    return max(rel(gram, gram_ref), rel(moment, moment_ref))


def backward_error(g, q, w) -> float:
    """Normwise backward error of W on G W = Q; NaN where all are zero."""
    g, q, w = (np.asarray(a, np.float64) for a in (g, q, w))
    den = np.linalg.norm(g) * np.linalg.norm(w) + np.linalg.norm(q)
    return float(np.linalg.norm(g @ w - q) / den) if den > 0 else float("nan")


def solve_like_server(g, q, dtype):
    """The host solve of the normal equations, Cholesky else pseudo-inverse,
    computed in ``dtype``."""
    g = np.asarray(g, dtype)
    q = np.asarray(q, dtype)
    try:
        low = np.linalg.cholesky(g)
    except np.linalg.LinAlgError:
        return np.linalg.pinv(g) @ q
    y = np.linalg.solve(low, q)
    return np.linalg.solve(low.T, y)


def lower_solve(g, q, solved_in: str):
    """The control's head: the same system solved one floating type below
    the one the coordinator solves in (float64 → float32; float32 →
    statistics rounded to bfloat16, solved in float32)."""
    if solved_in == "float64":
        return solve_like_server(g, q, np.float32)
    if solved_in == "float32":
        import jax.numpy as jnp

        rb = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)
        return solve_like_server(rb(g), rb(q), np.float32)
    raise ValueError(f"no solve below {solved_in!r}")
