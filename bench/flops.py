"""Operations and bytes from shapes, for the utilization and roofline
metrics. Counted from the configuration's own sizes, never from the
program's compiled cost analysis, so a change to the program cannot move
the yardstick. One multiply-add counts as two operations. Counts that
belong to one backbone family (its forward, its own kernels) live in that
family's module under ``bench/reference/``; what every family shares is here.
"""

from __future__ import annotations

from bench import reference


def forward_per_sample(cfg: dict, seq: int) -> float:
    """Operations of the backbone forward for one sequence of ``seq`` tokens
    (the embedding lookup, norms and pooling are not counted): the count
    ``forward_ops(cfg, seq)`` of the family's own module,
    ``bench/reference/<family>.py``. A family with no module, or whose
    module has no count, is refused."""
    count = getattr(reference.of(cfg), "forward_ops", None)
    if count is None:
        raise ValueError(f"no operation count for family {cfg['family']!r}")
    return count(cfg, seq)


def fold_per_sample(d: int, c: int) -> float:
    """One row folded into XᵀX (d × d) and XᵀY (d × c)."""
    return 2 * d * (d + c)


def local_per_sample(cfg: dict, seq: int) -> float:
    """The local stage's model operations per sample: forward and fold."""
    return (forward_per_sample(cfg, seq)
            + fold_per_sample(cfg["d_model"], cfg["num_classes"]))


def gram_kernel(n: int, d: int, c: int, itemsize: int = 4):
    """(operations, bytes) of one fused Gram update of n rows: the products
    XᵀX and XᵀY, reading X and Y once and writing G and Q once."""
    ops = 2 * n * d * d + 2 * n * d * c
    nbytes = itemsize * (n * d + n * c + d * d + d * c)
    return float(ops), float(nbytes)


def roofline_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
