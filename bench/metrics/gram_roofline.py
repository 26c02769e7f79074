"""The Gram kernel's share of its roofline, in %: the least time a chip
could take for one batch's fused XᵀX, XᵀY of its rows (``bench.flops``:
the larger of the compute and the HBM bound), over the kernel's measured
device time per batch."""

from bench import flops


def read(run):
    sec = run.trace.op_seconds(run.facts["gram_kernel_match"])
    steps = run.facts["steps"]
    if sec <= 0 or not steps:
        return None
    cfg = run.cell.cfg
    ops, nbytes = flops.gram_kernel(run.facts["rows_per_chip"], cfg["d_model"],
                                    cfg["num_classes"])
    return 100 * flops.roofline_seconds(ops, nbytes, run.peaks) / (sec / steps)
