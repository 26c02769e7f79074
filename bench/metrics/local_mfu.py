"""The local stage's share of the chips' peak, in %: model operations per
sample (``bench.flops.local_per_sample``: forward and fold, from the
configuration's shapes) times the samples per second of the traced run, over
chips × the bf16 peak."""

from bench import flops


def read(run):
    f = run.facts
    ops = flops.local_per_sample(run.cell.cfg, f["seq"])
    return 100 * ops * f["samples_per_s"] / (
        f["chips"] * run.peaks["bf16_flops_per_s"])
