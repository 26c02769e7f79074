"""Tiny cells for the CPU tests: the real configurations and limits with
every size cut so that a run fits a test."""

from __future__ import annotations

import json
import os

from bench import harness

SIZES = {
    "xlstm_350m": dict(num_layers=4, slstm_every=2, d_model=64, num_heads=4,
                       num_kv_heads=4, vocab_size=256, num_classes=8),
    "minicpm_2b": dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                       d_ff=128, vocab_size=256, num_classes=8),
}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# the one-chip cells' head_berr limits are for AFLServer's float64 solve;
# on a mesh, ShardedCoordinator solves in float32
MESH_HEAD_BERR = 1e-5


def cell(workload: str, chips: int = 1, batch: int = 8) -> harness.Cell:
    real = harness.load_cell(workload)
    cfg = dict(real.cfg, **SIZES[real.cfg["name"]])
    mix = dict(real.mix, batch=batch, seq=16, pool_batches=4)
    limits = (real.limits if chips == 1
              else dict(real.limits, head_berr=MESH_HEAD_BERR))
    return harness.Cell(workload, chips, cfg, mix, limits,
                        real.end_to_end, real.per_layer)


def run(c: harness.Cell, devices, *, control=False, trace=False, seed=12345,
        seconds=0.0) -> harness.Result:
    """One run; a window of 0 s folds one batch after the warm-up."""
    return harness.run_cell(c, devices, seed=seed, seconds=seconds,
                            trace=trace, t0=0.0, control=control)


def no_cache(monkeypatch):
    """The tests write no compile cache and know no chip's peaks."""
    monkeypatch.setattr(harness, "use_compile_cache", lambda *a: None)
    monkeypatch.setattr(harness, "peaks", lambda kind: PEAKS)


def load(path: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), path)) as f:
        return json.load(f)
