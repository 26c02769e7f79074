"""Operation and byte counts against counts made by hand, through the
family modules that keep them."""

import json
import os

import pytest

from bench import flops
from bench.reference import dense, xlstm

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_gram_kernel_counts():
    # n=32 rows, d=1024, c=100: 2·32·1024² + 2·32·1024·100 operations;
    # X, Y read once, G, Q written once, 4 bytes each
    ops, nbytes = flops.gram_kernel(32, 1024, 100)
    assert ops == 2 * 32 * 1024 * 1024 + 2 * 32 * 1024 * 100 == 73_662_464
    assert nbytes == 4 * (32 * 1024 + 32 * 100 + 1024 * 1024 + 1024 * 100)


def test_roofline_picks_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000.0, 50.0, peaks) == 10.0     # compute
    assert flops.roofline_seconds(100.0, 50.0, peaks) == 5.0       # memory


def test_minicpm_forward_by_hand():
    cfg = _cfg("minicpm_2b")
    d, L, f, s = 2304, 40, 5760, 512
    per_layer = 4 * d * d + 3 * d * f          # MHA q, k, v, o + SwiGLU
    assert per_layer * L == 2_441_871_360      # the 2.44e9 matmul weights
    want = 2 * per_layer * L * s + 4 * L * s * s * d
    assert flops.forward_per_sample(cfg, s) == want
    assert dense.forward_ops(cfg, s) == want


def test_xlstm_forward_by_hand():
    cfg = _cfg("xlstm_350m")
    d, di, h, dh = 1024, 2048, 4, 512
    m = d * 2 * di + di * 3 * di + di * 2 * h + di * d     # 21 mLSTM blocks
    s_ = d * 2 * di + di * 4 * di + di * d                 # 3 sLSTM blocks
    per_token = 2 * (21 * m + 3 * s_) + h * (21 * 4 * dh * dh + 3 * 8 * dh * dh)
    assert flops.forward_per_sample(cfg, 128) == per_token * 128
    assert xlstm.forward_ops(cfg, 128) == per_token * 128
    # the fold adds 2·d·(d + C) per sample
    assert (flops.local_per_sample(cfg, 128) - per_token * 128
            == 2 * 1024 * (1024 + 100))


def test_unknown_family_is_refused():
    with pytest.raises(ValueError):
        flops.forward_per_sample({"family": "unknown"}, 8)


@pytest.mark.parametrize("name,seq,want", [
    ("xlstm_350m", 128, 133_771_239_424),
    ("minicpm_2b", 512, 2_597_124_114_432),
])
def test_local_counts_are_the_ones_local_mfu_has_read(name, seq, want):
    """Each cell's operations a sample, exactly as counted before the counts
    moved into the family modules: ``local_mfu`` reads the same."""
    assert flops.local_per_sample(_cfg(name), seq) == want


def test_family_without_a_count_is_refused(monkeypatch):
    import sys
    import types

    monkeypatch.setitem(sys.modules, "bench.reference.uncounted",
                        types.ModuleType("bench.reference.uncounted"))
    with pytest.raises(ValueError, match="uncounted"):
        flops.forward_per_sample({"family": "uncounted"}, 8)
