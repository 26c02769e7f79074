"""The reduction from a trace to metrics: busy union, idle share, time by
name and idle gaps by host span, on small traces with known answers."""

import os

import pytest

from bench import trace as T

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "xlstm_350m.local.trace.json")


def _trace():
    E = T.Event
    # window 0..10 s; chip 0 runs ops 1–3 (twice, overlapping) and 5–6;
    # chip 1 runs 0–10 except 4–8
    return T.Trace(
        ops={0: [E("fusion.1", 1, 3), E("fusion.2", 2, 3), E("gram", 5, 6),
                 E("fusion.1", 11, 12)],
             1: [E("fusion.1", 0, 4), E("all-reduce.3", 8, 10)]},
        modules={0: [E("jit_fwd(1)", 1, 3), E("jit_gram_update(2)", 5, 6)],
                 1: [E("jit_fwd(1)", 0, 4), E("jit_local(3)", 8, 10)]},
        spans=[E("bench.window", 0, 10), E("bench.forward", 0.5, 3.5),
               E("bench.wait", 3.5, 9.0), E("bench.fold", 4.0, 5.0)])


def test_merged_and_union():
    assert T.merged([(2, 3), (1, 2.5), (5, 6), (-1, 0.5)], 0, 5.5) == [
        (0, 0.5), (1, 3), (5, 5.5)]
    assert T.union_length([(1, 3), (2, 4)], 0, 10) == 3


def test_busy_and_window():
    tr = _trace()
    assert tr.window() == (0, 10)
    assert tr.window_s() == 10
    # chip 0: 1–3 and 5–6 → 3 s; chip 1: 0–4 and 8–10 → 6 s; mean 4.5 s
    assert tr.busy_s() == pytest.approx(4.5)


def test_seconds_by_name():
    tr = _trace()
    # fusion.1: chip 0 has 2 s inside the window, chip 1 has 4 s → mean 3
    assert tr.op_seconds(lambda n: n == "fusion.1") == pytest.approx(3.0)
    assert tr.op_seconds(lambda n: n.startswith("all-reduce")) == 1.0
    fwd = lambda n: n.split("(")[0] == "jit_fwd"
    assert tr.module_seconds(fwd) == pytest.approx(3.0)
    assert tr.module_seconds(lambda n: not fwd(n)) == pytest.approx(1.5)


def test_top_ops_names_the_program_and_skips_holders():
    top = dict((k, v) for k, v in _trace().top_ops())
    # chip 0's fusion.1 (1–3) holds fusion.2 (2–3): only chip 1's 4 s count
    assert top["jit_fwd/fusion.1"] == pytest.approx(2.0)
    assert top["jit_fwd/fusion.2"] == pytest.approx(0.5)
    assert top["jit_gram_update/gram"] == pytest.approx(0.5)


def test_short_names_drop_the_hlo_text():
    assert T.short("%while.39 = (s32[], f32[32]) while(...)") == "%while.39"
    assert T.short("fusion.3") == "fusion.3"


def test_idle_gaps_go_to_the_host_span():
    gaps = dict((k, v) for k, v in _trace().idle_gaps())
    # chip 0 idle: 0–1 (forward span), 3–5 (mid 4: wait and fold open →
    # the shorter, fold), 6–10 (mid 8: wait)
    assert gaps == pytest.approx({"bench.forward": 1.0, "bench.fold": 2.0,
                                  "bench.wait": 4.0})


def test_json_round_trip():
    tr = _trace()
    back = T.Trace.from_json(tr.to_json())
    assert back.busy_s() == tr.busy_s() and back.spans == tr.spans


def test_window_must_be_unique():
    tr = _trace()
    tr.spans = [e for e in tr.spans if e.name != "bench.window"]
    with pytest.raises(ValueError):
        tr.window()


def _brute_busy(tr, step=1e-7):
    """Busy time of chip 0 by sampling the window every ``step`` seconds."""
    import numpy as np

    lo, hi = tr.window()
    t = np.arange(lo, hi, step) + step / 2
    on = np.zeros(len(t), bool)
    for e in tr.ops[0]:
        on |= (t >= e.start) & (t < e.end)
    return on.sum() * step


def test_recorded_trace():
    """3 ms of a v5e trace of ``xlstm_350m.local``: the end of one forward,
    the Gram fold, the host's turn between batches, the next forward."""
    tr = T.Trace.load_json(RECORDED)
    assert tr.window_s() == pytest.approx(0.003)
    assert tr.busy_s() == pytest.approx(_brute_busy(tr), abs=2e-7)
    fwd = lambda n: n.split("(")[0] == "jit_fwd"
    # the two forwards' parts inside the window
    assert tr.module_seconds(fwd) == pytest.approx(
        (1.334611953 - 1.334) + (1.337 - 1.33619072))
    # the fold: the Gram kernel's program, a convert, three accumulations
    assert tr.module_seconds(lambda n: n.startswith("jit_gram_update")) == \
        pytest.approx(35.693e-6, abs=1e-9)
    assert tr.module_seconds(lambda n: not fwd(n)) == pytest.approx(
        (35.693 + 0.592 + 19.351 + 2.338 + 0.940) * 1e-6, abs=1e-8)
    # idle between the programs goes to the host span around it
    gaps = dict((k, v) for k, v in tr.idle_gaps())
    assert set(gaps) == {"bench.fold"}
    assert gaps["bench.fold"] == pytest.approx(tr.window_s() - tr.busy_s())

