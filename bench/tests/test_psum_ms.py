"""``psum_ms``, the all-reduce that joins the chips' Gram statistics, read
from the ``%all-reduce`` operations of a trace; and the breakdown of the
recorded one-chip trace, pinned."""

import pytest

from bench import harness
from bench import trace as T
from bench.tests.test_trace_reduction import RECORDED

psum_ms = harness.metric_reader("psum_ms")


def _run(tr, steps):
    return harness.TracedRun(cell=None, trace=tr, facts={"steps": steps},
                             peaks={})


def _mesh_trace():
    E = T.Event
    # window 0..10 s on two chips that run a forward, the Gram kernel and
    # all-reduces, named as a v5e trace names them (HLO text after " = ");
    # on chip 1 one all-reduce starts before the window, one ends after it
    ar = "%all-reduce.1 = f32[1024,1024]{1,0} all-reduce(f32[1024,1024] %x)"
    return T.Trace(
        ops={0: [E("%fusion.3 = f32[32,1024] fusion(...)", 1, 6),
                 E("%gram_update.1 = f32[1024,1024] custom-call(...), "
                   "custom_call_target=\"tpu_custom_call\"", 6, 7),
                 E(ar, 7, 7.5), E(ar, 9, 9.25)],
             1: [E("%fusion.3 = f32[32,1024] fusion(...)", 1, 6),
                 E("%all-reduce-start.2 = f32[1024] all-reduce-start(...)",
                   -1, 0.5),
                 E(ar, 7, 7.5), E(ar, 9.75, 11)]},
        modules={0: [E("jit_fwd(1)", 1, 6), E("jit_local(2)", 6, 9.25)],
                 1: [E("jit_fwd(1)", 1, 6), E("jit_local(2)", 6, 11)]},
        spans=[E("bench.window", 0, 10)])


@pytest.mark.parametrize("steps,want_ms", [(1, 1.0e3), (2, 0.5e3),
                                           (0, None)])
def test_psum_ms_reads_the_all_reduces_per_batch(steps, want_ms):
    # chip 0: 0.5 + 0.25 s; chip 1: 0.5 (start, in window) + 0.5 + 0.25 s;
    # the mean over chips is 1.0 s
    got = psum_ms(_run(_mesh_trace(), steps))
    assert got == (pytest.approx(want_ms) if want_ms else None)


def test_psum_ms_is_silent_on_one_chip():
    """The recorded one-chip trace has no all-reduce: no reading, no error."""
    assert psum_ms(_run(T.Trace.load_json(RECORDED), steps=1)) is None


def test_recorded_trace_breakdown_is_unchanged():
    """The breakdown the reduction gives the recorded trace, as the
    result line reports it."""
    tr = T.Trace.load_json(RECORDED)
    want_top = [
        ["jit_fwd/%constant_dynamic-slice_fusion.22", 0.000715195999999807],
        ["jit_fwd/%fusion.118", 0.0005638680000001894],
        ["jit_fwd/%fusion", 6.792199999994253e-05],
        ["jit_gram_update/%gram_update.1", 3.390600000008348e-05],
        ["jit_fwd/%broadcast_select_fusion", 2.5551000000012536e-05],
        ["jit_fwd/%multiply_reduce_fusion.5", 2.331699999991166e-05],
        ["jit_fwd/%multiply_reduce_fusion.6", 2.3002000000049705e-05],
        ["jit_add/%add.1", 2.168200000007836e-05],
        ["jit_gram_update/%copy", 1.1619999999989972e-06],
        ["jit_fwd/%broadcast_multiply_fusion", 4.2599999994230586e-07]]
    assert tr.top_ops(10) == want_top
    assert tr.idle_gaps(10) == [["bench.fold", 0.0015219229999998696]]
