"""``seqmix_ms`` and ``ffn_ms``: device time a batch under the program's
``mixer/seqmix`` and ``ffn`` scopes, read from a trace's scoped operations;
nothing where no operation carries the scope."""

import pytest

from bench import harness
from bench import trace as T
from bench.tests.test_trace_reduction import RECORDED, _scoped_trace


def _run(tr, steps):
    return harness.TracedRun(cell=None, trace=tr, facts={"steps": steps},
                             peaks={})


@pytest.mark.parametrize("metric,steps,want_ms", [
    ("seqmix_ms", 1, 2.5e3), ("seqmix_ms", 5, 0.5e3), ("seqmix_ms", 0, None),
    ("ffn_ms", 1, 2.5e3), ("ffn_ms", 2, 1.25e3),
])
def test_reads_the_scope_per_batch(metric, steps, want_ms):
    got = harness.metric_reader(metric)(_run(_scoped_trace(), steps))
    assert got == (pytest.approx(want_ms) if want_ms else None)


@pytest.mark.parametrize("metric", ["seqmix_ms", "ffn_ms"])
def test_silent_without_scopes(metric):
    """The recorded trace carries no scopes: no reading, no error."""
    assert harness.metric_reader(metric)(
        _run(T.Trace.load_json(RECORDED), steps=1)) is None


def test_ffn_ms_is_silent_where_no_op_is_under_ffn():
    tr = _scoped_trace()
    tr.ops = {c: [e for e in evs if not T.in_scope(e.scope, "ffn")]
              for c, evs in tr.ops.items()}
    assert harness.metric_reader("ffn_ms")(_run(tr, 1)) is None
    assert harness.metric_reader("seqmix_ms")(_run(tr, 1)) is not None
