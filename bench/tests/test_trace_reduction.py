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



# -- scopes -----------------------------------------------------------------

def _scoped_trace():
    E = T.Event
    # window 0..10 s. Chip 0: a layer loop (0.5–8.5, scope "mixer") holds
    # its body's operations; one seqmix op runs after the window. Chip 1:
    # an ffn op straddles the window's end (1 s inside).
    return T.Trace(
        ops={0: [E("%while.1", 0.5, 8.5, "mixer"),
                 E("%fusion.1", 1, 3, "mixer/seqmix"),
                 E("%fusion.2", 3, 4, "mixer/seqmix/_mlstm_chunk"),
                 E("%fusion.3", 4, 5, "mixer"),
                 E("%fusion.4", 5, 6, "ffn"),
                 E("%fusion.5", 6, 7, "ffn2"),
                 E("%fusion.6", 7, 8, "ffn/mlp"),
                 E("%fusion.7", 9, 9.5, ""),
                 E("%fusion.1", 11, 12, "mixer/seqmix")],
             1: [E("%fusion.1", 0, 2, "mixer/seqmix"),
                 E("%fusion.4", 2, 4, "ffn"),
                 E("%fusion.4", 9, 11, "ffn")]},
        modules={0: [E("jit_fwd(1)", 0.5, 9.5)], 1: [E("jit_fwd(1)", 0, 11)]},
        spans=[E("bench.window", 0, 10)])


@pytest.mark.parametrize("scope,want", [
    # chip 0: 2 + 1 (nested) s, the op after the window left out; chip 1: 2
    ("mixer/seqmix", 2.5),
    # the loop that holds the body is not a leaf: chip 0 3 + 1, chip 1 2
    ("mixer", 3.0),
    # ffn and ffn/mlp, not ffn2: chip 0 1 + 1; chip 1 2 + 1 (window clip)
    ("ffn", 2.5),
    ("ffn2", 0.5),
    ("mixer/seqmix/_mlstm_chunk", 0.5),
    # a string prefix that is no whole segment matches nothing
    ("mix", 0.0),
    ("mixer/seq", 0.0),
])
def test_scope_seconds(scope, want):
    assert _scoped_trace().scope_seconds(scope) == pytest.approx(want)


@pytest.mark.parametrize("op_name,want", [
    ("jit(fwd)/while/body/closed_call/mixer/seqmix/dot_general",
     "mixer/seqmix"),
    ("jit(fwd)/while/body/closed_call/while/body/closed_call/mixer/seqmix/"
     "_mlstm_chunk/mul", "mixer/seqmix/_mlstm_chunk"),
    ("jit(fwd)/jit(main)/mixer/seqmix/bhgqd,bhkd->bhgqk/dot_general",
     "mixer/seqmix/bhgqd,bhkd->bhgqk"),
    ("jit(fwd)/while/body/closed_call/ffn/mul", "ffn"),
    ("jit(fwd)/while/body/dynamic_slice", ""),
    ("jit(fwd)/add", ""),
    ("", ""),
])
def test_scope_path_drops_what_jax_adds(op_name, want):
    assert T.scope_path(op_name) == want


@pytest.mark.parametrize("path,scope,want", [
    ("ffn", "ffn", True), ("ffn/mlp", "ffn", True), ("ffn2", "ffn", False),
    ("mixer/seqmix", "mixer", True), ("mixer", "mixer/seqmix", False),
    ("", "ffn", False),
])
def test_in_scope_takes_whole_segments(path, scope, want):
    assert T.in_scope(path, scope) is want


HLO = """\
HloModule jit_fwd, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(f32[8]{0} %param_0, f32[8]{0} %param_0), metadata={op_name="jit(fwd)/while/body/mixer/mul" source_file="x.py" source_line=3}
  ROOT %add.1 = f32[8]{0} add(f32[8]{0} %mul.1, f32[8]{0} %param_0), metadata={op_name="jit(fwd)/while/body/ffn/add" source_file="x.py" source_line=4}
}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %bitcast.1 = f32[8]{0} bitcast(f32[8]{0} %param_0.1)
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(fwd)/while/body/mixer/mul"}
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %fusion), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fwd)/mixer/seqmix/exp"}
  %copy.2 = f32[8]{0} copy(f32[8]{0} %fusion.1)
  ROOT %tanh.3 = f32[8]{0} tanh(f32[8]{0} %copy.2), metadata={op_name="jit(fwd)/pool/tanh"}
}
"""


def test_hlo_scopes_give_a_fusion_its_roots_scope():
    scopes = T.hlo_scopes(HLO)
    # the fusion's root is ffn/add, though the fusion itself says mixer/mul
    assert scopes["%fusion"] == "ffn"
    # a root with no op name: the fusion's own
    assert scopes["%fusion.1"] == "mixer/seqmix"
    assert scopes["%tanh.3"] == "pool"
    assert "%copy.2" not in scopes and "%x" not in scopes


_XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Modules"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000000000 }
    events { metadata_id: 2 offset_ps: 5000000000000 duration_ps: 1000000000000 }
  }
  lines {
    id: 2
    name: "XLA Ops"
    events { metadata_id: 3 offset_ps: 1000000000000 duration_ps: 2000000000000 }
    events { metadata_id: 4 offset_ps: 3000000000000 duration_ps: 1000000000000 }
    events { metadata_id: 3 offset_ps: 5000000000000 duration_ps: 1000000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_fwd(7)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_other(8)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%fused_computation" } }
  event_metadata { key: 4 value { id: 4 name: "%tanh.3 = f32[8]{0} tanh(f32[8]{0} %copy.2)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python3"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
"""


def test_from_profile_scopes_ops_by_their_programs_hlo():
    """The HLO of the program run that holds an operation names its scope,
    and only that program's: the same instruction name in another program
    stays unscoped, as does every operation without the HLO."""
    from jax.profiler import ProfileData

    data = ProfileData.from_text_proto(_XSPACE)
    tr = T.Trace.from_profile(data, {"jit_fwd": HLO})
    assert [(T.short(e.name), e.scope) for e in tr.ops[0]] == [
        ("%fusion", "ffn"), ("%tanh.3", "pool"), ("%fusion", "")]
    assert tr.scope_seconds("ffn") == pytest.approx(2.0)
    assert [e.scope for e in T.Trace.from_profile(data).ops[0]] == [""] * 3


def test_json_carries_the_scope():
    tr = _scoped_trace()
    back = T.Trace.from_json(tr.to_json())
    assert [e.scope for e in back.ops[0]] == [e.scope for e in tr.ops[0]]
    assert back.scope_seconds("ffn") == tr.scope_seconds("ffn")


def test_recorded_trace_loads_without_scopes():
    """The recorded trace has three fields an event: every scope empty."""
    tr = T.Trace.load_json(RECORDED)
    events = [e for evs in list(tr.ops.values()) + list(tr.modules.values())
              for e in evs] + tr.spans
    assert events and all(e.scope == "" for e in events)
    assert tr.scope_seconds("mixer") == 0.0
