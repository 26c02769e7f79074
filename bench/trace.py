"""From a profiler trace to the numbers the per-layer metrics read.

A run with ``--trace 1`` records its window with ``jax.profiler`` and reads
the ``.xplane.pb`` file back with ``jax.profiler.ProfileData``. Only three
kinds of event are kept, with times in seconds on the trace's one clock:

* device operations, per chip (the ``XLA Ops`` line of each TPU plane;
  the ``Async XLA Ops`` line holds copies in flight, which overlap the
  operations and are not read);
* device programs, per chip (the ``XLA Modules`` line): one event per run of
  a jitted program, named after it (``jit_<function>(<id>)``);
* host spans that the benchmark itself opened with
  ``jax.profiler.TraceAnnotation`` (names starting ``bench.``).

Busy time is the union of the operation intervals, so overlapping events
count once; the idle share is 1 − busy ÷ window.

Each device operation also keeps its scope path: the names of the
``jax.named_scope`` blocks open where the program traced it, from the HLO
``op_name`` metadata with the segments JAX adds itself left out
(``jit(fwd)/while/body/closed_call/mixer/seqmix/dot_general`` →
``mixer/seqmix``; see ``scope_path``). A v5e trace's operation events carry
no op name (their stats are offsets and durations), so it comes from the
compiled HLO text of the program that ran the operation, handed to
``from_xplane`` by program name. A fusion takes the scope of its root
instruction.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def start(trace_dir: str) -> None:
    """Start the profiler with the host's Python call tracer off: it would
    record every Python call of the loop and slow the host it measures."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float
    scope: str = ""     # a device operation's scope path; "" where none

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]        # chip index → device operations
    modules: Dict[int, List[Event]]    # chip index → device program runs
    spans: List[Event]                 # the benchmark's host spans

    # -- recording --------------------------------------------------------

    @classmethod
    def from_xplane(cls, path: str,
                    hlo: Optional[Dict[str, str]] = None) -> "Trace":
        """The trace in the file at ``path``; ``hlo`` maps a program's name
        (``jit_fwd``) to its compiled HLO text, which names the scopes of
        its operations (the operations of other programs have none)."""
        from jax.profiler import ProfileData

        return cls.from_profile(ProfileData.from_file(path), hlo)

    @classmethod
    def from_profile(cls, data,
                     hlo: Optional[Dict[str, str]] = None) -> "Trace":
        tables = {OPS_LINE: {}, MODULES_LINE: {}}
        spans: List[Event] = []
        for plane in data.planes:
            chip = _chip_index(plane.name)
            for line in plane.lines:
                if chip is not None and line.name in tables:
                    tables[line.name].setdefault(chip, []).extend(
                        _event(e) for e in line.events)
                elif chip is None and plane.name.startswith("/host:"):
                    spans.extend(_event(e) for e in line.events
                                 if e.name.startswith(SPAN_PREFIX))
        ops, modules = tables[OPS_LINE], tables[MODULES_LINE]
        if hlo:
            scopes = {prog: hlo_scopes(text) for prog, text in hlo.items()}
            ops = {c: _scoped_by_program(evs, modules.get(c, []), scopes)
                   for c, evs in ops.items()}
        return cls(ops, modules, spans)

    @classmethod
    def from_dir(cls, trace_dir: str,
                 hlo: Optional[Dict[str, str]] = None) -> "Trace":
        files = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        return cls.from_xplane(files[-1], hlo)

    def to_json(self) -> dict:
        """The trace as JSON, operations by their short names, each event
        ``[name, start, end, scope]`` (the format of the recorded trace the
        tests read; one recorded with three fields loads with no scopes)."""
        enc = lambda evs: [[short(e.name), e.start, e.end, e.scope]
                           for e in evs]
        table = lambda t: {str(k): enc(v) for k, v in t.items()}
        return {"ops": table(self.ops), "modules": table(self.modules),
                "spans": enc(self.spans)}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        dec = lambda evs: [Event(n, float(s), float(e), *scope)
                           for n, s, e, *scope in evs]
        table = lambda t: {int(k): dec(v) for k, v in t.items()}
        return cls(table(obj["ops"]), table(obj["modules"]), dec(obj["spans"]))

    @classmethod
    def load_json(cls, path: str) -> "Trace":
        with open(path) as f:
            return cls.from_json(json.load(f))

    # -- reduction --------------------------------------------------------

    def window(self) -> Interval:
        """The measured window: the benchmark's ``bench.window`` span."""
        w = [e for e in self.spans if e.name == WINDOW_SPAN]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
        return w[0].start, w[0].end

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        lo, hi = self.window()
        per = [union_length([(e.start, e.end) for e in self.ops[c]], lo, hi)
               for c in self.chips]
        return sum(per) / len(per) if per else 0.0

    def window_s(self) -> float:
        lo, hi = self.window()
        return hi - lo

    def op_seconds(self, match: Callable[[str], bool]) -> float:
        """Device seconds of the operations whose name matches, inside the
        window, averaged over the chips."""
        return self._seconds(self.ops, match)

    def module_seconds(self, match: Callable[[str], bool]) -> float:
        """Device seconds of the programs whose name matches, inside the
        window, averaged over the chips."""
        return self._seconds(self.modules, match)

    def _seconds(self, table, match) -> float:
        lo, hi = self.window()
        per = [sum(_clip(e, lo, hi) for e in table[c] if match(e.name))
               for c in self.chips]
        return sum(per) / len(per) if per else 0.0

    def scope_seconds(self, scope: str) -> float:
        """Device seconds of the leaf operations (``leaves``, as ``top_ops``
        counts them) whose scope path starts with ``scope`` as whole path
        segments (``ffn`` holds ``ffn/mlp``, not ``ffn2``), inside the
        window, averaged over the chips."""
        lo, hi = self.window()
        per = [sum(_clip(e, lo, hi) for e in leaves(self.ops[c])
                   if in_scope(e.scope, scope))
               for c in self.chips]
        return sum(per) / len(per) if per else 0.0

    def spans_named(self, name: str) -> List[Event]:
        return [e for e in self.spans if e.name == name]

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` operations that took most device time in the window,
        each named ``<program>/<operation>``, seconds averaged over chips.
        An operation that holds others (a loop) is left out: its time is
        theirs."""
        lo, hi = self.window()
        total: Dict[str, float] = {}
        for c in self.chips:
            programs = sorted(self.modules.get(c, []), key=lambda m: m.start)
            starts = [m.start for m in programs]
            for e in leaves(self.ops[c]):
                sec = _clip(e, lo, hi)
                if sec <= 0:
                    continue
                key = f"{_program_of(e, programs, starts)}/{short(e.name)}"
                total[key] = total.get(key, 0.0) + sec
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / len(self.chips)] for k, v in ranked if v > 0]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle device time in the window, by what the host was doing: each
        gap of chip 0 goes to the innermost benchmark span around its middle
        (``host`` where none is open); the ``n`` largest totals."""
        lo, hi = self.window()
        if not self.chips:
            return []
        chip = self.chips[0]
        busy = merged([(e.start, e.end) for e in self.ops[chip]], lo, hi)
        inner = [e for e in self.spans if e.name != WINDOW_SPAN]
        total: Dict[str, float] = {}
        t = lo
        for s, e in busy + [(hi, hi)]:
            if s > t:
                mid = (t + s) / 2
                around = [sp for sp in inner if sp.start <= mid < sp.end]
                name = (min(around, key=lambda sp: sp.seconds).name
                        if around else "host")
                total[name] = total.get(name, 0.0) + (s - t)
            t = max(t, e)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in ranked]


def leaves(events: List[Event]) -> List[Event]:
    """The events that hold no other event of the list."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    out = []
    for i, e in enumerate(order):
        nxt = order[i + 1] if i + 1 < len(order) else None
        if nxt is None or nxt.start >= e.end:
            out.append(e)
    return out


def short(name: str) -> str:
    """An operation's instruction name, without the HLO text a device trace
    gives it (``%fusion.3 = f32[...] fusion(...)`` → ``%fusion.3``)."""
    return name.split(" = ", 1)[0]


# Segments that JAX itself puts into an op name: transformations with their
# argument (``jit(fwd)``) and the loops and calls of ``lax.scan``
# (``while/body``, ``while/cond``, ``closed_call``). What is left of the
# path are the program's own scopes.
_JAX_SEGMENT = re.compile(r"^(?:[A-Za-z_]+\(.*\)|while|body|cond|closed_call)$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?(%[^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=(%[^\s,}]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(.*\{\s*$")


def scope_path(op_name: str) -> str:
    """The scopes of an HLO ``op_name``: its last segment (the primitive)
    and the segments JAX adds itself left out."""
    segments = op_name.split("/")[:-1]
    return "/".join(s for s in segments if not _JAX_SEGMENT.match(s))


def in_scope(path: str, scope: str) -> bool:
    """Whether the scope path ``path`` lies under ``scope``, as whole path
    segments."""
    return path == scope or path.startswith(scope + "/")


def hlo_scopes(text: str) -> Dict[str, str]:
    """Instruction name (``%fusion.3``) → scope path, from a compiled
    program's HLO text. A fusion takes the scope of its fused computation's
    root instruction, or its own where that root has no op name."""
    own: Dict[str, str] = {}        # instruction → its op_name, if any
    calls: Dict[str, str] = {}      # fusion → the computation it fuses
    roots: Dict[str, str] = {}      # computation → its root instruction
    computation = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        root, name, rest = m.groups()
        if root and computation:
            roots[computation] = name
        op = _OP_NAME.search(rest)
        if op:
            own[name] = op.group(1)
        called = _CALLS.search(rest)
        if called and " fusion(" in rest:
            calls[name] = called.group(1)

    def op_name(name: str) -> str:
        root = roots.get(calls.get(name))
        return (root and op_name(root)) or own.get(name, "")

    names = set(own) | set(calls)
    return {n: scope_path(op_name(n)) for n in names if op_name(n)}


def merged(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The union of the intervals, clipped to [lo, hi], as disjoint sorted
    intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def union_length(intervals: List[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(intervals, lo, hi))


def _clip(e: Event, lo: float, hi: float) -> float:
    return max(0.0, min(e.end, hi) - max(e.start, lo))


def _program_of(op: Event, programs: List[Event], starts: List[float]) -> str:
    """The program run (sorted by start) that holds the operation."""
    mid = (op.start + op.end) / 2
    i = bisect.bisect_right(starts, mid) - 1
    if i >= 0 and programs[i].end >= mid:
        return programs[i].name.split("(")[0]
    return "?"


def _chip_index(plane_name: str) -> Optional[int]:
    m = re.match(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def _event(e) -> Event:
    start = e.start_ns * 1e-9
    return Event(e.name, start, start + e.duration_ns * 1e-9)


def _scoped_by_program(ops: List[Event], programs: List[Event],
                       scopes: Dict[str, Dict[str, str]]) -> List[Event]:
    """The operations, each with the scope that the HLO of the program run
    holding it names."""
    programs = sorted(programs, key=lambda m: m.start)
    starts = [m.start for m in programs]
    out = []
    for e in ops:
        table = scopes.get(_program_of(e, programs, starts))
        out.append(Event(e.name, e.start, e.end, table.get(short(e.name), ""))
                   if table else e)
    return out
