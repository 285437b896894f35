"""Reduction of a profiler trace to what the per-layer metrics read.

A ``--trace 1`` run profiles a stretch of serving with the Python tracer
off.  The TPU's plane (``/device:TPU:<n>``) holds one event per program
execution on its ``XLA Modules`` line (named ``jit_<function>(<hash>)``:
the decode step is ``jit__step``, the whole-prompt prefill ``jit__lambda``)
and one per operation on its ``XLA Ops`` line (named by the HLO
instruction's text).  The host plane holds the harness's own spans
(``engine_step``, ``bench_enqueue``) on the same clock.

The traced window runs from the first ``engine_step`` span's start to the
last one's end.  Device busy time is the union of the operations'
intervals inside it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

STEP_SPAN = "engine_step"
SPANS = (STEP_SPAN, "bench_enqueue")
TOP = 10
MIN_GAP_NS = 2000.0   # shorter idle gaps are not named
LOOKBACK = 256       # host events searched backwards for a gap's name


def profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: float   # ns
    end: float     # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Cover:
    """Sorted disjoint intervals with fast queries over a span."""

    def __init__(self, intervals: List[Tuple[float, float]]):
        self.iv = union(intervals)
        self.ends = [b for _, b in self.iv]

    def within(self, lo: float, hi: float):
        i = bisect.bisect_right(self.ends, lo)
        while i < len(self.iv) and self.iv[i][0] < hi:
            yield self.iv[i]
            i += 1

    def length(self, lo: float, hi: float) -> float:
        """Length of ``[lo, hi]`` the intervals cover."""
        return sum(min(b, hi) - max(a, lo) for a, b in self.within(lo, hi))


def module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


_HLO = re.compile(r"^%(?P<name>\S+) = (?P<type>\S+) (?P<op>[\w-]+)\(")


def op_kind(text: str) -> str:
    """``opcode output-type`` of an HLO instruction's text (fusions add
    their kind), e.g. ``custom-call f32[64,8,8,64]``."""
    m = _HLO.match(text)
    if not m:
        return text[:60]
    kind = re.search(r"kind=(k\w+)", text)
    op = m.group("op") + (f"({kind.group(1)})" if kind else "")
    return f"{op} {m.group('type').split('{', 1)[0]}"


class Trace:
    def __init__(self, modules: List[Ev], ops: List[Ev], spans: List[Ev],
                 host: List[Ev]):
        steps = [s for s in spans if s.name == STEP_SPAN]
        if not steps:
            raise ValueError("the trace holds no engine_step span")
        self.lo = min(s.start for s in steps)
        self.hi = max(s.end for s in steps)
        inside = lambda e: e.end > self.lo and e.start < self.hi  # noqa
        self.modules = [m for m in modules if inside(m)]
        self.ops = [o for o in ops if inside(o)]
        self.steps = steps
        self.host = [h for h in host if inside(h)]
        self.busy = Cover([(o.start, o.end) for o in self.ops])

    # -- device time --------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy.length(self.lo, self.hi) * 1e-9

    def programs(self, name: str) -> List[Ev]:
        """Executions of the program ``name`` (e.g. ``jit__step``)."""
        return [m for m in self.modules if module_name(m.name) == name]

    def ops_within(self, progs: List[Ev]) -> List[Ev]:
        """Operations that ran inside the given program executions."""
        spans = sorted((p.start, p.end) for p in progs)
        out, i = [], 0
        for o in sorted(self.ops, key=lambda e: e.start):
            while i < len(spans) and spans[i][1] < o.start:
                i += 1
            if i < len(spans) and spans[i][0] <= o.start <= spans[i][1]:
                out.append(o)
        return out

    def step_idle_share(self) -> Optional[float]:
        """Share of the time inside the harness's ``engine_step`` spans in
        which no operation ran on the device."""
        total = sum(s.dur for s in self.steps)
        if total <= 0:
            return None
        busy = sum(self.busy.length(s.start, s.end) for s in self.steps)
        return 1.0 - busy / total

    # -- what the next writer reads -----------------------------------------
    def idle_gaps(self) -> List[Tuple[str, float, float]]:
        """Device-idle intervals inside ``engine_step`` spans, each named by
        the shortest host event that covers its middle."""
        gaps = []
        for s in self.steps:
            t = s.start
            for a, b in self.busy.within(s.start, s.end):
                if a > t:
                    gaps.append((t, a))
                t = max(t, b)
            if t < s.end:
                gaps.append((t, s.end))
        host = sorted(self.host, key=lambda e: e.start)
        starts = [h.start for h in host]
        out = []
        for a, b in gaps:
            if b - a < MIN_GAP_NS:
                out.append(("(between operations)", a, b))
                continue
            mid = 0.5 * (a + b)
            i = bisect.bisect_right(starts, mid)
            cover = [h for h in host[max(0, i - LOOKBACK):i] if h.end >= mid]
            name = min(cover, key=lambda h: h.dur).name if cover else \
                "(no host event)"
            out.append((name, a, b))
        return out

    def breakdown(self) -> Dict[str, list]:
        by_op: Dict[str, float] = defaultdict(float)
        progs = sorted(self.modules, key=lambda m: m.start)
        starts = [p.start for p in progs]
        for o in self.ops:
            i = bisect.bisect_right(starts, o.start) - 1
            prog = module_name(progs[i].name) if i >= 0 and \
                progs[i].end >= o.start else "?"
            by_op[f"{prog} {op_kind(o.name)}"] += o.dur * 1e-9
        by_gap: Dict[str, float] = defaultdict(float)
        for name, a, b in self.idle_gaps():
            by_gap[name] += (b - a) * 1e-9
        top = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa
                               key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": top(by_op), "idle_gaps": top(by_gap)}


def read_xplane(path: str) -> Trace:
    """A trace from an ``.xplane.pb`` file (or its gzip)."""
    import jax
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    modules: List[Ev] = []
    ops: List[Ev] = []
    spans: List[Ev] = []
    host: List[Ev] = []
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise ValueError(f"{path}: no TPU plane")
    # one chip: the busiest TPU plane is the one the cell ran on
    busiest = max(devices, key=lambda p: sum(
        len(list(line.events)) for line in p.lines if line.name == "XLA Ops"))
    for plane in (busiest,):
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules += [Ev(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            elif line.name == "XLA Ops":
                ops += [Ev(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
    # the harness's spans, and the host events of the thread that ran them
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [Ev(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if any(e.name in SPANS for e in evs):
                spans += [e for e in evs if e.name in SPANS]
                host += [e for e in evs if e.name not in SPANS]
    return Trace(modules, ops, spans, host)


def load(trace_dir: str) -> Trace:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return read_xplane(files[-1])
