"""The program's own host spans in a profile, and the interval logic that
the span readers share.

The serving engine wraps each phase of its step in a ``TraceAnnotation``
(``repro.engine.tracing``: ``engine.step`` at the root, then
``engine.admit``, ``engine.prefill``, ``engine.device_wait`` ...).  They
run on the harness's thread, so :func:`bench.trace.read_xplane` keeps them
in ``Trace.host`` beside JAX's own host events, on the device's clock.  An
event's name is matched up to any ``#`` (TraceMe metadata).  A program
that records no such span reads as nothing: every reader returns ``None``.

Spans on one thread nest, so at any moment the innermost program span is
the one opened last among those still open.  :func:`idle_by_span` cuts
the device-idle time inside the harness's ``engine_step`` spans at every
program span's edges and charges each piece to the innermost span open
over it.  It walks every span, with no lookback limit: one step holds
hundreds of JAX host events, so the span around a gap can open long
before it.

    python bench/spans.py <profile.xplane.pb[.gz]>

prints that attribution, the per-step numbers the readers report, and
the same split between steps that carry a prefill and steps that do not.
"""
from __future__ import annotations

import bisect
import json
import math
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench.trace import Cover, Ev  # noqa: E402

STEP = "engine.step"             # root span of one engine iteration
WAIT = "engine.device_wait"      # the host waits for the chip
PREFILL = "engine.prefill"       # one request's prefill, to its 1st token
PREFIXES = ("engine.", "prefill.", "transport.", "decode.", "spec.")
UNNAMED = "(no program span)"


def base(name: str) -> str:
    """An event's name without TraceMe metadata (``name#k=v,...#``)."""
    return name.split("#", 1)[0]


def program_spans(host: List[Ev]) -> List[Ev]:
    """The program's spans among ``host``, by start (outer span first)."""
    out = [Ev(base(e.name), e.start, e.end) for e in host
           if base(e.name).startswith(PREFIXES)]
    return sorted(out, key=lambda e: (e.start, -e.end))


def named(host: List[Ev], name: str) -> List[Ev]:
    return [e for e in program_spans(host) if e.name == name]


def segments(spans: List[Ev]) -> List[Tuple[float, float, Ev]]:
    """The time that nested ``spans`` cover, cut into pieces ``(a, b,
    span)``, each with the innermost span open over it."""
    out: List[Tuple[float, float, Ev]] = []
    stack: List[Ev] = []
    t = -math.inf

    def close(limit):
        nonlocal t
        while stack and stack[-1].end <= limit:
            top = stack.pop()
            if top.end > t:
                out.append((t, top.end, top))
            t = max(t, top.end)

    for s in sorted(spans, key=lambda e: (e.start, -e.end)):
        close(s.start)
        if stack:
            if s.start > t:
                out.append((t, s.start, stack[-1]))
            # a child ends inside its parent (clock rounding aside)
            s = Ev(s.name, s.start, min(s.end, stack[-1].end))
        stack.append(s)
        t = s.start
    close(math.inf)
    return out


def innermost_at(spans: List[Ev], t: float) -> Optional[Ev]:
    """The innermost of ``spans`` open at time ``t``."""
    for a, b, e in segments(spans):
        if a <= t < b:
            return e
    return None


def union_within(parent: Ev, children: List[Ev]) -> float:
    """Length of ``parent`` that the union of ``children`` covers."""
    return Cover([(max(c.start, parent.start), min(c.end, parent.end))
                  for c in children
                  if c.end > parent.start and c.start < parent.end]
                 ).length(parent.start, parent.end)


def host_ms_per_step(trace) -> Optional[float]:
    """Mean over the ``engine.step`` spans of the span's time outside its
    ``engine.device_wait`` children, in ms: host work the synchronous loop
    puts on each step's critical path."""
    steps = named(trace.host, STEP)
    if not steps:
        return None
    waits = named(trace.host, WAIT)
    return sum(s.dur - union_within(s, waits) for s in steps) / \
        len(steps) * 1e-6


def prefill_ms(trace) -> Optional[float]:
    """Mean duration of an ``engine.prefill`` span, in ms (dispatch, pool
    write, page handoff and the first token's transfer)."""
    spans = named(trace.host, PREFILL)
    return sum(s.dur for s in spans) / len(spans) * 1e-6 if spans else None


def idle_by_span(trace) -> Dict[str, float]:
    """Device-idle nanoseconds inside the harness's ``engine_step`` spans,
    by the innermost program span open over them (:data:`UNNAMED` where
    none is)."""
    steps = sorted(trace.steps, key=lambda s: s.start)
    starts = [s.start for s in steps]
    idle = lambda a, b: (b - a) - trace.busy.length(a, b)  # noqa: E731
    out: Dict[str, float] = defaultdict(float)
    named_ns = 0.0
    for a, b, e in segments(program_spans(trace.host)):
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        for s in steps[i:]:
            if s.start >= b:
                break
            lo, hi = max(a, s.start), min(b, s.end)
            if hi > lo:
                ns = idle(lo, hi)
                out[e.name] += ns
                named_ns += ns
    total = sum(idle(s.start, s.end) for s in steps)
    out[UNNAMED] = max(total - named_ns, 0.0)
    return dict(out)


def wait_idle_share(trace) -> Optional[float]:
    """Share of the ``engine.device_wait`` time in which no operation ran
    on the device.  The host waits there for device work; a high share
    means the busy cover misses some of it."""
    waits = named(trace.host, WAIT)
    total = sum(w.dur for w in waits)
    if total <= 0:
        return None
    return 1.0 - sum(trace.busy.length(w.start, w.end)
                     for w in waits) / total


def by_step_kind(trace) -> Dict[str, dict]:
    """Steps with an ``engine.prefill`` span (``prefill``) and without
    (``plain``): how many, their mean duration, and per step the mean
    inclusive ms of each program span and the mean device-idle ms under
    each innermost span, all on the ``engine.step`` spans."""
    ps = program_spans(trace.host)
    out: Dict[str, dict] = {}
    for kind in ("prefill", "plain"):
        steps, inside = [], []
        for s in (e for e in ps if e.name == STEP):
            kids = [c for c in ps if c is not s and s.start <= c.start
                    and c.end <= s.end]
            if any(c.name == PREFILL for c in kids) == (kind == "prefill"):
                steps.append(s)
                inside += kids
        if not steps:
            continue
        span_ns: Dict[str, float] = defaultdict(float)
        for c in inside:
            span_ns[c.name] += c.dur
        idle_ns: Dict[str, float] = defaultdict(float)
        for a, b, e in segments(steps + inside):
            idle_ns[e.name] += (b - a) - trace.busy.length(a, b)
        n = len(steps)
        per = lambda d: {k: v / n * 1e-6 for k, v in  # noqa: E731
                         sorted(d.items(), key=lambda kv: -kv[1])}
        out[kind] = {"steps": n, "step_ms": sum(s.dur for s in steps) / n
                     * 1e-6, "span_ms": per(span_ns), "idle_ms": per(idle_ns)}
    return out


def report(trace) -> dict:
    """What ``python bench/spans.py`` prints for one profile."""
    by = idle_by_span(trace)
    total = sum(by.values())
    below = total - by[UNNAMED] - by.get(STEP, 0.0)
    return {"steps": len(trace.steps),
            "step_idle_s": total * 1e-9,
            "idle_s_by_span": {k: v * 1e-9 for k, v in sorted(
                by.items(), key=lambda kv: -kv[1])},
            "idle_share_below_step": below / total if total > 0 else None,
            "host_ms_per_step": host_ms_per_step(trace),
            "prefill_ms": prefill_ms(trace),
            "prefills": len(named(trace.host, PREFILL)),
            "wait_idle_share": wait_idle_share(trace),
            "by_step_kind": by_step_kind(trace)}


if __name__ == "__main__":
    from bench.trace import read_xplane
    print(json.dumps(report(read_xplane(sys.argv[1])), indent=1))
