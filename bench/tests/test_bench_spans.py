"""The span readers (``bench/spans.py``, ``sched.host_ms.*``,
``prefill.host_ms.chat``): on hand-built traces, on a small trace
recorded on a TPU v5e with the engine's spans, and on the older recorded
trace of an engine without them."""
from pathlib import Path

import pytest

from bench import harness, spans, trace
from bench.trace import Ev, Trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6   # ns


def ev(name, a, b):
    return Ev(name, a * MS, b * MS)


def two_steps(prefill=True):
    """Two harness steps: the first pushes tables and waits twice, the
    second prefills one request (whose first token waits) and waits."""
    host = [ev("engine.step#_r=1,step_num=1#", 1, 99),
            ev("engine.push_tables", 5, 30),
            ev("PjitFunction(convert_element_type)", 10, 20),
            ev("engine.device_wait", 50, 80),
            ev("engine.device_wait", 85, 90),
            ev("engine.step#_r=1,step_num=2#", 101, 199),
            ev("engine.device_wait", 170, 190)]
    if prefill:
        host += [ev("engine.prefill#rid=3,tokens=128#", 105, 160),
                 ev("prefill.dispatch", 106, 120),
                 ev("engine.device_wait", 140, 150)]
    else:
        host += [ev("engine.grow", 105, 160),
                 ev("decode.dispatch", 106, 120)]
    return Trace(modules=[ev("jit__step(1)", 20, 60)],
                 ops=[ev("%a = f32[1] add()", 20, 60),
                      ev("%b = f32[1] add()", 150, 195)],
                 spans=[ev("engine_step", 0, 100),
                        ev("engine_step", 100, 200)],
                 host=host)


def run_of(tr):
    return harness.RunData(
        cell="c", slots=4, loop="closed", setup_s=1.0, t_open=0.0,
        t_close=1.0, steps=[], output_tokens=0, itl_s=[], ttft_s=None,
        queue_wait_s=[], lateness_s=[], arith=None, peaks={}, trace=tr)


def test_host_ms_subtracts_the_wait_union():
    # step 1: 98 ms less 30 + 5 of waits; step 2: 98 less 10 + 20
    tr = two_steps()
    for name in ("sched.host_ms.chat", "sched.host_ms.batch"):
        assert harness.reader(name)(run_of(tr)) == pytest.approx(65.5)


def test_prefill_ms_is_per_prefill_and_none_without_one():
    assert harness.reader("prefill.host_ms.chat")(run_of(two_steps())) == \
        pytest.approx(55.0)
    assert harness.reader("prefill.host_ms.chat")(
        run_of(two_steps(prefill=False))) is None


def test_readers_are_silent_without_spans_or_trace():
    tr = two_steps()
    tr.host = [h for h in tr.host if not h.name.startswith("engine.")]
    for name in ("sched.host_ms.chat", "sched.host_ms.batch",
                 "prefill.host_ms.chat"):
        assert harness.reader(name)(run_of(tr)) is None
        assert harness.reader(name)(run_of(None)) is None


def test_idle_by_innermost_span():
    tr = two_steps()
    by = {k: v / MS for k, v in spans.idle_by_span(tr).items()}
    assert by == pytest.approx({
        "engine.step": 26.0, "engine.push_tables": 15.0,
        "engine.device_wait": 35.0, "engine.prefill": 21.0,
        "prefill.dispatch": 14.0, spans.UNNAMED: 4.0})
    inside = sum(s.dur for s in tr.steps)
    assert sum(by.values()) * MS == pytest.approx(
        tr.step_idle_share() * inside)
    assert spans.wait_idle_share(tr) == pytest.approx(35 / 65)
    rep = spans.report(tr)
    assert rep["idle_share_below_step"] == pytest.approx(85 / 115)
    assert rep["prefills"] == 1


def test_split_by_step_kind():
    split = spans.by_step_kind(two_steps())
    assert split["prefill"]["steps"] == split["plain"]["steps"] == 1
    assert split["prefill"]["step_ms"] == pytest.approx(98.0)
    assert split["prefill"]["span_ms"] == pytest.approx({
        "engine.prefill": 55.0, "prefill.dispatch": 14.0,
        "engine.device_wait": 30.0})
    assert split["plain"]["idle_ms"] == pytest.approx({
        "engine.step": 18.0, "engine.push_tables": 15.0,
        "engine.device_wait": 25.0})


def test_innermost_and_union():
    ps = spans.program_spans(two_steps().host)
    assert spans.innermost_at(ps, 110 * MS).name == "prefill.dispatch"
    assert spans.innermost_at(ps, 125 * MS).name == "engine.prefill"
    assert spans.innermost_at(ps, 0.5 * MS) is None
    parent = ev("p", 0, 10)
    kids = [ev("a", 1, 3), ev("b", 2, 5), ev("c", 8, 12)]
    assert spans.union_within(parent, kids) == pytest.approx(6 * MS)


def test_segments_clip_a_child_to_its_parent():
    segs = spans.segments([ev("p", 0, 10), ev("c", 4, 11)])
    assert [(a / MS, b / MS, e.name) for a, b, e in segs] == \
        [(0, 4, "p"), (4, 10, "c")]


@pytest.fixture(scope="module")
def chip():
    """Recorded on a TPU v5e: the reduced granite (2 layers) through the
    engine, 4 requests of 128 prompt tokens and 6 output tokens over 4
    slots (capacity 256, whole-prompt prefill), inside the harness's
    spans."""
    return trace.read_xplane(str(DATA / "engine_spans.xplane.pb.gz"))


def test_chip_trace_span_tree(chip):
    ps = spans.program_spans(chip.host)
    steps = [s for s in ps if s.name == spans.STEP]
    assert len(steps) == len(chip.steps) == 8
    for s in ps:
        assert any(h.start <= s.start and s.end <= h.end
                   for h in chip.steps), s.name
    # one wait per decode step and one per finished prefill
    assert len(spans.named(chip.host, spans.WAIT)) == \
        len(chip.programs("jit__step")) + len(chip.programs("jit__lambda"))
    assert len(spans.named(chip.host, spans.PREFILL)) == 4


def test_readers_on_the_chip_trace(chip):
    run = run_of(chip)
    steps = spans.named(chip.host, spans.STEP)
    host = harness.reader("sched.host_ms.batch")(run)
    assert 0 < host < sum(s.dur for s in steps) / len(steps) * 1e-6
    assert harness.reader("sched.host_ms.chat")(run) == host
    # a prefill span waits for its program's first token
    assert harness.reader("prefill.host_ms.chat")(run) > \
        harness.reader("prefill.device_ms.chat")(run) > 0
    assert harness.reader("decode.device_ms.batch")(run) > 0


def test_chip_trace_idle_lies_inside_program_spans(chip):
    rep = spans.report(chip)
    inside = sum(s.dur for s in chip.steps)
    assert rep["step_idle_s"] == pytest.approx(
        chip.step_idle_share() * inside * 1e-9)
    assert rep["idle_share_below_step"] >= 0.9
    assert 0.0 <= rep["wait_idle_share"] <= 1.0


def test_older_trace_has_no_program_spans():
    """A trace of an engine without spans (the older recording) reads as
    nothing, so the readers stay silent on such a program."""
    tr = trace.read_xplane(str(DATA / "engine_trace.xplane.pb.gz"))
    assert spans.program_spans(tr.host) == []
    for name in ("sched.host_ms.batch", "prefill.host_ms.chat"):
        assert harness.reader(name)(run_of(tr)) is None
