"""The trace reduction on a small trace recorded on a TPU v5e: the
reduced granite (2 layers) served through the engine, 4 requests of 128
prompt tokens and 6 output tokens over 4 slots, inside the harness's
spans."""
import re
from pathlib import Path

import pytest

from bench import harness, trace

DATA = Path(__file__).resolve().parent / "data" / "engine_trace.xplane.pb.gz"


@pytest.fixture(scope="module")
def tr():
    return trace.read_xplane(str(DATA))


def test_programs_and_spans(tr):
    # 8 engine steps: 4 carry a whole-prompt prefill, 8 a decode step
    assert len(tr.steps) == 8
    assert len(tr.programs("jit__step")) == 8
    assert len(tr.programs("jit__lambda")) == 4
    assert all(p.dur > 0 for p in tr.programs("jit__step"))


def test_busy_and_idle(tr):
    assert 0 < tr.busy_s < tr.window_s
    assert tr.window_s == pytest.approx(
        (max(s.end for s in tr.steps) - min(s.start for s in tr.steps)) * 1e-9)
    share = tr.step_idle_share()
    assert 0.0 < share < 1.0
    inside = sum(s.dur for s in tr.steps) * 1e-9
    assert share == pytest.approx(1 - sum(
        tr.busy.length(s.start, s.end) for s in tr.steps) * 1e-9 / inside)


def test_decode_attention_kernels_are_found(tr):
    # one flash_decode call per layer per decode step (2 layers x 8 steps),
    # each reading the gathered [slots, capacity, kv_heads, head_dim] K/V
    kv = re.compile(r"\[\d+,\d+,2,16\]")
    ops = [o for o in tr.ops_within(tr.programs("jit__step"))
           if 'custom_call_target="tpu_custom_call"' in o.name
           and kv.search(o.name.split("custom-call(", 1)[-1])]
    assert len(ops) == 16


def test_breakdown_is_bounded_and_named(tr):
    b = tr.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 0 < len(b[key]) <= trace.TOP
        assert all(isinstance(n, str) and v >= 0 for n, v in b[key])
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert b["device_ops"][0][0].startswith("jit__step custom-call")
    assert sum(v for _, v in b["device_ops"]) <= tr.busy_s * 1.0001 + 1e-9


def test_device_readers_on_the_recorded_trace(tr):
    from bench.arith import Workload
    from bench.tests.tiny import tiny_conf
    run = harness.RunData(
        cell="tiny", slots=4, loop="closed", setup_s=1.0, t_open=0.0,
        t_close=1.0, steps=[], output_tokens=24, itl_s=[], ttft_s=None,
        queue_wait_s=[], lateness_s=[],
        arith=Workload.from_conf(tiny_conf()),
        peaks=harness.peaks_for("TPU v5 lite"), trace=tr,
        trace_s=tr.window_s,
        trace_tokens=[(128, j) for _ in range(4) for j in range(6)])
    ms = harness.reader("decode.device_ms.batch")(run)
    assert ms == pytest.approx(sum(p.dur for p in tr.programs("jit__step"))
                               / 8 * 1e-6)
    assert 0 < harness.reader("prefill.device_ms.chat")(run)
    assert 0 < harness.reader("kernel.attn_decode_roofline.batch")(run) < 100
    assert 0 < harness.reader("step.mfu.batch")(run) < 100
    assert harness.reader("device.step_idle_share.batch")(run) == \
        pytest.approx(100 * tr.step_idle_share())


@pytest.mark.parametrize("text,kind", [
    ("%_step.2 = f32[4,2,8,16]{3,2,1,0:T(8,128)} custom-call(f32[4] %a), "
     'custom_call_target="tpu_custom_call"', "custom-call f32[4,2,8,16]"),
    ("%fusion.3 = bf16[8,64]{1,0} fusion(bf16[8] %x), kind=kLoop, "
     "calls=%f", "fusion(kLoop) bf16[8,64]"),
    ("%copy.1 = s32[1,128]{1,0} copy(s32[1,128] %p)", "copy s32[1,128]"),
])
def test_op_kind(text, kind):
    assert trace.op_kind(text) == kind


def test_cover():
    c = trace.Cover([(5, 7), (0, 2), (1, 3), (10, 12)])
    assert c.iv == [(0, 3), (5, 7), (10, 12)]
    assert c.length(0, 12) == 7
    assert c.length(2, 6) == 2
    assert c.length(7, 10) == 0
