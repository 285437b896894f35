"""The host-clock and counter readers on a synthetic run."""
import math

import pytest

from bench import harness
from bench.arith import Workload


def run(**kw):
    base = dict(cell="c", slots=4, loop="open", setup_s=12.5, t_open=100.0,
                t_close=110.0, steps=[], output_tokens=500,
                itl_s=[0.01] * 95 + [0.05] * 5, ttft_s=None, queue_wait_s=[],
                lateness_s=[], arith=None, peaks={})
    base.update(kw)
    return harness.RunData(**base)


def test_nearest_rank():
    assert harness.nearest_rank(range(1, 101), 95) == 95
    assert harness.nearest_rank([3.0], 95) == 3.0
    assert harness.nearest_rank([1.0] * 19 + [math.inf], 95) == 1.0
    assert harness.nearest_rank([1.0] * 18 + [math.inf] * 2, 95) == math.inf


def test_end_to_end_readers():
    r = run()
    assert harness.reader("output_tok_s")(r) == pytest.approx(50.0)
    assert harness.reader("itl_p95_ms")(r) == pytest.approx(10.0)
    assert harness.reader("setup_s")(r) == 12.5


def test_counter_readers():
    steps = [harness.Step(0, 1, 3, d, 0, 0) for d in (4, 2)]
    r = run(steps=steps)
    assert harness.reader("sched.decode_occupancy.batch")(r) == 75.0
    # device readers stay silent without a trace
    for name in ("decode.device_ms.batch", "step.mfu.batch",
                 "kernel.attn_decode_roofline.batch",
                 "device.step_idle_share.chat", "prefill.device_ms.chat"):
        assert harness.reader(name)(r) is None


def test_mfu_counts_prefill_and_decode():
    conf = {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
            "intermediate_size": 32, "num_hidden_layers": 2,
            "vocab_size": 256, "precision": {"kv_cache": "float8_e5m2",
                                             "activations": "bfloat16"}}
    a = Workload.from_conf(conf)

    class FakeTrace:
        pass
    r = run(arith=a, peaks={"bf16_flops": 1e9}, trace=FakeTrace(),
            trace_s=2.0, trace_tokens=[(10, 0), (10, 1), (10, 2)])
    want = a.prefill_flops(10) + a.decode_flops(11) + a.decode_flops(12)
    assert harness.reader("step.mfu.batch")(r) == \
        pytest.approx(100 * want / 2e9)
