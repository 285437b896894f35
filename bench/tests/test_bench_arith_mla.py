"""Operations and needed bytes of the latent-attention MoE cell against hand
counts, and its three readers on hand-built traces."""
import json
from pathlib import Path

import pytest

from bench import harness
from bench.arith_mla import MlaWorkload, read_counters
from bench.trace import Ev, Trace

BENCH = Path(__file__).resolve().parents[1]
CELL = "dsv2lite.decode_16k"


@pytest.fixture(scope="module")
def w():
    return MlaWorkload.from_conf(json.loads(
        (BENCH / "configs" / "deepseek-v2-lite-ep8.json").read_text()))


def test_deepseek_counts(w):
    assert (w.experts, w.held, w.moe_layers) == (64, 8, 26)
    # q, kv_a (512 latent + 64 rope), kv_b (16 x (128 + 128)), o
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert w.attn_params == attn == 13_762_560
    assert w.expert_params == 3 * 2048 * 1408
    assert w.shared_expert_params == 3 * 2048 * 2 * 1408
    body = 27 * attn + 3 * 2048 * 10944 + 26 * (2048 * 64
                                                + 3 * 2048 * 2816)
    assert w.body_params == body
    # 2.45 B active: body, head and 6 routed experts in 26 layers (norms,
    # 126,464 parameters, multiply nothing)
    active = body + 2048 * 102400 + 26 * 6 * 3 * 2048 * 1408
    assert active + 27 * 2 * 2048 + 27 * 512 + 2048 == 2_451_435_008
    # both attention products, un-absorbed: (192 + 128) x 2 per key,
    # head and layer
    assert w.attn_flops(100) == 2 * 320 * 100 * 16 * 27
    assert w.decode_flops(100) - w.decode_flops(0) == w.attn_flops(100)
    assert w.routed_flops(6) == 2 * 6 * 3 * 2048 * 1408
    assert w.prefill_flops(2) == 2 * body * 2 + 2 * 2048 * 102400 \
        + 2 * 320 * 16 * 27 * 3


def test_needed_bytes(w):
    # 576 binary8 values a row (the padding to 640 is not needed), the
    # absorbed query (512 + 64) and latent output (512) in bf16
    assert w.latent_decode_bytes(1000) == \
        (1000 * 576 + 16 * (512 + 64 + 512) * 2) * 27
    # the routed experts hit and every MoE layer's shared experts, in
    # bf16, and the rows in and out (pairs, and tokens per MoE layer)
    need = w.expert_step_bytes(experts_hit=10, pairs=20, tokens=4, steps=1)
    assert need == (10 * 3 * 2048 * 1408 + 26 * 3 * 2048 * 2816) * 2 \
        + 2 * (20 + 4 * 26) * 2048 * 2


def test_read_counters():
    host = [Ev("counters moe_experts_hit.decode=3 moe_routed_here.decode=5",
               0, 1),
            Ev("counters moe_experts_hit.decode=2 moe_routed_here.decode=4 "
               "moe_routed_here.prefill=60", 2, 3),
            Ev("engine.record", 0, 4)]
    assert read_counters(host) == {"moe_experts_hit.decode": 5,
                                   "moe_routed_here.decode": 9,
                                   "moe_routed_here.prefill": 60}
    assert read_counters([Ev("engine.record", 0, 4)]) == {}


def _run(host, ops):
    steps = [Ev("jit__step(7)", 1000.0 * i, 1000.0 * i + 900) for i in
             range(2)]
    trace = Trace(modules=steps, ops=ops,
                  spans=[Ev("engine_step", 0, 2000)], host=host)
    return harness.RunData(
        cell=CELL, slots=16, loop="closed", setup_s=1.0, t_open=0.0,
        t_close=1.0, steps=[], output_tokens=0, itl_s=[], ttft_s=None,
        queue_wait_s=[], lateness_s=[], arith=None,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        trace=trace, trace_s=2e-6, trace_tokens=[(4096, 0), (4096, 5),
                                                 (8192, 9)])


def test_readers_on_a_hand_built_trace(w):
    kernel = ('%latent_decode.3 = f32[16,16,512]{2,1,0} custom-call(s32[16] '
              '%a, s32[16,128] %b, f32[16,16,640] %q, u8[2048,128,640]{2,1,0}'
              ' %pool), custom_call_target="tpu_custom_call"')
    expert = ('%ragged-dot-none = f32[96,1408] custom-call(s32[1] %m, '
              'bf16[96,2048] %x, bf16[8,2048,1408] %w), '
              'custom_call_target="tpu_custom_call"')
    shared = ('%fusion.9 = bf16[16,2816] fusion(bf16[16,2048] %x, '
              'bf16[2048,2816] %w), kind=kOutput')
    # a prefetch of shared weights into on-chip memory: its window, from
    # start to done, holds the copy, whatever runs meanwhile
    fetch = ('%slice-start.3 = ((bf16[2048,2816]), bf16[512,2816], s32[]) '
             'slice-start(bf16[2048,2816] %p__layers___1___ffn____shared)')
    fetched = ('%slice-done.3 = bf16[512,2816] slice-done(((bf16[2048,2816])'
               ', bf16[512,2816], s32[]) %slice-start.3)')
    other = '%fusion.1 = bf16[16,2048] fusion(bf16[16,10944] %a)'
    ops = [Ev(kernel, 100, 400), Ev(kernel, 1100, 1300),
           Ev(fetch, 420, 425), Ev(expert, 450, 550), Ev(fetched, 580, 590),
           Ev(shared, 590, 600), Ev(other, 600, 700)]
    host = [Ev("counters moe_experts_hit.decode=150 "
               "moe_routed_here.decode=190 moe_routed_here.prefill=5000",
               10, 11)]
    r = _run(host, ops)
    need = w.latent_decode_bytes(4101) + w.latent_decode_bytes(8201)
    assert harness.reader("kernel.latent_decode_roofline.long")(r) == \
        pytest.approx(100 * need / 819e9 * 1e9 / 500)
    # expert time: the prefetch window 420-590, then the fusion to 600
    need = w.expert_step_bytes(150, 190, 2, 2)
    assert harness.reader("kernel.expert_ffn_roofline.long")(r) == \
        pytest.approx(100 * need / 819e9 * 1e9 / 180)
    flops = (w.prefill_flops(4096) + w.decode_flops(4101)
             + w.decode_flops(8201) + w.routed_flops(5190))
    assert harness.reader("step.mfu.long")(r) == \
        pytest.approx(100 * flops / (2e-6 * 197e12))


def test_readers_fall_silent_without_the_program_s_counters():
    """A program that writes no counters and runs no latent kernel (the
    parent of the change that adds them, or a dense model) reads as
    nothing, without raising."""
    r = _run([Ev("engine.record", 10, 11)],
             [Ev('%c = f32[8] custom-call(u8[64,8,2,16] %k), '
                 'custom_call_target="tpu_custom_call"', 100, 200)])
    for name in ("kernel.latent_decode_roofline.long",
                 "kernel.expert_ffn_roofline.long", "step.mfu.long"):
        assert harness.reader(name)(r) is None


def test_expert_time_pairs_each_copy_with_its_own_done():
    """Two prefetches in flight at once, one named as a prefix of the
    other's name: each done closes its own start's window, and an
    operation on no expert weight counts nothing."""
    mod = harness.load_module(
        BENCH / "metrics" / "kernel.expert_ffn_roofline.long.py", "expert")
    stack = "[8,2048,1408]"
    start = ("%slice-start.{n} = ((bf16[8,2048,1408]), bf16[2,2048,1408], "
             "s32[]) slice-start(bf16[8,2048,1408] %p)")
    done = ("%slice-done.{n} = bf16[2,2048,1408] slice-done(((bf16"
            "[8,2048,1408]), bf16[2,2048,1408], s32[]) %slice-start.{n})")
    ops = [Ev(start.format(n=3), 0, 1), Ev(start.format(n=30), 10, 11),
           Ev("%fusion = f32[16,2048] fusion(f32[16,2048] %a)", 20, 40),
           Ev(done.format(n=30), 50, 52), Ev(done.format(n=3), 100, 101)]
    assert mod.expert_ns(ops, (stack,)) == 101
    ops[4] = Ev(done.format(n=3), 30, 31)
    assert mod.expert_ns(ops, (stack,)) == 52
