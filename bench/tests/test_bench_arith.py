"""Operations and needed bytes against hand counts."""
import json
from pathlib import Path

import pytest

from bench.arith import Workload

BENCH = Path(__file__).resolve().parents[1]


def load(name):
    return Workload.from_conf(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))


def test_granite_counts():
    w = load("granite-moe-1b-a400m")
    attn = 1024 * 1024 + 2 * 1024 * 512 + 1024 * 1024          # q, k, v, o
    moe = 1024 * 32 + 8 * 3 * 1024 * 512                        # router, 8 experts
    assert w.layer_matmul_params == attn + moe == 15_761_408
    assert w.head_params == 1024 * 49155
    # 0.43 B active parameters: 2 FLOPs each, plus attention over context
    assert w.decode_flops(0) == 2 * (24 * 15_761_408 + 50_334_720) \
        == 857_217_024
    assert w.decode_flops(100) - w.decode_flops(0) == \
        4 * 100 * 16 * 64 * 24
    # 24,576 B of binary8 K and V per token, plus bf16 q and o per layer
    assert w.decode_attn_bytes(1000) == 1000 * 2 * 8 * 64 * 24 \
        + 2 * 16 * 64 * 2 * 24
    assert w.decode_attn_bytes(1) - w.decode_attn_bytes(0) == 24_576


def test_yi_counts():
    w = load("yi-9b-24l")
    layer = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert w.layer_matmul_params == layer == 173_015_040
    assert 24 * layer + w.head_params == 4_414_504_960
    assert w.decode_attn_bytes(1) - w.decode_attn_bytes(0) == \
        2 * 4 * 128 * 24 == 24_576


@pytest.mark.parametrize("name", ["granite-moe-1b-a400m", "yi-9b-24l"])
def test_prefill_is_its_tokens(name):
    w = load(name)
    P = 512
    # every prompt token multiplies by every layer's matmuls; the head runs
    # once; causal attention sums 1..P keys
    expect = (2 * w.layers * w.layer_matmul_params * P + 2 * w.head_params
              + 4 * w.heads * w.head_dim * w.layers * P * (P + 1) // 2)
    assert w.prefill_flops(P) == expect
    # a prompt costs less than decoding the same tokens one by one
    assert w.prefill_flops(P) < sum(w.decode_flops(c) for c in range(1, P + 1))
