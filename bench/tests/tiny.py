"""Cells small enough for the CPU: the system's reduced yi config (2 dense
layers, d_model 64, vocab 256) -- dense, so no routing decision can flip
on rounding and a sound run's gaps stay at rounding level -- or the
reduced granite (4 experts top-2), and a short mix."""
import json
from pathlib import Path

from bench import harness, traffic

BENCH = Path(__file__).resolve().parents[1]


def tiny_dense_conf() -> dict:
    conf = json.loads((BENCH / "configs" / "yi-9b-24l.json").read_text())
    conf.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                vocab_size=256, attention_multiplier=0.25)
    conf["program"] = {"arch": "yi-9b", "reduced": True,
                       "policy": "transprecision", "overrides": {}}
    return conf


def tiny_conf() -> dict:
    conf = json.loads((BENCH / "configs" / "granite-moe-1b-a400m.json")
                      .read_text())
    conf.update(hidden_size=64, intermediate_size=32, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                num_local_experts=4, num_experts_per_tok=2, vocab_size=256,
                attention_multiplier=0.25)
    conf["program"] = {"arch": "granite-moe-1b-a400m", "reduced": True,
                       "policy": "transprecision",
                       "overrides": {"capacity_factor": 2.0}}
    return conf


def tiny_mix(loop: str = "closed") -> traffic.Mix:
    return traffic.Mix(
        name="tiny", loop=loop, slots=4, capacity=256, page_size=128,
        prefill_chunk=0, prompt_buckets=(16, 32), prompt_weights=(1, 1),
        out_median=12, out_sigma=0.5, out_min=4, out_max=40, requests=64,
        shape_seed=1, stagger=4, rate_rps=20.0, warmup_s=0.5, trace_s=1.0,
        sample=6)


def tiny_cell(limits: dict, loop: str = "closed",
              dense: bool = False) -> harness.Cell:
    """``limits`` maps each compared number to its limit."""
    e2e = [{"name": n, "unit": "u"} for n in
           ("output_tok_s", "itl_p95_ms", "setup_s")]
    conf = tiny_dense_conf() if dense else tiny_conf()
    return harness.Cell(name="tiny", chips=1, conf=conf,
                        mix=tiny_mix(loop),
                        limits={k: {"limit": v} for k, v in limits.items()},
                        end_to_end=e2e, per_layer=[])
