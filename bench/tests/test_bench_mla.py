"""The served latent-attention MoE model against its plain reference
(``bench/references/mla_moe_lm.py``), on logits, at reduced size on the
CPU: whole-prompt prefill into the paged binary8 latent pool, then eight
decode steps through it (absorbed), against the reference's un-absorbed
teacher-forced forward on the same seeded weights; and the ``Engine``
serving those prompts emits exactly the tokens whose logits were
compared."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

BENCH = Path(__file__).resolve().parents[1]
STEPS = 8


def tiny_mla_conf() -> dict:
    """The cell's configuration at the system's reduced DeepSeek sizes."""
    conf = json.loads((BENCH / "configs" / "deepseek-v2-lite-ep8.json")
                      .read_text())
    conf.update(hidden_size=64, intermediate_size=96, num_attention_heads=4,
                num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
                n_routed_experts=4, num_experts_per_tok=3,
                num_hidden_layers=3, vocab_size=256)
    conf["expert_share"] = {"offset": 0, "of": 16}
    conf["rope_scaling"] = dict(conf["rope_scaling"], factor=4,
                                original_max_position_embeddings=32)
    conf["program"] = {"arch": "deepseek-v2-lite", "reduced": True,
                       "policy": "transprecision", "overrides": {}}
    return conf


def _system(seed):
    from repro import configs
    from repro.models.registry import build_from_config
    from repro.tuning.artifact import load_policy

    cfg = configs.get("deepseek-v2-lite", reduced=True)
    model = build_from_config(cfg)
    pol = load_policy("transprecision")
    params = jax.jit(lambda k: model.init_params(k, pol))(
        jax.random.PRNGKey(harness.weight_seed(seed)))
    return model, cfg, pol, params


def served_logits(conf, prompts, seed):
    """The program's logits: each prompt prefilled whole and written into
    its slot's pages of every layer's latent pool, then ``STEPS`` batched
    decode steps over the pools, each feeding back its own argmax."""
    from repro.kernels import paged_cache

    model, cfg, pol, params = _system(seed)
    page, cap, B = 8, 32, len(prompts)
    pool = paged_cache.PagePool(B * cap // page, page, B, cap // page)
    for si in range(B):
        pool.allocate(si, cap)
    states = [paged_cache.set_block_tables(paged_cache.init_pool(
        model.cache_spec(li), B, pool.num_pages, page, cap // page,
        pol.dtype("kv_cache", layer=li)), pool.tables)
        for li in range(cfg.n_layers)]
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, pol))
    first, logits = [], []
    for si, prompt in enumerate(prompts):
        lg, one = prefill(params, jnp.asarray([prompt], jnp.int32))
        states = paged_cache.write_prefill_layers(
            states, si, [s.k[0] for s in one], [None] * len(one))
        first.append(np.asarray(lg[0, -1], np.float32))
    logits.append(np.stack(first))
    step = jax.jit(lambda p, t, s: model.decode_step(p, t, s, pol))
    for _ in range(STEPS):
        toks = jnp.asarray(np.argmax(logits[-1], -1)[:, None], jnp.int32)
        lg, states = step(params, toks, states)
        logits.append(np.asarray(lg[:, 0], np.float32))
    return np.stack(logits, 1)              # (B, 1 + STEPS, V)


def reference_logits(conf, prompts, served, seed, control=False):
    """The reference's logits at the same positions, teacher-forced on the
    prompt and the tokens the program chose."""
    Reference = harness.reference_class(conf)
    ref = Reference(conf, jax.random.PRNGKey(harness.weight_seed(seed)),
                    control=control)
    plen = max(len(p) for p in prompts)
    toks = np.zeros((len(prompts), plen + STEPS), np.int32)
    for b, p in enumerate(prompts):
        seq = list(p) + list(np.argmax(served[b, :-1], -1))
        toks[b, :len(seq)] = seq
    x = ref.hidden(toks, [len(p) for p in prompts])
    full = np.asarray(ref._mm(ref._norm(x), ref._head_w), np.float32)
    return np.stack([full[b, len(p) - 1:len(p) + STEPS]
                     for b, p in enumerate(prompts)])


SEED = 2_718_281_828


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(5)
    return [rng.integers(0, 256, n).tolist() for n in (9, 14)]


@pytest.fixture(scope="module")
def readings(prompts):
    conf, seed = tiny_mla_conf(), SEED
    served = served_logits(conf, prompts, seed)
    return (served, reference_logits(conf, prompts, served, seed),
            reference_logits(conf, prompts, served, seed, control=True))


def row_errors(got, ref):
    """Per logit row (a prefill or a decode step of one request): the
    largest |got - ref| over the vocabulary, relative to the row's
    largest reference logit."""
    scale = np.abs(ref).max(-1)
    return (np.abs(got - ref).max(-1) / scale).ravel(), scale


def test_served_logits_match_the_reference(readings):
    """Rows of the prefill's and 8 decode steps' logits (18 rows), against
    the reference at the same positions:

    * median row error <= 0.04 (readings 0.016-0.019 over 4 seeds; the
      float8 control reads 0.08-0.10): both round the same activations to
      bfloat16 at the same edges and the latent rows to binary8 for the
      decoded tokens, but the program's decode associates (q W_UK^T) c
      where the reference computes q (c W_UK) in float32, its prefill
      rounds attention probabilities to bfloat16, and a value that lands
      near a binary8 rounding edge (2 mantissa bits) can round the other
      way;
    * at most a quarter of the rows above 0.05: a token whose top-3 lies
      at a tie between a held expert and another moves its row by up to
      ~0.25 when rounding flips the choice (1-2 rows of 18 per seed);
    * in every row, the program's own token's reference logit within 0.05
      of the row's best (readings <= 0.015)."""
    served, ref, _ = readings
    err, scale = row_errors(served, ref)
    assert np.median(err) <= 0.04
    assert np.mean(err > 0.05) <= 0.25
    best = np.take_along_axis(ref, served.argmax(-1)[..., None], -1)[..., 0]
    assert np.all(ref.max(-1) - best <= 0.05 * scale)


def test_float8_control_misses_the_bounds(readings):
    """The reference one precision lower (float8 weights) against the
    sound reference fails the median bound: the comparison tells the
    configuration's precision from the one below it."""
    _, ref, low = readings
    err, _ = row_errors(low, ref)
    assert np.median(err) > 0.04


def test_the_engine_serves_the_compared_tokens(readings, prompts):
    """``Engine`` (admission, whole-prompt prefill into the paged binary8
    latent pools by the donated writer, batched decode over them) serves
    exactly the tokens whose logits the comparison above reads: each
    request's first token and its eight decoded ones are the argmax of
    those rows."""
    from repro.engine import Engine, Request

    served, _, _ = readings
    model, cfg, pol, params = _system(SEED)
    eng = Engine(model, cfg, pol, params, slots=len(prompts), capacity=32,
                 page_size=8, prefill_chunk=0)
    reqs = eng.run([Request(i, p, 1 + STEPS) for i, p in enumerate(prompts)])
    assert [r.generated for r in reqs] == served.argmax(-1).tolist()
