"""Engine-layer tests: scheduler interleaving, transient prefill memory,
eviction of in-flight prefills, the stats stream, and the shared CLI
builder.

The cross-impl greedy-token pins (engine vs the synchronous reference,
2-device mesh, disaggregated transport) live in ``tests/test_system.py``;
this file tests the engine's *scheduling* contracts on one model:

* chunked prefill never stalls the decode batch (the acceptance criterion
  of the disaggregation ROADMAP item);
* peak transient prefill staging is O(page_size), not O(prompt_len);
* a mid-prefill sequence can be evicted and still completes correctly.
"""
import argparse
import json

import jax
import numpy as np
import pytest

from repro.core.policy import get_policy
from repro.engine import (ColocatedTransport, Engine, EngineStats, Request,
                          StreamedTransport, synchronous_generate)
from repro.launch.cli import add_backend_args
from repro.models.registry import build


@pytest.fixture(scope="module")
def served_model():
    model, cfg = build("llama3-8b", reduced=True)
    pol = get_policy("binary32", decode_impl="paged")
    params = model.init_params(jax.random.PRNGKey(0), pol)
    return model, cfg, pol, params


def _prompts(cfg, n, length, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, min(cfg.vocab, 97), length).tolist()
            for i in range(n)]


# ------------------------------------------------------------- scheduling
def test_decode_progresses_during_chunked_prefill(served_model):
    """A 32-token prompt prefills over 4 page-sized chunks; the already-
    admitted sequence must emit a token on every one of those steps --
    long-prompt admission no longer stalls the decode batch."""
    model, cfg, pol, params = served_model
    eng = Engine(model, cfg, pol, params, slots=2, capacity=64, page_size=8)
    reqs = [Request(i, p, 6) for i, p in
            enumerate(_prompts(cfg, 3, 32))]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    steps = [r for r in eng.stats.records if r["kind"] == "step"]
    overlapped = [r for r in steps if r["prefilling"] and r["decoding"]]
    # 4 chunks per prompt, 3 prompts, 2 slots: overlap must actually occur
    assert len(overlapped) >= 4, steps
    for r in overlapped:  # decode batch progressed while prefill in flight
        assert r["new_tokens"] >= r["decoding"], r
    for r in steps:
        assert set(k for k in r if k.startswith("pool_")) >= {
            "pool_pages_used", "pool_occupancy",
            "pool_internal_fragmentation", "pool_peak_pages_used"}


def test_chunked_prefill_transient_is_one_page(served_model):
    """The regression the refactor exists for: chunked prefill stages at
    most one page of K/V per step, whole-prompt prefill stages the whole
    prompt -- O(page_size) vs O(prompt_len) transient memory."""
    model, cfg, pol, params = served_model
    page, prompt_len = 8, 32
    runs = {}
    for mode, chunk in (("chunked", None), ("whole", 0)):
        eng = Engine(model, cfg, pol, params, slots=2, capacity=64,
                     page_size=page, prefill_chunk=chunk)
        reqs = [Request(i, p, 4) for i, p in
                enumerate(_prompts(cfg, 2, prompt_len))]
        eng.run(reqs)
        runs[mode] = (eng.stats.peak_prefill_transient_tokens,
                      [r.generated for r in reqs])
    assert runs["chunked"][0] <= page
    assert runs["whole"][0] == prompt_len
    assert runs["chunked"][1] == runs["whole"][1]  # same greedy tokens


@pytest.mark.parametrize("chunk", [0, None], ids=["whole", "chunked"])
@pytest.mark.parametrize("transport", [ColocatedTransport, StreamedTransport])
def test_whole_prompt_pool_write_matches_reference(served_model, transport,
                                                   chunk):
    """Whole-prompt prefill writes every layer's pool in one donated call
    per prompt, through either transport, and serves the synchronous
    reference's tokens; the chunked path never makes that call."""
    model, cfg, pol, params = served_model
    prompts = _prompts(cfg, 3, 11) + _prompts(cfg, 1, 16, seed=1)
    want = synchronous_generate(model, cfg, pol, params, prompts,
                                max_new=5, capacity=32)
    eng = Engine(model, cfg, pol, params, slots=2, capacity=32, page_size=8,
                 prefill_chunk=chunk, transport=transport())
    reqs = [Request(i, list(p), 5) for i, p in enumerate(prompts)]
    eng.run(reqs)
    assert [r.generated for r in reqs] == want
    s = eng.stats.summary()
    if chunk == 0:
        assert s["pool_write_calls"] == sum(
            eng.stats.prefill_chunks.values()) == len(reqs)
        assert eng.prefill_worker._write_pools._cache_size() == 2
    else:
        assert s["pool_write_calls"] == 0


class _CountingTransport(ColocatedTransport):
    def __init__(self):
        self.aborts = 0

    def abort(self, engine, task):
        self.aborts += 1
        super().abort(engine, task)


def test_eviction_of_inflight_prefill_still_completes(served_model):
    """Pool pressure evicts the newest admission, which can be the
    sequence that is *mid-prefill*; the transport abort path must requeue
    it cleanly and the final tokens must still equal the synchronous
    reference.

    The setup is traced out so the eviction really lands mid-prefill:
    r0 (7-token prompt) is decoding and crosses a page boundary (3rd page)
    at step 10, while r1's 80-token prompt is still chunk-prefilling
    (10 chunks, steps 2-11) with the 12-page pool exhausted -- so the
    growth loop evicts r1 with its prefill in flight."""
    model, cfg, pol, params = served_model
    p0, p1 = _prompts(cfg, 1, 7)[0], _prompts(cfg, 1, 80, seed=1)[0]
    want0 = synchronous_generate(model, cfg, pol, params, [p0],
                                 max_new=12, capacity=96)[0]
    want1 = synchronous_generate(model, cfg, pol, params, [p1],
                                 max_new=4, capacity=96)[0]
    tr = _CountingTransport()
    eng = Engine(model, cfg, pol, params, slots=2, capacity=96,
                 page_size=8, pool_pages=12, transport=tr)
    reqs = [Request(0, list(p0), 12), Request(1, list(p1), 4)]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    assert reqs[1].evictions >= 1  # the long prompt got bumped
    assert tr.aborts >= 1          # ... while its prefill was in flight
    assert [r.generated for r in reqs] == [want0, want1]


# ------------------------------------------------------------------ stats
def test_stats_jsonl_stream(served_model, tmp_path):
    model, cfg, pol, params = served_model
    out = tmp_path / "engine.jsonl"
    eng = Engine(model, cfg, pol, params, slots=2, capacity=32, page_size=8,
                 stats=EngineStats(str(out)))
    reqs = [Request(i, p, 4) for i, p in enumerate(_prompts(cfg, 2, 8))]
    eng.run(reqs)
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    steps = [ln for ln in lines if ln["kind"] == "step"]
    summaries = [ln for ln in lines if ln["kind"] == "summary"]
    assert steps and len(summaries) == 1
    s = summaries[0]
    assert s["requests"] == 2 and s["decode_tokens"] >= 8
    assert s["ttft_mean_s"] > 0 and s["tokens_per_s"] > 0
    assert s["peak_prefill_transient_tokens"] == 8
    assert (s["peak_prefill_transient_bytes"]
            == 8 * eng.kv_bytes_per_token > 0)
    assert lines == sorted(lines, key=lambda ln: ln.get("step", 1 << 30))


# ------------------------------------------------------------- validation
def test_engine_rejects_capacity_beyond_window():
    model, cfg = build("recurrentgemma-2b", reduced=True)
    pol = get_policy("binary32")
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), pol))
    with pytest.raises(ValueError) as ei:
        Engine(model, cfg, pol, params, slots=1,
               capacity=cfg.window + 8, page_size=8)
    assert "window" in str(ei.value)


def test_engine_rejects_encoder_decoder_arch():
    model, cfg = build("whisper-tiny", reduced=True)
    pol = get_policy("binary32")
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), pol))
    with pytest.raises(ValueError) as ei:
        Engine(model, cfg, pol, params, slots=1, capacity=32)
    assert "decoder-only" in str(ei.value)


def test_disaggregate_rejects_wrapper_spellings():
    from repro.launch.serve import main
    with pytest.raises(ValueError) as ei:
        main(["--arch", "llama3-8b", "--reduced", "--requests", "1",
              "--decode-impl", "flash_shmap+paged", "--disaggregate"])
    assert "disaggregate" in str(ei.value)


def test_wrapped_spelling_without_usable_mesh_raises():
    """One device cannot shard: the entry point refuses a wrapped spelling
    instead of letting the wrapper serve it unsharded."""
    from repro.launch.serve import main
    for impl in ("flash_shmap+paged", "ring+paged", "flash_shmap"):
        with pytest.raises(ValueError, match="at least 2 devices"):
            main(["--arch", "llama3-8b", "--reduced", "--requests", "1",
                  "--max-new", "2", "--prompt-len", "4", "--capacity", "16",
                  "--page-size", "8", "--decode-impl", impl])


# ------------------------------------------------------------ CLI builder
def test_add_backend_args_validates_from_registry():
    from repro.kernels import dispatch
    ap = argparse.ArgumentParser()
    add_backend_args(ap)
    args = ap.parse_args([])
    assert args.decode_impl is None and args.matmul_impl is None
    assert args.page_size > 0 and args.pool_pages is None
    for impl in dispatch.legal_impls():  # every registry spelling parses
        assert ap.parse_args(["--decode-impl", impl]).decode_impl == impl
    with pytest.raises(SystemExit):
        ap.parse_args(["--decode-impl", "paged_flash"])
    with pytest.raises(SystemExit):
        ap.parse_args(["--matmul-impl", "qmm"])


def test_add_backend_args_pool_flags_optional():
    ap = argparse.ArgumentParser()
    add_backend_args(ap, include_pool=False)
    with pytest.raises(SystemExit):
        ap.parse_args(["--page-size", "8"])


def test_streamed_transport_single_device_roundtrip(served_model):
    """StreamedTransport on one device still exercises the page-copy
    handoff machinery (src pool -> decode pool) and must be token-exact."""
    model, cfg, pol, params = served_model
    prompts = _prompts(cfg, 2, 8)
    want = synchronous_generate(model, cfg, pol, params, prompts,
                                max_new=4, capacity=32)
    eng = Engine(model, cfg, pol, params, slots=2, capacity=32, page_size=8,
                 prefill_chunk=3, transport=StreamedTransport())
    reqs = [Request(i, list(p), 4) for i, p in enumerate(prompts)]
    eng.run(reqs)
    assert [r.generated for r in reqs] == want
    assert isinstance(eng.transport, StreamedTransport)
    assert ColocatedTransport().name == "colocated"
