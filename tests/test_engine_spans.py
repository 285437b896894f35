"""The engine's host spans (``repro.engine.tracing``) and the device-side
scopes of its programs, read back from a profile taken on the CPU.

* every phase span lies inside an ``engine.step``, and
  ``engine.device_wait`` appears once per decode step plus once per
  finished prefill;
* no span takes the benchmark harness's names (``engine_step``,
  ``bench_enqueue``);
* the spans per step do not grow with slots or layers;
* the ``jax.named_scope`` scopes change the compiled programs' metadata
  and nothing else.
"""
import contextlib
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import get_policy
from repro.engine import Engine, Request, tracing
from repro.engine.worker import DecodeWorker
from repro.models.registry import build, build_from_config

HARNESS_SPANS = ("engine_step", "bench_enqueue")


@pytest.fixture(scope="module")
def served():
    model, cfg = build("llama3-8b", reduced=True)
    pol = get_policy("binary32", decode_impl="paged")
    return model, cfg, pol


def _model(served, n_layers):
    _, cfg, pol = served
    cfg = dataclasses.replace(cfg, n_layers=n_layers,
                              attn_pattern=("attn",) * n_layers)
    model = build_from_config(cfg)
    return model, cfg, pol, model.init_params(jax.random.PRNGKey(0), pol)


def _requests(cfg, n, length, max_new):
    rng = np.random.default_rng(0)
    return [Request(i, rng.integers(0, cfg.vocab, length).tolist(), max_new)
            for i in range(n)]


def _host_events(trace_dir):
    """(name, start, end) of every host event on the thread that ran the
    engine, names without TraceMe metadata."""
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name.split("#", 1)[0], e.start_ns,
                    e.start_ns + e.duration_ns) for e in line.events]
            if any(name == tracing.STEP for name, _, _ in evs):
                return evs
    raise AssertionError("no engine.step span in the profile")


def _serve_traced(tmp_path, model, cfg, pol, params, *, slots, reqs,
                  **kw):
    eng = Engine(model, cfg, pol, params, slots=slots, capacity=64,
                 page_size=8, **kw)
    with jax.profiler.trace(str(tmp_path)):
        eng.run(reqs)
    return eng, _host_events(str(tmp_path))


def _per_step(evs):
    """Each ``engine.step`` span's program spans, in order."""
    prog = sorted((e for e in evs if e[0] in tracing.NAMES),
                  key=lambda e: (e[1], -e[2]))
    steps = [e for e in prog if e[0] == tracing.STEP]
    return [[n for n, a, b in prog if n != tracing.STEP
             and s[1] <= a and b <= s[2]] for s in steps]


def test_span_tree(served, tmp_path):
    model, cfg, pol, params = _model(served, 2)
    reqs = _requests(cfg, 3, 20, 5)    # 3 chunks each over 2 slots
    eng, evs = _serve_traced(tmp_path, model, cfg, pol, params, slots=2,
                             reqs=reqs)
    assert all(r.done for r in reqs)
    names = {n for n, _, _ in evs}
    assert not names & set(HARNESS_SPANS)
    steps = [(a, b) for n, a, b in evs if n == tracing.STEP]
    assert len(steps) == len(eng.stats.records)
    prog = [(n, a, b) for n, a, b in evs if n in tracing.NAMES
            and n != tracing.STEP]
    assert {n for n, _, _ in prog} >= {
        tracing.ADMIT, tracing.PREFILL, tracing.PREFILL_DISPATCH,
        tracing.ABSORB, tracing.FINISH, tracing.FIRST_TOKEN, tracing.GROW,
        tracing.PUSH_TABLES, tracing.DECODE_DISPATCH, tracing.DEVICE_WAIT,
        tracing.EMIT, tracing.RELEASE, tracing.RECORD}
    for n, a, b in prog:
        assert any(lo <= a and b <= hi for lo, hi in steps), n
    waits = [1 for n, _, _ in prog if n == tracing.DEVICE_WAIT]
    assert len(waits) == eng.stats.target_steps + len(reqs)
    # each prefill chunk is one engine.prefill around its dispatch
    assert sum(1 for n, _, _ in prog if n == tracing.PREFILL) == \
        sum(eng.stats.prefill_chunks.values())


@pytest.mark.parametrize("vary", ["slots", "layers"])
def test_spans_per_step_do_not_grow(served, tmp_path, vary):
    """The same two whole-prompt requests on 2 and 4 slots, or through 2
    and 4 layers, give the same spans in every step."""
    seqs = []
    for n in (2, 4):
        model, cfg, pol, params = _model(served, n if vary == "layers"
                                         else 2)
        slots = n if vary == "slots" else 2
        _, evs = _serve_traced(tmp_path / str(n), model, cfg, pol, params,
                               slots=slots, reqs=_requests(cfg, 2, 12, 4),
                               prefill_chunk=0)
        seqs.append(_per_step(evs))
    assert seqs[0] == seqs[1]
    assert any(tracing.POOL_WRITE in s for s in seqs[0])


def _strip(hlo: str) -> str:
    """The computations of a compiled module's text, without metadata
    (op names, source lines) or the stack-frame tables before them."""
    body = hlo[min(hlo.find(k) for k in ("\n%", "\nENTRY") if k in hlo):]
    return re.sub(r", metadata=\{[^}]*\}", "", body)


def test_scopes_change_metadata_only(served, monkeypatch):
    """The decode program over the gather bridge carries each scope in
    its metadata; without the scopes it compiles to the same program."""
    model, cfg, _, params = _model(served, 2)
    pol = get_policy("binary32", decode_impl="xla")
    eng = Engine(model, cfg, pol, params, slots=2, capacity=64, page_size=8)
    args = (params, eng._tokens, eng.states, jnp.zeros((2,), jnp.bool_))

    def compiled():
        return DecodeWorker(model, pol)._step.lower(*args).compile() \
            .as_text()

    scoped = compiled()
    ops = set(re.findall(r'op_name="([^"]*)"', scoped))
    for scope in ("kv_append", "gather_pages", "attn_kernel", "ffn",
                  "lm_head"):
        assert any(f"/{scope}/" in o for o in ops), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert _strip(scoped) == _strip(plain)


def test_moe_counters_ride_the_record_span(tmp_path):
    """An MoE model's step writes its counters into the profile as one
    event, named ``counters k=v ...``, inside ``engine.record``; summed
    over the run they are EngineStats' totals.  The dense model of the
    tests above writes none."""
    model, cfg = build("granite-moe-1b-a400m", reduced=True)
    pol = get_policy("binary32", decode_impl="paged")
    params = model.init_params(jax.random.PRNGKey(0), pol)
    eng, evs = _serve_traced(tmp_path, model, cfg, pol, params, slots=2,
                             reqs=_requests(cfg, 3, 12, 4), prefill_chunk=0)
    records = [(a, b) for n, a, b in evs if n == tracing.RECORD]
    counters = [(n, a, b) for n, a, b in evs
                if n.startswith(tracing.COUNTERS + " ")]
    assert 0 < len(counters) <= len(records)
    totals = {}
    for n, a, b in counters:
        assert any(lo <= a and b <= hi for lo, hi in records)
        for kv in n.split()[1:]:
            k, v = kv.split("=")
            totals[k.split(".")[0]] = totals.get(k.split(".")[0], 0) + int(v)
    assert totals == {"moe_routed_here": eng.stats.moe_routed_here,
                      "moe_experts_hit": eng.stats.moe_experts_hit}
    # summed over layers; every row of a decode step routes, idle too
    assert eng.stats.moe_routed_here == cfg.n_layers * cfg.moe_topk * (
        3 * 12 + 2 * eng.stats.target_steps)
