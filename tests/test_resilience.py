"""Fault injection and self-healing: the chaos invariant, classified
failures, and the recovery machinery's unit contracts.

The headline pin is the robustness analogue of the exactness pins the
repo already carries: under a deterministic seeded schedule of
*recoverable* faults -- page corruption and dropped/duplicated chunks in
the streamed handoff, NaN logits, draft divergence, a transient step
exception, injected pool exhaustion -- the engine's greedy tokens are
**bit-identical** to the fault-free synchronous oracle, across all four
paper formats.  Recovery is not best-effort: CRC refetch restores exact
page bytes, a retry re-runs a pure jitted step, quarantine replays
through the oracle the engine is pinned against, and greedy acceptance
makes draft divergence harmless by construction.

Non-recoverable failures (deadlines, dead letters, CRC exhaustion at the
transport, a wedged step) must surface as *classified* results or
exceptions -- distinct ``EngineError`` subtypes with stable exit codes --
and never as hangs or silent corruption; ``EngineStats`` counters must
account for every injected fault.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import BINARY8, PAPER_FORMATS
from repro.core.policy import get_policy
from repro.engine import (CircuitBreaker, ColocatedTransport,
                          DeadLetterRequest, DeadlineExceeded, Engine,
                          EngineError, EngineStats, Fault, FaultInjector,
                          FaultPlan, Request, RetryPolicy, SimulatedFault,
                          SpeculativeDecoder, StepFailure,
                          StreamedTransport, TransportError,
                          WatchdogTimeout, exit_code_for, format_error)
from repro.engine.resilience import page_checksums, with_retries
from repro.kernels import paged_cache as pc
from repro.models import qparams
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
            for _ in range(n)]


def _draft(model, cfg, k=3, seed=0):
    dpol = get_policy("transprecision", decode_impl="paged").with_overrides(
        embed_w=BINARY8, attn_w=BINARY8, ffn_w=BINARY8)
    dparams = qparams.encode_params(
        model.init_params(jax.random.PRNGKey(seed), dpol), dpol)
    return SpeculativeDecoder(model, cfg, dpol, dparams, k=k)


def _oracle(model, cfg, pol, params, prompts, max_new, capacity=64):
    from repro.engine import synchronous_generate
    return synchronous_generate(model, cfg, pol, params, prompts,
                                max_new=max_new, capacity=capacity)


# ----------------------------------------------------------- fault plans
def test_fault_plan_parse_and_json_roundtrip(tmp_path):
    plan = FaultPlan.parse(
        "page_corrupt@2,chunk_drop@5/1, nan_logits@3 ,seed=9")
    assert plan.seed == 9 and len(plan) == 3
    assert [f.step for f in plan] == [2, 3, 5]  # schedule is step-sorted
    assert plan.faults[2].slot == 1
    doc = plan.to_json()
    assert FaultPlan.from_json(doc).to_json() == doc
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(doc))
    assert FaultPlan.load(str(p)).to_json() == doc          # file form
    inline = FaultPlan.load("nan_logits@3,seed=9")          # inline form
    assert inline.faults[0].kind == "nan_logits" and inline.seed == 9
    assert "chunk_drop@5/1" in plan.describe()
    with pytest.raises(ValueError):
        Fault("bogus_kind", 1)
    with pytest.raises(ValueError):
        Fault("nan_logits", 0)  # steps are 1-based
    with pytest.raises(ValueError):
        FaultPlan.parse("nan_logits")  # missing @step


def test_injector_sticky_arming_and_accounting():
    stats = EngineStats()
    inj = FaultInjector(
        FaultPlan.parse("step_exception@3,nan_logits@2,seed=5"), stats)
    inj.begin_step(1)
    assert inj.take("step_exception") is None   # not armed yet
    assert inj.slot_mask("nan_logits", [0], 4) is None
    inj.begin_step(2)
    assert inj.take("step_exception") is None   # scheduled for 3
    mask = inj.slot_mask("nan_logits", [1], 4)  # sticky: fires at >= 2
    assert mask is not None and mask[1] and mask.sum() == 1
    inj.begin_step(7)                           # late opportunity still fires
    with pytest.raises(SimulatedFault):
        inj.maybe_raise()
    assert inj.all_fired
    assert stats.faults_injected == 2
    assert stats.faults_by_kind == {"nan_logits": 1, "step_exception": 1}


def test_injector_corrupt_flips_exactly_one_seeded_bit():
    inj = FaultInjector(FaultPlan(seed=7))
    pages = np.zeros((2, 8, 1, 4), np.uint32)
    out = inj.corrupt(pages)
    assert pages.sum() == 0                     # source untouched
    diff = out.view(np.uint8) ^ pages.view(np.uint8)
    nz = diff[diff != 0]
    assert nz.size == 1 and bin(int(nz[0])).count("1") == 1
    # same seed -> same flip (determinism is the whole point)
    out2 = FaultInjector(FaultPlan(seed=7)).corrupt(pages)
    assert np.array_equal(out, out2)
    ones = np.ones_like(pages)  # the CRC must catch any single-bit flip
    assert page_checksums(out, ones) != page_checksums(pages, ones)


# ----------------------------------------------------- classified errors
def test_classified_errors_distinct_codes_and_kinds():
    errs = (EngineError, DeadlineExceeded, DeadLetterRequest,
            TransportError, StepFailure, WatchdogTimeout, pc.PoolError)
    assert [e.exit_code for e in errs] == [70, 71, 72, 73, 74, 75, 76]
    assert len({e.kind for e in errs}) == len(errs)
    assert exit_code_for(DeadlineExceeded("x")) == 71
    assert exit_code_for(ValueError("x")) is None  # unclassified stays loud
    line = format_error(TransportError("page 3 bad"), requests=2)
    assert line.startswith("[serve:error] kind=transport exit=73")
    assert "requests=2" in line and "page 3 bad" in line


def test_with_retries_recovers_then_exhausts_classified():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise SimulatedFault("boom")
        return "ok"

    stats = EngineStats()
    pol = RetryPolicy(max_attempts=4, backoff_s=0.0)
    assert with_retries(flaky, pol, stats,
                        retriable=(SimulatedFault,)) == "ok"
    assert stats.retries == 2

    def always():
        raise SimulatedFault("still down")

    with pytest.raises(StepFailure) as ei:
        with_retries(always, pol, retriable=(SimulatedFault,),
                     what="decode step")
    assert "decode step" in str(ei.value) and "still down" in str(ei.value)

    def bug():
        raise KeyError("not transient")

    with pytest.raises(KeyError):  # non-retriable passes straight through
        with_retries(bug, pol, retriable=(SimulatedFault,))
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    assert RetryPolicy(backoff_s=0.01, backoff_cap_s=0.02).delay_s(5) \
        == 0.02  # capped


def test_circuit_breaker_lifecycle():
    br = CircuitBreaker(fail_rounds=2, cooldown_steps=3)
    assert br.allows(1) and br.state == "closed"
    br.record(step=1, proposed=4, accepted=0)
    assert br.state == "closed" and br.failures == 1
    br.record(step=2, proposed=4, accepted=0)
    assert br.state == "open" and br.trips == 1
    assert not br.allows(3) and not br.allows(4)
    assert br.allows(5)                 # cooldown over: one probe round
    assert br.state == "half_open"
    br.record(step=5, proposed=4, accepted=0)   # failed probe re-opens
    assert br.state == "open" and br.trips == 2
    assert br.allows(8)
    br.record(step=8, proposed=4, accepted=3)   # good probe closes
    assert br.state == "closed" and br.failures == 0
    br.record(step=9, proposed=0, accepted=0)   # empty round is a no-op
    assert br.state == "closed"
    with pytest.raises(ValueError):
        CircuitBreaker(fail_rounds=0)


# ------------------------------------------- PoolError (satellite bugfix)
def test_pool_free_and_allocate_raise_classified():
    pool = pc.PagePool(8, 8, 2, 4)
    with pytest.raises(pc.PoolError):
        pool.free_slot(0)               # never allocated: loud, not no-op
    assert pool.allocate(0, 8)
    assert pool.free_slot(0) == 1
    with pytest.raises(pc.PoolError):
        pool.free_slot(0)               # double free
    with pytest.raises(pc.PoolError):
        pool.allocate(5, 8)             # slot out of range
    assert pool.allocate(0, 8)
    with pytest.raises(pc.PoolError):
        pool.allocate(0, 8)             # slot already allocated


def test_release_slot_out_of_range_raises():
    cache = pc.init_paged_cache(2, 4, 8, 2, 1, 4, jnp.float32)
    with pytest.raises(pc.PoolError):
        pc.release_slot(cache, 2)
    with pytest.raises(pc.PoolError):
        pc.release_slot(cache, -1)
    pc.release_slot(cache, 1)           # in-range is fine


def test_quarantine_removes_pages_from_circulation_for_good():
    pool = pc.PagePool(4, 8, 2, 2)
    assert pool.allocate(0, 16)                      # 2 pages
    quarantined = pool.quarantine_slot(0)
    assert quarantined == 2
    assert sorted(pool.quarantined) == sorted(pool.quarantined)
    with pytest.raises(pc.PoolError):
        pool.free_slot(0)               # freed-after-quarantine is loud
    with pytest.raises(pc.PoolError):
        pool.quarantine_slot(0)         # nothing left to quarantine
    assert pool.stats()["quarantined_pages"] == 2
    assert pool.allocate(0, 16)                      # the 2 clean pages
    assert not pool.allocate(1, 8)      # pool dry: quarantine never refrees
    used = set(pool.tables[0][pool.tables[0] >= 0].tolist())
    assert not used & set(pool.quarantined)


def test_quarantine_covers_both_namespaces():
    pool = pc.PagePool(8, 8, 2, 4)
    assert pool.allocate(0, 16)
    assert pool.allocate(0, 8, ns="draft")
    assert pool.quarantine_slot(0) == 3              # 2 target + 1 draft
    assert len(pool.quarantined) == 3
    assert int(pool.lens[0]) == 0
    assert (pool.tables[0] == -1).all()
    assert (pool.ns_tables("draft")[0] == -1).all()


# --------------------------------------------------- the chaos invariant
@pytest.mark.parametrize("fmt", PAPER_FORMATS, ids=lambda f: f.name)
def test_chaos_recoverable_faults_tokens_bitidentical(fmt):
    """THE headline invariant: a seeded schedule with >= 1 of every
    recoverable fault kind -- streamed-page corruption, a dropped chunk, a
    duplicated chunk, NaN logits, draft divergence, a transient step
    exception, injected pool exhaustion -- and the engine's greedy tokens
    are bit-identical to the fault-free synchronous oracle, under every
    paper kv_cache format, with every injected fault accounted for."""
    model, cfg = build("llama3-8b", reduced=True)
    pol = get_policy("binary32", kv_fmt=fmt, decode_impl="paged")
    params = model.init_params(jax.random.PRNGKey(0), pol)
    prompts = _prompts(cfg, 3, 16)
    want = _oracle(model, cfg, pol, params, prompts, 10)

    plan = FaultPlan.parse(
        "page_corrupt@1,chunk_drop@3,chunk_dup@4,nan_logits@5,"
        "step_exception@6,draft_div@7,pool_exhaust@8,seed=11")
    eng = Engine(model, cfg, pol, params, slots=2, capacity=64,
                 page_size=8, pool_pages=32,
                 transport=StreamedTransport(),
                 speculative=_draft(model, cfg), fault_plan=plan)
    reqs = [Request(i, list(p), 10) for i, p in enumerate(prompts)]
    eng.run(reqs)

    assert [r.generated for r in reqs] == want          # bit-identical
    assert all(r.done and r.error is None for r in reqs)
    assert eng.injector.all_fired, [f.spec for f in eng.injector.pending]
    s = eng.summary
    assert s["faults_injected"] == len(plan) == 7
    assert s["faults_unfired"] == 0
    assert set(s["faults_by_kind"]) == {
        "page_corrupt", "chunk_drop", "chunk_dup", "nan_logits",
        "step_exception", "draft_div", "pool_exhaust"}
    assert s["crc_mismatches"] >= 2     # corrupt + drop (dup verifies clean)
    assert s["retries"] >= 3            # 2 refetches + 1 step re-run
    assert s["quarantines"] == 1 and s["quarantined_pages"] > 0
    assert s["evictions"] >= 1          # injected exhaustion walked LIFO
    assert s["failures"] == 0           # every fault recovered


def test_nan_guard_quarantines_and_replays_plain_decode(served_model):
    """The non-speculative NaN path: the poisoned slot's pages leave
    circulation and the request still finishes with oracle tokens."""
    model, cfg, pol, params = served_model
    prompts = _prompts(cfg, 2, 8)
    want = _oracle(model, cfg, pol, params, prompts, 4, capacity=32)
    eng = Engine(model, cfg, pol, params, slots=2, capacity=32, page_size=8,
                 fault_plan=FaultPlan.parse("nan_logits@2"))
    reqs = [Request(i, list(p), 4) for i, p in enumerate(prompts)]
    eng.run(reqs)
    assert [r.generated for r in reqs] == want
    s = eng.summary
    assert s["quarantines"] == 1 and s["failures"] == 0
    assert eng.pool.stats()["quarantined_pages"] > 0
    assert eng.injector.all_fired


def test_crc_exhaustion_recomputes_request_from_prompt(served_model):
    """Every refetch attempt corrupted: the transport raises a classified
    TransportError and the scheduler recomputes the request from its
    prompt -- same tokens, one eviction, max_attempts CRC mismatches."""
    model, cfg, pol, params = served_model
    prompts = _prompts(cfg, 1, 8)
    want = _oracle(model, cfg, pol, params, prompts, 4, capacity=32)
    plan = FaultPlan.parse(",".join(["page_corrupt@1"] * 4) + ",seed=2")
    eng = Engine(model, cfg, pol, params, slots=1, capacity=32, page_size=8,
                 transport=StreamedTransport(), fault_plan=plan,
                 retry_policy=RetryPolicy(max_attempts=4, backoff_s=0.0))
    reqs = [Request(0, list(prompts[0]), 4)]
    eng.run(reqs)
    assert [r.generated for r in reqs] == want
    s = eng.summary
    assert s["crc_mismatches"] == 4 and s["faults_injected"] == 4
    assert s["evictions"] == 1 and s["failures"] == 0


def test_step_exception_retry_exhaustion_raises_stepfailure(served_model):
    model, cfg, pol, params = served_model
    plan = FaultPlan.parse(",".join(["step_exception@2"] * 3))
    eng = Engine(model, cfg, pol, params, slots=1, capacity=32, page_size=8,
                 fault_plan=plan,
                 retry_policy=RetryPolicy(max_attempts=2, backoff_s=0.0))
    with pytest.raises(StepFailure):
        eng.run([Request(0, _prompts(cfg, 1, 8)[0], 4)])
    assert eng.stats.retries == 2       # both attempts burned


def test_deadlines_fail_classified_and_never_hang(served_model):
    """One slot, three requests: the slotted one and a queued one expire
    at the engine-wide 3-step deadline; a per-request override lets the
    third run to completion.  The run returns -- classified results, no
    hang -- and the counters account for both misses."""
    model, cfg, pol, params = served_model
    p = _prompts(cfg, 3, 8)
    r0 = Request(0, p[0], 8)                        # engine default: 3
    r1 = Request(1, p[1], 2, deadline_steps=50)     # per-request override
    r2 = Request(2, p[2], 8)                        # expires while queued
    eng = Engine(model, cfg, pol, params, slots=1, capacity=32, page_size=8,
                 deadline_steps=3)
    eng.run([r0, r1, r2])
    assert isinstance(r0.error, DeadlineExceeded) and not r0.done
    assert isinstance(r2.error, DeadlineExceeded) and not r2.done
    assert r2.generated == []           # never admitted
    assert r1.error is None and r1.done and len(r1.generated) == 2
    s = eng.summary
    assert s["deadline_misses"] == 2 and s["failures"] == 2


def test_dead_letter_after_bounded_requeues(served_model):
    """max_requeues=0 + one injected pool exhaustion: the first eviction
    dead-letters the request instead of thrashing the queue forever."""
    model, cfg, pol, params = served_model
    eng = Engine(model, cfg, pol, params, slots=1, capacity=32, page_size=8,
                 fault_plan=FaultPlan.parse("pool_exhaust@2"),
                 max_requeues=0)
    r = Request(0, _prompts(cfg, 1, 8)[0], 8)
    eng.run([r])
    assert isinstance(r.error, DeadLetterRequest) and not r.done
    s = eng.summary
    assert s["dead_letters"] == 1 and s["evictions"] == 1
    assert s["faults_by_kind"] == {"pool_exhaust": 1}


def test_breaker_opens_on_injected_divergence_and_recovers(served_model):
    """Two consecutive fully-diverged rounds trip the breaker (one slot,
    so the div mask zeroes the whole batch's acceptance); the engine
    decodes plain through the cooldown -- draft KV kept warm by the shadow
    step -- then the half-open probe succeeds and closes it.  Tokens stay
    oracle-exact throughout (greedy acceptance never trusted the draft)."""
    model, cfg, pol, params = served_model
    prompts = _prompts(cfg, 1, 8)
    want = _oracle(model, cfg, pol, params, prompts, 12, capacity=64)
    eng = Engine(model, cfg, pol, params, slots=1, capacity=64, page_size=8,
                 speculative=_draft(model, cfg, k=3),
                 breaker=CircuitBreaker(fail_rounds=2, cooldown_steps=3),
                 fault_plan=FaultPlan.parse("draft_div@2,draft_div@3"))
    reqs = [Request(0, list(prompts[0]), 12)]
    eng.run(reqs)
    assert [r.generated for r in reqs] == want
    s = eng.summary
    # at least the injected trip; a half-open probe may legitimately fail
    # again (binary8 draft vs this format's target argmax) and re-trip
    assert s["breaker_trips"] >= 1
    assert s["degraded_steps"] >= 2     # plain decode through the cooldown
    assert s["faults_by_kind"] == {"draft_div": 2}
    assert s["failures"] == 0


def test_watchdog_raises_classified_timeout(served_model):
    model, cfg, pol, params = served_model
    eng = Engine(model, cfg, pol, params, slots=1, capacity=32, page_size=8,
                 watchdog_s=0.0, watchdog_limit=2)  # every step over budget
    with pytest.raises(WatchdogTimeout):
        eng.run([Request(0, _prompts(cfg, 1, 8)[0], 8)])
    assert eng.stats.watchdog_trips >= 2


# -------------------------------- mid-stream abort + re-admission (sat 3)
class _AbortCounting:
    def __init__(self):
        self.aborts = 0

    def abort(self, engine, task):
        self.aborts += 1
        super().abort(engine, task)


class _AbortCountingColocated(_AbortCounting, ColocatedTransport):
    pass


class _AbortCountingStreamed(_AbortCounting, StreamedTransport):
    def __init__(self):
        _AbortCounting.__init__(self)
        StreamedTransport.__init__(self)


@pytest.mark.parametrize("transport_cls",
                         [_AbortCountingColocated, _AbortCountingStreamed],
                         ids=["colocated", "streamed"])
def test_midstream_abort_then_readmission_same_rid(served_model,
                                                   transport_cls):
    """A long prompt evicted *mid-prefill* (transport abort fires with
    pages already handed over) and re-admitted under the same request id
    must still produce oracle-exact tokens -- for both transports.  Same
    pressure trace as the engine-layer eviction test: r0 decodes across a
    page boundary while r1's 80-token prompt is mid-chunk with the
    12-page pool exhausted."""
    model, cfg, pol, params = served_model
    p0, p1 = _prompts(cfg, 1, 7)[0], _prompts(cfg, 1, 80, seed=1)[0]
    want0 = _oracle(model, cfg, pol, params, [p0], 12, capacity=96)[0]
    want1 = _oracle(model, cfg, pol, params, [p1], 4, capacity=96)[0]
    tr = transport_cls()
    eng = Engine(model, cfg, pol, params, slots=2, capacity=96,
                 page_size=8, pool_pages=12, transport=tr)
    reqs = [Request(0, list(p0), 12), Request(1, list(p1), 4)]
    eng.run(reqs)
    assert all(r.done and r.error is None for r in reqs)
    assert reqs[1].evictions >= 1       # bumped mid-prefill ...
    assert tr.aborts >= 1               # ... with the abort hook fired
    assert [r.generated for r in reqs] == [want0, want1]


# ------------------------------------------- serve CLI exit codes (sat 2)
def test_serve_cli_maps_classified_errors_to_exit_codes(capsys, monkeypatch,
                                                       tmp_path):
    from repro.launch.serve import cli_main
    # cli_main enables the persistent compile cache; keep it out of the repo
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    base = ["--arch", "llama3-8b", "--reduced", "--requests", "2",
            "--slots", "1", "--prompt-len", "8", "--max-new", "2",
            "--capacity", "32", "--decode-impl", "paged"]
    assert cli_main(base) == 0
    capsys.readouterr()

    code = cli_main(base + ["--deadline-steps", "1"])
    assert code == DeadlineExceeded.exit_code == 71
    err = capsys.readouterr().err
    assert "[serve:error] kind=deadline exit=71" in err
    # request 0 finishes inside step 1; the queued request 1 expires
    assert "requests=1" in err

    # --max-new 8 so the run outlasts the 3-consecutive-trips limit
    code = cli_main(base + ["--max-new", "8", "--watchdog-s", "0.0"])
    assert code == WatchdogTimeout.exit_code == 75
    assert "[serve:error] kind=watchdog exit=75" in capsys.readouterr().err


def test_serve_prints_resilience_line_for_unplanned_quarantine(
        capsys, monkeypatch):
    """A NaN-guard trip with no fault plan (a kernel returning NaN on the
    device, say) is recovered by the oracle replay -- the run still
    succeeds, so the recovery must be visible in the output."""
    import jax.numpy as jnp

    from repro.engine.scheduler import Engine
    from repro.launch.serve import main

    calls = []
    real = Engine._fault_mask

    def poison_first_decode(self, kind, decoding):
        if kind == "nan_logits" and not calls:
            calls.append(kind)
            return jnp.ones((self.slots,), jnp.bool_)
        return real(self, kind, decoding)

    monkeypatch.setattr(Engine, "_fault_mask", poison_first_decode)
    reqs = main(["--arch", "llama3-8b", "--reduced", "--requests", "1",
                 "--slots", "1", "--prompt-len", "8", "--max-new", "3",
                 "--capacity", "32", "--decode-impl", "paged"])
    assert all(r.done and r.error is None for r in reqs)
    out = capsys.readouterr().out
    assert "[serve] resilience:" in out
    assert "quarantines=1" in out
