"""The serving benchmark's run: set-up, warm-up, the measured window, the
metrics and the correctness check, for one cell of ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
metric lives in a file of its own, found by name:

* ``bench/configs/<config>.json``   the configuration as it is run, and the
                                    name of its plain reference;
* ``bench/references/<name>.py``    that reference;
* ``bench/traffic/<mix>.json``      the traffic mix (:mod:`bench.traffic`);
* ``bench/metrics/<metric>.py``     one reader per metric, ``read(run)``;
* ``bench/limits/<workload>.json``  the limit each compared number is held
                                    to, with the readings it was set from.

The system under test is driven through its public serving entry
(``Engine.enqueue`` / ``Engine.step``) on the model, policy and weights its
serving front end builds.  Only its spans (none yet), counters
(``EngineStats``) and program names are read from it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import arith, traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
DRAIN_S = 60.0        # longest wait past the window for a due first token
IDLE_WAIT_S = 0.05    # longest sleep of an idle open loop between checks


class BenchError(Exception):
    """The run cannot produce a result (no chip, a missing file, ...)."""


# ---------------------------------------------------------------------------
# finding a cell's files
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    mix: traffic.Mix
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench_json = root / "BENCHMARK.json"
    if not bench_json.exists():
        raise BenchError(f"{bench_json} not found")
    spec = json.loads(bench_json.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    conf = json.loads((root / confs[w["config"]]["file"]).read_text())
    mix = traffic.load_mix(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits_path = root / "bench" / "limits" / f"{workload}.json"
    if not limits_path.exists():
        raise BenchError(f"{limits_path} not found: no limit for the "
                         f"correctness check")
    return Cell(
        name=workload, chips=int(w["chips"]), conf=conf, mix=mix,
        limits=json.loads(limits_path.read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric_name: str):
    path = BENCH / "metrics" / f"{metric_name}.py"
    if not path.exists():
        raise BenchError(f"no reader {path} for metric {metric_name!r}")
    return load_module(path, "bench_metric_" + metric_name.replace(".", "_")
                       .replace("-", "_")).read


def reference_class(conf: dict):
    name = conf["reference"]
    return load_module(BENCH / "references" / f"{name}.py",
                       f"bench_reference_{name}").Reference


def peaks_for(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json ({sorted(table['devices'])}); "
                         f"add its published peaks there")
    return table["devices"][device_kind]


def weight_seed(seed: int) -> int:
    """The JAX PRNG seed of the weights: the run seed folded into 31 bits
    (the harness and the reference both use this)."""
    return abs(int(seed)) % (2 ** 31 - 1)


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    new_tokens: int
    decoding: int
    prefilling: int
    queue_depth: int


@dataclasses.dataclass
class RunData:
    """What the metric readers read.  Times are host ``perf_counter``
    seconds; the window is ``[t_open, t_close]``."""
    cell: str
    slots: int
    loop: str
    setup_s: float
    t_open: float
    t_close: float
    steps: List[Step]              # steps that ran inside the window
    output_tokens: int             # tokens emitted inside the window
    itl_s: List[float]             # gaps between a request's tokens
    ttft_s: Optional[List[float]]  # open loop: per request due in window
    queue_wait_s: List[float]      # requests admitted inside the window
    lateness_s: List[float]        # open loop: enqueue time - due time
    arith: "arith.Workload"
    peaks: dict
    trace: object = None           # bench.trace.Trace of a --trace 1 run
    trace_s: float = 0.0           # host seconds of the traced stretch
    # (prompt length, index of the token in its request) of every token
    # the traced stretch emitted; index 0 came out of a prefill
    trace_tokens: List[tuple] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank (``inf`` counts as a value)."""
    v = sorted(values)
    if not v:
        return float("nan")
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


class CompileCount:
    """Programs the process had to build: backend compiles plus loads from
    the persistent cache."""

    def __init__(self, jax):
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.compiles = self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, secs, **_: self._dur(ev, BACKEND_COMPILE_EVENT))
        jax.monitoring.register_event_listener(
            lambda ev, **_: self._ev(ev))

    def _dur(self, ev, want):
        if ev == want:
            self.compiles += 1

    def _ev(self, ev):
        if ev == "/jax/compilation_cache/cache_hits":
            self.loads += 1

    @property
    def total(self) -> int:
        return self.compiles + self.loads


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def build_system(jax, conf: dict, mix: traffic.Mix, seed: int):
    """Model, policy, weights and engine, on the path the serving front end
    takes (``repro.launch.serve.serve``)."""
    from repro import configs
    from repro.engine import Engine, EngineStats
    from repro.kernels import dispatch
    from repro.models.registry import build_from_config
    from repro.tuning.artifact import load_policy

    prog = conf["program"]
    cfg = configs.get(prog["arch"], reduced=bool(prog.get("reduced")))
    n_layers = int(conf["num_hidden_layers"])
    cfg = dataclasses.replace(cfg, n_layers=n_layers,
                              attn_pattern=cfg.attn_pattern[:n_layers],
                              **prog.get("overrides", {}))
    model = build_from_config(cfg)
    policy = load_policy(prog["policy"])
    if policy.decode_impl is None:
        policy = dataclasses.replace(
            policy, decode_impl=dispatch.default_serving_impl())
    key = jax.random.PRNGKey(weight_seed(seed))
    params = jax.jit(lambda k: model.init_params(k, policy))(key)
    jax.block_until_ready(params)
    engine = Engine(model, cfg, policy, params, slots=mix.slots,
                    capacity=mix.capacity, page_size=mix.page_size,
                    prefill_chunk=mix.prefill_chunk, stats=EngineStats())
    return cfg, policy, engine


def _serve_until_idle(engine, reqs):
    for r in reqs:
        engine.enqueue(r)
    while engine.has_work():
        engine.step()


def warm_shapes(engine, mix: traffic.Mix, vocab: int, seed: int):
    """Build every program the window will use before it opens: one
    request per prompt bucket, each decoding a few tokens (the decode step,
    each bucket's prefill, the eager pool writes, admission and release)."""
    from repro.engine import Request
    rng = traffic.seed_rng(seed + 1)
    reqs = [Request(-1 - i, rng.integers(0, vocab, b).tolist(), 3)
            for i, b in enumerate(mix.prompt_buckets)]
    _serve_until_idle(engine, reqs)


class Driver:
    """Feeds the plan into the engine and keeps the host-clock records:
    every step's span, and the time each request's every token reached the
    host (the end of the step that produced it: each step ends in one
    device-to-host transfer)."""

    def __init__(self, engine, mix: traffic.Mix, plan, span):
        self.engine, self.mix, self.plan, self.span = engine, mix, plan, span
        self.next = next(plan)
        self.inflight: Dict[int, object] = {}
        self.requests: Dict[int, object] = {}
        self.tok_t: Dict[int, List[float]] = {}
        self.due_t: Dict[int, float] = {}
        self.done_t: Dict[int, float] = {}
        self.late: Dict[int, float] = {}
        self.steps: List[Step] = []
        self.enqueued = 0
        self.admitted0 = engine.stats.admitted   # admitted before this plan
        self.t_sched = None

    def start(self, now: float):
        self.t_sched = now

    def _enqueue(self, p, now):
        from repro.engine import Request
        r = Request(p.index, p.prompt, p.max_new)
        with self.span("bench_enqueue"):
            self.engine.enqueue(r)
        self.inflight[p.index] = r
        self.requests[p.index] = r
        self.tok_t[p.index] = []
        self.enqueued += 1
        if self.mix.loop == "open":
            due = self.t_sched + p.due_s
            self.due_t[p.index] = due
            self.late[p.index] = now - due
        else:
            self.due_t[p.index] = now
        self.next = next(self.plan)

    def feed(self, now: float):
        if self.mix.loop == "open":
            while self.t_sched + self.next.due_s <= now:
                self._enqueue(self.next, now)
        else:
            while self.enqueued - (self.engine.stats.admitted
                                   - self.admitted0) < self.mix.backlog:
                self._enqueue(self.next, now)

    def step(self, feed: bool = True) -> float:
        """One engine step; with nothing to serve, wait for the next
        arrival instead (no empty steps are recorded)."""
        if feed:
            self.feed(time.perf_counter())
        if not self.engine.has_work():
            if feed and self.mix.loop == "open":
                wait = self.t_sched + self.next.due_s - time.perf_counter()
                time.sleep(min(max(wait, 0.0), IDLE_WAIT_S))
            return time.perf_counter()
        t0 = time.perf_counter()
        with self.span("engine_step"):
            self.engine.step()
        t1 = time.perf_counter()
        rec = self.engine.stats.records[-1]
        new = 0
        for rid, r in list(self.inflight.items()):
            times = self.tok_t[rid]
            k = len(r.generated) - len(times)
            if k > 0:
                new += k
                times.extend([t1] * k)
            if r.done or r.error is not None:
                self.done_t[rid] = t1
                del self.inflight[rid]
        self.steps.append(Step(t0, t1, new, rec["decoding"],
                               rec["prefilling"], rec["queue_depth"]))
        return t1

    def steps_in(self, lo: float, hi: float) -> List[Step]:
        return [s for s in self.steps if s.t0 >= lo and s.t1 <= hi]

    def tokens_in(self, lo: float, hi: float) -> List[tuple]:
        out = []
        for rid, times in self.tok_t.items():
            plen = len(self.requests[rid].prompt)
            out.extend((plen, j) for j, t in enumerate(times)
                       if lo <= t <= hi)
        return out

    def gaps_in(self, lo: float, hi: float) -> List[float]:
        """Every gap between two consecutive tokens of one request, both
        inside ``[lo, hi]``."""
        out = []
        for times in self.tok_t.values():
            t = [x for x in times if lo <= x <= hi]
            out.extend(b - a for a, b in zip(t, t[1:]))
        return out


def _device_peak(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _free_device(jax):
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


def sample_finished(drv: Driver, n: int, seed: int, t_open: float):
    """The requests the check replays: the longest finished one and
    ``n - 1`` more drawn from the seed, preferring those that finished
    inside the window."""
    done = [rid for rid, t in drv.done_t.items()
            if drv.requests[rid].done and drv.requests[rid].error is None]
    inside = [rid for rid in done if drv.done_t[rid] >= t_open]
    pool = inside if len(inside) >= n else done
    if not pool:
        return []

    def length(rid):
        r = drv.requests[rid]
        return len(r.prompt) + len(r.generated)

    pool = sorted(pool)
    longest = max(pool, key=length)
    rest = [rid for rid in pool if rid != longest]
    rng = traffic.seed_rng(seed + 2)
    pick = list(rng.choice(len(rest), size=min(n - 1, len(rest)),
                           replace=False)) if rest else []
    return [drv.requests[longest]] + [drv.requests[rest[i]] for i in pick]


def _served_gaps(best, got, reqs) -> List[np.ndarray]:
    """Per request: how far below the best logit each served token's
    logit lies, at the positions that produced served tokens."""
    out = []
    for b, r in enumerate(reqs):
        lo, hi = len(r.prompt) - 1, len(r.prompt) + len(r.generated) - 1
        out.append(np.asarray(best[b, lo:hi] - got[b, lo:hi], np.float64))
    return out


def gap_stats(per_request) -> dict:
    """The numbers a cell's limits file may compare, from each sampled
    request's gaps: over all served tokens together, and for the request
    that reads worst."""
    reqs = [np.asarray(g, np.float64) for g in per_request if len(g)]
    if not reqs:
        return {}
    g = np.concatenate(reqs)
    return {"max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "median_logit_gap": float(np.median(g)),
            "worst_request_mean_logit_gap": float(
                max(r.mean() for r in reqs)),
            "worst_request_median_logit_gap": float(
                max(np.median(r) for r in reqs)),
            "worst_request_q1_logit_gap": float(
                max(np.percentile(r, 25) for r in reqs))}


def verdict(stats: dict, limits: dict):
    """(correct, check): every number that ``limits`` names held to its
    limit; a number that could not be read fails."""
    ok, check = bool(stats), {}
    for name, lim in limits.items():
        value = stats.get(name, math.inf)
        check[name] = {"value": value, "limit": float(lim["limit"])}
        ok = ok and value <= float(lim["limit"])
    return ok, check


def check_outputs(jax, conf: dict, reqs, capacity: int, seed: int,
                  control: bool = False, witness: bool = False) -> dict:
    """Replays each request's prompt and served tokens through the plain
    reference; returns, per request, how far each served token's logit
    lies below the reference's best (``gaps``), with their statistics.
    ``control`` also reads the same gaps for the tokens that the reference
    computed one precision lower puts first (``control_gaps``); ``witness``
    those of the reference computed one precision higher
    (``witness_gaps``)."""
    Reference = reference_class(conf)
    key = jax.random.PRNGKey(weight_seed(seed))
    ref = Reference(conf, key)
    B = len(reqs)
    toks = np.zeros((B, capacity), np.int32)
    plen = np.zeros((B,), np.int32)
    for b, r in enumerate(reqs):
        seq = list(r.prompt) + list(r.generated)
        toks[b, :len(seq)] = seq
        plen[b] = len(r.prompt)
    pick = np.zeros_like(toks)
    pick[:, :-1] = toks[:, 1:]
    x = ref.hidden(toks, plen)
    best, _, got = ref.logit_stats(x, pick)
    gaps = _served_gaps(best, got, reqs)
    out = {"gaps": gaps, "tokens": int(sum(g.size for g in gaps)),
           **gap_stats(gaps)}

    def first_of(other):
        _, first, _ = other.logit_stats(other.hidden(toks, plen), pick)
        _, _, got_other = ref.logit_stats(x, first)
        return _served_gaps(best, got_other, reqs)

    if control:
        low = Reference(conf, key, control=True)
        w_ref, w_low = (r._gen_layer(r.keys[2])["wq"].astype(np.float32)
                        for r in (ref, low))
        out["control_weight_rel_err"] = float(
            np.max(np.abs(w_low - w_ref)) / np.max(np.abs(w_ref)))
        out["control_gaps"] = first_of(low)
    if witness:
        out["witness_gaps"] = first_of(
            Reference(conf, key, activations="float32"))
    return out


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True, control: bool = False,
             witness: bool = False, out=sys.stdout, err=sys.stderr) -> dict:
    """One run of one cell; returns the result object that ``run.py``
    prints as its last line.  ``control`` (and ``witness``) add
    ``readings``: the gaps of the program, of the control and of the
    witness, and the control's own verdict under the cell's limits
    (``control_correct``), reached by the same comparison as the
    program's."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"JAX finds no TPU (platform {dev.platform!r}); "
                         f"the benchmark measures the chip only")
    if len(devices) < cell.chips:
        raise BenchError(f"the cell asks for {cell.chips} chips, JAX sees "
                         f"{len(devices)}")
    peaks = peaks_for(dev.device_kind) if require_tpu else {}
    counter = CompileCount(jax)
    conf, mix = cell.conf, cell.mix
    log = lambda msg: print(f"[bench] {msg}", file=out, flush=True)  # noqa

    t_phase = time.perf_counter()
    cfg, policy, engine = build_system(jax, conf, mix, seed)
    phases = {"build": time.perf_counter() - t_phase}
    kv = sorted({policy.fmt("kv_cache", layer=li).name
                 for li in range(cfg.n_layers)})
    log(f"{cell.name}: {cfg.arch} x{cfg.n_layers} layers, decode_impl "
        f"{policy.decode_impl or cfg.decode_impl}, matmul_impl "
        f"{policy.matmul_impl or cfg.matmul_impl}, kv {','.join(kv)}, "
        f"{mix.slots} slots x {mix.capacity}, page {mix.page_size}, "
        f"prefill_chunk {mix.prefill_chunk}, {mix.loop} loop")
    t_phase = time.perf_counter()
    warm_shapes(engine, mix, cfg.vocab, seed)
    phases["warm_shapes"] = time.perf_counter() - t_phase

    span = (lambda name: jax.profiler.TraceAnnotation(name)) if trace \
        else (lambda name: contextlib.nullcontext())
    drv = Driver(engine, mix, traffic.plan(mix, seed, cfg.vocab), span)
    t_warm = time.perf_counter()
    drv.start(t_warm)
    # the closed loop warms up until every slot has been filled once
    while (time.perf_counter() < t_warm + mix.warmup_s
           or (mix.loop == "closed"
               and engine.stats.admitted - drv.admitted0 < mix.slots)):
        drv.step()
    phases["warm_traffic"] = time.perf_counter() - t_warm

    # ---- the measured window -------------------------------------------
    built = counter.total
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    t_end = t_open + seconds
    t_close = t_open
    while time.perf_counter() < t_end:
        t_close = drv.step()
    built = counter.total - built

    # ---- a --trace 1 run profiles a steady stretch after the window ----
    traced, trace_dir = None, None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        from bench import trace as trace_mod
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=trace_mod.profile_options())
        lo = hi = time.perf_counter()
        while time.perf_counter() < lo + min(mix.trace_s, seconds):
            hi = drv.step()
        jax.profiler.stop_trace()
        traced = (lo, hi)

    # ---- open loop: wait for the first token of every request due in the
    # window (its latency counts the wait) ---------------------------------
    due = [rid for rid, t in drv.due_t.items() if t_open <= t < t_end]
    ttft = None
    if mix.loop == "open":
        deadline = time.perf_counter() + DRAIN_S
        while any(not drv.tok_t[rid] and drv.requests[rid].error is None
                  for rid in due) and time.perf_counter() < deadline:
            drv.step()
        ttft = [drv.tok_t[rid][0] - drv.due_t[rid]
                if drv.tok_t[rid] and drv.requests[rid].error is None
                else math.inf for rid in due]
    failed = sum(1 for r in drv.requests.values() if r.error is not None)
    qwait = [engine.stats.queue_wait_s[rid] for rid in due
             if rid in engine.stats.queue_wait_s]
    late = [drv.late[rid] for rid in due if rid in drv.late]
    steps = drv.steps_in(t_open, t_close)
    tokens = sum(s.new_tokens for s in steps)
    peak = _device_peak(dev)
    log("set-up phases: " + ", ".join(f"{k} {v:.3f} s"
                                      for k, v in phases.items()))
    log(f"window {t_close - t_open:.3f} s, {len(steps)} steps, {tokens} "
        f"tokens, {len(due)} requests due, programs built in the window "
        f"{built}, peak_bytes_in_use {peak}, set-up {setup_s:.3f} s"
        + (f", generator lateness p50 {nearest_rank(late, 50):.6f} s max "
           f"{max(late):.6f} s" if late else ""))

    run = RunData(
        cell=cell.name, slots=mix.slots, loop=mix.loop, setup_s=setup_s,
        t_open=t_open, t_close=t_close, steps=steps, output_tokens=tokens,
        itl_s=drv.gaps_in(t_open, t_close), ttft_s=ttft, queue_wait_s=qwait,
        lateness_s=late, arith=arith.Workload.from_conf(conf), peaks=peaks)

    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        run.trace = trace_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.trace_s = traced[1] - traced[0]
        run.trace_tokens = drv.tokens_in(*traced)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # ---- correctness: the reference over a sample of finished requests ---
    sample = sample_finished(drv, mix.sample, seed, t_open)
    drv = engine = None
    _free_device(jax)
    check = {}
    correct = False
    readings = {}
    attempted = len(due)
    if sample:
        got = check_outputs(jax, conf, sample, mix.capacity, seed,
                            control=control, witness=witness)
        ok, check = verdict(got, cell.limits)
        correct = ok and failed == 0
        if control:
            c_ok, c_check = verdict(gap_stats(got["control_gaps"]),
                                    cell.limits)
            readings = {k: got[k] for k in (
                "gaps", "control_gaps", "control_weight_rel_err")}
            readings.update(control_correct=c_ok, control_check=c_check)
        if witness:
            readings["witness_gaps"] = got["witness_gaps"]
        log(f"checked {len(sample)} requests, {got['tokens']} served "
            f"tokens against the reference: " + ", ".join(
                f"{k} {v}" for k, v in gap_stats(got["gaps"]).items()))
    check["failed_requests"] = {"value": failed, "limit": 0}
    for name, c in check.items():
        print(f"check: {name} {c['value']} limit {c['limit']}", file=err,
              flush=True)
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = check
    if control or witness:
        result["readings"] = readings
    return result
