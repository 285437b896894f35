"""Continuous-batching scheduler: admission, chunked prefill interleaved
with decode, growth, and LIFO eviction over one shared page pool.

The engine unifies the two serving loops the old ``launch/serve.py``
carried (contiguous fixed-capacity vs. paged): every sequence now lives in
a block-table page pool, and contiguous attention backends read it through
the gather bridge in ``models/attention.py`` -- so any registry spelling
serves through one code path.

Each engine step does, in order:

1. **Deadlines** -- requests (queued or slotted) past their per-request
   step deadline fail with a classified
   :class:`~repro.engine.resilience.DeadlineExceeded` result (slot
   released, never a hang).  Deadlines count engine steps since the
   request was *enqueued* (for :meth:`Engine.run` that is run start; for
   the async router it is submission time).
2. **Admission** -- while a prefill worker is idle and a slot is free, pop
   the queue head if ``PagePool.can_admit`` says its KV (plus one decode
   token) fits, and reserve its pages up front.  With ``prefill_workers
   == 1`` (the default) this is the classic single-prompt-in-flight loop;
   the router runs 2+ workers, each prefilling its own prompt through its
   own transport.
3. **One prefill chunk per in-flight prompt** -- every active
   :class:`~repro.engine.worker.PrefillTask` advances by one chunk
   (default: one page of tokens) via its worker's
   :class:`~repro.engine.worker.PrefillWorker`; finished pages move
   through that worker's :mod:`~repro.engine.transport` into the decode
   pool.  Because only a chunk runs per step, a long prompt never stalls
   the decode batch below.
4. **Growth / eviction** -- every decoding slot needs a mapped page for
   its next token; when the pool runs dry the most recently admitted
   sequence (decoding *or* mid-prefill) is evicted back to the queue head
   and its pages reused immediately (LIFO: the oldest admitted sequence
   always finishes, so the loop makes progress).  A request evicted more
   than ``max_requeues`` times fails as a
   :class:`~repro.engine.resilience.DeadLetterRequest`.
5. **One batched decode step** (or speculation round) -- every
   mid-prefill slot's block-table row is masked to -1 on the device, so
   its in-progress KV is invisible: ``append_decode`` drops the write and
   its length does not advance; the garbage logits for those rows are
   discarded host-side.

**Serving mode.**  :meth:`Engine.run` drives a fixed request list to
completion; the async router (:mod:`repro.engine.router`) instead feeds
the same loop incrementally through :meth:`Engine.enqueue` /
:meth:`Engine.step` / :meth:`Engine.finalize` -- ``step()`` returns the
requests that reached a terminal state (done or classified failure) so
the router can resolve their futures without polling.

**Self-healing** (see docs/resilience.md for the full recovery matrix):
batched steps run through a retry wrapper (transient exceptions re-run the
pure jitted step bit-identically); every step's logits carry an in-jit
NaN/Inf guard whose verdict rides the existing single host transfer -- a
non-finite slot has its pages quarantined (:meth:`~repro.kernels.
paged_cache.PagePool.quarantine_slot`, pages never recycled) and the
request replays through :func:`~repro.engine.reference.
synchronous_generate`, the oracle the engine is already pinned
bit-identical to; a :class:`~repro.engine.resilience.CircuitBreaker`
drops persistent draft-model divergence back to plain batched decode
(draft KV kept warm by a shadow step) and re-probes after a cooldown; and
an optional wall-clock watchdog turns a wedged step into a classified
:class:`~repro.engine.resilience.WatchdogTimeout`.  Deterministic fault
schedules (:class:`~repro.engine.faults.FaultPlan`) exercise every one of
these paths: under a plan of recoverable faults the greedy tokens are
bit-identical to the fault-free run.

Per-step observability flows through :class:`~repro.engine.stats.
EngineStats` (queue depth, pool occupancy / fragmentation, TTFT vs
queue-wait, decode tokens/s, per-worker prefill utilization,
fault/recovery counters) as JSON lines.  The summary line and the stream
close run in a ``finally`` (:meth:`Engine.finalize`), so even a run that
raises a classified error leaves a complete, closed JSONL stream behind.
"""
from __future__ import annotations

import time
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import paged_cache

from . import resilience, tracing
from .faults import FaultInjector, FaultPlan, SimulatedFault
from .reference import synchronous_generate
from .stats import EngineStats
from .transport import ColocatedTransport
from .worker import DecodeWorker, PrefillTask, PrefillWorker


def _host(tree):
    """The engine loop's single device->host synchronization point.

    Everything the host needs from a step -- the argmax'd next-token ids
    plus the NaN/Inf guard verdicts, or a speculation round's (targets,
    emit counts, accept counts, guard verdicts) -- crosses in ONE explicit
    ``jax.device_get`` per step, instead of one implicit transfer per
    sequence (the old ``int(nxt[si])`` loop pulled the whole logits row
    once per slot).  Tests monkeypatch this to count transfers and run the
    loop under ``jax.transfer_guard_device_to_host("disallow")`` to prove
    no implicit transfer remains.  Its span is the only one in which the
    host waits for the chip."""
    with tracing.span(tracing.DEVICE_WAIT):
        return jax.device_get(tree)


class Request:
    def __init__(self, rid: int, prompt: List[int], max_new: int,
                 deadline_steps: Optional[int] = None):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.deadline_steps = deadline_steps  # overrides the engine default
        self.generated: List[int] = []
        self.done = False
        self.evictions = 0
        self.error: Optional[Exception] = None  # classified EngineError
        self.enqueued_step = 0     # engine step at enqueue (deadline base)

    @property
    def failed(self) -> bool:
        return self.error is not None

    def reset(self):
        """Requeued after eviction: generation restarts from the prompt.

        Also clears any stale classified error -- a request retried after
        a transient failure must not read as ``failed`` once it requeues
        (the terminal state is whatever THIS attempt produces)."""
        self.generated = []
        self.evictions += 1
        self.error = None


def shard_pool(cache, mesh):
    """Place a page pool with its page axis split over the mesh's ``model``
    axis (tables and lengths replicated) -- the layout the sharded
    wrappers' ``shard_map`` reads, so no step reshards the pool."""
    pages = NamedSharding(mesh, P("model"))
    rep = NamedSharding(mesh, P())
    return cache._replace(
        k_pool=jax.device_put(cache.k_pool, pages),
        v_pool=jax.device_put(cache.v_pool, pages),
        block_tables=jax.device_put(cache.block_tables, rep),
        seq_lens=jax.device_put(cache.seq_lens, rep))


def _insert_slot(all_states, one_states, slot: int, n_slots: int):
    """Write a 1-sequence state pytree into row ``slot`` of the batched
    state (arrays without a leading slots axis are taken wholesale)."""
    return jax.tree.map(
        lambda all_s, one: all_s.at[slot:slot + 1].set(one)
        if hasattr(all_s, "at") and all_s.ndim and
        all_s.shape[0] == n_slots else one,
        all_states, one_states)


class Engine:
    """Paged continuous-batching engine over a fixed number of slots.

    prefill_chunk: tokens prefilled per engine step.  ``None`` defaults to
    one page (the transient staging buffer is then one page per attention
    layer); ``0`` forces whole-prompt prefill (the old serve.py behavior,
    and the only mode for prefix-LM archs).

    transport / prefill_workers: ``transport`` may be a single transport
    (the classic one-prompt-in-flight engine) or a sequence of them -- one
    per concurrent prefill worker.  ``prefill_workers`` defaults to the
    number of transports; when both are given they must agree (every
    worker owns exactly one transport, because a
    :class:`~repro.engine.transport.StreamedTransport` carries a private
    single-slot source pool that cannot serve two prompts at once).

    Resilience knobs (all optional; docs/resilience.md):

    fault_plan: a :class:`~repro.engine.faults.FaultPlan` to inject
        deterministically during the run (None = no faults; the injector
        hooks are no-ops).
    deadline_steps: default per-request deadline in *engine steps* from
        the request's enqueue (deterministic, unlike wall clock); a
        request's own ``deadline_steps`` overrides it.  Expired requests
        fail with a classified ``DeadlineExceeded`` result.
    max_requeues: evictions a request survives before failing as a
        ``DeadLetterRequest`` (None = requeue forever, the old behavior).
    retry_policy: backoff schedule for step retries and transport
        refetches.
    breaker: speculative :class:`~repro.engine.resilience.CircuitBreaker`
        (defaults to one with stock thresholds when speculation is on).
    watchdog_s / watchdog_limit: wall-clock budget per engine step; after
        ``watchdog_limit`` consecutive over-budget steps the run raises a
        classified ``WatchdogTimeout`` (None = watchdog off).
    mesh: the ``"model"`` mesh a sharded (``flash_shmap+`` / ``ring+``)
        spelling serves under.  Params are replicated over it and every
        page pool is placed with its page axis sharded; a mesh the
        wrappers could not shard over raises instead of serving on one
        device.  None (the default) keeps everything on device 0.
    """

    def __init__(self, model, cfg, policy, params, *, slots: int,
                 capacity: int,
                 page_size: int = paged_cache.DEFAULT_PAGE_SIZE,
                 pool_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 transport=None, prefill_workers: Optional[int] = None,
                 stats: Optional[EngineStats] = None,
                 speculative=None, calibration_tap=None,
                 fault_plan: Optional[FaultPlan] = None,
                 deadline_steps: Optional[int] = None,
                 max_requeues: Optional[int] = None,
                 retry_policy: Optional[resilience.RetryPolicy] = None,
                 breaker: Optional[resilience.CircuitBreaker] = None,
                 watchdog_s: Optional[float] = None,
                 watchdog_limit: int = 3,
                 mesh=None):
        self.model, self.cfg, self.policy = model, cfg, policy
        self.calibration_tap = calibration_tap
        self.params = params
        self.slots = slots
        self.capacity = capacity
        if cfg.encoder_layers:
            raise ValueError(
                f"arch {cfg.arch}: the serving engine is decoder-only "
                f"(enc-dec decode needs per-step encoder context)")
        self.attn_layers = [li for li, k in enumerate(cfg.attn_pattern)
                            if k == "attn"]
        # what each attention layer caches per token (K and V rows, or
        # one latent row): its pool's shape and its bytes per token
        self.cache_specs = {li: model.cache_spec(li)
                            for li in self.attn_layers}
        if any(spec.latent for spec in self.cache_specs.values()):
            self._check_latent(prefill_chunk, speculative, transport, mesh)
        if (self.attn_layers and cfg.window is not None
                and capacity > cfg.window):
            raise ValueError(
                f"arch {cfg.arch}: --capacity {capacity} exceeds the "
                f"sliding window {cfg.window}; the paged engine keeps every "
                f"cached token, which matches windowed attention only while "
                f"capacity <= window -- lower --capacity")
        page = paged_cache.validate_page_size(page_size)
        self.page = page
        self.pages_per_seq = -(-capacity // page)
        if pool_pages is None:
            self.num_pages = slots * self.pages_per_seq
        elif pool_pages > 0:
            self.num_pages = pool_pages
        else:
            raise ValueError(
                f"--pool-pages must be positive, got {pool_pages}")
        self.pool = paged_cache.PagePool(self.num_pages, page, slots,
                                         self.pages_per_seq)
        self.stats = stats if stats is not None else EngineStats()
        self.device = jax.devices()[0]
        self.mesh = mesh
        if mesh is not None:
            self._check_mesh(mesh)
            self.params = jax.device_put(params, NamedSharding(mesh, P()))

        self.injector = FaultInjector(fault_plan, self.stats)
        self.retry_policy = (retry_policy if retry_policy is not None
                             else resilience.RetryPolicy())
        self.deadline_steps = deadline_steps
        self.max_requeues = max_requeues
        self.watchdog_s = watchdog_s
        self.watchdog_limit = int(watchdog_limit)

        states = model.init_state(slots, page, policy)
        for li in self.attn_layers:
            # each attention layer owns its own pool, so the KV format may
            # vary by layer depth (tuned policies bind layers.{li}.kv_cache)
            states[li] = paged_cache.init_pool(
                self.cache_specs[li], slots, self.num_pages, page,
                self.pages_per_seq, policy.dtype("kv_cache", layer=li))
            if mesh is not None:
                states[li] = shard_pool(states[li], mesh)
        self.states = states

        if transport is None:
            n_workers = 1 if prefill_workers is None else int(prefill_workers)
            transports = [ColocatedTransport() for _ in range(n_workers)]
        elif isinstance(transport, (list, tuple)):
            transports = list(transport)
            n_workers = (len(transports) if prefill_workers is None
                         else int(prefill_workers))
        else:
            transports = [transport]
            n_workers = 1 if prefill_workers is None else int(prefill_workers)
        if n_workers < 1:
            raise ValueError(f"prefill_workers must be >= 1, got {n_workers}")
        if len(transports) != n_workers:
            raise ValueError(
                f"prefill_workers={n_workers} needs exactly that many "
                f"transports (each worker owns one source pool), got "
                f"{len(transports)} -- pass transport=[...] with one entry "
                f"per worker")
        if len(set(map(id, transports))) != len(transports):
            raise ValueError(
                "the same transport instance appears twice in the worker "
                "list; each prefill worker needs its own transport")
        self.transports = transports
        self.transport = transports[0]  # back-compat single-worker alias
        self.n_prefill_workers = n_workers
        for tr in self.transports:
            tr.setup(self)
        chunk_tokens = page if prefill_chunk is None else prefill_chunk
        self.prefill_workers = [
            PrefillWorker(model, cfg, policy, tr, self.stats,
                          chunk_tokens=chunk_tokens)
            for tr in self.transports]
        self.prefill_worker = self.prefill_workers[0]
        self.decode_worker = DecodeWorker(model, policy)
        self.kv_bytes_per_token = sum(
            self.cache_specs[li].bytes_per_token(
                policy.dtype("kv_cache", layer=li))
            for li in self.attn_layers)
        self.spec = speculative
        if self.spec is not None:
            self.spec.setup(self)
        self.breaker = breaker if breaker is not None else (
            resilience.CircuitBreaker() if speculative is not None
            else None)
        self._zero_mask = jnp.zeros((slots,), jnp.bool_)
        self.summary: Optional[dict] = None

        # serving-loop state: run() and the async router drive the same
        # incremental step machine (enqueue -> step* -> finalize)
        self._queue: List[Request] = []
        self._slots: List[Optional[Request]] = [None] * slots
        self._admitted_at = [0] * slots  # admission counter per slot
        self._admissions = 0             # (LIFO eviction: newest first)
        self._tasks: List[PrefillTask] = []  # in-flight prompts, <= workers
        self._tokens = jnp.zeros((slots, 1), jnp.int32)
        self._terminal = 0        # requests that reached a terminal state
        self._step_done: List[Request] = []  # terminal this step
        self._engine_step = 0
        self._progressed = False  # non-step progress (failures) this step
        self._new_tokens = 0
        self._wd_over = 0         # consecutive over-budget steps (watchdog)
        self._finalized = False

    # ------------------------------------------------------------------ utils
    def _check_mesh(self, mesh) -> None:
        """A sharded spelling needs a model axis of > 1 device that divides
        the axis its wrapper shards (the pool's pages for a paged base, the
        gathered sequence for contiguous bases).  The wrappers themselves
        run unsharded when it does not, which would serve on one device
        while the caller asked for many -- so refuse here, loudly."""
        n = mesh.shape.get("model", 1)
        if n < 2:
            raise ValueError(
                f"a sharded decode spelling needs a 'model' mesh axis of at "
                f"least 2 devices, got {dict(mesh.shape)}")
        if self.num_pages % n or (self.pages_per_seq * self.page) % n:
            raise ValueError(
                f"pool pages ({self.num_pages}) and per-slot capacity "
                f"({self.pages_per_seq * self.page} tokens) must both divide "
                f"over the {n}-device 'model' axis; adjust --pool-pages / "
                f"--capacity")

    @staticmethod
    def _check_latent(prefill_chunk, speculative, transport, mesh) -> None:
        """A latent (MLA) pool is written by whole-prompt prefill and read
        by one-token decode on one device; everything else is refused."""
        why = []
        if prefill_chunk != 0:
            why.append("chunked prefill (pass prefill_chunk=0 for "
                       "whole-prompt prefill)")
        if speculative is not None:
            why.append("speculative decoding")
        transports = transport if isinstance(transport, (list, tuple)) \
            else [transport]
        if any(t is not None and not isinstance(t, ColocatedTransport)
               for t in transports):
            why.append("a streamed page transport")
        if mesh is not None:
            why.append("a sharded decode mesh")
        if why:
            raise ValueError(
                "latent attention (MLA) layers do not support "
                + ", ".join(why))

    def _push_tables(self, mask_slots=()) -> None:
        """Mirror the host block tables onto the device; ``mask_slots``
        hides the mid-prefill slots from the decode step (-1 rows drop
        ``append_decode`` writes and keep their lengths frozen)."""
        with tracing.span(tracing.PUSH_TABLES):
            tables = self.pool.tables
            if mask_slots:
                tables = tables.copy()
                for si in mask_slots:
                    tables[si] = -1
            for li in self.attn_layers:
                self.states[li] = paged_cache.set_block_tables(
                    self.states[li], tables)
            if self.spec is not None:
                dtables = self.pool.ns_tables(self.spec.NS)
                if mask_slots:
                    dtables = dtables.copy()
                    for si in mask_slots:
                        dtables[si] = -1
                self.spec.push_tables(dtables)

    def _init_pstates(self, transport):
        """B=1 recurrent-layer states for a fresh prompt (attn -> None:
        attention KV goes straight into the page pool)."""
        one = self.model.init_state(1, self.page, self.policy)
        one = [None if k == "attn" else s
               for k, s in zip(self.cfg.attn_pattern, one)]
        return transport.to_prefill(one)

    def _fault_mask(self, kind: str, decoding: List[int]):
        """Injected per-slot poison mask for the jitted step (the cached
        all-False mask when nothing is armed, so the common case costs
        nothing and compiles once)."""
        mask = self.injector.slot_mask(kind, decoding, self.slots)
        return self._zero_mask if mask is None else jnp.asarray(mask)

    def _check_feasible(self, r: Request) -> None:
        worst = self.pool.pages_for(len(r.prompt) + r.max_new)
        total = worst * (2 if self.spec is not None else 1)
        if worst > self.pages_per_seq or total > self.num_pages:
            raise ValueError(
                f"a single request needs {total} pages (prompt "
                f"{len(r.prompt)} + max-new {r.max_new}, page size "
                f"{self.page}"
                + (", x2 for the draft namespace"
                   if self.spec is not None else "")
                + f") but the pool offers min({self.pages_per_seq} "
                f"per-seq, {self.num_pages} total); raise "
                f"--capacity/--pool-pages")

    def _deadline_of(self, r: Request) -> Optional[int]:
        return (r.deadline_steps if r.deadline_steps is not None
                else self.deadline_steps)

    def _task_for_slot(self, si: int) -> Optional[PrefillTask]:
        for task in self._tasks:
            if task.slot == si:
                return task
        return None

    # ----------------------------------------------------- serving interface
    def enqueue(self, r: Request) -> Request:
        """Admit ``r`` into the serving queue (feasibility-checked: an
        impossible request is rejected loudly here, at submission, not as
        a mid-run stall).  The deadline clock starts now."""
        self._check_feasible(r)
        r.enqueued_step = self._engine_step
        self.stats.note_enqueued(r.rid)
        self._queue.append(r)
        return r

    def has_work(self) -> bool:
        """True while any request is queued, prefilling, or decoding."""
        return bool(self._queue or self._tasks
                    or any(s is not None for s in self._slots))

    def finalize(self) -> Optional[dict]:
        """Emit the summary line and close the stats stream.  Idempotent;
        run() calls it in a ``finally`` so the JSONL stream ends with a
        summary (and a closed file handle) even when the loop raises a
        classified error."""
        if not self._finalized:
            self._finalized = True
            self.summary = self.stats.summary(
                kv_bytes_per_token=self.kv_bytes_per_token,
                faults_unfired=len(self.injector.pending))
            self.stats.close()
        return self.summary

    # --------------------------------------------------------- step internals
    def _fail_request(self, r: Request, err: Exception) -> None:
        """Classified failure result: the request completes with
        ``r.error`` set, never hangs the loop."""
        r.error = err
        self._terminal += 1
        self._step_done.append(r)
        self._progressed = True
        self.stats.note_failure(getattr(type(err), "kind", "engine"))

    def _release_slot_state(self, si: int) -> None:
        """Free ``si`` everywhere: pool pages (all namespaces), device
        table rows, draft rows, and any in-flight prefill."""
        with tracing.span(tracing.RELEASE, rid=self._slots[si].rid):
            self.pool.free_slot(si)  # frees BOTH namespaces atomically
            for li in self.attn_layers:
                self.states[li] = paged_cache.release_slot(self.states[li],
                                                           si)
            if self.spec is not None:
                self.spec.release_slot(si)
            task = self._task_for_slot(si)
            if task is not None:
                self.transports[task.worker].abort(self, task)
                self._tasks.remove(task)
            self._slots[si] = None

    def _evict(self, si: int) -> None:
        # an eviction IS step progress: the requeued request becomes
        # admissible next iteration (it may have emptied the decode
        # batch this one, so the stall guard must not fire)
        r = self._slots[si]
        self._release_slot_state(si)
        r.reset()
        self._progressed = True
        self.stats.note_eviction()
        if (self.max_requeues is not None
                and r.evictions > self.max_requeues):
            self._fail_request(r, resilience.DeadLetterRequest(
                f"request {r.rid} evicted {r.evictions} times "
                f"(max_requeues={self.max_requeues}); failing instead "
                f"of thrashing the pool"))
        else:
            self._queue.insert(0, r)

    def _newest_active(self) -> Optional[int]:
        active = [si for si in range(self.slots)
                  if self._slots[si] is not None]
        return max(active, key=lambda si: self._admitted_at[si]) \
            if active else None

    def _finish_slot(self, si: int) -> None:
        r = self._slots[si]
        r.done = True
        self._terminal += 1
        self._step_done.append(r)
        self.stats.note_completed()
        self._release_slot_state(si)

    def _quarantine_and_replay(self, si: int, why: str) -> int:
        """The NaN/Inf guard tripped for ``si``: pull its pages out of
        circulation (suspect memory is never recycled) and regenerate
        the request through the synchronous oracle -- which the
        engine's tokens are pinned bit-identical to, so recovery
        preserves the determinism contract.  -> tokens emitted now."""
        r = self._slots[si]
        with tracing.span(tracing.RELEASE, rid=r.rid):
            pages = self.pool.quarantine_slot(si)
            for li in self.attn_layers:
                self.states[li] = paged_cache.release_slot(self.states[li],
                                                           si)
            if self.spec is not None:
                self.spec.release_slot(si)
            self._slots[si] = None
        self.stats.note_quarantine(pages)
        prev = len(r.generated)
        out = synchronous_generate(
            self.model, self.cfg, self.policy, self.params,
            [r.prompt], max_new=r.max_new,
            capacity=max(self.capacity, len(r.prompt) + r.max_new))
        r.generated = list(out[0])
        r.done = True
        self._terminal += 1
        self._step_done.append(r)
        self._progressed = True
        self.stats.note_completed()
        self.stats.note_first_token(r.rid)
        self.stats.note_decode_tokens(len(r.generated) - prev)
        return len(r.generated) - prev

    def _complete_prefill(self, task: PrefillTask) -> None:
        """A prompt's last chunk just landed: insert its recurrent-layer
        states, read its first token (one host transfer), and hand the
        slot to the decode batch."""
        r, si = task.request, task.slot
        with tracing.span(tracing.FIRST_TOKEN, rid=r.rid):
            tr = self.transports[task.worker]
            for li, kind in enumerate(self.cfg.attn_pattern):
                if kind != "attn":
                    self.states[li] = _insert_slot(
                        self.states[li], tr.to_decode(task.pstates[li]),
                        si, self.slots)
            am, fin, counts = _host((jnp.argmax(task.logits[0, -1]),
                                     jnp.isfinite(task.logits[0, -1]).all(),
                                     task.counts))
            if counts is not None:
                self.stats.note_moe("prefill", *counts)
            if not bool(fin):
                self._new_tokens += self._quarantine_and_replay(
                    si, "prefill logits")
                return
            nxt = int(am)
            r.generated.append(nxt)
            self.stats.note_first_token(r.rid)
            self.stats.note_decode_tokens(1)
            self._new_tokens += 1
            self._tokens = self._tokens.at[si, 0].set(nxt)
            if self.spec is not None:
                # the target prompt just landed; write the draft's KV for
                # it into the draft-namespace pages (tables were pushed at
                # the top of the prefill section)
                self.spec.prefill_prompt(si, r.prompt)

    # -------------------------------------------------------------------- step
    def step(self) -> List[Request]:
        """One engine iteration over the current queue/slots; returns the
        requests that reached a terminal state (done or classified
        failure) during this step.  The whole iteration is the root span
        (:mod:`~repro.engine.tracing`)."""
        with tracing.step_span(tracing.STEP, step_num=self._engine_step + 1):
            return self._step()

    def _step(self) -> List[Request]:
        n = self.slots
        self._step_done = []
        step = self._engine_step + 1    # 1-based, matches stats records
        self.injector.begin_step(step)
        t_step = time.perf_counter()
        self._new_tokens = 0
        self._progressed = False
        # ---- deadlines: expired requests fail classified, never hang --
        for r in [q for q in self._queue]:
            dl = self._deadline_of(r)
            if dl is not None and self._engine_step - r.enqueued_step >= dl:
                self._queue.remove(r)
                self._fail_request(r, resilience.DeadlineExceeded(
                    f"request {r.rid} still queued after its "
                    f"{dl}-step deadline"))
        for si in range(n):
            r = self._slots[si]
            dl = self._deadline_of(r) if r is not None else None
            if dl is not None and self._engine_step - r.enqueued_step >= dl:
                self._release_slot_state(si)
                self._fail_request(r, resilience.DeadlineExceeded(
                    f"request {r.rid} exceeded its {dl}-step deadline "
                    f"({len(r.generated)}/{r.max_new} tokens)"))
        # ---- admission: one prompt in flight per idle prefill worker ----
        with tracing.span(tracing.ADMIT):
            while self._queue and len(self._tasks) < self.n_prefill_workers:
                si = next((i for i in range(n) if self._slots[i] is None),
                          None)
                if si is None:
                    break
                need = len(self._queue[0].prompt)
                needs = ((need + 1, need) if self.spec is not None
                         else (need + 1,))
                if not self.pool.can_admit(*needs):
                    break
                r = self._queue.pop(0)
                ok = self.pool.allocate(si, need)
                if self.spec is not None:
                    ok = ok and self.pool.allocate(si, need,
                                                   ns=self.spec.NS)
                assert ok, (si, need)  # can_admit held above
                self._slots[si] = r
                self._admissions += 1
                self._admitted_at[si] = self._admissions
                self.stats.note_admitted(r.rid)
                if self.calibration_tap is not None:
                    # live-traffic tap: admitted prompts feed the serve-
                    # time precision tuner's calibration reservoir
                    self.calibration_tap.observe(r.prompt)
                busy = {t.worker for t in self._tasks}
                wi = next(w for w in range(self.n_prefill_workers)
                          if w not in busy)
                task = PrefillTask(r, si, need, worker=wi)
                task.pstates = self._init_pstates(self.transports[wi])
                self.transports[wi].begin(self, task)
                self._tasks.append(task)
        # ---- one prefill chunk per task (decode below still runs) -------
        ran_chunks = 0
        if self._tasks:
            self._push_tables()
            for task in list(self._tasks):
                ran_chunks += 1
                self.stats.note_prefill_chunk(task.worker)
                tr = self.transports[task.worker]
                worker = self.prefill_workers[task.worker]
                with tracing.span(tracing.PREFILL, rid=task.request.rid,
                                  tokens=worker.next_tokens(task)):
                    try:
                        view, vslot = tr.prefill_view(self, task)
                        view = worker.step(task, view, vslot)
                        with tracing.span(tracing.ABSORB):
                            tr.absorb(self, task, view)
                        if task.done:
                            with tracing.span(tracing.FINISH):
                                tr.finish(self, task)
                    except resilience.TransportError:
                        # checksum refetch exhausted: the page handoff
                        # cannot be trusted, so recompute the request from
                        # its prompt (bounded by max_requeues like any
                        # other eviction)
                        self._evict(task.slot)
                        continue
                    if task.done:
                        self._tasks.remove(task)
                        self._complete_prefill(task)
        # ---- growth: every decoding slot needs a mapped page for its
        # next token; evict LIFO when the pool runs dry ------------------
        use_spec = (self.spec is not None
                    and self.breaker.allows(step))
        task_slots = {t.slot for t in self._tasks}
        with tracing.span(tracing.GROW):
            for si in range(n):
                if self._slots[si] is None or si in task_slots:
                    continue
                while self._slots[si] is not None:
                    L = int(self.pool.lens[si])
                    if use_spec:
                        # grow by this round's worst case in BOTH
                        # namespaces: k appends, clamped to what the
                        # request can still emit
                        gi = min(self.spec.k, self._slots[si].max_new
                                 - len(self._slots[si].generated))
                        ok = (self.pool.ensure_capacity(si, L + gi)
                              and self.pool.ensure_capacity(
                                  si, L + gi, ns=self.spec.NS))
                    elif self.spec is not None:
                        # degraded (breaker-open) step: one token, but the
                        # draft shadow append needs its page too
                        ok = (self.pool.ensure_capacity(si, L + 1)
                              and self.pool.ensure_capacity(
                                  si, L + 1, ns=self.spec.NS))
                    else:
                        ok = self.pool.ensure_capacity(si, L + 1)
                    if ok and self.injector.pool_exhausted():
                        ok = False  # injected exhaustion: walk the normal
                    if ok:          # eviction/requeue path below
                        break
                    victim = self._newest_active()
                    self._evict(victim)
                    task_slots = {t.slot for t in self._tasks}
                    if victim == si:
                        break
        # ---- one batched decode step over the page pool ---------------
        decoding = [si for si in range(n)
                    if self._slots[si] is not None and si not in task_slots]
        if decoding and use_spec:
            # ---- one speculation round: k draft steps + 1 verify -----
            self._push_tables(mask_slots=task_slots)
            nan_mask = self._fault_mask("nan_logits", decoding)
            div_mask = self._fault_mask("draft_div", decoding)

            def _spec_call():
                self.injector.maybe_raise()
                with tracing.span(tracing.SPEC_ROUND):
                    return self.spec.round(
                        self.params, self._tokens, self.states,
                        nan_mask=nan_mask, div_mask=div_mask)

            (tgt_d, m_d, acc_d, pending, bad_d,
             self.states) = resilience.with_retries(
                _spec_call, self.retry_policy, self.stats,
                retriable=(SimulatedFault,), what="speculation round")
            self.stats.note_target_step()
            tgt, m, acc, bad = _host((tgt_d, m_d, acc_d, bad_d))
            proposed = accepted = 0
            with tracing.span(tracing.EMIT):
                for si in decoding:
                    if bool(bad[si]):
                        self._new_tokens += self._quarantine_and_replay(
                            si, "verify logits")
                        continue
                    r = self._slots[si]
                    L = int(self.pool.lens[si])
                    gi = min(self.spec.k, r.max_new - len(r.generated))
                    # positions >= gi had no mapped page (growth clamped
                    # to gi); the device rollback took the same min, so
                    # clamp the host-side view identically
                    mi = min(int(m[si]), gi)
                    r.generated.extend(int(t) for t in tgt[si, :mi])
                    self.stats.note_decode_tokens(mi)
                    self._new_tokens += mi
                    proposed += gi
                    accepted += min(int(acc[si]), gi)
                    self.pool.truncate(si, L + mi)
                    self.pool.truncate(si, L + mi, ns=self.spec.NS)
                    if len(r.generated) >= r.max_new:
                        self._finish_slot(si)
                self.stats.note_spec_round(proposed=proposed,
                                           accepted=accepted)
                self.breaker.record(step=step, proposed=proposed,
                                    accepted=accepted, stats=self.stats)
                self._tokens = pending
        elif decoding:
            self._push_tables(mask_slots=task_slots)
            nan_mask = self._fault_mask("nan_logits", decoding)

            def _decode_call():
                self.injector.maybe_raise()
                with tracing.span(tracing.DECODE_DISPATCH):
                    return self.decode_worker.step(
                        self.params, self._tokens, self.states, nan_mask)

            nxt, bad_d, self.states = resilience.with_retries(
                _decode_call, self.retry_policy, self.stats,
                retriable=(SimulatedFault,), what="decode step")
            self.stats.note_target_step()
            if self.spec is not None:
                # breaker open: plain decode, but keep the draft KV in
                # lockstep so the half-open probe can accept again
                self.spec.shadow_step(self._tokens)
                self.stats.note_degraded_step()
            nxt_h, bad, counts = _host((nxt, bad_d,
                                        self.decode_worker.counts))
            self.stats.note_moe("decode", *counts)
            with tracing.span(tracing.EMIT):
                for si in decoding:
                    if bool(bad[si]):
                        self._new_tokens += self._quarantine_and_replay(
                            si, "decode logits")
                        continue
                    r = self._slots[si]
                    self.pool.note_decode_step(si)
                    if self.spec is not None:
                        self.pool.note_decode_step(si, ns=self.spec.NS)
                    r.generated.append(int(nxt_h[si]))
                    self.stats.note_decode_tokens(1)
                    self._new_tokens += 1
                    if len(r.generated) >= r.max_new:
                        self._finish_slot(si)
                self._tokens = nxt[:, None]
        elif self.has_work() and not ran_chunks and not self._progressed:
            # pre-run feasibility makes this unreachable without page
            # quarantine; with it, a loud classified error beats a hang
            raise resilience.EngineError(
                "engine stalled: queue non-empty but no slot "
                "admissible and no sequence decoding (quarantined "
                f"pages: {len(self.pool.quarantined)})")
        self._engine_step += 1
        with tracing.span(tracing.RECORD):
            rec = self.stats.step_record(
                step=self._engine_step, queue_depth=len(self._queue),
                prefilling=ran_chunks, decoding=len(decoding),
                new_tokens=self._new_tokens, pool_stats=self.pool.stats())
            moe = {k: v for k, v in rec.items() if k.startswith("moe_")}
            if any(moe.values()):
                with tracing.counters(**moe):
                    pass
        if self.watchdog_s is not None:
            if time.perf_counter() - t_step > self.watchdog_s:
                self.stats.note_watchdog_trip()
                self._wd_over += 1
                if self._wd_over >= self.watchdog_limit:
                    raise resilience.WatchdogTimeout(
                        f"{self._wd_over} consecutive engine steps over "
                        f"the {self.watchdog_s}s watchdog budget")
            else:
                self._wd_over = 0
        return self._step_done

    # -------------------------------------------------------------------- run
    def run(self, reqs: List[Request]) -> List[Request]:
        """Drive a fixed request list to completion (the synchronous
        entry point; the async router uses enqueue/step/finalize
        directly)."""
        for r in reqs:
            self._check_feasible(r)  # all-or-nothing, before any enqueue
        for r in reqs:
            self.enqueue(r)
        base = self._terminal
        try:
            while self._terminal - base < len(reqs):
                self.step()
        finally:
            self.finalize()
        return reqs
