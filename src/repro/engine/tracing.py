"""Host spans of the serving engine, on the profiler's own clock.

Every phase of :meth:`~repro.engine.scheduler.Engine.step` runs inside a
``jax.profiler.TraceAnnotation``.  With no profiler active a span costs
under a microsecond (0.2-0.9 us on a TPU v5e host); under
``jax.profiler.trace`` each one is an event in the same XPlane, on the same
clock, as the device's ``XLA Ops``, so a gap in which the chip idles can be
put down to the innermost span that covers it.  Spans sit at phase
granularity: a loop over slots or layers gets one span around it, never one
per iteration, so the number of spans per step does not grow with slots or
layers.  Where a span has a request, it records its id (``rid``); prefill
spans also record their token count.

The counters stay in :class:`~repro.engine.stats.EngineStats`; the spans
are the timing.  A step's non-zero MoE counters are also written into the
profile, inside ``engine.record``, as one event whose *name* carries them
(:func:`counters`): a span's keyword arguments become XPlane statistics,
which a reader of event names and times does not see.  The names
``engine_step`` and ``bench_enqueue`` belong to the benchmark harness's own
spans and are never used here.
"""
import jax

span = jax.profiler.TraceAnnotation
step_span = jax.profiler.StepTraceAnnotation

COUNTERS = "counters"                  # prefix of a counter event's name


def counters(**values):
    """An event named ``counters k=v k=v ...`` (sorted keys)."""
    return span(" ".join([COUNTERS] + [f"{k}={v}" for k, v in
                                       sorted(values.items())]))

STEP = "engine.step"                  # root: one engine iteration
ADMIT = "engine.admit"                # pool allocation, transport.begin
PREFILL = "engine.prefill"            # one task's chunk or whole prompt
PREFILL_DISPATCH = "prefill.dispatch"  # batch build, prefill program call
POOL_WRITE = "prefill.pool_write"     # one donated write into every pool
ABSORB = "transport.absorb"           # page handoff into the decode pool
FINISH = "transport.finish"           # ragged tail, device sequence length
FIRST_TOKEN = "prefill.first_token"   # the prompt's first token to host
GROW = "engine.grow"                  # page growth and LIFO eviction
PUSH_TABLES = "engine.push_tables"    # block tables to every layer's pool
DECODE_DISPATCH = "decode.dispatch"   # decode program call
SPEC_ROUND = "spec.round"             # speculation round call
SPEC_SHADOW = "spec.shadow"           # degraded-mode draft step
DEVICE_WAIT = "engine.device_wait"    # the device-to-host synchronisation
EMIT = "engine.emit"                  # per-slot token append and finish
RELEASE = "engine.release"            # slot pages and device rows freed
RECORD = "engine.record"              # EngineStats step record

NAMES = (STEP, ADMIT, PREFILL, PREFILL_DISPATCH, POOL_WRITE, ABSORB,
         FINISH, FIRST_TOKEN, GROW, PUSH_TABLES, DECODE_DISPATCH,
         SPEC_ROUND, SPEC_SHADOW, DEVICE_WAIT, EMIT, RELEASE, RECORD)
