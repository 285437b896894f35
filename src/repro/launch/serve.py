"""Batched serving CLI: a thin front-end over :mod:`repro.engine`.

``python -m repro.launch.serve --arch llama3-8b --reduced --requests 16``

The serving loop itself lives in the engine package (scheduler / workers /
transport -- see docs/engine.md); this module only parses flags, builds
the model + policy, and prints the summary line.  Every request is served
out of one block-table page pool:

  * KV caches stored in the policy's ``kv_cache`` format (binary8/e5m2 by
    default -- 4x smaller working set, the paper's trick on the serving
    bottleneck);
  * any registry spelling from kernels/dispatch.py is accepted: paged
    backends read the pool natively, contiguous backends (``xla``,
    ``flash_pallas``, the ``flash_shmap+``/``ring+`` wrappers) read it
    through the gather bridge in models/attention.py -- one code path,
    eleven spellings, unknown ones fail loudly at argparse time;
  * prompts prefill in page-sized chunks interleaved with decode steps
    (``--prefill-chunk``; 0 restores whole-prompt prefill), so a long
    prompt never stalls the decode batch and the transient prefill
    staging buffer is one page per layer instead of prompt-sized;
  * ``--disaggregate`` moves prefill to a second device (simulate hosts
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=2``) and
    streams finished KV pages into the decode pool page-by-page;
  * ``--router`` serves through the asyncio front-end
    (:mod:`repro.engine.router`) and ``--prefill-workers N`` runs N
    concurrent prefill workers -- one transport (one streamed source
    pool, one simulated device) each -- feeding the single decode batch;
    ``--max-pending`` bounds the in-flight queue (backpressure).  Tokens
    stay bit-identical to the synchronous single-worker run;
  * admission is gated on pool occupancy; when the pool runs dry the most
    recently admitted sequence is evicted back to the queue (LIFO) and its
    pages reused immediately -- the vLLM memory model on top of
    transprecision packed storage.  ``--page-size`` sets the granule,
    ``--pool-pages`` caps the pool (default: no memory pressure);
  * ``--stats-out`` streams per-step scheduler/pool stats as JSON lines;
  * the self-healing layer (docs/resilience.md) is always on:
    ``--deadline-steps`` / ``--max-requeues`` / ``--watchdog-s`` bound it,
    ``--fault-plan`` exercises it with a deterministic seeded fault
    schedule, and a failed request surfaces as a classified
    ``EngineError`` -- ``python -m repro.launch.serve`` exits with the
    error's distinct code (70-76) plus one structured stderr line, never
    a bare traceback.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import sys

import jax
import numpy as np

from repro import compat, configs
from repro.core.formats import BINARY8
from repro.core.policy import get_policy
from repro.tuning.artifact import load_policy
from repro.engine import (ColocatedTransport, Engine, EngineStats,
                          FaultPlan, Request, SpeculativeDecoder,
                          StreamedTransport, exit_code_for, format_error,
                          run_router)
from repro.kernels import dispatch
from repro.launch.cli import (add_backend_args, add_resilience_args,
                              add_router_args, add_speculative_args)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import qparams
from repro.models.registry import build, build_from_config

__all__ = ["RECOVERY_COUNTERS", "Request", "build_draft", "cli_main",
           "main", "serve"]

# summary counters that are zero in a healthy run without a fault plan: a
# non-zero value means the engine recovered from something (a NaN guard
# trip replayed through the oracle, a retried step, a refetched page, ...)
RECOVERY_COUNTERS = ("quarantines", "retries", "crc_mismatches",
                     "degraded_steps", "failures", "evictions")


def build_draft(model, cfg, *, arch=None, reduced=False, k):
    """Build the binary8 packed draft side for speculative serving.

    By default the draft shares the target's architecture (and, via the
    shared PRNG seed, its weights) but serves them through the narrowest
    transprecision point: binary8 weights in the packed container store,
    binary8 KV in its own page-pool namespace.  ``arch`` swaps in a
    different (typically smaller) draft architecture; the vocab must match
    the target's or ``SpeculativeDecoder.setup`` rejects it.
    """
    dmodel, dcfg = model, cfg
    if arch is not None and arch != cfg.arch:
        dmodel, dcfg = build(arch, reduced=reduced)
    draft_policy = get_policy(
        "transprecision", decode_impl="paged").with_overrides(
        embed_w=BINARY8, attn_w=BINARY8, ffn_w=BINARY8)
    dparams = dmodel.init_params(jax.random.PRNGKey(0), draft_policy)
    dparams = qparams.encode_params(dparams, draft_policy)
    return SpeculativeDecoder(dmodel, dcfg, draft_policy, dparams, k=k)


def serve(argv=None, *, n_layers=None):
    """Parse ``argv``, build the model and engine, serve every request;
    returns ``(engine, requests)`` so callers can read the engine's
    summary counters and state after the run.  ``n_layers`` serves only
    the config's first N decoder layers (every width unchanged): a depth
    cut for checks that cannot afford the full stack's compile time."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=configs.ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--capacity", type=int, default=128)
    add_backend_args(ap, include_pool=True)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="tokens prefilled per engine step (default: one "
                         "page; 0 = whole-prompt prefill, the old "
                         "monolithic behavior)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="run prefill on a second device and stream "
                         "finished KV pages into the decode pool "
                         "(simulate hosts with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=2)")
    ap.add_argument("--stats-out", default=None,
                    help="write per-step engine stats as JSON lines here")
    add_router_args(ap)
    add_speculative_args(ap)
    add_resilience_args(ap)
    args = ap.parse_args(argv)
    if args.prefill_workers < 1:
        raise ValueError(
            f"--prefill-workers must be >= 1, got {args.prefill_workers}")

    # the policy-level override wins inside attention.decode_impl(), so no
    # config rewrite / model rebuild is needed; with no explicit flag,
    # serving prefers the fused path wherever a TPU backend is present.
    # --policy accepts a registry name or a tuned-artifact path; an
    # artifact pins its knobs, so only the *explicit* flags participate in
    # conflict checking and the serving default fills in afterwards
    policy = load_policy(args.policy, decode_impl=args.decode_impl,
                         matmul_impl=args.matmul_impl)
    if policy.decode_impl is None:
        policy = dataclasses.replace(
            policy, decode_impl=dispatch.default_serving_impl())
    impl = policy.decode_impl
    model, cfg = build(args.arch, reduced=args.reduced)
    if n_layers:
        if not 0 < n_layers <= cfg.n_layers:
            raise ValueError(f"n_layers={n_layers}: {cfg.arch} has "
                             f"{cfg.n_layers} decoder layers")
        cfg = dataclasses.replace(cfg, n_layers=n_layers,
                                  attn_pattern=cfg.attn_pattern[:n_layers])
        model = build_from_config(cfg)
    effective_impl = impl or cfg.decode_impl
    if args.disaggregate and len(dispatch.canonicalize_impl(
            effective_impl)) > 1:
        raise ValueError(
            f"--disaggregate streams pages between single-device pools; "
            f"mesh-sharded spelling {effective_impl!r} keeps the pool "
            f"sharded across the mesh -- use a base spelling "
            f"(xla / flash_pallas / paged)")
    mesh = None
    if len(dispatch.canonicalize_impl(effective_impl)) > 1:
        # a wrapped spelling shards over a 1-D "model" mesh of every
        # device; the Engine refuses one its wrapper could not shard over
        mesh = compat.make_mesh((len(jax.devices()),), ("model",))
        print(f"[serve] mesh: model={mesh.shape['model']} "
              f"({jax.devices()[0].platform})")
    params = model.init_params(jax.random.PRNGKey(0), policy)
    if (policy.matmul_impl or cfg.matmul_impl) == "qmm_pallas":
        # the packed parameter store is built ONCE at load time; every
        # decode step then reads container-width weight bytes
        packed = qparams.encode_params(params, policy)
        print(f"[serve] {qparams.describe_packing(params, packed)}")
        params = packed
    rng = np.random.default_rng(0)

    reqs = [Request(i, rng.integers(0, min(cfg.vocab, 97),
                                    args.prompt_len).tolist(),
                    args.max_new)
            for i in range(args.requests)]

    speculative = None
    if args.speculate_k:
        speculative = build_draft(model, cfg, arch=args.draft_config,
                                  reduced=args.reduced, k=args.speculate_k)
        print(f"[serve] speculative: draft={speculative.cfg.arch} "
              f"(binary8 packed weights, binary8 KV), k={args.speculate_k}")

    fault_plan = None
    if args.fault_plan:
        fault_plan = FaultPlan.load(args.fault_plan)
        print(f"[serve] fault plan: {fault_plan.describe()}")

    n_workers = args.prefill_workers
    if args.disaggregate:
        # one streamed source pool per worker, spread across the non-
        # decode devices (worker i's pool on device 1 + i mod (ndev - 1))
        ndev = len(jax.devices())
        transports = [
            StreamedTransport(device_index=(1 + i % (ndev - 1))
                              if ndev > 1 else 0)
            for i in range(n_workers)]
    else:
        transports = [ColocatedTransport() for _ in range(n_workers)]
    transport = transports[0]
    engine = Engine(model, cfg, policy, params,
                    slots=args.slots, capacity=args.capacity,
                    page_size=args.page_size, pool_pages=args.pool_pages,
                    prefill_chunk=args.prefill_chunk,
                    transport=transports, prefill_workers=n_workers,
                    stats=EngineStats(args.stats_out),
                    speculative=speculative,
                    fault_plan=fault_plan,
                    deadline_steps=args.deadline_steps,
                    max_requeues=args.max_requeues,
                    watchdog_s=args.watchdog_s,
                    mesh=mesh)
    with (compat.use_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        if args.router:
            # async front-end: submissions flow through the Router's
            # queue into the same engine; a ticket's classified
            # per-request failure comes back on the Request, engine-fatal
            # errors raise here
            asyncio.run(run_router(engine, reqs,
                                   max_pending=args.max_pending))
            print(f"[serve] router: {n_workers} prefill worker(s), "
                  f"queue wait mean: "
                  f"{engine.summary['queue_wait_mean_s']}s, "
                  f"per-worker prefill chunks: "
                  f"{engine.summary['prefill_chunks_by_worker']}")
        else:
            engine.run(reqs)

    s = engine.summary
    st = engine.pool.stats()
    total_tokens = sum(len(r.generated) for r in reqs)
    dt = max(s["elapsed_s"], 1e-9)
    kv_fmts = sorted({policy.fmt("kv_cache", layer=li).name
                      for li in range(len(cfg.attn_pattern))})
    kv_desc = kv_fmts[0] if len(kv_fmts) == 1 \
        else "per-layer[" + ",".join(kv_fmts) + "]"
    print(f"[serve] {len(reqs)} requests, {total_tokens} tokens, "
          f"{engine.stats.target_steps} batched steps, "
          f"{total_tokens/dt:.1f} tok/s "
          f"(kv format: {kv_desc}, "
          f"decode: {effective_impl}, "
          f"matmul: {policy.matmul_impl or cfg.matmul_impl}, "
          f"page_size: {engine.page}, pool: {st['peak_pages_used']}/"
          f"{st['num_pages']} pages peak, frag: "
          f"{st['internal_fragmentation']}, "
          f"evictions: {s['evictions']}, "
          + (f"accept rate: {s['accept_rate']}, "
             f"steps/token: {s['steps_per_token']}, "
             if args.speculate_k else "")
          + f"transport: {transport.name}, "
          f"ttft mean: {s['ttft_mean_s']}s, "
          f"peak prefill staging: {s['peak_prefill_transient_tokens']} "
          f"tokens)")
    if (args.fault_plan or s["faults_injected"]
            or any(s[k] for k in RECOVERY_COUNTERS)):
        print(f"[serve] resilience: faults={s['faults_injected']} "
              f"(unfired: {s['faults_unfired']}), "
              f"retries={s['retries']}, "
              f"crc_mismatches={s['crc_mismatches']}, "
              f"quarantines={s['quarantines']}, "
              f"degraded_steps={s['degraded_steps']}, "
              f"breaker_trips={s['breaker_trips']}, "
              f"deadline_misses={s['deadline_misses']}, "
              f"dead_letters={s['dead_letters']}, "
              f"failures={s['failures']}")
    return engine, reqs


def main(argv=None):
    """Serve one CLI invocation in-process; returns the Request list
    (``r.generated`` holds the token ids).  Raises classified engine
    errors -- :func:`cli_main` turns them into exit codes."""
    return serve(argv)[1]


def cli_main(argv=None) -> int:
    """Process entry point: classified engine errors become distinct exit
    codes (70-76) plus one structured stderr line instead of a bare
    traceback.  In-process callers use :func:`main`, which raises."""
    enable_compile_cache()
    try:
        reqs = main(argv)
    except Exception as e:  # noqa: BLE001 -- classified errors only
        code = exit_code_for(e)
        if code is None:
            raise  # a real bug deserves its traceback
        print(format_error(e), file=sys.stderr)
        return code
    failed = [r for r in reqs if r.error is not None]
    if failed:
        # requests that failed with classified results (deadline misses,
        # dead letters): the run completed, but the process should not
        # exit 0 -- report the most severe class
        worst = max(failed, key=lambda r: exit_code_for(r.error) or 0)
        print(format_error(worst.error, requests=len(failed)),
              file=sys.stderr)
        return exit_code_for(worst.error) or 70
    return 0


if __name__ == "__main__":
    sys.exit(cli_main())
