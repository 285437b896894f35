"""Chip smoke test: serve granite-moe-1b-a400m at its published widths on TPU.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: the cross-chip paths only

One process, no network, nothing outside the repo: weights come from
``PRNGKey(0)`` and prompts from ``numpy.random.default_rng(0)``, exactly as
``python -m repro.launch.serve`` makes them.  The script refuses to run
anywhere but a TPU -- there is no CPU fallback -- and exits non-zero on any
failed check.  Its last line is one JSON object naming the device.

One chip (the full published config, all 24 layers):

* **Phase A** serves through ``repro.launch.serve`` with the default policy
  and the TPU's default decode spelling (``flash_pallas``).
* **Phase B** serves with ``--decode-impl paged --matmul-impl qmm_pallas``:
  the packed-KV page kernel plus the packed-weight GEMV, the paper's path.

Each phase must complete every request with ``max_new`` tokens, fire no
recovery counter (there is no fault plan), and hold a Mosaic kernel
(``tpu_custom_call``) in its compiled decode and prefill programs, so no
kernel ran interpreted.  Then its outputs are compared with the ``xla``
spelling's, sublayer by sublayer over all 24 layers, down to the logits
(see :class:`SublayerCheck`).

Four chips (``--chips 4``; widths published, depth cut to
``FOUR_CHIP_LAYERS`` for compile time) run only what exists across chips,
each against the one-chip ``paged`` spelling on device 0 in the same
process:

* ``flash_shmap+paged`` and ``ring+paged`` under the 4-device ``"model"``
  mesh -- the pool's sharding must span 4 devices and the compiled decode
  step must hold the merge collective;
* ``--disaggregate --prefill-workers 3`` -- prefill on chips 1-3 streams
  checksummed KV pages to the decode pool on chip 0; its tokens must equal
  the one-chip run's.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "granite-moe-1b-a400m"
PAGE, PROMPT, MAX_NEW, CAPACITY = 128, 256, 16, 512
# 4 requests over 2 slots; a 256-token prompt prefills as two page-sized
# chunks.  The engine compiles one prefill program per (slot, chunk
# offset), so slots x prompt pages (2 x 2) stays small on purpose.
SERVE = ["--arch", ARCH, "--requests", "4", "--slots", "2",
         "--prompt-len", str(PROMPT), "--page-size", str(PAGE),
         "--max-new", str(MAX_NEW), "--capacity", str(CAPACITY)]
# The cross-chip paths are served at the published widths but only the
# first FOUR_CHIP_LAYERS layers: at all 24, the four serve phases would
# compile about 4 x 180 s of full-depth programs (one phase took 179.3 s of
# compile on one v5e), at four chips' cost for every second of it.
FOUR_CHIP_LAYERS = 4
FORCED_STEPS = 4
# see SublayerCheck
ROUNDINGS = 8


def sublayer_bound(ref_scale: float) -> float:
    return ROUNDINGS * 2.0 ** -8 * ref_scale


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileCounter:
    """Counts backend compiles (a persistent-cache hit is not one)."""

    def __init__(self, jax):
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.event, self.n, self.secs = BACKEND_COMPILE_EVENT, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.event:
            self.n += 1
            self.secs += secs


def _on_mesh(mesh):
    from repro import compat
    return compat.use_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()


def serve_phase(counter, name: str, argv: list, **kw):
    """Serve ``argv`` through the CLI entry; check completion and recovery
    counters.  Returns (engine, requests)."""
    from repro.launch import serve
    n0, s0 = counter.n, counter.secs
    t0 = time.perf_counter()
    engine, reqs = serve.serve(argv, **kw)
    wall = time.perf_counter() - t0
    bad = [r.rid for r in reqs if not r.done or r.error is not None
           or len(r.generated) != MAX_NEW]
    check(not bad, f"phase {name}: requests {bad} did not complete with "
                   f"{MAX_NEW} tokens")
    fired = {k: engine.summary[k] for k in serve.RECOVERY_COUNTERS
             if engine.summary[k]}
    check(not fired, f"phase {name}: recovery counters fired without a "
                     f"fault plan: {fired}")
    tokens = sum(len(r.generated) for r in reqs)
    print(f"[smoke] phase {name}: {len(reqs)} requests, {tokens} tokens "
          f"served, {wall:.1f} s wall, {counter.n - n0} compiles "
          f"({counter.secs - s0:.1f} s)", flush=True)
    return engine, reqs


def compiled_decode_text(engine) -> str:
    """HLO of the engine's decode step and of one prefill chunk, compiled
    for the device (the persistent cache serves the repeat); each must
    hold a Mosaic kernel, i.e. none ran interpreted.  Returns the decode
    step's text."""
    import jax.numpy as jnp
    pw = engine.prefill_workers[0]
    tr = pw.transport
    view = getattr(tr, "src_states", engine.states)  # streamed: own pool
    # the sharded wrappers read the ambient mesh at trace time
    with _on_mesh(engine.mesh):
        dec = engine.decode_worker._step.lower(
            engine.params, engine._tokens, engine.states,
            engine._zero_mask).compile().as_text()
        pre = pw._chunk.lower(
            tr.params, tr.to_prefill(jnp.zeros((1, PAGE), jnp.int32)), view,
            [None] * len(engine.cfg.attn_pattern), 0, 0).compile().as_text()
    for what, text in (("decode step", dec), ("prefill chunk", pre)):
        check("tpu_custom_call" in text,
              f"{engine.policy.decode_impl}: no Mosaic kernel in the "
              f"compiled {what} (a kernel ran interpreted?)")
    return dec


class SublayerCheck:
    """A spelling against a reference spelling, sublayer by sublayer over
    every layer, teacher-forced.

    The reference runs the sublayer functions that ``Model.prefill_chunk``
    and ``Model.decode_step`` chain (embedding; per layer the attention
    sublayer, the MoE router, the experts; the LM head) over one prompt:
    each page-sized prefill chunk, then ``FORCED_STEPS`` decode steps of
    tokens the served run emitted.  It records every sublayer's input,
    cache and output.  The spelling under test then runs each sublayer on
    the reference's recorded input and cache, and the experts under the
    reference's routing.  So its errors cannot compound over layers or
    calls, and no top-k routing decision can flip between the two.  (With
    random weights one flip changes a token's FFN output by a whole
    expert's share; over 24 layers flips grow a rounding difference into
    unrelated logits: whole stacks compared on the chip differed by 171.76
    against max|logit| 142.27.)

    Bound: both spellings read the same bf16 weights and binary8 KV and
    accumulate in f32.  They may differ in summation order and in where an
    f32 intermediate is rounded to bf16 (or multiplied as bf16 at the TPU's
    default matmul precision): up to five roundings through an attention
    sublayer (q, probabilities, the attention mix, its projection, the
    output), fewer through a matmul, each at most 2^-8 of its value, and
    softmax scales a score's error by the score, which is of order one
    after the norm.  Each compared output must stay within
    ``ROUNDINGS * 2^-8 * max|reference|``; a wrong mask, head, page or
    expert moves it by its own scale.
    """

    def __init__(self, jax, cfg, prompt, forced, ref_policy):
        from repro.kernels import paged_cache
        from repro.models.registry import build_from_config
        check(set(cfg.attn_pattern) == {"attn"} and cfg.moe_experts,
              f"{cfg.arch}: the sublayer check covers attention + MoE "
              f"decoders only")
        self.jax, self.cfg = jax, cfg
        self.model = build_from_config(cfg)
        self.params = self.model.init_params(jax.random.PRNGKey(0),
                                             ref_policy)
        # one slot owning every page of its capacity (4 pages: divisible
        # over a 4-device mesh too)
        pages = CAPACITY // PAGE
        table = jax.numpy.arange(pages, dtype=jax.numpy.int32)[None]
        states = self.model.init_state(1, PAGE, ref_policy)
        for li in range(cfg.n_layers):
            states[li] = paged_cache.set_block_tables(
                paged_cache.init_paged_cache(
                    1, pages, PAGE, pages, cfg.n_kv, cfg.head_dim,
                    ref_policy.dtype("kv_cache", layer=li)), table)
        jnp = jax.numpy
        self.calls = [("prefill", jnp.asarray([prompt[o:o + PAGE]],
                                              jnp.int32), o)
                      for o in range(0, len(prompt), PAGE)]
        self.calls += [("decode", jnp.asarray([[t]], jnp.int32), None)
                       for t in forced]
        self.records = self._reference(ref_policy, states)

    def _fns(self, policy, layer: int):
        """Jitted sublayer functions of ``policy`` at ``layer`` (the layer
        weights are arguments, so layers of one policy share programs)."""
        from repro.models import attention as attn
        from repro.models import moe
        from repro.models.layers import (apply_norm, embed_lookup,
                                         lm_logits, residual_add)
        jit, cfg, d = self.jax.jit, self.cfg, self.cfg.d_model
        lp = policy.at_layer(layer)
        cache = self.__dict__.setdefault("_fn_cache", {})
        if id(lp) in cache:
            return cache[id(lp)][1]
        chunk = cfg.attn_chunk if PAGE > cfg.attn_chunk else None
        fns = {
            "embed": jit(lambda table, toks: embed_lookup(
                table, toks, policy, scale=cfg.embed_scale)),
            "prefill": jit(lambda mix, h, st, off: attn.prefill_paged_chunk(
                mix, h, cfg, lp, st, 0, off, chunk=chunk),
                static_argnums=3),
            "decode": jit(lambda mix, h, st: attn.mha(
                mix, h, cfg, lp, causal=True, cache=st)),
            "router": jit(lambda ffn, h: moe.route(
                ffn, h.reshape(-1, d), cfg, lp)),
            "experts": jit(lambda ffn, h, top_p, top_e: moe.experts(
                ffn, h.reshape(-1, d), top_p, top_e, cfg, lp)),
            "head": jit(lambda w, x: lm_logits(x, w, policy)),
            "norm": jit(lambda x, p: apply_norm(x, p, lp, cfg.norm)),
            "add": jit(residual_add),
        }
        cache[id(lp)] = (lp, fns)
        return fns

    def _reference(self, policy, states):
        params, records = self.params, []
        head_w = self.model._head_w(params)
        for kind, toks, off in self.calls:
            x = self._fns(policy, 0)["embed"](params["embed"], toks)
            rec = {"embed": x, "layers": []}
            new_states = []
            for li, layer in enumerate(params["layers"]):
                f = self._fns(policy, li)
                h1 = f["norm"](x, layer["norm1"])
                a, st = (f["prefill"](layer["mix"], h1, states[li], off)
                         if kind == "prefill"
                         else f["decode"](layer["mix"], h1, states[li]))
                x = f["add"](x, a)
                h2 = f["norm"](x, layer["norm2"])
                rl, _, top_p, top_e = f["router"](layer["ffn"], h2)
                y = f["experts"](layer["ffn"], h2, top_p, top_e)
                x = f["add"](x, y.reshape(x.shape))
                rec["layers"].append(dict(
                    h1=h1, state=states[li], attention=a, h2=h2, router=rl,
                    top_p=top_p, top_e=top_e, experts=y))
                new_states.append(st)
            f = self._fns(policy, 0)
            rec["final"] = f["norm"](x, params["final_norm"])[:, -1:]
            rec["logits"] = f["head"](head_w, rec["final"])
            records.append(rec)
            states = new_states
        return records

    def delta(self, policy, params, *, label, mesh=None, put=None,
              put_state=None, decode_only=False, ffn=True):
        """Runs ``policy``'s spelling on the recorded inputs; checks that
        every output is finite and within the bound.  ``put`` /
        ``put_state`` move recorded arrays / cache states where the
        spelling runs (``params`` must already live there).  Returns the
        worst |dlogit|."""
        import numpy as np
        put = put or (lambda a: a)
        put_state = put_state or put
        head_w = self.model._head_w(params)
        worst = {}   # quantity -> (|d| / max|ref|, |d|, call, layer)
        failed = []

        def cmp(what, got, want, call, layer):
            got = np.asarray(got, np.float64)
            want = np.asarray(want, np.float64)
            check(np.isfinite(got).all() and np.isfinite(want).all(),
                  f"{label}: non-finite {what} at call {call}, layer "
                  f"{layer}")
            d = float(np.abs(got - want).max())
            scale = float(np.abs(want).max())
            r = d / scale if scale else d
            if r > worst.get(what, (-1.0,))[0]:
                worst[what] = (r, d, call, layer)
            if d > sublayer_bound(scale):
                failed.append((what, call, layer, d, scale))

        with _on_mesh(mesh):
            for ci, ((kind, toks, off), rec) in enumerate(
                    zip(self.calls, self.records)):
                if kind == "prefill" and decode_only:
                    continue
                call = f"{kind}@{off}" if kind == "prefill" else \
                    f"decode{ci}"
                f0 = self._fns(policy, 0)
                cmp("embed", f0["embed"](params["embed"], toks),
                    rec["embed"], call, None)
                for li, (layer, r) in enumerate(zip(params["layers"],
                                                    rec["layers"])):
                    f = self._fns(policy, li)
                    h1, st = put(r["h1"]), put_state(r["state"])
                    a = (f["prefill"](layer["mix"], h1, st, off)
                         if kind == "prefill"
                         else f["decode"](layer["mix"], h1, st))[0]
                    cmp("attention", a, r["attention"], call, li)
                    if ffn:
                        h2 = put(r["h2"])
                        cmp("router", f["router"](layer["ffn"], h2)[0],
                            r["router"], call, li)
                        cmp("experts", f["experts"](
                            layer["ffn"], h2, put(r["top_p"]),
                            put(r["top_e"])), r["experts"], call, li)
                cmp("logits", f0["head"](head_w, put(rec["final"])),
                    rec["logits"], call, None)
        n_layers = self.cfg.n_layers
        parts = ", ".join(f"{what} {w[0]:.4g} ({w[2]}, layer {w[3]})"
                          for what, w in worst.items())
        print(f"[smoke] {label}: max |d| / max|ref| over {n_layers} "
              f"layers: {parts}; bound {ROUNDINGS} * 2^-8 = "
              f"{sublayer_bound(1.0):.4g}", flush=True)
        print(f"[smoke] {label}: max |dlogit| = {worst['logits'][1]:.6g}",
              flush=True)
        check(not failed, f"{label}: outside the bound (quantity, call, "
                          f"layer, max |d|, max|ref|): {failed[:8]}")
        return worst["logits"][1]


def one_chip(jax, counter):
    from repro.core.policy import get_policy
    from repro.models import qparams

    # phase A: the TPU default spelling (no --decode-impl)
    eng_a, reqs_a = serve_phase(counter, "A", SERVE)
    pol_a = eng_a.policy
    check(pol_a.decode_impl == "flash_pallas",
          f"TPU default decode spelling is {pol_a.decode_impl!r}, "
          f"expected 'flash_pallas'")
    compiled_decode_text(eng_a)
    cfg, prompt = eng_a.cfg, reqs_a[0].prompt
    forced = reqs_a[0].generated[:FORCED_STEPS]
    del eng_a  # free phase A's weights before phase B loads its own

    # phase B: packed-KV pages + packed-weight GEMV
    eng_b, _ = serve_phase(counter, "B", SERVE + [
        "--decode-impl", "paged", "--matmul-impl", "qmm_pallas"])
    pol_b = eng_b.policy
    compiled_decode_text(eng_b)
    del eng_b

    sc = SublayerCheck(jax, cfg, prompt, forced, get_policy(
        "transprecision", decode_impl="xla", matmul_impl="xla"))
    sc.delta(pol_a, sc.params, label="phase A flash_pallas vs xla")
    sc.delta(pol_b, qparams.encode_params(sc.params, pol_b),
             label="phase B paged+qmm_pallas vs xla")


def four_chips(jax, counter):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.engine.scheduler import shard_pool
    base, depth = SERVE, {"n_layers": FOUR_CHIP_LAYERS}
    ref, ref_reqs = serve_phase(counter, "paged, chip 0",
                                base + ["--decode-impl", "paged"], **depth)
    cfg, ref_pol = ref.cfg, ref.policy
    forced = ref_reqs[0].generated[:FORCED_STEPS]
    want_tokens = [r.generated for r in ref_reqs]
    del ref
    sc = SublayerCheck(jax, cfg, ref_reqs[0].prompt, forced, ref_pol)

    for spelling, collective in (("flash_shmap+paged", "all-reduce"),
                                 ("ring+paged", "collective-permute")):
        eng, _ = serve_phase(counter, spelling,
                             base + ["--decode-impl", spelling], **depth)
        k_pool = eng.states[eng.attn_layers[0]].k_pool
        n_dev = len(k_pool.sharding.device_set)
        check(n_dev == 4, f"{spelling}: pool spans {n_dev} devices")
        text = compiled_decode_text(eng)
        check(collective in text,
              f"{spelling}: no {collective} in the compiled decode step")
        print(f"[smoke] {spelling}: pool sharded over {n_dev} devices "
              f"({k_pool.sharding.spec}), {collective} x"
              f"{text.count(collective)} in the decode step", flush=True)
        mesh, pol = eng.mesh, eng.policy
        del eng
        rep = NamedSharding(mesh, P())
        # the FFN is the reference's own spelling: only attention differs
        sc.delta(pol, jax.device_put(sc.params, rep), mesh=mesh,
                 decode_only=True, ffn=False,
                 put=lambda a, s=rep: jax.device_put(a, s),
                 put_state=lambda st, m=mesh: shard_pool(st, m),
                 label=f"{spelling} vs paged on chip 0")

    eng, reqs = serve_phase(counter, "disaggregated, 3 prefill workers",
                            base + ["--decode-impl", "paged",
                                    "--disaggregate", "--prefill-workers",
                                    "3"], **depth)
    devs = sorted(tr.prefill_device.id for tr in eng.transports)
    check(devs == [1, 2, 3], f"prefill workers on devices {devs}")
    check([r.generated for r in reqs] == want_tokens,
          "disaggregated tokens differ from the one-chip paged run")
    print(f"[smoke] disaggregated: prefill on devices {devs}, tokens "
          f"identical to the one-chip paged run", flush=True)
    # the same spelling on a prefill chip: its logits must agree too
    dev1 = eng.transports[0].prefill_device
    del eng
    sc.delta(ref_pol, jax.device_put(sc.params, dev1),
             put=lambda a: jax.device_put(a, dev1),
             label="paged on chip 1 vs chip 0")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): phases A and B on one chip; 4: the "
                         "sharded and streamed paths across four chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"[smoke] FAIL: the repo's src/ is not next to this script "
              f"({e})", file=sys.stderr)
        return 1
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: JAX finds no TPU (platform {dev.platform!r}); "
              f"this smoke test never falls back to another device",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"[smoke] FAIL: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    counter = CompileCounter(jax)
    print(f"[smoke] jax {jax.__version__}, {len(devices)} x "
          f"{dev.device_kind}, compile cache {cache}", flush=True)

    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(jax, counter)
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(f"[smoke] all checks passed in {time.perf_counter() - t0:.1f} s; "
          f"{counter.n} compiles took {counter.secs:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
