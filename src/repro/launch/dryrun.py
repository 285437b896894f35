import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Dry-run-only compile accelerators (single-core container): skip LLVM -O3
# codegen -- buffer assignment / cost analysis / collective selection are
# unaffected, only the (never executed) machine code is less optimized.
# Opt out with REPRO_DRYRUN_FAST=0.
if os.environ.get("REPRO_DRYRUN_FAST", "1") == "1":
    os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"

# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init).  Everything below is ordinary code.
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402

from repro import compat, configs              # noqa: E402
from repro.configs.shapes import (ALL_SHAPES, SHAPES, runnable,  # noqa: E402
                                  skip_reason)
from repro.tuning.artifact import (is_artifact_spec,  # noqa: E402
                                   load_policy)
from repro.launch import hlo_analysis          # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.sharding import (batch_spec, scalar_sharding,  # noqa: E402
                                   tree_param_shardings,
                                   tree_state_shardings)
from repro.models.registry import build_from_config  # noqa: E402
from repro.optim import adamw                  # noqa: E402

from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no device allocation)
# ---------------------------------------------------------------------------

def batch_struct(cfg, B: int, S: int, mesh, *, with_labels: bool):
    bspec = batch_spec(B, mesh, extra_dims=1)
    sh = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    d: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=sh(bspec)),
    }
    if with_labels:
        d["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32,
                                           sharding=sh(bspec))
    if cfg.prefix_len:
        d["prefix_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.prefix_len, cfg.d_model), jnp.float32,
            sharding=sh(batch_spec(B, mesh, extra_dims=2)))
    if cfg.encoder_layers:
        d["encoder_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_len, cfg.d_model), jnp.float32,
            sharding=sh(batch_spec(B, mesh, extra_dims=2)))
    return d


def input_specs(arch: str, shape_name: str, mesh, policy,
                cfg_overrides=None, speculate_k: int = 0):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    import dataclasses as _dc
    cfg = configs.get(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    spec = ALL_SHAPES[shape_name]
    model = build_from_config(cfg)
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), policy))
    if getattr(cfg, "matmul_impl", "xla") == "qmm_pallas":
        # serving-time storage transform: the cell lowers against the
        # PACKED parameter store (container-width weight bytes), exactly
        # what launch/serve.py builds at load time
        from repro.models import qparams
        params = jax.eval_shape(
            lambda p: qparams.encode_params(p, policy), params)
    p_sh = tree_param_shardings(params, mesh)
    params = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params, p_sh)

    if spec.kind == "train":
        opt = jax.eval_shape(lambda p: adamw.init(p, policy), params)
        o_sh = tree_param_shardings(opt, mesh)
        opt = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            opt, o_sh)
        batch = batch_struct(cfg, spec.global_batch, spec.seq_len, mesh,
                             with_labels=True)
        return model, cfg, {"params": params, "opt": opt, "batch": batch}

    if spec.kind == "prefill":
        batch = batch_struct(cfg, spec.global_batch, spec.seq_len, mesh,
                             with_labels=False)
        return model, cfg, {"params": params, "batch": batch}

    if speculate_k:
        # speculative verify: k tokens per sequence against the PAGED
        # cache (the serving engine's layout) -- roofline of the verify
        # half of a speculation round
        from repro.kernels import paged_cache as _pc
        if (cfg.encoder_layers or cfg.prefix_len
                or any(k != "attn" for k in cfg.attn_pattern)):
            raise ValueError(
                f"--speculate-k: arch {arch} is not an all-attention "
                f"decoder (verify_step cannot roll back recurrent / "
                f"prefix state)")
        B, page = spec.global_batch, _pc.DEFAULT_PAGE_SIZE
        pps = -(-spec.seq_len // page)
        states = jax.eval_shape(lambda: [
            _pc.init_paged_cache(B, B * pps, page, pps, cfg.n_kv,
                                 cfg.head_dim,
                                 policy.dtype("kv_cache", layer=li))
            for li, _ in enumerate(cfg.attn_pattern)])
        s_sh = tree_state_shardings(states, mesh, B)
        states = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            states, s_sh)
        tokens = jax.ShapeDtypeStruct(
            (B, speculate_k), jnp.int32,
            sharding=NamedSharding(mesh, batch_spec(B, mesh, extra_dims=1)))
        return model, cfg, {"params": params, "tokens": tokens,
                            "states": states, "extra": {}}

    # decode: one new token against a cache of length seq_len
    states = jax.eval_shape(
        lambda: model.init_state(spec.global_batch, spec.seq_len, policy))
    s_sh = tree_state_shardings(states, mesh, spec.global_batch)
    states = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        states, s_sh)
    tokens = jax.ShapeDtypeStruct(
        (spec.global_batch, 1), jnp.int32,
        sharding=NamedSharding(mesh, batch_spec(spec.global_batch, mesh)))
    extra = {}
    if cfg.encoder_layers:
        extra["encoder_embeds"] = jax.ShapeDtypeStruct(
            (spec.global_batch, cfg.encoder_len, cfg.d_model), jnp.float32,
            sharding=NamedSharding(
                mesh, batch_spec(spec.global_batch, mesh, extra_dims=2)))
    return model, cfg, {"params": params, "tokens": tokens,
                        "states": states, "extra": extra}


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def make_step_fn(model, cfg, kind: str, policy, lr: float = 3e-4,
                 speculate_k: int = 0):
    if kind == "train":
        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: model.train_loss(p, batch, policy))(params)
            _, new_opt = adamw.apply(grads, opt_state, policy, lr=lr)
            new_params = adamw.materialize_params(new_opt, params, policy)
            return loss, new_params, new_opt
        return train_step
    if kind == "prefill":
        def prefill_step(params, batch):
            return model.prefill(params, batch, policy)
        return prefill_step

    if speculate_k:
        def verify_step(params, tokens, states, extra):
            return model.verify_step(params, tokens, states, policy)
        return verify_step

    def serve_step(params, tokens, states, extra):
        return model.decode_step(params, tokens, states, policy, **extra)
    return serve_step


# ---------------------------------------------------------------------------
# one dry-run cell
# ---------------------------------------------------------------------------

def model_flops(cfg, spec, speculate_k: int = 0) -> float:
    n_active = cfg.active_param_count()
    if spec.kind == "train":
        return 6.0 * n_active * spec.global_batch * spec.seq_len
    if spec.kind == "prefill":
        return 2.0 * n_active * spec.global_batch * spec.seq_len
    # decode: one token per seq; verify: k tokens per seq in one step
    return 2.0 * n_active * spec.global_batch * max(speculate_k, 1)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             policy_name: str = "transprecision",
             cfg_overrides=None, kv_fmt=None, tag: str = "",
             speculate_k: int = 0,
             verbose: bool = True) -> Dict[str, Any]:
    spec = ALL_SHAPES[shape_name]
    if not runnable(arch, shape_name):
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "policy": policy_name, "status": "skipped",
                "reason": skip_reason(arch, shape_name)}
    # shape-pinned overrides (e.g. decode_impl for the *_flash variants)
    cfg_overrides = {**spec.cfg_overrides(), **(cfg_overrides or {})}

    # registry name or tuned-artifact path, same resolver as serve.py
    # (an artifact pins its formats, so kv_fmt conflicts raise here)
    policy = load_policy(policy_name, kv_fmt=kv_fmt)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    t0 = time.time()
    # set_mesh (not the bare Mesh context manager) where available so model
    # code can reach the ambient abstract mesh for shard_map paths (MoE EP,
    # flash-decode); compat falls back to the Mesh context manager
    if speculate_k and spec.kind != "decode":
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "policy": policy_name, "status": "skipped",
                "reason": "--speculate-k lowers the verify step of a "
                          "speculation round; only serve shapes decode"}
    with compat.use_mesh(mesh):
        model, cfg, ins = input_specs(arch, shape_name, mesh, policy,
                                      cfg_overrides,
                                      speculate_k=speculate_k)
        step = make_step_fn(model, cfg, spec.kind, policy,
                            speculate_k=speculate_k)

        if spec.kind == "train":
            args = (ins["params"], ins["opt"], ins["batch"])
            jitted = jax.jit(step, donate_argnums=(0, 1))
        elif spec.kind == "prefill":
            args = (ins["params"], ins["batch"])
            jitted = jax.jit(step)
        else:
            args = (ins["params"], ins["tokens"], ins["states"],
                    ins["extra"])
            jitted = jax.jit(step, donate_argnums=(2,))

        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    coll = hlo_analysis.collective_stats(hlo)
    coll_bytes = hlo_analysis.total_collective_bytes(coll)
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    mf = model_flops(cfg, spec, speculate_k)
    terms = hlo_analysis.roofline(flops_dev, bytes_dev, coll_bytes, n_chips,
                                  mf)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_chips": n_chips, "policy": policy_name, "status": "ok",
        "kind": "verify" if speculate_k else spec.kind,
        "speculate_k": speculate_k,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_bytes,
        "collectives": {k: v for k, v in coll.items()},
        "roofline": terms,
        "memory": _mem_dict(mem),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "hlo_lines": hlo.count("\n"),
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
        "overrides": cfg_overrides or {}, "tag": tag,
    }
    if verbose:
        print(f"== {arch} x {shape_name} x "
              f"{'multi(2,16,16)' if multi_pod else 'single(16,16)'} "
              f"[{policy_name}] ==")
        print("memory_analysis:", _mem_dict(mem))
        print("cost_analysis: flops/dev=%.3e bytes/dev=%.3e" %
              (flops_dev, bytes_dev))
        print("collectives:", {k: v for k, v in coll.items()
                               if v["count"]})
        print("roofline:", {k: (round(v, 6) if isinstance(v, float) else v)
                            for k, v in terms.items()})
    return result


def _mem_dict(mem) -> Dict[str, float]:
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "peak_memory_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = float(v)
    return out


# ---------------------------------------------------------------------------
# sweep driver
# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (e.g. moe_impl=shard_map)")
    # shared backend flags (shorthand for --set decode_impl=... /
    # --set matmul_impl=...; argparse choices validate the spelling)
    from repro.launch.cli import add_backend_args
    add_backend_args(ap, include_pool=False)
    ap.add_argument("--kv-fmt", default=None,
                    help="override kv_cache format (e.g. binary16alt)")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="lower the k-token speculative verify step "
                         "instead of single-token decode for decode-kind "
                         "shapes (paged-cache stand-ins)")
    ap.add_argument("--tag", default="", help="suffix for the result file")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            pass
        overrides[k] = v
    if args.decode_impl is not None:
        overrides["decode_impl"] = args.decode_impl
    if args.matmul_impl is not None:
        overrides["matmul_impl"] = args.matmul_impl
    if is_artifact_spec(args.policy):
        # fail fast (before the sweep) on per-knob overrides that
        # conflict with what the artifact pins
        load_policy(args.policy, decode_impl=args.decode_impl,
                    matmul_impl=args.matmul_impl, kv_fmt=args.kv_fmt)
        policy_tag = os.path.splitext(os.path.basename(args.policy))[0]
    else:
        policy_tag = args.policy

    archs = configs.ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = (f"{arch}__{shape}__{'multi' if mp else 'single'}"
                       f"__{policy_tag}"
                       + (f"__{args.tag}" if args.tag else ""))
                fn = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(fn):
                    print("cached:", tag)
                    continue
                try:
                    res = run_cell(arch, shape, multi_pod=mp,
                                   policy_name=args.policy,
                                   cfg_overrides=overrides or None,
                                   kv_fmt=args.kv_fmt,
                                   speculate_k=args.speculate_k,
                                   tag=args.tag)
                except Exception as e:  # record failures, keep sweeping
                    res = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "policy": args.policy, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    failures.append(tag)
                    print("FAILED:", tag, res["error"])
                with open(fn, "w") as f:
                    json.dump(res, f, indent=1)
    if failures:
        print(f"\n{len(failures)} failures: {failures}")
        raise SystemExit(1)
    print("\nall cells ok")


if __name__ == "__main__":
    main()
