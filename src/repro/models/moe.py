"""Mixture-of-Experts FFN with sort-based token dispatch (MaxText-style).

Tokens are routed top-k, sorted by expert id, packed into (E, C, d) with
capacity dropping, processed by a grouped einsum (active-FLOPs only), and
combined back with router weights.  The expert dimension shards over the
mesh "model"/"expert" axis; GSPMD turns the gathers into all-to-alls.

Transprecision notes (paper Sec. V-B analogues): router logits/probs are
range-critical -> binary32 by default policy; expert weights/activations
follow the tuned ffn_w/act formats.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core.policy import PrecisionPolicy
from repro.core.qtensor import QTensor
from .layers import _nonlin, act_cast, dense_init, pdot, pgrouped_dot
from .qparams import as_array


def moe_init(key, cfg, dtype):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.moe_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, E), dtype=jnp.float32),
        "w_in": dense_init(ks[1], (E, d, ff), dtype=dtype),
        "w_out": dense_init(ks[2], (E, ff, d), dtype=dtype),
    }
    if cfg.gated_ffn:
        p["w_gate"] = dense_init(ks[3], (E, d, ff), dtype=dtype)
    return p


def moe_apply(p, x, cfg, policy: PrecisionPolicy):
    """x: (B, S, d) -> (B, S, d), plus load-balancing aux loss.

    Dispatches to the shard_map expert-parallel path when the config asks
    for it and a mesh with a "model" axis is active (see moe_apply_sharded).
    """
    if getattr(cfg, "moe_impl", "dense") == "shard_map":
        mesh = compat.get_abstract_mesh()
        if mesh is not None and "model" in (mesh.axis_names or ()):
            return moe_apply_sharded(p, x, cfg, policy, mesh)
    return _moe_apply_global(p, x, cfg, policy)


def route(p, xt, cfg, policy: PrecisionPolicy):
    """Top-k routing of flattened tokens ``xt`` (T, d) (f32;
    "router_w"/"router_probs" roles).  Returns (router logits, probs,
    renormalized top-k weights, top-k expert ids)."""
    logits = pdot(xt, p["router"], policy, "router_w",
                  out_act=False).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.moe_topk)         # (T, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)    # renormalize
    return logits, probs, act_cast(top_p, policy, "router_probs"), top_e


def _aux_loss(probs, top_e, E, K):
    """Switch-style load-balancing loss: E * sum_e f_e * p_e."""
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=1), axis=0)
    return E * jnp.sum(me * ce / K)


def _moe_apply_global(p, x, cfg, policy: PrecisionPolicy):
    """Paper-faithful baseline path: global sort-based dispatch, GSPMD left
    to shard it (it cannot -- data-dependent scatter indices force
    replication; kept as the measured baseline in EXPERIMENTS.md Perf)."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    _, probs, top_p, top_e = route(p, xt, cfg, policy)
    aux = _aux_loss(probs, top_e, cfg.moe_experts, cfg.moe_topk)
    return experts(p, xt, top_p, top_e, cfg, policy).reshape(B, S, d), aux


def experts(p, xt, top_p, top_e, cfg, policy: PrecisionPolicy):
    """The expert FFN of tokens ``xt`` (T, d) under a given routing
    (:func:`route`): sort-based dispatch with capacity dropping, grouped
    expert matmuls, weighted combine.  Returns (T, d) activations."""
    T, d = xt.shape
    E, K = cfg.moe_experts, cfg.moe_topk

    # --- sort-based dispatch -------------------------------------------------
    C = int(np.ceil(cfg.capacity_factor * T * K / E))
    C = max(8, min(C, T))
    flat_e = top_e.reshape(T * K)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_p = top_p.reshape(T * K)

    order = jnp.argsort(flat_e)                               # stable
    se, st, sp = flat_e[order], flat_t[order], flat_p[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos < C
    dest = jnp.where(keep, se.astype(jnp.int32) * C + pos, E * C)  # drop slot

    xe = jnp.zeros((E * C + 1, d), xt.dtype).at[dest].set(xt[st])
    xe = xe[:E * C].reshape(E, C, d)

    # --- grouped expert FFN (active FLOPs only; registry-routed, so with
    # matmul_impl="qmm_pallas" each expert's packed block streams through
    # the fused kernel) -------------------------------------------------------
    h = pgrouped_dot(xe, p["w_in"], policy, "ffn_w")
    a = _nonlin(h, cfg.act_fn)
    if "w_gate" in p:
        a = a * pgrouped_dot(xe, p["w_gate"], policy, "ffn_w")
    a = act_cast(a, policy)
    ye = pgrouped_dot(a, p["w_out"], policy, "ffn_w")
    ye = act_cast(ye, policy).reshape(E * C, d)

    # --- combine -------------------------------------------------------------
    gathered = jnp.where(keep[:, None], ye[jnp.where(keep, dest, 0)], 0)
    weighted = gathered.astype(jnp.float32) * sp[:, None].astype(jnp.float32)
    yt = jnp.zeros((T, d), jnp.float32).at[st].add(weighted)
    return act_cast(yt, policy)


# ---------------------------------------------------------------------------
# shard_map expert-parallel path (beyond-baseline: EXPERIMENTS.md Perf #1)
# ---------------------------------------------------------------------------
#
# Hypothesis (from the baseline roofline): the global dispatch's scatter/
# gather indices are data-dependent, so GSPMD replicates the (E*C_global, d)
# buffers per device => O(TB) temp bytes.  Making the dispatch *shard-local*
# (tokens stay on their data shard, each model shard owns E_loc experts and
# serves every data shard's local tokens) bounds every buffer to
# (E_loc * C_loc, d) and turns the combine into one psum over "model" --
# the standard expert-parallel schedule, with zero all-to-all because
# activations are already replicated across the model axis at that point.

def moe_apply_sharded(p, x, cfg, policy: PrecisionPolicy, mesh):
    from jax.sharding import PartitionSpec as P

    # Packed expert weights are dequantized host-side before the shard_map:
    # the EP schedule runs XLA math on its shard-local blocks (a packed
    # expert-parallel kernel is an open item -- see ROADMAP), and the
    # in_specs below describe plain arrays.
    p = {k: (as_array(v) if isinstance(v, QTensor) else v)
         for k, v in p.items()}

    B, S, d = x.shape
    E, K = cfg.moe_experts, cfg.moe_topk
    dp = tuple(a for a in mesh.axis_names if a != "model")
    n_model = mesh.shape["model"]
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    assert E % n_model == 0, (E, n_model)
    E_loc = E // n_model
    T_loc = (B * S) // n_dp
    C = max(8, int(np.ceil(cfg.capacity_factor * T_loc * K / E)))

    def local(xb, router, w_in, w_gate, w_out):
        # xb: (B_loc, S, d) tokens of this data shard (replicated over model)
        Tl, dd = T_loc, xb.shape[-1]
        xt = xb.reshape(Tl, dd)
        _, probs, top_p, top_e = route({"router": router}, xt, cfg, policy)
        aux = _aux_loss(probs, top_e, E, K)
        aux = jax.lax.pmean(aux, dp) if dp else aux
        aux = jax.lax.pmean(aux, "model")  # identical; makes out_spec P()

        my_shard = jax.lax.axis_index("model")
        flat_e = top_e.reshape(Tl * K)
        flat_t = jnp.repeat(jnp.arange(Tl, dtype=jnp.int32), K)
        flat_p = top_p.reshape(Tl * K)
        mine = (flat_e // E_loc) == my_shard
        loc_e = jnp.where(mine, flat_e - my_shard * E_loc, E_loc)

        order = jnp.argsort(loc_e)  # foreign tokens sort to the end
        se, st, sp = loc_e[order], flat_t[order], flat_p[order]
        counts = jnp.bincount(loc_e, length=E_loc + 1)[:E_loc]
        starts = jnp.cumsum(counts) - counts
        pos = (jnp.arange(Tl * K, dtype=jnp.int32)
               - jnp.where(se < E_loc, starts[jnp.minimum(se, E_loc - 1)],
                           0).astype(jnp.int32))
        keep = (se < E_loc) & (pos < C)
        dest = jnp.where(keep, se.astype(jnp.int32) * C + pos, E_loc * C)

        xe = jnp.zeros((E_loc * C + 1, dd), xt.dtype).at[dest].set(xt[st])
        xe = xe[:E_loc * C].reshape(E_loc, C, dd)

        h = pgrouped_dot(xe, w_in, policy, "ffn_w")
        a = _nonlin(h, cfg.act_fn)
        if w_gate is not None:
            a = a * pgrouped_dot(xe, w_gate, policy, "ffn_w")
        a = act_cast(a, policy)
        ye = pgrouped_dot(a, w_out, policy, "ffn_w")
        ye = ye.reshape(E_loc * C, dd)

        gathered = jnp.where(keep[:, None], ye[jnp.where(keep, dest, 0)], 0)
        weighted = gathered * sp[:, None].astype(jnp.float32)
        yt = jnp.zeros((Tl, dd), jnp.float32).at[st].add(weighted)
        yt = jax.lax.psum(yt, "model")  # combine partial expert outputs
        return act_cast(yt, policy).reshape(xb.shape), aux

    has_gate = "w_gate" in p
    if not has_gate:
        def local_nogate(xb, router, w_in, w_out):
            return local(xb, router, w_in, None, w_out)

    fn = local if has_gate else local_nogate
    in_specs = [P(dp, None, None), P(None, None), P("model", None, None)]
    if has_gate:
        in_specs.append(P("model", None, None))
    in_specs.append(P("model", None, None))
    out_specs = (P(dp, None, None), P())
    args = [x, p["router"], p["w_in"]]
    if has_gate:
        args.append(p["w_gate"])
    args.append(p["w_out"])
    y, aux = compat.shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                              out_specs=out_specs)(*args)
    return y, aux
