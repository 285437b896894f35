"""Mixture-of-Experts FFN: dropless, over this chip's share of the experts.

One expert layer serves every MoE architecture.  The router scores all
``cfg.moe_experts`` published experts and picks the top-k; the layer holds
only experts ``[moe_expert_offset, moe_expert_offset + moe_experts_held)``
and computes, for the tokens routed to them, exactly their part of the
result (expert parallelism without its exchange: on one chip what the
absent experts would add is left out, and nothing stands in for it).
The (token, expert) pairs routed here are sorted by expert and run
through one grouped matmul per weight (``pragged_dot``: on the TPU a
ragged grouped kernel that skips empty groups), so the work and every
buffer scale with the pairs routed here, ``tokens * min(k, held)`` rows at
most -- never tokens x held experts, and no pair is dropped.  Shared
experts (DeepSeek-V2) are one dense SwiGLU of ``n_shared_experts *
moe_d_ff`` that every token passes, added once.

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
from .layers import (_nonlin, act_cast, dense_init, ffn_apply, ffn_init,
                     pgrouped_dot, pdot, pragged_dot)
from .qparams import as_array

# per-call counters the expert layer returns: (token, expert) pairs routed
# to held experts, and held experts that got at least one of them
N_COUNTS = 2
# tokens the expert layer takes in one pass: a 12k-token prompt's pairs at
# once would need ~2 GB of buffers a layer (T * min(k, held) rows)
EXPERT_TOKENS = 2048


def moe_init(key, cfg, dtype):
    """Router over every published expert, this chip's share of the
    expert weights (the slice of the whole stack that one draw per weight
    gives, so every share holds the values the uncut model holds; the
    whole stack is drawn, so ``Model.init_params`` draws one layer's
    stacks at a time), and the shared experts."""
    d, ff, E = cfg.d_model, cfg.moe_d_ff, cfg.moe_experts
    lo, n = cfg.moe_expert_offset, cfg.moe_experts_held
    ks = jax.random.split(key, 4)

    def stack(k, shape):
        return dense_init(k, (E,) + shape, dtype=jnp.float32)[
            lo:lo + n].astype(dtype)

    p = {
        "router": dense_init(ks[0], (d, E), dtype=jnp.float32),
        "w_in": stack(ks[1], (d, ff)),
        "w_out": stack(ks[2], (ff, d)),
    }
    if cfg.gated_ffn:
        p["w_gate"] = stack(ks[3], (d, ff))
    if cfg.n_shared_experts:
        p["shared"] = ffn_init(jax.random.fold_in(key, 1), d,
                               cfg.n_shared_experts * ff, cfg.gated_ffn,
                               cfg.use_bias, dtype)
    return p


def moe_apply(p, x, cfg, policy: PrecisionPolicy):
    """x: (B, S, d) -> ((B, S, d), load-balancing aux loss, counts).

    ``counts`` (int32 (N_COUNTS,)): pairs routed to held experts and held
    experts hit.  Dispatches to the shard_map expert-parallel path when
    the config asks for it and a mesh with a "model" axis is active (see
    moe_apply_sharded; it counts nothing).
    """
    with jax.named_scope("ffn"):
        if getattr(cfg, "moe_impl", "dense") == "shard_map":
            mesh = compat.get_abstract_mesh()
            if mesh is not None and "model" in (mesh.axis_names or ()):
                y, aux = moe_apply_sharded(p, x, cfg, policy, mesh)
                return y, aux, jnp.zeros((N_COUNTS,), jnp.int32)
        return _moe_apply_global(p, x, cfg, policy)


def route(p, xt, cfg, policy: PrecisionPolicy):
    """Top-k routing of flattened tokens ``xt`` (T, d) over every published
    expert (f32; "router_w"/"router_probs" roles).  Returns (router
    logits, probs, top-k weights -- renormalised when ``moe_norm_topk``,
    times ``moe_routed_scale`` --, top-k expert ids)."""
    logits = pdot(xt, p["router"], policy, "router_w",
                  out_act=False).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.moe_topk)         # (T, K)
    if cfg.moe_norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if cfg.moe_routed_scale != 1.0:
        top_p = top_p * np.float32(cfg.moe_routed_scale)
    return logits, probs, act_cast(top_p, policy, "router_probs"), top_e


def _aux_loss(probs, top_e, E, K):
    """Switch-style load-balancing loss: E * sum_e f_e * p_e."""
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=1), axis=0)
    return E * jnp.sum(me * ce / K)


def _moe_apply_global(p, x, cfg, policy: PrecisionPolicy):
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    with jax.named_scope("moe_route"):
        _, probs, top_p, top_e = route(p, xt, cfg, policy)
        aux = _aux_loss(probs, top_e, cfg.moe_experts, cfg.moe_topk)
    y, counts = experts(p, xt, top_p, top_e, cfg, policy, counts=True)
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            ys = ffn_apply(p["shared"], xt, policy, cfg)
        y = act_cast(y.astype(jnp.float32) + ys.astype(jnp.float32), policy)
    return y.reshape(B, S, d), aux, counts


def experts(p, xt, top_p, top_e, cfg, policy: PrecisionPolicy, *,
            counts: bool = False):
    """The held experts' part of the expert FFN of tokens ``xt`` (T, d)
    under a given routing (:func:`route`), dropless: the pairs routed to
    held experts, sorted by expert, through one grouped matmul per weight,
    then weighted and summed per token.  A long prompt passes
    ``EXPERT_TOKENS`` tokens at a time, so every buffer is bounded by
    one pass's pairs.  Returns (T, d) activations, and with ``counts``
    also the int32 (pairs routed here, experts hit)."""
    T, d = xt.shape
    K = cfg.moe_topk
    with jax.named_scope("moe_experts"):
        n = T // EXPERT_TOKENS if T > EXPERT_TOKENS else 0
        head = n * EXPERT_TOKENS
        ys, sizes = [], jnp.zeros((cfg.moe_experts_held,), jnp.int32)
        if n:
            y, s = jax.lax.map(
                lambda a: _held_experts(p, *a, cfg, policy),
                (xt[:head].reshape(n, EXPERT_TOKENS, d),
                 top_p[:head].reshape(n, EXPERT_TOKENS, K),
                 top_e[:head].reshape(n, EXPERT_TOKENS, K)))
            ys.append(y.reshape(head, d))
            sizes = sizes + s.sum(axis=0)
        if head < T:
            y, s = _held_experts(p, xt[head:], top_p[head:], top_e[head:],
                                 cfg, policy)
            ys.append(y)
            sizes = sizes + s
        yt = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=0)
    if not counts:
        return yt
    return yt, jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)]).astype(
        jnp.int32)


def _held_experts(p, xt, top_p, top_e, cfg, policy: PrecisionPolicy):
    """:func:`experts` over one pass of tokens: ((T, d), pairs per held
    expert (int32 (held,)))."""
    T, d = xt.shape
    K, lo, n = cfg.moe_topk, cfg.moe_expert_offset, cfg.moe_experts_held
    # every token routes at most min(K, n) of its K pairs here
    M = T * min(K, n)
    e = top_e.reshape(T * K).astype(jnp.int32) - lo
    here = (e >= 0) & (e < n)
    loc = jnp.where(here, e, n)                      # foreign -> n
    order = jnp.argsort(loc, stable=True).astype(jnp.int32)
    # where each pair sits in expert order (a permutation)
    rank = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * K, dtype=jnp.int32), unique_indices=True)
    sizes = jnp.bincount(loc, length=n + 1)[:n].astype(jnp.int32)

    xs = xt[order[:M] // K]                          # the pairs' tokens
    h = pragged_dot(xs, p["w_in"], sizes, policy, "ffn_w")
    a = _nonlin(h, cfg.act_fn)
    if "w_gate" in p:
        a = a * pragged_dot(xs, p["w_gate"], sizes, policy, "ffn_w")
    a = act_cast(a, policy)
    ye = act_cast(pragged_dot(a, p["w_out"], sizes, policy, "ffn_w"),
                  policy)
    # each token gathers its pairs' rows back, one of its k at a time:
    # no scatter-add, and no (T, k, d) f32 temporary (the chip's tiled
    # layout pads k to 8 rows); rows past the routed pairs are
    # undefined: select, never scale
    src = jnp.minimum(rank, M - 1).reshape(T, K)
    keep = here.reshape(T, K)
    wk = top_p.astype(jnp.float32)
    yt = jnp.zeros((T, d), jnp.float32)
    for k in range(K):
        yt = yt + jnp.where(keep[:, k:k + 1],
                            ye[src[:, k]].astype(jnp.float32)
                            * wk[:, k:k + 1], 0.0)
    return act_cast(yt, policy), sizes


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

    if cfg.moe_experts_held != cfg.moe_experts or "shared" in p:
        raise ValueError(
            "the shard_map expert-parallel path holds every routed expert "
            "and no shared ones; serve an expert share or shared experts "
            "with moe_impl='dense'")

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
