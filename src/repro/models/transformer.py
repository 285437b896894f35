"""Model assembly: decoder-only LM, prefix-LM (VLM), and enc-dec (audio),
with per-layer kinds (attn | rwkv | rglru) from ``cfg.attn_pattern``.

Layers are Python-unrolled (loop-free HLO -- see DESIGN.md) and wrapped in
``jax.checkpoint`` during training so activation memory stays one-layer deep.
All parameter/activation tensors follow the :class:`PrecisionPolicy`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import PrecisionPolicy
from . import attention as attn
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import rwkv6 as rwkv_mod
from .base import ModelConfig
from .layers import (apply_norm, dense_init, embed_lookup, ffn_apply,
                     ffn_init, lm_head_loss, lm_logits, norm_init,
                     residual_add)


def _no_counts():
    """MoE counters of a call without expert layers."""
    return jnp.zeros((moe_mod.N_COUNTS,), jnp.int32)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def _policy(self, policy: PrecisionPolicy) -> PrecisionPolicy:
        """Lift the config's ``matmul_impl`` into the policy (the policy
        override wins, mirroring ``decode_impl``), so every pdot/peinsum
        downstream resolves the right matmul backend."""
        if policy.matmul_impl is None and self.cfg.matmul_impl != "xla":
            policy = dataclasses.replace(policy,
                                         matmul_impl=self.cfg.matmul_impl)
        return policy

    # ------------------------------------------------------------------ init
    def init_params(self, rng, policy: PrecisionPolicy) -> Dict[str, Any]:
        cfg = self.cfg
        wdt = policy.dtype("attn_w")
        fdt = policy.dtype("ffn_w")
        edt = policy.dtype("embed_w")
        # decoder layers honor per-layer bindings ("layers.{li}.attn_w" etc.)
        # so a tuned policy's storage dtypes land at init time, matching what
        # quantizing a binary32 master checkpoint would produce (both are
        # f32 -> narrow RNE casts of the same values)
        keys = jax.random.split(rng, cfg.n_layers + cfg.encoder_layers + 3)
        params: Dict[str, Any] = {
            "embed": dense_init(keys[0], (cfg.vocab, cfg.d_model), scale=1.0,
                                dtype=edt),
            "final_norm": norm_init(cfg.d_model, cfg.norm),
            "layers": [],
        }
        if not cfg.tied_embeddings:
            params["head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab),
                                        dtype=edt)
        sliced = cfg.moe_experts_held < cfg.moe_experts
        for li, kind in enumerate(cfg.attn_pattern):
            lp = policy.at_layer(li)
            lwdt = lp.dtype("attn_w")
            lfdt = lp.dtype("ffn_w")
            k = keys[2 + li]
            if sliced and params["layers"]:
                # an expert share slices each whole-stack draw (moe_init):
                # one layer's draws at a time, not every layer's at once
                _, k = jax.lax.optimization_barrier(
                    (params["layers"][-1], k))
            ks = jax.random.split(k, 4)
            layer: Dict[str, Any] = {"norm1": norm_init(cfg.d_model,
                                                         cfg.norm)}
            if kind == "attn":
                layer["mix"] = attn.attn_init(ks[0], cfg, lwdt)
            elif kind == "rwkv":
                layer["mix"] = rwkv_mod.rwkv_init(ks[0], cfg, lwdt)
            elif kind == "rglru":
                layer["mix"] = rglru_mod.rglru_init(ks[0], cfg, lfdt)
            else:
                raise ValueError(kind)
            if kind != "rwkv":  # rwkv channel-mix lives inside its params
                layer["norm2"] = norm_init(cfg.d_model, cfg.norm)
                if cfg.is_moe_layer(li):
                    layer["ffn"] = moe_mod.moe_init(ks[1], cfg, lfdt)
                else:
                    layer["ffn"] = ffn_init(ks[1], cfg.d_model, cfg.d_ff,
                                            cfg.gated_ffn, cfg.use_bias,
                                            lfdt)
            else:
                layer["norm2"] = norm_init(cfg.d_model, cfg.norm)
            if cfg.encoder_layers:  # decoder cross-attention
                layer["norm_x"] = norm_init(cfg.d_model, cfg.norm)
                layer["xattn"] = attn.attn_init(ks[2], cfg, lwdt)
            params["layers"].append(layer)

        if cfg.encoder_layers:
            enc = []
            for li in range(cfg.encoder_layers):
                k = keys[2 + cfg.n_layers + li]
                ks = jax.random.split(k, 2)
                enc.append({
                    "norm1": norm_init(cfg.d_model, cfg.norm),
                    "mix": attn.attn_init(ks[0], cfg, wdt),
                    "norm2": norm_init(cfg.d_model, cfg.norm),
                    "ffn": ffn_init(ks[1], cfg.d_model, cfg.d_ff,
                                    cfg.gated_ffn, cfg.use_bias, fdt),
                })
            params["encoder"] = enc
        return params

    # ------------------------------------------------------------- internals
    def _encode(self, params, embeds, policy):
        cfg = self.cfg
        x = embeds
        for layer in params["encoder"]:
            h = apply_norm(x, layer["norm1"], policy, cfg.norm)
            a, _ = attn.mha(layer["mix"], h, cfg, policy, causal=False)
            x = residual_add(x, a)
            h = apply_norm(x, layer["norm2"], policy, cfg.norm)
            x = residual_add(x, ffn_apply(layer["ffn"], h, policy, cfg))
        return x

    def _ffn(self, layer, li, h, policy):
        """The block's FFN: (out, aux loss, MoE counts)."""
        cfg = self.cfg
        if cfg.is_moe_layer(li):
            return moe_mod.moe_apply(layer["ffn"], h, cfg, policy)
        return (ffn_apply(layer["ffn"], h, policy, cfg),
                jnp.zeros((), jnp.float32), _no_counts())

    def _layer(self, layer, li, kind, x, policy, *, prefix_len=0,
               state=None, enc_out=None, chunk=None, positions=None):
        """One decoder block.  Returns (x, new_state, aux_loss, counts)."""
        cfg = self.cfg
        aux, counts = jnp.zeros((), jnp.float32), _no_counts()
        h = apply_norm(x, layer["norm1"], policy, cfg.norm)
        if kind == "attn":
            a, new_state = attn.mha(layer["mix"], h, cfg, policy,
                                    causal=True, prefix_len=prefix_len,
                                    cache=state, chunk=chunk,
                                    positions=positions)
        elif kind == "rwkv":
            a, new_state = rwkv_mod.time_mix(layer["mix"], h, cfg, policy,
                                             state=state)
        else:
            a, new_state = rglru_mod.rglru_block(layer["mix"], h, cfg, policy,
                                                 state=state)
        x = residual_add(x, a)
        if enc_out is not None:
            h = apply_norm(x, layer["norm_x"], policy, cfg.norm)
            a, _ = attn.mha(layer["xattn"], h, cfg, policy,
                            kv_source=enc_out)
            x = residual_add(x, a)
        h = apply_norm(x, layer["norm2"], policy, cfg.norm)
        if kind == "rwkv":
            f, new_state = rwkv_mod.channel_mix(layer["mix"], h, cfg, policy,
                                                state=new_state)
        else:
            f, aux, counts = self._ffn(layer, li, h, policy)
        return residual_add(x, f), new_state, aux, counts

    def _backbone(self, params, x, policy, *, prefix_len=0, states=None,
                  enc_out=None, chunk=None, positions=None, training=False):
        cfg = self.cfg
        new_states: List[Any] = []
        aux_total = jnp.zeros((), jnp.float32)
        counts_total = _no_counts()

        for li, layer in enumerate(params["layers"]):
            st = states[li] if states is not None else None
            kind = cfg.attn_pattern[li]
            lp = policy.at_layer(li)

            def run(xx, stt, layer=layer, li=li, kind=kind, lp=lp):
                return self._layer(layer, li, kind, xx, lp,
                                   prefix_len=prefix_len, state=stt,
                                   enc_out=enc_out, chunk=chunk,
                                   positions=positions)

            if training and cfg.remat:
                run = jax.checkpoint(run)
            x, ns, aux, counts = run(x, st)
            new_states.append(ns)
            aux_total = aux_total + aux
            counts_total = counts_total + counts
        x = apply_norm(x, params["final_norm"], policy, cfg.norm)
        return x, new_states, aux_total, counts_total

    def _head_w(self, params):
        if self.cfg.tied_embeddings:
            return params["embed"].T
        return params["head"]

    # ----------------------------------------------------------------- train
    def train_loss(self, params, batch, policy: PrecisionPolicy):
        cfg = self.cfg
        policy = self._policy(policy)
        tokens = batch["tokens"]
        labels = batch["labels"]
        x = embed_lookup(params["embed"], tokens, policy,
                         scale=cfg.embed_scale)
        prefix_len = 0
        label_mask = batch.get("label_mask")
        enc_out = None
        if cfg.prefix_len and "prefix_embeds" in batch:
            pe = batch["prefix_embeds"].astype(x.dtype)
            x = jnp.concatenate([pe, x], axis=1)
            prefix_len = pe.shape[1]
        if cfg.encoder_layers:
            enc_out = self._encode(params, batch["encoder_embeds"]
                                   .astype(x.dtype), policy)
        chunk = cfg.attn_chunk if x.shape[1] > cfg.attn_chunk else None
        x, _, aux, _ = self._backbone(params, x, policy,
                                      prefix_len=prefix_len,
                                      enc_out=enc_out, chunk=chunk,
                                      training=True)
        if prefix_len:
            x = x[:, prefix_len:]
        loss = lm_head_loss(x, self._head_w(params), labels, policy,
                            n_chunks=cfg.loss_chunks, label_mask=label_mask)
        if cfg.moe_experts:
            loss = loss + 0.01 * aux / max(cfg.n_layers, 1)
        return loss

    # ----------------------------------------------------------------- serve
    def cache_spec(self, li: int):
        """What attention layer ``li`` caches per token
        (:class:`~repro.kernels.paged_cache.CacheSpec`): K and V rows, or
        one latent row (MLA)."""
        assert self.cfg.attn_pattern[li] == "attn", li
        return attn.cache_spec(self.cfg)

    def init_state(self, batch_size, capacity, policy):
        cfg = self.cfg
        states = []
        for li, kind in enumerate(cfg.attn_pattern):
            lp = policy.at_layer(li)
            if kind == "attn":
                states.append(attn.init_cache(cfg, batch_size, capacity,
                                              lp, layer_kinds=("attn",))[0])
            elif kind == "rwkv":
                states.append(rwkv_mod.rwkv_init_state(cfg, batch_size,
                                                       lp))
            else:
                states.append(rglru_mod.rglru_init_state(cfg, batch_size,
                                                         lp))
        return states

    def prefill(self, params, batch, policy: PrecisionPolicy,
                capacity: Optional[int] = None, with_counts: bool = False):
        """Full-sequence forward; returns (last-position logits, states),
        and with ``with_counts`` the MoE counters summed over layers
        (int32 (pairs routed to held experts, held experts hit))."""
        cfg = self.cfg
        policy = self._policy(policy)
        tokens = batch["tokens"]
        B, S = tokens.shape
        capacity = capacity or S
        x = embed_lookup(params["embed"], tokens, policy,
                         scale=cfg.embed_scale)
        prefix_len = 0
        if cfg.prefix_len and "prefix_embeds" in batch:
            pe = batch["prefix_embeds"].astype(x.dtype)
            x = jnp.concatenate([pe, x], axis=1)
            prefix_len = pe.shape[1]
        enc_out = None
        if cfg.encoder_layers:
            enc_out = self._encode(params, batch["encoder_embeds"]
                                   .astype(x.dtype), policy)
        chunk = cfg.attn_chunk if x.shape[1] > cfg.attn_chunk else None

        # run backbone while also building decode states
        states = []
        counts = _no_counts()
        for li, (kind, layer) in enumerate(zip(cfg.attn_pattern,
                                               params["layers"])):
            lp = policy.at_layer(li)
            h = apply_norm(x, layer["norm1"], lp, cfg.norm)
            if kind == "attn":
                a, st = attn.prefill_to_cache(layer["mix"], h, cfg, lp,
                                              capacity,
                                              prefix_len=prefix_len,
                                              chunk=chunk)
            elif kind == "rwkv":
                st0 = rwkv_mod.rwkv_init_state(cfg, B, lp)
                a, st = rwkv_mod.time_mix(layer["mix"], h, cfg, lp,
                                          state=st0)
            else:
                st0 = rglru_mod.rglru_init_state(cfg, B, lp)
                a, st = rglru_mod.rglru_block(layer["mix"], h, cfg, lp,
                                              state=st0)
            x = residual_add(x, a)
            if enc_out is not None:
                hx = apply_norm(x, layer["norm_x"], lp, cfg.norm)
                a, _ = attn.mha(layer["xattn"], hx, cfg, lp,
                                kv_source=enc_out)
                x = residual_add(x, a)
            h = apply_norm(x, layer["norm2"], lp, cfg.norm)
            if kind == "rwkv":
                f, st = rwkv_mod.channel_mix(layer["mix"], h, cfg, lp,
                                             state=st)
            else:
                f, _, c = self._ffn(layer, li, h, lp)
                counts = counts + c
            x = residual_add(x, f)
            states.append(st)
        x = apply_norm(x, params["final_norm"], policy, cfg.norm)
        logits = lm_logits(x[:, -1:, :], self._head_w(params), policy)
        if with_counts:
            return logits, states, counts
        return logits, states

    def prefill_chunk(self, params, tokens, states, pstates,
                      policy: PrecisionPolicy, *, slot: int, q_offset: int):
        """One chunked-prefill step for ONE sequence (tokens: (1, C)).

        Attention layers scatter the chunk's K/V page-by-page into ``slot``
        of the shared :class:`~repro.kernels.paged_cache.PagedKVCache` in
        ``states`` (``attn.prefill_paged_chunk``); recurrent layers (rwkv /
        rglru) carry their own B=1 state through ``pstates`` -- their
        chunked parallel forms already thread state across chunks.  Non-attn
        entries of ``states`` pass through untouched; the scheduler merges
        ``pstates`` into the batched state when the prompt completes.

        ``slot`` / ``q_offset`` must be static under jit.  Returns
        (last-position logits, new_states, new_pstates).
        """
        cfg = self.cfg
        policy = self._policy(policy)
        if cfg.prefix_len or cfg.encoder_layers:
            raise ValueError(
                "prefill_chunk is decoder-only; prefix-LM / enc-dec archs "
                "prefill whole-prompt (Model.prefill)")
        B, C = tokens.shape
        x = embed_lookup(params["embed"], tokens, policy,
                         scale=cfg.embed_scale)
        chunk = cfg.attn_chunk if C > cfg.attn_chunk else None
        new_states = list(states)
        new_pstates = list(pstates)
        for li, (kind, layer) in enumerate(zip(cfg.attn_pattern,
                                               params["layers"])):
            lp = policy.at_layer(li)
            h = apply_norm(x, layer["norm1"], lp, cfg.norm)
            if kind == "attn":
                a, st = attn.prefill_paged_chunk(
                    layer["mix"], h, cfg, lp, states[li], slot,
                    q_offset, chunk=chunk)
                new_states[li] = st
            elif kind == "rwkv":
                a, st = rwkv_mod.time_mix(layer["mix"], h, cfg, lp,
                                          state=pstates[li])
                new_pstates[li] = st
            else:
                a, st = rglru_mod.rglru_block(layer["mix"], h, cfg, lp,
                                              state=pstates[li])
                new_pstates[li] = st
            x = residual_add(x, a)
            h = apply_norm(x, layer["norm2"], lp, cfg.norm)
            if kind == "rwkv":
                f, st = rwkv_mod.channel_mix(layer["mix"], h, cfg, lp,
                                             state=new_pstates[li])
                new_pstates[li] = st
            else:
                f, _, _ = self._ffn(layer, li, h, lp)
            x = residual_add(x, f)
        x = apply_norm(x, params["final_norm"], policy, cfg.norm)
        logits = lm_logits(x[:, -1:, :], self._head_w(params), policy)
        return logits, new_states, new_pstates

    def verify_step(self, params, tokens, states,
                    policy: PrecisionPolicy):
        """Speculative-verify forward: k tokens per slot in ONE batched
        step, logits for every position.

        tokens: (B, K) -- position ``i`` of row ``b`` is the token the
        sequence consumes at cache position ``seq_lens[b] + i`` (the
        pending token followed by the draft's proposals).  Returns
        (logits (B, K, V), new states with K entries appended per mapped
        slot) where ``logits[:, i]`` is bit-identical to the logits K
        sequential :meth:`decode_step` calls would produce -- the
        embeddings, projections, norms, FFN and lm-head all act row-wise,
        and the attention core dispatches per position through the same
        registry decode backend (``attn.verify_paged``).  That identity is
        what makes greedy acceptance exact: a verified token IS the token
        non-speculative decode would have emitted.

        Requires an all-attention decoder-only arch over paged caches --
        recurrent layer states (rwkv / rglru) cannot roll back to a
        mid-chunk position, and enc-dec / prefix-LM archs never reach the
        engine's speculative path.
        """
        cfg = self.cfg
        policy = self._policy(policy)
        if cfg.encoder_layers or cfg.prefix_len:
            raise ValueError(
                "verify_step is decoder-only (no prefix / encoder context)")
        if any(kind != "attn" for kind in cfg.attn_pattern):
            raise ValueError(
                f"arch {cfg.arch}: verify_step needs an all-attention "
                f"pattern -- recurrent layer states (rwkv / rglru) cannot "
                f"roll back rejected speculative positions")
        x = embed_lookup(params["embed"], tokens, policy,
                         scale=cfg.embed_scale)
        new_states = list(states)
        for li, layer in enumerate(params["layers"]):
            lp = policy.at_layer(li)
            h = apply_norm(x, layer["norm1"], lp, cfg.norm)
            a, st = attn.verify_paged(layer["mix"], h, cfg, lp,
                                      states[li])
            new_states[li] = st
            x = residual_add(x, a)
            h = apply_norm(x, layer["norm2"], lp, cfg.norm)
            f, _, _ = self._ffn(layer, li, h, lp)
            x = residual_add(x, f)
        x = apply_norm(x, params["final_norm"], policy, cfg.norm)
        logits = lm_logits(x, self._head_w(params), policy)
        return logits, new_states

    def decode_step(self, params, tokens, states, policy: PrecisionPolicy,
                    enc_out=None, encoder_embeds=None,
                    with_counts: bool = False):
        """tokens: (B, 1).  Returns (logits (B, 1, V), new states), and
        with ``with_counts`` the MoE counters (as :meth:`prefill`)."""
        cfg = self.cfg
        policy = self._policy(policy)
        x = embed_lookup(params["embed"], tokens, policy,
                         scale=cfg.embed_scale)
        if cfg.encoder_layers and enc_out is None:
            enc_out = self._encode(params, encoder_embeds.astype(x.dtype),
                                   policy)
        x, new_states, _, counts = self._backbone(
            params, x, policy, states=states, enc_out=enc_out,
            training=False)
        logits = lm_logits(x, self._head_w(params), policy)
        if with_counts:
            return logits, new_states, counts
        return logits, new_states
