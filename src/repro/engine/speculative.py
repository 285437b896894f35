"""Speculative decoding: a binary8 packed draft model sharing the page pool.

The draft model IS the transprecision approximation -- binary8 weights and
binary8 KV, the narrowest point the codec expresses -- and exact greedy
acceptance is the accuracy constraint that makes the approximation safe:
``Model.verify_step`` produces logits bit-identical to k sequential
``decode_step`` calls, so an accepted token is *the* token non-speculative
decode would have emitted.  Rejections cost nothing but the draft's (cheap,
narrow-format) forward passes.

One speculation **round** per engine step replaces one decode step:

1. **Propose** -- the draft runs ``k`` greedy decode steps from each slot's
   pending token against its own KV pages, yielding proposals
   ``q_1 .. q_k`` (the draft cache absorbs ``pending, q_1 .. q_{k-1}``).
2. **Verify** -- the target runs ONE batched :meth:`~repro.models.
   transformer.Model.verify_step` over ``[pending, q_1 .. q_{k-1}]``; its
   per-position argmax ``t_1 .. t_k`` is what sequential decode would emit.
3. **Accept** -- with ``j`` leading positions where ``t_i == q_i``, emit
   ``t_1 .. t_{m}`` where ``m = min(j + 1, k)`` (the first mismatching
   target token is *free* -- it is exact regardless of the draft).
4. **Roll back** -- both caches appended ``k`` entries but only ``m`` are
   canon: device ``seq_lens`` drop to ``base + m`` inside the round's jit
   (:func:`~repro.kernels.paged_cache.truncate_seq_lens`), and the host
   :class:`~repro.kernels.paged_cache.PagePool` frees pages past the
   truncation point in BOTH namespaces (``PagePool.truncate``).

Draft and target KV live in the same ``PagePool`` under distinct page
namespaces (the target in the default ``""``, the draft under
:data:`DRAFT_NAMESPACE`), so admission, growth, eviction and occupancy
stats remain one allocator and evicting a sequence frees both sides
atomically.

The whole round -- k draft steps, one verify, acceptance arithmetic and
the device-side rollback -- is one jitted function; the scheduler performs
a single device->host transfer per round (targets / emit counts / accept
counts) while the pending tokens stay on device for the next round.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.kernels import paged_cache

from . import tracing

DRAFT_NAMESPACE = "draft"


class SpeculativeDecoder:
    """Owns the draft side of speculative serving: the draft model, its
    packed params, its per-layer paged KV caches (same pool geometry as
    the target's, pages allocated from the shared ``PagePool`` under the
    ``draft`` namespace), and the jitted propose->verify->rollback round.

    Built by ``launch/serve.py`` (or directly in tests) and handed to
    :class:`~repro.engine.scheduler.Engine`, which calls :meth:`setup`
    once and then :meth:`round` in place of its batched decode step.
    """

    NS = DRAFT_NAMESPACE

    def __init__(self, draft_model, draft_cfg, draft_policy, draft_params,
                 *, k: int):
        if k < 1:
            raise ValueError(f"--speculate-k must be >= 1, got {k}")
        self.model = draft_model
        self.cfg = draft_cfg
        self.policy = draft_policy
        self.params = draft_params
        self.k = int(k)
        self.states: Optional[List] = None

    # ----------------------------------------------------------------- setup
    def setup(self, engine) -> None:
        """Validate draft/target compatibility, build the draft's paged
        caches over the engine's pool geometry, and jit the round."""
        tcfg = engine.cfg
        for name, cfg in (("target", tcfg), ("draft", self.cfg)):
            if cfg.encoder_layers or cfg.prefix_len:
                raise ValueError(
                    f"speculative decoding: {name} arch {cfg.arch} is not "
                    f"decoder-only (enc-dec / prefix-LM context cannot "
                    f"roll back)")
            if any(kind != "attn" for kind in cfg.attn_pattern):
                raise ValueError(
                    f"speculative decoding: {name} arch {cfg.arch} has "
                    f"recurrent layers (rwkv / rglru state cannot roll "
                    f"back rejected positions)")
        if self.cfg.vocab != tcfg.vocab:
            raise ValueError(
                f"draft vocab {self.cfg.vocab} != target vocab "
                f"{tcfg.vocab}: proposals would index a different token "
                f"space")
        if self.cfg.window is not None and engine.capacity > self.cfg.window:
            raise ValueError(
                f"draft arch {self.cfg.arch}: engine capacity "
                f"{engine.capacity} exceeds the draft's sliding window "
                f"{self.cfg.window}")
        self.n_layers = len(self.cfg.attn_pattern)
        self.states = [
            paged_cache.init_paged_cache(
                engine.slots, engine.num_pages, engine.page,
                engine.pages_per_seq, self.cfg.n_kv, self.cfg.head_dim,
                self.policy.dtype("kv_cache", layer=li))
            for li in range(self.n_layers)]

        k = self.k
        dmodel, dpolicy = self.model, self.policy
        tmodel, tpolicy = engine.model, engine.policy
        target_attn = list(engine.attn_layers)

        vocab = self.cfg.vocab

        def _round(params, dparams, tokens, states, dstates,
                   nan_mask, div_mask):
            # -- propose: k greedy draft steps from the pending token ------
            t = tokens
            props = []
            for _ in range(k):
                dlogits, dstates = dmodel.decode_step(dparams, t, dstates,
                                                      dpolicy)
                t = jnp.argmax(dlogits[:, -1, :], axis=-1) \
                       .astype(jnp.int32)[:, None]
                props.append(t[:, 0])
            props = jnp.stack(props, axis=1)                       # (n, k)
            # injected draft divergence: shift a masked slot's proposals
            # off the target argmax (+1 mod vocab is never a match); only
            # acceptance can suffer -- greedy verification stays exact
            props = jnp.where(div_mask[:, None], (props + 1) % vocab,
                              props)
            # -- verify: the target consumes [pending, q_1 .. q_{k-1}] -----
            v = jnp.concatenate([tokens, props[:, :-1]], axis=1)   # (n, k)
            bases = {li: states[li].seq_lens for li in target_attn}
            dbases = [s.seq_lens for s in dstates]
            logits, states = tmodel.verify_step(params, v, states, tpolicy)
            # injected NaN logits land here (same traced-mask trick as
            # DecodeWorker); the finite guard is computed in-jit so the
            # scheduler's single host transfer carries the verdict
            logits = jnp.where(nan_mask[:, None, None], jnp.nan, logits)
            tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # (n, k)
            bad = ~jnp.isfinite(logits).all(axis=(1, 2))
            # -- accept: j leading matches, emit m = min(j + 1, k) ---------
            matches = (tgt == props).astype(jnp.int32)
            accepted = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)
            m = jnp.minimum(accepted + 1, k)
            # -- roll back: both caches keep exactly base + m entries ------
            states = list(states)
            for li in target_attn:
                states[li] = paged_cache.truncate_seq_lens(
                    states[li], bases[li] + m)
            dstates = [paged_cache.truncate_seq_lens(s, b + m)
                       for s, b in zip(dstates, dbases)]
            pending = jnp.take_along_axis(tgt, (m - 1)[:, None], axis=1)
            return tgt, m, accepted, pending, bad, states, dstates

        self._round = jax.jit(_round)
        # degraded-mode draft warm-up: one plain draft decode step, KV
        # append only (logits discarded) -- see shadow_step
        self._shadow = jax.jit(
            lambda dp, t, ds: dmodel.decode_step(dp, t, ds, dpolicy)[1])
        self._zero_mask = jnp.zeros((engine.slots,), jnp.bool_)
        npl = self.n_layers
        self._prefill = jax.jit(
            lambda p, t, s, slot: dmodel.prefill_chunk(
                p, t, s, [None] * npl, dpolicy, slot=slot, q_offset=0)[1],
            static_argnums=3)

    # ------------------------------------------------------------- host hooks
    def push_tables(self, tables) -> None:
        """Mirror the draft namespace's host block tables onto the draft
        caches (same masking contract as the engine's ``_push_tables``)."""
        for li in range(self.n_layers):
            self.states[li] = paged_cache.set_block_tables(
                self.states[li], tables)

    def prefill_prompt(self, slot: int, prompt: List[int]) -> None:
        """Write ``prompt``'s draft KV into ``slot``'s draft-namespace
        pages (one whole-prompt chunk; the target side already landed via
        the engine's chunked prefill).  Caller must have pushed the draft
        block tables first."""
        t = jnp.asarray([list(prompt)], jnp.int32)
        self.states = self._prefill(self.params, t, self.states, slot)

    def release_slot(self, slot: int) -> None:
        """Reset ``slot``'s draft device row (eviction / completion)."""
        for li in range(self.n_layers):
            self.states[li] = paged_cache.release_slot(self.states[li],
                                                       slot)

    def shadow_step(self, tokens) -> None:
        """While the circuit breaker holds speculation open, advance the
        draft KV by the token the target just consumed (the scheduler
        decodes plain): the draft cache stays in lockstep with the target,
        so acceptance has a chance the moment the breaker re-probes."""
        with tracing.span(tracing.SPEC_SHADOW):
            self.states = self._shadow(self.params, tokens, self.states)

    def round(self, params, tokens, states, nan_mask=None, div_mask=None):
        """One speculation round.  Returns device-side
        ``(tgt (n, k), m (n,), accepted (n,), pending (n, 1), bad (n,),
        states)``; the draft caches are updated in place on ``self``.
        ``nan_mask`` / ``div_mask`` are the fault injector's per-slot
        poison masks (None = no fault)."""
        nan_mask = self._zero_mask if nan_mask is None else nan_mask
        div_mask = self._zero_mask if div_mask is None else div_mask
        tgt, m, accepted, pending, bad, states, self.states = self._round(
            params, self.params, tokens, states, self.states,
            nan_mask, div_mask)
        return tgt, m, accepted, pending, bad, states
