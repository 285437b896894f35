"""Prefill and decode workers: the jitted compute the scheduler drives.

The prefill worker is the memory story of this package.  The old serve
loop materialised a *prompt-sized* contiguous K/V buffer per layer
(``Model.prefill`` then a bulk ``write_prefill``); the chunked path here
runs ``Model.prefill_chunk`` one page-sized chunk at a time, scattering
each chunk's K/V page-by-page into the pool -- the peak transient staging
buffer drops from O(prompt_len) to O(page_size) per layer, and the
scheduler interleaves a decode step between chunks so long prompts never
stall the decode batch.

``slot`` / ``q_offset`` are static jit arguments: the XLA prefill path
sizes its causal masks with Python arithmetic on ``q_offset``, so each
(chunk length, offset) pair compiles once and is reused across requests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import paged_cache

from . import tracing


class PrefillTask:
    """One in-flight prompt: chunk cursor, stream cursor, and result."""

    def __init__(self, request, slot: int, n_tokens: int, worker: int = 0):
        self.request = request
        self.slot = slot
        self.n_tokens = n_tokens   # KV rows the prompt occupies
        self.worker = worker       # prefill worker / transport index
        self.offset = 0            # tokens already prefilled
        self.streamed = 0          # pages already handed to the decode pool
        self.done = False
        self.logits = None         # last-position logits once done
        self.pstates = None        # B=1 recurrent-layer states (rwkv/rglru)


def make_batch(cfg, request) -> dict:
    batch = {"tokens": jnp.asarray([request.prompt], jnp.int32)}
    if cfg.prefix_len:
        batch["prefix_embeds"] = jnp.zeros(
            (1, cfg.prefix_len, cfg.d_model), jnp.float32)
    if cfg.encoder_layers:
        batch["encoder_embeds"] = jnp.zeros(
            (1, cfg.encoder_len, cfg.d_model), jnp.float32)
    return batch


class PrefillWorker:
    """Runs prompts into the transport-provided page-pool view.

    chunk_tokens > 0 on a decoder-only arch: page-granular chunked prefill
    (transient staging = one chunk).  chunk_tokens == 0, or a prefix-LM
    arch whose prefix rows need the whole-sequence path: one-shot
    ``Model.prefill`` followed by a bulk ``write_prefill`` (transient
    staging = the whole prompt, the old serve.py behavior).
    """

    def __init__(self, model, cfg, policy, transport, stats, *,
                 chunk_tokens: int):
        self.cfg = cfg
        self.transport = transport
        self.stats = stats
        self.chunk_tokens = int(chunk_tokens or 0)
        self.chunked = self.chunk_tokens > 0 and not (
            cfg.prefix_len or cfg.encoder_layers)
        self._chunk = jax.jit(
            lambda p, t, s, ps, slot, off: model.prefill_chunk(
                p, t, s, ps, policy, slot=slot, q_offset=off),
            static_argnums=(4, 5))
        # capacity=None: the transient contiguous prefill cache is
        # prompt-sized, immediately rewritten into pages
        self._whole = jax.jit(lambda p, b: model.prefill(p, b, policy, None))

    def next_tokens(self, task: PrefillTask) -> int:
        """Prompt tokens the next :meth:`step` of ``task`` prefills."""
        left = task.n_tokens - task.offset
        return min(self.chunk_tokens, left) if self.chunked else left

    def step(self, task: PrefillTask, view_states, slot: int):
        """Advance ``task`` by one chunk (or the whole prompt); returns
        the updated state view for the transport to absorb."""
        if not self.chunked:
            return self._whole_step(task, view_states, slot)
        C = self.next_tokens(task)
        with tracing.span(tracing.PREFILL_DISPATCH):
            toks = task.request.prompt[task.offset:task.offset + C]
            t = self.transport.to_prefill(jnp.asarray([toks], jnp.int32))
            # the chunk's K/V land in the pool inside this program
            # (paged_cache.write_chunk): no eager pool write follows
            logits, view_states, task.pstates = self._chunk(
                self.transport.params, t, view_states, task.pstates,
                slot, task.offset)
        self.stats.note_prefill_transient(C)
        task.offset += C
        if task.offset >= task.n_tokens:
            task.done = True
            task.logits = logits
        return view_states

    def _whole_step(self, task: PrefillTask, view_states, slot: int):
        with tracing.span(tracing.PREFILL_DISPATCH):
            batch = self.transport.to_prefill(
                make_batch(self.cfg, task.request))
            logits, one_states = self._whole(self.transport.params, batch)
        with tracing.span(tracing.POOL_WRITE):
            for li, kind in enumerate(self.cfg.attn_pattern):
                if kind == "attn":
                    view_states[li] = paged_cache.write_prefill(
                        view_states[li], slot,
                        one_states[li].k[0], one_states[li].v[0])
                else:
                    task.pstates[li] = one_states[li]
        self.stats.note_prefill_transient(task.n_tokens)
        task.offset = task.n_tokens
        task.done = True
        task.logits = logits
        return view_states


class DecodeWorker:
    """One jitted batched decode step over the shared page pool.

    The step returns ``(next_tokens, bad, states)`` rather than raw
    logits: the argmax AND the NaN/Inf guard (``bad[s]`` = slot ``s``'s
    last-position logits contain a non-finite value) are computed inside
    the jit, so the scheduler's single per-step host transfer carries the
    guard verdict for free.  ``nan_mask`` is a traced ``(n_slots,)`` bool
    argument the fault injector uses to poison one slot's logits -- all
    False (the no-fault case) compiles to the same program.
    """

    def __init__(self, model, policy):
        def _step(p, t, s, nan_mask):
            logits, s = model.decode_step(p, t, s, policy)
            logits = jnp.where(nan_mask[:, None, None], jnp.nan, logits)
            last = logits[:, -1, :]
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
            bad = ~jnp.isfinite(last).all(axis=-1)
            return nxt, bad, s

        self._step = jax.jit(_step)

    def step(self, params, tokens, states, nan_mask):
        return self._step(params, tokens, states, nan_mask)
