"""Prefill and decode workers: the jitted compute the scheduler drives.

The prefill worker is the memory story of this package.  The old serve
loop materialised a *prompt-sized* contiguous K/V buffer per layer
(``Model.prefill`` then a bulk ``write_prefill``); the chunked path here
runs ``Model.prefill_chunk`` one page-sized chunk at a time, scattering
each chunk's K/V page-by-page into the pool -- the peak transient staging
buffer drops from O(prompt_len) to O(page_size) per layer, and the
scheduler interleaves a decode step between chunks so long prompts never
stall the decode batch.

``slot`` / ``q_offset`` are static arguments of the chunk program: the
XLA prefill path sizes its causal masks with Python arithmetic on
``q_offset``, so each (chunk length, offset) pair compiles once and is
reused across requests.
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
        self.counts = None         # whole-prompt MoE counters (device)
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
    ``Model.prefill`` followed by one ``_write_pools`` call, a jitted
    ``paged_cache.write_prefill_layers`` that writes the prompt into
    every attention layer's pool (K and V, or one latent pool) and
    donates them, so each is updated in place rather than copied (transient staging = the whole
    prompt, the old serve.py behavior).  ``slot`` is traced there, so the
    write compiles once per prompt length.
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
        self._whole = jax.jit(lambda p, b: model.prefill(
            p, b, policy, None, with_counts=True))
        self._attn = [li for li, kind in enumerate(cfg.attn_pattern)
                      if kind == "attn"]

        def _write_pools(k_pools, v_pools, tables, lens, slot, ks, vs):
            caches = [paged_cache.PagedKVCache(*c)
                      for c in zip(k_pools, v_pools, tables, lens)]
            out = paged_cache.write_prefill_layers(
                caches, slot, [k[0] for k in ks],
                [None if v is None else v[0] for v in vs])
            # outputs in the donated inputs' order: JAX hands a donated
            # buffer to the first output of its shape and dtype, and any
            # other order makes XLA copy the pools to honour that pairing
            return ([c.k_pool for c in out], [c.v_pool for c in out],
                    [c.seq_lens for c in out])

        # the pools are donated: undonated, one program over every layer
        # would hold a second copy of each pool at once
        self._write_pools = jax.jit(_write_pools, donate_argnums=(0, 1))

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
            logits, one_states, task.counts = self._whole(
                self.transport.params, batch)
        with tracing.span(tracing.POOL_WRITE):
            for li, kind in enumerate(self.cfg.attn_pattern):
                if kind != "attn":
                    task.pstates[li] = one_states[li]
            if self._attn:
                caches = [view_states[li] for li in self._attn]
                written = self._write_pools(
                    [c.k_pool for c in caches], [c.v_pool for c in caches],
                    [c.block_tables for c in caches],
                    [c.seq_lens for c in caches], slot,
                    [one_states[li].k for li in self._attn],
                    [one_states[li].v for li in self._attn])
                for li, c, k, v, n in zip(self._attn, caches, *written):
                    view_states[li] = c._replace(k_pool=k, v_pool=v,
                                                 seq_lens=n)
                self.stats.note_pool_write()
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
    guard verdict for free, and with it the MoE counters the step leaves
    in :attr:`counts` (pairs routed to held experts, held experts hit,
    summed over layers; device values).  ``nan_mask`` is a traced ``(n_slots,)`` bool
    argument the fault injector uses to poison one slot's logits -- all
    False (the no-fault case) compiles to the same program.

    ``donate`` (default: on the TPU) donates the state, so every pool is
    updated in place: undonated, the step writes a second copy of every
    pool each step (DeepSeek-V2-Lite's 16 x 16384 latent pools are 4.5 GB
    beside 6.2 GB of weights).  A donated state is deleted by the step, so
    a caller keeps only the state the step returns.
    """

    def __init__(self, model, policy, *, donate: bool | None = None):
        def _step(p, t, s, nan_mask):
            logits, s, counts = model.decode_step(p, t, s, policy,
                                                  with_counts=True)
            logits = jnp.where(nan_mask[:, None, None], jnp.nan, logits)
            last = logits[:, -1, :]
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
            bad = ~jnp.isfinite(last).all(axis=-1)
            # the state first: JAX hands a donated buffer to the first
            # output of its shape and dtype, so each pool keeps its own
            return s, nxt, bad, counts

        if donate is None:
            donate = jax.default_backend() == "tpu"
        self._step = jax.jit(_step, donate_argnums=(2,) if donate else ())
        self.counts = None

    def step(self, params, tokens, states, nan_mask):
        states, nxt, bad, self.counts = self._step(params, tokens, states,
                                                   nan_mask)
        return nxt, bad, states
