"""Operations and needed bytes of a latent-attention mixture-of-experts
model (DeepSeek-V2) on one chip's expert share, from the configuration
alone: the yardstick of ``step.mfu.long`` and of the latent-decode and
expert rooflines.

What the mathematics needs, not what one implementation reads: attention
counts the published un-absorbed formulation (per head and layer, the
scores over ``qk_nope + qk_rope`` values and the mix over ``v_head_dim``);
decode reads each live latent row once at its stored format, without the
row's padding; the experts count the pairs the program reports routed to
the held experts and the held experts those pairs hit (the engine's MoE
counters, ``read_counters``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from bench.arith import _dtype

COUNTERS = "counters "   # the engine's counter events: "counters k=v ..."


@dataclasses.dataclass(frozen=True)
class MlaWorkload:
    d: int
    heads: int
    rank: int              # kv_lora_rank: latent values per row
    nope: int              # qk_nope_head_dim
    rope: int              # qk_rope_head_dim (one shared rope key)
    dv: int                # v_head_dim
    ff: int                # dense FFN width
    moe_ff: int            # expert width
    layers: int
    dense_layers: int      # leading dense layers
    vocab: int
    experts: int           # routed experts the router scores (published)
    held: int              # routed experts this chip holds
    shared: int            # shared experts (one SwiGLU of shared * moe_ff)
    kv_bytes: int          # bytes of one stored latent value
    act_bytes: int
    w_bytes: int

    @classmethod
    def from_conf(cls, conf: dict) -> "MlaWorkload":
        prec = conf["precision"]
        return cls(
            d=int(conf["hidden_size"]),
            heads=int(conf["num_attention_heads"]),
            rank=int(conf["kv_lora_rank"]),
            nope=int(conf["qk_nope_head_dim"]),
            rope=int(conf["qk_rope_head_dim"]),
            dv=int(conf["v_head_dim"]),
            ff=int(conf["intermediate_size"]),
            moe_ff=int(conf["moe_intermediate_size"]),
            layers=int(conf["num_hidden_layers"]),
            dense_layers=int(conf["first_k_dense_replace"]),
            vocab=int(conf["vocab_size"]),
            experts=int(conf["expert_share"]["of"]),
            held=int(conf["n_routed_experts"]),
            shared=int(conf["n_shared_experts"]),
            kv_bytes=np.dtype(_dtype(prec["kv_cache"])).itemsize,
            act_bytes=np.dtype(_dtype(prec["activations"])).itemsize,
            w_bytes=np.dtype(_dtype(prec["weights"])).itemsize)

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    # -- parameters ------------------------------------------------------
    @property
    def attn_params(self) -> int:
        H, d = self.heads, self.d
        return (d * H * (self.nope + self.rope) + d * (self.rank + self.rope)
                + self.rank * H * (self.nope + self.dv) + H * self.dv * d)

    @property
    def expert_params(self) -> int:
        """One routed expert's SwiGLU."""
        return 3 * self.d * self.moe_ff

    @property
    def shared_expert_params(self) -> int:
        """One MoE layer's shared experts."""
        return 3 * self.d * self.shared * self.moe_ff

    @property
    def head_params(self) -> int:
        return self.d * self.vocab

    @property
    def body_params(self) -> int:
        """Matmul parameters every token multiplies by below the LM head:
        attention, the dense FFNs, routers and shared experts."""
        return (self.layers * self.attn_params
                + self.dense_layers * 3 * self.d * self.ff
                + self.moe_layers * (self.d * self.experts
                                     + self.shared_expert_params))

    # -- operations --------------------------------------------------------
    def attn_flops(self, keys: int) -> int:
        """One query over ``keys`` keys, all heads and layers: scores over
        qk_nope + qk_rope values and the mix over v_head_dim."""
        return (2 * (self.nope + self.rope + self.dv) * keys * self.heads
                * self.layers)

    def decode_flops(self, context: int) -> int:
        """One decoded token over ``context`` keys (its own included),
        without its routed experts (:meth:`routed_flops`)."""
        return (2 * (self.body_params + self.head_params)
                + self.attn_flops(context))

    def prefill_flops(self, prompt: int) -> int:
        """A whole causal prompt, without its routed experts; the LM head
        runs on its last position only."""
        return (2 * self.body_params * prompt + 2 * self.head_params
                + 2 * (self.nope + self.rope + self.dv) * self.heads
                * self.layers * prompt * (prompt + 1) // 2)

    def routed_flops(self, pairs: int) -> int:
        """``pairs`` (token, held expert) pairs through their experts."""
        return 2 * pairs * self.expert_params

    # -- bytes ---------------------------------------------------------------
    def latent_decode_bytes(self, context: int) -> int:
        """HBM bytes one token's absorbed decode attention needs, all
        layers: ``context`` latent rows at their stored format, the
        absorbed query read and the latent output written at the
        activations' format."""
        rows = context * (self.rank + self.rope) * self.kv_bytes
        qo = self.heads * (2 * self.rank + self.rope) * self.act_bytes
        return (rows + qo) * self.layers

    def expert_step_bytes(self, experts_hit: int, pairs: int, tokens: int,
                          steps: int) -> int:
        """HBM bytes ``steps`` decode steps' expert FFNs need: the three
        matrices of every held expert hit (``experts_hit`` summed over
        layers and steps), each MoE layer's shared experts once a step, and
        the rows in and out: every routed pair's and every token's through
        the shared experts, per MoE layer."""
        weights = (experts_hit * self.expert_params
                   + steps * self.moe_layers * self.shared_expert_params
                   ) * self.w_bytes
        rows = 2 * (pairs + tokens * self.moe_layers) * self.d \
            * self.act_bytes
        return weights + rows


def read_counters(host) -> Dict[str, int]:
    """The engine's MoE counters summed over the counter events among
    ``host`` (``Trace.host``), by name (``moe_routed_here.decode`` ...);
    empty where the program writes none."""
    out: Dict[str, int] = {}
    for e in host:
        if not e.name.startswith(COUNTERS):
            continue
        for kv in e.name[len(COUNTERS):].split():
            k, _, v = kv.partition("=")
            if v.lstrip("-").isdigit():
                out[k] = out.get(k, 0) + int(v)
    return out

