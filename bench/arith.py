"""Operations and bytes a serving workload needs, from the configuration
alone: the yardstick of ``step.mfu.*`` and of the decode-attention
roofline.  What one implementation happens to read (a capacity-long
gather, whole pages) does not enter: these are the operations and bytes the
model's mathematics needs, the same whatever computes it.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Workload:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    layers: int
    vocab: int
    experts: int
    topk: int
    kv_bytes: int          # bytes of one stored K or V element
    act_bytes: int         # bytes of one activation element

    @classmethod
    def from_conf(cls, conf: dict) -> "Workload":
        d = int(conf["hidden_size"])
        heads = int(conf["num_attention_heads"])
        prec = conf["precision"]
        return cls(
            d=d, heads=heads, kv_heads=int(conf["num_key_value_heads"]),
            head_dim=int(conf.get("head_dim") or d // heads),
            ff=int(conf["intermediate_size"]),
            layers=int(conf["num_hidden_layers"]),
            vocab=int(conf["vocab_size"]),
            experts=int(conf.get("num_local_experts", 0) or 0),
            topk=int(conf.get("num_experts_per_tok", 0) or 0),
            kv_bytes=np.dtype(_dtype(prec["kv_cache"])).itemsize,
            act_bytes=np.dtype(_dtype(prec["activations"])).itemsize)

    # -- matmul parameters one token multiplies by ------------------------
    @property
    def layer_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        attn = self.d * q + 2 * self.d * kv + q * self.d
        if self.experts:
            ffn = self.d * self.experts + self.topk * 3 * self.d * self.ff
        else:
            ffn = 3 * self.d * self.ff
        return attn + ffn

    @property
    def head_params(self) -> int:
        return self.d * self.vocab

    def attn_flops(self, keys: int) -> int:
        """Both attention products of one query over ``keys`` keys, all
        layers: 2 * keys * head_dim multiply-adds each, per head."""
        return 4 * keys * self.heads * self.head_dim * self.layers

    def decode_flops(self, context: int) -> int:
        """One decoded token whose query attends ``context`` keys (its own
        included)."""
        return (2 * (self.layers * self.layer_matmul_params
                     + self.head_params) + self.attn_flops(context))

    def prefill_flops(self, prompt: int) -> int:
        """A whole causal prompt of ``prompt`` tokens; the LM head runs on
        its last position only."""
        return (2 * self.layers * self.layer_matmul_params * prompt
                + 2 * self.head_params
                + 4 * self.heads * self.head_dim * self.layers
                * prompt * (prompt + 1) // 2)

    def decode_attn_bytes(self, context: int) -> int:
        """HBM bytes one sequence's decode attention needs, all layers: the
        live K and V at their stored format, plus the query read and the
        output written at the activations' format."""
        kv = 2 * context * self.kv_heads * self.head_dim * self.kv_bytes
        qo = 2 * self.heads * self.head_dim * self.act_bytes
        return (kv + qo) * self.layers


def _dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    if name.startswith("float8"):
        import ml_dtypes
        return getattr(ml_dtypes, name)
    return np.dtype(name)
