"""Model substrate: configuration dataclass shared by all architectures."""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rope scaling (arXiv:2309.00071), as DeepSeek-V2 states it:
    frequencies blended between interpolated and extrapolated by a ramp
    over the correction range of ``beta_fast`` / ``beta_slow`` rotations
    within ``original_max`` positions; ``mscale`` / ``mscale_all_dim`` set
    the cos/sin factor and the softmax scale's correction."""
    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _m(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0

    @property
    def rope_mscale(self) -> float:
        """Factor on cos and sin."""
        return (self._m(self.factor, self.mscale)
                / self._m(self.factor, self.mscale_all_dim))

    @property
    def softmax_mscale(self) -> float:
        """Factor on the softmax scale (the square of the attention
        mscale: it multiplies both q and k)."""
        if not self.mscale_all_dim:
            return 1.0
        return self._m(self.factor, self.mscale_all_dim) ** 2


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config describes any member of the supported families.

    family: "dense" | "moe" | "ssm" (rwkv6) | "hybrid" (rg-lru+local attn) |
            "vlm" (prefix-LM over stub patch embeddings) |
            "audio" (enc-dec over stub frame embeddings)
    """

    arch: str
    family: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int

    # MoE
    moe_experts: int = 0                  # routed experts, as published
    moe_topk: int = 0
    capacity_factor: float = 1.25         # shard_map path only (dropping)
    moe_d_ff: Optional[int] = None        # expert width (default d_ff)
    moe_norm_topk: bool = True            # renormalise the top-k weights
    moe_routed_scale: float = 1.0         # factor on the routed weights
    n_shared_experts: int = 0             # one SwiGLU of n * moe_d_ff
    first_dense_layers: int = 0           # leading layers with a d_ff FFN
    # this chip's share: experts [offset, offset + held) of every MoE
    # layer (held None = all); the router still scores all of them
    moe_expert_offset: int = 0
    moe_experts_held: Optional[int] = None

    # latent attention (MLA, DeepSeek-V2): kv_lora_rank > 0 turns it on;
    # one cached row per token of kv_lora_rank + qk_rope_head_dim values
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    yarn: Optional[Yarn] = None           # rope scaling (None = plain)

    # attention details
    head_dim: Optional[int] = None
    rope_theta: float = 10_000.0
    window: Optional[int] = None          # sliding-window size (local attn)
    attn_pattern: Tuple[str, ...] = ()    # per-layer kind; default all "attn"
    use_bias: bool = False
    norm: str = "rmsnorm"                 # "rmsnorm" | "layernorm"
    act_fn: str = "silu"                  # ffn nonlinearity
    gated_ffn: bool = True                # SwiGLU/GeGLU style
    tied_embeddings: bool = False
    embed_scale: bool = False             # gemma-style sqrt(d) embed scaling

    # ssm (rwkv6)
    rwkv_head_dim: int = 64
    rwkv_chunk: int = 128
    rwkv_fused: int = 0                   # fuse token-shift projections

    # hybrid (recurrentgemma)
    rglru_width: Optional[int] = None     # recurrent branch width (d_model)
    conv_width: int = 4

    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_len: int = 1500               # stub frame-embedding count

    # vlm
    prefix_len: int = 0                   # stub patch-embedding count

    # execution knobs
    moe_impl: str = "dense"               # "dense" | "shard_map" (EP)
    decode_impl: str = "xla"              # attention backend: any spelling
    #                                       from kernels/dispatch.py, e.g.
    #                                       "flash_pallas" (fused packed-KV
    #                                       kernel) or the composed
    #                                       "flash_shmap+flash_pallas"
    matmul_impl: str = "xla"              # GEMM backend for pdot/peinsum:
    #                                       "xla" or "qmm_pallas" (fused
    #                                       transprecision GEMV over the
    #                                       packed weight store)
    attn_chunk: int = 4096                # q-chunk for long prefill
    loss_chunks: int = 4                  # chunked cross-entropy
    remat: bool = True

    def __post_init__(self):
        from repro.kernels.dispatch import validate_impl, validate_matmul_impl
        validate_impl(self.decode_impl, allow_none=False,
                      what="ModelConfig.decode_impl")
        validate_matmul_impl(self.matmul_impl, allow_none=False,
                             what="ModelConfig.matmul_impl")
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.d_model // max(self.n_heads, 1))
        if not self.attn_pattern:
            if self.family == "ssm":
                pat = ("rwkv",) * self.n_layers
            elif self.family == "hybrid":
                # recurrentgemma: 2 recurrent blocks then 1 local-attention
                pat = tuple("attn" if (i % 3) == 2 else "rglru"
                            for i in range(self.n_layers))
            else:
                pat = ("attn",) * self.n_layers
            object.__setattr__(self, "attn_pattern", pat)
        if self.rglru_width is None and self.family == "hybrid":
            object.__setattr__(self, "rglru_width", self.d_model)
        if self.moe_d_ff is None:
            object.__setattr__(self, "moe_d_ff", self.d_ff)
        if self.moe_experts_held is None:
            object.__setattr__(self, "moe_experts_held",
                               self.moe_experts - self.moe_expert_offset)
        if self.moe_experts and not (
                0 <= self.moe_expert_offset
                and 0 < self.moe_experts_held
                and self.moe_expert_offset + self.moe_experts_held
                <= self.moe_experts):
            raise ValueError(
                f"expert share [{self.moe_expert_offset}, "
                f"{self.moe_expert_offset + self.moe_experts_held}) does "
                f"not lie within the {self.moe_experts} routed experts")
        if self.kv_lora_rank:
            object.__setattr__(self, "head_dim", self.qk_nope_head_dim
                               + self.qk_rope_head_dim)

    # ---- derived sizes ------------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv * self.head_dim

    @property
    def latent(self) -> bool:
        """Latent attention (MLA): one shared cached row per token."""
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Values in one cached latent row: the compressed KV plus the
        shared rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_moe_layer(self, li: int) -> bool:
        return bool(self.moe_experts) and li >= self.first_dense_layers

    def _attn_params(self) -> int:
        d, H = self.d_model, self.n_heads
        if self.latent:
            return (d * H * self.head_dim                    # q
                    + d * self.latent_dim                    # kv_a
                    + self.kv_lora_rank                      # latent norm
                    + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                               + self.v_head_dim)  # kv_b
                    + H * self.v_head_dim * d)               # o
        return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d

    def _ffn_params(self, ff: int) -> int:
        d = self.d_model
        if self.gated_ffn:
            return 3 * d * ff
        return 2 * d * ff + (ff + d if self.use_bias else 0)

    def _moe_params(self, experts: int) -> int:
        """Router (all routed experts), ``experts`` routed experts and the
        shared experts of one MoE layer."""
        d, ff = self.d_model, self.moe_d_ff
        per = 3 * d * ff if self.gated_ffn else 2 * d * ff
        return (d * self.moe_experts + experts * per
                + self._ffn_params(self.n_shared_experts * ff)
                * bool(self.n_shared_experts))

    def param_count(self, experts: Optional[int] = None) -> int:
        """Exact parameter count (cross-checked against init in tests):
        of this chip's expert share, or with ``experts`` routed experts
        per MoE layer (``moe_experts`` for the whole published model)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab
        if experts is None:
            experts = self.moe_experts_held
        nrm = d if self.norm == "rmsnorm" else 2 * d  # gamma (+beta)
        attn_p = self._attn_params()
        ffn_p = self._ffn_params(ff)
        n = v * d  # embedding
        if not self.tied_embeddings:
            n += v * d
        for li, kind in enumerate(self.attn_pattern):
            n += 2 * nrm  # norm1 + norm2
            if kind == "attn":
                n += attn_p
            elif kind == "rwkv":
                # time-mix: 5 square proj + mu(5d) + w0/u (2d) + rank-64
                # decay lora (128d) + per-head groupnorm (2d)
                n += 5 * d * d + 137 * d
                # channel-mix: cm_mu(2d) + k/v (2*d*ff) + receptance (d^2)
                n += 2 * d + 2 * d * ff + d * d
            elif kind == "rglru":
                w = self.rglru_width
                n += 2 * d * w + w * d            # branch, gate, out
                n += w * self.conv_width + w      # conv + bias
                n += 2 * w * w + w                # rec/in gates + lambda
            if kind != "rwkv":
                n += (self._moe_params(experts) if self.is_moe_layer(li)
                      else ffn_p)
        n += nrm  # final norm
        if self.encoder_layers:
            per = attn_p + 2 * nrm + ffn_p
            n += self.encoder_layers * per          # encoder blocks
            n += len(self.attn_pattern) * (attn_p + nrm)  # cross-attn
        return n

    def active_param_count(self) -> int:
        """Params one token multiplies by in the published model: top-k
        routed experts of each MoE layer, and no untied input embedding
        (a row lookup, not a product).  A tied table is the LM head and
        counts once."""
        n = self.param_count(experts=self.moe_topk)
        if not self.tied_embeddings:
            n -= self.vocab * self.d_model
        return n
