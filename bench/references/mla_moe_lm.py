"""Plain reference of a latent-attention mixture-of-experts language model
(DeepSeek-V2, arXiv:2405.04434), teacher-forced.

The architecture: token embedding; per layer RMSNorm, latent attention, a
residual add, RMSNorm, then a SwiGLU feed-forward block (the first
``first_k_dense_replace`` layers) or a mixture of SwiGLU experts plus the
shared experts, and a residual add; a final RMSNorm and an untied LM head.

Latent attention (no query compression): ``q = x W_q`` gives each head
``qk_nope_head_dim`` values and ``qk_rope_head_dim`` rotated ones;
``x W_kv_a`` gives the latent ``c`` (``kv_lora_rank`` values, then
RMS-normed) and one rotated key ``k_pe`` that every head shares; ``c
W_kv_b`` gives each head its unrotated key and its value.  Scores are
``q . [k_nope, k_pe]`` times ``qk_head ** -0.5`` times YaRN's attention
factor squared.  This reference computes exactly that (the un-absorbed
formulation); it never carries the query into the latent space.  Rotary
positions use YaRN's frequencies (``rope_scaling``) and rotate the two
halves of each head together.

The experts: a float32 softmax over all ``expert_share.of`` routed
experts, the top ``num_experts_per_tok``, weights as they come
(``norm_topk_prob`` false) times ``routed_scaling_factor``.  Only the
experts ``[offset, offset + n_routed_experts)`` of the configuration's
``expert_share`` are computed: this chip's part of the layer, as the
configuration's deployment divides it.  The shared experts are one SwiGLU
of ``n_shared_experts * moe_intermediate_size``, added to every token.

It is written from the configuration file alone and imports nothing of the
system under test.  The weights are drawn from the run's seed by the
recipe the configuration's ``init`` names (``normal_fan_in``): one key per
leaf along a fixed split tree, each leaf ``N(0, 1) / sqrt(rows)`` (the
embedding ``N(0, 1)``; a stack of experts is one draw whose rows are the
experts, of which the share is the slice), rounded to the weights' type,
so the reference holds the checkpoint the served model holds.

Precision follows the configuration's ``precision`` table: weights and
activations in bfloat16 at every edge where the configuration stores an
activation, products accumulated in float32 (``default_matmul_precision
("highest")`` for float32 operands), the router's softmax and attention's
softmax and mix in float32, and the latent cache rounded to its stored
format for every position that reads it from the cache (the decoded
tokens), while a prompt attends over its own unrounded keys and values.
``control=True`` rounds every weight matrix to float8 (4 exponent, 3
mantissa bits) with a scale per output column; ``activations="float32"``
keeps every activation in float32.

It runs layer by layer over a batch of whole sequences, attention one
sequence and one block of queries at a time and the feed-forward blocks
over blocks of rows, and reports per position only the best logit, its
token, and the logit of a given token.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
F32 = jnp.float32
Q_BLOCK = 512          # query rows per attention block
ROW_BLOCK = 4096       # rows per feed-forward block
HEAD_BLOCK = 256       # positions per LM-head block
E4M3_MAX = 240.0
BITS = {"float8_e5m2": (5, 2), "float8_e4m3": (4, 3), "bfloat16": (8, 7)}


def _round(x, fmt: str):
    e, m = BITS[fmt]
    return jax.lax.reduce_precision(x.astype(F32), exponent_bits=e,
                                    mantissa_bits=m)


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, F32) * scale


def _fan_in(key, shape):
    return _normal(key, shape, 1.0 / math.sqrt(shape[0]))


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


class Dims:
    def __init__(self, conf):
        self.d = int(conf["hidden_size"])
        self.heads = int(conf["num_attention_heads"])
        self.rank = int(conf["kv_lora_rank"])
        self.nope = int(conf["qk_nope_head_dim"])
        self.rope = int(conf["qk_rope_head_dim"])
        self.dv = int(conf["v_head_dim"])
        self.dq = self.nope + self.rope
        self.ff = int(conf["intermediate_size"])
        self.moe_ff = int(conf["moe_intermediate_size"])
        self.layers = int(conf["num_hidden_layers"])
        self.dense = int(conf["first_k_dense_replace"])
        self.vocab = int(conf["vocab_size"])
        share = conf["expert_share"]
        self.experts = int(share["of"])
        self.lo = int(share["offset"])
        self.held = int(conf["n_routed_experts"])
        self.topk = int(conf["num_experts_per_tok"])
        self.norm_topk = bool(conf["norm_topk_prob"])
        self.routed_scale = float(conf["routed_scaling_factor"])
        self.shared = int(conf["n_shared_experts"])
        self.eps = float(conf["rms_norm_eps"])
        self.theta = float(conf["rope_theta"])
        y = conf["rope_scaling"]
        if y.get("type") != "yarn":
            raise ValueError(f"unknown rope scaling {y.get('type')!r}")
        self.yarn = y
        self.scale = self.dq ** -0.5 * _mscale(
            float(y["factor"]), float(y["mscale_all_dim"])) ** 2
        self.kv_fmt = conf["precision"]["kv_cache"]
        if self.kv_fmt not in BITS:
            raise ValueError(f"unknown KV format {self.kv_fmt!r}")
        if conf["precision"]["weights"] != "bfloat16" or \
                conf["precision"]["activations"] != "bfloat16":
            raise ValueError("this reference states bfloat16 weights and "
                             "activations")
        if conf.get("init") != "normal_fan_in":
            raise ValueError(f"unknown init recipe {conf.get('init')!r}")
        if conf.get("tie_word_embeddings"):
            raise ValueError("this reference states an untied LM head")

    def rope_freqs(self):
        """YaRN: interpolated (``/ factor``) and extrapolated frequencies,
        blended by a linear ramp over the correction range."""
        y, dim, base = self.yarn, self.rope, self.theta
        ext = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        orig = float(y["original_max_position_embeddings"])

        def corr(rot):
            return (dim * math.log(orig / (rot * 2 * math.pi))
                    / (2 * math.log(base)))

        low = max(math.floor(corr(float(y["beta_fast"]))), 0)
        high = min(math.ceil(corr(float(y["beta_slow"]))), dim - 1)
        span = (high - low) if high != low else 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / span, 0.0, 1.0)
        extrapolate = 1.0 - ramp
        f = ext / float(y["factor"]) * ramp + ext * extrapolate
        m = (_mscale(float(y["factor"]), float(y["mscale"]))
             / _mscale(float(y["factor"]), float(y["mscale_all_dim"])))
        return f.astype(np.float32), np.float32(m)


def _quant_fp8(w):
    """float8 (4 exponent, 3 mantissa bits) with one scale per output
    column (the last axis), in bfloat16."""
    wf = w.astype(F32)
    s = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (_round(wf / s, "float8_e4m3") * s).astype(BF16)


class Reference:
    """``logit_stats(hidden(tokens, prompt_lens), pick)`` -> per-position
    (best logit, its token, logit of ``pick``) as numpy arrays (B, S)."""

    def __init__(self, conf, seed_key, *, control: bool = False,
                 activations: str = "bfloat16"):
        self.dims = dm = Dims(conf)
        if activations not in ("bfloat16", "float32"):
            raise ValueError(f"unknown activation type {activations!r}")
        self.act = BF16 if activations == "bfloat16" else F32
        self.keys = jax.random.split(seed_key, dm.layers + 3)
        post = _quant_fp8 if control else (lambda w: w)

        def ffn(k, width):
            kf = jax.random.split(k, 3)
            return {"w_in": _fan_in(kf[0], (dm.d, width)),
                    "w_out": _fan_in(kf[1], (width, dm.d)),
                    "w_gate": _fan_in(kf[2], (dm.d, width))}

        def gen_layer(k, moe):
            ks = jax.random.split(k, 4)
            ka = jax.random.split(ks[0], 4)
            w = {"wq": _fan_in(ka[0], (dm.d, dm.heads * dm.dq)),
                 "wkv_a": _fan_in(ka[1], (dm.d, dm.rank + dm.rope)),
                 "wkv_b": _fan_in(ka[2], (dm.rank,
                                          dm.heads * (dm.nope + dm.dv))),
                 "wo": _fan_in(ka[3], (dm.heads * dm.dv, dm.d))}
            if moe:
                km = jax.random.split(ks[1], 4)
                E, lo, n = dm.experts, dm.lo, dm.held

                def share(kk, shape):
                    return _fan_in(kk, (E,) + shape)[lo:lo + n]

                router = _fan_in(km[0], (dm.d, E))
                w.update(w_in=share(km[1], (dm.d, dm.moe_ff)),
                         w_out=share(km[2], (dm.moe_ff, dm.d)),
                         w_gate=share(km[3], (dm.d, dm.moe_ff)))
                w.update({"s_" + n_: a for n_, a in ffn(
                    jax.random.fold_in(ks[1], 1),
                    dm.shared * dm.moe_ff).items()})
            else:
                w.update(ffn(ks[1], dm.ff))
            w = {n_: post(a.astype(BF16)) for n_, a in w.items()}
            if moe:
                # stored in float32; the router's product takes the
                # activations' type (bfloat16 operands, float32 sums)
                w["router"] = router.astype(BF16)
            return w

        def gen_embed(k_embed, k_head):
            emb = _normal(k_embed, (dm.vocab, dm.d), 1.0).astype(BF16)
            return emb, post(_fan_in(k_head, (dm.d, dm.vocab)).astype(BF16))

        self._gen_dense = jax.jit(lambda k: gen_layer(k, False))
        self._gen_moe = jax.jit(lambda k: gen_layer(k, True))
        self._gen_embed = jax.jit(gen_embed)
        self._attn_fn = jax.jit(self._attention)
        self._ffn_fn = jax.jit(self._ffn_block, static_argnums=2)
        self._head = jax.jit(self._head_fn)

    def _gen_layer(self, k, li: int = 0):
        """Layer ``li``'s weights (``li`` picks dense or experts; the
        float8 control's check reads ``_gen_layer(keys[2])["wq"]``)."""
        moe = li >= self.dims.dense
        return (self._gen_moe if moe else self._gen_dense)(k)

    # -- pieces ---------------------------------------------------------------
    def _mm(self, x, w):
        with jax.default_matmul_precision("highest"):
            if self.act == F32:
                return jnp.einsum("...i,io->...o", x.astype(F32),
                                  w.astype(F32))
            return jnp.einsum("...i,io->...o", x.astype(BF16),
                              w.astype(BF16), preferred_element_type=F32)

    def _norm(self, x):
        xf = x.astype(F32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                               + self.dims.eps)
        return y.astype(self.act)

    def _rope(self, x, pos):
        freqs, m = self.dims.rope_freqs()
        half = x.shape[-1] // 2
        ang = pos[..., None].astype(F32) * freqs
        cos = (jnp.cos(ang) * m)[..., None, :]
        sin = (jnp.sin(ang) * m)[..., None, :]
        x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1).astype(self.act)

    def _attention(self, h, w, prompt_lens):
        """Latent attention of ``h`` (B, S, d), one sequence at a time."""
        dm, act = self.dims, self.act
        B, S, _ = h.shape
        H = dm.heads
        q = self._mm(h, w["wq"]).astype(act).reshape(B, S, H, dm.dq)
        kv = self._mm(h, w["wkv_a"]).astype(act)
        c = self._norm(kv[..., :dm.rank])
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        q = jnp.concatenate([q[..., :dm.nope],
                             self._rope(q[..., dm.nope:], pos)], -1)
        k_pe = self._rope(kv[..., None, dm.rank:], pos)[:, :, 0]
        w_b = w["wkv_b"].reshape(dm.rank, H, dm.nope + dm.dv)

        def keys_values(c_, kpe_, rounded):
            """Per-head keys (S, H, dq) and values (S, H, dv) of one
            sequence: from the stored latent (rounded, float32 products,
            as a decoded token reads them) or from the prompt's own."""
            if rounded:
                c_ = _round(c_, dm.kv_fmt)
                kpe_ = _round(kpe_, dm.kv_fmt)
            with jax.default_matmul_precision("highest"):
                kvh = jnp.einsum("sr,rhe->she", c_.astype(F32),
                                 w_b.astype(F32))
            if not rounded:
                kvh = kvh.astype(act)
            kvh = kvh.astype(F32)
            k = jnp.concatenate(
                [kvh[..., :dm.nope],
                 jnp.broadcast_to(kpe_.astype(F32)[:, None, :],
                                  (S, H, dm.rope))], -1)
            return k, kvh[..., dm.nope:]

        nb = -(-S // Q_BLOCK)
        pad = nb * Q_BLOCK - S

        def one(args):
            qs, c_, kpe_, plen = args
            kf, vf = keys_values(c_, kpe_, False)
            kc, vc = keys_values(c_, kpe_, True)
            qb = jnp.pad(qs.astype(F32), ((0, pad), (0, 0), (0, 0)))
            qb = qb.reshape(nb, Q_BLOCK, H, dm.dq)

            def block(args):
                qq, i = args
                q0 = i * Q_BLOCK
                qpos = q0 + jnp.arange(Q_BLOCK)
                causal = jnp.arange(S)[None, :] <= qpos[:, None]

                def mix(kk, vv):
                    with jax.default_matmul_precision("highest"):
                        s = jnp.einsum("qhd,khd->hqk", qq, kk) * dm.scale
                        s = jnp.where(causal, s, -jnp.inf)
                        return jnp.einsum("hqk,khd->qhd",
                                          jax.nn.softmax(s, -1), vv)

                decoded = (qpos >= plen)[:, None, None]
                # the prompt's own keys, the stored ones, or both
                return jax.lax.switch(
                    jnp.where(q0 + Q_BLOCK <= plen, 0,
                              jnp.where(q0 >= plen, 1, 2)),
                    [lambda: mix(kf, vf), lambda: mix(kc, vc),
                     lambda: jnp.where(decoded, mix(kc, vc), mix(kf, vf))])

            o = jax.lax.map(block, (qb, jnp.arange(nb)))
            return o.reshape(nb * Q_BLOCK, H, dm.dv)[:S]

        o = jax.lax.map(one, (q, c, k_pe, prompt_lens))
        o = o.astype(act).reshape(B, S, H * dm.dv)
        return self._mm(o, w["wo"]).astype(act)

    def _swiglu(self, h, w_in, w_gate, w_out):
        a = jax.nn.silu(self._mm(h, w_in)) * self._mm(h, w_gate)
        return self._mm(a.astype(self.act), w_out).astype(self.act)

    def _moe(self, h, w):
        dm = self.dims
        probs = jax.nn.softmax(self._mm(h, w["router"]), axis=-1)
        top_p, top_e = jax.lax.top_k(probs, dm.topk)
        if dm.norm_topk:
            top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        top_p = top_p * dm.routed_scale
        gate = jnp.sum(jax.nn.one_hot(top_e, dm.experts, dtype=F32)
                       * top_p[..., None], axis=-2)[..., dm.lo:dm.lo + dm.held]

        def one(acc, e):
            y = self._swiglu(h, w["w_in"][e], w["w_gate"][e],
                             w["w_out"][e]).astype(F32)
            return acc + gate[..., e, None] * y, None

        acc, _ = jax.lax.scan(one, jnp.zeros(h.shape, F32),
                              jnp.arange(dm.held))
        shared = self._swiglu(h, w["s_w_in"], w["s_w_gate"], w["s_w_out"])
        return (acc.astype(self.act).astype(F32)
                + shared.astype(F32)).astype(self.act)

    def _ffn_block(self, x, w, moe):
        """The residual feed-forward half of a layer, over row blocks."""
        B, S, d = x.shape
        rows = x.reshape(B * S, d)
        n = -(-rows.shape[0] // ROW_BLOCK)
        rows = jnp.pad(rows, ((0, n * ROW_BLOCK - B * S), (0, 0)))

        def block(r):
            h = self._norm(r)
            f = (self._moe(h, w) if moe
                 else self._swiglu(h, w["w_in"], w["w_gate"], w["w_out"]))
            return (r + f).astype(self.act)

        out = jax.lax.map(block, rows.reshape(n, ROW_BLOCK, d))
        return out.reshape(-1, d)[:B * S].reshape(B, S, d)

    def _head_fn(self, x, head, pick):
        h = self._norm(x)
        best, arg, got = [], [], []
        for p0 in range(0, x.shape[1], HEAD_BLOCK):
            lg = self._mm(h[:, p0:p0 + HEAD_BLOCK], head)
            best.append(jnp.max(lg, -1))
            arg.append(jnp.argmax(lg, -1).astype(jnp.int32))
            got.append(jnp.take_along_axis(
                lg, pick[:, p0:p0 + HEAD_BLOCK, None], -1)[..., 0])
        return (jnp.concatenate(best, 1), jnp.concatenate(arg, 1),
                jnp.concatenate(got, 1))

    # -- entry points ---------------------------------------------------------
    def hidden(self, tokens, prompt_lens):
        """Final-layer activations (B, S, d) of ``tokens`` (B, S) int32."""
        tokens = jnp.asarray(tokens, jnp.int32)
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
        emb, head = self._gen_embed(self.keys[0], self.keys[1])
        self._head_w = head
        x = jnp.take(emb, tokens, axis=0).astype(self.act)
        del emb
        for li in range(self.dims.layers):
            w = self._gen_layer(self.keys[2 + li], li)
            x = (x + self._attn_fn(self._norm(x), w, prompt_lens)
                 ).astype(self.act)
            x = self._ffn_fn(x, w, li >= self.dims.dense)
            del w
        return x

    def logit_stats(self, x, pick):
        """(best logit, best token, logit of ``pick``) per position of the
        final activations ``x``, as numpy arrays."""
        out = self._head(x, self._head_w, jnp.asarray(pick, jnp.int32))
        return tuple(np.asarray(a) for a in out)
