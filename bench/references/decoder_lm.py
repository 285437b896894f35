"""Plain reference of a decoder-only language model, teacher-forced.

The architecture: token embedding; per layer RMSNorm, grouped-query
attention with rotary positions (the two halves of each head rotated), a
residual add, RMSNorm, then either a SwiGLU feed-forward block or a dropless
top-k mixture of SwiGLU experts, and a residual add; a final RMSNorm and the
LM head (the transposed embedding when the configuration ties them).

It is written from the configuration file alone and imports nothing of the
system under test.  The weights are drawn from the run's seed by the recipe
the configuration's ``init`` names (``normal_fan_in``): one key per leaf
along a fixed split tree, each leaf ``N(0, 1) / sqrt(rows)`` (the embedding
``N(0, 1)``), rounded to the weights' stated type.  So the reference holds
the same checkpoint the served model holds, without taking it from there.

Precision follows the configuration's ``precision`` table: weights and
activations rounded to bfloat16 at every edge where the configuration
stores an activation, products accumulated in float32, the router's
softmax in float32, the KV cache rounded to its stored format for every
position that reads it from the cache (the decoded tokens) while a prompt
attends over its own unrounded keys and values, and attention's softmax
and its mix in float32.  ``control=True`` computes the same model one step
lower: every weight matrix rounded to float8 (4 exponent and 3 mantissa
bits) with a scale per output channel.  ``activations="float32"`` computes
it one step higher: the same weights, every activation kept in float32 and
every product at full float32 precision (the KV cache still rounded to its
stored format); it shows how far rounding alone moves this model's tokens.

It runs layer by layer over a batch of whole sequences (prompt and served
tokens), so it never holds more than one layer's weights, and it reports
per position only what the comparison needs: the best logit, the position
of the best logit, and the logit of a given token.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
HEAD_BLOCK = 256       # positions per LM-head block
E4M3_MAX = 240.0       # largest finite value at 4 exponent, 3 mantissa bits
# (exponent bits, mantissa bits) of the stored formats; rounding goes
# through lax.reduce_precision, which the compiler may not elide
BITS = {"float8_e5m2": (5, 2), "float8_e4m3": (4, 3), "bfloat16": (8, 7)}


def _round(x, fmt: str):
    e, m = BITS[fmt]
    return jax.lax.reduce_precision(x.astype(F32), exponent_bits=e,
                                    mantissa_bits=m)


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, F32) * scale


def _fan_in(key, shape):
    return _normal(key, shape, 1.0 / math.sqrt(shape[0]))


class Dims:
    def __init__(self, conf):
        self.d = int(conf["hidden_size"])
        self.heads = int(conf["num_attention_heads"])
        self.kv = int(conf["num_key_value_heads"])
        self.dh = int(conf.get("head_dim") or self.d // self.heads)
        self.ff = int(conf["intermediate_size"])
        self.layers = int(conf["num_hidden_layers"])
        self.vocab = int(conf["vocab_size"])
        self.experts = int(conf.get("num_local_experts", 0) or 0)
        self.topk = int(conf.get("num_experts_per_tok", 0) or 0)
        self.tied = bool(conf.get("tie_word_embeddings", False))
        self.eps = float(conf["rms_norm_eps"])
        self.theta = float(conf["rope_theta"])
        self.scale = float(conf["attention_multiplier"])
        self.kv_fmt = conf["precision"]["kv_cache"]
        if self.kv_fmt not in BITS:
            raise ValueError(f"unknown KV format {self.kv_fmt!r}")
        if conf["precision"]["weights"] != "bfloat16" or \
                conf["precision"]["activations"] != "bfloat16":
            raise ValueError("this reference states bfloat16 weights and "
                             "activations")
        if conf.get("init") != "normal_fan_in":
            raise ValueError(f"unknown init recipe {conf.get('init')!r}")


def _quant_fp8(w):
    """Round a weight matrix to float8 (4 exponent, 3 mantissa bits) with
    one scale per output column (the last axis), returned in bfloat16."""
    wf = w.astype(F32)
    s = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (_round(wf / s, "float8_e4m3") * s).astype(BF16)


class Reference:
    """``logit_stats(tokens, prompt_lens, pick)`` -> per-position (best
    logit, its token, logit of ``pick``) as numpy arrays (B, S)."""

    def __init__(self, conf, seed_key, *, control: bool = False,
                 activations: str = "bfloat16"):
        self.dims = dm = Dims(conf)
        self.control = control
        if activations not in ("bfloat16", "float32"):
            raise ValueError(f"unknown activation type {activations!r}")
        self.act = BF16 if activations == "bfloat16" else F32
        self.keys = jax.random.split(seed_key, dm.layers + 3)
        post = _quant_fp8 if control else (lambda w: w)

        def gen_layer(k):
            ks = jax.random.split(k, 4)
            ka = jax.random.split(ks[0], 4)
            w = {"wq": _fan_in(ka[0], (dm.d, dm.heads * dm.dh)),
                 "wk": _fan_in(ka[1], (dm.d, dm.kv * dm.dh)),
                 "wv": _fan_in(ka[2], (dm.d, dm.kv * dm.dh)),
                 "wo": _fan_in(ka[3], (dm.heads * dm.dh, dm.d))}
            if dm.experts:
                km = jax.random.split(ks[1], 4)
                E = dm.experts
                router = _fan_in(km[0], (dm.d, E))
                w.update(w_in=_fan_in(km[1], (E, dm.d, dm.ff)),
                         w_out=_fan_in(km[2], (E, dm.ff, dm.d)),
                         w_gate=_fan_in(km[3], (E, dm.d, dm.ff)))
            else:
                kf = jax.random.split(ks[1], 3)
                w.update(w_in=_fan_in(kf[0], (dm.d, dm.ff)),
                         w_out=_fan_in(kf[1], (dm.ff, dm.d)),
                         w_gate=_fan_in(kf[2], (dm.d, dm.ff)))
            w = {n: post(a.astype(BF16)) for n, a in w.items()}
            if dm.experts:
                # stored in float32; the router's product takes the
                # activations' type (bfloat16 operands, float32 sums)
                w["router"] = router.astype(BF16)
            return w

        def gen_embed(k_embed, k_head):
            emb = _normal(k_embed, (dm.vocab, dm.d), 1.0).astype(BF16)
            head = emb.T if dm.tied else \
                _fan_in(k_head, (dm.d, dm.vocab)).astype(BF16)
            return emb, post(head)

        self._gen_layer = jax.jit(gen_layer)
        self._gen_embed = jax.jit(gen_embed)
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)

    # -- pieces ---------------------------------------------------------------
    def _norm(self, x):
        xf = x.astype(F32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                               + self.dims.eps)
        return y.astype(self.act)

    def _rope(self, x, pos):
        half = x.shape[-1] // 2
        freqs = np.exp(-np.log(self.dims.theta) * np.arange(half) / half)
        ang = pos[..., None].astype(F32) * freqs.astype(np.float32)
        cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
        x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1).astype(self.act)

    def _mm(self, x, w):
        if self.act == F32:
            return jnp.einsum("...i,io->...o", x.astype(F32), w.astype(F32),
                              precision=HIGHEST)
        return jnp.einsum("...i,io->...o", x.astype(BF16), w.astype(BF16),
                          preferred_element_type=F32)

    def _attention(self, h, w, prompt_lens):
        dm = self.dims
        B, S, _ = h.shape
        G = dm.heads // dm.kv
        act = self.act
        q = self._mm(h, w["wq"]).astype(act).reshape(B, S, dm.heads, dm.dh)
        k = self._mm(h, w["wk"]).astype(act).reshape(B, S, dm.kv, dm.dh)
        v = self._mm(h, w["wv"]).astype(act).reshape(B, S, dm.kv, dm.dh)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        q, k = self._rope(q, pos), self._rope(k, pos)
        # the decoded positions read every key and value from the cache,
        # stored in its own format; the prompt attends over its own
        kc, vc = _round(k, dm.kv_fmt), _round(v, dm.kv_fmt)
        kf, vf = k.astype(F32), v.astype(F32)
        qg = q.astype(F32).reshape(B, S, dm.kv, G, dm.dh)
        outs = []
        for q0 in range(0, S, Q_BLOCK):
            q1 = min(S, q0 + Q_BLOCK)
            qb = qg[:, q0:q1]
            causal = (jnp.arange(q1)[None, :]
                      <= jnp.arange(q0, q1)[:, None])       # (q, k)

            def mix(kk, vv, qb=qb, q1=q1, causal=causal):
                s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kk[:, :q1],
                               precision=HIGHEST) * dm.scale
                s = jnp.where(causal, s, -jnp.inf)
                p = jax.nn.softmax(s, axis=-1)
                return jnp.einsum("bhgqk,bkhd->bqhgd", p, vv[:, :q1],
                                  precision=HIGHEST)

            decoded = (jnp.arange(q0, q1)[None, :]
                       >= prompt_lens[:, None])[:, :, None, None, None]
            outs.append(jnp.where(decoded, mix(kc, vc), mix(kf, vf)))
        o = jnp.concatenate(outs, 1).astype(self.act).reshape(B, S, -1)
        return self._mm(o, w["wo"]).astype(self.act)

    def _swiglu(self, h, w_in, w_gate, w_out):
        a = jax.nn.silu(self._mm(h, w_in)) * self._mm(h, w_gate)
        return self._mm(a.astype(self.act), w_out).astype(self.act)

    def _moe(self, h, w):
        dm = self.dims
        logits = self._mm(h, w["router"])
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, dm.topk)
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        gate = jnp.sum(jax.nn.one_hot(top_e, dm.experts, dtype=F32)
                       * top_p[..., None], axis=-2)          # (B, S, E)

        def one(acc, e):
            y = self._swiglu(h, w["w_in"][e], w["w_gate"][e],
                             w["w_out"][e]).astype(F32)
            return acc + gate[..., e, None] * y, None

        acc, _ = jax.lax.scan(one, jnp.zeros(h.shape, F32),
                              jnp.arange(dm.experts))
        return acc.astype(self.act)

    def _layer_fn(self, x, w, prompt_lens):
        x = (x + self._attention(self._norm(x), w, prompt_lens)
             ).astype(self.act)
        h = self._norm(x)
        f = (self._moe(h, w) if self.dims.experts
             else self._swiglu(h, w["w_in"], w["w_gate"], w["w_out"]))
        return (x + f).astype(self.act)

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
            w = self._gen_layer(self.keys[2 + li])
            x = self._layer(x, w, prompt_lens)
            del w
        return x

    def logit_stats(self, x, pick):
        """(best logit, best token, logit of ``pick``) per position of the
        final activations ``x``, as numpy arrays."""
        out = self._head(x, self._head_w, jnp.asarray(pick, jnp.int32))
        return tuple(np.asarray(a) for a in out)
