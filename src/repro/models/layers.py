"""Policy-aware neural-net primitives shared by all architectures.

Every parameter-consuming op routes through :func:`pdot` /
:func:`peinsum` / :func:`pgrouped_dot`, which implement the transprecision
contract: operands in their assigned storage formats, accumulation in f32
(the MXU/FlexFloat "compute wide" rule), results re-sanitized (emulated
mode) or kept in the activation dtype (native mode).

The *implementation* of each contraction is resolved through the
matmul-backend registry (``kernels/dispatch.py``, knob
``matmul_impl`` on policies/configs/shapes):

``"xla"``
    ``jnp.dot``/``jnp.einsum``; packed (:class:`QTensor`) weights from the
    packed parameter store (``models/qparams.py``) are dequantized through
    XLA first -- the oracle and the honest CPU baseline.
``"qmm_pallas"``
    the fused transprecision GEMV/GEMM kernel (``kernels/qmatmul.py``):
    packed weight tiles stream from HBM at container width (4x fewer bytes
    than f32 for binary8), decoded in-register via the shared codec, with
    bias + nonlinearity + gate + output quantize fused into the epilogue
    (see :func:`ffn_apply`).  Plain-array weights fall back to the XLA
    path -- only a packed store shrinks bytes.

This module registers both backends at import time; no other module under
``models/`` may call ``jnp.dot``/``jnp.einsum`` directly (a grep-level test
enforces it), so every new layer inherits the registry.  Activation-only
contractions with no parameter operand use :func:`aeinsum`.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flexfloat import quantize
from repro.core.policy import PrecisionPolicy
from repro.core.qtensor import QTensor
from repro.kernels import dispatch
from repro.kernels.qmatmul import _apply_act, qmatmul, qmatmul_ragged, qmm_ffn


# ---------------------------------------------------------------------------
# initialization
# ---------------------------------------------------------------------------

def dense_init(key, shape, scale: Optional[float] = None, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) >= 2 else 1
    if scale is None:
        scale = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# transprecision matmul / elementwise helpers (registry-routed)
# ---------------------------------------------------------------------------

def _impl(policy: PrecisionPolicy) -> str:
    return policy.matmul_impl or "xla"


def pdot(x, w, policy: PrecisionPolicy, role: str, *, out_act: bool = True):
    """x @ w with the transprecision contract for weight-role ``role``.

    ``w`` is a plain array or a packed :class:`QTensor` leaf from the
    packed parameter store; the backend comes from ``policy.matmul_impl``.
    """
    return dispatch.resolve_matmul(_impl(policy)).dot(
        x, w, policy, role, out_act=out_act)


def peinsum(expr, a, b, policy: PrecisionPolicy, role: str, *,
            out_act: bool = True):
    return dispatch.resolve_matmul(_impl(policy)).einsum(
        expr, a, b, policy, role, out_act=out_act)


def pgrouped_dot(a, w, policy: PrecisionPolicy, role: str):
    """Batched expert matmul ``(E, M, K) @ (E, K, N) -> (E, M, N)`` (MoE
    grouped FFN).  Returns raw f32 (callers ``act_cast`` as needed)."""
    return dispatch.resolve_matmul(_impl(policy)).grouped(a, w, policy, role)


def pragged_dot(a, w, group_sizes, policy: PrecisionPolicy, role: str):
    """Grouped matmul over row groups: rows ``[sum(gs[:g]), sum(gs[:g+1]))``
    of ``a`` (M, K) times ``w[g]`` (G, K, N) -> (M, N) raw f32 (dropless
    MoE experts).  Rows past ``sum(group_sizes)`` are left undefined;
    callers mask them."""
    return dispatch.resolve_matmul(_impl(policy)).ragged(
        a, w, group_sizes, policy, role)


def aeinsum(expr, *ops):
    """Activation-only einsum: no parameter operand, so no registry --
    always f32 math (the wide-accumulation rule for intermediates)."""
    return jnp.einsum(expr, *[o.astype(jnp.float32) for o in ops],
                      preferred_element_type=jnp.float32)


def _finish(y, policy: PrecisionPolicy, out_act: bool):
    """The contract's output edge: sanitize (emulated) / act dtype (native)."""
    if not out_act:
        return y
    if policy.mode == "native":
        return y.astype(policy.dtype("act"))
    return quantize(y, policy.fmt("act"))


# -- the "xla" backend -------------------------------------------------------

def _dot_xla(x, w, policy, role, *, out_act=True):
    if isinstance(w, QTensor):
        # the dequantize path: exact f32 expansion of the packed store,
        # f32 math (the compute-wide contract the kernel also honors)
        y = jnp.dot(x.astype(jnp.float32), w.dequantize(),
                    preferred_element_type=jnp.float32)
        return _finish(y, policy, out_act)
    if policy.mode == "native":
        # narrow operands, f32 accumulation, result back in activation dtype
        cd = jnp.bfloat16
        if w.dtype == jnp.float32 and x.dtype == jnp.float32:
            cd = jnp.float32
        y = jnp.dot(x.astype(cd), w.astype(cd),
                    preferred_element_type=jnp.float32)
        return y.astype(policy.dtype("act")) if out_act else y
    y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    return quantize(y, policy.fmt("act")) if out_act else y


def _einsum_xla(expr, a, b, policy, role, *, out_act=True):
    if isinstance(a, QTensor) or isinstance(b, QTensor):
        af = a.dequantize() if isinstance(a, QTensor) else a.astype(
            jnp.float32)
        bf = b.dequantize() if isinstance(b, QTensor) else b.astype(
            jnp.float32)
        y = jnp.einsum(expr, af, bf, preferred_element_type=jnp.float32)
        return _finish(y, policy, out_act)
    if policy.mode == "native":
        cd = jnp.bfloat16
        if a.dtype == jnp.float32 and b.dtype == jnp.float32:
            cd = jnp.float32
        y = jnp.einsum(expr, a.astype(cd), b.astype(cd),
                       preferred_element_type=jnp.float32)
        return y.astype(policy.dtype("act")) if out_act else y
    y = jnp.einsum(expr, a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return quantize(y, policy.fmt("act")) if out_act else y


def _grouped_xla(a, w, policy, role):
    if isinstance(w, QTensor):
        return jnp.einsum("eck,ekn->ecn", a.astype(jnp.float32),
                          w.dequantize(), preferred_element_type=jnp.float32)
    if policy.mode == "native" and not (a.dtype == w.dtype == jnp.float32):
        cd = jnp.bfloat16
        return jnp.einsum("eck,ekn->ecn", a.astype(cd), w.astype(cd),
                          preferred_element_type=jnp.float32)
    return jnp.einsum("eck,ekn->ecn", a.astype(jnp.float32),
                      w.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _ragged_xla(a, w, group_sizes, policy, role):
    if isinstance(w, QTensor):
        a, w = a.astype(jnp.float32), w.dequantize()
    elif policy.mode == "native" and not (a.dtype == w.dtype == jnp.float32):
        a, w = a.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    else:
        a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    return jax.lax.ragged_dot(a, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32)


@dispatch.register_matmul("xla")
class _XlaMatmul:
    dot = staticmethod(_dot_xla)
    einsum = staticmethod(_einsum_xla)
    grouped = staticmethod(_grouped_xla)
    ragged = staticmethod(_ragged_xla)


# -- the "qmm_pallas" backend ------------------------------------------------

def _out_fmt(policy, out_act):
    """Output sanitization the kernel fuses (emulated mode only; native
    casts to the act dtype outside -- a free elementwise op)."""
    return policy.fmt("act") if (out_act and policy.mode == "emulated") \
        else None


def _dot_qmm(x, w, policy, role, *, out_act=True):
    if not isinstance(w, QTensor):
        return _dot_xla(x, w, policy, role, out_act=out_act)
    lead, K = x.shape[:-1], x.shape[-1]
    y = qmatmul(x.reshape(-1, K).astype(jnp.float32), w.payload, None,
                w.fmt, _out_fmt(policy, out_act))
    y = y.reshape(*lead, w.shape[-1])
    if out_act and policy.mode == "native":
        y = y.astype(policy.dtype("act"))
    return y


def _einsum_qmm(expr, a, b, policy, role, *, out_act=True):
    # attention's einsums contract activations (q/k/probs/v), not
    # parameters; the kernel only wins on a packed *weight* stream, so
    # anything without one takes the XLA math verbatim
    return _einsum_xla(expr, a, b, policy, role, out_act=out_act)


def _grouped_qmm(a, w, policy, role):
    if not isinstance(w, QTensor):
        return _grouped_xla(a, w, policy, role)
    # one kernel over all experts (the group axis is a grid axis): each
    # expert's packed block streams through it once, and a model compiles
    # one Mosaic program per grouped matmul, not one per expert
    return qmatmul(a.astype(jnp.float32), w.payload, None, w.fmt)


def _ragged_qmm(a, w, group_sizes, policy, role):
    if not isinstance(w, QTensor):
        return _ragged_xla(a, w, group_sizes, policy, role)
    # the dropless expert layer: each held expert's packed block streams
    # through one kernel, for the row tiles of the pairs routed to it
    return qmatmul_ragged(a, w.payload, group_sizes, w.fmt)


@dispatch.register_matmul("qmm_pallas")
class _QmmMatmul:
    dot = staticmethod(_dot_qmm)
    einsum = staticmethod(_einsum_qmm)
    grouped = staticmethod(_grouped_qmm)
    ragged = staticmethod(_ragged_qmm)


def act_cast(x, policy: PrecisionPolicy, role: str = "act"):
    if policy.mode == "native":
        return x.astype(policy.dtype(role))
    return quantize(x, policy.fmt(role))


# ---------------------------------------------------------------------------
# norms (computed in f32 regardless of policy -- range-critical accumulations,
# exactly the variables the paper's tuner pins at binary32)
# ---------------------------------------------------------------------------

def rmsnorm(x, gamma, policy, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = y * (1.0 + gamma.astype(jnp.float32))
    return act_cast(y, policy)


def layernorm(x, gamma, beta, policy, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return act_cast(y, policy)


def apply_norm(x, p, policy, kind):
    if kind == "rmsnorm":
        return rmsnorm(x, p["gamma"], policy)
    return layernorm(x, p["gamma"], p["beta"], policy)


def norm_init(d, kind):
    if kind == "rmsnorm":
        return {"gamma": jnp.zeros((d,), jnp.float32)}
    return {"gamma": jnp.ones((d,), jnp.float32),
            "beta": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# rotary position embeddings (f32 math)
# ---------------------------------------------------------------------------

def rope_freqs(dh: int, theta: float, yarn=None) -> np.ndarray:
    """Rotary frequencies of a ``dh``-wide head, (dh // 2,) float32.  With
    ``yarn`` (:class:`~repro.models.base.Yarn`), DeepSeek-V2's blend of
    interpolated (``/ factor``) and extrapolated frequencies: a linear
    ramp over the dimensions whose wavelengths lie between ``beta_fast``
    and ``beta_slow`` rotations of the original context."""
    half = dh // 2
    freqs = np.exp(-np.log(theta) * np.arange(half) / half)  # (half,)
    if yarn is None:
        return freqs.astype(np.float32)

    def dim_of(rot):
        return (dh * math.log(yarn.original_max / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), dh - 1)
    ramp = np.clip((np.arange(half) - low)
                   / ((high - low) if high != low else 0.001), 0.0, 1.0)
    keep = 1.0 - ramp                    # 1 = extrapolate (fast dims)
    return (freqs / yarn.factor * (1.0 - keep)
            + freqs * keep).astype(np.float32)


def rope(x, positions, theta: float, yarn=None):
    """x: (..., S, H, dh); positions: (..., S).  The two halves of each
    head rotate together (``yarn``: :func:`rope_freqs`)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = rope_freqs(dh, theta, yarn)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None and yarn.rope_mscale != 1.0:
        cos = cos * np.float32(yarn.rope_mscale)
        sin = sin * np.float32(yarn.rope_mscale)
    cos = cos[..., :, None, :]  # broadcast over heads
    sin = sin[..., :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# feed-forward (dense)
# ---------------------------------------------------------------------------

def ffn_init(key, d, ff, gated, use_bias, dtype):
    ks = jax.random.split(key, 3)
    p = {"w_in": dense_init(ks[0], (d, ff), dtype=dtype),
         "w_out": dense_init(ks[1], (ff, d), dtype=dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], (d, ff), dtype=dtype)
    if use_bias:
        p["b_in"] = jnp.zeros((ff,), dtype)
        p["b_out"] = jnp.zeros((d,), dtype)
    return p


def _nonlin(x, name):
    # one nonlinearity table for the XLA paths AND the fused-kernel
    # epilogue: an act_fn that exists here but not in the kernel would
    # fail only once its weights are packed
    return _apply_act(x.astype(jnp.float32), name)


def ffn_apply(p, x, policy, cfg):
    with jax.named_scope("ffn"):
        if _impl(policy) == "qmm_pallas" and isinstance(p["w_in"], QTensor) \
                and isinstance(p.get("w_gate", p["w_in"]), QTensor):
            return _ffn_apply_fused(p, x, policy, cfg)
        h = pdot(x, p["w_in"], policy, "ffn_w", out_act=False)
        if "b_in" in p:
            h = h + p["b_in"].astype(jnp.float32)
        a = _nonlin(h, cfg.act_fn)
        if "w_gate" in p:
            g = pdot(x, p["w_gate"], policy, "ffn_w", out_act=False)
            a = a * g
        a = act_cast(a, policy)
        y = pdot(a, p["w_out"], policy, "ffn_w")
        if "b_out" in p:
            y = act_cast(y.astype(jnp.float32)
                         + p["b_out"].astype(jnp.float32), policy)
        return y


def _ffn_apply_fused(p, x, policy, cfg):
    """The decode hot loop on the packed store: ONE kernel computes
    ``act_cast(act(x @ w_in + b_in) * (x @ w_gate))`` -- both packed weight
    matrices stream through the same K sweep and the two ff-wide
    activations live only in VMEM scratch, never round-tripping HBM."""
    w_in, w_gate = p["w_in"], p.get("w_gate")
    assert w_gate is None or w_gate.fmt == w_in.fmt, (w_in.fmt, w_gate.fmt)
    lead, K = x.shape[:-1], x.shape[-1]
    a = qmm_ffn(x.reshape(-1, K).astype(jnp.float32), w_in.payload,
                w_gate.payload if w_gate is not None else None, w_in.fmt,
                bias=p["b_in"].astype(jnp.float32) if "b_in" in p else None,
                act=cfg.act_fn, out_fmt=_out_fmt(policy, True))
    if policy.mode == "native":
        a = a.astype(policy.dtype("act"))
    y = pdot(a.reshape(*lead, -1), p["w_out"], policy, "ffn_w")
    if "b_out" in p:
        y = act_cast(y.astype(jnp.float32) + p["b_out"].astype(jnp.float32),
                     policy)
    return y


# ---------------------------------------------------------------------------
# embedding + LM head (chunked cross-entropy)
# ---------------------------------------------------------------------------

def residual_add(x, y):
    """Promotion-safe residual add.  8-bit float activations refuse
    implicit promotion, so a mixed-width residual stream (e.g. a scaled
    f32 embedding plus a narrow attention branch) adds through f32
    explicitly -- the same result promotion produced for >=16-bit
    pairs."""
    if x.dtype == y.dtype:
        return x + y
    return x.astype(jnp.float32) + y.astype(jnp.float32)


def embed_lookup(table, tokens, policy, scale=False):
    e = jnp.take(table, tokens, axis=0)
    e = e.astype(policy.dtype("act") if policy.mode == "native"
                 else jnp.float32)
    if scale:
        # explicit f32: same result promotion gave for >=16-bit acts, and
        # 8-bit floats refuse implicit promotion entirely
        e = e.astype(jnp.float32) * np.sqrt(table.shape[1]).astype(np.float32)
    return act_cast(e, policy) if policy.mode == "emulated" else e


def lm_head_loss(x, head_w, labels, policy, n_chunks: int = 4,
                 label_mask=None):
    """Mean cross-entropy, computed over sequence chunks so the (B, S, V)
    logits tensor is never materialized whole (V up to 257k here)."""
    B, S, D = x.shape
    n_chunks = max(1, min(n_chunks, S))
    while S % n_chunks:
        n_chunks -= 1
    C = S // n_chunks
    total = jnp.zeros((), jnp.float32)
    count = jnp.zeros((), jnp.float32)
    for i in range(n_chunks):
        xs = jax.lax.slice_in_dim(x, i * C, (i + 1) * C, axis=1)
        ls = jax.lax.slice_in_dim(labels, i * C, (i + 1) * C, axis=1)
        logits = pdot(xs, head_w, policy, "embed_w", out_act=False)
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        nll = lse - picked
        if label_mask is not None:
            ms = jax.lax.slice_in_dim(label_mask, i * C, (i + 1) * C, axis=1)
            nll = nll * ms
            count = count + jnp.sum(ms)
        else:
            count = count + np.float32(B * C)
        total = total + jnp.sum(nll)
    return total / jnp.maximum(count, 1.0)


def lm_logits(x, head_w, policy):
    with jax.named_scope("lm_head"):
        y = pdot(x, head_w, policy, "embed_w", out_act=False)
        if policy.mode == "emulated":
            return quantize(y, policy.fmt("logits"))
        return y.astype(policy.dtype("logits"))
