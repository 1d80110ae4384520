"""Shared neural building blocks (norms, activations, RoPE, linear)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.nesting import NestedTensor
from ..kernels.nested_matmul import ops as nested_ops
from ..kernels.packed_matmul import ops as packed_ops


def pdot(x, w, precision=None, preferred=None):
    """Matmul emitting the input dtype (TPU MXU accumulates f32 internally
    and rounds on output, so a bf16-out dot is f32-accumulated on the
    target hardware).  Emitting bf16 keeps the Megatron-TP partial-sum
    all-reduces at 2 bytes/elem instead of 4 (§Perf change P2)."""
    return jnp.matmul(x, w, preferred_element_type=preferred,
                      precision=precision)


def resolve_weight(w, dtype):
    """NestedTensor leaves are dequantized on the fly, honouring the
    stamped serving mode.  Fallback for non-matmul uses (embedding gather,
    stacked expert einsums); the matmul hot path is :func:`packed_linear`."""
    if isinstance(w, NestedTensor):
        return w.dequant(dtype)
    return w


def packed_linear(x: jax.Array, nt: NestedTensor, out_dtype=None) -> jax.Array:
    """Matmul straight from the packed NestQuant words - the serving path
    never materializes a dense weight.

    Dispatch by the stamped serving ``rung``: the base rung streams
    ``w_base`` alone through kernels/packed_matmul with the inflated scale
    s*2^(n-h) (Eq. 10); one resident delta takes the fused dual-stream
    kernel (kernels/nested_matmul, the 2-stream fast path); deeper rungs
    take the general K-stream ladder kernel (DESIGN.md Sec. 8).  Pallas on
    TPU, jnp reference on CPU (same storage, same numbers).  Leaves with
    stacked leading dims fall back to on-the-fly dequant inside the jit;
    the MoE serving path reads its expert stacks through
    kernels/expert_matmul instead (models/moe.py)."""
    if nt.w_base.ndim != 2:
        return pdot(x, nt.dequant(x.dtype), preferred=out_dtype)
    r = nt.rung
    rung_scale = nt.rung_scale(r).reshape(1, -1)
    if r == 0:
        return packed_ops.packed_matmul(x, nt.w_base, rung_scale,
                                        k=nt.bits[0], K=nt.K, block_k=nt.block,
                                        out_dtype=out_dtype)
    if r == 1:
        return nested_ops.nested_matmul(x, nt.w_base, nt.deltas[0], rung_scale,
                                        n=nt.bits[1], h=nt.bits[0], K=nt.K,
                                        block_k=nt.block, out_dtype=out_dtype)
    return nested_ops.ladder_matmul(x, (nt.w_base,) + nt.deltas[:r],
                                    rung_scale, bits=nt.bits[:r + 1], K=nt.K,
                                    block_k=nt.block, out_dtype=out_dtype)


def linear(x: jax.Array, w, b=None) -> jax.Array:
    if isinstance(w, NestedTensor):
        y = packed_linear(x, w)
    else:
        y = pdot(x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y.astype(x.dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm(x, params, kind: str):
    if kind == "rmsnorm":
        return rms_norm(x, params["scale"])
    return layer_norm(x, params["scale"], params["bias"])


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def yarn_freqs(head_dim: int, theta: float, factor: float,
               original_max_len: int, beta_fast: float,
               beta_slow: float) -> jax.Array:
    """YaRN inverse frequencies, as ``transformers`` defines them: the
    interpolated (over ``factor``) and the extrapolated frequencies,
    blended by a linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow`` rotations in ``original_max_len``."""
    def corr(rot):
        return (head_dim * math.log(original_max_len / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    pos = theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    extrapolate = 1.0 - ramp
    return ((1.0 / (factor * pos)) * (1.0 - extrapolate)
            + (1.0 / pos) * extrapolate)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: Optional[jax.Array] = None,
               scale: Optional[jax.Array] = None) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).
    ``freqs`` (hd/2,) replaces the plain frequencies of ``theta`` and
    ``scale`` multiplies cos and sin (YaRN's attention factor)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                   # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                    # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mlp(x, params, act: str):
    if act == "swiglu":
        g = linear(x, params["w_gate"]["w"])
        u = linear(x, params["w_up"]["w"])
        return linear(silu(g) * u, params["w_down"]["w"])
    u = linear(x, params["w_up"]["w"])
    return linear(gelu(u), params["w_down"]["w"])
