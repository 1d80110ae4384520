"""Attention: GQA + RoPE, memory-safe blockwise (flash-semantics) prefill,
single-token decode against a (possibly sequence-sharded) KV cache.

The blockwise path scans over KV blocks with running (max, denom, acc)
carries so the S x S score matrix is never materialized - required for the
32k prefill shapes.  A Pallas TPU kernel with the same contract lives in
kernels/flash_attention; this jnp implementation is the oracle and the
CPU/dry-run path.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.ctx import shard_hint

NEG_INF = -1e30


def _gqa_scores(q, k):
    """q: (B,Sq,Hkv,G,hd)  k: (B,Skv,Hkv,hd) -> (B,Hkv,G,Sq,Skv) f32."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                      preferred_element_type=jnp.float32)


def _in_window(qpos, kpos, window):
    """(Sq, Skv) keys within ``window`` positions of their query, the
    query's own position included."""
    return kpos[None, :] > qpos[:, None] - window


def full_attention(q, k, v, *, causal: bool, q_offset=0,
                   kv_len: Optional[jax.Array] = None,
                   window: Optional[jax.Array] = None) -> jax.Array:
    """Direct attention (small S / decode). q: (B,Sq,Hq,hd), k/v: (B,Skv,Hkv,hd).
    ``window`` (a scalar, or None for none) masks keys more than
    window - 1 positions before their query (query i at q_offset + i)."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    s = _gqa_scores(qg, k) / jnp.sqrt(hd).astype(jnp.float32)
    Skv = k.shape[1]
    if causal:
        qpos = jnp.arange(Sq) + q_offset
        kpos = jnp.arange(Skv)
        mask = kpos[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    if kv_len is not None:
        valid = jnp.arange(Skv) < kv_len
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    if window is not None:
        near = _in_window(jnp.arange(Sq) + q_offset, jnp.arange(Skv), window)
        s = jnp.where(near[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return o.reshape(B, Sq, Hq, hd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def blockwise_attention(q, k, v, causal: bool = True,
                        kv_block: int = 512, window=None) -> jax.Array:
    """Flash-semantics attention with a custom blockwise VJP.

    q: (B,S,Hq,hd), k/v: (B,S,Hkv,hd).  Neither direction materializes the
    (S, S) score matrix: forward scans KV blocks with running (m, l, acc);
    backward recomputes per-block probabilities from the saved (m, l) row
    statistics [FlashAttention, arXiv:2205.14135].  Residuals are O(S*hd),
    which is what keeps 32k prefill training viable (a plain scan-of-softmax
    backward stores S*S/kv_block probability blocks and forces GSPMD into
    per-block regather - observed as the dominant collective in the naive
    baseline; see EXPERIMENTS.md §Perf).  ``window`` as in
    :func:`full_attention`.
    """
    if q.shape[1] % kv_block != 0:
        return full_attention(q, k, v, causal=causal, window=window)
    o, m, l = _flash_fwd_inner(q, k, v, causal, kv_block, window)
    return o


def _flash_fwd_inner(q, k, v, causal, kv_block, window=None):
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    nb = S // kv_block
    qg = q.reshape(B, S, Hkv, G, hd)
    kb = jnp.moveaxis(k.reshape(B, nb, kv_block, Hkv, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, kv_block, Hkv, hd), 1, 0)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qpos = jnp.arange(S)

    def step(carry, blk):
        m, l, acc = carry
        kj, vj, j = blk
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kj,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = j * kv_block + jnp.arange(kv_block)
            mask = kpos[None, :] <= qpos[:, None]          # (S, kv_block)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        if window is not None:
            near = _in_window(qpos, j * kv_block + jnp.arange(kv_block),
                              window)
            s = jnp.where(near[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, S), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, S, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (kb, vb, jnp.arange(nb)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    o = jnp.moveaxis(o, -2, 1).reshape(B, S, Hq, hd).astype(q.dtype)
    return o, m, l


def _flash_fwd(q, k, v, causal, kv_block, window=None):
    if q.shape[1] % kv_block != 0:
        o = full_attention(q, k, v, causal=causal, window=window)
        return o, (q, k, v, o, None, None, window)
    o, m, l = _flash_fwd_inner(q, k, v, causal, kv_block, window)
    return o, (q, k, v, o, m, l, window)


def _no_grad(window):
    """The cotangent of the integer window: none."""
    if window is None:
        return None
    return np.zeros(np.shape(window), jax.dtypes.float0)


def _flash_bwd(causal, kv_block, res, do):
    q, k, v, o, m, l, window = res
    if m is None:                       # small-shape fallback path
        _, vjp = jax.vjp(lambda q, k, v: full_attention(
            q, k, v, causal=causal, window=window), q, k, v)
        return vjp(do) + (_no_grad(window),)
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    nb = S // kv_block
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qg = q.reshape(B, S, Hkv, G, hd)
    dog = do.reshape(B, S, Hkv, G, hd).astype(jnp.float32)
    og = o.reshape(B, S, Hkv, G, hd).astype(jnp.float32)
    # D_i = sum_d dO_id * O_id   (B,Hkv,G,S)
    delta = jnp.moveaxis(jnp.sum(dog * og, axis=-1), 1, -1)
    linv = 1.0 / jnp.maximum(l, 1e-30)
    kb = jnp.moveaxis(k.reshape(B, nb, kv_block, Hkv, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, kv_block, Hkv, hd), 1, 0)
    qpos = jnp.arange(S)

    def step(dq_acc, blk):
        kj, vj, j = blk
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kj,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = j * kv_block + jnp.arange(kv_block)
            mask = kpos[None, :] <= qpos[:, None]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        if window is not None:
            near = _in_window(qpos, j * kv_block + jnp.arange(kv_block),
                              window)
            s = jnp.where(near[None, None, None], s, NEG_INF)
        p = jnp.exp(s - m[..., None]) * linv[..., None]     # normalized probs
        dv = jnp.einsum("bhgqk,bqhgd->bkhd", p, dog)        # sum over G, q
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", dog, vj.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        dq_blk = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kj,
                            preferred_element_type=jnp.float32)
        dk = jnp.einsum("bhgqk,bqhgd->bkhd", ds,
                        qg.astype(jnp.float32))
        return dq_acc + dq_blk, (dk, dv)

    dq0 = jnp.zeros((B, S, Hkv, G, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(step, dq0, (kb, vb, jnp.arange(nb)))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, S, Hkv, hd)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, S, Hkv, hd)
    return (dq.reshape(B, S, Hq, hd).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype), _no_grad(window))


blockwise_attention.defvjp(_flash_fwd, _flash_bwd)


def decode_attention(q, k_cache, v_cache, pos, window=None) -> jax.Array:
    """One-token attention. q: (B,1,Hq,hd); caches: (B,S,Hkv,hd); pos: scalar
    index of the current token (entries <= pos are valid, and with a
    ``window`` only the latest ``window`` of them)."""
    return full_attention(q, k_cache, v_cache, causal=False, kv_len=pos + 1,
                          q_offset=pos, window=window)
