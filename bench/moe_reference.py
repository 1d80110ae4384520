"""The plain reference of the mixture-of-experts decoder, in float32.

It imports nothing of the program under test.  Weights are made again
from the configuration's weight seed by ``moe_weights.py``, one layer at
a time, with the same share of experts as the served model holds.
Every matmul runs at ``Precision.HIGHEST``.

Each layer: RMSNorm, grouped-query attention with rotary embeddings
(rotate-half), causal, and on sliding layers only the latest ``window``
positions (the query's own included), plain RoPE there and YaRN RoPE on
full layers (cos and sin times the attention factor); then RMSNorm and
the experts: the router scores every expert in float32, softmax, the
top k, renormalised; each token adds the gate-weighted SwiGLU output of
those of its top k that this share holds, and nothing for the others.
A final RMSNorm and the LM head close it.  Each row is one request
alone, positions counted from its first token.  Every quantized weight
is used as the rung serves it (``reference.dequant``).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from moe_weights import LAYER_STREAM, expert_keys, expert_weights, \
    layer_weights
from reference import HEAD_ROWS, HI, _embed, _head, _mm, _rms, _vocab_head, \
    dequant
from weights import VOCAB_STREAM, key


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies: the extrapolated ones 1 / theta^(2i/d)
    below the correction dim of ``beta_fast`` rotations over the
    original context, the interpolated ones (over ``factor``) above that
    of ``beta_slow``, and a linear blend between (floored and ceiled
    dims, as the published modelling code has them)."""
    def dim_of(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(head_dim // 2, dtype=np.float32)
    blend = np.clip((i - low) / (high - low), 0.0, 1.0)
    base = theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    return ((1.0 / (factor * base)) * blend
            + (1.0 / base) * (1.0 - blend)).astype(np.float32)


def rope_of(s, kind: str) -> Tuple[np.ndarray, float]:
    """(inverse frequencies, cos/sin scale) of a layer of ``kind``."""
    half = s.head_dim // 2
    if kind == "full" and s.yarn:
        factor, original, fast, slow, attn = s.yarn
        return (yarn_inv_freq(s.head_dim, s.full_theta, factor, original,
                              fast, slow), attn)
    theta = s.full_theta if kind == "full" else s.rope_theta
    return (1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
            ).astype(np.float32), 1.0


def _rope(x, inv_freq, scale):
    """x: (B, T, H, hd), positions 0..T-1."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(ang)[None, :, None] * scale
    sin = jnp.sin(ang)[None, :, None] * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _layer(h, k, ekeys, s, wcfg: Tuple, quant: Tuple, kind: str):
    p = layer_weights(k, s, dict(wcfg))
    w = {n: dequant(p[n].astype(jnp.float32), *quant)
         for n in ("q", "k", "v", "o")}
    ex = jax.vmap(lambda kk: expert_weights(kk, s))(ekeys)
    ex = {n: dequant(a.astype(jnp.float32), *quant) for n, a in ex.items()}
    B, T, _ = h.shape
    H, KV, hd = s.heads, s.kv_heads, s.head_dim
    inv_freq, scale = rope_of(s, kind)
    x = _rms(h, p["attn_norm"], s.eps)
    q = _rope(_mm(x, w["q"]).reshape(B, T, H, hd), inv_freq, scale)
    kk = _rope(_mm(x, w["k"]).reshape(B, T, KV, hd), inv_freq, scale)
    v = _mm(x, w["v"]).reshape(B, T, KV, hd)
    q = q.reshape(B, T, KV, H // KV, hd)
    sc = jnp.einsum("btkgd,bskd->bkgts", q, kk, precision=HI) / np.sqrt(hd)
    qpos, kpos = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = kpos <= qpos
    if kind == "sliding":
        seen &= kpos > qpos - s.window
    sc = jnp.where(seen, sc, -jnp.inf)
    o = jnp.einsum("bkgts,bskd->btkgd", jax.nn.softmax(sc, -1), v,
                   precision=HI).reshape(B, T, H * hd)
    h = h + _mm(o, w["o"])
    x = _rms(h, p["mlp_norm"], s.eps)
    probs = jax.nn.softmax(_mm(x, p["router"]), -1)          # (B, T, E)
    top, idx = jax.lax.top_k(probs, s.top_k)
    top = top / top.sum(-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, s.experts) * top[..., None], -2)
    gate = gate[..., s.first:s.first + s.held]               # this share
    g = jnp.einsum("btd,edf->ebtf", x, ex["gate"], precision=HI)
    u = jnp.einsum("btd,edf->ebtf", x, ex["up"], precision=HI)
    y = jnp.einsum("ebtf,efd->ebtd", jax.nn.silu(g) * u, ex["down"],
                   precision=HI)
    return h + jnp.einsum("bte,ebtd->btd", gate, y, precision=HI)


def score(rows: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]],
          s, wcfg: Dict, seed: int,
          quant: Tuple[Tuple[int, ...], int]) -> List[Dict[str, np.ndarray]]:
    """As ``reference.score``: per row, at its positions, ``top`` the
    largest logit, ``tgt`` the logits of the targets and ``arg`` the
    argmax, with the weights of rung ``quant[1]`` of the ladder
    ``quant[0]``."""
    w = tuple(sorted(wcfg.items()))
    quant = (tuple(sorted(quant[0])), int(quant[1]))
    T = max(len(t) for t, _, _ in rows)
    toks = np.zeros((len(rows), T), np.int32)       # right pad: causal
    for i, (t, _, _) in enumerate(rows):
        toks[i, :len(t)] = t
    h = _embed(jnp.asarray(toks), key(seed, VOCAB_STREAM), s, w, quant)
    for l in range(s.layers):
        h = _layer(h, key(seed, LAYER_STREAM, l), expert_keys(seed, l, s), s,
                   w, quant, s.layer_types[l])
    flat = np.concatenate([i * T + np.asarray(p) for i, (_, p, _) in
                           enumerate(rows)])
    tg = np.concatenate([np.asarray(t) for _, _, t in rows]).astype(np.int32)
    hs = h.reshape(-1, s.d)[jnp.asarray(flat)]
    del h
    final_norm, head = _vocab_head(key(seed, VOCAB_STREAM), s, w, quant)
    outs = [[], [], []]
    for a in range(0, len(flat), HEAD_ROWS):
        r = _head(hs[a:a + HEAD_ROWS], jnp.asarray(tg[a:a + HEAD_ROWS]),
                  final_norm, head, s)
        for o, x in zip(outs, r):
            o.append(np.asarray(x))
    top, tgt, arg = (np.concatenate(o) for o in outs)
    res, a = [], 0
    for _, p, _ in rows:
        n = len(p)
        res.append({"top": top[a:a + n], "tgt": tgt[a:a + n],
                    "arg": arg[a:a + n]})
        a += n
    return res

