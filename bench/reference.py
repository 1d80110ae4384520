"""The plain reference: the published decoder in float32, layer by layer.

It imports nothing of the program under test.  Weights are made again
from the configuration's weight seed by ``weights.py``, one layer at a
time, so the reference fits on the chip beside what is left of the run.
Every matmul runs at ``Precision.HIGHEST`` (float32 on the TPU's MXU).

The architecture is the Qwen2 / Mistral decoder: RMSNorm before
attention and MLP, grouped-query attention with rotary position
embeddings (rotate-half convention), optional q/k/v biases, a SwiGLU
MLP, a final RMSNorm and the LM head.  Each row is one request alone:
its own prompt, positions counted from its first token, no padding.

Every quantized weight (the layer matmuls, the embedding and the LM
head) is used as the rung serves it, dequantized by ``dequant``: the
NestQuant ladder with round-to-nearest codes, written out from the
paper's definition.  ``score`` gives, at chosen positions, the largest
logit, the logits of given target tokens and the argmax.  A ladder of
one width, ``((b,), 0)``, is plain round-to-nearest at b bits: the
control.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from shapes import Sizes
from weights import LAYER_STREAM, VOCAB_STREAM, key, layer_weights, \
    vocab_weights

HI = jax.lax.Precision.HIGHEST
MATMULS = ("q", "k", "v", "o", "gate", "up", "down")
HEAD_ROWS = 512          # rows of hidden states per LM-head block


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, T, H, hd), positions 0..T-1."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def dequant(w: jax.Array, bits: Tuple[int, ...], rung: int) -> jax.Array:
    """w as rung ``rung`` of the ladder ``bits`` (ascending) serves it.

    The top rung is symmetric INT-n, one scale per output column (over
    axis -2), amax / (2^(n-1) - 1), codes rounded to nearest (ties to
    even).  Each lower rung rounds the codes above it divided by 2^gap
    to nearest and clips them to its width; rung r's weight is its codes
    times the scale times 2^(n - bits[r])."""
    n = bits[-1]
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                        1e-12) / (2.0 ** (n - 1) - 1)
    c = jnp.clip(jnp.round(w / scale), -2.0 ** (n - 1), 2.0 ** (n - 1) - 1)
    for i in range(len(bits) - 1, rung, -1):
        hi, lo = bits[i], bits[i - 1]
        c = jnp.clip(jnp.round(c / 2.0 ** (hi - lo)), -2.0 ** (lo - 1),
                     2.0 ** (lo - 1) - 1)
    return c * scale * 2.0 ** (n - bits[rung])


def _weights_f32(p: Dict, quant: Tuple) -> Dict:
    out = {}
    for name, a in p.items():
        a = a.astype(jnp.float32)
        out[name] = dequant(a, *quant) if name in MATMULS else a
    return out


@partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(h, k, s: Sizes, wcfg: Tuple, quant: Tuple):
    p = _weights_f32(layer_weights(k, s, dict(wcfg)), quant)
    B, T, _ = h.shape
    H, KV, hd = s.heads, s.kv_heads, s.head_dim
    x = _rms(h, p["attn_norm"], s.eps)
    q, kk, v = _mm(x, p["q"]), _mm(x, p["k"]), _mm(x, p["v"])
    if s.qkv_bias:
        q, kk, v = q + p["q_b"], kk + p["k_b"], v + p["v_b"]
    q = _rope(q.reshape(B, T, H, hd), s.rope_theta)
    kk = _rope(kk.reshape(B, T, KV, hd), s.rope_theta)
    v = v.reshape(B, T, KV, hd)
    q = q.reshape(B, T, KV, H // KV, hd)
    sc = jnp.einsum("btkgd,bskd->bkgts", q, kk, precision=HI) / np.sqrt(hd)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    sc = jnp.where(causal, sc, -jnp.inf)
    o = jnp.einsum("bkgts,bskd->btkgd", jax.nn.softmax(sc, -1), v,
                   precision=HI).reshape(B, T, H * hd)
    h = h + _mm(o, p["o"])
    x = _rms(h, p["mlp_norm"], s.eps)
    return h + _mm(jax.nn.silu(_mm(x, p["gate"])) * _mm(x, p["up"]), p["down"])


@partial(jax.jit, static_argnums=(2, 3, 4))
def _embed(tokens, k, s: Sizes, wcfg: Tuple, quant: Tuple):
    """Embedded rows.  The served table is the embedding over sqrt(d) in
    bfloat16 (``weights.program_params``), quantized as it is stored,
    and its rows are multiplied by sqrt(d) again."""
    e = vocab_weights(k, s, dict(wcfg))["embed"].astype(jnp.float32)
    root = jnp.sqrt(jnp.float32(s.d))
    table = (e / root).astype(jnp.bfloat16).astype(jnp.float32)
    return dequant(table, *quant)[tokens] * root


@partial(jax.jit, static_argnums=(1, 2, 3))
def _vocab_head(k, s: Sizes, wcfg: Tuple, quant: Tuple):
    v = vocab_weights(k, s, dict(wcfg))
    return v["final_norm"], dequant(v["head"].astype(jnp.float32), *quant)


@partial(jax.jit, static_argnums=(4,))
def _head(h, targets, final_norm, head, s: Sizes):
    logits = _mm(_rms(h, final_norm, s.eps), head)
    return (logits.max(-1), jnp.take_along_axis(logits, targets, -1),
            logits.argmax(-1).astype(jnp.int32))


def score(rows: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]],
          s: Sizes, wcfg: Dict, seed: int,
          quant: Tuple[Tuple[int, ...], int]) -> List[Dict[str, np.ndarray]]:
    """Score rows of (tokens (T,), positions (n,), targets (n, m)), with
    the weights of rung ``quant[1]`` of the ladder ``quant[0]``.

    Logits at position p are those that predict token p + 1.  Returns,
    per row, ``top`` (n,) the largest logit, ``tgt`` (n, m) the logits
    of the targets and ``arg`` (n,) the argmax."""
    w = tuple(sorted(wcfg.items()))
    quant = (tuple(sorted(quant[0])), int(quant[1]))
    T = max(len(t) for t, _, _ in rows)
    toks = np.zeros((len(rows), T), np.int32)       # right pad: causal
    for i, (t, _, _) in enumerate(rows):
        toks[i, :len(t)] = t
    h = _embed(jnp.asarray(toks), key(seed, VOCAB_STREAM), s, w, quant)
    for l in range(s.layers):
        h = _layer(h, key(seed, LAYER_STREAM, l), s, w, quant)
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
