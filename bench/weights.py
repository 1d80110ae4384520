"""Random weights of a configuration, made on the device from a seed.

One function makes one decoder layer from its own key, and one makes
the vocabulary tables, so the plain reference can make layer l alone
and get the very values the served model was quantized from.  The
weights follow the published layout: x @ W with W of shape (K, N), an
unscaled embedding, and an LM head tied to the embedding where the
configuration ties it.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from shapes import Sizes

VOCAB_STREAM, LAYER_STREAM = 0, 1


def key(seed: int, stream: int, index: int = 0) -> jax.Array:
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), stream), index)


def _normal(k, shape, std):
    return jax.random.normal(k, shape, jnp.float32) * std


def layer_weights(k: jax.Array, s: Sizes, w: Dict) -> Dict[str, jax.Array]:
    """One decoder layer: bf16 matmul weights with std 1/sqrt(K), float32
    norm scales around 1 and, where the configuration has them, float32
    q/k/v biases."""
    ks = jax.random.split(k, 10)
    out = {name: _normal(ks[i], (K, N), K ** -0.5).astype(jnp.bfloat16)
           for i, (name, K, N) in enumerate(s.layer_matmuls())}
    out["attn_norm"] = 1.0 + _normal(ks[7], (s.d,), w["norm_std"])
    out["mlp_norm"] = 1.0 + _normal(ks[8], (s.d,), w["norm_std"])
    if s.qkv_bias:
        q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
        b = _normal(ks[9], (q + 2 * kv,), w["bias_std"])
        out["q_b"], out["k_b"], out["v_b"] = b[:q], b[q:q + kv], b[q + kv:]
    return out


def vocab_weights(k: jax.Array, s: Sizes, w: Dict) -> Dict[str, jax.Array]:
    """Embedding (V, d), final norm scale (d,) and LM head (d, V)."""
    ks = jax.random.split(k, 3)
    embed = _normal(ks[0], (s.vocab, s.d), w["embed_std"]).astype(jnp.bfloat16)
    head = (embed.T if s.tied else
            _normal(ks[2], (s.d, s.vocab), w["embed_std"]).astype(jnp.bfloat16))
    return {"embed": embed, "final_norm": 1.0 + _normal(ks[1], (s.d,),
                                                        w["norm_std"]),
            "head": head}


def program_params(seed: int, s: Sizes, w: Dict) -> Dict:
    """The whole model in the serving program's parameter layout, made
    in one call: layers stacked on a leading axis, and the embedding
    table divided by sqrt(d), because the program multiplies embedded
    rows by sqrt(d_model) where the published models do not."""
    keys = jnp.stack([key(seed, LAYER_STREAM, l) for l in range(s.layers)])
    L = jax.vmap(lambda k: layer_weights(k, s, w))(keys)
    v = vocab_weights(key(seed, VOCAB_STREAM), s, w)
    table = (v["embed"].astype(jnp.float32) / jnp.sqrt(jnp.float32(s.d)))
    blocks = {"attn_norm": {"scale": L["attn_norm"]},
              "mlp_norm": {"scale": L["mlp_norm"]},
              "o": {"w": L["o"]},
              "mlp": {"w_gate": {"w": L["gate"]}, "w_up": {"w": L["up"]},
                      "w_down": {"w": L["down"]}}}
    for name in ("q", "k", "v"):
        blocks[name] = {"w": L[name]}
        if s.qkv_bias:
            blocks[name]["b"] = L[name + "_b"]
    return {"embed": {"table": table.astype(jnp.bfloat16)}, "blocks": blocks,
            "final_norm": {"scale": v["final_norm"]},
            "lm_head": {"w": v["head"]}}
