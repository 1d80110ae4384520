"""Random weights of the mixture-of-experts decoder, made on the device.

One function makes one layer's attention, norms and router from the
layer's own key, one makes one expert from its own key (the expert's
index among all the layer's experts, so a share of them is the same
values whichever share it is), and the vocabulary tables come from
``weights.vocab_weights``.  The plain reference makes layer l alone and
gets the very values the served model was quantized from.  The weights
follow the published layout: x @ W with W of shape (K, N).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from weights import VOCAB_STREAM, key, vocab_weights

LAYER_STREAM, EXPERT_STREAM = 1, 2
ATTENTION = ("q", "k", "v", "o")


def _normal(k, shape, std):
    return jax.random.normal(k, shape, jnp.float32) * std


def layer_weights(k: jax.Array, s, w: Dict) -> Dict[str, jax.Array]:
    """One layer outside its experts: bf16 attention matmuls with std
    1/sqrt(K), float32 norm scales around 1 and a float32 router of
    std 1/sqrt(d) over every expert."""
    ks = jax.random.split(k, 7)
    out = {name: _normal(ks[i], (K, N), K ** -0.5).astype(jnp.bfloat16)
           for i, (name, K, N) in enumerate(s.attention_matmuls())}
    out["attn_norm"] = 1.0 + _normal(ks[4], (s.d,), w["norm_std"])
    out["mlp_norm"] = 1.0 + _normal(ks[5], (s.d,), w["norm_std"])
    out["router"] = _normal(ks[6], (s.d, s.experts), s.d ** -0.5)
    return out


def expert_weights(k: jax.Array, s) -> Dict[str, jax.Array]:
    """One SwiGLU expert: bf16 gate and up (d, ff) and down (ff, d),
    std 1/sqrt(K)."""
    ks = jax.random.split(k, 3)
    return {"gate": _normal(ks[0], (s.d, s.ff), s.d ** -0.5
                            ).astype(jnp.bfloat16),
            "up": _normal(ks[1], (s.d, s.ff), s.d ** -0.5
                          ).astype(jnp.bfloat16),
            "down": _normal(ks[2], (s.ff, s.d), s.ff ** -0.5
                            ).astype(jnp.bfloat16)}


def expert_keys(seed: int, layer: int, s) -> jax.Array:
    return jnp.stack([key(seed, EXPERT_STREAM, layer * s.experts + e)
                      for e in range(s.first, s.first + s.held)])


def held_experts(seed: int, layer: int, s) -> Dict[str, jax.Array]:
    """The held experts of one layer, stacked: (held, K, N) each."""
    return jax.vmap(lambda k: expert_weights(k, s))(
        expert_keys(seed, layer, s))


def program_params(seed: int, s, w: Dict) -> Dict:
    """The whole model in the serving program's parameter layout, made
    in one call: layers stacked on a leading axis, the held experts on
    the next, and the embedding table divided by sqrt(d), because the
    program multiplies embedded rows by sqrt(d_model) where the
    published model does not."""
    keys = jnp.stack([key(seed, LAYER_STREAM, l) for l in range(s.layers)])
    L = jax.vmap(lambda k: layer_weights(k, s, w))(keys)
    ekeys = jnp.stack([expert_keys(seed, l, s) for l in range(s.layers)])
    ex = jax.vmap(jax.vmap(lambda k: expert_weights(k, s)))(ekeys)
    v = vocab_weights(key(seed, VOCAB_STREAM), s, w)
    table = (v["embed"].astype(jnp.float32) / jnp.sqrt(jnp.float32(s.d)))
    blocks = {"attn_norm": {"scale": L["attn_norm"]},
              "mlp_norm": {"scale": L["mlp_norm"]},
              "moe": {"router": {"w": L["router"]},
                      "experts": {"w_gate": {"w": ex["gate"]},
                                  "w_up": {"w": ex["up"]},
                                  "w_down": {"w": ex["down"]}}}}
    for name in ATTENTION:
        blocks[name] = {"w": L[name]}
    return {"embed": {"table": table.astype(jnp.bfloat16)}, "blocks": blocks,
            "final_norm": {"scale": v["final_norm"]},
            "lm_head": {"w": v["head"]}}
