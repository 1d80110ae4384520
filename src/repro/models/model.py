"""Composable LM assembly for every assigned architecture family.

One code path builds dense GQA transformers (qwen2/qwen2.5/qwen1.5/
mistral-nemo/musicgen/phi3v backbones), MoE transformers (llama4-scout,
dbrx), pure SSM stacks (mamba2) and the Zamba2 hybrid (Mamba2 trunk +
one shared attention/MLP block applied every N layers).

Layers are stacked on a leading L axis and executed with ``lax.scan`` so
the HLO stays one-layer-sized (critical for 40-cell dry-run compiles on a
single CPU core and for TPU compile times at scale).

Public surface (all pure functions, built by :func:`make_model`):
  init(rng)                     -> params
  loss_fn(params, batch)        -> scalar LM loss        (train shapes)
  prefill(params, inputs)       -> (last_logits, cache)  (prefill shapes)
  decode_step(params, inputs, cache) -> (logits, cache)  (decode shapes)
  decode_chunk(params, inputs, cache) -> (logits, cache) (S-token verify;
                                          None for ssm/hybrid families)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.nesting import NestedTensor
from ..distributed.ctx import shard_hint
from . import mamba2
from .attention import blockwise_attention, decode_attention, full_attention
from .layers import (apply_rope, linear, mlp, norm, packed_linear, pdot,
                     rope_freqs, yarn_freqs)
from .moe import held_moe_ffn, moe_ffn


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


# ===========================================================================
# Initialization
# ===========================================================================
def _dense_init(key, shape, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / jnp.sqrt(shape[-2] if len(shape) >= 2 else shape[-1])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _norm_init(cfg, d):
    p = {"scale": jnp.ones((cfg.num_layers, d), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((cfg.num_layers, d), jnp.float32)
    return p


def _norm_init_single(cfg, d):
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def _attn_init(key, cfg, in_dim: int, stacked: bool):
    L = (cfg.num_layers,) if stacked else ()
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    p = {
        "q": {"w": _dense_init(ks[0], L + (in_dim, qd), dt)},
        "k": {"w": _dense_init(ks[1], L + (in_dim, kvd), dt)},
        "v": {"w": _dense_init(ks[2], L + (in_dim, kvd), dt)},
        "o": {"w": _dense_init(ks[3], L + (qd, cfg.d_model), dt)},
    }
    if cfg.qkv_bias:
        p["q"]["b"] = jnp.zeros(L + (qd,), jnp.float32)
        p["k"]["b"] = jnp.zeros(L + (kvd,), jnp.float32)
        p["v"]["b"] = jnp.zeros(L + (kvd,), jnp.float32)
    return p


def _mlp_init(key, cfg, in_dim: int, stacked: bool):
    L = (cfg.num_layers,) if stacked else ()
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    p = {"w_up": {"w": _dense_init(ks[1], L + (in_dim, cfg.d_ff), dt)},
         "w_down": {"w": _dense_init(ks[2], L + (cfg.d_ff, cfg.d_model), dt)}}
    if cfg.act == "swiglu":
        p["w_gate"] = {"w": _dense_init(ks[0], L + (in_dim, cfg.d_ff), dt)}
    return p


def _moe_init(key, cfg):
    """The router scores every expert; the stacks hold the held ones."""
    L, E, n = cfg.num_layers, cfg.num_experts, cfg.held_experts
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    return {
        "router": {"w": _dense_init(ks[0], (L, cfg.d_model, E), jnp.float32)},
        "experts": {
            "w_gate": {"w": _dense_init(ks[1], (L, n, cfg.d_model, cfg.d_ff), dt)},
            "w_up": {"w": _dense_init(ks[2], (L, n, cfg.d_model, cfg.d_ff), dt)},
            "w_down": {"w": _dense_init(ks[3], (L, n, cfg.d_ff, cfg.d_model), dt)},
        },
    }


def _mamba_init(key, cfg):
    L, d = cfg.num_layers, cfg.d_model
    din, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_dim = din + 2 * N
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    proj_out = 2 * din + 2 * N + H
    return {
        "norm": _norm_init(cfg, d),
        "in_proj": {"w": _dense_init(ks[0], (L, d, proj_out), dt)},
        "conv": {"w": _dense_init(ks[1], (L, cfg.ssm_conv_width, conv_dim),
                                  jnp.float32, scale=0.5),
                 "b": jnp.zeros((L, conv_dim), jnp.float32)},
        "dt_bias": jnp.zeros((L, H), jnp.float32),
        "A_log": jnp.zeros((L, H), jnp.float32),          # A = -1
        "D": jnp.ones((L, H), jnp.float32),
        "ssm_norm": {"scale": jnp.ones((L, din), jnp.float32)},
        "out_proj": {"w": _dense_init(ks[2], (L, din, d), dt)},
    }


def init_params(cfg: ModelConfig, rng) -> Dict:
    dt = _dtype(cfg)
    keys = jax.random.split(rng, 8)
    params: Dict[str, Any] = {}
    if cfg.input_kind == "tokens":
        params["embed"] = {"table": _dense_init(keys[0], (cfg.vocab_size, cfg.d_model),
                                                dt, scale=0.02)}
    if cfg.family in ("dense", "moe"):
        blocks = {"attn_norm": _norm_init(cfg, cfg.d_model),
                  "mlp_norm": _norm_init(cfg, cfg.d_model)}
        blocks.update(_attn_init(keys[1], cfg, cfg.d_model, stacked=True))
        if cfg.family == "moe":
            blocks["moe"] = _moe_init(keys[2], cfg)
        else:
            blocks["mlp"] = _mlp_init(keys[2], cfg, cfg.d_model, stacked=True)
        params["blocks"] = blocks
    else:  # ssm / hybrid
        params["blocks"] = _mamba_init(keys[1], cfg)
        if cfg.family == "hybrid":
            shared_cfg = dataclasses.replace(cfg, qkv_bias=False)
            shared = {"attn_norm": _norm_init_single(cfg, 2 * cfg.d_model),
                      "mlp_norm": _norm_init_single(cfg, 2 * cfg.d_model)}
            shared.update(_attn_init(keys[2], shared_cfg, 2 * cfg.d_model,
                                     stacked=False))
            shared["mlp"] = _mlp_init(keys[3], cfg, 2 * cfg.d_model,
                                      stacked=False)
            # shared MLP re-projects 2d -> d
            shared["mlp"]["w_down"]["w"] = _dense_init(
                keys[4], (cfg.d_ff, cfg.d_model), dt)
            params["shared"] = shared
    params["final_norm"] = _norm_init_single(cfg, cfg.d_model)
    params["lm_head"] = {"w": _dense_init(keys[5], (cfg.d_model, cfg.vocab_size),
                                          dt, scale=0.02)}
    return params


# ===========================================================================
# Attention sub-block (dense / moe layers + zamba2 shared block)
# ===========================================================================
FULL_WINDOW = 1 << 30       # a full layer's window: every earlier position


def layer_info(cfg: ModelConfig):
    """Per-layer attention settings of a config with a layer pattern,
    scanned beside the layer weights: RoPE frequencies ``freqs`` (L,
    hd/2), cos/sin ``rope_scale`` (L,) and ``window`` (L,).  None for a
    config without a pattern, whose layers then run today's code."""
    if not cfg.layer_types:
        return None
    plain = rope_freqs(cfg.head_dim, cfg.rope_theta)
    yarn = plain if not cfg.yarn_factor else yarn_freqs(
        cfg.head_dim, cfg.rope_theta, cfg.yarn_factor,
        cfg.yarn_original_max_len, cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    full = [cfg.layer_type(l) == "full" for l in range(cfg.num_layers)]
    yarn_scale = cfg.yarn_attention_factor if cfg.yarn_factor else 1.0
    return {
        "freqs": jnp.stack([yarn if f else plain for f in full]),
        "rope_scale": jnp.asarray([yarn_scale if f else 1.0 for f in full],
                                  jnp.float32),
        "window": jnp.asarray([FULL_WINDOW if f else cfg.sliding_window
                               for f in full], jnp.int32),
    }


def _rope(x, positions, cfg, li):
    if li is None:
        return apply_rope(x, positions, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta, freqs=li["freqs"],
                      scale=li["rope_scale"])


def _window(li):
    return None if li is None else li["window"]

def _qkv(x, lp, cfg):
    q = linear(x, lp["q"]["w"], lp["q"].get("b"))
    k = linear(x, lp["k"]["w"], lp["k"].get("b"))
    v = linear(x, lp["v"]["w"], lp["v"].get("b"))
    B, S = x.shape[:2]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def attn_seq(x, lp, cfg, kv_block: int = 512, li=None):
    """Full-sequence causal attention. Returns (out, (k, v)).  ``li``:
    this layer's row of :func:`layer_info`, or None."""
    B, S = x.shape[:2]
    q, k, v = _qkv(x, lp, cfg)
    pos = jnp.arange(S)
    q = _rope(q, pos, cfg, li)
    k = _rope(k, pos, cfg, li)
    # head-TP mode: heads -> model axis.  Sequence-parallel mode (head count
    # does not divide the model axis): q/o shard the sequence dim instead,
    # k/v replicate over model (small for GQA).
    q = shard_hint(q, ("batch", "attn_seq", "heads", None))
    k = shard_hint(k, ("batch", None, "kv_heads", None))
    v = shard_hint(v, ("batch", None, "kv_heads", None))
    if S > 1024:
        o = blockwise_attention(q, k, v, True, kv_block, _window(li))
    else:
        o = full_attention(q, k, v, causal=True, window=_window(li))
    o = shard_hint(o, ("batch", "attn_seq", "heads", None))
    o = o.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return linear(o, lp["o"]["w"]), (k, v)


def attn_decode(x, lp, cfg, k_cache, v_cache, pos, li=None):
    """One-token attention against cache. x: (B,1,in_dim);
    caches: (B,Smax,Hkv,hd); pos: scalar. Returns (out, (k_cache, v_cache))."""
    B = x.shape[0]
    q, k, v = _qkv(x, lp, cfg)
    q = _rope(q, jnp.full((1,), pos), cfg, li)
    k = _rope(k, jnp.full((1,), pos), cfg, li)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                           (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                           (0, pos, 0, 0))
    o = decode_attention(q, k_cache, v_cache, pos, window=_window(li))
    o = o.reshape(B, 1, cfg.num_heads * cfg.head_dim)
    return linear(o, lp["o"]["w"]), (k_cache, v_cache)


def attn_decode_chunk(x, lp, cfg, k_cache, v_cache, pos, li=None):
    """S-token chunked attention against cache (speculative verify).

    x: (B,S,in_dim); caches: (B,Smax,Hkv,hd); pos: scalar start position.
    Query row j sees exactly the keys at positions <= pos + j - the same
    valid set (and the same Smax-wide masked softmax, where NEG_INF
    underflows to an exact 0 weight) as j sequential attn_decode calls,
    which is what makes chunked verification bit-identical to the
    sequential decode it replaces.  Returns (out, (k_cache, v_cache))."""
    B, S = x.shape[:2]
    q, k, v = _qkv(x, lp, cfg)
    positions = pos + jnp.arange(S)
    q = _rope(q, positions, cfg, li)
    k = _rope(k, positions, cfg, li)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                           (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                           (0, pos, 0, 0))
    o = full_attention(q, k_cache, v_cache, causal=True, q_offset=pos,
                       window=_window(li))
    o = o.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return linear(o, lp["o"]["w"]), (k_cache, v_cache)


# ===========================================================================
# Transformer (dense / moe) forward
# ===========================================================================
def _ffn(h, lp, cfg, inference=False):
    """(out, aux loss, rows per held expert or None).  MoE layers route
    with the inference dispatch (exact, held experts only) on the
    serving paths and with the capacity dispatch in training."""
    if cfg.family != "moe":
        return mlp(h, lp["mlp"], cfg.act), 0.0, None
    if inference:
        y, rows = held_moe_ffn(h, lp["moe"], num_experts=cfg.num_experts,
                               top_k=cfg.top_k, first=cfg.expert_first,
                               held=cfg.held_experts, act=cfg.act)
        return y, 0.0, rows
    y, aux = moe_ffn(h, lp["moe"], num_experts=cfg.num_experts,
                     top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                     act=cfg.act)
    return y, aux, None


def _tf_layer_seq(h, lp, cfg, li=None, inference=False):
    a, kv = attn_seq(norm(h, lp["attn_norm"], cfg.norm), lp, cfg, li=li)
    h = h + a
    y, aux, _ = _ffn(norm(h, lp["mlp_norm"], cfg.norm), lp, cfg,
                     inference=inference)
    h = h + y
    h = shard_hint(h, ("batch", None, None))
    return h, kv, aux


def transformer_seq(params, x, cfg, want_cache: bool):
    """x: (B,S,d) embedded input. Returns (h, cache, aux_sum).

    Cache-building runs (prefill) route MoE layers with the exact
    inference dispatch, so that the cached decode after them reproduces
    them; training (want_cache=False) keeps the capacity-dropped
    dispatch."""
    body = partial(_tf_layer_seq, inference=want_cache)
    if cfg.remat:
        body = jax.checkpoint(body, static_argnums=(2,),
                              policy=jax.checkpoint_policies.nothing_saveable)

    def scan_fn(carry, xs):
        h, aux = carry
        lp, li = xs
        h, kv, aux_l = body(h, lp, cfg, li)
        ys = kv if want_cache else None
        return (h, aux + aux_l), ys

    (h, aux), kvs = jax.lax.scan(scan_fn, (x, 0.0),
                                 (params["blocks"], layer_info(cfg)))
    cache = None
    if want_cache:
        cache = {"k": kvs[0], "v": kvs[1]}    # (L,B,S,Hkv,hd)
    return h, cache, aux


def _cached_layers(params, x, cfg, cache, attend):
    """Run every layer against the cache with ``attend`` (one token or a
    chunk); a cache with ``expert_rows`` adds each layer's count of rows
    routed to its held experts."""
    def scan_fn(h, xs):
        lp, kc, vc, li = xs
        a, (kc, vc) = attend(norm(h, lp["attn_norm"], cfg.norm), lp, cfg,
                             kc, vc, li)
        h = h + a
        y, _, rows = _ffn(norm(h, lp["mlp_norm"], cfg.norm), lp, cfg,
                          inference=True)
        return h + y, (kc, vc, rows)

    h, (kc, vc, rows) = jax.lax.scan(
        scan_fn, x, (params["blocks"], cache["k"], cache["v"],
                     layer_info(cfg)))
    new = {"k": kc, "v": vc}
    if "expert_rows" in cache:
        new["expert_rows"] = cache["expert_rows"] + rows
    return h, new


def transformer_decode(params, x, cfg, cache, pos):
    return _cached_layers(
        params, x, cfg, cache,
        lambda xn, lp, cfg, kc, vc, li: attn_decode(xn, lp, cfg, kc, vc, pos,
                                                    li))


def transformer_decode_chunk(params, x, cfg, cache, pos):
    """Decode S tokens in ONE pass against the cache (x: (B,S,d)).

    The verify half of self-speculative decoding: one weight-streaming
    pass scores every drafted position, where sequential decode would
    stream the full-bit weights S times."""
    return _cached_layers(
        params, x, cfg, cache,
        lambda xn, lp, cfg, kc, vc, li: attn_decode_chunk(xn, lp, cfg, kc,
                                                          vc, pos, li))


# ===========================================================================
# SSM / hybrid forward
# ===========================================================================
def _shared_block_seq(h, emb0, sp, cfg):
    u = jnp.concatenate([h, emb0], axis=-1)                # (B,S,2d)
    a, kv = attn_seq(norm(u, sp["attn_norm"], cfg.norm), sp, cfg)
    h = h + a
    m = mlp(norm(jnp.concatenate([h, emb0], axis=-1), sp["mlp_norm"], cfg.norm),
            sp["mlp"], cfg.act)
    return h + m, kv


def ssm_seq(params, x, cfg, want_cache: bool):
    """Mamba2 trunk (+ shared attn for hybrid). x: (B,S,d)."""
    every = cfg.hybrid_attn_every
    napps = (cfg.num_layers + every - 1) // every if every else 0
    B, S, d = x.shape
    emb0 = x

    body = mamba2.mamba_block
    if cfg.remat:
        body = jax.checkpoint(mamba2.mamba_block, static_argnums=(2,),
                              policy=jax.checkpoint_policies.nothing_saveable)

    if every:
        # Zamba2 structure: ngroups = L/every groups, each = one application
        # of the SHARED attention block followed by `every` Mamba2 layers.
        # Nested scan (no lax.cond) keeps the HLO exact and one-group-sized.
        assert cfg.num_layers % every == 0, (cfg.num_layers, every)
        ngroups = cfg.num_layers // every
        sp = params["shared"]
        grouped = jax.tree.map(
            lambda a: a.reshape((ngroups, every) + a.shape[1:]),
            params["blocks"])

        shared_body = _shared_block_seq
        if cfg.remat:
            shared_body = jax.checkpoint(_shared_block_seq, static_argnums=(3,),
                                         policy=jax.checkpoint_policies.nothing_saveable)

        def outer(h, gp):
            h, kv = shared_body(h, emb0, sp, cfg)

            def inner(hh, lp):
                y, mcache = body(norm_res(hh, lp, cfg), lp, cfg)
                return hh + y, (mcache["state"], mcache["conv_buf"])

            h, (st, bufs) = jax.lax.scan(inner, h, gp)
            return h, (kv[0], kv[1], st, bufs)

        h, (ks, vs, states, bufs) = jax.lax.scan(outer, x, grouped)
        cache = None
        if want_cache:
            cache = {
                "state": states.reshape((cfg.num_layers,) + states.shape[2:]),
                "conv_buf": bufs.reshape((cfg.num_layers,) + bufs.shape[2:]),
                "k": ks.astype(_cdtype(cfg)), "v": vs.astype(_cdtype(cfg)),
            }
        return h, cache, 0.0

    def scan_fn(h, lp):
        y, mcache = body(norm_res(h, lp, cfg), lp, cfg)
        return h + y, (mcache["state"], mcache["conv_buf"])

    h, (states, bufs) = jax.lax.scan(scan_fn, x, params["blocks"])
    cache = {"state": states, "conv_buf": bufs} if want_cache else None
    return h, cache, 0.0


def norm_res(h, lp, cfg):
    return norm(h, lp["norm"], cfg.norm)


def ssm_decode(params, x, cfg, cache, pos):
    every = cfg.hybrid_attn_every
    emb0 = x

    if every:
        assert cfg.num_layers % every == 0
        ngroups = cfg.num_layers // every
        sp = params["shared"]
        grouped = jax.tree.map(
            lambda a: a.reshape((ngroups, every) + a.shape[1:]),
            params["blocks"])
        g_state = cache["state"].reshape((ngroups, every) + cache["state"].shape[1:])
        g_buf = cache["conv_buf"].reshape((ngroups, every) + cache["conv_buf"].shape[1:])

        def outer(h, xs):
            gp, st_g, buf_g, kc, vc = xs
            u = jnp.concatenate([h, emb0], axis=-1)
            a, (kc, vc) = attn_decode(norm(u, sp["attn_norm"], cfg.norm),
                                      sp, cfg, kc, vc, pos)
            h = h + a
            m = mlp(norm(jnp.concatenate([h, emb0], axis=-1),
                         sp["mlp_norm"], cfg.norm), sp["mlp"], cfg.act)
            h = h + m

            def inner(hh, xs2):
                lp, st, buf = xs2
                y, mc = mamba2.mamba_decode_step(
                    norm_res(hh, lp, cfg), lp,
                    {"state": st, "conv_buf": buf}, cfg)
                return hh + y, (mc["state"], mc["conv_buf"])

            h, (st2, buf2) = jax.lax.scan(inner, h, (gp, st_g, buf_g))
            return h, (st2, buf2, kc, vc)

        h, (states, bufs, ks, vs) = jax.lax.scan(
            outer, x, (grouped, g_state, g_buf, cache["k"], cache["v"]))
        return h, {
            "state": states.reshape((cfg.num_layers,) + states.shape[2:]),
            "conv_buf": bufs.reshape((cfg.num_layers,) + bufs.shape[2:]),
            "k": ks, "v": vs}

    def scan_fn(h, xs):
        lp, st, buf = xs
        y, mc = mamba2.mamba_decode_step(
            norm_res(h, lp, cfg), lp, {"state": st, "conv_buf": buf}, cfg)
        return h + y, (mc["state"], mc["conv_buf"])

    h, (states, bufs) = jax.lax.scan(
        scan_fn, x, (params["blocks"], cache["state"], cache["conv_buf"]))
    return h, {"state": states, "conv_buf": bufs}


# ===========================================================================
# Embedding / head / losses
# ===========================================================================
def embed_inputs(params, inputs, cfg):
    if cfg.input_kind == "tokens":
        tok = inputs["tokens"]
        table = params["embed"]["table"]
        if isinstance(table, NestedTensor):
            # row gather straight from the packed words: reads only the
            # word rows of the batch's tokens, never the whole table.
            h = table.gather_rows(tok, _cdtype(cfg))
        else:
            h = table[tok].astype(_cdtype(cfg))
        h = h * jnp.sqrt(cfg.d_model).astype(h.dtype)
    else:
        h = inputs["embeddings"].astype(_cdtype(cfg))
    return shard_hint(h, ("batch", None, None))


def lm_logits(params, h, cfg):
    w = params["lm_head"]["w"]
    if isinstance(w, NestedTensor):
        logits = packed_linear(h, w, out_dtype=jnp.float32)
    else:
        logits = pdot(h, w.astype(h.dtype), preferred=jnp.float32)
    return shard_hint(logits, ("batch", None, "vocab"))


def xent_loss(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ===========================================================================
# Public model surface
# ===========================================================================
class Model(NamedTuple):
    cfg: ModelConfig
    init: Any
    loss_fn: Any
    prefill: Any
    decode_step: Any
    make_cache: Any
    # decode S tokens in one pass against the cache (the speculative
    # verify step); None for families without a chunked decode path
    decode_chunk: Any = None


def _forward_seq(params, inputs, cfg, want_cache: bool):
    h = embed_inputs(params, inputs, cfg)
    if cfg.family in ("dense", "moe"):
        h, cache, aux = transformer_seq(params, h, cfg, want_cache)
    else:
        h, cache, aux = ssm_seq(params, h, cfg, want_cache)
    h = norm(h, params["final_norm"], cfg.norm)
    return h, cache, aux


def make_model(cfg: ModelConfig) -> Model:
    def init(rng):
        return init_params(cfg, rng)

    def loss_fn(params, batch):
        h, _, aux = _forward_seq(params, batch, cfg, want_cache=False)
        logits = lm_logits(params, h, cfg)
        return xent_loss(logits, batch["labels"]) + 0.01 * aux

    def prefill(params, inputs):
        h, cache, _ = _forward_seq(params, inputs, cfg, want_cache=True)
        last = lm_logits(params, h[:, -1:, :], cfg)
        if cache is not None:
            cache["pos"] = jnp.array(h.shape[1], jnp.int32)
        return last, cache

    def decode_step(params, inputs, cache):
        pos = cache["pos"]
        h = embed_inputs(params, inputs, cfg)
        if cfg.family in ("dense", "moe"):
            h, new = transformer_decode(params, h, cfg, cache, pos)
        else:
            h, new = ssm_decode(params, h, cfg, cache, pos)
        h = norm(h, params["final_norm"], cfg.norm)
        logits = lm_logits(params, h, cfg)
        new["pos"] = pos + 1
        return logits, new

    def decode_chunk(params, inputs, cache):
        """Decode inputs['tokens'] (B,S) in one cached pass -> (logits
        (B,S,V), cache).  Position j's logits are bit-identical to what
        S sequential decode_step calls would produce at that position
        (the speculative-verify contract); cache advances by S."""
        pos = cache["pos"]
        h = embed_inputs(params, inputs, cfg)
        h, new = transformer_decode_chunk(params, h, cfg, cache, pos)
        h = norm(h, params["final_norm"], cfg.norm)
        logits = lm_logits(params, h, cfg)
        new["pos"] = pos + inputs["tokens"].shape[1]
        return logits, new

    # SSM/hybrid state recurrences have no cached multi-token re-score
    # path; the speculative decoder refuses those families explicitly
    if cfg.family not in ("dense", "moe"):
        decode_chunk = None

    def make_cache(batch_size: int, max_len: int, dtype=None):
        dt = dtype or _cdtype(cfg)
        cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
        L = cfg.num_layers
        if cfg.family in ("dense", "moe"):
            shp = (L, batch_size, max_len, cfg.num_kv_heads, cfg.head_dim)
            cache["k"] = jnp.zeros(shp, dt)
            cache["v"] = jnp.zeros(shp, dt)
            if cfg.family == "moe":
                # rows routed to each held expert, summed over decode steps
                cache["expert_rows"] = jnp.zeros((L, cfg.held_experts),
                                                 jnp.int32)
        else:
            H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
            conv_dim = cfg.d_inner + 2 * N
            cache["state"] = jnp.zeros((L, batch_size, H, P, N), jnp.float32)
            cache["conv_buf"] = jnp.zeros(
                (L, batch_size, cfg.ssm_conv_width - 1, conv_dim), dt)
            if cfg.family == "hybrid":
                every = cfg.hybrid_attn_every
                napps = (L + every - 1) // every
                shp = (napps, batch_size, max_len, cfg.num_kv_heads, cfg.head_dim)
                cache["k"] = jnp.zeros(shp, dt)
                cache["v"] = jnp.zeros(shp, dt)
        return cache

    return Model(cfg, init, loss_fn, prefill, decode_step, make_cache,
                 decode_chunk)
