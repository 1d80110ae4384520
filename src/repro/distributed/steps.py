"""Jitted step builders: train_step / prefill_step / serve(decode)_step.

These close over (model, mesh, sharding specs) and return AOT-lowerable
jitted callables plus the abstract input specs (ShapeDtypeStruct stand-ins,
no allocation) for the dry-run.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig
from ..models.model import Model, make_model
from ..optim import adamw
from . import sharding as shd
from .ctx import logical_rules as rules_ctx


# ---------------------------------------------------------------------------
# Abstract inputs (ShapeDtypeStruct): no device allocation
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeConfig,
                model: Optional[Model] = None) -> Dict[str, Any]:
    B, S = shape.global_batch, shape.seq_len
    cdt = jnp.dtype(cfg.compute_dtype)
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        out = {"labels": sds((B, S), jnp.int32)}
        if cfg.input_kind == "tokens":
            out["tokens"] = sds((B, S), jnp.int32)
        else:
            out["embeddings"] = sds((B, S, cfg.d_model), cdt)
        return out
    if shape.kind == "prefill":
        if cfg.input_kind == "tokens":
            return {"tokens": sds((B, S), jnp.int32)}
        return {"embeddings": sds((B, S, cfg.d_model), cdt)}
    # decode: one new token + cache of length S
    model = model or make_model(cfg)
    cache = jax.eval_shape(lambda: model.make_cache(B, S))
    if cfg.input_kind == "tokens":
        inp = {"tokens": sds((B, 1), jnp.int32)}
    else:
        inp = {"embeddings": sds((B, 1, cfg.d_model), cdt)}
    return {"inputs": inp, "cache": cache}


# ---------------------------------------------------------------------------
# Train step (gradient accumulation over microbatches inside one jit)
# ---------------------------------------------------------------------------
def build_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                     peak_lr: float = 3e-4, total_steps: int = 10_000):
    """Returns (jitted_step, specs) where specs carries all shardings.

    Mixed precision (§Perf P5): bf16 live params (FSDP gathers and TP
    collectives ship 2 bytes/elem) + f32 master weights and Adam moments in
    the optimizer state.
    """
    train_cfg = dataclasses.replace(cfg, dtype="bfloat16")
    model = make_model(train_cfg)
    nm = shape.num_microbatches
    mb = shape.global_batch // nm
    rules = shd.logical_rules(train_cfg, shape, mesh)

    abstract_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspec = shd.param_pspecs(train_cfg, abstract_params, mesh)
    ospec = shd.opt_pspecs(pspec)
    bspec = shd.batch_pspecs(train_cfg, shape, mesh, with_labels=True)

    def micro_view(batch, i):
        return jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0), batch)

    def train_step(params, opt_state, batch, step):
        with rules_ctx(mesh, rules):
            def micro_grads(i, carry):
                gsum, lsum = carry
                l, g = jax.value_and_grad(model.loss_fn)(params, micro_view(batch, i))
                gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return gsum, lsum + l

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if nm > 1:
                gsum, lsum = jax.lax.fori_loop(0, nm, lambda i, c: micro_grads(i, c),
                                               (g0, 0.0))
            else:
                gsum, lsum = micro_grads(0, (g0, 0.0))
            grads = jax.tree.map(lambda g: g / nm, gsum)
            lr = adamw.warmup_cosine(step, peak_lr=peak_lr, warmup=100,
                                     total=total_steps)
            params, opt_state, metrics = adamw.apply_update(
                params, grads, opt_state, lr=lr)
            metrics["loss"] = lsum / nm
            return params, opt_state, metrics

    jitted = jax.jit(
        train_step,
        in_shardings=(shd.named(mesh, pspec), shd.named(mesh, ospec),
                      shd.named(mesh, bspec), NamedSharding(mesh, P())),
        out_shardings=(shd.named(mesh, pspec), shd.named(mesh, ospec),
                       None),
        donate_argnums=(0, 1),
    )
    specs = {"model": model, "params": pspec, "opt": ospec, "batch": bspec,
             "rules": rules}
    return jitted, specs


# ---------------------------------------------------------------------------
# Prefill / decode steps
# ---------------------------------------------------------------------------
def build_prefill_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    model = make_model(cfg)
    rules = shd.logical_rules(cfg, shape, mesh)
    abstract_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # serving: TP-only weights (no FSDP gathers in the latency path);
    # 2D sharding stays available for archs whose weights exceed HBM.
    pspec = shd.param_pspecs(cfg, abstract_params, mesh,
                             fsdp="data" if cfg.param_count() * 2 / 16
                             > 12e9 else None)
    bspec = shd.batch_pspecs(cfg, shape, mesh, with_labels=False)
    cspec = shd.cache_pspecs(cfg, shape, mesh)

    def prefill_step(params, inputs):
        with rules_ctx(mesh, rules):
            return model.prefill(params, inputs)

    jitted = jax.jit(
        prefill_step,
        in_shardings=(shd.named(mesh, pspec), shd.named(mesh, bspec)),
        out_shardings=(None, shd.named(mesh, cspec)),
    )
    return jitted, {"model": model, "params": pspec, "batch": bspec,
                    "cache": cspec, "rules": rules}


def _nested_pspecs(nested_abs, dense_pspecs):
    """PartitionSpecs for a NestQuant-packed parameter tree: packed words
    and scales shard the output-channel dim, and a stacked expert leaf's
    expert dim, like the dense weight; the packed K dim stays unsharded
    (word rows are not evenly divisible)."""
    from jax.sharding import PartitionSpec as P

    from ..core.nesting import NestedTensor

    def f(leaf, spec):
        if isinstance(leaf, NestedTensor):
            nd = leaf.w_base.ndim
            spec = tuple(spec) + (None,) * (nd - len(spec))
            lead = spec[:-2] if nd == 4 else (None,) * (nd - 2)
            packed = P(*lead, None, spec[-1])
            return NestedTensor(w_base=packed,
                                deltas=tuple(packed for _ in leaf.deltas),
                                scale=packed, shape=leaf.shape,
                                bits=leaf.bits, block=leaf.block,
                                rung=leaf.rung)
        return spec

    return jax.tree.map(f, nested_abs, dense_pspecs,
                        is_leaf=lambda x: isinstance(x, NestedTensor))


def quantize_abstract(cfg: ModelConfig, n: int = 8, h: int = 4):
    """Abstract NestQuant-packed parameter tree (eval_shape, no compute).

    The embedding table stays dense (token gather from packed rows is not a
    matmul; production serving keeps it int8/bf16 row-addressable)."""
    from ..core.nesting import default_predicate
    from ..core.recipe import QuantRecipe, quantize
    model = make_model(cfg)
    params_abs = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def pred(path, leaf):
        return "embed" not in path.lower() and default_predicate(path, leaf)

    recipe = QuantRecipe(bits=(h, n), rounding="rtn", predicate=pred)
    return jax.eval_shape(lambda p: quantize(p, recipe), params_abs)


def build_decode_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                      quant: Optional[str] = None):
    """quant: None (bf16 weights) | 'nested' (packed NestQuant weights,
    dequantized on the fly - jnp reference of the Pallas packed_matmul)."""
    model = make_model(cfg)
    rules = shd.logical_rules(cfg, shape, mesh)
    abstract_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspec = shd.param_pspecs(cfg, abstract_params, mesh,
                             fsdp="data" if cfg.param_count() * 2 / 16
                             > 12e9 else None, attn_cols=True)
    if quant == "nested":
        nested_abs = quantize_abstract(cfg)
        pspec = _nested_pspecs(nested_abs, pspec)
        abstract_params = nested_abs
    bspec = shd.batch_pspecs(cfg, shape, mesh, with_labels=False)
    bspec = {k: (P(v[0], *([None] * (len(v) - 1)))) for k, v in bspec.items()}
    cspec = shd.cache_pspecs(cfg, shape, mesh)

    def serve_step(params, inputs, cache):
        with rules_ctx(mesh, rules):
            return model.decode_step(params, inputs, cache)

    jitted = jax.jit(
        serve_step,
        in_shardings=(shd.named(mesh, pspec), shd.named(mesh, bspec),
                      shd.named(mesh, cspec)),
        out_shardings=(None, shd.named(mesh, cspec)),
        donate_argnums=(2,),
    )
    return jitted, {"model": model, "params": pspec, "batch": bspec,
                    "cache": cspec, "rules": rules,
                    "abstract_params": abstract_params}
