"""Sharding rules: logical axes -> mesh axes for every arch x shape cell.

Policy (see DESIGN.md Sec. 4):
  * TP on the ``model`` axis: FFN hidden, attention projections, MoE expert
    dim (EP), vocab.
  * DP on ``data`` (+ ``pod`` multi-pod): batch; FSDP-style 2D weight
    sharding (``shard_2d``) additionally shards a weight dim over ``data``
    for the large archs so params/optimizer state fit HBM.
  * SP: long-context / decode KV caches shard the sequence dim when batch
    or kv-head counts are too small to cover the mesh.
  * Head dims shard over ``model`` only when the head count reaches the
    axis size; GSPMD padding of uneven shards is allowed for dims >= 4096
    (waste < ~2%), otherwise the dim stays replicated.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _ok(dim: int, size: int) -> bool:
    """Accept sharding if divisible, or big enough that padding is cheap.
    (Lenient rule: only for activation CONSTRAINTS, where GSPMD pads.)"""
    return dim % size == 0 or dim >= 4096


def _maybe(axis: Optional[str], dim: int, mesh: Mesh) -> Optional[str]:
    """Strict divisibility - required for jit in_shardings (params/IO)."""
    if axis is None:
        return None
    size = 1
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        size *= mesh.shape[a]
    return axis if dim % size == 0 else None


# ---------------------------------------------------------------------------
# Parameter PartitionSpecs (pattern-matched on the param tree path)
# ---------------------------------------------------------------------------
_REDUCE_FIRST = ("o", "w_down", "out_proj")    # weights whose dim -2 is sharded on model


def param_pspecs(cfg: ModelConfig, abstract_params, mesh: Mesh,
                 fsdp: Optional[str] = "data", attn_cols: bool = False):
    """abstract_params: pytree of ShapeDtypeStruct (jax.eval_shape of init).

    attn_cols: for DECODE, non-head-divisible attention weights are
    column-sharded over ``model`` (activation regathers are ~B*qd bytes at
    S=1, while replicated weights cost GB/step of HBM reads - §Perf P4).
    """
    fsdp = fsdp if cfg.shard_2d else None
    msz = mesh.shape["model"]
    head_tp = (bool(cfg.num_heads) and cfg.num_heads % msz == 0) or attn_cols

    def spec(path, leaf) -> P:
        names = [getattr(k, "key", str(k)) for k in path]
        shape = leaf.shape
        nd = len(shape)
        if nd <= 1:
            return P()
        key = names[-2] if names[-1] in ("w", "b", "scale", "table") else names[-1]
        if names[-1] == "b" or "norm" in key or key in ("dt_bias",):
            return P()
        if key == "embed" or "embed" in names[:-1] or names[-1] == "table":
            ax0 = _maybe("model", shape[0], mesh)
            return P(ax0, _maybe(fsdp, shape[1], mesh))
        if key == "lm_head":
            return P(_maybe(fsdp, shape[0], mesh), _maybe("model", shape[1], mesh))
        if key == "router":
            return P(*([None] * nd))
        if key == "conv":
            return P(*([None] * (nd - 1)), _maybe("model", shape[-1], mesh))
        if nd == 4:  # stacked MoE experts (L, E, d, ff) / (L, E, ff, d)
            if key == "w_down":
                return P(None, _maybe("model", shape[1], mesh),
                         _maybe(fsdp, shape[2], mesh), None)
            return P(None, _maybe("model", shape[1], mesh), None,
                     _maybe(fsdp, shape[3], mesh))
        if key in ("q", "k", "v", "o") and not head_tp:
            # sequence-parallel attention: weights replicated over model
            # (activations shard the sequence dim instead)
            return P(*([None] * (nd - 2)),
                     _maybe(fsdp, shape[-2], mesh) if key != "o" else None,
                     None if key != "o" else _maybe(fsdp, shape[-1], mesh))
        if key in _REDUCE_FIRST:
            return P(*([None] * (nd - 2)),
                     _maybe("model", shape[-2], mesh),
                     _maybe(fsdp, shape[-1], mesh))
        # default: shard output dim on model, input dim on fsdp
        return P(*([None] * (nd - 2)),
                 _maybe(fsdp, shape[-2], mesh),
                 _maybe("model", shape[-1], mesh))

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    return jax.tree_util.tree_unflatten(treedef, [spec(p, l) for p, l in flat])


# ---------------------------------------------------------------------------
# Activation logical rules (consumed by distributed.ctx.shard_hint)
# ---------------------------------------------------------------------------
def logical_rules(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> Dict:
    dp = dp_axes(mesh)
    dp = dp if len(dp) > 1 else (dp[0] if dp else None)
    msz = mesh.shape["model"]
    batch_ax = dp
    dpsz = 1
    for a in (dp if isinstance(dp, tuple) else ((dp,) if dp else ())):
        dpsz *= mesh.shape[a]
    local_b = shape.microbatch if shape.kind == "train" and shape.microbatch \
        else shape.global_batch
    if local_b < dpsz:
        batch_ax = "data" if local_b >= mesh.shape["data"] else None
    # attention mode: clean head-TP when head count divides the model axis;
    # otherwise sequence-parallel attention (replicated small attn weights,
    # seq-sharded activations) - see DESIGN.md Sec. 4.
    head_tp = bool(cfg.num_heads) and cfg.num_heads % msz == 0
    seq_attn = bool(cfg.num_heads) and not head_tp
    return {
        "batch": batch_ax,
        "heads": "model" if head_tp else None,
        "kv_heads": ("model" if (head_tp and cfg.num_kv_heads
                                 and cfg.num_kv_heads % msz == 0) else None),
        "attn_seq": "model" if seq_attn else None,
        "vocab": "model" if _ok(cfg.vocab_size, msz) else None,
        "experts": "model" if cfg.num_experts and _ok(cfg.num_experts, msz) else None,
        "expert_cap": batch_ax,     # MoE capacity shards with the tokens
        "seq": None,
    }


# ---------------------------------------------------------------------------
# Batch / cache / optimizer specs
# ---------------------------------------------------------------------------
def batch_pspecs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                 with_labels: bool) -> Dict[str, P]:
    rules = logical_rules(cfg, shape, mesh)
    b = rules["batch"]
    out: Dict[str, P] = {}
    if cfg.input_kind == "tokens":
        out["tokens"] = P(b, None)
    else:
        out["embeddings"] = P(b, None, None)
    if with_labels:
        out["labels"] = P(b, None)
    return out


def cache_pspecs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> Dict[str, P]:
    """KV / SSM cache specs for decode cells.

    Dense caches are (L, B, S, Hkv, hd). kv-heads shard over ``model`` when
    wide enough, else the sequence dim takes ``model`` (SP).  Batch shards
    over dp when it covers the axis, else sequence takes ``data`` too
    (long-context, batch=1).
    """
    rules = logical_rules(cfg, shape, mesh)
    b = rules["batch"]
    kvh = rules["kv_heads"]
    out: Dict[str, Any] = {"pos": P()}
    if cfg.family in ("dense", "moe", "hybrid"):
        seq_ax = None
        if kvh is None:
            seq_ax = "model"
        if b is None:
            seq_ax = ("data", "model") if kvh is None else "data"
        if cfg.family == "hybrid":
            out["k"] = P(None, b, seq_ax, kvh, None)
            out["v"] = P(None, b, seq_ax, kvh, None)
        else:
            out["k"] = P(None, b, seq_ax, kvh, None)
            out["v"] = P(None, b, seq_ax, kvh, None)
        if cfg.family == "moe" and shape.kind == "decode":
            out["expert_rows"] = P()     # make_cache's count; prefill has none
    if cfg.family in ("ssm", "hybrid"):
        h_ax = "model" if cfg.ssm_heads >= mesh.shape["model"] else None
        out["state"] = P(None, b, h_ax, None, None)
        out["conv_buf"] = P(None, b, None, "model")
    return out


def opt_pspecs(param_specs):
    from ..optim.adamw import AdamWState
    return AdamWState(step=P(), m=param_specs,
                      v=jax.tree.map(lambda s: s, param_specs),
                      master=jax.tree.map(lambda s: s, param_specs))


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
