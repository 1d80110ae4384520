"""Mixture-of-Experts FFN: top-k routing, sort-based dispatch.

Two dispatches live here.  Training (``moe_ffn``) keeps the capacity
dispatch described next.  Inference (``held_moe_ffn``: prefill, decode
and the speculative verify) routes over every expert but computes only
the experts this device holds, on ragged groups of rows sorted by
expert, straight from the packed expert stacks (kernels/expert_matmul).

GShard/Switch-style "dropping" implementation, XLA/TPU-friendly: tokens are
argsorted by assigned expert, given a position-in-expert via the sorted
prefix, dropped beyond the per-expert capacity C, and gathered into a dense
(E, C, d) tensor for grouped einsum matmuls.

Distribution (§Perf change P1, see EXPERIMENTS.md): the gather/scatter of
the dispatch is wrapped in ``shard_map`` over the data-parallel axes, so
each data shard routes ONLY its own tokens with a local capacity
C_local = C / dp - the gather and combine scatter-add are provably local.
Under plain GSPMD the same global gather lowered to a full all-gather of
the (T, d) token buffer plus an all-reduce of the scatter (observed 7.2 of
8.6 TB/device collective wire on llama4-scout train_4k).  The only EP
communication left is the all-gather of expert outputs over the ``model``
axis (the minimal token<->expert exchange), and its mirror in backward.

FLOPs are honest: 2*E*C*(3*d*ff) per layer = tokens*top_k*cf*(3*d*ff)*2.
Per-shard capacity changes the drop pattern vs global capacity under
imbalance - the standard trade of grouped dispatch (GShard groups).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.decompose import delta_bits
from ..core.nesting import NestedTensor
from ..distributed import ctx
from ..distributed.ctx import shard_hint
from ..kernels.expert_matmul import ops as expert_ops
from .layers import linear, pdot, resolve_weight, silu

# tokens routed in one pass of the inference dispatch: a longer input is
# cut into passes, so the padded row buffer stays within a few hundred MB
HELD_PASS_TOKENS = 8192


def capacity(tokens: int, num_experts: int, top_k: int, factor: float,
             multiple: int = 8) -> int:
    """Per-expert slot count C of the training dispatch."""
    c = math.ceil(tokens * top_k * factor / num_experts)
    return max(multiple, math.ceil(c / multiple) * multiple)


# ---------------------------------------------------------------------------
# dispatch core (runs globally on one device, or per data shard in shard_map)
# ---------------------------------------------------------------------------
def _dispatch(xf, router_w, *, E: int, K: int, C: int):
    """xf: (T, d) -> (xg (E,C,d), table (E*C,), gates (E*C,), aux)."""
    T, d = xf.shape
    logits = pdot(xf, router_w.astype(xf.dtype), preferred=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)                  # (T,K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch):  E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32), axis=1),
        axis=0)
    aux = E * jnp.sum(me * ce)

    ef = expert_idx.reshape(T * K)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    gf = gate_vals.reshape(T * K)
    order = jnp.argsort(ef, stable=True)
    se, st, sg = ef[order], tok[order], gf[order]
    starts = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos = jnp.arange(T * K, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos < C
    slot = jnp.where(keep, se.astype(jnp.int32) * C + pos, E * C)

    table = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(st)[: E * C]
    gates = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(sg)[: E * C]
    xp = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xg = xp[table].reshape(E, C, d)
    return xg, table, gates, aux


def _combine(y, table, gates, T: int, d: int):
    """y: (E,C,d) -> (T,d) scatter-add with gate weights."""
    E, C, _ = y.shape
    yf = y.reshape(E * C, d) * gates[:, None].astype(y.dtype)
    return jnp.zeros((T + 1, d), y.dtype).at[table].add(yf)[:T]


# ---------------------------------------------------------------------------
# public MoE FFN
# ---------------------------------------------------------------------------
def moe_ffn(x: jax.Array, params: Dict, *, num_experts: int, top_k: int,
            capacity_factor: float, act: str = "swiglu",
            cap_multiple: int = 8) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss), the training dispatch: capacity
    C per expert, assignments past it dropped (the standard GShard
    efficiency trade).  Inference routes exactly with
    :func:`held_moe_ffn`."""
    B, S, d = x.shape
    T = B * S
    E, K = num_experts, top_k
    xf = x.reshape(T, d)
    rw = params["router"]["w"]

    cur = ctx.current()
    dp_axes = None
    if cur is not None:
        mesh, rules = cur
        b_ax = rules.get("batch")
        if b_ax:
            dp_axes = b_ax if isinstance(b_ax, tuple) else (b_ax,)

    if dp_axes:
        dpsz = 1
        for a in dp_axes:
            dpsz *= mesh.shape[a]
        if T % dpsz == 0:
            C_loc = capacity(T // dpsz, E, K, capacity_factor, cap_multiple)
            xg, table, gates, aux = _sharded_dispatch(
                mesh, dp_axes, xf, rw, E=E, K=K, C=C_loc)
            y = _expert_compute(xg, params, act, x.dtype)
            y = shard_hint(y, (None, "expert_cap", None))   # gather E over model
            out = _sharded_combine(mesh, dp_axes, y, table, gates,
                                   T_loc=T // dpsz, d=d)
            return out.reshape(B, S, d), aux
        # fall through to the global path when tokens don't split evenly

    C = capacity(T, E, K, capacity_factor, cap_multiple)
    xg, table, gates, aux = _dispatch(xf, rw, E=E, K=K, C=C)
    xg = shard_hint(xg, ("experts", "expert_cap", None))
    y = _expert_compute(xg, params, act, x.dtype)
    out = _combine(y, table, gates, T, d)
    return out.reshape(B, S, d), aux


def _expert_compute(xg, params, act, dtype):
    wg = resolve_weight(params["experts"]["w_gate"]["w"], dtype).astype(dtype)
    wu = resolve_weight(params["experts"]["w_up"]["w"], dtype).astype(dtype)
    wd = resolve_weight(params["experts"]["w_down"]["w"], dtype).astype(dtype)
    xg = xg.astype(dtype)
    if act == "swiglu":
        h = silu(jnp.einsum("ecd,edf->ecf", xg, wg)) * \
            jnp.einsum("ecd,edf->ecf", xg, wu)
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xg, wu))
    h = h.astype(dtype)
    return jnp.einsum("ecf,efd->ecd", h, wd).astype(dtype)


def _sharded_dispatch(mesh, dp_axes, xf, rw, *, E, K, C):
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    def local(xf_loc, rw_loc):
        xg, table, gates, aux = _dispatch(xf_loc, rw_loc, E=E, K=K, C=C)
        aux = jax.lax.pmean(aux, dp)
        return xg.astype(xf_loc.dtype), table, gates, aux

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None), P(None, None)),
        out_specs=(P(None, dp, None), P(dp), P(dp), P()),
        check_vma=False,
    )(xf, rw)


def _sharded_combine(mesh, dp_axes, y, table, gates, *, T_loc, d):
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    def local(y_loc, table_loc, gates_loc):
        return _combine(y_loc, table_loc, gates_loc, T_loc, d)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, dp, None), P(dp), P(dp)),
        out_specs=P(dp, None),
        check_vma=False,
    )(y, table, gates)


# ---------------------------------------------------------------------------
# inference: the held experts on ragged groups, from the packed stacks
# ---------------------------------------------------------------------------
def route(xf, router_w, top_k: int):
    """Softmax over every expert, top-k, renormalised: (gates (T, K) f32,
    experts (T, K) int32)."""
    logits = jnp.matmul(xf.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    return gates / jnp.sum(gates, axis=-1, keepdims=True), experts


def grouped_matmul(x, w, plan: expert_ops.GroupPlan):
    """Rows of ``x`` laid out by ``plan`` times their expert's weight of
    the (n, K, N) stack ``w``: packed NestQuant streams at the stamped
    rung, or a float stack."""
    if not isinstance(w, NestedTensor):
        return jax.lax.ragged_dot(x, w.astype(x.dtype),
                                  plan.sizes).astype(x.dtype)
    r = w.rung
    return expert_ops.expert_matmul(
        x, (w.w_base,) + tuple(w.deltas[:r]), w.rung_scale(r), plan,
        bits=w.bits[:r + 1], widths=(w.bits[0],) + delta_bits(w.bits)[:r],
        K=w.K, block_k=w.block, out_dtype=x.dtype)


def _held_pass(xf, params, *, num_experts: int, top_k: int, first,
               held: int, act: str, out_dtype):
    """One pass of ``held_moe_ffn`` over xf (T, d): (out (T, d),
    rows (held,))."""
    T, d = xf.shape
    E, K, n = num_experts, top_k, held
    gates, experts = route(xf, params["router"]["w"], K)
    local = experts - first
    mine = (local >= 0) & (local < n)
    group = jnp.where(mine, local, n).reshape(T * K)   # n: not held here
    sizes = jnp.sum(jax.nn.one_hot(group, n, dtype=jnp.int32), axis=0)
    tm = expert_ops.row_tile(T * K / E)
    tiles = -(-(T * min(K, n) + n * (tm - 1)) // tm)
    plan = expert_ops.group_plan(sizes, tm, tiles)
    P = tiles * tm
    # the assignments in expert order, and the padded row of each
    order = jnp.argsort(group, stable=True)
    sg = group[order]
    held_g = jnp.minimum(sg, n - 1)
    rank = jnp.arange(T * K, dtype=jnp.int32) - (
        jnp.cumsum(sizes) - sizes)[held_g]
    row = jnp.where(sg < n, plan.starts[held_g] + rank, P)
    token = jnp.full((P + 1,), T, jnp.int32).at[row].set(
        (order // K).astype(jnp.int32))[:P]
    xs = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)[token]
    ex = params["experts"]
    if act == "swiglu":
        h = silu(grouped_matmul(xs, ex["w_gate"]["w"], plan)) * \
            grouped_matmul(xs, ex["w_up"]["w"], plan)
    else:
        h = jax.nn.gelu(grouped_matmul(xs, ex["w_up"]["w"], plan))
    y = grouped_matmul(h.astype(xf.dtype), ex["w_down"]["w"], plan)
    # each assignment reads its row back, one of the k at a time; rows
    # not held here add nothing
    at = jnp.zeros((T * K,), jnp.int32).at[order].set(
        jnp.minimum(row, P - 1)).reshape(T, K)
    out = jnp.zeros((T, d), jnp.float32)
    for k in range(K):
        yk = jnp.where(mine[:, k, None], y[at[:, k]], 0)
        out = out + yk.astype(jnp.float32) * gates[:, k, None]
    return out.astype(out_dtype), sizes


def _held_tokens(xf, params, **kw):
    """``_held_pass`` over xf (T, d) in passes of at most
    HELD_PASS_TOKENS tokens: (out (T, d), rows (held,))."""
    T, d = xf.shape
    passes = -(-T // HELD_PASS_TOKENS)
    while T % passes:
        passes += 1
    if passes == 1:
        return _held_pass(xf, params, **kw)
    out, rows = jax.lax.map(partial(_held_pass, params=params, **kw),
                            xf.reshape(passes, T // passes, d))
    return out.reshape(T, d), jnp.sum(rows, axis=0)


def _expert_parallel(T: int, held: int):
    """(mesh, data axes or None, expert axis) when a mesh context shards
    the held experts evenly over an axis and the tokens over the data
    axes, else None."""
    cur = ctx.current()
    if cur is None:
        return None
    mesh, rules = cur
    ep = rules.get("experts")
    if not ep or held % mesh.shape[ep]:
        return None
    dp = rules.get("batch")
    dp = (dp if isinstance(dp, tuple) else (dp,)) if dp else ()
    dpsz = math.prod(mesh.shape[a] for a in dp)
    if T % dpsz:
        return None
    return mesh, dp, ep


def held_moe_ffn(x: jax.Array, params: Dict, *, num_experts: int,
                 top_k: int, first: int = 0, held: int = 0,
                 act: str = "swiglu") -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, rows), the inference dispatch.

    The router scores all ``num_experts`` experts; this device holds
    experts [first, first + held) (``held`` 0: all of them), stacked on
    the leading axis of ``params["experts"]``.  Each token's top-k
    assignments that fall on a held expert are sorted by expert into
    ragged groups, each padded to the row tile, and computed by the
    grouped matmul; the output is their gate-weighted sum, and what the
    experts held elsewhere would add is left out.  Routing is exact and
    per token, so a token's output does not depend on the batch around
    it.  ``rows`` (held,) counts the assignments each held expert took.

    Under a mesh whose rules put ``experts`` on an axis (expert
    parallelism), each device of that axis holds its slice of the held
    stacks and routes its data shard's tokens over every expert; the
    slices' partial sums meet in one f32 ``psum`` over the expert axis,
    so no device gathers another's experts."""
    B, S, d = x.shape
    T = B * S
    n = held or num_experts
    xf = x.reshape(T, d)
    kw = dict(num_experts=num_experts, top_k=top_k, act=act)
    layout = _expert_parallel(T, n)
    if layout is None:
        out, rows = _held_tokens(xf, params, first=first, held=n,
                                 out_dtype=x.dtype, **kw)
        return out.reshape(B, S, d), rows
    mesh, dp, ep = layout
    n_loc = n // mesh.shape[ep]
    tok = P(dp or None, None)

    def local(xf_loc, p_loc):
        f = first + jax.lax.axis_index(ep) * n_loc
        out, rows = _held_tokens(xf_loc, p_loc, first=f, held=n_loc,
                                 out_dtype=jnp.float32, **kw)
        out = jax.lax.psum(out, ep)
        if dp:
            rows = jax.lax.psum(rows, dp)
        return out.astype(xf_loc.dtype), rows

    spec = {"router": jax.tree.map(lambda _: P(), params["router"]),
            "experts": jax.tree.map(lambda _: P(ep), params["experts"])}
    out, rows = jax.shard_map(
        local, mesh=mesh, in_specs=(tok, spec), out_specs=(tok, P(ep)),
        check_vma=False)(xf, params)
    return out.reshape(B, S, d), rows
