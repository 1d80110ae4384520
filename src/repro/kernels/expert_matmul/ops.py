"""Grouped expert matmul: the row plan and the platform dispatch."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import kernel, ref

MAX_BLOCK_N = 1024


class GroupPlan(NamedTuple):
    """Where each expert's rows lie in a buffer sorted by expert, each
    group padded to a multiple of ``tm`` rows."""
    tile_group: jax.Array     # (tiles,) int32 expert of each row tile
    tile_block: jax.Array     # (tiles,) int32 row block each tile reads
    count: jax.Array          # (1,) int32 row tiles that hold rows
    sizes: jax.Array          # (n,) int32 rows of each group, padded
    starts: jax.Array         # (n,) int32 first row of each group
    tm: int                   # rows per tile (static)


def row_tile(rows_per_group: float) -> int:
    """Rows per tile for groups of about ``rows_per_group`` rows: the
    power of two at least twice that, within [16, 128]."""
    tm = 16
    while tm < 128 and tm < 2 * rows_per_group:
        tm *= 2
    return tm


def group_plan(sizes: jax.Array, tm: int, tiles: int) -> GroupPlan:
    """The plan of ``tiles`` row tiles for groups of ``sizes`` (n,)
    rows.  ``tiles`` is a static bound that any routing fits in; tiles
    past the live ones point at the last live tile."""
    n = sizes.shape[0]
    padded = (sizes + tm - 1) // tm * tm
    ends = jnp.cumsum(padded // tm)
    count = ends[-1]
    t = jnp.minimum(jnp.arange(tiles), jnp.maximum(count - 1, 0))
    group = jnp.minimum(jnp.searchsorted(ends, t, side="right"), n - 1)
    return GroupPlan(group.astype(jnp.int32), t.astype(jnp.int32),
                     count.reshape(1).astype(jnp.int32),
                     padded.astype(jnp.int32),
                     (jnp.cumsum(padded) - padded).astype(jnp.int32), tm)


def block_n(N: int) -> int:
    """The widest multiple of 128 dividing N, at most MAX_BLOCK_N."""
    best = 128
    for b in range(128, min(N, MAX_BLOCK_N) + 1, 128):
        if N % b == 0:
            best = b
    return best


def expert_matmul(x, streams, scale, plan: GroupPlan, *, bits, widths,
                  K: int, block_k: int, use_pallas: bool = None,
                  interpret: bool = False, out_dtype=None):
    """y[r] = x[r] @ dequant(streams of the expert whose group holds r).

    x: (P, K), P = tiles * tm rows laid out by ``plan``; streams: the
    rung's resident streams (base first) of an (n, K, N) expert stack,
    block-packed along K; bits: their bit widths as a ladder (ascending,
    one per stream); widths: the stored width of each stream; scale:
    (n, 1, N) rung scale.  Rows outside the live tiles are not written.
    Pallas on TPU (or interpret=True); the jnp reference elsewhere or
    with use_pallas=False.  A TPU shape that misses the tile contract
    (N % 128 == 0, K % block_k == 0) raises."""
    on_chip = use_pallas is None and jax.default_backend() == "tpu"
    if use_pallas is None:
        use_pallas = on_chip
    streams = tuple(streams)
    N = streams[0].shape[-1]
    tiles = N % 128 == 0 and K % block_k == 0
    if on_chip and not tiles:
        raise ValueError(
            f"expert matmul of shape K={K} N={N} misses the kernel tile "
            f"contract (N % 128 == 0, K % block_k == 0 with "
            f"block_k={block_k}); pass use_pallas=False to run the jnp "
            "reference")
    if (use_pallas or interpret) and tiles:
        return kernel.by_streams(len(streams))(
            x, streams, scale, plan.tile_group, plan.tile_block, plan.count,
            bits=tuple(bits), widths=tuple(widths), K=K, block_k=block_k,
            tm=plan.tm, block_n=block_n(N), interpret=interpret,
            out_dtype=out_dtype)
    return ref.expert_matmul_ref(x, streams, scale, plan.sizes,
                                 bits=tuple(bits), widths=tuple(widths), K=K,
                                 block_k=block_k, out_dtype=out_dtype)
