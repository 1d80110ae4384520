"""Pure-jnp oracle for the grouped expert dequant-matmul."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import packing
from ...core.decompose import chain_recompose


def expert_matmul_ref(x, streams, scale, group_sizes, *, bits, widths,
                      K: int, block_k: int, out_dtype=None):
    """Row r of y is x[r] @ (codes[g] * scale[g]) for the expert g whose
    group holds row r.

    x: (P, K) rows sorted by expert; streams: (base, delta_0, ...) of
    block-packed int32 (n, rows_i, N); scale: (n, 1, N) f32, the rung
    scale; group_sizes: (n,) int32 rows of each group in order, padding
    included.  Rows past the groups come out zero."""
    codes = [packing.unpack_blocked(s, w, K, block_k, axis=1)
             for s, w in zip(streams, widths)]
    c = codes[0] if len(codes) == 1 else chain_recompose(codes[0], codes[1:],
                                                         bits)
    w = c.astype(jnp.float32) * scale
    y = jax.lax.ragged_dot(x.astype(jnp.float32), w,
                           group_sizes.astype(jnp.int32))
    return y.astype(out_dtype or x.dtype)
