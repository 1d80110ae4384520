"""Shared tile-contract dispatch for the packed matmul wrappers.

The Pallas kernels compute over an (M // block_m, N // block_n, K //
block_k) grid, so shapes that do not tile evenly would silently leave
tail rows unwritten.  The single plan() here is what both wrappers
(packed_matmul, nested_matmul) consult: it flattens leading dims, pads M
up to the sublane/tile contract (decode micro-batches of 1-7 tokens stay
on the packed kernel path - the serving hot path must never fall back to
dense dequant), and picks a block_m that divides the padded M.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def plan(x, N: int, K: int, block_k: int, use_pallas, interpret: bool):
    """Returns (x2, lead, M, block_m, take_kernel).

    x2 is x flattened to (M_padded, K) with zero rows appended up to the
    tile: a multiple of 8 (sublane) for small M, a multiple of the full
    128-row MXU tile when M > 128 - padding rows are strictly cheaper
    than shrinking block_m and multiplying grid steps.  Callers slice
    the kernel output back to the original M rows.  The kernel path
    additionally requires N a multiple of the 128-lane block_n and K a
    multiple of block_k.  A shape that misses it raises on a TPU backend
    (``use_pallas=None``), so the chip never runs the reference unseen;
    elsewhere, or with ``use_pallas=False``, the jnp reference runs on
    the unpadded input."""
    on_chip = use_pallas is None and jax.default_backend() == "tpu"
    if use_pallas is None:
        use_pallas = on_chip
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    M = x2.shape[0]
    tiles = N % 128 == 0 and K % block_k == 0
    if on_chip and not tiles:
        raise ValueError(
            f"packed matmul of shape M={M} K={K} N={N} misses the kernel "
            f"tile contract (N % 128 == 0, K % block_k == 0 with "
            f"block_k={block_k}); pass use_pallas=False to run the jnp "
            "reference")
    take_kernel = (use_pallas or interpret) and M > 0 and tiles
    if not take_kernel:
        return x2, lead, M, 0, False
    tile = 8 if M <= 128 else 128
    pad = (-M) % tile
    if pad:
        x2 = jnp.concatenate(
            [x2, jnp.zeros((pad, x2.shape[1]), x2.dtype)], axis=0)
    return x2, lead, M, min(128, M + pad), True
