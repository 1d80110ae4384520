"""Jitted public wrapper: platform dispatch + weight preparation."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ...core import packing
from ...core.nesting import NestedTensor
from ..dispatch import plan
from . import kernel, ref

DEFAULT_BLOCK_K = 512


def prepare(nt: NestedTensor, mode: str = "full",
            block_k: int = DEFAULT_BLOCK_K) -> Tuple[jax.Array, jax.Array, int, int]:
    """NestedTensor -> (block-packed words, scale, k, K) for the kernel.

    mode 'full': recomposed INT-n codes re-packed as ONE k=n stream
    (single-stream fallback; the dual-stream kernels/nested_matmul reads
    the stored streams directly); 'part': INT-h codes with the inflated
    nesting scale s*2^l (paper Eq. 10).  Repacks to ``block_k`` blocks,
    padding K up to a block multiple.
    """
    assert len(nt.shape) == 2, "kernel path expects a 2-D weight"
    K = nt.shape[-2]
    if mode == "full":
        codes, k, scale = nt.codes_full(), nt.n, nt.scale
    else:
        codes, k, scale = nt.codes_high(), nt.h, nt.part_scale
    pad = (-K) % block_k
    if pad:
        codes = jnp.concatenate(
            [codes, jnp.zeros((pad,) + codes.shape[1:], codes.dtype)], axis=0)
    words = packing.pack_blocked(codes, k, block_k, axis=0)
    return words, scale.reshape(1, -1), k, codes.shape[0]


def packed_matmul(x, words, scale, *, k: int, K: int,
                  block_k: int = DEFAULT_BLOCK_K, use_pallas: bool = None,
                  interpret: bool = False, out_dtype=None):
    """y = x @ dequant(words).  Pallas on TPU (or interpret=True for
    validation); the jnp reference on other backends or with
    use_pallas=False.  A TPU shape that misses the tile contract raises
    (kernels.dispatch.plan)."""
    N = words.shape[-1]
    x2, lead, M, bm, take_kernel = plan(x, N, K, block_k, use_pallas, interpret)
    if take_kernel:
        y = kernel.packed_matmul(x2, words, scale, k=k, K=K,
                                 block_m=bm, block_k=block_k,
                                 interpret=interpret, out_dtype=out_dtype)[:M]
    else:
        y = ref.packed_matmul_ref(x2, words, scale, k=k, K=K, block_k=block_k,
                                  out_dtype=out_dtype)
    return y.reshape(lead + (y.shape[-1],))
