"""Jitted public wrapper: platform dispatch for the dual-stream matmul."""
from __future__ import annotations

from ..dispatch import plan
from . import kernel, ref

DEFAULT_BLOCK_K = 512


def nested_matmul(x, words_high, words_low, scale, *, n: int, h: int, K: int,
                  block_k: int = DEFAULT_BLOCK_K, use_pallas: bool = None,
                  interpret: bool = False, out_dtype=None):
    """y = x @ dequant(recompose(words_high, words_low)).

    Pallas on TPU (or interpret=True for validation); the jnp reference
    on other backends or with use_pallas=False.  A TPU shape that misses
    the tile contract raises (kernels.dispatch.plan).
    """
    N = words_high.shape[-1]
    x2, lead, M, bm, take_kernel = plan(x, N, K, block_k, use_pallas, interpret)
    if take_kernel:
        y = kernel.nested_matmul(x2, words_high, words_low, scale,
                                 n=n, h=h, K=K, block_m=bm, block_k=block_k,
                                 interpret=interpret, out_dtype=out_dtype)[:M]
    else:
        y = ref.nested_matmul_ref(x2, words_high, words_low, scale,
                                  n=n, h=h, K=K, block_k=block_k,
                                  out_dtype=out_dtype)
    return y.reshape(lead + (y.shape[-1],))


def ladder_matmul(x, streams, scale, *, bits, K: int,
                  block_k: int = DEFAULT_BLOCK_K, use_pallas: bool = None,
                  interpret: bool = False, out_dtype=None):
    """y = x @ dequant(chain-recompose(streams)) for a serving rung with
    ``len(streams)`` resident streams (base + deltas; bits ascending, one
    entry per stream; scale = the rung scale).

    Pallas on TPU (or interpret=True for validation); the jnp reference
    on other backends or with use_pallas=False.  A TPU shape that misses
    the tile contract raises (kernels.dispatch.plan).
    """
    streams = tuple(streams)
    N = streams[0].shape[-1]
    x2, lead, M, bm, take_kernel = plan(x, N, K, block_k, use_pallas, interpret)
    if take_kernel:
        y = kernel.ladder_matmul(x2, streams, scale, bits=tuple(bits), K=K,
                                 block_m=bm, block_k=block_k,
                                 interpret=interpret, out_dtype=out_dtype)[:M]
    else:
        y = ref.ladder_matmul_ref(x2, streams, scale, bits=tuple(bits), K=K,
                                  block_k=block_k, out_dtype=out_dtype)
    return y.reshape(lead + (y.shape[-1],))
