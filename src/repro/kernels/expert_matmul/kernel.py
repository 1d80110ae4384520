"""Pallas TPU kernel: grouped dequant-matmul over packed expert stacks.

The expert layer of a mixture-of-experts model holds a stack of n
expert weights, each a NestQuant ladder (base stream plus deltas,
block-packed along K exactly as kernels/packed_matmul and
kernels/nested_matmul read them, with one leading expert axis).  The
rows of a step are sorted by expert, and each expert's group of rows is
padded to a multiple of the row tile ``tm``, so every row tile belongs
to one expert.  Three scalar-prefetch arrays map the grid onto the
groups: ``tile_group[i]`` is the expert of row tile i, ``tile_block[i]``
the row block it reads and writes, and ``count[0]`` how many row tiles
hold rows; tiles past it skip all work and point at the last live
tile's blocks, so they move no bytes.

The grid is (N // bn, row tiles), row tiles innermost: consecutive tiles
of one expert see the same weight block, which the pipeline then does
not fetch again, and the kernel unpacks and recomposes the block into
a VMEM scratch once per expert and column block (when the tile's expert
differs from the tile before it).  Each held expert's packed words are
therefore read and unpacked once per call, however many row tiles its
group spans, and only routed rows (padded to the tile) reach the MXU.

One kernel serves every rung: the streams it is given (the base alone
at rung 0, base and first delta at rung 1, more at deeper rungs) are
chained by Eq. 6 per level in VMEM and dequantized by the rung scale.
The whole of K is one grid step; inside it the kernel walks K one pack
block at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.decompose import chain_recompose
from ...core.packing import blocked_rows, unpack_block_words

VMEM_LIMIT = 64 * 1024 * 1024


def _kernel(group_ref, block_ref, count_ref, x_ref, *refs, bits, widths,
            nb, bk):
    """refs = (*stream_refs, s_ref, o_ref, w_scratch)."""
    n_streams = len(widths)
    stream_refs = refs[:n_streams]
    s_ref, o_ref, w_ref = refs[n_streams:]
    i = pl.program_id(1)
    live = i < count_ref[0]
    fresh = jnp.logical_or(i == 0,
                           group_ref[i] != group_ref[jnp.maximum(i - 1, 0)])

    @pl.when(jnp.logical_and(live, fresh))
    def _unpack():
        for b in range(nb):
            codes = []
            for ref, w in zip(stream_refs, widths):
                rows = blocked_rows(bk, w)
                codes.append(unpack_block_words(ref[b * rows:(b + 1) * rows, :],
                                                w, bk))
            c = (codes[0] if n_streams == 1
                 else chain_recompose(codes[0], codes[1:], bits))
            w_ref[b * bk:(b + 1) * bk, :] = c.astype(w_ref.dtype)

    @pl.when(live)
    def _matmul():
        acc = jnp.dot(x_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
        o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


def _call(name, x, streams, scale, tile_group, tile_block, count, *, bits,
          widths, K, block_k, tm, block_n, interpret, out_dtype):
    P = x.shape[0]
    N = streams[0].shape[-1]
    nb = K // block_k
    J = N // block_n
    rows = [nb * blocked_rows(block_k, w) for w in widths]

    # a tile past the live ones names the last live tile's blocks, so
    # the pipeline sees no new block and moves nothing
    def x_map(j, i, g, blk, cnt):
        return (blk[i], 0)

    def w_map(j, i, g, blk, cnt):
        return (g[i], 0, j)

    def o_map(j, i, g, blk, cnt):
        return (blk[i], j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(J, P // tm),
        in_specs=[pl.BlockSpec((tm, K), x_map),
                  *[pl.BlockSpec((None, r, block_n), w_map) for r in rows],
                  pl.BlockSpec((None, 1, block_n), w_map)],
        out_specs=pl.BlockSpec((tm, block_n), o_map),
        scratch_shapes=[pltpu.VMEM((K, block_n), x.dtype)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, widths=widths, nb=nb,
                          bk=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, N), out_dtype or x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(tile_group, tile_block, count, x, *streams, scale)


def _entry(name):
    def entry(x, streams, scale, tile_group, tile_block, count, *, bits,
              widths, K, block_k, tm, block_n, interpret=False,
              out_dtype=None):
        return _call(name, x, streams, scale, tile_group, tile_block, count,
                     bits=bits, widths=widths, K=K, block_k=block_k, tm=tm,
                     block_n=block_n, interpret=interpret,
                     out_dtype=out_dtype)
    entry.__name__ = entry.__qualname__ = name
    return jax.jit(entry, static_argnames=(
        "bits", "widths", "K", "block_k", "tm", "block_n", "interpret",
        "out_dtype"))


# One jitted entry per stream count, so that the device trace names the
# rung's kernel: the base alone, the dual stream, or the ladder.
expert_packed_matmul = _entry("expert_packed_matmul")
expert_nested_matmul = _entry("expert_nested_matmul")
expert_ladder_matmul = _entry("expert_ladder_matmul")


def by_streams(n_streams: int):
    """The jitted kernel entry for a rung with ``n_streams`` streams."""
    return (expert_packed_matmul, expert_nested_matmul,
            expert_ladder_matmul)[min(n_streams, 3) - 1]
