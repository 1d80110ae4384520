"""The served-path packed kernels compile for a TPU v5e at qwen2-1.5b
widths, and the grouped expert kernels at mellum2-12b-a2.5b's, alone
and in an expert-parallel MoE layer over four chips.

Nothing runs: each test lowers one packed matmul wrapper for one chip of
a described (not attached) ``v5e:2x2`` topology, or the MoE layer for
all four, and compiles it with the TPU compiler, which refuses what
interpret mode cannot see (unaligned blocks, too much VMEM, a Pallas
call left for GSPMD to partition).  The topology is described inside a
fixture, so only the test worker that runs this file loads the TPU
library.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.decompose import delta_bits
from repro.core.packing import blocked_rows, choose_block
from repro.kernels.expert_matmul import kernel as em_kernel
from repro.kernels.expert_matmul import ops as em_ops
from repro.kernels.nested_matmul import ops as nm_ops
from repro.kernels.packed_matmul import ops as pm_ops

M = 8                                       # one decode step, sublane-padded
# qwen2-1.5b (K, N): q/o and MLP up/gate, MLP down, LM head
WIDTHS = [(1536, 8960), (8960, 1536), (1536, 151936)]
KERNELS = ["packed4", "packed8", "nested6in4", "ladder864"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _wrapper_and_streams(kernel, K):
    """(jitted-able wrapper, bit widths of its packed streams)."""
    block = choose_block(K)
    kw = dict(K=K, block_k=block, use_pallas=True, out_dtype=jnp.bfloat16)
    if kernel == "packed4":
        return functools.partial(pm_ops.packed_matmul, k=4, **kw), (4,), block
    if kernel == "packed8":
        return functools.partial(pm_ops.packed_matmul, k=8, **kw), (8,), block
    if kernel == "nested6in4":
        return (functools.partial(nm_ops.nested_matmul, n=6, h=4, **kw),
                (4,) + delta_bits((4, 6)), block)
    bits = (4, 6, 8)
    fn = functools.partial(nm_ops.ladder_matmul, bits=bits, **kw)
    return ((lambda x, *streams_scale: fn(x, streams_scale[:-1],
                                          streams_scale[-1])),
            (4,) + delta_bits(bits), block)


@pytest.mark.parametrize("K,N", WIDTHS, ids=[f"{k}x{n}" for k, n in WIDTHS])
@pytest.mark.parametrize("kernel", KERNELS)
def test_served_kernel_compiles_for_v5e(one_chip, kernel, K, N):
    fn, widths, block = _wrapper_and_streams(kernel, K)
    args = [jax.ShapeDtypeStruct((M, K), jnp.bfloat16, sharding=one_chip)]
    args += [jax.ShapeDtypeStruct((K // block * blocked_rows(block, w), N),
                                  jnp.int32, sharding=one_chip)
             for w in widths]
    args.append(jax.ShapeDtypeStruct((1, N), jnp.float32, sharding=one_chip))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# mellum2-12b-a2.5b's experts (K, N): gate and up, down; 16 held, one
# decode step of 128 rows routed top-8 of 64 (tiles as the model plans)
EXPERT_WIDTHS = [(2304, 896), (896, 2304)]
HELD, ROWS_K = 16, 128 * 8


@pytest.mark.parametrize("K,N", EXPERT_WIDTHS,
                         ids=[f"{k}x{n}" for k, n in EXPERT_WIDTHS])
@pytest.mark.parametrize("rung", [0, 1, 2])
def test_expert_kernel_compiles_for_v5e(one_chip, rung, K, N):
    bits = (4, 6, 8)[:rung + 1]
    widths = ((4,) + delta_bits((4, 6, 8)))[:rung + 1]
    block = choose_block(K)
    tm = em_ops.row_tile(ROWS_K / 64)
    tiles = -(-(ROWS_K + HELD * (tm - 1)) // tm)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    streams = tuple(sds((HELD, K // block * blocked_rows(block, w), N),
                        jnp.int32) for w in widths)
    fn = functools.partial(em_kernel.by_streams(rung + 1), bits=bits,
                           widths=widths, K=K, block_k=block, tm=tm,
                           block_n=em_ops.block_n(N), out_dtype=jnp.bfloat16)
    text = jax.jit(fn).lower(
        sds((tiles * tm, K), jnp.bfloat16), streams,
        sds((HELD, 1, N), jnp.float32), sds((tiles,), jnp.int32),
        sds((tiles,), jnp.int32), sds((1,), jnp.int32)).compile().as_text()
    name = ("expert_packed_matmul", "expert_nested_matmul",
            "expert_ladder_matmul")[rung]
    assert "tpu_custom_call" in text and name in text


def _expert_stacks(mesh, spec):
    """Mellum's held gate, up and down stacks, packed 8 ⊃ 6 ⊃ 4 and
    stamped at rung 2, as abstract arrays laid out by ``spec``."""
    from jax.sharding import NamedSharding

    from repro.core.nesting import nest_quantize

    def stack(K, N):
        nt = jax.eval_shape(lambda: nest_quantize(
            jnp.zeros((HELD, K, N), jnp.float32), bits=(4, 6, 8),
            rounding="rtn", block=choose_block(K)))
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)), nt)

    (d, ff), _ = EXPERT_WIDTHS
    return {"w_gate": {"w": stack(d, ff)}, "w_up": {"w": stack(d, ff)},
            "w_down": {"w": stack(ff, d)}}


def test_expert_parallel_layer_compiles_for_v5e_2x2(topo, one_chip,
                                                    monkeypatch):
    """Mellum's MoE layer on the four described chips, experts over
    ``model``, 128 decode rows over ``data``: the grouped Pallas kernel
    runs on each chip's own experts, the shares meet in an all-reduce,
    and no chip all-gathers an expert stack."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.distributed.ctx import logical_rules
    from repro.models.moe import held_moe_ffn
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    d = EXPERT_WIDTHS[0][0]
    params = {"router": {"w": jax.ShapeDtypeStruct(
        (d, 64), jnp.float32, sharding=NamedSharding(mesh, P()))},
        "experts": _expert_stacks(mesh, P("model"))}
    x = jax.ShapeDtypeStruct((128, 1, d), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    with logical_rules(mesh, {"experts": "model", "batch": "data"}):
        text = jax.jit(lambda x, p: held_moe_ffn(
            x, p, num_experts=64, top_k=8, held=HELD)).lower(
            x, params).compile().as_text()
    assert "expert_ladder_matmul" in text and "all-reduce" in text
    stacks = {",".join(map(str, a.shape))
              for a in jax.tree.leaves(params["experts"])}
    gathers = [l for l in text.splitlines() if "all-gather" in l
               and any(f"[{s}]" in l for s in stacks)]
    assert not gathers, gathers[:3]
