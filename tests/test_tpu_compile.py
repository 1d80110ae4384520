"""The served-path packed kernels compile for a TPU v5e at qwen2-1.5b widths.

Nothing runs: each test lowers one packed matmul wrapper for one chip of
a described (not attached) ``v5e:2x2`` topology and compiles it with the
TPU compiler, which refuses what interpret mode cannot see (unaligned
blocks, too much VMEM).  The topology is described inside a fixture, so
only the test worker that runs this file loads the TPU library.
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
