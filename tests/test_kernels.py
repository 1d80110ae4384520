"""Pallas kernel validation: interpret-mode vs pure-jnp oracle, sweeping
shapes / dtypes / bitwidths (assignment requirement)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import int_range, packing
from repro.core.decompose import decompose
from repro.core.nesting import nest_quantize
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.nest_recompose import kernel as nr_kernel
from repro.kernels.nest_recompose import ref as nr_ref
from repro.kernels.nested_matmul import kernel as nm_kernel
from repro.kernels.nested_matmul import ref as nm_ref
from repro.kernels.packed_matmul import kernel as pm_kernel
from repro.kernels.packed_matmul import ref as pm_ref


@pytest.mark.parametrize("k", [3, 4, 5, 6, 7, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_packed_matmul_bit_sweep(k, dtype):
    rng = np.random.default_rng(k)
    K, N, M, bk = 1024, 256, 32, 512
    lo, hi = int_range(k)
    codes = jnp.asarray(rng.integers(lo, hi + 1, size=(K, N)), jnp.int32)
    words = packing.pack_blocked(codes, k, bk, axis=0)
    scale = jnp.asarray(rng.uniform(0.01, 0.1, size=(1, N)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(M, K)), dtype)
    y_ref = pm_ref.packed_matmul_ref(x, words, scale, k=k, K=K, block_k=bk)
    y_ker = pm_kernel.packed_matmul(x, words, scale, k=k, K=K, block_m=32,
                                    block_n=128, block_k=bk, interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y_ker, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol * 20)


@pytest.mark.parametrize("shape", [(512, 128, 64, 128),   # K,N,M,bk
                                   (2048, 128, 16, 512),
                                   (1024, 512, 8, 256)])
def test_packed_matmul_shape_sweep(shape):
    K, N, M, bk = shape
    rng = np.random.default_rng(0)
    k = 4
    lo, hi = int_range(k)
    codes = jnp.asarray(rng.integers(lo, hi + 1, size=(K, N)), jnp.int32)
    words = packing.pack_blocked(codes, k, bk, axis=0)
    scale = jnp.asarray(rng.uniform(0.01, 0.1, size=(1, N)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    y_ref = pm_ref.packed_matmul_ref(x, words, scale, k=k, K=K, block_k=bk)
    y_ker = pm_kernel.packed_matmul(x, words, scale, k=k, K=K, block_m=min(M, 128),
                                    block_n=128, block_k=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("nh", [(8, 3), (8, 4), (8, 5), (8, 6), (8, 7),
                                (6, 4), (6, 5)])
def test_nest_recompose_exact(nh):
    n, h = nh
    rng = np.random.default_rng(n * 10 + h)
    K, N, bk = 1024, 256, 512
    lo, hi = int_range(n)
    w_int = jnp.asarray(rng.integers(lo, hi + 1, size=(K, N)), jnp.int32)
    wh, wl = decompose(w_int, n, h, method="adaptive")
    wph = packing.pack_blocked(wh, h, bk, axis=0)
    wpl = packing.pack_blocked(wl, n - h + 1, bk, axis=0)
    out_ref = nr_ref.recompose_ref(wph, wpl, n=n, h=h, K=K, block_k=bk)
    out_ker = nr_kernel.nest_recompose(wph, wpl, n=n, h=h, K=K, block_k=bk,
                                       interpret=True)
    assert jnp.array_equal(out_ref, out_ker)
    # kernel output must recompose the original codes exactly (compensation)
    assert jnp.array_equal(out_ker.astype(jnp.int32), w_int)


# ---------------------------------------------------------------------------
# packed execution path: full-bit dual-stream + part-bit single-stream
# matmuls straight from the NestedTensor's stored words (no re-packing)
# ---------------------------------------------------------------------------
NH_SWEEP = [(8, 6), (8, 4), (6, 4)]


def _nested_weight(n, h, K=1024, N=256, seed=0, rounding="rtn"):
    rng = np.random.default_rng(seed + 10 * n + h)
    w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32) * 0.05)
    return w, nest_quantize(w, n=n, h=h, rounding=rounding)


@pytest.mark.parametrize("nh", NH_SWEEP)
def test_nested_matmul_dual_stream_matches_dense(nh):
    """Full-bit: the fused dual-stream kernel reading the STORED packed
    streams must match x @ dense(full_bit) to <=1e-4 relative error."""
    n, h = nh
    K, N, M = 1024, 256, 16
    w, nt = _nested_weight(n, h, K, N)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    dense = x @ nt.full_bit(jnp.float32)
    scale = nt.scale.reshape(1, -1)
    y_ker = nm_kernel.nested_matmul(x, nt.w_high, nt.w_low, scale, n=n, h=h,
                                    K=K, block_m=M, block_k=nt.block,
                                    interpret=True)
    y_ref = nm_ref.nested_matmul_ref(x, nt.w_high, nt.w_low, scale, n=n, h=h,
                                     K=K, block_k=nt.block)
    rel = float(jnp.linalg.norm(y_ker - dense) / jnp.linalg.norm(dense))
    assert rel <= 1e-4, rel
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("nh", NH_SWEEP)
def test_packed_matmul_part_bit_matches_dense(nh):
    """Part-bit: packed_matmul on the stored w_high stream with the
    inflated scale s*2^l must match x @ dense(part_bit) to <=1e-4."""
    n, h = nh
    K, N, M = 1024, 256, 16
    w, nt = _nested_weight(n, h, K, N, seed=2)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    dense = x @ nt.part_bit(jnp.float32)
    scale = (nt.scale * (2.0 ** nt.l)).reshape(1, -1)
    y_ker = pm_kernel.packed_matmul(x, nt.w_high, scale, k=h, K=K,
                                    block_m=M, block_k=nt.block,
                                    interpret=True)
    rel = float(jnp.linalg.norm(y_ker - dense) / jnp.linalg.norm(dense))
    assert rel <= 1e-4, rel


# ---------------------------------------------------------------------------
# adaptive (SQuant CASE) packed trees: the kernels read whatever codes the
# splitter produced - parity must hold for flip-rounded streams, not just
# the analytic RTN sweep above (DESIGN.md Sec. 13)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nh", NH_SWEEP)
def test_nested_matmul_dual_stream_adaptive(nh):
    """Full-bit dual-stream kernel on an ADAPTIVELY-rounded packed tree:
    kernel == jnp ref == dense dequant (CASE flips change the per-stream
    codes but never the recomposed product)."""
    n, h = nh
    K, N, M = 1024, 256, 16
    w, nt = _nested_weight(n, h, K, N, seed=11, rounding="adaptive")
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    dense = x @ nt.full_bit(jnp.float32)
    scale = nt.scale.reshape(1, -1)
    y_ker = nm_kernel.nested_matmul(x, nt.w_high, nt.w_low, scale, n=n, h=h,
                                    K=K, block_m=M, block_k=nt.block,
                                    interpret=True)
    y_ref = nm_ref.nested_matmul_ref(x, nt.w_high, nt.w_low, scale, n=n, h=h,
                                     K=K, block_k=nt.block)
    rel = float(jnp.linalg.norm(y_ker - dense) / jnp.linalg.norm(dense))
    assert rel <= 1e-4, rel
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("nh", NH_SWEEP)
def test_packed_matmul_part_bit_adaptive(nh):
    """Part-bit path on the adaptively-flipped base stream: the inflated
    scale s*2^l must reproduce x @ dense(part_bit) exactly as for RTN."""
    n, h = nh
    K, N, M = 1024, 256, 16
    w, nt = _nested_weight(n, h, K, N, seed=13, rounding="adaptive")
    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    dense = x @ nt.part_bit(jnp.float32)
    scale = (nt.scale * (2.0 ** nt.l)).reshape(1, -1)
    y_ker = pm_kernel.packed_matmul(x, nt.w_high, scale, k=h, K=K,
                                    block_m=M, block_k=nt.block,
                                    interpret=True)
    rel = float(jnp.linalg.norm(y_ker - dense) / jnp.linalg.norm(dense))
    assert rel <= 1e-4, rel


@pytest.mark.parametrize("rounding", ["rtn", "adaptive"])
def test_ladder_matmul_adaptive_three_rung(rounding):
    """3-rung ladder kernel vs jnp ref vs dense, on both roundings: the
    packed delta streams of an adaptive split feed the same fused
    accumulate as the analytic split."""
    from repro.kernels.nested_matmul import kernel as lm_kernel
    from repro.kernels.nested_matmul import ref as lm_ref
    rng = np.random.default_rng(15)
    K, N, M = 256, 128, 8
    w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32) * 0.05)
    nt = nest_quantize(w, bits=(8, 6, 4), rounding=rounding, block=256)
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    streams = (nt.w_base,) + nt.deltas
    scale = nt.scale.reshape(1, -1)
    y_ref = lm_ref.ladder_matmul_ref(x, streams, scale, bits=nt.bits,
                                     K=K, block_k=256)
    y_ker = lm_kernel.ladder_matmul(x, streams, scale, bits=nt.bits, K=K,
                                    block_m=8, block_n=128, block_k=256,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    dense = x @ nt.full_bit(jnp.float32)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("M", [3, 136])
def test_dispatch_pads_uneven_m(M):
    """M that violates the tile contract (decode micro-batch of 3; 136 not
    a multiple of 128) must STILL run the packed kernel path - the
    dispatcher pads M and slices the output, it never drops tail rows and
    never falls back to dense dequant on the serving hot path."""
    n, h = 8, 4
    K, N = 1024, 256
    w, nt = _nested_weight(n, h, K, N, seed=7)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    from repro.kernels.dispatch import plan
    _, _, _, bm, take_kernel = plan(x, N, K, nt.block, None, True)
    assert take_kernel and bm in (8, 128)
    from repro.kernels.nested_matmul import ops as nm_ops
    from repro.kernels.packed_matmul import ops as pm_ops
    y = nm_ops.nested_matmul(x, nt.w_high, nt.w_low, nt.scale.reshape(1, -1),
                             n=n, h=h, K=K, block_k=nt.block, interpret=True)
    dense = x @ nt.full_bit(jnp.float32)
    assert y.shape == dense.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)
    assert np.all(np.isfinite(np.asarray(y)))      # tail rows included
    yp = pm_ops.packed_matmul(x, nt.w_high, nt.part_scale.reshape(1, -1),
                              k=h, K=K, block_k=nt.block, interpret=True)
    np.testing.assert_allclose(np.asarray(yp),
                               np.asarray(x @ nt.part_bit(jnp.float32)),
                               rtol=1e-4, atol=1e-4)


def test_dispatch_mis_tiled_shape_raises_on_tpu(monkeypatch):
    """On a TPU backend a shape that misses the tile contract (N = 96 is
    not a multiple of 128) raises and names the shape instead of running
    the jnp reference unseen; use_pallas=False still runs the reference."""
    from repro.kernels import dispatch
    from repro.kernels.packed_matmul import ops as pm_ops
    n, h = 8, 4
    K, N = 256, 96
    w, nt = _nested_weight(n, h, K, N, seed=11)
    x = jnp.asarray(np.random.default_rng(12).normal(size=(4, K))
                    .astype(np.float32))
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match=rf"M=4 K={K} N={N}"):
        pm_ops.packed_matmul(x, nt.w_high, nt.part_scale.reshape(1, -1),
                             k=h, K=K, block_k=nt.block)
    y = pm_ops.packed_matmul(x, nt.w_high, nt.part_scale.reshape(1, -1),
                             k=h, K=K, block_k=nt.block, use_pallas=False)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(x @ nt.part_bit(jnp.float32)),
                               rtol=1e-4, atol=1e-4)


def test_gather_rows_matches_dense_dequant():
    """Packed embedding gather: rows read straight from the words must
    equal indexing the dense dequantized table, in both modes."""
    n, h = 8, 4
    w, nt = _nested_weight(n, h, K=192, N=128, seed=9)   # 3 blocks of 64
    rng = np.random.default_rng(10)
    idx = jnp.asarray(rng.integers(0, 192, size=(2, 7)), jnp.int32)
    for mode in ("full", "part"):
        m = nt.with_mode(mode)
        got = m.gather_rows(idx, jnp.float32)
        want = m.dequant(jnp.float32)[idx]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_layers_dispatch_serves_from_packed_words():
    """models.layers.linear on a NestedTensor leaf must agree with the
    dense dequantized matmul in BOTH modes (CPU reference dispatch)."""
    from repro.models.layers import linear
    n, h = 8, 4
    w, nt = _nested_weight(n, h, K=512, N=128, seed=4)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 8, 512)).astype(np.float32))
    y_full = linear(x, nt.with_mode("full"))
    y_part = linear(x, nt.with_mode("part"))
    np.testing.assert_allclose(np.asarray(y_full),
                               np.asarray(x @ nt.full_bit(jnp.float32)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_part),
                               np.asarray(x @ nt.part_bit(jnp.float32)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dims", [(1, 512, 4, 2, 64), (2, 256, 8, 2, 32),
                                  (1, 256, 4, 4, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(dims, dtype):
    B, S, Hq, Hkv, hd = dims
    rng = np.random.default_rng(S)
    q = jnp.asarray(rng.normal(size=(B, S, Hq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), dtype)
    o_ref = fa_ref.attention_ref(q, k, v)
    o_ker = fa_kernel.flash_attention(q, k, v, block_q=128, block_kv=128,
                                      interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o_ker, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)


def test_blockwise_attention_custom_vjp_grads():
    """The jnp flash path (models.attention) must match full attention in
    both directions - it is the training-path oracle of the Pallas kernel."""
    from repro.models.attention import blockwise_attention, full_attention
    rng = np.random.default_rng(7)
    B, S, Hq, Hkv, hd = 2, 256, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.tanh(full_attention(q, k, v, causal=True)))

    def loss_blk(q, k, v):
        return jnp.sum(jnp.tanh(blockwise_attention(q, k, v, True, 64)))

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
