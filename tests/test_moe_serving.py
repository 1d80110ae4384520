"""The mixture-of-experts decoder with window and full attention layers
(Mellum2's layout) at a tiny size with the same structure: one period
of three sliding-window layers and one full layer, window 8, YaRN with
an original context of 16, 8 experts, top-2.

The program, on its ladder, is held to a plain float32 reference
written here from the published definitions (each quantized weight
dequantized as the rung serves it): prefill then decode through the
cache, at every rung.  Beside it: the expert shares add up to the
uncut layer and its expert-parallel mesh path equals the plain one,
the grouped matmul equals per-expert dense matmuls, a
chunked verify equals sequential decode bit for bit, and the routing
counter equals the reference's routing.
"""
import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.api import QuantRecipe, quantize
from repro.configs import get_config
from repro.core.decompose import delta_bits
from repro.core.nesting import materialize, nest_quantize, set_tree_rung
from repro.distributed.ctx import logical_rules
from repro.kernels.expert_matmul import ops as expert_ops
from repro.models import make_model
from repro.models.attention import blockwise_attention, full_attention
from repro.models.moe import held_moe_ffn
from repro.serving.engine import Request, ServeEngine
from repro.core.switching import NestQuantStore

BITS = (4, 6, 8)
B, S, STEPS = 2, 12, 6          # the prompt passes the window (8)
# Float32 program against the float32 reference: they differ only in
# the order of float32 sums, about 1e-6 of logits of order 1.  The same
# reference in bfloat16 misses by more than 1e-2 (checked below), so
# 1e-4 tells the precisions apart with room on both sides.
LOGIT_TOL = 1e-4


def _cfg(**kw):
    return dataclasses.replace(get_config("mellum2-12b-a2.5b").reduced(),
                               num_experts=8, top_k=2, **kw)


@pytest.fixture(scope="module")
def tiny():
    cfg = _cfg()
    params = make_model(cfg).init(jax.random.PRNGKey(0))
    q = quantize(params, QuantRecipe(bits=BITS, rounding="rtn"))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + STEPS), 0,
                              cfg.vocab_size)
    return cfg, q, toks


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _ref_inv_freq(cfg, full: bool):
    """Plain RoPE, or YaRN's blend of interpolated and extrapolated
    frequencies between the beta_fast and beta_slow correction dims."""
    d, theta = cfg.head_dim, cfg.rope_theta
    base = theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if not full:
        return 1.0 / base, 1.0

    def dim_of(rot):
        return d * math.log(cfg.yarn_original_max_len / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(dim_of(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.yarn_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = (1 / (cfg.yarn_factor * base)) * ramp + (1 / base) * (1 - ramp)
    return inv, cfg.yarn_attention_factor


def _ref_forward(cfg, dense, toks, dt=jnp.float32, routes=None):
    """Logits (B, T, V) of the whole sequence, computed in ``dt`` (norms,
    softmaxes and the router in float32); ``routes`` collects each
    layer's top-k expert ids (B, T, k)."""
    f = lambda a: jnp.asarray(a, jnp.float32).astype(dt)   # noqa: E731
    toks = np.asarray(toks)
    Bn, T = toks.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h = f(dense["embed"]["table"])[toks] * math.sqrt(cfg.d_model)

    def rms(x, w):
        x32 = x.astype(jnp.float32)
        y = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6)
        return (y * jnp.asarray(w, jnp.float32)).astype(dt)

    blocks = dense["blocks"]
    pos = np.arange(T)
    for l in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[l], blocks)
        full = cfg.layer_type(l) == "full"
        inv, sc = _ref_inv_freq(cfg, full)
        ang = pos[:, None] * inv[None]
        cos = jnp.asarray(np.cos(ang) * sc)[None, :, None].astype(dt)
        sin = jnp.asarray(np.sin(ang) * sc)[None, :, None].astype(dt)

        def rope(x):
            x1, x2 = jnp.split(x, 2, -1)
            return jnp.concatenate([x1 * cos - x2 * sin,
                                    x1 * sin + x2 * cos], -1)
        x = rms(h, lp["attn_norm"]["scale"])
        q = rope((x @ f(lp["q"]["w"])).reshape(Bn, T, H, hd))
        k = rope((x @ f(lp["k"]["w"])).reshape(Bn, T, KV, hd))
        v = (x @ f(lp["v"]["w"])).reshape(Bn, T, KV, hd)
        k, v = jnp.repeat(k, H // KV, 2), jnp.repeat(v, H // KV, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        seen = pos[None, :] <= pos[:, None]
        if not full:
            seen = seen & (pos[None, :] > pos[:, None] - cfg.sliding_window)
        p = jax.nn.softmax(jnp.where(seen, s.astype(jnp.float32), -jnp.inf),
                           -1).astype(dt)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(Bn, T, H * hd)
        h = h + o @ f(lp["o"]["w"])
        x = rms(h, lp["mlp_norm"]["scale"])
        prob = np.asarray(jax.nn.softmax(
            x.astype(jnp.float32) @ jnp.asarray(lp["moe"]["router"]["w"],
                                                jnp.float32), -1))
        top = np.argsort(-prob, -1, kind="stable")[..., :cfg.top_k]
        if routes is not None:
            routes.append(top)
        g = np.take_along_axis(prob, top, -1)
        g = g / g.sum(-1, keepdims=True)
        ex = lp["moe"]["experts"]
        y = jnp.zeros_like(h)
        for e in range(cfg.held_experts):
            w = jnp.asarray(np.sum(np.where(top == cfg.expert_first + e, g,
                                            0.0), -1), dt)
            m = jax.nn.silu(x @ f(ex["w_gate"]["w"][e])) * (
                x @ f(ex["w_up"]["w"][e]))
            y = y + w[..., None] * (m @ f(ex["w_down"]["w"][e]))
        h = h + y
    h = rms(h, dense["final_norm"]["scale"])
    return np.asarray((h @ f(dense["lm_head"]["w"])).astype(jnp.float32))


def _serve(model, params, toks, steps=STEPS):
    """Prefill S tokens, then decode the next ``steps`` through the
    cache: logits of every position S-1 .. S-1+steps, and the cache."""
    logits, cache = jax.jit(model.prefill)(params, {"tokens": toks[:, :S]})
    full = model.make_cache(B, S + STEPS + 2, dtype=jnp.float32)
    for key, v in cache.items():
        full[key] = v if key == "pos" else jax.lax.dynamic_update_slice(
            full[key], v, (0,) * v.ndim)
    out = [logits[:, -1]]
    dec = jax.jit(model.decode_step)
    for t in range(steps):
        lg, full = dec(params, {"tokens": toks[:, S + t:S + t + 1]}, full)
        out.append(lg[:, -1])
    return np.stack([np.asarray(o) for o in out], 1), full


@pytest.mark.parametrize("rung", [0, 1, 2])
def test_prefill_then_decode_matches_float32_reference(tiny, rung):
    cfg, q, toks = tiny
    params = set_tree_rung(q, rung)
    got, _ = _serve(make_model(cfg), params, toks, steps=STEPS - 1)
    dense = materialize(q, mode=rung, dtype=jnp.float32)
    want = _ref_forward(cfg, dense, toks[:, :S + STEPS - 1])[:, S - 1:]
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    low = _ref_forward(cfg, dense, toks[:, :S + STEPS - 1],
                       dt=jnp.bfloat16)[:, S - 1:]
    assert np.abs(low - want).max() > 100 * LOGIT_TOL


def test_the_window_masks_what_a_full_layer_sees(tiny):
    """Past the window the sliding layers change the logits: the
    reference with every layer full differs from the served model."""
    cfg, q, toks = tiny
    got, _ = _serve(make_model(cfg), q, toks, steps=1)
    dense = materialize(q, mode=2, dtype=jnp.float32)
    unwindowed = _ref_forward(dataclasses.replace(cfg, sliding_window=10 ** 6),
                              dense, toks[:, :S + 1])[:, S - 1:]
    assert np.abs(got - unwindowed).max() > 100 * LOGIT_TOL


def test_expert_shares_add_up_to_the_uncut_layer(tiny):
    """Four shares of two experts each, each routing over all eight and
    computing its own: their outputs add up to the uncut layer's (float32
    sums in another order: 1e-5)."""
    cfg, q, _ = tiny
    moe = jax.tree.map(lambda a: a[0], q["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 5, cfg.d_model))
    kw = dict(num_experts=8, top_k=2)
    whole, rows = held_moe_ffn(x, moe, **kw)
    parts = []
    for first in range(0, 8, 2):
        share = {"router": moe["router"], "experts": jax.tree.map(
            lambda a: a[first:first + 2], moe["experts"])}
        y, r = held_moe_ffn(x, share, first=first, held=2, **kw)
        parts.append(y)
        np.testing.assert_array_equal(r, rows[first:first + 2])
    np.testing.assert_allclose(sum(parts), whole, atol=1e-5, rtol=0)
    assert int(rows.sum()) == 3 * 5 * 2


def _stack(rng, n, K, N):
    w = jnp.asarray(rng.normal(size=(n, K, N)), jnp.float32)
    return nest_quantize(w, bits=BITS, rounding="rtn", block=128)


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("rung", [0, 1, 2])
def test_grouped_matmul_equals_per_expert_dense(rung, interpret):
    """Groups of 5, 0, 16 and 21 rows at a row tile of 16: an expert
    with no rows, groups that do not fill their last tile, one that
    fills it exactly.  float32: the packed path and the dense matmul
    differ by rounding only (1e-4 of outputs of order 10)."""
    rng = np.random.default_rng(rung)
    n, K, N, tm = 4, 256, 256, 16
    nt = _stack(rng, n, K, N)
    sizes = jnp.asarray([5, 0, 16, 21], jnp.int32)
    tiles = 8
    plan = expert_ops.group_plan(sizes, tm, tiles)
    x = jnp.asarray(rng.normal(size=(tiles * tm, K)), jnp.float32)
    y = expert_ops.expert_matmul(
        x, (nt.w_base,) + nt.deltas[:rung], nt.rung_scale(rung), plan,
        bits=nt.bits[:rung + 1], widths=(nt.bits[0],) + delta_bits(BITS)[:rung],
        K=K, block_k=128, use_pallas=False, interpret=interpret)
    dense = nt.rung_weight(rung, jnp.float32)
    starts = np.asarray(plan.starts)
    assert list(starts) == [0, 16, 16, 32] and int(plan.count[0]) == 4
    for g, rows in enumerate([5, 0, 16, 21]):
        sl = slice(starts[g], starts[g] + rows)
        np.testing.assert_allclose(y[sl], x[sl] @ dense[g], atol=1e-4,
                                   rtol=1e-5)


def test_decode_chunk_is_bit_identical_to_sequential_decode(tiny):
    cfg, q, toks = tiny
    model = make_model(cfg)
    seq, cache = _serve(model, q, toks, steps=0)
    chunk_cache = jax.tree.map(jnp.copy, cache)
    seq, _ = _serve(model, q, toks, steps=4)
    lg, _ = jax.jit(model.decode_chunk)(q, {"tokens": toks[:, S:S + 4]},
                                        chunk_cache)
    np.testing.assert_array_equal(np.asarray(lg), seq[:, 1:])


def test_routing_counter_equals_the_reference_routing(tiny):
    """Held experts 2-5 of 8: the decode steps' count of rows per layer
    and held expert is the routing of the same tokens by the reference
    of the same share."""
    cfg, q, toks = tiny
    held = _cfg(expert_first=2, experts_held=4)
    share = jax.tree.map(lambda a: a, q)
    ex = share["blocks"]["moe"]["experts"]
    share["blocks"]["moe"]["experts"] = jax.tree.map(
        lambda a: a[:, 2:6], ex)
    _, cache = _serve(make_model(held), share, toks)
    routes = []
    _ref_forward(held, materialize(share, mode=2, dtype=jnp.float32), toks,
                 routes=routes)
    want = np.stack([[int(np.sum(r[:, S:S + STEPS] == e))
                      for e in range(2, 6)] for r in routes])
    np.testing.assert_array_equal(np.asarray(cache["expert_rows"]), want)


def test_engine_reads_the_routing_count_once_per_batch(tiny):
    cfg, q, _ = tiny
    eng = ServeEngine(cfg, NestQuantStore(q, mode="full", dtype=jnp.float32),
                      max_batch=B, max_len=32)
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 10).astype(np.int32),
                    5) for i in range(B)]
    eng.generate(reqs)
    rows = eng.stats.expert_rows
    assert rows.shape == (cfg.num_layers, cfg.held_experts)
    assert rows.sum() == cfg.num_layers * 5 * B * cfg.top_k
    eng.generate(reqs)
    assert eng.stats.expert_rows.sum() == 2 * rows.sum()


def test_expert_parallel_mesh_equals_the_single_device_layer(tiny):
    """Under a mesh whose rules shard the experts, the layer runs its
    shard_map path; on this one-device mesh it must give the plain
    path's output and counts (one f32 psum over one device: exact)."""
    cfg, q, _ = tiny
    moe = jax.tree.map(lambda a: a[0], q["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 3, cfg.d_model))
    kw = dict(num_experts=8, top_k=2)
    want, want_rows = jax.jit(partial(held_moe_ffn, **kw))(x, moe)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with logical_rules(mesh, {"experts": "model", "batch": "data"}):
        # a function of its own, so no trace of the plain path is reused
        lowered = jax.jit(lambda x, p: held_moe_ffn(x, p, **kw)).lower(x, moe)
    assert "all_reduce" in lowered.as_text()       # the psum of the shares
    got, rows = lowered.compile()(x, moe)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(want_rows))


def test_param_count_counts_the_held_experts_at_their_width():
    cfg = get_config("mellum2-12b-a2.5b")
    d, ff, V, L = 2304, 896, 98304, 28
    attn = d * 4096 + 2 * d * 512 + 4096 * d
    whole = 2 * V * d + L * (attn + 64 * 3 * d * ff + d * 64)
    assert cfg.param_count() == whole
    share = dataclasses.replace(cfg, experts_held=16).param_count()
    assert share == 2 * V * d + L * (attn + 16 * 3 * d * ff + d * 64)
    assert 3.8e9 < share < 3.9e9


def test_yarn_full_layers_and_plain_sliding_layers():
    from repro.models.model import FULL_WINDOW, layer_info
    cfg = get_config("mellum2-12b-a2.5b")
    li = layer_info(cfg)
    full = [l for l in range(28) if l % 4 == 3]
    assert [int(w) for w in li["window"]] == [
        FULL_WINDOW if l in full else 1024 for l in range(28)]
    for l in (0, 3):
        inv, sc = _ref_inv_freq(cfg, l in full)
        np.testing.assert_allclose(li["freqs"][l], inv, rtol=1e-5)
        assert float(li["rope_scale"][l]) == pytest.approx(sc)
    assert layer_info(get_config("qwen2-1.5b")) is None


@pytest.mark.parametrize("window", [None, 5])
def test_blockwise_attention_honours_the_window(window):
    """The blockwise path (long prompts, training) masks as the direct
    one does, and its gradient flows (window None is today's path)."""
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)
               for _ in range(3))
    w = None if window is None else jnp.int32(window)
    want = full_attention(q, k, v, causal=True, window=w)
    got = blockwise_attention(q, k, v, True, 8, w)
    np.testing.assert_allclose(got, want, atol=1e-5)
    g = jax.grad(lambda q: blockwise_attention(q, k, v, True, 8, w).sum())(q)
    gw = jax.grad(lambda q: full_attention(q, k, v, causal=True,
                                           window=w).sum())(q)
    np.testing.assert_allclose(g, gw, atol=1e-4)
