"""Model-level nesting, switching ledger, and storage accounting tests."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import (NestQuantStore, diverse_bitwidth_bytes, materialize,
                        nest_quantize_tree, tree_bytes)
from repro.core.nesting import NestedTensor
from repro.models import make_model


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("qwen2-1.5b").reduced()
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_tree_nesting_selects_matmul_weights(small_model):
    cfg, model, params = small_model
    nested = nest_quantize_tree(params, n=8, h=4)
    leaves = jax.tree_util.tree_leaves(
        nested, is_leaf=lambda x: isinstance(x, NestedTensor))
    nts = [l for l in leaves if isinstance(l, NestedTensor)]
    assert len(nts) >= 7    # embed, q, o, mlp x3, lm_head (k/v below min_dim)
    names = jax.tree_util.tree_flatten_with_path(
        nested, is_leaf=lambda x: isinstance(x, NestedTensor))[0]
    for path, leaf in names:
        key = jax.tree_util.keystr(path).lower()
        if "norm" in key or "bias" in key:
            assert not isinstance(leaf, NestedTensor)


def test_full_bit_model_runs_and_close_to_fp(small_model):
    cfg, model, params = small_model
    nested = nest_quantize_tree(params, n=8, h=4)
    full = materialize(nested, "full", dtype=jnp.float32)
    part = materialize(nested, "part", dtype=jnp.float32)
    B, S = 2, 16
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          cfg.vocab_size)}
    logits_fp, _ = jax.jit(model.prefill)(params, batch)
    logits_full, _ = jax.jit(model.prefill)(full, batch)
    logits_part, _ = jax.jit(model.prefill)(part, batch)
    # top-1 agreement, the accuracy proxy
    agree_full = float(jnp.mean(jnp.argmax(logits_fp, -1) ==
                                jnp.argmax(logits_full, -1)))
    err_full = float(jnp.mean(jnp.abs(logits_fp - logits_full)))
    err_part = float(jnp.mean(jnp.abs(logits_fp - logits_part)))
    assert err_full < err_part        # full-bit strictly better
    assert np.isfinite(err_part)
    assert agree_full >= 0.5


def test_switching_ledger_table11_semantics(small_model):
    cfg, model, params = small_model
    nested = nest_quantize_tree(params, n=8, h=4)
    store = NestQuantStore(nested, n=8, h=4, mode="part")
    b = store.bytes()
    assert b["high"] > 0 and b["low"] > 0
    # upgrade: page-in w_low only, zero page-out
    store.to_full()
    assert store.ledger.page_in_bytes == b["low"]
    assert store.ledger.page_out_bytes == 0
    # downgrade: page-out w_low only
    store.to_part()
    assert store.ledger.page_out_bytes == b["low"]
    # diverse-bitwidths baseline must cost strictly more on a switch
    div = store.diverse_baseline()
    assert div["switch_page_in"] + div["switch_page_out"] > b["low"]
    red = store.switch_reduction()
    assert 0.3 < red < 0.95           # paper reports 57-87%


def test_storage_reduction_close_to_ideal(small_model):
    """Paper Table 8: NestQuant vs storing INT8+INT4 models ~ 25% saving."""
    cfg, model, params = small_model
    nested = nest_quantize_tree(params, n=8, h=4)
    b = tree_bytes(nested)
    nest_packed = b["high"] + b["low"]
    div = diverse_bitwidth_bytes(nested, 8, 4)
    reduction = 1 - nest_packed / div["total"]
    # ideal (h + l+1)/(n + h) = (4+5)/(8+4) = 25%; packing rounds off a bit
    assert 0.15 < reduction < 0.35


@pytest.mark.parametrize("shape", [(3, 64, 96), (128, 200)])
def test_piecewise_quantize_and_materialize_match_whole(shape, monkeypatch):
    """Quantizing and dequantizing in pieces (per layer, per column block)
    gives exactly the codes and weights of the whole tensor at once."""
    from repro.core import nesting
    w = jax.random.normal(jax.random.PRNGKey(3), shape, jnp.float32) * 0.05

    def run():
        nt = nesting.nest_quantize(w, bits=(8, 6, 4))
        dense = [materialize({"w": nt}, r, jnp.float32)["w"] for r in range(3)]
        return [nt.w_base, *nt.deltas, nt.scale, *dense]

    monkeypatch.setattr(nesting, "_pieces", lambda shape: None)
    whole = run()
    monkeypatch.undo()
    monkeypatch.setattr(nesting, "PIECE_ELEMS", 64 * 48)
    assert nesting._pieces(shape[-2:]) is not None     # columns are cut too
    for a, b in zip(run(), whole):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
