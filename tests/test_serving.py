"""Serving engine tests: batched generation, budget-driven switching."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import NestQuantStore, nest_quantize_tree
from repro.models import make_model
from repro.serving import Request, ServeEngine


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("qwen2-1.5b").reduced()
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    nested = nest_quantize_tree(params, n=8, h=4)
    store = NestQuantStore(nested, n=8, h=4, mode="part", dtype=jnp.float32)
    return cfg, ServeEngine(cfg, store, max_batch=4, max_len=48), store


def _reqs(cfg, n, seed=0, new_tokens=4):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                    max_new_tokens=new_tokens) for i in range(n)]


def test_generate_produces_tokens(engine):
    cfg, eng, store = engine
    reqs = eng.generate(_reqs(cfg, 3))
    for r in reqs:
        assert len(r.out_tokens) == 4
        assert all(0 <= t < cfg.vocab_size for t in r.out_tokens)
    assert eng.stats.prefills == 1 and eng.stats.decode_steps == 4


@pytest.fixture(scope="module")
def ladder_engine():
    cfg = get_config("qwen2-1.5b").reduced()
    params = make_model(cfg).init(jax.random.PRNGKey(0))
    store = NestQuantStore(nest_quantize_tree(params, bits=(8, 6, 4)),
                           mode="part", dtype=jnp.float32)
    return cfg, ServeEngine(cfg, store, max_batch=4, max_len=48), store


def _reference_tokens(cfg, model, params, reqs, max_len):
    """Plain greedy loop with no pipelining: prefill, argmax, then
    decode_step and argmax, with one ``int()`` per live row per step."""
    S = max(len(r.prompt) for r in reqs)
    toks = np.zeros((len(reqs), S), np.int32)
    for i, r in enumerate(reqs):
        toks[i, S - len(r.prompt):] = r.prompt
    logits, short = jax.jit(model.prefill)(params,
                                           {"tokens": jnp.asarray(toks)})
    cache = model.make_cache(len(reqs), max_len,
                             dtype=jnp.dtype(cfg.compute_dtype))
    cache["pos"] = short["pos"]
    for key in ("k", "v"):
        cache[key] = jax.lax.dynamic_update_slice(
            cache[key].astype(short[key].dtype), short[key],
            (0,) * short[key].ndim)
    decode = jax.jit(model.decode_step)
    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    out = [[] for _ in reqs]
    for _ in range(max(r.max_new_tokens for r in reqs)):
        for i, r in enumerate(reqs):
            if len(out[i]) < r.max_new_tokens:
                out[i].append(int(tok[i, 0]))
        logits, cache = decode(params, {"tokens": tok}, cache)
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    return out


@pytest.mark.parametrize("rung", [0, 1, 2])
def test_pipelined_tokens_match_a_plain_loop_at_every_rung(ladder_engine,
                                                           rung):
    """Tokens read one step behind, in one transfer a step, are the
    tokens of the unpipelined loop, row by row, with ragged answer
    lengths; and the host pulls once a step, not once a row a step."""
    cfg, eng, store = ladder_engine
    rng = np.random.default_rng(rung)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, S).astype(np.int32),
                    max_new_tokens=n)
            for i, (S, n) in enumerate([(6, 1), (4, 5), (6, 3), (5, 4)])]
    budget = None if rung == 2 else store.rung_resident_bytes(rung)
    steps0, pulls0 = eng.stats.decode_steps, eng.stats.token_pulls
    eng.generate(reqs, memory_budget_bytes=budget)
    assert store.rung == rung
    assert [len(r.out_tokens) for r in reqs] == [1, 5, 3, 4]
    want = _reference_tokens(cfg, eng.model, store.params(), reqs,
                             eng.max_len)
    assert [r.out_tokens for r in reqs] == want
    assert eng.stats.decode_steps - steps0 == 5
    assert eng.stats.token_pulls - pulls0 == 5


def test_budget_switching(engine):
    cfg, eng, store = engine
    b = store.bytes()
    full_need = b["high"] + b["low"] + b["scales"] + b["fp"]
    eng.generate(_reqs(cfg, 2, seed=1), memory_budget_bytes=full_need * 2)
    assert store.mode == "full"
    eng.generate(_reqs(cfg, 2, seed=2),
                 memory_budget_bytes=full_need - b["low"] // 2)
    assert store.mode == "part"
    assert store.resident_bytes() < full_need
    # ledger: exactly one page-in (upgrade) and one page-out (downgrade)
    assert store.ledger.page_in_bytes == b["low"]
    assert store.ledger.page_out_bytes == b["low"]


def test_modes_agree_on_greedy_tokens_mostly(engine):
    """Part-bit vs full-bit generations overlap heavily on an (untrained)
    model - the serving-level echo of the accuracy-proxy tests."""
    cfg, eng, store = engine
    full = eng.generate(_reqs(cfg, 4, seed=3, new_tokens=3),
                        memory_budget_bytes=None)          # full mode
    full_toks = [tuple(r.out_tokens) for r in full]
    b = store.bytes()
    part = eng.generate(_reqs(cfg, 4, seed=3, new_tokens=3),
                        memory_budget_bytes=b["high"] + b["scales"] + b["fp"])
    part_toks = [tuple(r.out_tokens) for r in part]
    agree = np.mean([a == b_ for a, b_ in zip(full_toks, part_toks)])
    assert agree >= 0.25      # loose: random-init logits are near-uniform


def test_serving_path_never_materializes(engine, monkeypatch):
    """The packed execution path: generate/ensure_mode must perform ZERO
    materialize() calls - weights are served straight from NestQuant words."""
    import repro.core.nesting as nesting
    import repro.core.switching as switching
    cfg, eng, store = engine

    def _boom(*args, **kwargs):
        raise AssertionError("materialize() called on the serving path")

    monkeypatch.setattr(nesting, "materialize", _boom)
    monkeypatch.setattr(switching, "materialize", _boom)
    eng._params = None                      # force a full param (re)pickup
    reqs = eng.generate(_reqs(cfg, 2, seed=11))
    assert all(len(r.out_tokens) == 4 for r in reqs)
    # and a budget-driven mode flip is also materialize-free
    b = store.bytes()
    eng.generate(_reqs(cfg, 2, seed=12),
                 memory_budget_bytes=b["high"] + b["scales"] + b["fp"])
    eng.generate(_reqs(cfg, 2, seed=13), memory_budget_bytes=None)


def test_ensure_mode_counts_only_real_switches(engine):
    """stats.switches must not increment on first materialization when the
    mode did not change (Table-11 switching accounting)."""
    cfg, _, store = engine
    store.to_full()
    eng = ServeEngine(cfg, store, max_batch=2, max_len=32)
    assert eng.stats.switches == 0
    eng.ensure_mode(None)                   # already full: params pickup only
    assert eng.stats.switches == 0
    eng.ensure_mode(None)                   # no-op
    assert eng.stats.switches == 0
    b = store.bytes()
    eng.ensure_mode(b["high"] + b["scales"] + b["fp"])   # full -> part
    assert eng.stats.switches == 1
    eng.ensure_mode(b["high"] + b["scales"] + b["fp"])   # stays part
    assert eng.stats.switches == 1
    eng.ensure_mode(None)                   # part -> full
    assert eng.stats.switches == 2


def test_warmup_kills_rung_switch_retrace():
    """warmup() pre-traces every (rung, shape) dispatch the serve loop
    can hit - residency pattern AND rung stamp both live in the pytree
    structure, so each is its own jit cache entry.  After warmup, a
    switch to a NEVER-BEFORE-SERVED rung (plain or speculative) must
    trigger ZERO new compilations (DESIGN.md Sec. 15)."""
    from repro.core.recipe import QuantRecipe, quantize
    from repro.serving import SpecConfig
    from repro.serving.policies import StaticRungPolicy

    cfg = get_config("qwen2-1.5b").reduced()
    model = make_model(cfg)
    traces = {"prefill": 0, "decode": 0, "chunk": 0}

    def counting(fn, key):
        def inner(*a, **kw):            # body runs once per jax TRACE
            traces[key] += 1
            return fn(*a, **kw)
        return inner

    counted = model._replace(
        prefill=counting(model.prefill, "prefill"),
        decode_step=counting(model.decode_step, "decode"),
        decode_chunk=counting(model.decode_chunk, "chunk"))
    compiled = (jax.jit(counted.prefill),
                jax.jit(counted.decode_step, donate_argnums=(2,)),
                jax.jit(counted.decode_chunk, donate_argnums=(2,)))
    params = model.init(jax.random.PRNGKey(0))
    nested = quantize(params, QuantRecipe(bits=(8, 6, 4)))
    store = NestQuantStore(nested, mode="part", dtype=jnp.float32)
    eng = ServeEngine(cfg, store, max_batch=2, max_len=48,
                      policy=StaticRungPolicy(0), model=counted,
                      compiled=compiled)
    spec = SpecConfig(k=3, draft=0)
    eng.warmup(6, batch=2, spec=spec)
    assert sum(traces.values()) > 0
    snap = dict(traces)
    # rungs 1 and 2 (and the draft stamp, and the verify chunk) have
    # never been SERVED - only warmed.  No dispatch may retrace.
    for rung in (1, 2, 0):
        eng.policy = StaticRungPolicy(rung)
        eng.generate(_reqs(cfg, 2, seed=20 + rung, new_tokens=4),
                     speculate=spec)
        eng.generate(_reqs(cfg, 2, seed=30 + rung, new_tokens=4))
    assert traces == snap, f"retraced after warmup: was {snap}, now {traces}"


class _JointPin:
    """Policy pinning BOTH halves of the joint rung state: ``decide``
    serves the weight rung, ``kv_decide`` the cache rung (the engine
    clamps + applies it through the ledgered walk)."""

    def __init__(self, weight_rung, kv_rung):
        self.weight_rung, self.kv_rung = weight_rung, kv_rung

    def decide(self, store, signal):
        from repro.serving.policies import RungAssignment
        return RungAssignment.uniform(self.weight_rung)

    def kv_decide(self, kv, signal):
        return self.kv_rung


def test_warmup_kills_kv_rung_switch_retrace():
    """Satellite of DESIGN.md Sec. 16: warmup() covers every
    (weight-rung x KV-rung x prompt shape) the serve loop dispatches -
    a KV cache rung switch AFTER warmup must add ZERO new jit traces,
    on the model dispatches AND on the KV quantize/render pipeline."""
    from repro.core.recipe import QuantRecipe, quantize
    from repro.serving import KVCacheConfig, NestedKVCache
    from repro.serving.kv_cache import KV_TRACES

    cfg = get_config("qwen2-1.5b").reduced()
    model = make_model(cfg)
    traces = {"prefill": 0, "decode": 0, "chunk": 0}

    def counting(fn, key):
        def inner(*a, **kw):            # body runs once per jax TRACE
            traces[key] += 1
            return fn(*a, **kw)
        return inner

    counted = model._replace(
        prefill=counting(model.prefill, "prefill"),
        decode_step=counting(model.decode_step, "decode"),
        decode_chunk=counting(model.decode_chunk, "chunk"))
    compiled = (jax.jit(counted.prefill),
                jax.jit(counted.decode_step, donate_argnums=(2,)),
                jax.jit(counted.decode_chunk, donate_argnums=(2,)))
    params = model.init(jax.random.PRNGKey(0))
    nested = quantize(params, QuantRecipe(bits=(8, 6, 4)))
    store = NestQuantStore(nested, mode="part", dtype=jnp.float32)
    kv = NestedKVCache(KVCacheConfig(bits=(4, 8), page=4))
    eng = ServeEngine(cfg, store, max_batch=2, max_len=48,
                      policy=_JointPin(0, kv.rung), model=counted,
                      compiled=compiled, kv=kv)
    eng.warmup(6, batch=2)
    assert sum(traces.values()) > 0
    assert KV_TRACES["quantize"] > 0 and KV_TRACES["render"] > 0
    snap, kv_snap = dict(traces), dict(KV_TRACES)

    # joint walk over rung pairs never served before: cache downshift,
    # re-climb, and weight+KV moving in the same step - zero retraces.
    switches0 = eng.stats.kv_switches
    for wr, kr in ((0, 0), (1, 1), (2, 0), (0, 1)):
        eng.policy = _JointPin(wr, kr)
        eng.generate(_reqs(cfg, 2, seed=40 + 2 * wr + kr, new_tokens=3))
        assert kv.rung == kr            # the switch genuinely committed
    assert eng.stats.kv_switches >= switches0 + 4
    assert traces == snap, f"retraced after warmup: was {snap}, now {traces}"
    assert KV_TRACES == kv_snap, \
        f"KV pipeline retraced after warmup: was {kv_snap}, now {KV_TRACES}"
