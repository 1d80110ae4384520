"""Program spans (``repro.obs``): what a profiler session records from
``ServeEngine.generate`` and from an upgrade through a ``FilePager``,
and that recording changes no token."""
import glob
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import obs
from repro.api import QuantRecipe, Request, ServeEngine, quantize, \
    save_artifact
from repro.configs import get_config
from repro.models import make_model

B, STEPS, PROMPT = 3, 4, 5


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    cfg = get_config("qwen2-1.5b").reduced()
    params = make_model(cfg).init(jax.random.PRNGKey(0))
    path = str(tmp_path_factory.mktemp("obs") / "artifact")
    save_artifact(quantize(params, QuantRecipe(bits=(8, 6, 4))), path)
    return cfg, path


def _engine(artifact):
    cfg, path = artifact
    return ServeEngine.from_artifact(cfg, path, max_batch=4, max_len=32,
                                     dtype=jnp.float32)


def _reqs(cfg):
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, PROMPT)
                    .astype(np.int32), max_new_tokens=STEPS)
            for i in range(B - 1)]
    # a filler clone (uid -1) rides along and is not a real row
    return reqs + [Request(-1, reqs[-1].prompt, STEPS)]


def _spans(logdir):
    """``(name, start_ns, end_ns, args, thread)`` of every ``nq.`` span."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats), (plane.name, line.name))
                        for e in line.events
                        if e.name.startswith(obs.PREFIX)]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _traced(logdir, fn):
    jax.profiler.start_trace(str(logdir))
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def test_generate_records_one_span_with_a_token_sync_and_step_per_step(
        artifact, tmp_path):
    cfg = artifact[0]
    eng = _engine(artifact)
    eng.generate(_reqs(cfg))            # compile outside the session
    _traced(tmp_path, lambda: eng.generate(_reqs(cfg)))
    spans = _spans(tmp_path)
    gen = [s for s in spans if s[0] == "nq.generate"]
    assert len(gen) == 1
    _, a, b, args, thread = gen[0]
    assert args == {"batch": 1, "rows": B, "real_rows": B - 1,
                    "prompt_len": PROMPT, "steps": STEPS,
                    "rung": eng.store.rung}
    for name in ("nq.token_sync", "nq.decode_step"):
        inner = [s for s in spans if s[0] == name]
        assert [s[3]["step"] for s in inner] == list(range(STEPS))
        assert all(a <= s[1] <= s[2] <= b and s[4] == thread for s in inner)
    syncs = [s[3]["rows"] for s in spans if s[0] == "nq.token_sync"]
    assert syncs == [B] * STEPS
    for name in ("nq.ensure_mode", "nq.prefill", "nq.cache_rehome"):
        assert [a <= s[1] <= s[2] <= b for s in spans
                if s[0] == name] == [True]


def test_each_decode_step_is_queued_before_the_host_waits_for_tokens(
        artifact, tmp_path):
    cfg = artifact[0]
    eng = _engine(artifact)
    eng.generate(_reqs(cfg))            # compile outside the session
    _traced(tmp_path, lambda: eng.generate(_reqs(cfg)))
    spans = _spans(tmp_path)
    opens = {(s[0], s[3]["step"]): s[1] for s in spans
             if s[0] in ("nq.decode_step", "nq.token_sync")}
    assert len(opens) == 2 * STEPS
    for step in range(STEPS):
        assert (opens[("nq.decode_step", step)]
                < opens[("nq.token_sync", step)])
        if step:
            assert (opens[("nq.token_sync", step - 1)]
                    < opens[("nq.decode_step", step)])


def test_upgrade_through_the_file_pager_records_a_triple_per_stream(
        artifact, tmp_path):
    eng = _engine(artifact)
    store = eng.store
    assert store.rung == 0
    in0, ev0 = store.ledger.page_in_bytes, len(store.ledger.events)
    _traced(tmp_path, lambda: eng.ensure_mode(None))
    page_in = store.ledger.page_in_bytes - in0
    assert store.rung == store.num_rungs - 1 and page_in > 0
    assert len(store.ledger.events) - ev0 == store.num_rungs - 1
    spans = _spans(tmp_path)
    switch = [s for s in spans if s[0] == "nq.switch"]
    assert len(switch) == 1
    _, a, b, args, _ = switch[0]
    assert args == {"from_rung": 0, "to_rung": store.num_rungs - 1}
    pages = [s for s in spans if s[0].startswith("nq.page_in.")]
    assert pages and all(a <= s[1] <= s[2] <= b for s in pages)
    names = [s[0].rsplit(".", 1)[1] for s in pages]
    n = len(names) // 3
    assert names == ["read", "crc", "put"] * n
    streams = sum(len(s) - 1 for s in store.leaf_streams().values())
    assert n == streams
    for part in ("read", "crc", "put"):
        assert sum(s[3]["nbytes"] for s in pages
                   if s[0] == "nq.page_in." + part) == page_in


def test_tokens_are_identical_with_the_profiler_on_and_off(artifact,
                                                           tmp_path):
    cfg = artifact[0]
    eng = _engine(artifact)
    off = [r.out_tokens for r in eng.generate(_reqs(cfg))]
    on = _traced(tmp_path, lambda: eng.generate(_reqs(cfg)))
    assert [r.out_tokens for r in on] == off
    assert len(off[0]) == STEPS
