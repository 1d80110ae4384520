"""A whole run at a tiny size on the CPU: the chip check skipped, the
rest as on the chip.  A sound run is correct; the control and each fault
planted in the timed path make ``correct`` come out false."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import check
import harness
import run
import traffic
from conftest import BENCH

DATA = BENCH / "tests" / "data"
E2E = [{"name": n, "unit": "u"} for n in
       ("ttft_p95_ms", "itl_p95_ms", "out_tok_s", "switch_ms", "setup_s")]


def _cell(mix):
    return harness.Cell(f"tiny.{mix}", 1, traffic.load(DATA / "tiny.json"),
                        traffic.load(DATA / f"{mix}.json"), E2E, [])


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    """A cache directory whose artifact and compiled programs the tests
    share; each test boots its own server from it."""
    path = tmp_path_factory.mktemp("cache")
    harness.Server(_cell("tiny-swing"), path).close()
    return path


@pytest.fixture
def swing(cache):
    cell = _cell("tiny-swing")
    return cell, harness.Server(cell, cache)


def _run(cell, server, seed=2 ** 31 + 17):
    res = run.run(cell, seed, 1.5, 0, time.perf_counter(), server=server)
    json.dumps(res)
    return res


def test_sound_run_is_correct(swing):
    cell, server = swing
    res = _run(cell, server)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"gap_r0", "gap_r1", "gap_r2"} <= set(res["checks"])
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"out_tok_s", "switch_ms", "setup_s",
                                   "itl_p95_ms", "ttft_p95_ms"}


def _planted(server, fault):
    """Wrap the engine's compiled decode step with ``fault``."""
    eng = server.engine
    good = eng._decode

    def bad(params, inputs, cache):
        return fault(good, params, inputs, cache)
    eng._decode = bad
    return good


def _altered_token(good, params, inputs, cache):
    logits, cache = good(params, inputs, cache)
    return jnp.roll(logits, 1, axis=-1), cache      # every argmax moves


def _unchanged_state(good, params, inputs, cache):
    keep = jax.tree.map(jnp.copy, cache)
    logits, _ = good(params, inputs, cache)
    return logits, keep


@pytest.mark.parametrize("fault", [_altered_token, _unchanged_state])
def test_planted_fault_is_not_correct(swing, fault):
    cell, server = swing
    _planted(server, fault)
    res = _run(cell, server)
    assert not res["correct"], res["checks"]


def test_control_is_not_correct(swing):
    cell, server = swing
    res = run.run(cell, 5, 1.5, 0, time.perf_counter(), server=server,
                  control=True)
    assert not res["correct"], res["checks"]
    over = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert over and all(k.startswith("gap_r") for k in over), res["checks"]


def test_batches_hold_one_prompt_length(cache):
    cell = _cell("tiny-open")
    server = harness.Server(cell, cache)
    w = server.window(4, 1.5)
    server.close()
    assert len(w.batches) > 1
    assert all(len({p for p, _ in b.rows}) == 1 for b in w.batches)
    assert {p for b in w.batches for p, _ in b.rows} == {8, 16}


def test_open_loop_run_is_correct(cache):
    cell = _cell("tiny-open")
    res = run.run(cell, 99, 1.5, 0, time.perf_counter(), cache=cache)
    assert res["correct"], res["checks"]
    assert "switch_ms" not in res["metrics"]


def _bench_run(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen2-1.5b.decode-steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
        timeout=300)


def test_no_tpu_means_no_result():
    p = _bench_run(BENCH.parent)
    assert p.returncode == 2 and p.stdout == "", p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    p = _bench_run(tmp_path)
    assert p.returncode != 0 and p.stdout == "", p.stderr
