"""Per-layer readers: found by name, silent where they have nothing to read."""
import pytest

import arch
import layer_metrics as lm
import run
import shapes
import traffic
from conftest import BENCH
from harness import Batch, Window

TINY_CONFIG = traffic.load(BENCH / "tests" / "data" / "tiny.json")
DENSE = arch.load(TINY_CONFIG)
TINY = DENSE.sizes(TINY_CONFIG)
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(events=None):
    w = Window(t0=0.0, t1=10.0)
    w.batches = [Batch(0, 2, 1.0, 3, [(16, 3), (8, 2)], traced=True)]
    reduced = None
    if events:
        import devtrace
        reduced = devtrace.reduce(events)
    return lm.Context(w, 4, DENSE, TINY, (8, 6, 4), PEAKS, events, reduced)


def _events():
    steps = [("jit_decode_step(7)", 1000 + 1000 * i, 400) for i in range(3)]
    ops = []
    for _, a, _ in steps:
        ops += [("ladder_matmul.3", a + 10, 100), ("fusion.1", a + 200, 50)]
    return {"spans": [("bench.traced", 0, 5000), ("bench.generate", 500, 4000)],
            "modules": [("jit_prefill(2)", 600, 300)] + steps,
            "ops": [("ladder_matmul.9", 610, 200)] + ops}


def test_every_per_layer_metric_has_a_reader():
    import json
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(run.reader(m["name"]))


def test_trace_readers_are_silent_without_a_trace():
    ctx = _ctx()
    for f in (lm.decode_step_ms, lm.step_mfu, lm.kernel_roofline,
              lm.device_idle):
        assert f(ctx) is None
    assert lm.idle_slot_share(ctx) == pytest.approx(100 * (1 - 5 / 12))


def test_trace_readers():
    ctx = _ctx(_events())
    assert lm.decode_step_ms(ctx) == pytest.approx(400e-6)
    flops = shapes.decode_flops(TINY, [(16, 3), (8, 2)], 3)
    assert lm.step_mfu(ctx) == pytest.approx(100 * flops / (1.2e-6 * 197e12))
    least = 3 * shapes.decode_step_least_s(TINY, (8, 6, 4), 2, 4, PEAKS)
    # the prefill's kernel is outside the decode steps and not counted
    assert lm.kernel_roofline(ctx) == pytest.approx(100 * least / 300e-9)
    assert lm.device_idle(ctx) == pytest.approx(100 * (1 - 650 / 5000))
