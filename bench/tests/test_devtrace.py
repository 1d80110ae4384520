"""Trace reduction: busy union, idle gaps by span, kernel time."""
import pytest

import devtrace as tr


def _ev():
    return {
        "spans": [("bench.traced", 0, 1000), ("bench.wait", 0, 100),
                  ("bench.generate", 100, 800), ("bench.switch", 900, 100)],
        "ops": [("custom-call.1", 150, 100), ("fusion", 250, 50),
                ("custom-call.1", 400, 50), ("fusion", 950, 20),
                ("late", 1200, 10)],
        "modules": [("jit_decode_step(1)", 140, 320),
                    ("jit_prefill(2)", 900, 80)],
    }


def test_busy_is_the_union_of_operations_inside_the_window():
    r = tr.reduce(_ev())
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx((150 + 50 + 20) * 1e-9)


def test_idle_gaps_are_labelled_by_the_open_span():
    gaps = dict(tr.reduce(_ev())["idle_gaps"])
    assert gaps["bench.wait"] == pytest.approx(100e-9)
    assert gaps["bench.generate"] == pytest.approx((50 + 100 + 450) * 1e-9)
    assert gaps["bench.switch"] == pytest.approx(80e-9)


def test_device_ops_are_ranked_by_time():
    ops = tr.reduce(_ev())["device_ops"]
    assert ops[0][0] == "custom-call.1"
    assert ops[0][1] == pytest.approx(150e-9)


def test_kernel_time_inside_decode_modules():
    ev = _ev()
    mods = tr.modules(ev, "decode_step", 0, 1000)
    assert len(mods) == 1
    assert tr.ops_inside(ev, mods, lambda n: n == "custom-call.1") == 150


def test_op_names_and_self_time():
    assert tr.op_name("%ladder_matmul.43 = bf16[32,1536] custom-call(x)") == \
        "ladder_matmul.43"
    assert tr.op_name("fusion.2") == "fusion.2"
    ops = [("while.2", 0, 100), ("fusion.1", 10, 30), ("ladder_matmul.4", 50,
                                                        40), ("copy", 200, 5)]
    assert dict(tr.self_times(ops)) == {"while.2": 30, "fusion.1": 30,
                                        "ladder_matmul.4": 40, "copy": 5}


def test_recorded_chip_trace():
    """Three decode steps of qwen2-1.5b at rung 2, batch 32, recorded on
    one TPU v5e (``tool.py trace``) and cut to a slice."""
    import arch
    import layer_metrics as lm
    import traffic
    from conftest import BENCH
    from harness import Batch, Window
    ev = tr.read(str(BENCH / "tests" / "data" / "qwen_decode_trace.json.gz"))
    r = tr.reduce(ev)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"][0][0] == "ladder_matmul.43"
    assert [n for n, _ in r["idle_gaps"]] == ["bench.generate"]
    w = Window(0.0, 1.0)
    w.batches = [Batch(0, 2, 0.0, 3, [(512, 256)] * 32, traced=True)]
    cfg = traffic.load(BENCH / "configs" / "qwen2-1.5b.json")
    dense = arch.load(cfg)
    ctx = lm.Context(w, 32, dense, dense.sizes(cfg), (8, 6, 4),
                     {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, ev, r)
    assert lm.decode_step_ms(ctx) == pytest.approx(24.185, abs=0.01)
    assert 5 < lm.kernel_roofline(ctx) < 100
    assert 0 < lm.step_mfu(ctx) < 5
    assert 0 < lm.device_idle(ctx) < 100
