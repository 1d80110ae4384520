"""The mixture-of-experts architecture behind the seam: its contract, its
yardstick at the published sizes (worked out here from the element
counts), and a tiny configuration run to ``correct`` through
``run.run`` with its control refused."""
import json
import time

import pytest

import arch
import harness
import run
import shapes
import traffic
from conftest import BENCH

DATA = BENCH / "tests" / "data"
CONFIG = BENCH / "configs" / "mellum2-12b-a2.5b.json"
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]
BITS = (8, 6, 4)
D, FF, Q, KV, V, L, HELD = 2304, 896, 32 * 128, 4 * 128, 98304, 28, 16


def _published():
    config = traffic.load(CONFIG)
    a = arch.load(config)
    return config, a, a.sizes(config)


def test_the_configuration_names_the_moe_architecture():
    import arch.moe
    config, a, s = _published()
    assert a is arch.moe
    assert callable(a.expert_step_least_s)
    assert (s.layers, s.held, s.experts, s.first, s.top_k) == (L, 16, 64, 0, 8)
    assert s.layer_types == ("sliding", "sliding", "sliding", "full") * 7
    assert s.yarn == (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    pc = a.program_config(config)
    assert (pc.experts_held, pc.expert_first, pc.d_ff, pc.sliding_window) == (
        16, 0, 896, 1024)
    assert pc.layer_type(3) == "full" and pc.layer_type(4) == "sliding"


def test_an_older_program_is_refused_before_any_build(monkeypatch):
    """The parent program has no such configuration: ``sizes`` stops at
    once, so a run there fails in seconds and not after the build."""
    import repro.configs

    def missing(name):
        raise KeyError(name)
    monkeypatch.setattr(repro.configs, "get_config", missing)
    with pytest.raises(SystemExit, match="no configuration"):
        _published()


def test_quantized_leaves_and_switch_bytes():
    _, a, s = _published()
    leaves = a.quantized_leaves(s)
    assert leaves == [(D, Q, L), (D, KV, L), (D, KV, L), (Q, D, L),
                      (D, FF, L * HELD), (D, FF, L * HELD), (FF, D, L * HELD),
                      (V, D, 1), (D, V, 1)]
    # every K here is a multiple of its block, and each block of 32:
    # a w-bit stream is exactly K * N * w / 8 bytes; both deltas are 3 bits
    elements = (L * (2 * D * Q + 2 * D * KV) + 3 * L * HELD * D * FF
                + 2 * V * D)
    assert elements == 3_822_059_520
    assert [shapes.delta_bytes(leaves, BITS, k) for k in (0, 1)] == [
        elements * 3 // 8] * 2


def _least(M, K, N, wbits, rows):
    """Bytes (weights at wbits, f32 scales, bf16 rows in and out) over
    819 GB/s, or FLOPs over 197 TFLOP/s, whichever is longer."""
    nbytes = K * N * wbits / 8 + 4 * N + 2 * rows * K + 2 * rows * N
    return max(2 * rows * K * N / 197e12, nbytes / 819e9)


@pytest.mark.parametrize("rung", [0, 1, 2])
def test_decode_step_least_time_outside_the_experts(rung):
    _, a, s = _published()
    wbits = (4, 7, 10)[rung]
    M = 128
    attn = L * (_least(M, D, Q, wbits, M) + 2 * _least(M, D, KV, wbits, M)
                + _least(M, Q, D, wbits, M))
    head = max(2 * M * D * V / 197e12,
               (D * V * wbits / 8 + 4 * V + 2 * M * D + 4 * M * V) / 819e9)
    assert a.decode_step_least_s(s, BITS, rung, M, PEAKS) == pytest.approx(
        attn + head, rel=1e-12)


@pytest.mark.parametrize("rung", [0, 1, 2])
def test_expert_step_least_time(rung):
    """16 held experts' streams once a step; 128 rows top-8 of 64 send
    256 rows to this chip's experts."""
    _, a, s = _published()
    wbits = (4, 7, 10)[rung]
    R = 128 * 8 * 16 / 64
    per = [max(2 * R * K * N / 197e12,
               (HELD * (K * N * wbits / 8 + 4 * N) + 2 * R * K + 2 * R * N)
               / 819e9) for K, N in ((D, FF), (D, FF), (FF, D))]
    got = a.expert_step_least_s(s, BITS, rung, 128, PEAKS)
    assert got == pytest.approx(L * sum(per), rel=1e-12)
    if rung == 2:
        # 3.47 GB of packed expert words: 4.2 ms at 819 GB/s
        assert 4.2e-3 < got < 4.5e-3


def test_decode_flops_count_this_chips_expected_experts():
    _, a, s = _published()
    per_layer = 2 * D * Q + 2 * D * KV + D * 64 + 2 * 3 * D * FF
    ctx = 100
    want = 2 * (L * per_layer + D * V) + L * 4 * Q * ctx
    assert a.decode_flops(s, [(99, 2)], 1) == want


# ---------------------------------------------------------------------------
# a tiny configuration through the whole run
# ---------------------------------------------------------------------------
def _cell():
    return harness.Cell("tiny-moe.swing", 1, traffic.load(DATA / "tiny-moe.json"),
                        traffic.load(DATA / "tiny-swing.json"),
                        [{"name": "out_tok_s", "unit": "u"}], [])


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    path = tmp_path_factory.mktemp("cache-moe")
    harness.Server(_cell(), path).close()
    return path


@pytest.mark.parametrize("control", [False, True], ids=["sound", "control"])
def test_a_tiny_moe_cell_is_correct_and_its_control_is_not(cache, control):
    cell = _cell()
    res = run.run(cell, 2 ** 31 + 41, 1.5, 0, time.perf_counter(),
                  server=harness.Server(cell, cache), control=control)
    json.dumps(res)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["bad_switches"]["value"] == 0
    assert {"gap_r0", "gap_r1", "gap_r2"} <= set(res["checks"])
    assert res["correct"] is not control, res["checks"]


def test_the_expert_roofline_reads_only_expert_kernels_in_decode_steps():
    """Two traced decode steps of rung 2: the reader divides their least
    expert time by the ``expert_*matmul`` ops inside the decode modules,
    and leaves out the packed kernels and the ops outside the steps."""
    import layer_metrics
    config, a, s = _published()
    read = run.reader("expert_matmul_roofline.expert")
    steps = [("jit_decode_step(7)", 1000 + 10_000_000 * i, 9_000_000)
             for i in range(2)]
    ops = [("expert_ladder_matmul.12", a0 + 10, 2_000_000)
           for _, a0, _ in steps]
    ops += [("ladder_matmul.3", a0 + 5, 500_000) for _, a0, _ in steps]
    ops += [("expert_ladder_matmul.12", 30_000_000, 2_000_000)]
    events = {"spans": [("bench.traced", 0, 25_000_000)], "modules": steps,
              "ops": ops}
    batch = harness.Batch(0, 2, 0.0, 2, [(128, 2)], traced=True)
    w = harness.Window(0.0, 1.0, batches=[batch])
    ctx = layer_metrics.Context(w, 128, a, s, BITS, PEAKS, events,
                                {"t0": 0, "t1": 25_000_000})
    least = 2 * a.expert_step_least_s(s, BITS, 2, 128, PEAKS)
    assert read(ctx) == pytest.approx(100 * least / 4e-3)
    ctx.arch = arch.load({})            # a dense decoder has none to read
    assert read(ctx) is None
