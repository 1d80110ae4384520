"""The architecture seam: the dense decoder reads through ``arch.load``
exactly as the benchmark read it before there was a seam, and an
architecture the benchmark does not have runs a whole cell through the
seam alone."""
import hashlib
import json
import sys
import time
import types

import jax
import numpy as np
import pytest

import arch
import devtrace
import harness
import run
import shapes
import traffic
from conftest import BENCH

DATA = BENCH / "tests" / "data"
CONFIGS = {"qwen2-1.5b": BENCH / "configs" / "qwen2-1.5b.json",
           "mistral-nemo-12b": BENCH / "configs" / "mistral-nemo-12b.json",
           "tiny": DATA / "tiny.json"}
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]
BITS = (8, 6, 4)
ROWS = [(64, 8), (128, 32), (512, 64), (256, 1)]

# Read with bench/shapes.py as it stood before the seam: the leaves'
# (K, N, count), bytes(delta_k) for k = 0, 1, the least time of a decode
# step of 32 rows at rungs 0-2 on the TPU v5 lite row of peaks.json, and
# the FLOPs of 48 decode steps over ROWS.
GOLDEN = {
    "qwen2-1.5b": {
        "quantized_leaves": [
            (1536, 1536, 28), (1536, 256, 28), (1536, 256, 28),
            (1536, 1536, 28), (1536, 8960, 28), (1536, 8960, 28),
            (8960, 1536, 28), (151936, 1536, 1), (1536, 151936, 1)],
        "delta_bytes": [666353664, 666353664],
        "decode_step_least_s": [0.0010602907350427352, 0.0017670532844932844,
                                0.002473815833943834],
        "decode_flops": 270773944320},
    "mistral-nemo-12b": {
        "quantized_leaves": [
            (5120, 4096, 8), (5120, 1024, 8), (5120, 1024, 8),
            (4096, 5120, 8), (5120, 14336, 8), (5120, 14336, 8),
            (14336, 5120, 8), (131072, 5120, 1), (5120, 131072, 1)],
        "delta_bytes": [1321205760, 1321205760],
        "decode_step_least_s": [0.001820204385836386, 0.003126123213675214,
                                0.004432042041514042],
        "decode_flops": 494588657664},
    "tiny": {
        "quantized_leaves": [
            (64, 128, 2), (64, 64, 2), (64, 64, 2), (128, 64, 2),
            (64, 128, 2), (64, 128, 2), (128, 64, 2), (512, 64, 1),
            (64, 512, 1)],
        "delta_bytes": [61440, 61440],
        "decode_step_least_s": [3.6383882783882783e-07,
                                4.2385347985347987e-07,
                                4.83868131868132e-07],
        "decode_flops": 53972992},
}

# sha256 of the arrays (``_digest``) that bench/weights.py and
# bench/reference.py gave for tiny.json on the CPU before the seam
PARAMS_SEED3 = ("b3e6201059749391e9a2b2e08d59ec69"
                "e1f47e95af2158fb28a7dd51febb1d47")
SCORE = {((8, 6, 4), 2): ("2d95a42316ca737dff7381fb82766878"
                          "778ec199a81fe0bc444c00555d01b275"),
         ((4,), 0): ("3dfaac7069614182cd0a79b56fd90fd7"
                     "d88f2b5c210cca9041b019d94335a064")}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(a)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _two_rows():
    """Two requests of different lengths, scored at chosen positions."""
    a = ((np.arange(12) * 37 + 5) % 512).astype(np.int32)
    b = ((np.arange(7) * 101 + 11) % 512).astype(np.int32)
    return [(a, np.arange(4, 11), a[5:12, None]),
            (b, np.arange(0, 6), b[1:7, None])]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_dense_yardstick_is_unchanged(name):
    config = traffic.load(CONFIGS[name])
    a = arch.load(config)
    s, want = a.sizes(config), GOLDEN[name]
    leaves = a.quantized_leaves(s)
    assert leaves == want["quantized_leaves"]
    assert [shapes.delta_bytes(leaves, BITS, k) for k in (0, 1)] == (
        want["delta_bytes"])
    assert [a.decode_step_least_s(s, BITS, r, 32, PEAKS)
            for r in range(3)] == want["decode_step_least_s"]
    assert a.decode_flops(s, ROWS, 48) == want["decode_flops"]


def test_dense_weights_are_unchanged():
    config = traffic.load(DATA / "tiny.json")
    a = arch.load(config)
    s = a.sizes(config)
    params = jax.jit(lambda: a.program_params(3, s, config["weights"]))()
    assert _digest(params) == PARAMS_SEED3


@pytest.mark.parametrize("quant", sorted(SCORE))
def test_dense_reference_is_unchanged(quant):
    """At rung 2 of the ladder, and as the control (plain int4)."""
    config = traffic.load(DATA / "tiny.json")
    a = arch.load(config)
    got = a.score(_two_rows(), a.sizes(config), config["weights"],
                  config["weight_seed"], quant)
    assert _digest(got) == SCORE[quant]


def test_a_file_without_arch_is_dense():
    import arch.dense
    for path in CONFIGS.values():
        assert arch.load(traffic.load(path)) is arch.dense


@pytest.mark.parametrize("name", ["no_such_arch", "../dense", "", 3])
def test_an_unknown_architecture_names_the_known_ones(name):
    with pytest.raises(SystemExit) as e:
        arch.load({"name": "x", "arch": name})
    assert "dense" in arch.known()
    for known in arch.known():
        assert known in str(e.value)


def test_an_incomplete_architecture_is_refused(monkeypatch):
    mod = types.ModuleType("arch.halfway")
    mod.sizes = arch.load({}).sizes
    monkeypatch.setitem(sys.modules, "arch.halfway", mod)
    with pytest.raises(SystemExit, match="program_config"):
        arch.load({"arch": "halfway"})


def _counting(monkeypatch):
    """A test-only architecture ``counting``: the dense decoder, every
    function of the contract counting its calls."""
    dense = arch.load({})
    calls = dict.fromkeys(arch.CONTRACT, 0)
    mod = types.ModuleType("arch.counting")
    for name in arch.CONTRACT:
        def f(*a, _name=name, **kw):
            calls[_name] += 1
            return getattr(dense, _name)(*a, **kw)
        setattr(mod, name, f)
    monkeypatch.setitem(sys.modules, "arch.counting", mod)
    return mod, calls


def _decode_trace():
    """Three decode steps with a packed kernel each, in a traced window."""
    steps = [("jit_decode_step(7)", 1000 + 1000 * i, 400) for i in range(3)]
    return {"spans": [("bench.traced", 0, 5000)], "modules": steps,
            "ops": [("ladder_matmul.3", a + 10, 100) for _, a, _ in steps]}


def test_a_new_architecture_runs_a_cell_through_the_seam(monkeypatch,
                                                         tmp_path):
    """A copy of tiny.json that names ``counting`` runs a traced cell on
    the CPU, from the cold build to the check, correct; the CPU trace
    has no TPU plane, so the recorded events stand in for it."""
    mod, calls = _counting(monkeypatch)
    config = dict(traffic.load(DATA / "tiny.json"), arch="counting")
    per_layer = [{"name": "step_mfu.steady", "unit": "%"},
                 {"name": "packed_matmul_roofline.steady", "unit": "%"}]
    cell = harness.Cell("tiny.counting", 1, config,
                        traffic.load(DATA / "tiny-swing.json"), [],
                        per_layer)
    assert cell.arch is mod
    monkeypatch.setattr(devtrace, "load", lambda _dir: _decode_trace())
    monkeypatch.setattr(run, "peaks_for", lambda _kind: PEAKS)
    res = run.run(cell, 2 ** 31 + 29, 1.5, 1, time.perf_counter(),
                  cache=tmp_path)
    assert res["correct"], res["checks"]
    assert res["checks"]["bad_switches"]["value"] == 0
    assert set(res["metrics"]) == {m["name"] for m in per_layer}
    assert all(calls.values()), calls
