"""Program spans in a trace: the innermost span of an idle gap, the
page-in shares of a switch, and the readers' silence without spans."""
import glob
import os
import tempfile

import pytest

import devtrace as tr
import layer_metrics as lm
import arch
import progtrace as pt
import traffic
from conftest import BENCH
from harness import Batch, Window

DATA = BENCH / "tests" / "data"
QWEN_CONFIG = traffic.load(BENCH / "configs" / "qwen2-1.5b.json")
DENSE = arch.load(QWEN_CONFIG)
QWEN = DENSE.sizes(QWEN_CONFIG)
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _ev():
    """A decode batch of two steps and an upgrade: nested program spans
    under the harness's, device operations between them."""
    return {
        "spans": [("bench.traced", 0, 2000), ("bench.generate", 0, 1000),
                  ("bench.switch", 1000, 400), ("bench.switch", 1500, 100)],
        "program": [
            ("nq.generate", 10, 980, {"batch": 0}),
            ("nq.prefill", 20, 30, {"prompt_len": 8}),
            ("nq.token_sync", 100, 100, {"step": 0, "rows": 4}),
            ("nq.decode_step", 200, 50, {"step": 0}),
            ("nq.token_sync", 400, 200, {"step": 1, "rows": 4}),
            ("nq.decode_step", 600, 50, {"step": 1}),
            ("nq.ensure_mode", 1000, 390, {}),
            ("nq.switch", 1010, 370, {"from_rung": 0, "to_rung": 1}),
            ("nq.page_in.read", 1020, 100, {"nbytes": 64}),
            ("nq.page_in.crc", 1120, 150, {"nbytes": 64}),
            ("nq.page_in.put", 1270, 50, {"nbytes": 64}),
            # a downgrade pages nothing in and is not counted
            ("nq.switch", 1510, 80, {"from_rung": 1, "to_rung": 0}),
        ],
        "ops": [("prefill", 50, 50), ("decode", 250, 150),
                ("decode", 650, 300), ("put", 1280, 40)],
        "modules": [],
    }


def _ctx(ev, reduced=None):
    w = Window(0.0, 1.0)
    return lm.Context(w, 32, DENSE, QWEN, (8, 6, 4), PEAKS, ev,
                      reduced or tr.reduce(ev))


def test_innermost_span_takes_the_time_less_its_children():
    ev = _ev()
    got = pt.innermost(ev["program"], 0, 1000)
    assert got == {"outside program spans": 20, "nq.generate": 980 - 30
                   - 300 - 100, "nq.prefill": 30, "nq.token_sync": 300,
                   "nq.decode_step": 100}
    # a piece of an interval, and a child cut at its parent's end
    assert pt.innermost(ev["program"], 150, 220) == {
        "nq.token_sync": 50, "nq.decode_step": 20}
    assert pt.innermost([("nq.a", 0, 10, {}), ("nq.b", 5, 20, {})],
                        0, 30) == {"nq.a": 5, "nq.b": 5,
                                   "outside program spans": 20}


def test_idle_time_by_innermost_span():
    ev = _ev()
    idle = pt.idle_by_program_span(ev, tr.reduce(ev))
    assert idle["nq.token_sync"] == 100 + 200
    assert idle["nq.decode_step"] == 50 + 50
    assert idle["nq.page_in.crc"] == 150
    assert idle["nq.page_in.put"] == 50 - 40
    assert sum(idle.values()) == 2000 - (50 + 150 + 300 + 40)


def test_token_sync_idle_reads_the_share_of_the_window():
    ctx = _ctx(_ev())
    assert pt.token_sync_idle(ctx) == pytest.approx(100 * 300 / 2000)
    assert pt.token_sync_idle(ctx) <= lm.device_idle(ctx)


def test_switch_shares_are_over_the_switches_that_page_in():
    ctx = _ctx(_ev())
    shares = {p: pt.switch_share(ctx, p) for p in ("read", "crc", "put")}
    assert shares == pytest.approx({"read": 25.0, "crc": 37.5,
                                    "put": 12.5})
    assert sum(shares.values()) <= 100


def test_readers_are_silent_without_program_spans():
    ev = {**_ev(), "program": []}
    ctx = _ctx(ev)
    assert pt.token_sync_idle(ctx) is None
    assert all(pt.switch_share(ctx, p) is None
               for p in ("read", "crc", "put"))
    no_switch = {**_ev(), "program": [s for s in _ev()["program"]
                                      if not s[0].startswith("nq.page")]}
    assert pt.switch_share(_ctx(no_switch), "read") is None
    assert pt.token_sync_idle(lm.Context(Window(0.0, 1.0), 32, DENSE,
                                         QWEN, (8, 6, 4), PEAKS)) is None


def test_program_spans_change_no_harness_reading(tmp_path):
    """The older recorded trace has no program spans: it reads as
    empty, and adding spans to it moves nothing ``reduce`` or the
    accepted readers return."""
    ev = tr.read(str(DATA / "qwen_decode_trace.json.gz"))
    assert "program" not in ev
    r = tr.reduce(ev)
    assert pt.innermost(ev.get("program", []), r["t0"], r["t1"]) == {
        "outside program spans": r["t1"] - r["t0"]}
    t0 = r["t0"]
    spans = [("nq.generate", t0, r["t1"] - t0, {"batch": 1}),
             ("nq.token_sync", t0 + 1e6, 5e6, {"step": 0, "rows": 32})]
    path = str(tmp_path / "with_program.json.gz")
    tr.save({**ev, "program": spans}, path)
    back = tr.read(path)
    assert back["program"] == [tuple(s) for s in spans]
    assert tr.reduce(back) == r
    w = Window(0.0, 1.0)
    w.batches = [Batch(0, 2, 0.0, 3, [(512, 256)] * 32, traced=True)]
    for f in (lm.decode_step_ms, lm.kernel_roofline, lm.step_mfu,
              lm.device_idle):
        a = f(lm.Context(w, 32, DENSE, QWEN, (8, 6, 4), PEAKS, ev, r))
        b = f(lm.Context(w, 32, DENSE, QWEN, (8, 6, 4), PEAKS, back,
                         tr.reduce(back)))
        assert a == b


def test_a_reader_finds_the_runs_own_trace(tmp_path, monkeypatch):
    """Without a ``program`` key the spans come from the run's xplane,
    the one under the temp dir whose traced window matches."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    for k in range(2):
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir)
        with TraceAnnotation("bench.traced"):
            with TraceAnnotation("nq.token_sync", step=k, rows=3):
                pass
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    program, bench = pt.host_spans(ProfileData.from_file(path))
    (_, a, d), = [s for s in bench if s[0] == "bench.traced"]

    def ctx(t0):
        return lm.Context(Window(0.0, 1.0), 32, DENSE, QWEN, (8, 6, 4), PEAKS,
                          {"ops": [], "spans": bench, "modules": []},
                          {"t0": t0, "t1": a + d})
    found = ctx(a)
    (name, _, _, args), = pt.program_spans(found)
    assert (name, args) == ("nq.token_sync", {"step": 1, "rows": 3})
    assert found.events["program"] == pt.program_spans(found)
    assert pt.program_spans(ctx(a - 1.0)) == []


def test_recorded_chip_trace_with_program_spans():
    """Decode steps of qwen2-1.5b at rung 2, batch 32, with the program's
    spans, recorded on one TPU v5e (``tool.py trace``) and cut to a
    slice."""
    ev = tr.read(str(DATA / "qwen_decode_program_trace.json.gz"))
    ctx = _ctx(ev)
    names = {s[0] for s in ev["program"]}
    assert {"nq.token_sync", "nq.decode_step"} <= names
    sync = pt.token_sync_idle(ctx)
    assert 0 < sync <= lm.device_idle(ctx)
    assert lm.decode_step_ms(ctx) > 0
