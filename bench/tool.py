#!/usr/bin/env python3
"""Measurements that set the benchmark's numbers, each in one process.

    python bench/tool.py knee   --workload W --seconds S --saturate R \
        [--fractions 0.8,0.7] [--confirm-seconds S2]
    python bench/tool.py limits --workload W --seconds S --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--control-sets "3,5,4 3,5,7"] [--mix JSON]
    python bench/tool.py trace  --workload W --seed N --seconds S --out DIR

``knee`` offers an open-loop mix at ``--saturate`` requests a second,
far above what it can serve, for ``--seconds``; the capacity is the
requests of the full batches after the first two over their time.  It
then offers each fraction of that capacity for ``--confirm-seconds``
and reports the waiting queue at each batch start, until a fraction
whose mean queue over the window's last third exceeds that over its
middle third by less than half a batch: its rate is the cell's, printed
last as ``{"rate": ...}``.  ``limits``
serves one window per seed and prints every number the correctness
check compares, with ``correct`` as a run would judge it; a control
seed puts the control in the program's place, once for each of
``--control-sets`` (control bits per rung, rung 0 first; the
configuration's ``control_bits`` if none).  ``--mix`` overrides keys of
the traffic mix (``{"batching": "fifo"}``).  ``trace`` records one
traced window and writes its events and its plane and line names under
``--out``.  None of these run in the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import check  # noqa: E402
import harness  # noqa: E402
import measure  # noqa: E402


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def _served(w, B: int):
    full = [b for b in w.batches[2:] if len(b.rows) == B]
    return full, (sum(len(b.rows) for b in full)
                  / (full[-1].end - full[0].start) if full else None)


def knee(server, a) -> None:
    B = server.mix["max_batch"]
    w = server.window(a.seed, a.seconds, rate=a.saturate)
    full, capacity = _served(w, B)
    _emit({"saturate": a.saturate, "batches": len(w.batches),
           "full_batches": len(full), "capacity_per_s": capacity,
           "batch_s": [b.end - b.start for b in w.batches],
           "compiles": w.compiles})
    chosen = None
    for f in [float(x) for x in a.fractions.split(",")]:
        rate = round(f * capacity, 2)
        w = server.window(a.seed, a.confirm_seconds, rate=rate)
        third = (w.t1 - w.t0) / 3
        mid, last = ([n for t, n in w.depth if w.t0 + k * third <= t
                      < w.t0 + (k + 1) * third] for k in (1, 2))
        ok = bool(mid and last) and (sum(last) / len(last)
                                     < sum(mid) / len(mid) + B / 2)
        _emit({"fraction": f, "rate": rate, "stable": ok,
               "depth": [(round(t - w.t0, 2), n) for t, n in w.depth],
               "backlog_at_end": w.queue_at_end,
               "mean_real_rows": sum(len(b.rows) for b in w.batches)
               / max(1, len(w.batches)),
               **{k: v for k, v in measure.end_to_end(w, 0.0).items()
                  if k != "setup_s"}, "compiles": w.compiles})
        if ok:
            chosen = rate
            break
    _emit({"rate": chosen})


def limits(server, a) -> None:
    cfg = server.cell.config
    sets = [[int(b) for b in x.split(",")] for x in a.control_sets.split()]
    for seed in _ints(a.seeds) + _ints(a.control_seeds):
        control = seed in _ints(a.control_seeds)
        t = time.perf_counter()
        w = server.window(seed, a.seconds)
        served = [r for r in w.requests if r["rung"] is not None]
        failed = sum(check.exact_failures(r, server.sizes) for r in served)
        for bits in (sets or [cfg["control_bits"]]) if control else [None]:
            c = dict(cfg, control_bits=bits) if bits else cfg
            got = check.numbers(w, seed, c, server.mix["check_per_rung"],
                                control)
            checks = check.compare(w, c, got)
            _emit({"seed": seed, "control_bits": bits,
                   "correct": check.passed(checks) and failed == 0,
                   "batches": len(w.batches), "compiles": w.compiles,
                   "seconds": time.perf_counter() - t, "numbers": got,
                   "checks": {k: v["value"] for k, v in checks.items()}})


def trace(server, a) -> None:
    import jax
    import devtrace as tr
    from jax.profiler import ProfileData
    out = Path(a.out)
    out.mkdir(parents=True, exist_ok=True)
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    w = server.window(a.seed, a.seconds, trace_dir=tdir)
    path = next(Path(tdir).rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    summary = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {"n": len(evs), "first": [
                (e.name, e.start_ns, e.duration_ns) for e in evs[:5]]}
        summary[plane.name] = lines
    (out / "planes.json").write_text(json.dumps(summary, indent=1))
    events = tr.load(tdir)
    tr.save(events, str(out / "events.json.gz"))
    reduced = tr.reduce(events)
    _emit({"batches": [(b.index, b.rung, b.steps, b.traced)
                       for b in w.batches],
           "reduced": reduced, "xplane_bytes": path.stat().st_size,
           "device": jax.devices()[0].device_kind})


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("what", choices=("knee", "limits", "trace"))
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--saturate", type=float, default=0.0)
    p.add_argument("--fractions", default="0.8,0.7")
    p.add_argument("--confirm-seconds", type=float, default=120.0)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--control-sets", default="")
    p.add_argument("--mix", default="{}")
    p.add_argument("--out", default=str(BENCH / ".cache" / "trace"))
    a = p.parse_args(argv)
    t = time.perf_counter()
    cell = harness.load_cell(a.workload)
    cell.mix = {**cell.mix, **json.loads(a.mix)}
    server = harness.Server(cell)
    _emit({"setup_s": time.perf_counter() - t, "cold": server.cold,
           "mix": cell.mix})
    {"knee": knee, "limits": limits, "trace": trace}[a.what](server, a)
    return 0


if __name__ == "__main__":
    sys.exit(main())
