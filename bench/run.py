#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell names a configuration
(``bench/configs``) and a traffic mix (``bench/traffic``) in
``BENCHMARK.json``.  The run boots the served model from its artifact
(building it first if the checkout has none, which is the cold run),
warms up every shape the mix uses, serves the mix for ``--seconds``,
checks a sample of what it served against the plain reference, and
prints one JSON line last: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, ``breakdown`` when traced, and
``checks``, every number compared with its limit.

It needs a TPU: without one, or with fewer chips than the cell asks
for, it exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import measure  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def peaks_for(kind: str) -> dict:
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run(cell: harness.Cell, seed: int, seconds: float, trace: int,
        t_start: float, cache: Path = harness.CACHE, server=None,
        control: bool = False) -> dict:
    """One run of ``cell``; returns the result line as a dict.  With
    ``control`` the control is judged in the program's place."""
    import jax
    seed %= 2 ** 63
    if server is None:
        server = harness.Server(cell, cache)
    setup_s = time.perf_counter() - t_start
    print(f"[setup] cold={int(server.cold)} setup_s={setup_s:.3f}",
          file=sys.stderr, flush=True)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else ""
    try:
        w = server.window(seed, seconds, trace_dir=tdir)
        devs = jax.devices()[:cell.chips]
        stats = [d.memory_stats() or {} for d in devs]
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                           for s in stats)}
        print(f"[window] requests={len(w.requests)} batches={len(w.batches)}"
              f" switches={len(w.switches)} queue_at_end={w.queue_at_end}"
              f" traces={w.traces} compiles={w.compiles}",
              file=sys.stderr, flush=True)
        metrics, breakdown = {}, None
        if trace:
            import layer_metrics
            import devtrace as tr
            events = tr.load(tdir)
            reduced = tr.reduce(events)
            device["busy_s"], device["window_s"] = (reduced["busy_s"],
                                                    reduced["window_s"])
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            ctx = layer_metrics.Context(
                w, server.mix["max_batch"], cell.arch, cell.sizes,
                server.bits, peaks_for(device["kind"]), events, reduced)
            for m in cell.per_layer:
                v = reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            values = measure.end_to_end(w, setup_s)
            for m in cell.end_to_end:
                if values.get(m["name"]) is not None:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    server.close()
    import check
    checks = check.judge(w, seed, cell.config, server.mix["check_per_rung"],
                         control)
    served = [r for r in w.requests if r["rung"] is not None]
    failed = sum(check.exact_failures(r, cell.sizes) for r in served)
    result = {"correct": check.passed(checks) and failed == 0,
              "attempted": len(served), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        print(f"[check] {k} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def main(argv=None) -> int:
    a = parse(argv)
    cell = harness.load_cell(a.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    result = run(cell, a.seed, a.seconds, a.trace, T_START)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
