"""From a profiler trace to device busy time, step times and idle gaps.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain event lists: the device operations and the executed modules of
the first TPU, and the harness's host spans (``bench.*``).  ``reduce``
works on those lists alone, so it can be checked on a recorded trace.
Every time is in nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Callable, Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench.traced"


def op_name(name: str) -> str:
    """An HLO instruction's name from a device event's name, which can be
    the instruction's whole text: ``%ladder_matmul.43 = bf16[...] ...``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line) -> List[Event]:
    return [(op_name(e.name), float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def load(logdir: str) -> Dict[str, List[Event]]:
    """Events of the one ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found "
                           f"{len(paths)}")
    data = ProfileData.from_file(paths[0])
    tpus = sorted((p for p in data.planes
                   if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)
    if not tpus:
        raise RuntimeError("the trace holds no TPU plane: planes are "
                           + ", ".join(p.name for p in data.planes))
    out = {"ops": [], "modules": [], "spans": []}
    for line in tpus[0].lines:
        if line.name == OPS_LINE:
            out["ops"] = _events(line)
        elif line.name == MODULES_LINE:
            out["modules"] = _events(line)
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"] += [e for e in _events(line)
                                 if e[0].startswith("bench.")]
    return out


def save(events: Dict[str, List[Event]], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read(path: str) -> Dict[str, List[Event]]:
    with gzip.open(path, "rt") as f:
        return {k: [tuple(e) for e in v] for k, v in json.load(f).items()}


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class _Spans:
    """The harness spans, which follow one another without nesting, and
    which one is open at a given time."""

    def __init__(self, spans: Sequence[Event]):
        self.spans = sorted((a, a + d, n) for n, a, d in spans
                            if n != WINDOW_SPAN)
        self.starts = [a for a, _, _ in self.spans]

    def split(self, a: float, b: float):
        """(label, ns) pieces of the interval [a, b): each span's overlap
        with it, and the rest as ``outside spans``."""
        i = max(0, bisect.bisect_right(self.starts, a) - 1)
        covered = 0.0
        while i < len(self.spans) and self.spans[i][0] < b:
            s, e, name = self.spans[i]
            part = min(e, b) - max(s, a)
            if part > 0:
                covered += part
                yield name, part
            i += 1
        if b - a - covered > 0:
            yield "outside spans", b - a - covered


def reduce(ev: Dict[str, List[Event]], top: int = 10) -> Dict:
    """Busy and idle time of the traced window (the ``bench.traced``
    span), the device operations that took most time, and the idle time
    grouped by the harness span open in each gap."""
    win = [e for e in ev["spans"] if e[0] == WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found {len(win)}")
    t0, t1 = win[0][1], win[0][1] + win[0][2]
    busy = union([(max(a, t0), min(a + d, t1)) for _, a, d in ev["ops"]
                  if a < t1 and a + d > t0])
    busy_ns = sum(b - a for a, b in busy)
    idle: Dict[str, float] = defaultdict(float)
    spans = _Spans(ev["spans"])
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    for a, b in zip(edges[::2], edges[1::2]):
        for name, ns in spans.split(a, b):
            idle[name] += ns
    by_op: Dict[str, float] = defaultdict(float)
    for name, d in self_times([e for e in ev["ops"] if t0 <= e[1] < t1]):
        by_op[name] += d
    ranked = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (t1 - t0) / 1e9, "busy_s": busy_ns / 1e9,
            "device_ops": [[n, d / 1e9] for n, d in ranked],
            "idle_gaps": [[n, d / 1e9] for n, d in gaps],
            "t0": t0, "t1": t1}


def self_times(ops: Sequence[Event]) -> List[Tuple[str, float]]:
    """(name, ns) of each operation less the operations nested in it (a
    loop's event spans the operations of its body)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [d for _, _, d in ops]
    stack: List[int] = []
    for i in order:
        a = ops[i][1]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return [(ops[i][0], own[i]) for i in range(len(ops))]


def modules(ev: Dict[str, List[Event]], part: str, t0: float,
            t1: float) -> List[Event]:
    """Executions of modules whose name holds ``part`` inside [t0, t1)."""
    return [e for e in ev["modules"] if part in e[0] and t0 <= e[1] < t1]


def ops_inside(ev: Dict[str, List[Event]], outer: Sequence[Event],
               wanted: Callable[[str], bool]) -> float:
    """Summed device time (ns) of the operations whose name is
    ``wanted`` that start inside one of the ``outer`` executions."""
    spans = sorted((a, a + d) for _, a, d in outer)
    starts = [a for a, _ in spans]
    total = 0.0
    for name, a, d in ev["ops"]:
        if wanted(name):
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < spans[i][1]:
                total += d
    return total
