"""The program's own spans in a profiler trace, and the per-layer metrics
that read them.

The program opens host spans named ``nq.*`` at its layer boundaries
(``src/repro/obs.py`` lists them).  They are in the same xplane, on the
same clock, as the device operations that ``devtrace`` reads, so a
device-idle gap can be put down to the innermost program span open
during it: a token pull, a decode dispatch, a file read.  Here a
program span is ``(name, start_ns, duration_ns, args)``, and an events
dict holds them under ``"program"`` beside ``devtrace``'s keys;
``devtrace.save`` and ``devtrace.read`` carry that key as they are.

``devtrace.load`` keeps only the harness's ``bench.*`` spans, and
``run.py`` hands readers what it loaded.  So where the events have no
``"program"`` key, a reader finds the run's own xplane again: the one
under the temp dir whose ``bench.traced`` window is the one the run
reduced.  A trace with no ``nq.*`` spans (a program that records none)
reads as None.
"""
from __future__ import annotations

import bisect
import glob
import math
import os
import tempfile
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import devtrace as tr

Span = Tuple[str, float, float, Dict]     # (name, start_ns, duration_ns, args)
PREFIX = "nq."
OUTSIDE = "outside program spans"
TOKEN_SYNC = "nq.token_sync"
PAGE_IN = "nq.page_in."
SWITCH_SPAN = "bench.switch"


# ---------------------------------------------------------------------------
# from the xplane
# ---------------------------------------------------------------------------
def host_spans(data) -> Tuple[List[Span], List[tr.Event]]:
    """The program spans and the harness spans of a ``ProfileData``."""
    program, bench = [], []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    program.append((e.name, float(e.start_ns),
                                    float(e.duration_ns), dict(e.stats)))
                elif e.name.startswith("bench."):
                    bench.append((e.name, float(e.start_ns),
                                  float(e.duration_ns)))
    return program, bench


def load(logdir: str) -> Dict[str, list]:
    """``devtrace.load`` of ``logdir`` and its program spans."""
    from jax.profiler import ProfileData
    events = tr.load(logdir)
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    events["program"] = host_spans(ProfileData.from_file(path))[0]
    return events


def _run_program(t0: float, t1: float) -> List[Span]:
    """The program spans of the run whose traced window is [t0, t1): the
    newest ``bench-trace-*`` xplane under the temp dir that holds that
    window, which ``run.py`` removes only once its readers are done."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(tempfile.gettempdir(), "bench-trace-*",
                                   "**", "*.xplane.pb"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        program, bench = host_spans(ProfileData.from_file(path))
        if any(n == tr.WINDOW_SPAN and (a, a + d) == (t0, t1)
               for n, a, d in bench):
            return program
    return []


def program_spans(ctx) -> List[Span]:
    """The program spans of the traced run a ``layer_metrics.Context``
    describes ([] without a trace).  Found once, they are kept in the
    run's events for the readers after."""
    if not ctx.events or not ctx.reduced:
        return []
    if "program" not in ctx.events:
        ctx.events["program"] = _run_program(ctx.reduced["t0"],
                                             ctx.reduced["t1"])
    return ctx.events["program"]


# ---------------------------------------------------------------------------
# innermost span
# ---------------------------------------------------------------------------
class Timeline:
    """Program spans laid flat: each instant they cover belongs to the
    innermost span open then (the latest opened), so a span's share is
    its self time, its duration less the time its children cover.  A
    child that outlasts its parent is cut at the parent's end."""

    def __init__(self, program: Sequence[Span]):
        pieces: List[Tuple[float, float, str]] = []
        stack: List[Tuple[float, str]] = []      # (end, name), innermost last
        t = -math.inf

        def close(upto: float) -> None:
            nonlocal t
            while stack and stack[-1][0] <= upto:
                end, name = stack.pop()
                if end > t:
                    pieces.append((t, end, name))
                    t = end

        for name, a, d, *_ in sorted(program, key=lambda s: (s[1], -s[2])):
            close(a)
            if stack and a > t:
                pieces.append((t, a, stack[-1][1]))
            t = a
            stack.append((min(a + d, stack[-1][0]) if stack else a + d,
                          name))
        close(math.inf)
        self.pieces = [p for p in pieces if p[1] > p[0]]
        self.starts = [a for a, _, _ in self.pieces]

    def split(self, a: float, b: float):
        """(name, ns) pieces of [a, b) by innermost span, the rest as
        ``OUTSIDE``."""
        i = max(0, bisect.bisect_right(self.starts, a) - 1)
        covered = 0.0
        while i < len(self.pieces) and self.pieces[i][0] < b:
            s, e, name = self.pieces[i]
            part = min(e, b) - max(s, a)
            if part > 0:
                covered += part
                yield name, part
            i += 1
        if b - a - covered > 0:
            yield OUTSIDE, b - a - covered


def innermost(program: Sequence[Span], a: float, b: float) -> Dict[str, float]:
    """Nanoseconds of [a, b) under each innermost program span."""
    out: Dict[str, float] = defaultdict(float)
    for name, ns in Timeline(program).split(a, b):
        out[name] += ns
    return dict(out)


def idle_by_program_span(events: Dict, reduced: Dict) -> Dict[str, float]:
    """Nanoseconds of the traced window [t0, t1) in which no device
    operation ran, by the innermost program span open then."""
    t0, t1 = reduced["t0"], reduced["t1"]
    busy = tr.union([(max(a, t0), min(a + d, t1)) for _, a, d in events["ops"]
                     if a < t1 and a + d > t0])
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    line = Timeline(events.get("program", ()))
    out: Dict[str, float] = defaultdict(float)
    for a, b in zip(edges[::2], edges[1::2]):
        for name, ns in line.split(a, b):
            out[name] += ns
    return dict(out)


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------
def _inside(spans, t0: float, t1: float):
    return [s for s in spans if t0 <= s[1] < t1]


def token_sync_idle(ctx) -> Optional[float]:
    """Percent of the traced window in which no device operation ran and
    the innermost open program span was ``nq.token_sync``."""
    program, r = program_spans(ctx), ctx.reduced
    if not any(s[0] == TOKEN_SYNC
               for s in (_inside(program, r["t0"], r["t1"]) if program
                         else ())):
        return None
    idle = idle_by_program_span({"ops": ctx.events["ops"],
                                 "program": program}, r)
    return 100.0 * idle.get(TOKEN_SYNC, 0.0) / (r["t1"] - r["t0"])


def switch_share(ctx, part: str) -> Optional[float]:
    """Time of the ``nq.page_in.<part>`` spans inside the harness's
    ``bench.switch`` spans that hold a page-in, over those switches'
    summed duration (what ``switch_ms`` times), in percent."""
    program = program_spans(ctx)
    if not program:
        return None
    r = ctx.reduced
    pages = sorted((a, a + d, n) for n, a, d, *_ in
                   _inside(program, r["t0"], r["t1"])
                   if n.startswith(PAGE_IN))
    starts = [a for a, _, _ in pages]
    switch_ns = part_ns = 0.0
    for n, a, d in _inside(ctx.events["spans"], r["t0"], r["t1"]):
        if n != SWITCH_SPAN:
            continue
        lo, hi = (bisect.bisect_left(starts, a),
                  bisect.bisect_left(starts, a + d))
        if lo == hi:
            continue
        switch_ns += d
        part_ns += sum(min(e, a + d) - s for s, e, name in pages[lo:hi]
                       if name == PAGE_IN + part)
    return 100.0 * part_ns / switch_ns if switch_ns > 0 else None
