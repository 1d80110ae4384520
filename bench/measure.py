"""End-to-end metrics of one window, from the token stamps and switch times.

Every number is taken over all the work of the window [t0, t1): a tail
is the tail of every request due in it, a rate is over its whole
length.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def p95(values: List[float]) -> Optional[float]:
    return float(np.percentile(values, 95)) if values else None


def ttft_ms(w) -> List[float]:
    """Per request due in the window: first token stamp minus due time;
    a request with no token by the window's end counts until its end."""
    out = []
    for r in w.requests:
        if w.t0 <= r["due"] < w.t1:
            st = r["req"].out_tokens.stamps
            first = st[0] if st and st[0] < w.t1 else w.t1
            out.append(1e3 * (first - r["due"]))
    return out


def itl_ms(w) -> List[float]:
    """Every gap between consecutive token stamps of one request, both
    inside the window, all requests pooled."""
    out = []
    for r in w.requests:
        st = np.asarray(r["req"].out_tokens.stamps)
        st = st[(st >= w.t0) & (st < w.t1)]
        out += list(1e3 * np.diff(st))
    return out


def out_tokens(w) -> int:
    return sum(int(np.sum((np.asarray(r["req"].out_tokens.stamps) >= w.t0)
                          & (np.asarray(r["req"].out_tokens.stamps) < w.t1)))
               for r in w.requests)


def switches_in(w) -> List[Dict]:
    return [s for s in w.switches if w.t0 <= s["start"] < w.t1]


def upgrades_in(w) -> List[Dict]:
    """The window's switches that paged bytes in."""
    return [s for s in switches_in(w) if any(e[2] > 0 for e in s["events"])]


def end_to_end(w, setup_s: float) -> Dict[str, Optional[float]]:
    """Every end-to-end metric this window can give, by name."""
    up = upgrades_in(w)
    return {
        "ttft_p95_ms": p95(ttft_ms(w)),
        "itl_p95_ms": p95(itl_ms(w)),
        "out_tok_s": out_tokens(w) / (w.t1 - w.t0),
        "switch_ms": (1e3 * sum(s["seconds"] for s in up) / len(up)
                      if up else None),
        "setup_s": setup_s,
    }


def idle_slot_share(w, B: int) -> Optional[float]:
    """Percent of batch rows x decode steps, over the batches started in
    the window, that stamped no real token (filler rows, and rows whose
    answer ended before the batch's longest)."""
    slots = real = 0
    for b in w.batches:
        if w.t0 <= b.start < w.t1:
            slots += B * b.steps
            real += sum(min(m, b.steps) for _, m in b.rows)
    return 100.0 * (1.0 - real / slots) if slots else None


def page_in_gbps(w) -> Optional[float]:
    """Bytes the ledger paged in on upgrades over the wall time of those
    switches, in GB/s."""
    up = upgrades_in(w)
    secs = sum(s["seconds"] for s in up)
    nbytes = sum(e[2] for s in up for e in s["events"])
    return nbytes / secs / 1e9 if secs > 0 else None
