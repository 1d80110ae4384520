"""One generator for every traffic mix; a mix is a JSON file of parameters.

Every seed of a mix gets the same work: prompt lengths, answer lengths
and arrival times are drawn once from the mix's own ``shape_seed``, and
``--seed`` draws the prompt tokens (and, in the closed loop, the order
of each batch's requests).  Runs with different seeds therefore differ
in content, not in how much there is to do or when it arrives.

Keys of a mix file:

- ``loop``: ``"open"`` (arrivals at ``rate_per_s``, timed from when each
  is due) or ``"closed"`` (``max_batch`` clients that each send their
  next request when the last reply comes; one batch of the pool each).
- ``prompt_tokens`` / ``answer_tokens``: lognormal ``median`` and
  ``sigma``, clipped to ``[min, max]``.  Prompt lengths are then rounded
  up to the nearest of ``buckets`` (the largest bucket caps them), so a
  batch's padded length is always a bucket.
- ``batching``: ``"same_length"`` (a batch holds one prompt length: the
  open loop admits by it, and each closed-loop batch draws one length
  for all its rows) or ``"fifo"`` (lengths mix in a batch, which the
  engine then left-pads).
- ``max_batch``, ``max_len``, ``admit_wait_s``: the engine's batch and
  cache sizes, and how long a partial batch waits for more arrivals.
- ``rung_cycle``: ladder rungs, one per batch in turn, or null to hold
  the top rung.
- ``trace``: when the ``--trace 1`` run starts and stops its profile,
  both at batch boundaries (``start_s`` and ``seconds`` after the
  window opens).
- ``check_per_rung``: requests per served rung that the correctness
  check scores against the reference.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np


@dataclass
class Spec:
    """One request as the generator makes it."""
    uid: int
    due_s: float            # offset from the window's start (open loop)
    prompt: np.ndarray      # int32 tokens
    max_new: int


def load(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def lengths(dist: Dict, n: int, rng: np.random.Generator,
            buckets: Optional[List[int]] = None) -> np.ndarray:
    """n lognormal lengths, clipped, and rounded up to ``buckets``."""
    x = np.ceil(rng.lognormal(np.log(dist["median"]), dist["sigma"], n))
    x = np.clip(x, dist["min"], dist["max"]).astype(np.int64)
    if buckets:
        b = np.asarray(sorted(buckets))
        x = b[np.minimum(np.searchsorted(b, x), len(b) - 1)]
    return x


def _prompt(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=int(n)).astype(np.int32)


def open_loop(mix: Dict, seed: int, seconds: float, vocab: int,
              rate: Optional[float] = None) -> List[Spec]:
    """round(rate * seconds) requests due inside [0, seconds).

    Arrival times and lengths are one fixed draw from ``shape_seed`` (a
    Poisson process given its count: that many uniform points), the same
    for every seed, so that batches form alike and runs measure the same
    work; ``seed`` draws the prompt tokens."""
    rate = mix["rate_per_s"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    shape = np.random.default_rng(mix["shape_seed"])
    due = np.sort(shape.uniform(0.0, seconds, n))
    plen = lengths(mix["prompt_tokens"], n, shape, mix["buckets"])
    alen = lengths(mix["answer_tokens"], n, shape)
    rng = np.random.default_rng(seed)
    return [Spec(i, float(due[i]), _prompt(rng, plen[i], vocab), int(alen[i]))
            for i in range(n)]


def closed_batch(mix: Dict, seed: int, index: int, vocab: int) -> List[Spec]:
    """Batch ``index`` of the closed loop: the same lengths for every
    seed (drawn from ``shape_seed`` and ``index``), in the seed's order."""
    B = mix["max_batch"]
    shape = np.random.default_rng([mix["shape_seed"], index])
    plen = lengths(mix["prompt_tokens"], B, shape, mix["buckets"])
    if mix["batching"] == "same_length":
        plen[:] = plen[0]
    alen = lengths(mix["answer_tokens"], B, shape)
    rng = np.random.default_rng([seed, index])
    plen, alen = rng.permutation(plen), rng.permutation(alen)
    return [Spec(index * B + i, 0.0, _prompt(rng, plen[i], vocab),
                 int(alen[i])) for i in range(B)]
