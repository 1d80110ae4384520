"""Pluggable delta pagers (DESIGN.md Sec. 10).

Before the storage tier existed, every delta stream of every leaf was
resident in host memory forever and "paging" was ledger arithmetic.  A
:class:`DeltaPager` owns the NON-RESIDENT delta streams instead: the
:class:`~repro.core.switching.NestQuantStore` calls ``fetch(path, level)``
on upgrade (the returned packed words become resident in the serving
tree) and ``evict(path, level)`` on downgrade, so the ledger records
bytes that were *observed* to move through the pager - asserted equal to
the metadata-computed ``bytes(delta_k)``.

Shipped pagers:

* :class:`InMemoryPager` - every stream held in host memory (exactly the
  pre-storage-tier behavior; the default when a store is built from an
  in-memory tree).
* :class:`FilePager` - streams read on demand from a saved artifact
  (storage.artifact), CRC-checked per array.  ``available`` is true once
  the segment file exists on disk, which is how progressive delivery
  observes delta segments "arriving" on the device.
* :class:`ThrottledPager` - wraps any pager with a simulated link
  (bandwidth + latency), so switching/transport benchmarks measure real
  byte movement instead of assuming it is free.

Fault tolerance (DESIGN.md Sec. 12): real device links stall, corrupt,
and drop segments mid-switch, so the fetch path is hardened in layers:

* a typed error hierarchy - :class:`PagerError` /
  :class:`TransientPagerError` / :class:`CorruptStreamError` - lets
  callers distinguish retryable faults from fatal ones;
* :class:`ChaosPager` injects a seeded, deterministic fault schedule
  (transient fetch errors, CRC-corrupting bit flips, latency stalls,
  and :class:`Outage` windows) into any inner pager - the test/bench
  harness for everything below;
* :class:`ResilientPager` retries with exponential backoff + jitter
  under a :class:`RetryPolicy` (max attempts, per-attempt timeout,
  overall deadline), re-verifies the CRC of every fetched stream, keeps
  per-(path, level) :class:`StreamHealth` stats, and quarantines
  streams that fail repeatedly (``available`` turns False until the
  cooldown expires, so policies stop upgrading into a failing link).

Time is injectable everywhere (:class:`VirtualClock`): throttled-link
tests, retry/backoff schedules, and the chaos benchmark all run on a
deterministic virtual clock, instantly.
"""
from __future__ import annotations

import re
import time
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Protocol, Tuple, runtime_checkable

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from .artifact import ArtifactError


# ---------------------------------------------------------------------------
# error taxonomy (DESIGN.md Sec. 12)
# ---------------------------------------------------------------------------
class PagerError(RuntimeError):
    """A delta stream could not be delivered.  Base of the delivery
    fault taxonomy; subclasses say whether a retry can help."""


class TransientPagerError(PagerError):
    """Retryable delivery fault: a dropped connection, a timeout, an
    injected outage window.  The same fetch may succeed on retry."""


class CorruptStreamError(PagerError, ArtifactError):
    """The fetched bytes do not match their recorded CRC-32.  Retryable
    exactly once per attempt (a re-read may heal a link flip); repeated
    corruption means the source itself is bad.  Also an
    :class:`~repro.storage.artifact.ArtifactError` so pre-taxonomy
    callers catching that still work."""


# ---------------------------------------------------------------------------
# injectable clocks
# ---------------------------------------------------------------------------
class VirtualClock:
    """Deterministic clock: ``now()`` reads, ``sleep()`` advances
    instantly, ``set()`` jumps forward (never backward).  Calling the
    clock is the same as ``now()``.  Throttled links, retry backoff, and
    chaos schedules all take one of these so tests and benchmarks are
    deterministic and fast; :class:`WallClock` is the real-time drop-in."""

    def __init__(self, start_s: float = 0.0):
        self._now = float(start_s)
        self.slept_s = 0.0

    def now(self) -> float:
        return self._now

    __call__ = now

    def sleep(self, dt: float) -> None:
        dt = max(float(dt), 0.0)
        self._now += dt
        self.slept_s += dt

    def set(self, t: float) -> None:
        """Jump to absolute time ``t`` (monotone: never moves backward)."""
        self._now = max(self._now, float(t))


class WallClock:
    """Real time with the VirtualClock interface (``time.monotonic`` +
    ``time.sleep``)."""

    def __init__(self):
        self.slept_s = 0.0

    def now(self) -> float:
        return time.monotonic()

    __call__ = now

    def sleep(self, dt: float) -> None:
        dt = max(float(dt), 0.0)
        self.slept_s += dt
        if dt:
            time.sleep(dt)

    def set(self, t: float) -> None:
        pass                        # real time cannot be jumped


@runtime_checkable
class DeltaPager(Protocol):
    """Owner of the non-resident delta streams of one nested model.

    ``path`` is the pytree key string (``jax.tree_util.keystr``) of a
    nested leaf and ``level`` the delta-stream index (level k upgrades
    rung k to rung k+1)."""

    def fetch(self, path: str, level: int) -> jax.Array:
        """Return the packed int32 words of one delta stream."""
        ...

    def evict(self, path: str, level: int) -> None:
        """Drop a previously fetched stream from device/host residency."""
        ...

    def resident_bytes(self) -> int:
        """Bytes the pager itself currently holds in host memory."""
        ...

    def available(self, path: str, level: int) -> bool:
        """Whether ``fetch(path, level)`` would succeed right now."""
        ...

    # Pagers MAY also provide ``expected_crc(path, level) -> Optional[int]``
    # - the CRC-32 the stream's packed bytes should hash to.  It is not
    # part of the required protocol; ResilientPager probes for it with
    # getattr and skips re-verification when a pager cannot answer.


class InMemoryPager:
    """All delta streams pinned in host memory - the classic behavior.

    ``evict`` is a residency no-op (the bytes stay in host RAM, exactly
    as before the storage tier existed); ``fetch`` hands back the very
    same array object, so a page-out/page-in round trip is bit-identical
    by construction."""

    def __init__(self, streams: Optional[Dict[Tuple[str, int], jax.Array]] = None):
        self._streams: Dict[Tuple[str, int], jax.Array] = dict(streams or {})
        self._crc: Dict[Tuple[str, int], int] = {}

    @classmethod
    def from_tree(cls, nested_params) -> "InMemoryPager":
        """Harvest every present delta stream of a nested pytree."""
        from ..core.nesting import NestedTensor

        flat, _ = jax.tree_util.tree_flatten_with_path(
            nested_params, is_leaf=lambda x: isinstance(x, NestedTensor))
        streams = {}
        for path, leaf in flat:
            if not isinstance(leaf, NestedTensor):
                continue
            key = jax.tree_util.keystr(path)
            for i, d in enumerate(leaf.deltas):
                if d is not None:
                    streams[(key, i)] = d
        return cls(streams)

    def fetch(self, path: str, level: int) -> jax.Array:
        try:
            return self._streams[(path, level)]
        except KeyError:
            raise KeyError(
                f"no delta stream (level {level}) for {path!r} in the "
                "in-memory pager - was the store built from a base-only "
                "tree without a FilePager?") from None

    def put(self, path: str, level: int, words: jax.Array) -> None:
        """Register a stream produced at runtime (the nested KV cache
        deposits freshly quantized page deltas here, so later rung
        upgrades re-fetch them through the same protocol as weights)."""
        self._streams[(path, level)] = words
        self._crc.pop((path, level), None)

    def discard(self, path: str, level: int) -> None:
        """Forget a stream entirely (page retirement - unlike ``evict``,
        which keeps the pristine host copy for later re-fetch)."""
        self._streams.pop((path, level), None)
        self._crc.pop((path, level), None)

    def evict(self, path: str, level: int) -> None:
        pass                        # host copy stays: the classic behavior

    def resident_bytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize
                   for a in self._streams.values())

    def available(self, path: str, level: int) -> bool:
        return (path, level) in self._streams

    def expected_crc(self, path: str, level: int) -> Optional[int]:
        """CRC-32 of the pristine host copy (computed once, cached)."""
        key = (path, level)
        if key not in self._streams:
            return None
        if key not in self._crc:
            self._crc[key] = zlib.crc32(
                np.ascontiguousarray(np.asarray(self._streams[key])).tobytes())
        return self._crc[key]


class FilePager:
    """Delta streams read on demand from a saved artifact directory.

    Each ``fetch`` reads exactly one array's byte range from the delta
    segment file (CRC-checked); ``resident_bytes`` counts only the
    streams currently fetched and not yet evicted.  A segment file that
    does not exist yet is simply *not available* - progressive delivery
    (``ServeEngine.poll_delivery``) upgrades as files arrive."""

    def __init__(self, artifact, verify: bool = True):
        from .artifact import Artifact, open_artifact
        self.artifact: Artifact = (artifact if isinstance(artifact, Artifact)
                                   else open_artifact(artifact))
        self.verify = verify
        self._resident: Dict[Tuple[str, int], int] = {}
        self._landed: set = set()       # segments seen on disk (stay there)

    def _spec(self, path: str, level: int) -> dict:
        entry = self.artifact.leaf(path)
        deltas = entry["arrays"].get("deltas", ())
        if not 0 <= level < len(deltas):
            raise KeyError(f"{path!r} has no delta level {level} "
                           f"({len(deltas)} streams in the artifact)")
        return deltas[level]

    def fetch(self, path: str, level: int) -> jax.Array:
        spec = self._spec(path, level)
        try:
            arr = self.artifact.read_array(spec, verify=self.verify)
        except CorruptStreamError as e:
            # the artifact layer knows the byte range; this layer knows
            # WHOSE stream it is - recovery (and the operator reading the
            # log) needs both
            raise CorruptStreamError(
                f"delta stream corrupted: leaf {path!r} level {level}: "
                f"{e}") from e
        self._resident[(path, level)] = spec["nbytes"]
        with obs.span("page_in.put", nbytes=spec["nbytes"]):
            return jnp.asarray(arr)

    def expected_crc(self, path: str, level: int) -> Optional[int]:
        """The manifest's recorded CRC-32 for one delta stream."""
        try:
            return int(self._spec(path, level)["crc32"])
        except KeyError:
            return None

    def evict(self, path: str, level: int) -> None:
        self._resident.pop((path, level), None)

    def resident_bytes(self) -> int:
        return sum(self._resident.values())

    def available(self, path: str, level: int) -> bool:
        try:
            spec = self._spec(path, level)
        except KeyError:
            return False
        # availability is a SEGMENT property and segments never un-arrive,
        # so cache positives: max_available_rung probes every (leaf, level)
        # on the serving path and must not stat the same file per leaf
        seg = spec["segment"]
        if seg in self._landed:
            return True
        if self.artifact.segment_available(seg):
            self._landed.add(seg)
            return True
        return False


class LinkBudget:
    """ONE physical link shared by any number of pagers (DESIGN.md
    Sec. 14).

    Before this existed, two :class:`ThrottledPager`\\ s "over the same
    link" each accounted bandwidth independently - two concurrent fetches
    of ``B`` bytes both finished after ``B/bw`` seconds, as if the link
    doubled.  A LinkBudget serializes instead: it remembers when the link
    frees up (:attr:`busy_until`), and every transfer starts at
    ``max(now, busy_until)``.  The second of two concurrent fetches waits
    for the first, exactly like frames on a wire.

    ``reserve(nbytes, now)`` books one transfer and returns
    ``(start_s, finish_s, total_s)`` where ``total_s = finish_s - now``
    is what the CALLER experienced (queueing + latency + transfer).
    Aggregate accounting: :attr:`bytes_moved`, :attr:`busy_s` (seconds
    the wire itself carried bits), :attr:`queued_s` (seconds callers
    spent waiting behind other transfers)."""

    def __init__(self, bandwidth_bytes_per_s: float = 12.5e6,
                 latency_s: float = 0.0):
        if bandwidth_bytes_per_s <= 0:
            raise ValueError("bandwidth must be > 0")
        self.bandwidth_bytes_per_s = float(bandwidth_bytes_per_s)
        self.latency_s = float(latency_s)
        self.busy_until = 0.0
        self.bytes_moved = 0
        self.busy_s = 0.0
        self.queued_s = 0.0
        self.transfers = 0

    def reserve(self, nbytes: int, now: float) -> Tuple[float, float, float]:
        start = max(float(now), self.busy_until)
        hold = self.latency_s + nbytes / self.bandwidth_bytes_per_s
        finish = start + hold
        self.busy_until = finish
        self.bytes_moved += int(nbytes)
        self.busy_s += hold
        self.queued_s += start - float(now)
        self.transfers += 1
        return start, finish, finish - float(now)


class ThrottledPager:
    """Simulated-link wrapper: every fetch pays ``latency_s`` plus
    ``nbytes / bandwidth_bytes_per_s`` of virtual transfer time, recorded
    in :attr:`transfers` / :attr:`simulated_seconds` (and slept on the
    injected ``clock`` when ``sleep=True``).  Evictions are free -
    dropping residency moves no bytes over the link.  Lets
    switching-overhead benchmarks report byte movement on a concrete
    link instead of assuming it is free.

    ``clock`` defaults to a :class:`WallClock`; pass a
    :class:`VirtualClock` and throttled-link tests (and ``bench_chaos``)
    run the same schedule deterministically, without real sleeping.

    ``link`` shares ONE :class:`LinkBudget` between several pagers: each
    fetch reserves the wire through the shared budget, so concurrent
    fetches SERIALIZE (the second waits out the first's transfer, on the
    common clock the budget's timeline is read from) instead of each
    pretending it owns the full bandwidth.  The fleet distribution tier
    (DESIGN.md Sec. 14) uses this for the shared origin->edge uplink.
    Without ``link`` the pager keeps the classic single-tenant timing:
    every fetch is charged its standalone ``latency + nbytes/bandwidth``
    hold, never queueing behind its own earlier transfers (unchanged from
    the pre-LinkBudget implementation)."""

    def __init__(self, inner: DeltaPager,
                 bandwidth_bytes_per_s: float = 12.5e6,   # 100 Mbit/s
                 latency_s: float = 0.0, sleep: bool = False, clock=None,
                 link: Optional[LinkBudget] = None):
        if link is not None:
            bandwidth_bytes_per_s = link.bandwidth_bytes_per_s
            latency_s = link.latency_s
        elif bandwidth_bytes_per_s <= 0:
            raise ValueError("bandwidth must be > 0")
        self.link = link
        self.inner = inner
        self.bandwidth_bytes_per_s = float(bandwidth_bytes_per_s)
        self.latency_s = float(latency_s)
        self.sleep = sleep
        self.clock = clock if clock is not None else WallClock()
        self.bytes_moved = 0
        self.simulated_seconds = 0.0
        # (path, level, nbytes, seconds) per fetch, arrival order
        self.transfers: List[Tuple[str, int, int, float]] = []

    def fetch(self, path: str, level: int) -> jax.Array:
        arr = self.inner.fetch(path, level)
        nb = int(arr.size) * arr.dtype.itemsize
        if self.link is not None:
            # shared wire: dt is the caller-observed seconds, including
            # time queued behind whatever other pagers put on the link
            _, _, dt = self.link.reserve(nb, self.clock.now())
        else:
            dt = self.latency_s + nb / self.bandwidth_bytes_per_s
        self.bytes_moved += nb
        self.simulated_seconds += dt
        self.transfers.append((path, level, nb, dt))
        if self.sleep:
            self.clock.sleep(dt)
        return arr

    def evict(self, path: str, level: int) -> None:
        self.inner.evict(path, level)

    def resident_bytes(self) -> int:
        return self.inner.resident_bytes()

    def available(self, path: str, level: int) -> bool:
        return self.inner.available(path, level)

    def expected_crc(self, path: str, level: int) -> Optional[int]:
        fn = getattr(self.inner, "expected_crc", None)
        return fn(path, level) if fn is not None else None


# ---------------------------------------------------------------------------
# fault injection (DESIGN.md Sec. 12)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Outage:
    """A segment-unavailable window on the chaos clock: every matching
    (path, level) is unfetchable - ``available`` False, ``fetch`` raising
    :class:`TransientPagerError` - while ``start_s <= now < end_s``.

    ``level=None`` matches every delta level; ``pattern`` is an
    ``re.search`` over the leaf path (empty = all leaves).  One Outage
    over a whole delta level is the simulated version of "the CDN edge
    lost delta_k.seg for a while"."""
    start_s: float
    end_s: float
    level: Optional[int] = None
    pattern: str = ""

    def __post_init__(self):
        if not 0 <= self.start_s < self.end_s:
            raise ValueError(f"need 0 <= start_s < end_s, got "
                             f"[{self.start_s}, {self.end_s})")
        re.compile(self.pattern)

    def covers(self, path: str, level: int, now: float) -> bool:
        return (self.start_s <= now < self.end_s
                and (self.level is None or self.level == level)
                and (not self.pattern or re.search(self.pattern, path)
                     is not None))


class ChaosPager:
    """Seeded, deterministic fault injection over any inner pager.

    Four fault families, all drawn from one ``seed`` so a run replays
    bit-for-bit (:attr:`faults` counts what actually fired):

    * ``p_transient`` - the fetch raises :class:`TransientPagerError`
      before touching the inner pager (a dropped connection);
    * ``p_corrupt``  - the fetch succeeds but ONE bit of a copy of the
      returned words is flipped (a link flip; the inner pager's own copy
      stays pristine, so a retry can heal it);
    * ``p_stall``    - the fetch first stalls ``stall_s`` on the chaos
      clock (a latency spike; with a per-attempt timeout downstream this
      becomes a timeout fault);
    * ``outages``    - :class:`Outage` windows during which matching
      streams are unavailable (``available`` goes False, fetches fail).

    The clock defaults to a fresh :class:`VirtualClock`; share one with
    the Scheduler/ResilientPager so outage windows and backoff live on
    the same timeline."""

    def __init__(self, inner: DeltaPager, *, seed: int = 0,
                 p_transient: float = 0.0, p_corrupt: float = 0.0,
                 p_stall: float = 0.0, stall_s: float = 0.05,
                 outages: Tuple[Outage, ...] = (), clock=None):
        for name, p in (("p_transient", p_transient),
                        ("p_corrupt", p_corrupt), ("p_stall", p_stall)):
            if not 0.0 <= p <= 1.0:
                raise ValueError(f"{name} must be in [0, 1], got {p}")
        self.inner = inner
        self.p_transient = float(p_transient)
        self.p_corrupt = float(p_corrupt)
        self.p_stall = float(p_stall)
        self.stall_s = float(stall_s)
        self.outages = tuple(outages)
        self.clock = clock if clock is not None else VirtualClock()
        self._rng = np.random.default_rng(seed)
        self.fetches = 0
        self.faults: Dict[str, int] = {"transient": 0, "corrupt": 0,
                                       "stall": 0, "outage": 0}

    def _active_outage(self, path: str, level: int) -> Optional[Outage]:
        now = self.clock.now()
        for o in self.outages:
            if o.covers(path, level, now):
                return o
        return None

    def fetch(self, path: str, level: int) -> jax.Array:
        self.fetches += 1
        out = self._active_outage(path, level)
        if out is not None:
            self.faults["outage"] += 1
            raise TransientPagerError(
                f"injected outage: {path!r} delta {level} unavailable "
                f"until t={out.end_s:g}s (now t={self.clock.now():g}s)")
        # one 3-draw vector per fetch: the schedule depends only on the
        # seed and the fetch order, never on which faults fired
        stall, transient, corrupt = self._rng.random(3)
        if stall < self.p_stall:
            self.faults["stall"] += 1
            self.clock.sleep(self.stall_s)
        if transient < self.p_transient:
            self.faults["transient"] += 1
            raise TransientPagerError(
                f"injected transient fetch failure: {path!r} delta {level}")
        words = self.inner.fetch(path, level)
        if corrupt < self.p_corrupt:
            self.faults["corrupt"] += 1
            raw = np.array(words)             # copy: never corrupt the source
            # flip one bit of the raw byte buffer (a uint8 view is
            # dtype-agnostic; shifting within the element dtype would
            # overflow signed types at the sign bit)
            flat = raw.reshape(-1).view(np.uint8)
            i = int(self._rng.integers(flat.size))
            flat[i] ^= np.uint8(1 << int(self._rng.integers(8)))
            return jnp.asarray(raw)
        return words

    def evict(self, path: str, level: int) -> None:
        self.inner.evict(path, level)

    def resident_bytes(self) -> int:
        return self.inner.resident_bytes()

    def available(self, path: str, level: int) -> bool:
        if self._active_outage(path, level) is not None:
            return False
        return self.inner.available(path, level)

    def expected_crc(self, path: str, level: int) -> Optional[int]:
        fn = getattr(self.inner, "expected_crc", None)
        return fn(path, level) if fn is not None else None


# ---------------------------------------------------------------------------
# hardened fetch path (DESIGN.md Sec. 12)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class RetryPolicy:
    """How hard :class:`ResilientPager` tries before giving up on a
    stream.  Backoff for attempt ``a`` (0-based) is
    ``backoff_base_s * backoff_factor**a``, jittered by a seeded
    ``+/- jitter`` fraction; ``fetch_timeout_s`` bounds ONE attempt on
    the clock (stalls surface as timeouts), ``deadline_s`` bounds the
    whole fetch call including backoff sleeps."""
    max_attempts: int = 4
    backoff_base_s: float = 0.05
    backoff_factor: float = 2.0
    jitter: float = 0.25
    fetch_timeout_s: Optional[float] = None
    deadline_s: Optional[float] = None
    verify_crc: bool = True
    quarantine_after: int = 3         # consecutive failures -> quarantine
    quarantine_s: float = 60.0        # cooldown before re-probing

    def __post_init__(self):
        if self.max_attempts < 1:
            raise ValueError(f"max_attempts must be >= 1, "
                             f"got {self.max_attempts}")
        if self.backoff_base_s < 0 or self.backoff_factor < 1:
            raise ValueError("need backoff_base_s >= 0 and "
                             "backoff_factor >= 1")
        if not 0.0 <= self.jitter <= 1.0:
            raise ValueError(f"jitter must be in [0, 1], got {self.jitter}")
        if self.quarantine_after < 1 or self.quarantine_s < 0:
            raise ValueError("need quarantine_after >= 1 and "
                             "quarantine_s >= 0")


@dataclass
class StreamHealth:
    """Per-(path, level) delivery record kept by ResilientPager."""
    attempts: int = 0
    failures: int = 0
    consecutive: int = 0              # failures since the last success
    corrupt: int = 0
    timeouts: int = 0
    quarantined_until: float = field(default=float("-inf"))
    last_error: str = ""


class ResilientPager:
    """Retry/verify/quarantine wrapper: the hardened fetch path.

    Every fetch runs up to ``policy.max_attempts`` attempts with
    exponential backoff + seeded jitter between them, treats
    :class:`TransientPagerError` and :class:`CorruptStreamError` as
    retryable, re-verifies the CRC-32 of every fetched stream against
    the inner pager's ``expected_crc`` (so corruption injected - or
    real - BELOW the CRC check still cannot reach the serving tree), and
    converts attempts that overrun ``fetch_timeout_s`` on the clock into
    transient faults.  A stream whose consecutive failures reach
    ``quarantine_after`` is quarantined: its ``available`` reads False
    (policies stop upgrading into it, the store's max_available_rung
    drops) until ``quarantine_s`` of cooldown passes, after which the
    next probe retries for real.  :attr:`health` holds the
    per-(path, level) :class:`StreamHealth` stats; failed attempts evict
    whatever the inner pager had provisionally delivered, so pager
    residency accounting survives every fault."""

    def __init__(self, inner: DeltaPager,
                 policy: Optional[RetryPolicy] = None, *,
                 seed: int = 0, clock=None):
        self.inner = inner
        self.policy = policy if policy is not None else RetryPolicy()
        # share the fault injector's timeline unless told otherwise:
        # backoff sleeps then tick outage windows toward expiry
        self.clock = (clock if clock is not None
                      else getattr(inner, "clock", None) or VirtualClock())
        self._rng = np.random.default_rng(seed)
        self.health: Dict[Tuple[str, int], StreamHealth] = {}
        self.retries = 0
        self.quarantines = 0

    def _health(self, path: str, level: int) -> StreamHealth:
        return self.health.setdefault((path, level), StreamHealth())

    def quarantined(self) -> Dict[Tuple[str, int], float]:
        """Streams currently in quarantine -> cooldown expiry time."""
        now = self.clock.now()
        return {k: h.quarantined_until for k, h in self.health.items()
                if h.quarantined_until > now}

    def _verified(self, path: str, level: int, words: jax.Array) -> jax.Array:
        if not self.policy.verify_crc:
            return words
        fn = getattr(self.inner, "expected_crc", None)
        want = fn(path, level) if fn is not None else None
        if want is None:
            return words
        got = zlib.crc32(np.ascontiguousarray(np.asarray(words)).tobytes())
        if got != want:
            raise CorruptStreamError(
                f"delta stream corrupted: leaf {path!r} level {level}: "
                f"CRC-32 re-verification failed (expected {want:#010x}, "
                f"observed {got:#010x})")
        return words

    def fetch(self, path: str, level: int) -> jax.Array:
        pol, h = self.policy, self._health(path, level)
        now = self.clock.now()
        if h.quarantined_until > now:
            raise TransientPagerError(
                f"{path!r} delta {level} quarantined until "
                f"t={h.quarantined_until:g}s (now t={now:g}s, "
                f"{h.consecutive} consecutive failures)")
        t_start = now
        last: Optional[PagerError] = None
        for attempt in range(pol.max_attempts):
            t0 = self.clock.now()
            h.attempts += 1
            try:
                words = self.inner.fetch(path, level)
                if (pol.fetch_timeout_s is not None
                        and self.clock.now() - t0 > pol.fetch_timeout_s):
                    h.timeouts += 1
                    self.inner.evict(path, level)
                    raise TransientPagerError(
                        f"fetch of {path!r} delta {level} took "
                        f"{self.clock.now() - t0:g}s > per-attempt timeout "
                        f"{pol.fetch_timeout_s:g}s")
                try:
                    words = self._verified(path, level, words)
                except CorruptStreamError:
                    self.inner.evict(path, level)
                    raise
                h.consecutive = 0
                return words
            except (TransientPagerError, CorruptStreamError) as e:
                h.failures += 1
                h.consecutive += 1
                h.last_error = str(e)
                if isinstance(e, CorruptStreamError):
                    h.corrupt += 1
                last = e
                if h.consecutive >= pol.quarantine_after:
                    h.quarantined_until = self.clock.now() + pol.quarantine_s
                    self.quarantines += 1
                    break             # a failing stream earns no more retries
                if attempt + 1 >= pol.max_attempts:
                    break
                back = (pol.backoff_base_s * pol.backoff_factor ** attempt
                        * (1.0 + pol.jitter
                           * (2.0 * float(self._rng.random()) - 1.0)))
                if (pol.deadline_s is not None
                        and self.clock.now() + back - t_start
                        > pol.deadline_s):
                    break             # the deadline outlaws another attempt
                self.retries += 1
                self.clock.sleep(back)
        assert last is not None
        raise last

    def evict(self, path: str, level: int) -> None:
        self.inner.evict(path, level)

    def resident_bytes(self) -> int:
        return self.inner.resident_bytes()

    def available(self, path: str, level: int) -> bool:
        h = self.health.get((path, level))
        if h is not None and h.quarantined_until > self.clock.now():
            return False
        return self.inner.available(path, level)

    def expected_crc(self, path: str, level: int) -> Optional[int]:
        fn = getattr(self.inner, "expected_crc", None)
        return fn(path, level) if fn is not None else None
