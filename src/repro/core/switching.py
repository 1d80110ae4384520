"""On-device model switching runtime (paper Sec. 3.3, Table 11),
generalized to a K-rung ladder state machine (DESIGN.md Sec. 8) with
per-leaf rung assignments (DESIGN.md Sec. 9).

A :class:`NestQuantStore` owns the packed decomposed weights of one model.
On TPU the paper's memory page-in/page-out maps to HBM residency (see
DESIGN.md Sec. 3): the base stream ``w_base`` is always resident; the
delta streams are paged in from host/storage on upgrade and dropped on
downgrade, ONE ADJACENT RUNG AT A TIME - moving from rung k to rung k+1
touches exactly bytes(delta_k), nothing else.

NON-RESIDENT delta streams live in a pluggable
:class:`~repro.storage.pager.DeltaPager` (DESIGN.md Sec. 10), not in the
serving tree: an upgrade calls ``pager.fetch`` and splices the returned
packed words into the leaf, a downgrade calls ``pager.evict`` and drops
them, and the ledger records the bytes OBSERVED to move - which the
store asserts equal the metadata-computed ``bytes(delta_k)``.  The
default :class:`~repro.storage.pager.InMemoryPager` reproduces the
classic everything-host-resident behavior bit-for-bit; a
:class:`~repro.storage.pager.FilePager` pages from an on-disk artifact.

The ledger generalizes the paper's Table 11 accounting to K rungs:
  * NestQuant upgrade k->k+1:    page-in  = bytes(delta_k), page-out = 0
  * NestQuant downgrade k+1->k:  page-in  = 0,  page-out = bytes(delta_k)
  * diverse-bitwidths switch r->r': page-in = bytes(INT-bits[r'] model),
                                    page-out = bytes(INT-bits[r] model)
The paper's two-level nesting is the 2-rung special case ('part' = rung 0,
'full' = the top rung).

Rung state is tracked PER LEAF: a :class:`RungAssignment` maps pytree
paths to rungs and :meth:`NestQuantStore.apply` ledgers each leaf's delta
page-ins/outs exactly; the classic whole-tree ``to_rung`` is the uniform
special case.  Per-layer recipes (core.recipe) produce trees whose leaves
carry DIFFERENT ladders, so rung indices are clamped to each leaf's own
ladder top.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from . import packing
from .decompose import normalize_bits
from .nesting import (NestedTensor, check_rung, materialize, mode_to_rung,
                      rung_to_mode, set_tree_rung, tree_bytes,
                      tree_ladder_bytes, tree_num_rungs)


@dataclass
class SwitchLedger:
    page_in_bytes: int = 0
    page_out_bytes: int = 0
    switches: int = 0
    # (from_rung, to_rung, page_in, page_out) per rung move; whole-tree
    # walks record one event per adjacent step, per-leaf applies one event
    # per moved leaf (possibly spanning several rungs, bytes still exact)
    events: List[Tuple[int, int, int, int]] = field(default_factory=list)

    def record(self, page_in: int, page_out: int, *,
               from_rung: int, to_rung: int):
        """Every caller must say WHICH move it is logging - defaulted
        from/to rungs silently produced bogus 0->0 events."""
        self.page_in_bytes += page_in
        self.page_out_bytes += page_out
        self.switches += 1
        self.events.append((from_rung, to_rung, page_in, page_out))


# ---------------------------------------------------------------------------
# Per-leaf rung assignments
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class RungAssignment:
    """Maps nested-leaf paths to target rungs (DESIGN.md Sec. 9).

    Resolution order per leaf: ``exact`` path entry -> first matching
    ``overrides`` regex (``re.search`` on the keystr) -> ``default``.
    Entries accept anything :func:`mode_to_rung` does (int, 'part',
    'full', 'rungK'); resolved rungs are clamped to each leaf's own
    ladder top, since per-layer recipes mix ladder depths."""
    default: object = -1
    overrides: Tuple[Tuple[str, object], ...] = ()
    exact: Tuple[Tuple[str, object], ...] = ()

    def __post_init__(self):
        object.__setattr__(self, "overrides", tuple(
            (str(p), r) for p, r in self.overrides))
        object.__setattr__(self, "exact", tuple(
            (str(p), r) for p, r in self.exact))
        for pat, _ in self.overrides:
            re.compile(pat)
        object.__setattr__(self, "_exact_map", dict(self.exact))

    @classmethod
    def uniform(cls, rung) -> "RungAssignment":
        return cls(default=rung)

    @property
    def is_uniform(self) -> bool:
        return not self.overrides and not self.exact

    def rung_for(self, path: str, tree_rungs: int, leaf_rungs: int) -> int:
        want = self._exact_map.get(path)
        if want is None:
            for pat, r in self.overrides:
                if re.search(pat, path):
                    want = r
                    break
            else:
                want = self.default
        return min(mode_to_rung(want, tree_rungs), leaf_rungs - 1)


def diverse_bitwidth_bytes(nested_params, n: int, h: int) -> Dict[str, int]:
    """Storage of the baseline: two separate packed PTQ models (INT-n + INT-h)."""
    d = diverse_ladder_bytes(nested_params, (h, n))
    return {"int_n": d["models"][1], "int_h": d["models"][0],
            "total": d["total"]}


def diverse_ladder_bytes(nested_params, bits: Sequence[int]) -> Dict[str, object]:
    """Storage of the K-rung baseline: one separate packed PTQ model per
    bitwidth in ``bits`` (the AdaBits-style model zoo NestQuant replaces).

    Returns {'bits': ascending tuple, 'models': [bytes per bitwidth], 'total'}."""
    bits = normalize_bits(bits)
    models = [0] * len(bits)
    for leaf in jax.tree_util.tree_leaves(
            nested_params, is_leaf=lambda x: isinstance(x, NestedTensor)):
        if isinstance(leaf, NestedTensor):
            K = leaf.shape[-2]
            rest = 1
            for d in leaf.shape[:-2] + leaf.shape[-1:]:
                rest *= d
            for r, b in enumerate(bits):
                models[r] += packing.packed_rows(K, b) * rest * 4
    return {"bits": bits, "models": models, "total": sum(models)}


@dataclass
class NestQuantStore:
    """Holds a nested model + the rung-switching state machine.

    ``mode`` accepts the two-level-era strings ('part' | 'full'), a
    'rungK' string, or an int rung index; internally the store tracks a
    rung PER LEAF plus the tree-level ``rung`` summary (when leaves
    disagree the store is *mixed*: ``mode`` reads 'mixed' and ``rung`` is
    the minimum resident rung, the guaranteed floor).  ``n``/``h``
    default to the tree's own ladder extremes (top/base bitwidths); pass
    them only to pin a different 2-level diverse baseline."""
    nested_params: object
    n: Optional[int] = None
    h: Optional[int] = None
    mode: object = "part"                  # initial rung (str or int)
    dtype: object = jnp.bfloat16
    ledger: SwitchLedger = field(default_factory=SwitchLedger)
    pager: object = None                   # DeltaPager; None -> InMemoryPager

    def __post_init__(self):
        self.num_rungs = tree_num_rungs(self.nested_params)
        self.rung = mode_to_rung(self.mode, self.num_rungs)
        self.mode = rung_to_mode(self.rung, self.num_rungs)
        # byte accounting is metadata-computed (shape/bits/block), so it is
        # exact whatever the current residency; walk the tree ONCE
        # (ensure_mode consults these totals on every request batch)
        self._ladder_bytes = tree_ladder_bytes(self.nested_params)
        self._bytes = tree_bytes(self.nested_params)
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.nested_params, is_leaf=lambda x: isinstance(x, NestedTensor))
        self._treedef = treedef
        self._flat = [leaf for _, leaf in flat]
        self._leaf_paths: List[str] = []
        self._leaf_index: Dict[str, int] = {}
        self._leaf_streams: Dict[str, Tuple[int, ...]] = {}
        self._leaf_bits: Dict[str, Tuple[int, ...]] = {}
        self._leaf_rungs: Dict[str, int] = {}
        for i, (path, leaf) in enumerate(flat):
            if not isinstance(leaf, NestedTensor):
                continue
            key = jax.tree_util.keystr(path)
            self._leaf_paths.append(key)
            self._leaf_index[key] = i
            self._leaf_streams[key] = leaf.stream_nbytes()
            self._leaf_bits[key] = leaf.bits
            self._leaf_rungs[key] = min(self.rung, leaf.num_rungs - 1)
        bits = list(self._leaf_bits.values())
        if self.n is None:
            self.n = max((b[-1] for b in bits), default=8)
        if self.h is None:
            self.h = min((b[0] for b in bits), default=4)
        # residency tier: the pager owns every non-resident delta stream.
        # Default = InMemoryPager harvested from the input tree (classic
        # everything-in-host-memory behavior); a FilePager pages from an
        # on-disk artifact instead.  Establishing the INITIAL residency is
        # not a switch: no ledger events.
        if self.pager is None:
            from ..storage.pager import InMemoryPager
            self.pager = InMemoryPager.from_tree(self.nested_params)
        for key in self._leaf_paths:
            self._page_leaf(key, self._leaf_rungs[key])
        self._rebuild_tree()

    # -- residency plumbing ----------------------------------------------
    def _rebuild_tree(self):
        self.nested_params = jax.tree_util.tree_unflatten(
            self._treedef, self._flat)

    def _page_leaf(self, path: str, target: int) -> Tuple[int, int]:
        """Move ONE leaf's residency to ``target`` delta levels through
        the pager, one adjacent level at a time.  Returns the OBSERVED
        (page_in, page_out) bytes, each level asserted equal to the
        metadata-computed stream size - the executable version of the
        Table-11 claim that a rung move touches exactly bytes(delta_k).

        ATOMIC per leaf: a failed fetch (e.g. a delta segment not yet
        delivered) evicts anything fetched so far and leaves the leaf,
        the rung map, and the pager accounting untouched."""
        i = self._leaf_index[path]
        leaf: NestedTensor = self._flat[i]
        cur = leaf.resident_levels
        if cur == target:
            self._leaf_rungs[path] = target
            return (0, 0)
        ds = list(leaf.deltas)
        streams = self._leaf_streams[path]
        obs_in = obs_out = 0
        fetched = []
        try:
            while cur < target:
                words = self.pager.fetch(path, cur)
                fetched.append(cur)
                got = int(words.size) * words.dtype.itemsize
                if got != streams[1 + cur]:
                    raise RuntimeError(
                        f"pager returned {got} bytes for {path} delta {cur}; "
                        f"metadata says bytes(delta_{cur}) = {streams[1 + cur]}")
                ds[cur] = words
                obs_in += got
                cur += 1
        except BaseException:
            for lvl in fetched:
                self.pager.evict(path, lvl)
            raise
        while cur > target:
            cur -= 1
            got = int(ds[cur].size) * ds[cur].dtype.itemsize
            if got != streams[1 + cur]:
                raise RuntimeError(
                    f"resident stream {cur} of {path} holds {got} bytes; "
                    f"metadata says bytes(delta_{cur}) = {streams[1 + cur]}")
            self.pager.evict(path, cur)
            ds[cur] = None
            obs_out += got
        self._flat[i] = leaf.with_deltas(tuple(ds))
        self._leaf_rungs[path] = target
        return (obs_in, obs_out)

    # -- two-phase switching plumbing (DESIGN.md Sec. 12) -----------------
    def _stage_leaf(self, path: str, target: int) -> Dict[str, object]:
        """STAGE one leaf's move to ``target`` levels: fetch every upgrade
        stream (size-validated against metadata), size-validate every
        downgrade stream - WITHOUT touching the leaf, the rung map, or
        the ledger.  Returns the plan :meth:`_commit_leaf` executes; a
        raise here leaves the store bit-identical (the caller evicts the
        plan's ``fetched`` list).  Committing a validated plan cannot
        fail, which is what makes multi-leaf switches all-or-nothing."""
        leaf: NestedTensor = self._flat[self._leaf_index[path]]
        cur = leaf.resident_levels
        streams = self._leaf_streams[path]
        plan = {"path": path, "cur": cur, "target": target,
                "words": {}, "fetched": [], "pin": 0, "pout": 0}
        lvl = cur
        try:
            while lvl < target:
                words = self.pager.fetch(path, lvl)
                plan["fetched"].append(lvl)
                got = int(words.size) * words.dtype.itemsize
                if got != streams[1 + lvl]:
                    raise RuntimeError(
                        f"pager returned {got} bytes for {path} delta {lvl}; "
                        f"metadata says bytes(delta_{lvl}) = {streams[1 + lvl]}")
                plan["words"][lvl] = words
                plan["pin"] += got
                lvl += 1
        except BaseException:
            for l in plan["fetched"]:
                self.pager.evict(path, l)
            raise
        while lvl > target:
            lvl -= 1
            d = leaf.deltas[lvl]
            got = int(d.size) * d.dtype.itemsize
            if got != streams[1 + lvl]:
                for l in plan["fetched"]:
                    self.pager.evict(path, l)
                raise RuntimeError(
                    f"resident stream {lvl} of {path} holds {got} bytes; "
                    f"metadata says bytes(delta_{lvl}) = {streams[1 + lvl]}")
            plan["pout"] += got
        return plan

    def _abort_stage(self, plans: List[Dict[str, object]]) -> None:
        """Roll back staged plans: re-evict every fetched stream.  The
        leaves, rung map, and ledger were never touched, so this is the
        WHOLE rollback."""
        for plan in plans:
            for lvl in plan["fetched"]:
                self.pager.evict(plan["path"], lvl)

    def _commit_leaf(self, plan: Dict[str, object]) -> None:
        """COMMIT a staged plan: splice fetched streams in, evict
        downgraded levels, stamp the leaf rung.  Pre-validated - cannot
        fail."""
        path = plan["path"]
        i = self._leaf_index[path]
        leaf: NestedTensor = self._flat[i]
        ds = list(leaf.deltas)
        for lvl, words in plan["words"].items():
            ds[lvl] = words
        for lvl in range(plan["cur"] - 1, plan["target"] - 1, -1):
            self.pager.evict(path, lvl)
            ds[lvl] = None
        self._flat[i] = leaf.with_deltas(tuple(ds))
        self._leaf_rungs[path] = plan["target"]

    def _refresh_summary(self) -> None:
        """Re-derive the tree-level rung/mode summary from the per-leaf
        rung map (after a committed per-leaf switch)."""
        uni = self._uniform_rung()
        if uni is None:
            self.rung = min(self._leaf_rungs.values())
            self.mode = "mixed"
        else:
            self.rung = uni
            self.mode = rung_to_mode(uni, self.num_rungs)

    # -- byte accounting ------------------------------------------------
    def bytes(self) -> Dict[str, int]:
        return dict(self._bytes)           # copy: callers may adjust theirs

    def ladder_bytes(self) -> Dict[str, object]:
        return {**self._ladder_bytes,
                "deltas": list(self._ladder_bytes["deltas"])}

    def delta_bytes(self, i: int) -> int:
        """Bytes of delta stream i == the cost of the rung i -> i+1 upgrade."""
        if not 0 <= i < self.num_rungs - 1:
            raise ValueError(f"no delta stream {i} on a "
                             f"{self.num_rungs}-rung ladder")
        return self._ladder_bytes["deltas"][i]

    def rung_resident_bytes(self, rung: int) -> int:
        """HBM the store needs WITH rung ``rung`` uniformly resident
        (base + scales + fp leftovers + the first ``rung`` delta streams)."""
        rung = check_rung(rung, self.num_rungs)
        b = self._ladder_bytes
        return (b["base"] + b["scales"] + b["fp"] + sum(b["deltas"][:rung]))

    def resident_bytes(self) -> int:
        """HBM needed for the CURRENT (possibly mixed) per-leaf residency."""
        if not self.is_mixed:
            return self.rung_resident_bytes(self.rung)
        return self.assignment_resident_bytes(self.current_assignment())

    def assignment_resident_bytes(self, assignment: RungAssignment) -> int:
        """Would-be HBM residency under ``assignment``: base + scales + fp
        plus each leaf's first ``rung`` delta streams (exact per-leaf sum,
        the mixed-rung generalization of :meth:`rung_resident_bytes`)."""
        b = self._ladder_bytes
        total = b["base"] + b["scales"] + b["fp"]
        for path, rung in self.resolve_assignment(assignment).items():
            total += sum(self._leaf_streams[path][1:1 + rung])
        return total

    def best_rung_for(self, memory_budget_bytes: Optional[int]) -> int:
        """Highest uniform rung whose resident bytes fit the budget AND
        whose delta segments the pager can deliver (max_available_rung).

        Rung 0 is the FLOOR: the base stream is always resident, so a
        budget below even rung 0's bytes still returns 0 - the store
        never serves less than the base model (callers wanting to refuse
        service below the floor must compare rung_resident_bytes(0)
        themselves).  Residency is monotone in the rung, so the scan
        stops at the first rung that no longer fits."""
        avail = self.max_available_rung()
        if memory_budget_bytes is None:
            return avail
        want = 0
        for r in range(self.num_rungs):
            if self.rung_resident_bytes(r) <= memory_budget_bytes:
                want = r
            else:
                break
        return min(want, avail)

    def max_available_rung(self) -> int:
        """Highest uniform rung the pager can deliver RIGHT NOW.

        With the default InMemoryPager this is always the top rung; with
        a FilePager over a progressively delivered artifact it climbs as
        delta segments arrive (DESIGN.md Sec. 10), so budget policies
        transparently serve the best rung that has actually landed."""
        for k in range(self.num_rungs - 1):
            for path in self._leaf_paths:
                if (k < len(self._leaf_streams[path]) - 1
                        and self._leaf_rungs[path] <= k
                        and not self.pager.available(path, k)):
                    return k
        return self.num_rungs - 1

    # -- per-leaf rung state ---------------------------------------------
    @property
    def is_mixed(self) -> bool:
        """True when leaves sit on different rungs (beyond each ladder's
        own depth clamp)."""
        return self._uniform_rung() is None

    def _uniform_rung(self) -> Optional[int]:
        """The tree-level rung r such that every leaf sits at
        min(r, leaf top), or None when the residency is mixed."""
        if not self._leaf_rungs:
            return self.rung
        # the deepest leaf always reaches the tree-level rung un-clamped,
        # so the max leaf rung IS the candidate tree rung
        cand = max(self._leaf_rungs.values())
        for path, r in self._leaf_rungs.items():
            if r != min(cand, len(self._leaf_streams[path]) - 1):
                return None
        return cand

    def leaf_rungs(self) -> Dict[str, int]:
        """Copy of the current per-leaf rung map (keystr path -> rung)."""
        return dict(self._leaf_rungs)

    def leaf_bits(self) -> Dict[str, Tuple[int, ...]]:
        """Per-leaf ladder bitwidths (keystr path -> ascending bits)."""
        return dict(self._leaf_bits)

    def leaf_streams(self) -> Dict[str, Tuple[int, ...]]:
        """Per-leaf packed stream sizes (keystr path -> (base bytes,
        delta_0 bytes, ...)), metadata-computed once at construction -
        what external accounting (e.g. the serving Scheduler's per-switch
        exactness checks) should read instead of re-deriving."""
        return dict(self._leaf_streams)

    def nested_leaves(self) -> List[Tuple[str, NestedTensor]]:
        """(keystr path, NestedTensor) for every nested leaf, tree order,
        at their CURRENT residency (non-resident delta slots are None)."""
        flat, _ = jax.tree_util.tree_flatten_with_path(
            self.nested_params, is_leaf=lambda x: isinstance(x, NestedTensor))
        return [(jax.tree_util.keystr(p), leaf) for p, leaf in flat
                if isinstance(leaf, NestedTensor)]

    def hydrated_leaves(self) -> List[Tuple[str, NestedTensor]]:
        """Like :meth:`nested_leaves` but with EVERY delta level present,
        paging missing streams through the pager transiently (residency
        and ledger unchanged).  Off the serving path: quality probes and
        offline export need the full ladder regardless of what is
        resident; with a throttled pager the transfer cost is recorded."""
        out = []
        for path in self._leaf_paths:
            leaf: NestedTensor = self._flat[self._leaf_index[path]]
            missing = range(leaf.resident_levels, len(leaf.deltas))
            if missing:
                ds = list(leaf.deltas)
                fetched = []
                try:
                    for i in missing:
                        ds[i] = self.pager.fetch(path, i)
                        fetched.append(i)
                finally:            # transient: evict even on a failed fetch
                    for i in fetched:
                        self.pager.evict(path, i)
                leaf = leaf.with_deltas(tuple(ds))
            out.append((path, leaf))
        return out

    def params_for(self, rungs) -> Dict:
        """Serving tree with per-leaf rung stamps ``rungs`` (an int or a
        ``{keystr: rung}`` map), clamped to the CURRENT residency - the
        draft-side read of the resident artifact (O(#leaves) metadata
        flip; no paging, no ledger events).  Unmapped leaves keep their
        current stamp."""
        if isinstance(rungs, int):
            rungs = {p: rungs for p in self._leaf_paths}
        clamped = {p: max(0, min(int(r), self._leaf_rungs[p]))
                   for p, r in rungs.items() if p in self._leaf_rungs}
        return set_tree_rung(self.nested_params, clamped)

    def rung_view(self, rung: int, *, stamp=None) -> Dict:
        """The packed tree AS IF uniform rung ``rung`` were resident,
        without changing actual residency (no ledger events).

        Each nested leaf carries exactly its first ``min(rung, top)``
        delta streams - streams not currently resident are fetched
        transiently through the pager (and evicted again), streams
        resident beyond the view are dropped from the copy - and is
        stamped ``stamp`` (an int or a ``{keystr: rung}`` map, default
        ``rung``; clamped to the view's residency).  The resulting
        pytree structure (delta-residency pattern + rung aux) matches
        ``params()`` after ``to_rung(rung)`` bit-for-bit, which is what
        engine warm-up pre-traces against so a later live switch hits
        the jit cache instead of recompiling (DESIGN.md Sec. 15).  A
        draft view uses ``stamp < rung`` - same residency, lower rung
        read - matching the speculative decoder's draft parameters."""
        rung = check_rung(rung, self.num_rungs)
        out = []
        for i, leaf in enumerate(self._flat):
            if not isinstance(leaf, NestedTensor):
                out.append(leaf)
                continue
            path = self._leaf_paths_by_index.get(i)
            r = min(rung, leaf.top)
            ds = list(leaf.deltas)
            fetched = []
            try:
                for j in range(r):
                    if ds[j] is None:
                        ds[j] = self.pager.fetch(path, j)
                        fetched.append(j)
            finally:            # transient: evict even on a failed fetch
                for j in fetched:
                    self.pager.evict(path, j)
            ds = ds[:r] + [None] * (len(ds) - r)
            s = stamp.get(path, r) if isinstance(stamp, dict) else (
                r if stamp is None else stamp)
            s = min(check_rung(s, self.num_rungs), r)
            out.append(leaf.with_deltas(tuple(ds)).with_rung(s))
        return jax.tree_util.tree_unflatten(self._treedef, out)

    @property
    def _leaf_paths_by_index(self) -> Dict[int, str]:
        return {self._leaf_index[p]: p for p in self._leaf_paths}

    def resolve_assignment(self, assignment: RungAssignment) -> Dict[str, int]:
        """Concrete per-leaf target rungs under ``assignment`` (clamped to
        each leaf's ladder)."""
        return {p: assignment.rung_for(p, self.num_rungs,
                                       len(self._leaf_streams[p]))
                for p in self._leaf_paths}

    def current_assignment(self) -> RungAssignment:
        """The current residency as an exact-path RungAssignment (what a
        policy returns to mean 'hold')."""
        return RungAssignment(default=self.rung,
                              exact=tuple(self._leaf_rungs.items()))

    # -- switching -------------------------------------------------------
    def apply(self, assignment: RungAssignment) -> Dict[str, int]:
        """Move residency to ``assignment``, ledgering each leaf's delta
        page-ins/outs EXACTLY (DESIGN.md Sec. 9).

        ALL-OR-NOTHING (DESIGN.md Sec. 12): the switch first STAGES every
        leaf's move - fetching and size-validating each upgrade stream,
        validating each downgrade - with zero store mutation, then
        COMMITS residency + ledger only once every leaf staged cleanly.
        A failed fetch (undelivered segment, chaos fault, quarantine)
        rolls back by re-evicting the staged streams and re-raises: the
        serving tree, the rung map, ``resident_bytes`` and the ledger
        read exactly as before the call, so the bytes(delta_k) exactness
        invariant holds across failures.

        The uniform case delegates to :meth:`to_rung` (one tree-wide
        ledger event per adjacent step, the classic Table-11 form);
        otherwise one event per moved leaf, whose bytes are the exact sum
        of that leaf's walked delta streams.  Returns
        ``{'page_in', 'page_out', 'moves'}`` for this call alone."""
        if not isinstance(assignment, RungAssignment):
            assignment = RungAssignment.uniform(assignment)
        to = (mode_to_rung(assignment.default, self.num_rungs)
              if assignment.is_uniform else -1)
        with obs.span("switch", from_rung=self.rung, to_rung=to):
            before_in = self.ledger.page_in_bytes
            before_out = self.ledger.page_out_bytes
            before_ev = len(self.ledger.events)
            if assignment.is_uniform and not self.is_mixed:
                self.to_rung(to)
            else:
                targets = self.resolve_assignment(assignment)
                moves = [(p, self._leaf_rungs[p], targets[p])
                         for p in self._leaf_paths
                         if targets[p] != self._leaf_rungs[p]]
                plans = []
                try:                        # phase 1: stage (no mutation)
                    for path, _, tgt in moves:
                        plans.append(self._stage_leaf(path, tgt))
                except BaseException:
                    self._abort_stage(plans)
                    raise
                for (path, cur, tgt), plan in zip(moves, plans):
                    self._commit_leaf(plan)  # phase 2: commit (cannot fail)
                    self.ledger.record(page_in=plan["pin"],
                                       page_out=plan["pout"],
                                       from_rung=cur, to_rung=tgt)
                self._refresh_summary()
                self._rebuild_tree()
            return {"page_in": self.ledger.page_in_bytes - before_in,
                    "page_out": self.ledger.page_out_bytes - before_out,
                    "moves": len(self.ledger.events) - before_ev}

    def to_rung(self, rung: int):
        """Walk the whole tree one adjacent rung at a time, fetching /
        evicting each leaf's level-k stream through the pager and
        ledgering the OBSERVED bytes - asserted equal to the computed
        bytes(delta_k) per step (Table 11, K-rung).  From a MIXED state
        this delegates to :meth:`apply` so each leaf's walk is ledgered
        exactly.

        ALL-OR-NOTHING across the WHOLE walk (DESIGN.md Sec. 12): every
        adjacent step is staged - all fetches done and size-validated,
        per-step totals checked against bytes(delta_k) - before anything
        commits.  Any failure re-evicts all staged streams and re-raises
        with the store bit-identical to before the call: rung, mode,
        per-leaf residency, and ledger untouched (the pre-Sec.-12 walk
        committed completed steps, stranding the store between rungs)."""
        rung = mode_to_rung(rung, self.num_rungs)
        if self.is_mixed:
            self.apply(RungAssignment.uniform(rung))
            return self
        # phase 1: stage the whole walk.  Upgrades fetch + validate every
        # stream; downgrades validate resident sizes.  No store mutation.
        words: Dict[Tuple[str, int], jax.Array] = {}
        fetched: List[Tuple[str, int]] = []
        steps: List[Tuple[int, int, int]] = []   # (k, to, observed bytes)
        try:
            for k in range(self.rung, rung):               # upgrade steps
                obs = 0
                for path in self._leaf_paths:
                    if k < len(self._leaf_streams[path]) - 1:
                        w = self.pager.fetch(path, k)
                        fetched.append((path, k))
                        got = int(w.size) * w.dtype.itemsize
                        if got != self._leaf_streams[path][1 + k]:
                            raise RuntimeError(
                                f"pager returned {got} bytes for {path} "
                                f"delta {k}; metadata says bytes(delta_{k})"
                                f" = {self._leaf_streams[path][1 + k]}")
                        words[(path, k)] = w
                        obs += got
                if obs != self.delta_bytes(k):
                    raise RuntimeError(
                        f"upgrade {k}->{k + 1} observed {obs} bytes moved; "
                        f"computed bytes(delta_{k}) = {self.delta_bytes(k)}")
                steps.append((k, k + 1, obs))
            for k in range(self.rung - 1, rung - 1, -1):   # downgrade steps
                obs = 0
                for path in self._leaf_paths:
                    if k < len(self._leaf_streams[path]) - 1:
                        d = self._flat[self._leaf_index[path]].deltas[k]
                        got = int(d.size) * d.dtype.itemsize
                        if got != self._leaf_streams[path][1 + k]:
                            raise RuntimeError(
                                f"resident stream {k} of {path} holds {got} "
                                f"bytes; metadata says bytes(delta_{k}) = "
                                f"{self._leaf_streams[path][1 + k]}")
                        obs += got
                if obs != self.delta_bytes(k):
                    raise RuntimeError(
                        f"downgrade {k + 1}->{k} observed {obs} bytes moved; "
                        f"computed bytes(delta_{k}) = {self.delta_bytes(k)}")
                steps.append((k + 1, k, obs))
        except BaseException:
            # rollback = drop the stage: leaves/rung map/ledger were
            # never touched, so re-evicting the fetches restores the
            # store bit-identically
            for path, lvl in fetched:
                self.pager.evict(path, lvl)
            raise
        # phase 2: commit (cannot fail) - splice/evict each staged step,
        # one ledger event per adjacent step, the classic Table-11 form
        new_ds = {path: list(self._flat[self._leaf_index[path]].deltas)
                  for path in self._leaf_paths}
        for frm, to, obs in steps:
            k = min(frm, to)
            for path in self._leaf_paths:
                if k < len(self._leaf_streams[path]) - 1:
                    if to > frm:                           # upgrade
                        new_ds[path][k] = words[(path, k)]
                        self._leaf_rungs[path] = to
                    else:                                  # downgrade
                        self.pager.evict(path, k)
                        new_ds[path][k] = None
                        self._leaf_rungs[path] = min(
                            to, len(self._leaf_streams[path]) - 1)
            self.ledger.record(page_in=obs if to > frm else 0,
                               page_out=obs if to < frm else 0,
                               from_rung=frm, to_rung=to)
            self.rung = to
        for path in self._leaf_paths:
            i = self._leaf_index[path]
            self._flat[i] = self._flat[i].with_deltas(tuple(new_ds[path]))
        self.mode = rung_to_mode(self.rung, self.num_rungs)
        self._rebuild_tree()
        return self

    def to_full(self):
        """Upgrade to the top rung (2-rung: page in w_low, zero page-out)."""
        return self.to_rung(self.num_rungs - 1)

    def to_part(self):
        """Downgrade to the base rung (2-rung: page out w_low, zero page-in)."""
        return self.to_rung(0)

    # -- weights for inference -------------------------------------------
    def params(self):
        """Serving parameters: the PACKED tree, rung-stamped per leaf.

        No dequantization happens here - NestedTensor leaves flow into the
        model as-is and the matmul dispatch (models.layers.packed_linear)
        streams the packed words directly.  A rung switch is therefore an
        O(#leaves) metadata flip (plus the ledgered adjacent-delta page-in
        on upgrade), never a whole-tree dequant.  Mixed residency stamps
        each leaf's own rung; packed_linear needs no change since it
        dispatches on the per-leaf stamp."""
        if self.is_mixed:
            return set_tree_rung(self.nested_params, dict(self._leaf_rungs))
        return set_tree_rung(self.nested_params, self.rung)

    def dense_params(self):
        """Seed-style dense materialization (benchmark baseline / offline
        export only - NOT on the serving path)."""
        return materialize(self.nested_params, mode=self.rung, dtype=self.dtype)

    # -- comparison baseline ----------------------------------------------
    def diverse_baseline(self) -> Dict[str, int]:
        d = diverse_bitwidth_bytes(self.nested_params, self.n, self.h)
        d["switch_page_in"] = d["int_n"]   # upgrade: load full INT-n model
        d["switch_page_out"] = d["int_h"]  # upgrade: evict INT-h model
        return d

    def diverse_ladder_baseline(self, bits: Sequence[int]) -> Dict[str, object]:
        """K diverse-bitwidth PTQ models; switch r->r' swaps whole models."""
        return diverse_ladder_bytes(self.nested_params, bits)

    def switch_reduction(self) -> float:
        """Paper's 'Reduced Overhead' column: 1 - nest/(diverse) for one
        base-to-top upgrade."""
        nest = self.bytes()["low"]
        div = self.diverse_baseline()
        return 1.0 - nest / max(div["switch_page_in"] + div["switch_page_out"], 1)
