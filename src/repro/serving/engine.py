"""Serving engine: batched requests, prefill/decode, NestQuant switching.

The engine owns (a) a :class:`NestQuantStore` (packed weights + rung
state machine), (b) a :class:`RungPolicy` that turns resource signals
into per-leaf rung assignments (DESIGN.md Sec. 9), and (c) the jitted
prefill/decode steps.  At every request boundary the policy sees the
HBM budget, queue depth, and recent switch history, and the store pages
exactly the delta streams its assignment moves (DESIGN.md Sec. 8); the
paper's full/part pair is the 2-rung case under the default
:class:`BudgetPolicy`.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import obs
from ..configs.base import ModelConfig
from ..core.switching import NestQuantStore, RungAssignment
from ..models.model import Model, make_model
from ..storage.artifact import ArtifactError
from ..storage.pager import PagerError
from .kv_cache import KVCacheConfig, NestedKVCache, dense_kv_bytes_per_token, \
    kv_bytes_per_token
from .policies import (BudgetPolicy, QualityFloorPolicy, ResourceSignal,
                       RungPolicy, SignalTracker, resolve_kv_decide)

# what a failed rung switch looks like to the engine: every pager-tier
# fault (transient, corrupt, quarantine) plus artifact-tier errors from
# undelivered / corrupted segments.  Rollback in the store (DESIGN.md
# Sec. 12) guarantees the current residency survived, so the engine can
# always keep serving at the rung it already has.
SWITCH_FAILURES = (PagerError, ArtifactError)

# mode_history is a diagnostic ring, not a ledger: the SwitchLedger keeps
# the exact per-move accounting, so the engine only retains a recent
# window plus rolling per-mode counts (one entry per generate() call
# forever would grow unbounded on a long-lived server)
MODE_HISTORY_CAP = 512


@dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 16
    out_tokens: List[int] = field(default_factory=list)


@dataclass(frozen=True)
class SpecConfig:
    """Self-speculative decoding knobs (DESIGN.md Sec. 15).

    ``k`` drafted tokens per round; ``draft`` picks the draft rung:
    an int (uniform rung, clamped per-leaf to what is resident), a
    ``{keystr: rung}`` map, a :class:`~repro.core.switching.
    RungAssignment` (e.g. ``SearchResult.assignment_for(budget)`` - the
    calibration-search sensitivity table as a draft model), or
    ``'floor'`` (the :class:`~repro.serving.policies.QualityFloorPolicy`
    in the engine's policy chain supplies per-leaf lowest-acceptable
    rungs).  Drafts never page anything in: the draft rung reads a
    PREFIX of the streams already resident for the verify rung."""
    k: int = 3
    draft: object = 0


@dataclass(frozen=True)
class DecodeProfile:
    """What one ``generate`` call actually dispatched - the honest input
    to :meth:`~repro.serving.scheduler.ServiceModel.speculative_seconds`
    (drafts are charged at their resident-rung bytes, verifies at the
    full residency, so the virtual-clock speedup is real arithmetic,
    not an assumed acceptance rate)."""
    steps: int = 0                # sequential full-residency decode steps
    draft_steps: int = 0          # draft-rung decode steps
    verify_passes: int = 0        # chunked verify passes
    draft_bytes: int = 0          # resident bytes the draft rung streams
    verify_bytes: int = 0         # resident bytes the verify pass streams
    drafted: int = 0              # tokens drafted (real requests only)
    accepted: int = 0             # drafted tokens accepted (real only)

    @property
    def speculative(self) -> bool:
        return self.verify_passes > 0

    @property
    def acceptance(self) -> float:
        return self.accepted / self.drafted if self.drafted else 0.0


@dataclass
class EngineStats:
    prefills: int = 0
    decode_steps: int = 0
    switches: int = 0
    # degraded-mode counters (DESIGN.md Sec. 12): switch attempts that
    # failed and rolled back, and the last failure's message (diagnostic)
    switch_failures: int = 0
    last_failure: str = ""
    mode_history: deque = field(
        default_factory=lambda: deque(maxlen=MODE_HISTORY_CAP))
    mode_counts: Dict[str, int] = field(default_factory=dict)
    # scheduler counters (DESIGN.md Sec. 11): batches dispatched by a
    # Scheduler, real requests it admitted, and filler clones it padded
    # batches with to keep jit shapes stable (not served to any client)
    sched_steps: int = 0
    sched_admitted: int = 0
    sched_filler: int = 0
    # speculative counters (DESIGN.md Sec. 15).  Token counts cover REAL
    # requests only: filler clones ride in the same batch rows but are
    # excluded here exactly as sched_filler excludes them from admission
    # accounting - a padded batch must not dilute the acceptance rate.
    spec_rounds: int = 0          # draft/verify rounds (= verify passes)
    spec_drafted: int = 0         # tokens drafted for real requests
    spec_accepted: int = 0        # drafted tokens accepted (real only)
    spec_rejected: int = 0        # drafted tokens rejected (real only)
    # nested KV cache counters (DESIGN.md Sec. 16)
    kv_switches: int = 0          # committed cache rung moves
    kv_switch_failures: int = 0   # cache switch attempts rolled back
    kv_pages: int = 0             # pages ingested over the engine's life
    # device->host transfers of decoded tokens: one per plain decode step,
    # whatever the number of rows
    token_pulls: int = 0
    # rows routed to each held expert, (layers, held experts), summed
    # over the plain decode steps of the engine's life (MoE only)
    expert_rows: Optional[np.ndarray] = None

    @property
    def spec_acceptance(self) -> float:
        """Accepted fraction of drafted tokens (real requests only)."""
        return (self.spec_accepted / self.spec_drafted
                if self.spec_drafted else 0.0)

    def record_mode(self, mode: str):
        self.mode_history.append(mode)
        self.mode_counts[mode] = self.mode_counts.get(mode, 0) + 1


class ServeEngine:
    def __init__(self, cfg: ModelConfig, store: NestQuantStore,
                 max_batch: int = 8, max_len: int = 128,
                 policy: Optional[RungPolicy] = None, *,
                 model: Optional[Model] = None, compiled=None, kv=None):
        self.cfg = cfg
        self.model = model if model is not None else make_model(cfg)
        self.store = store
        self.max_batch = max_batch
        self.max_len = max_len
        self.policy = policy if policy is not None else BudgetPolicy()
        # nested KV cache (DESIGN.md Sec. 16): None keeps the dense bf16
        # cache; a KVCacheConfig builds a fresh NestedKVCache; an existing
        # cache (e.g. over a chaos/resilient pager) is adopted as-is.
        if isinstance(kv, KVCacheConfig):
            kv = NestedKVCache(kv)
        self.kv: Optional[NestedKVCache] = kv
        self.stats = EngineStats()
        self.artifact = None          # set by from_artifact
        self._tracker = SignalTracker()
        self._params = None
        self.last_profile: Optional[DecodeProfile] = None
        self._decode_chunk = None
        if compiled is not None:
            if len(compiled) == 3:
                self._prefill, self._decode, self._decode_chunk = compiled
            else:
                self._prefill, self._decode = compiled
        else:
            self._prefill = jax.jit(self.model.prefill)
            self._decode = jax.jit(self.model.decode_step,
                                   donate_argnums=(2,))
        if self._decode_chunk is None and self.model.decode_chunk is not None:
            self._decode_chunk = jax.jit(self.model.decode_chunk,
                                         donate_argnums=(2,))

    @property
    def compiled(self):
        """The jitted ``(prefill, decode_step, decode_chunk)`` triple
        (``decode_chunk`` is None for families without a chunked verify
        path).  A fleet of N same-config replicas passes one engine's
        ``compiled`` (plus its ``model``) to the other N-1 constructors
        so jax traces each function once, not N times (DESIGN.md
        Sec. 14); 2-tuples from older callers still unpack."""
        return (self._prefill, self._decode, self._decode_chunk)

    # -- deployment --------------------------------------------------------
    @classmethod
    def from_artifact(cls, cfg: ModelConfig, path, *, pager=None,
                      policy: Optional[RungPolicy] = None, max_batch: int = 8,
                      max_len: int = 128, dtype=jnp.bfloat16,
                      verify: bool = True) -> "ServeEngine":
        """Cold-boot from a saved artifact (DESIGN.md Sec. 10).

        Reads ONLY ``manifest.json`` + the base segment and serves at
        rung 0 immediately; delta streams page in through the pager
        (default: a :class:`~repro.storage.pager.FilePager` over the same
        artifact) - on a budget upgrade, or rung-by-rung via
        :meth:`poll_delivery` as delta segments arrive on disk."""
        from ..storage.artifact import Artifact, open_artifact
        from ..storage.pager import FilePager
        art = path if isinstance(path, Artifact) else open_artifact(path)
        store = NestQuantStore(
            art.load_base_tree(), mode="part", dtype=dtype,
            pager=pager if pager is not None else FilePager(art, verify=verify))
        eng = cls(cfg, store, max_batch=max_batch, max_len=max_len,
                  policy=policy)
        eng.artifact = art
        return eng

    def poll_delivery(self) -> Dict[str, object]:
        """Progressive rung delivery: climb one adjacent rung at a time
        while the pager has the next delta level available (the paper's
        "page in lower-bit weights when resources allow" as a control
        loop).  Call it whenever the transport may have delivered more
        segments; serving keeps working between polls at whatever rung
        has landed.  A climb step that FAILS (chaos fault, late
        corruption) rolls back in the store (DESIGN.md Sec. 12) and ends
        this poll - the engine stays pinned at the highest rung that
        actually committed and the next poll re-probes.  Returns
        {'from_rung', 'rung', 'modes', 'page_in', 'failed'} for this
        poll alone (page_in = observed bytes, ledgered)."""
        start = self.store.rung
        in0 = self.store.ledger.page_in_bytes
        reached: List[str] = []
        failed = ""
        while (self.store.rung < self.store.num_rungs - 1
               and self.store.max_available_rung() > self.store.rung):
            try:
                self.store.to_rung(self.store.rung + 1)
            except SWITCH_FAILURES as e:
                failed = str(e)
                self.stats.switch_failures += 1
                self.stats.last_failure = failed
                self._tracker.note(False, failed=True)
                break
            self.stats.switches += 1
            self.stats.record_mode(self.store.mode)
            reached.append(self.store.mode)
        if reached:
            self._params = self.store.params()
        return {"from_rung": start, "rung": self.store.rung,
                "modes": reached,
                "page_in": self.store.ledger.page_in_bytes - in0,
                "failed": failed}

    # -- warm-up (kill the per-rung retrace, DESIGN.md Sec. 15) ------------
    def warmup(self, prompt_len, *, batch: Optional[int] = None,
               rungs=None, spec: Optional["SpecConfig"] = None) -> int:
        """Pre-trace every (rung, shape) the serve loop will dispatch.

        A rung switch changes the rung stamp AND the delta-residency
        pattern of every packed leaf - both live in the pytree structure,
        so each uniform rung is a distinct jit cache entry and the first
        switch to it used to pay a mid-serve retrace.  This calls the
        jitted prefill / decode(/chunk/draft) functions once per rung on
        :meth:`~repro.core.switching.NestQuantStore.rung_view` trees
        whose structure matches the live ``store.params()`` at that rung
        bit-for-bit, so later switches hit the cache (``.lower().
        compile()`` would NOT populate the call cache - the calls are
        real, on throwaway buffers).  ``prompt_len`` is an int or a list
        of the prompt lengths generate() will see after left-padding;
        ``batch`` defaults to ``max_batch`` (what a bucketing Scheduler
        dispatches); ``spec`` additionally warms the draft-stamp and
        (k+1)-chunk verify entries.  Mixed per-leaf assignments beyond
        the draft map are not enumerated here - a policy that emits one
        still traces on first use.  Returns the number of warm-up calls."""
        B = self.max_batch if batch is None else batch
        plens = ([prompt_len] if isinstance(prompt_len, int)
                 else sorted(set(prompt_len)))
        rungs = (range(self.store.num_rungs) if rungs is None
                 else sorted(set(rungs)))
        cdt = jnp.dtype(self.cfg.compute_dtype)
        tok1 = jnp.zeros((B, 1), jnp.int32)
        calls = 0
        for r in rungs:
            stamps = [None]
            if spec is not None:
                draft = self._draft_rungs(spec, {p: min(r, len(s) - 1)
                                                 for p, s in
                                                 self.store.leaf_streams().items()})
                stamps.append(draft)
            params = self.store.rung_view(r)
            for S in plens:
                self._prefill(params, {"tokens": jnp.zeros((B, S), jnp.int32)})
                calls += 1
            for stamp in stamps:
                p = params if stamp is None else self.store.rung_view(
                    r, stamp=stamp)
                self._decode(p, {"tokens": tok1},
                             self.model.make_cache(B, self.max_len, dtype=cdt))
                calls += 1
            if spec is not None and self._decode_chunk is not None:
                self._decode_chunk(
                    params, {"tokens": jnp.zeros((B, spec.k + 1), jnp.int32)},
                    self.model.make_cache(B, self.max_len, dtype=cdt))
                calls += 1
        # nested KV cache (DESIGN.md Sec. 16): warm the quantize + render
        # jit entries for every (KV rung x prompt shape) this loop will
        # dispatch.  The dense jit cache shape never changes with the KV
        # rung, so this is the ONLY extra trace surface a KV switch has -
        # after it, a post-warmup cache rung switch retraces nothing.
        if self.kv is not None:
            probe = self.model.make_cache(B, self.max_len, dtype=cdt)
            if "k" in probe:
                Lk = probe["k"].shape[0]
                for S in plens:
                    calls += self.kv.warm(Lk, B, S, self.cfg.num_kv_heads,
                                          self.cfg.head_dim)
        return calls

    # -- draft-rung selection (DESIGN.md Sec. 15) --------------------------
    def _draft_rungs(self, spec: "SpecConfig",
                     cur: Optional[Dict[str, int]] = None) -> Dict[str, int]:
        """Per-leaf draft rungs for ``spec``, clamped to the CURRENT
        residency (drafting must never page anything in - the draft
        reads a prefix of the streams the verify rung already holds)."""
        if cur is None:
            cur = self.store.leaf_rungs()
        d = spec.draft
        if isinstance(d, str):
            if d != "floor":
                raise ValueError(f"unknown draft spec {d!r}; expected an "
                                 "int rung, a path map, a RungAssignment, "
                                 "or 'floor'")
            pol, floors, seen = self.policy, None, set()
            while pol is not None and id(pol) not in seen:
                seen.add(id(pol))
                if isinstance(pol, QualityFloorPolicy):
                    floors = pol.floor_rungs(self.store)
                    break
                pol = getattr(pol, "inner", None)
            if floors is None:
                raise ValueError("draft='floor' needs a QualityFloorPolicy "
                                 "in the engine's policy chain")
            want = floors
        elif isinstance(d, RungAssignment):
            want = self.store.resolve_assignment(d)
        elif isinstance(d, dict):
            want = {p: d.get(p, 0) for p in cur}
        else:
            want = {p: int(d) for p in cur}
        return {p: max(0, min(int(want[p]), cur[p])) for p in cur}

    def draft_resident_bytes(self, spec: "SpecConfig") -> int:
        """Bytes one draft-rung decode step streams (what the
        ServiceModel charges a draft at)."""
        return self.store.assignment_resident_bytes(RungAssignment(
            default=0, exact=tuple(self._draft_rungs(spec).items())))

    # -- switching ---------------------------------------------------------
    def ensure_mode(self, memory_budget_bytes: Optional[int] = None,
                    queue_depth: int = 0, backlog_age_s: float = 0.0):
        """Let the policy pick the residency for the current resource
        signal and flip it (the default BudgetPolicy serves the HIGHEST
        ladder rung fitting the HBM budget; rung 0 = the always-resident
        base, the top rung = the full-bit model).

        The serving path never materializes dense weights: ``store.params()``
        is the packed tree with the rung stamped on each leaf, so a switch
        is an O(1)-per-leaf metadata flip plus the ledgered adjacent-delta
        page-ins (upgrade) / page-outs (downgrade).  ``stats.switches``
        counts only REAL residency changes - first-time parameter pickup
        is not a switch.  The scalar-budget call form is unchanged from
        the pre-policy API; ``queue_depth``/``backlog_age_s`` are the
        traffic half of the signal - the Scheduler (DESIGN.md Sec. 11)
        feeds them from its real request queue.

        DEGRADED MODE (DESIGN.md Sec. 12): a switch attempt that fails
        rolls back all-or-nothing in the store, so the engine catches
        pager/artifact faults, notes the failure in the tracker (the
        next signal's ``delivery_health`` carries it to the policy),
        and KEEPS SERVING at the current residency - the highest rung
        that is actually healthy.  No request is ever dropped because a
        delta stream would not arrive."""
        with obs.span("ensure_mode"):
            quarantined = getattr(self.store.pager, "quarantined", None)
            signal = self._tracker.signal(
                memory_budget_bytes=memory_budget_bytes,
                queue_depth=queue_depth, backlog_age_s=backlog_age_s,
                available_rung=self.store.max_available_rung(),
                quarantined=len(quarantined()) if callable(quarantined) else 0,
                kv_rung=self.kv.rung if self.kv is not None else -1,
                kv_num_rungs=(self.kv.config.num_rungs
                              if self.kv is not None else 0),
                kv_resident_bytes=(self.kv.resident_bytes()
                                   if self.kv is not None else 0))
            self._ensure_kv_rung(signal)
            try:
                report = self.store.apply(
                    self.policy.decide(self.store, signal))
            except SWITCH_FAILURES as e:
                self.stats.switch_failures += 1
                self.stats.last_failure = str(e)
                self._tracker.note(False, failed=True)
                if self._params is None:    # first pickup cannot have staged
                    self._params = self.store.params()
                self.stats.record_mode(self.store.mode)
                return self.store.mode
            changed = report["moves"] > 0
            self._tracker.note(changed)
            if changed:
                self.stats.switches += 1
            if changed or self._params is None:
                self._params = self.store.params()
            self.stats.record_mode(self.store.mode)
            return self.store.mode

    # -- nested KV cache (DESIGN.md Sec. 16) -------------------------------
    def _ensure_kv_rung(self, signal: ResourceSignal) -> None:
        """Joint weight+KV rung selection, cache half: let the policy
        chain pick a cache rung (``kv_decide``), clamp it to what the
        pager can deliver, and walk there through the ledgered adjacent
        steps.  A failed walk (chaos fault, quarantine) rolls back in
        the cache and only LOWERS the cache rung ceiling - decode state
        lives in the dense jit cache and is never touched, so serving
        continues at whatever cache rung is healthy."""
        if self.kv is None:
            return
        want = resolve_kv_decide(self.policy, self.kv, signal)
        if want is None:
            return
        want = min(max(int(want), 0), self.kv.max_available_rung())
        if want == self.kv.rung:
            return
        try:
            self.kv.to_rung(want)
        except SWITCH_FAILURES as e:
            self.stats.kv_switch_failures += 1
            self.stats.last_failure = str(e)
            return
        self.stats.kv_switches += 1

    def kv_bytes_per_seq(self, rung: Optional[int] = None) -> int:
        """Worst-case cache bytes ONE admitted sequence costs (max_len
        positions): the packed nested cost at ``rung`` (default: the
        cache's current rung) when a nested cache is attached, the dense
        compute-dtype cost otherwise.  Pure metadata - the scheduler
        prices admission with it before any page exists."""
        probe = self.model.make_cache(1, 1,
                                      dtype=jnp.dtype(self.cfg.compute_dtype))
        if "k" not in probe:
            return 0
        Lk = probe["k"].shape[0]
        if self.kv is None:
            per_tok = dense_kv_bytes_per_token(
                Lk, self.cfg.num_kv_heads, self.cfg.head_dim,
                jnp.dtype(self.cfg.compute_dtype).itemsize)
        else:
            per_tok = kv_bytes_per_token(
                self.kv.config, self.kv.rung if rung is None else int(rung),
                Lk, self.cfg.num_kv_heads, self.cfg.head_dim)
        return per_tok * self.max_len

    def kv_admissible_batch(self, memory_budget_bytes: Optional[int]) -> int:
        """Largest batch whose KV cache fits beside the CURRENT weight
        residency under the budget (>= 1: the engine never refuses the
        single-sequence floor; None budget = no cache constraint).  This
        is the honest admission cap a KV downshift buys batch size
        through - nested pages cost fewer bytes per sequence, so the
        same free HBM admits strictly more sequences."""
        if memory_budget_bytes is None:
            return self.max_batch
        per_seq = self.kv_bytes_per_seq()
        if per_seq <= 0:
            return self.max_batch
        free = memory_budget_bytes - self.store.resident_bytes()
        return max(1, min(self.max_batch, free // per_seq))

    def _kv_ingest(self, cache, S: int) -> None:
        """Quantize the prompt region of a freshly re-homed cache into
        nested pages and render them back at the current cache rung (the
        recompose-to-bf16 fallback path - the packed streams are the
        cache of record, the dense buffer its rendering).  The partial
        tail page and all decode positions stay dense."""
        if self.kv is None or "k" not in cache:
            return
        n = self.kv.ingest(cache["k"][:, :, :S], cache["v"][:, :, :S])
        if not n:
            return
        self.stats.kv_pages += n
        kq, vq = self.kv.render()
        zeros = (0,) * cache["k"].ndim
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], kq.astype(cache["k"].dtype), zeros)
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], vq.astype(cache["v"].dtype), zeros)

    def _kv_rewind(self, pos: int) -> None:
        """Rung-aware speculative rewind hook: retire nested pages the
        rewind invalidates WITHOUT fetching anything (see
        NestedKVCache.rewind).  No-op for the dense cache."""
        if self.kv is not None:
            self.kv.rewind(pos)

    # -- serving -----------------------------------------------------------
    def generate(self, requests: List[Request],
                 memory_budget_bytes: Optional[int] = None, *,
                 queue_depth: Optional[int] = None,
                 backlog_age_s: float = 0.0,
                 speculate=None) -> List[Request]:
        """Greedy-decode a batch of requests with the current mode.

        ``queue_depth``/``backlog_age_s`` let a scheduler report the
        backlog BEHIND this batch (the admission-step hook, DESIGN.md
        Sec. 11) so the policy decides once per batch from real traffic
        pressure; bare calls keep the old behavior of reporting the
        batch size itself.

        ``speculate`` (an int ``k`` or a :class:`SpecConfig`) switches to
        self-speculative decoding (DESIGN.md Sec. 15): the resident
        part-bit rung drafts k greedy tokens, ONE chunked full-residency
        pass verifies all k+1 positions, and the longest matching prefix
        is accepted - output token ids are bit-identical to this same
        call without ``speculate``.  Either way ``last_profile`` records
        what was dispatched for the virtual-clock cost model.

        The call is one ``nq.generate`` span, with the spans of its
        phases inside (``repro.obs``)."""
        with obs.span("generate", batch=self.stats.prefills,
                      experts_held=(self.cfg.held_experts
                                    if self.cfg.family == "moe" else 0),
                      rows=len(requests),
                      real_rows=sum(r.uid >= 0 for r in requests),
                      prompt_len=max((len(r.prompt) for r in requests),
                                     default=0),
                      steps=max((r.max_new_tokens for r in requests),
                                default=0)) as span:
            return self._generate(span, requests, memory_budget_bytes,
                                  queue_depth, backlog_age_s, speculate)

    def _generate(self, span, requests, memory_budget_bytes, queue_depth,
                  backlog_age_s, speculate) -> List[Request]:
        if len(requests) > self.max_batch:
            raise ValueError(f"batch of {len(requests)} exceeds "
                             f"max_batch={self.max_batch}")
        spec = None
        if speculate:
            spec = (speculate if isinstance(speculate, SpecConfig)
                    else SpecConfig(k=int(speculate)))
            if spec.k < 1:
                raise ValueError(f"speculate needs k >= 1, got {spec.k}")
            if self._decode_chunk is None:
                raise NotImplementedError(
                    f"speculative decoding needs a chunked verify pass; "
                    f"family {self.cfg.family!r} has none")
        self.ensure_mode(
            memory_budget_bytes,
            queue_depth=len(requests) if queue_depth is None else queue_depth,
            backlog_age_s=backlog_age_s)
        span.set_metadata(rung=self.store.rung)
        params = self._params
        B = len(requests)
        S = max(len(r.prompt) for r in requests)
        n_steps = max(r.max_new_tokens for r in requests)
        if spec is not None and S + n_steps + spec.k > self.max_len:
            raise ValueError(
                f"speculative decode can write up to prompt+new+k = "
                f"{S + n_steps + spec.k} cache positions; max_len="
                f"{self.max_len} is too small")
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(requests):
            toks[i, S - len(r.prompt):] = r.prompt       # left-pad
        with obs.span("prefill", prompt_len=S):
            logits, cache = self._prefill(params,
                                          {"tokens": jnp.asarray(toks)})
        self.stats.prefills += 1
        with obs.span("cache_rehome"):
            # re-home the cache into a max_len buffer
            full = self.model.make_cache(
                B, self.max_len, dtype=jnp.dtype(self.cfg.compute_dtype))
            for key, v in cache.items():
                if key == "pos":
                    full["pos"] = v
                elif key in ("k", "v") and v.shape[-3] == S:
                    full[key] = jax.lax.dynamic_update_slice(
                        full[key].astype(v.dtype), v, (0,) * v.ndim)
                else:
                    full[key] = v
            cache = full
            self._kv_ingest(cache, S)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        if spec is not None:
            SpeculativeDecoder(self, spec).decode(
                requests, params, cache, next_tok, pos=S)
            return requests
        # The host reads each step's tokens one step behind: step t's
        # decode is queued on the device before the host waits for the
        # tokens step t-1 produced, in one transfer for all rows.
        next_tok.copy_to_host_async()
        for step in range(n_steps):
            with obs.span("decode_step", step=step):
                logits, cache = self._decode(params, {"tokens": next_tok},
                                             cache)
                nxt = jnp.argmax(logits[:, -1, :],
                                 axis=-1)[:, None].astype(jnp.int32)
            nxt.copy_to_host_async()
            self.stats.decode_steps += 1
            live = [(i, r) for i, r in enumerate(requests)
                    if len(r.out_tokens) < r.max_new_tokens]
            with obs.span("token_sync", step=step, rows=len(live)):
                host = np.asarray(next_tok)
                self.stats.token_pulls += 1
                for i, r in live:
                    r.out_tokens.append(int(host[i, 0]))
            next_tok = nxt
        if "expert_rows" in cache:
            self._expert_load(cache["expert_rows"], n_steps)
        self.last_profile = DecodeProfile(
            steps=n_steps, verify_bytes=self.store.resident_bytes())
        return requests

    def _expert_load(self, rows, steps: int) -> None:
        """Read the batch's count of rows routed to each held expert, in
        one transfer once its last token is read, and keep it."""
        with obs.span("expert_load", held=int(rows.shape[-1])) as span:
            rows = np.asarray(rows)
            per_step = rows / max(steps, 1)
            span.set_metadata(rows_max=float(per_step.max()),
                              rows_mean=float(per_step.mean()))
        total = self.stats.expert_rows
        self.stats.expert_rows = rows if total is None else total + rows


class SpeculativeDecoder:
    """Draft/verify round state machine (DESIGN.md Sec. 15).

    The nesting ladder makes the draft model FREE: the part-bit rung is
    a prefix of the packed streams already resident for the full-bit
    rung, so drafting re-reads fewer bytes of the same artifact - no
    second model, no extra HBM, and the one shared KV cache serves both
    phases (draft-rung K/V written at the drafted positions is always
    overwritten by the verify chunk before any later query can attend
    to it).

    One round from cache position ``pos`` with pending token ``t``:

      1. DRAFT   - k sequential decode steps with the draft-stamped
                   params produce d_1..d_k (greedy argmax each).
      2. VERIFY  - rewind to ``pos``; ONE chunked full-residency pass
                   over [t, d_1..d_k] scores every position.
      3. ACCEPT  - per row, the longest prefix of drafts matching the
                   verify argmaxes; the BATCH accepts the minimum m over
                   live real rows (shapes and the shared position scalar
                   stay static), emits d_1..d_m plus the verify argmax
                   at position m (correction or bonus token - every
                   round advances at least one token), and resumes from
                   ``pos + m + 1``.

    Because the verify pass reproduces sequential full-bit decode
    bit-for-bit (chunked attention sees identical masked key sets) and
    every emitted token is a verify argmax or a draft that matched one,
    the emitted sequence IS the full-bit greedy sequence."""

    def __init__(self, engine: ServeEngine, spec: SpecConfig):
        self.engine = engine
        self.spec = spec
        self.draft_rungs = engine._draft_rungs(spec)
        self.draft_params = engine.store.params_for(self.draft_rungs)
        self.draft_bytes = engine.store.assignment_resident_bytes(
            RungAssignment(default=0, exact=tuple(self.draft_rungs.items())))

    def decode(self, requests: List[Request], params, cache, first_tok,
               pos: int) -> None:
        eng, k = self.engine, self.spec.k
        stats = eng.stats
        verify_bytes = eng.store.resident_bytes()
        for i, r in enumerate(requests):
            if len(r.out_tokens) < r.max_new_tokens:
                r.out_tokens.append(int(first_tok[i, 0]))
        t_last = first_tok                       # emitted, not yet in cache
        rounds = draft_steps = drafted = accepted = 0

        def live(r):
            return len(r.out_tokens) < r.max_new_tokens

        while any(live(r) for r in requests):
            # 1. draft: k greedy steps at the draft rung, shared cache
            cur = t_last
            drafts = []
            for _ in range(k):
                logits, cache = eng._decode(self.draft_params,
                                            {"tokens": cur}, cache)
                cur = jnp.argmax(logits[:, -1, :],
                                 axis=-1)[:, None].astype(jnp.int32)
                drafts.append(cur)
            draft_steps += k
            d = jnp.concatenate(drafts, axis=1)             # (B, k)
            # 2. verify: ONE full-residency chunk over [t, d_1..d_k].
            # Rung-aware rewind first (DESIGN.md Sec. 16): nested pages
            # past ``pos`` are retired without re-fetching paged-out
            # deltas; the dense cache just has its position moved back.
            eng._kv_rewind(pos)
            cache["pos"] = jnp.asarray(pos, jnp.int32)      # rewind
            chunk = jnp.concatenate([t_last, d], axis=1)    # (B, k+1)
            vlogits, cache = eng._decode_chunk(params, {"tokens": chunk},
                                               cache)
            rounds += 1
            vnext = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)  # (B,k+1)
            # 3. accept the longest matching prefix (batch-min over the
            # rows still generating; finished rows must not throttle)
            dn, vn = np.asarray(d), np.asarray(vnext)
            match = dn == vn[:, :k]
            m_row = np.where(match.all(axis=1), k, match.argmin(axis=1))
            rows = [i for i, r in enumerate(requests) if live(r)]
            m = int(min(m_row[i] for i in rows))
            n_real = sum(1 for i in rows if requests[i].uid >= 0)
            drafted += k * n_real
            accepted += m * n_real
            for i, r in enumerate(requests):
                for t in [*dn[i, :m], vn[i, m]]:
                    if live(r):
                        r.out_tokens.append(int(t))
            t_last = vnext[:, m:m + 1]
            pos += m + 1
            cache["pos"] = jnp.asarray(pos, jnp.int32)
        stats.spec_rounds += rounds
        stats.spec_drafted += drafted
        stats.spec_accepted += accepted
        stats.spec_rejected += drafted - accepted
        eng.last_profile = DecodeProfile(
            draft_steps=draft_steps, verify_passes=rounds,
            draft_bytes=self.draft_bytes, verify_bytes=verify_bytes,
            drafted=drafted, accepted=accepted)
