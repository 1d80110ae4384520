"""Boot the served model, drive a measured window, and judge what it served.

The window drives ``ServeEngine.generate`` through a wall-clock
admission loop: the oldest waiting request opens a batch, which takes
waiting requests in arrival order up to ``max_batch``, waits
``admit_wait_s`` past its oldest request for more, and is then padded
with filler clones (uid -1) to one batch shape.  With the mix's
``batching`` at ``"same_length"`` a batch takes only requests of its
opening request's prompt length: the engine left-pads shorter prompts
and attends the padding (it has no pad mask), so in a batch of mixed
lengths (``"fifo"``) an answer depends on the batch it landed in.
Where the mix cycles rungs, the switch runs at the batch boundary through
``engine.ensure_mode`` and is timed until the new parameters are on
the device, so the ``generate`` call after it finds nothing to move.

Every real request's ``out_tokens`` stamps ``time.perf_counter()`` as
each token is appended.  The engine appends a token only once it holds
it on the host, so a stamp is when a streaming client could have it.
"""
from __future__ import annotations

import gc
import json
import os
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

import arch
import traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"


class StampedList(list):
    """A list that records ``time.perf_counter()`` for every element as
    it is added, whichever way it grows."""

    def __init__(self, *a):
        super().__init__(*a)
        self.stamps: List[float] = [time.perf_counter()] * len(self)

    def append(self, x):
        self.stamps.append(time.perf_counter())
        super().append(x)

    def extend(self, xs):
        xs = list(xs)
        self.stamps.extend([time.perf_counter()] * len(xs))
        super().extend(xs)

    def __iadd__(self, xs):
        self.extend(xs)
        return self


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------
@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def arch(self):
        """The configuration's architecture module (``bench/arch/``)."""
        return arch.load(self.config)

    @property
    def sizes(self):
        return self.arch.sizes(self.config)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``, with its configuration
    and traffic files read."""
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    w = {x["name"]: x for x in spec["workloads"]}.get(workload)
    if w is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    c = {x["name"]: x for x in spec["configs"]}[w["config"]]
    return Cell(workload, w["chips"], traffic.load(ROOT / c["file"]),
                traffic.load(BENCH / "traffic" / f"{w['traffic']}.json"),
                [m for m in spec["end_to_end"] if _applies(m, workload)],
                [m for m in spec["per_layer"] if _applies(m, workload)])


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def artifact_dir(config: Dict) -> Path:
    bits = "-".join(str(b) for b in config["quant_bits"])
    return CACHE / "artifacts" / (f"{config['name']}-w{config['weight_seed']}"
                                  f"-b{bits}-{config['quant_rounding']}")


def build_artifact(config: Dict, path: Path) -> None:
    """Make the weights on the device, nest them on the ladder one leaf
    at a time (each bf16 leaf is dropped once it is packed, so the peak
    is the bf16 tree plus one leaf's work), and save the artifact the
    deployed engine boots from."""
    import jax
    from repro.api import quantize, save_artifact
    a = arch.load(config)
    s = a.sizes(config)
    params = jax.jit(lambda: a.program_params(config["weight_seed"], s,
                                              config["weights"]))()
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    del params
    recipe = a.recipe(config)
    packed = []
    for i, (where, _) in enumerate(flat):
        keys = [p.key for p in where]
        one = flat[i][1]
        flat[i] = None
        for k in reversed(keys):          # a one-leaf tree at the same path
            one = {k: one}
        one = quantize(one, recipe)
        for k in keys:
            one = one[k]
        packed.append(one)
    save_artifact(jax.tree_util.tree_unflatten(treedef, packed), str(path),
                  recipe)


def enable_cache(cache: Path) -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout, handed to the program's own cache switch."""
    import jax
    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache
    os.environ[ENV_VAR] = str(cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return enable_compile_cache()


class CompileCounter:
    """Counts traces and compiles JAX reports while ``on``."""
    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on = False
        self.traces = self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on:
            self.traces += name == self.TRACE
            self.compiles += name == self.COMPILE


@dataclass
class Batch:
    index: int
    rung: int
    start: float
    steps: int
    rows: List[tuple]            # (prompt tokens, answer tokens) of real rows
    traced: bool = False
    end: float = 0.0


@dataclass
class Window:
    """What one measured window did."""
    t0: float
    t1: float
    requests: List[Dict] = field(default_factory=list)
    batches: List[Batch] = field(default_factory=list)
    switches: List[Dict] = field(default_factory=list)
    traces: int = 0
    compiles: int = 0
    queue_at_end: int = 0
    depth: List[tuple] = field(default_factory=list)   # (time, waiting)


class Server:
    """The deployed engine of one cell, booted from its artifact."""

    def __init__(self, cell: Cell, cache: Path = CACHE):
        import jax
        from repro.api import ServeEngine
        self.cell, self.mix = cell, cell.mix
        self.sizes = cell.sizes
        self.bits = tuple(cell.config["quant_bits"])
        enable_cache(cache / "jax")
        self.counter = CompileCounter()
        art = artifact_dir(cell.config) if cache == CACHE else (
            cache / "artifact")
        self.cold = not (art / "manifest.json").exists()
        if self.cold:
            build_artifact(cell.config, art)
        self.engine = ServeEngine.from_artifact(
            cell.arch.program_config(cell.config), str(art),
            max_batch=self.mix["max_batch"], max_len=self.mix["max_len"])
        self.store = self.engine.store
        self.top = self.store.num_rungs - 1
        self.rungs = self.mix["rung_cycle"] or [self.top]
        self.warm()
        jax.block_until_ready(self.store.params())

    def budget(self, rung: int) -> Optional[int]:
        return None if rung == self.top else self.store.rung_resident_bytes(
            rung)

    def warm(self) -> None:
        """Run every (rung, prompt bucket) the cell serves once, at the
        full batch, highest rung first; end on the cycle's first rung."""
        from repro.api import Request
        B = self.mix["max_batch"]
        for r in sorted(set(self.rungs), reverse=True):
            self.engine.ensure_mode(self.budget(r))
            for S in self.mix["buckets"]:
                reqs = [Request(-1, np.ones(S, np.int32), 2)
                        for _ in range(B)]
                self.engine.generate(reqs, memory_budget_bytes=self.budget(r))
        self.engine.ensure_mode(self.budget(self.rungs[0]))

    # -- the window -----------------------------------------------------
    def window(self, seed: int, seconds: float, *, trace_dir: str = "",
               rate: Optional[float] = None,
               force_rung: Optional[int] = None) -> Window:
        """Serve the mix for ``seconds``; ``trace_dir`` profiles the
        mix's trace span into that directory.  ``force_rung`` serves every
        batch at that rung while labelling it with the cycle's rung (the
        program's lower-precision path in the place of the cell's)."""
        import jax
        from repro.api import Request
        mix, B, eng = self.mix, self.mix["max_batch"], self.engine
        V = self.sizes.vocab
        closed = mix["loop"] == "closed"
        specs = [] if closed else traffic.open_loop(mix, seed, seconds, V,
                                                    rate)
        clock = time.perf_counter
        tr = mix["trace"]
        tracing, traced_span, trace_t0 = False, None, 0.0
        self.counter.traces = self.counter.compiles = 0
        self.counter.on = True
        t0 = clock()
        w = Window(t0=t0, t1=t0 + seconds)
        queue: List[Dict] = []
        nxt = 0
        while True:
            now = clock()
            if now >= w.t1:
                break
            if trace_dir and not tracing and traced_span is None and (
                    now >= t0 + tr["start_s"]):
                jax.profiler.start_trace(trace_dir)
                traced_span = jax.profiler.TraceAnnotation("bench.traced")
                traced_span.__enter__()
                tracing, trace_t0 = True, now
            elif tracing and now >= trace_t0 + tr["seconds"]:
                traced_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
            k = len(w.batches)
            if closed:
                batch = [self._request(s, now) for s in
                         traffic.closed_batch(mix, seed, k, V)]
                w.requests += batch
            else:
                while nxt < len(specs) and t0 + specs[nxt].due_s <= now:
                    queue.append(self._request(specs[nxt], t0 + specs[nxt].due_s))
                    w.requests.append(queue[-1])
                    nxt += 1
                if not queue:
                    wake = (t0 + specs[nxt].due_s if nxt < len(specs)
                            else w.t1)
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        time.sleep(max(0.0, min(wake, w.t1) - clock()))
                    continue
                with jax.profiler.TraceAnnotation("bench.admit"):
                    fits = self._admits(queue[0])
                    deadline = queue[0]["due"] + mix["admit_wait_s"]
                    while (sum(map(fits, queue)) < B and nxt < len(specs)
                           and t0 + specs[nxt].due_s <= deadline):
                        time.sleep(max(0.0, t0 + specs[nxt].due_s - clock()))
                        queue.append(self._request(specs[nxt],
                                                   t0 + specs[nxt].due_s))
                        w.requests.append(queue[-1])
                        nxt += 1
                    batch = [r for r in queue if fits(r)][:B]
                    taken = {id(r) for r in batch}
                    queue = [r for r in queue if id(r) not in taken]
                    w.depth.append((clock(), len(queue)))
            rung = self.rungs[k % len(self.rungs)]
            serve = rung if force_rung is None else force_rung
            if self.store.rung != serve:
                self._switch(w, serve)
            reqs = [r["req"] for r in batch]
            reqs += [Request(-1, reqs[-1].prompt, reqs[-1].max_new_tokens)
                     for _ in range(B - len(reqs))]
            start = clock()
            with jax.profiler.TraceAnnotation("bench.generate"):
                eng.generate(reqs, memory_budget_bytes=self.budget(serve))
            for r in batch:
                r["rung"], r["batch"] = rung, k
            w.batches.append(Batch(
                k, rung, start, max(x.max_new_tokens for x in reqs),
                [(len(r["req"].prompt), r["req"].max_new_tokens)
                 for r in batch], traced=tracing, end=clock()))
        if tracing:
            traced_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.counter.on = False
        w.traces, w.compiles = self.counter.traces, self.counter.compiles
        w.queue_at_end = len(queue) + sum(
            1 for s in specs[nxt:] if t0 + s.due_s < w.t1)
        return w

    def _admits(self, head: Dict):
        """Which waiting requests may join the batch ``head`` opens."""
        if self.mix["batching"] == "fifo":
            return lambda r: True
        S = len(head["req"].prompt)
        return lambda r: len(r["req"].prompt) == S

    @staticmethod
    def _request(spec: traffic.Spec, due: float) -> Dict:
        from repro.api import Request
        return {"req": Request(spec.uid, spec.prompt, spec.max_new,
                               out_tokens=StampedList()),
                "due": due, "rung": None, "batch": None}

    def _switch(self, w: Window, rung: int) -> None:
        import jax
        ledger = self.store.ledger
        ev0 = len(ledger.events)
        with jax.profiler.TraceAnnotation("bench.switch"):
            a = time.perf_counter()
            self.engine.ensure_mode(self.budget(rung))
            jax.block_until_ready(self.store.params())
            b = time.perf_counter()
        w.switches.append({"start": a, "seconds": b - a, "to": rung,
                           "events": [tuple(e) for e in ledger.events[ev0:]],
                           "rung_after": self.store.rung})

    def close(self) -> None:
        """Drop the engine, its store and the device buffers they hold."""
        self.engine = self.store = None
        gc.collect()
