#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU chip at full published width.

Builds qwen2-1.5b at its published config (28 layers, d_model 1536,
vocab 151936) with random weights made from a seed, nests it on the
8 > 6 > 4 ladder, and serves batched requests through
``ServeEngine.generate`` at rungs 2, 1, 0 and 2 again.  At each rung it
checks that

  * the compiled decode step holds the packed Pallas kernels
    (``tpu_custom_call`` ops);
  * the packed prefill logits agree with the same model run in float32
    on the dense dequantized weights of that rung (relative L2 error
    <= 2e-2);
  * every rung switch moved exactly the computed ``bytes(delta_k)``;
  * rung 2 served twice gives the same greedy tokens.

Run it from the root of a checkout on a machine with a TPU:

    python chip_smoke.py

It exits non-zero without a TPU and when any check fails.  The last line
of its output is one JSON object that names the device.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "qwen2-1.5b"
BITS = (8, 6, 4)
SEED = 0
REQUESTS, PROMPT_LEN, NEW_TOKENS, MAX_LEN = 4, 8, 8, 64
PHASES = ("full", "rung1", "part", "full")
# The served path computes in bf16: activations and the residual stream
# are rounded to 8 mantissa bits at every layer, which moves the last
# logits of this 28-layer model by about 1.5e-2 (relative L2) from a
# float32 reference.  A kernel that reads a wrong or missing stream
# moves them by far more: rung 0 against rung 2 differs by tens of
# percent, and the check below requires that gap to exceed this one.
MAX_REL_ERR = 2e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def count_kernels(compiled_text: str) -> int:
    return compiled_text.count('custom_call_target="tpu_custom_call"')


def peak_bytes() -> int:
    return jax.devices()[0].memory_stats()["peak_bytes_in_use"]


def timed(label: str, fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    print(f"[setup] {label}_s={time.perf_counter() - t0:.3f}", flush=True)
    return out


def serve_and_check(cfg) -> None:
    """Build, nest and serve ``cfg``; raise on the first failed check."""
    from repro.api import QuantRecipe, Request, ServeEngine, quantize
    from repro.core import NestQuantStore
    from repro.core.nesting import mode_to_rung
    from repro.models import make_model

    model = make_model(cfg)
    params = timed("init", lambda: model.init(jax.random.PRNGKey(SEED)))
    nested = timed("quantize",
                   lambda: quantize(params, QuantRecipe(bits=BITS)))
    del params                  # only the packed tree stays on the device
    print(f"[memory] peak_bytes_in_use after quantize={peak_bytes()}")
    store = NestQuantStore(nested, mode="part", dtype=jnp.float32)
    del nested
    engine = ServeEngine(cfg, store, max_batch=REQUESTS, max_len=MAX_LEN,
                         model=model)
    timed("warmup_compile", lambda: engine.warmup(PROMPT_LEN))

    top = store.num_rungs - 1
    need = [store.rung_resident_bytes(r) for r in range(store.num_rungs)]
    delta = [sum(leaf.nbytes_delta(k) for _, leaf in store.nested_leaves())
             for k in range(top)]
    prompts = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, size=(REQUESTS, PROMPT_LEN)).astype(np.int32)
    toks = jnp.asarray(prompts)
    prefill, decode, _ = engine.compiled
    # the plain reference: the same architecture in float32, on the dense
    # dequantized weights, with float32 matmuls on the chip
    dense_prefill = jax.jit(make_model(dataclasses.replace(
        cfg, compute_dtype="float32")).prefill)
    logits, errs, served = {}, {}, {}
    for phase in PHASES:
        rung = mode_to_rung(phase, store.num_rungs)
        was, ev0 = store.rung, len(store.ledger.events)
        reqs = [Request(u, prompts[u], max_new_tokens=NEW_TOKENS)
                for u in range(REQUESTS)]
        engine.generate(reqs, memory_budget_bytes=int(
            2 * need[top] if rung == top else need[rung]))
        check(store.rung == rung, f"phase {phase} serves rung {store.rung}")
        events = store.ledger.events[ev0:]
        check(len(events) == abs(rung - was),
              f"{len(events)} ledger events for rung {was} -> {rung}")
        for frm, to, page_in, page_out in events:
            k = min(frm, to)
            moved, other = (page_in, page_out) if to > frm else (page_out,
                                                                 page_in)
            check(abs(to - frm) == 1 and moved == delta[k] and other == 0,
                  f"switch {frm} -> {to} ledgered in={page_in} "
                  f"out={page_out}; bytes(delta_{k}) = {delta[k]}")
            print(f"[switch] rung {frm} -> {to}: {moved} bytes "
                  f"= bytes(delta_{k})")
        out = [r.out_tokens for r in reqs]
        check(all(len(t) == NEW_TOKENS and all(0 <= x < cfg.vocab_size
                                               for x in t) for t in out),
              f"greedy tokens {out}")
        print(f"[rung {rung}] greedy tokens {out}", flush=True)
        if rung in served:
            check(out == served[rung],
                  f"rung {rung} served again gives other tokens")
            continue
        served[rung] = out
        params = store.params()
        cache = model.make_cache(REQUESTS, MAX_LEN)
        kernels = count_kernels(decode.lower(
            params, {"tokens": toks[:, :1]}, cache).compile().as_text())
        print(f"[rung {rung}] decode step tpu_custom_call ops: {kernels}")
        check(kernels > 0, f"rung {rung} decode step runs no packed kernel")
        packed = np.asarray(prefill(params, {"tokens": toks})[0])
        dense = store.dense_params()
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(dense_prefill(dense, {"tokens": toks})[0])
        del dense
        check(np.isfinite(packed).all() and np.isfinite(ref).all(),
              f"rung {rung} logits are not finite")
        errs[rung] = rel_err(packed, ref)
        logits[rung] = packed
        print(f"[rung {rung}] prefill logits {packed.shape} packed vs float32 "
              f"dense reference: rel_l2={errs[rung]:.6e}", flush=True)
        check(errs[rung] <= MAX_REL_ERR,
              f"rung {rung} rel_l2 {errs[rung]:.3e} > {MAX_REL_ERR}")
    sep = rel_err(logits[0], logits[top])
    print(f"[rungs] rung 0 vs rung {top} logits rel_l2={sep:.6e}")
    check(sep > max(errs.values()),
          f"rung 0 and rung {top} logits differ by {sep:.3e}, no more than "
          f"the kernel-vs-reference error {max(errs.values()):.3e}")


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[cache] {enable_compile_cache()}")
    cfg = get_config(ARCH)
    print(f"[model] {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} compute={cfg.compute_dtype} bits={BITS}")
    serve_and_check(cfg)
    print(f"[memory] peak_bytes_in_use={peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
