"""Per-layer metric arithmetic shared by the readers in ``metrics/``.

A reader gets the context of one run (``Context``) and returns a
number, or None when the run has nothing for it to read; the harness
then leaves the metric out.  Trace-based readers look only at the
batches that ran wholly inside the traced window.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import measure
import devtrace as tr

DECODE_MODULE = "decode_step"
# the program's packed Pallas kernels, as their custom calls are named
# in the compiled step (``ladder_matmul.43``)
KERNELS = ("packed_matmul", "nested_matmul", "ladder_matmul")


@dataclass
class Context:
    window: object                  # harness.Window
    B: int                          # batch rows per step
    arch: object                    # the configuration's bench/arch module
    sizes: object                   # arch.sizes() of the configuration
    bits: tuple
    peaks: Optional[Dict] = None    # this device's row of peaks.json
    events: Optional[Dict] = None   # trace.load() of the traced run
    reduced: Optional[Dict] = None  # trace.reduce() of those events

    def traced_batches(self) -> List:
        return [b for b in self.window.batches if b.traced]

    def decode_modules(self) -> List:
        if not self.events:
            return []
        return tr.modules(self.events, DECODE_MODULE, self.reduced["t0"],
                          self.reduced["t1"])


def idle_slot_share(ctx: Context) -> Optional[float]:
    return measure.idle_slot_share(ctx.window, ctx.B)


def page_in_gbps(ctx: Context) -> Optional[float]:
    return measure.page_in_gbps(ctx.window)


def decode_step_ms(ctx: Context) -> Optional[float]:
    """Mean device time of one decode-step module execution."""
    mods = ctx.decode_modules()
    return sum(d for _, _, d in mods) / len(mods) / 1e6 if mods else None


def step_mfu(ctx: Context) -> Optional[float]:
    """Model FLOPs of the real rows of the traced decode steps over the
    decode steps' device time times the bf16 peak, in percent."""
    mods = ctx.decode_modules()
    if not mods:
        return None
    flops = sum(ctx.arch.decode_flops(ctx.sizes, b.rows, b.steps)
                for b in ctx.traced_batches())
    secs = sum(d for _, _, d in mods) / 1e9
    return 100.0 * flops / (secs * ctx.peaks["bf16_flops"]) if flops else None


def kernel_roofline(ctx: Context) -> Optional[float]:
    """Least time of the packed matmuls of the traced decode steps over
    their summed device time, in percent."""
    mods = ctx.decode_modules()
    if not mods:
        return None
    kernel_ns = tr.ops_inside(ctx.events, mods,
                              lambda n: n.split(".")[0] in KERNELS)
    least = sum(b.steps * ctx.arch.decode_step_least_s(
        ctx.sizes, ctx.bits, b.rung, ctx.B, ctx.peaks)
        for b in ctx.traced_batches())
    return 100.0 * least / (kernel_ns / 1e9) if kernel_ns > 0 else None


def device_idle(ctx: Context) -> Optional[float]:
    """Percent of the traced window in which no device operation ran."""
    if not ctx.reduced or ctx.reduced["window_s"] <= 0:
        return None
    r = ctx.reduced
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
