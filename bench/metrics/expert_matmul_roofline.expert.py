"""Least time of the grouped expert matmuls of traced decode steps
(the architecture's ``expert_step_least_s``) over the device time of the
``expert_*matmul`` kernels inside them (%), at each step's rung.  None
where the architecture or the trace has no expert matmul."""
import devtrace as tr


def _expert_kernel(name: str) -> bool:
    op = name.split(".")[0]
    return op.startswith("expert_") and op.endswith("matmul")


def read(ctx):
    least_s = getattr(ctx.arch, "expert_step_least_s", None)
    mods = ctx.decode_modules()
    if least_s is None or not mods:
        return None
    kernel_ns = tr.ops_inside(ctx.events, mods, _expert_kernel)
    least = sum(b.steps * least_s(ctx.sizes, ctx.bits, b.rung, ctx.B,
                                  ctx.peaks)
                for b in ctx.traced_batches())
    return 100.0 * least / (kernel_ns / 1e9) if kernel_ns > 0 else None
