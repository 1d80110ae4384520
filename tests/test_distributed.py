"""Distributed tests on a small host mesh.

jax locks the device count at first init, so these run in a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=4 (the main pytest
process keeps 1 device, per the dry-run isolation requirement).

On single-core hosts XLA:CPU in-process collectives starve their 40 s
rendezvous (one Eigen worker thread cannot run two device thunks
concurrently), so execution is attempted only with >= 4 cores; otherwise
the test still verifies the sharded train/serve steps COMPILE and the
data/parameter shardings resolve on the mesh (the execution semantics are
covered by the 1-device-mesh shard_map tests in test_substrate.py).
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.distributed import steps as steps_lib
from repro.data import DataConfig, SyntheticLM
from repro.optim import adamw

cfg = get_config("qwen2-1.5b").reduced()
shape = ShapeConfig("t", "train", 16, 4, microbatch=2)
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

jitted, specs = steps_lib.build_train_step(cfg, shape, mesh)
model = specs["model"]
params = model.init(jax.random.PRNGKey(0))
opt = adamw.init_state(params)
data = SyntheticLM(DataConfig(cfg.vocab_size, shape.seq_len,
                              shape.global_batch), 0, 1)
batch0 = {k: jnp.asarray(v) for k, v in data.batch(0).items()}

can_execute = (os.cpu_count() or 1) >= 4
compiled = jitted.lower(params, opt, batch0, jnp.asarray(0)).compile()
print("TRAIN-COMPILE-OK")

if can_execute:
    losses = []
    for s in range(6):
        batch = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
        params, opt, metrics = jitted(params, opt, batch, jnp.asarray(s))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    print("TRAIN-EXEC-OK", losses[0], losses[-1])

shape_d = ShapeConfig("d", "decode", 32, 4)
jd, sd = steps_lib.build_decode_step(cfg, shape_d, mesh)
cache = sd["model"].make_cache(4, 32)
tok = jnp.zeros((4, 1), jnp.int32)
fp32_params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
jd.lower(fp32_params, {"tokens": tok}, cache).compile()
print("SERVE-COMPILE-OK")
if can_execute:
    logits, cache = jd(fp32_params, {"tokens": tok}, cache)
    assert logits.shape == (4, 1, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits)))
    print("SERVE-EXEC-OK")
"""


@pytest.mark.slow
def test_sharded_train_and_serve_steps():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "TRAIN-COMPILE-OK" in proc.stdout
    assert "SERVE-COMPILE-OK" in proc.stdout
    if (os.cpu_count() or 1) >= 4:
        assert "TRAIN-EXEC-OK" in proc.stdout
        assert "SERVE-EXEC-OK" in proc.stdout


_MOE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.nesting import NestedTensor
from repro.distributed import steps as steps_lib

cfg = get_config("dbrx-132b").reduced()
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)


def stack_shapes(tree):
    # each expert leaf's array shapes with the layer axis taken off
    out = set()
    for leaf in jax.tree.leaves(tree["blocks"]["moe"]["experts"]):
        out.add(",".join(map(str, leaf.shape[1:])))
    return out


def gathered(hlo, shapes):
    return [l for l in hlo.splitlines() if "all-gather" in l
            and any(f"[{s}]" in l or f",{s}]" in l for s in shapes)]


shape_p = ShapeConfig("p", "prefill", 16, 4)
jp, sp = steps_lib.build_prefill_step(cfg, shape_p, mesh)
params = jax.eval_shape(sp["model"].init, jax.random.PRNGKey(0))
hlo = jp.lower(params, {"tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32)}
               ).compile().as_text()
assert not gathered(hlo, stack_shapes(params)), gathered(hlo, stack_shapes(params))
print("PREFILL-MOE-COMPILE-OK")

for quant in (None, "nested"):
    shape_d = ShapeConfig("d", "decode", 32, 4)
    jd, sd = steps_lib.build_decode_step(cfg, shape_d, mesh, quant=quant)
    ap = sd["abstract_params"]
    cache = jax.eval_shape(lambda: sd["model"].make_cache(4, 32))
    hlo = jd.lower(ap, {"tokens": jax.ShapeDtypeStruct((4, 1), jnp.int32)},
                   cache).compile().as_text()
    assert "all-reduce" in hlo
    assert not gathered(hlo, stack_shapes(ap)), gathered(hlo, stack_shapes(ap))
    print("DECODE-MOE-COMPILE-OK", quant)
"""


def test_sharded_moe_serve_steps_keep_each_expert_on_its_device():
    """Reduced dbrx on a 2x2 mesh: the prefill and decode steps (bf16
    and packed weights) compile with the experts sharded over ``model``,
    and no device all-gathers an expert stack it does not hold."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _MOE_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PREFILL-MOE-COMPILE-OK" in proc.stdout
    assert "DECODE-MOE-COMPILE-OK None" in proc.stdout
    assert "DECODE-MOE-COMPILE-OK nested" in proc.stdout
