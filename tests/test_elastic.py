"""Elastic scaling: checkpoints written on one mesh restore onto another.

Runs in a subprocess with 4 host devices (the main process stays at 1).
The checkpoint is saved from a (2,2) mesh and restored with (1,4) and
(4,1) layouts plus a plain single-device restore - values must be
identical in all cases.
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.checkpoint import CheckpointManager

mesh_kw = {"axis_types": (jax.sharding.AxisType.Auto,) * 2}
meshA = jax.make_mesh((2, 2), ("data", "model"), **mesh_kw)
meshB = jax.make_mesh((1, 4), ("data", "model"), **mesh_kw)

tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        "b": jnp.arange(8, dtype=jnp.bfloat16)}
specs = {"w": P("data", "model"), "b": P()}
sharded = {k: jax.device_put(v, NamedSharding(meshA, specs[k]))
           for k, v in tree.items()}

d = tempfile.mkdtemp()
mgr = CheckpointManager(d)
mgr.save(1, sharded, extra={"mesh": "2x2"})

tmpl = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in tree.items()}
# restore onto a different mesh shape
restB, _ = mgr.restore(tmpl, mesh=meshB, pspecs=specs)
assert restB["w"].sharding.mesh.shape["model"] == 4
np.testing.assert_array_equal(np.asarray(restB["w"]), np.asarray(tree["w"]))
# plain single-layout restore
restC, _ = mgr.restore(tmpl)
np.testing.assert_array_equal(np.asarray(restC["b"], np.float32),
                              np.asarray(tree["b"], np.float32))
print("ELASTIC-OK")
"""


@pytest.mark.slow
def test_checkpoint_mesh_reshard():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ELASTIC-OK" in proc.stdout
