"""Distributed-execution tests: actually RUN sharded steps on 8 host
devices (subprocess; the main test process keeps 1 device). This goes
beyond the dry-run's compile-only proof: it checks GSPMD numerics equal
single-device numerics for a sharded train step and a routed bank scoring.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import constant_lr
from repro.sharding import mesh_context
from repro.sharding.rules import batch_spec, param_specs
from repro.train.loop import init_train_state, make_train_step

assert len(jax.devices()) == 8, jax.devices()
mesh = make_mesh((4, 2), ("data", "model"))

cfg = get_config("llama3.2-1b").reduced(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
    vocab_size=256)
model = build_model(cfg)
state = init_train_state(model, jax.random.PRNGKey(0))
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256)
batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
step = make_train_step(model, lr_fn=constant_lr(1e-3))

# single-device reference
ref_state, ref_metrics = jax.jit(step)(state, batch)
ref_loss = float(ref_metrics["loss"])

# sharded execution on the 4x2 mesh
pshapes = jax.eval_shape(lambda: state)
sspecs = {
    "params": param_specs(pshapes["params"], mesh),
    "opt": {"m": param_specs(pshapes["params"], mesh),
            "v": param_specs(pshapes["params"], mesh), "step": P()},
    "step": P(),
}
bspecs = batch_spec(jax.eval_shape(lambda: batch), mesh)
named = lambda t: jax.tree_util.tree_map(
    lambda s: NamedSharding(mesh, s), t)
with mesh_context(mesh):
    state_sh = jax.device_put(state, named(sspecs))
    batch_sh = jax.device_put(batch, named(bspecs))
    jstep = jax.jit(step, in_shardings=(named(sspecs), named(bspecs)),
                    out_shardings=(named(sspecs), None))
    new_state, metrics = jstep(state_sh, batch_sh)
sh_loss = float(metrics["loss"])

# param agreement after one step
ref_leaves = jax.tree_util.tree_leaves(ref_state["params"])
sh_leaves = jax.tree_util.tree_leaves(jax.device_get(new_state["params"]))
max_diff = max(float(np.abs(np.asarray(a, np.float32)
                            - np.asarray(b, np.float32)).max())
               for a, b in zip(ref_leaves, sh_leaves))
print(json.dumps({"ref_loss": ref_loss, "sh_loss": sh_loss,
                  "max_param_diff": max_diff}))
"""


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(res["ref_loss"] - res["sh_loss"]) < 1e-4, res
    assert res["max_param_diff"] < 5e-4, res


PLACEMENT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import jax
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_expert_mesh
from repro.models import build_model
from repro.serve import BankedEngine

assert len(jax.devices()) == 8, jax.devices()
cfg = get_config("smollm-135m").reduced(name="placed")
model = build_model(cfg)
params = [model.init(jax.random.PRNGKey(i)) for i in range(4)]
rng = np.random.default_rng(0)
groups = {i: ([i], [rng.integers(0, 50, 5 + 3 * i)], [4])
          for i in range(4)}

def run(mesh, deferred):
    # deferred=False is the blocking serial reference (each tick
    # materialises its token); deferred=True is the overlapped
    # executor's engine protocol: enqueue everything, harvest once per
    # step with a single batched device->host transfer per wave
    bank = BankedEngine(model, params, max_len=32, mesh=mesh)
    bank.admit(groups, defer=deferred)
    if deferred:
        bank.harvest()
    while bank.n_active:
        bank.tick(defer=deferred)
        if deferred:
            bank.harvest()
    out = {(l, u): t.tolist() for l, u, t in bank.poll()}
    return out, bank.stats.host_blocks

mesh = make_expert_mesh()  # (expert=8) -> bank submesh below
from repro.serve.placement import _bank_submesh
sub, devs = _bank_submesh(4, mesh)
assert sub is not None and dict(sub.shape) == {"expert": 4}, sub
sharded_serial, blocks_serial = run(sub, False)
sharded_over, blocks_over = run(sub, True)
single, _ = run(None, False)
print(json.dumps({
    "n_devices": len(jax.devices()), "bank_devices": len(devs),
    "match": all(single[k] == sharded_serial[k] for k in single),
    "match_overlapped": all(single[k] == sharded_over[k]
                            for k in single),
    "blocks_serial": blocks_serial, "blocks_over": blocks_over}))
"""


@pytest.mark.slow
def test_banked_placement_sharded_matches_single_device():
    """A 4-expert bank sharded over 4 of 8 host devices must emit the
    same tokens as the unsharded bank — under both the blocking serial
    protocol and the overlapped executor's deferred enqueue-then-harvest
    protocol, which must also host-block strictly less (GSPMD numerics +
    async dispatch check for the serving placement path)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", PLACEMENT_SCRIPT], capture_output=True,
        text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n_devices"] == 8 and res["bank_devices"] == 4, res
    assert res["match"], res
    assert res["match_overlapped"], res
    assert res["blocks_over"] < res["blocks_serial"], res
