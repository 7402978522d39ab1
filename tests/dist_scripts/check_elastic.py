"""Elastic scaling: train on 8 devices, checkpoint, resume on 4 devices.

Proves the shardings are re-derivable for a different mesh shape and the
checkpoint is mesh-independent — the slice-resize flow a 1000-node job
uses after losing a slice.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import tempfile

import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ParallelConfig, TrainConfig
from repro.configs import llama32_1b
from repro.distributed.elastic import remesh
from repro.models import model as M
from repro.training import checkpoint as ckpt
from repro.training import data as data_mod
from repro.training import optimizer as opt
from repro.training import train_step as ts

assert len(jax.devices()) == 8
cfg = llama32_1b.reduced()
pcfg = ParallelConfig(compute_dtype="float32")
tcfg = TrainConfig(seq_len=64, global_batch=8, lr=1e-3, steps=10)
pipe = data_mod.SyntheticLM(cfg.vocab, 64, 8, seed=0)

def shardings(mesh, params):
    pspec = M.param_specs(cfg, pcfg, params)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                       is_leaf=lambda x: isinstance(x, P))
    osh = {"mu": psh, "nu": psh, "step": NamedSharding(mesh, P())}
    return psh, osh

# --- phase 1: 8 devices (4 data x 2 model)
mesh8 = remesh(8, model_parallel=2)
jax.set_mesh(mesh8)
params = M.init_params(cfg, jax.random.PRNGKey(0))
state = opt.init_opt_state(params)
psh8, osh8 = shardings(mesh8, params)
params = jax.device_put(params, psh8)
state = jax.device_put(state, osh8)
step_fn, _, jit_step = ts.make_train_step(cfg, pcfg, tcfg, mesh8)
fn8 = jit_step(psh8, osh8, None)
for i in range(3):
    batch = jax.tree.map(jnp.asarray, pipe.batch(i))
    params, state, m = fn8(params, state, batch)
loss8 = float(m["loss"])
d = tempfile.mkdtemp()
ckpt.save(d, 3, {"params": jax.device_get(params),
                 "opt": jax.device_get(state)})
print("phase1 done on 8 devices, loss", loss8)

# --- phase 2: resume on 4 devices (2 data x 2 model) — simulated shrink
mesh4 = remesh(4, model_parallel=2)
jax.set_mesh(mesh4)
tree = ckpt.restore(d, 3, {"params": jax.device_get(params),
                           "opt": jax.device_get(state)})
psh4, osh4 = shardings(mesh4, tree["params"])
params4 = jax.device_put(tree["params"], psh4)
state4 = jax.device_put(tree["opt"], osh4)
fn4 = ts.make_train_step(cfg, pcfg, tcfg, mesh4)[2](psh4, osh4, None)
for i in range(3, 6):
    batch = jax.tree.map(jnp.asarray, pipe.batch(i))
    params4, state4, m4 = fn4(params4, state4, batch)
print("phase2 done on 4 devices, loss", float(m4["loss"]))
assert np.isfinite(float(m4["loss"]))
assert int(state4["step"]) == 6
print("ELASTIC OK")
