"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --steps 200 --seq 512 --batch 8 --ckpt-dir /tmp/ckpt [--smoke]

Production control flow on a laptop: real config system, synthetic data
pipeline, pjit'd train step with explicit shardings, checkpoint/restart
(resume is automatic if the checkpoint dir has a committed step), step
monitoring with straggler flagging, loss logging.  ``--smoke`` swaps in the
reduced config of the same family.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import ParallelConfig, TrainConfig, get_config
from repro.distributed.elastic import StepMonitor, run_step_resilient
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.training import checkpoint as ckpt
from repro.training import data as data_mod
from repro.training import optimizer as opt
from repro.training import train_step as ts

SMOKE_MODULES = {
    "jamba-v0.1-52b": "jamba_v01_52b", "stablelm-1.6b": "stablelm_1_6b",
    "llama3.2-1b": "llama32_1b", "qwen3-1.7b": "qwen3_1_7b",
    "qwen3-4b": "qwen3_4b", "qwen2-vl-72b": "qwen2_vl_72b",
    "mamba2-1.3b": "mamba2_1_3b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "phi3.5-moe-42b-a6.6b": "phi35_moe_42b",
    "hubert-xlarge": "hubert_xlarge",
}


def resolve_config(arch: str, smoke: bool):
    if smoke:
        mod = importlib.import_module("repro.configs."
                                      + SMOKE_MODULES[arch])
        return mod.reduced()
    return get_config(arch)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--log-file", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = resolve_config(args.arch, args.smoke)
    mesh = make_local_mesh(model=args.model_parallel)
    jax.set_mesh(mesh)
    pcfg = ParallelConfig(remat="none", compute_dtype="float32",
                          param_dtype="float32")
    tcfg = TrainConfig(seq_len=args.seq, global_batch=args.batch,
                       lr=args.lr, steps=args.steps,
                       microbatch=args.microbatch, seed=args.seed)

    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt_state = opt.init_opt_state(params)
    step0 = 0
    if args.ckpt_dir:
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            params = ckpt.restore(args.ckpt_dir, last,
                                  {"params": params,
                                   "opt": opt_state})
            params, opt_state = params["params"], params["opt"]
            step0 = last
            print(f"resumed from step {step0}")

    _, shardings_for, jit_step = ts.make_train_step(cfg, pcfg, tcfg, mesh)
    psh, osh = shardings_for(jax.eval_shape(lambda: params))
    fn = jit_step(psh, osh, None)   # batch placement inferred on local mesh

    params = jax.device_put(params, psh)
    opt_state = jax.device_put(opt_state, osh)
    pipe = data_mod.SyntheticLM(cfg.vocab, args.seq, args.batch,
                                seed=args.seed)
    mon = StepMonitor(on_straggler=lambda s, t, m: print(
        f"[straggler] step {s}: {t:.2f}s vs median {m:.2f}s"))
    logf = open(args.log_file, "a") if args.log_file else None

    def make_batch(step):
        b = pipe.batch(step)
        if not cfg.embed_inputs:
            eb = data_mod.embeds_batch(step, args.batch, args.seq,
                                       cfg.d_model,
                                       pos3=(cfg.pos_dims == 3))
            b = dict(eb, labels=b["labels"])
        return jax.tree.map(jnp.asarray, b)

    def restore_latest():
        last = ckpt.latest_step(args.ckpt_dir)
        tree = ckpt.restore(args.ckpt_dir, last,
                            {"params": params, "opt": opt_state})
        return (jax.device_put(tree["params"], psh),
                jax.device_put(tree["opt"], osh))

    t_start = time.time()
    for step in range(step0, args.steps):
        batch = make_batch(step)

        def do(p, o, b):
            return mon.timed(step, fn, p, o, b)

        if args.ckpt_dir:
            params, opt_state, metrics = run_step_resilient(
                do, None, lambda: restore_latest() + (batch,),
                params, opt_state, batch)
        else:
            params, opt_state, metrics = do(params, opt_state, batch)

        if step % args.log_every == 0 or step == args.steps - 1:
            rec = dict(step=step, loss=float(metrics["loss"]),
                       grad_norm=float(metrics["grad_norm"]),
                       lr=float(metrics["lr"]),
                       elapsed=round(time.time() - t_start, 1))
            print(json.dumps(rec), flush=True)
            if logf:
                logf.write(json.dumps(rec) + "\n")
                logf.flush()
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, step + 1,
                      {"params": jax.device_get(params),
                       "opt": jax.device_get(opt_state)})
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps,
                  {"params": jax.device_get(params),
                   "opt": jax.device_get(opt_state)})
    print("TRAINING DONE")
    return 0


if __name__ == "__main__":
    sys.exit(main())
