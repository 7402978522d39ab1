"""Batched serving driver: prefill a stream of prompt batches, decode.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --batches 3 --batch 4 --prompt-len 16 --gen 16

Production control flow: request batching, prefill+decode split, per-step
latency stats, straggler monitoring — on the local mesh.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ParallelConfig
from repro.distributed.elastic import StepMonitor
from repro.launch.mesh import make_local_mesh
from repro.launch.train import resolve_config
from repro.models import model as M
from repro.serving import decode


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batches", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = resolve_config(args.arch, args.smoke)
    mesh = make_local_mesh()
    jax.set_mesh(mesh)
    pcfg = ParallelConfig(compute_dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    mon = StepMonitor()

    for b in range(args.batches):
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)),
            jnp.int32)
        t0 = time.perf_counter()
        logits, cache = decode.prefill(cfg, pcfg, params,
                                       {"tokens": prompts})
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0
        cache = decode.extend_cache(cache, args.gen)
        tok = jnp.argmax(logits[:, -1], -1)
        lat = []
        for i in range(args.gen - 1):
            t0 = time.perf_counter()
            logits, cache = decode.decode_step(
                cfg, pcfg, params, {"tokens": tok[:, None]}, cache)
            jax.block_until_ready(logits)
            lat.append(time.perf_counter() - t0)
            mon.observe(b * args.gen + i, lat[-1])
            tok = jnp.argmax(logits[:, -1], -1)
        print(json.dumps(dict(
            batch=b, prefill_s=round(t_prefill, 4),
            decode_p50_ms=round(float(np.median(lat)) * 1e3, 2),
            decode_p99_ms=round(float(np.quantile(lat, 0.99)) * 1e3, 2),
            tokens=args.batch * args.gen)))
    print("SERVING DONE")
    return 0


if __name__ == "__main__":
    sys.exit(main())
