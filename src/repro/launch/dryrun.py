import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " \
    + os.environ.get("XLA_FLAGS", "")
os.environ["JAX_PLATFORMS"] = "cpu"   # a compile-only tool: never take a chip
# ^ MUST precede any jax import: device count locks at first backend init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each runnable cell this lowers the real jitted step function
(train_step for train_*, prefill for prefill_*, decode_step for
decode_*/long_*) against ShapeDtypeStruct inputs on the production mesh,
compiles it (SPMD partitioning actually runs), and records:

  memory_analysis()   -> per-device bytes (proves the cell fits a v5e)
  cost_analysis()     -> HLO FLOPs / bytes for the roofline
  collective traffic  -> loop-aware HLO parse (repro.roofline.hlo_parse)

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k \
      [--multi-pod] [--out out.json] [--opt '{"remat":"full"}']
  python -m repro.launch.dryrun --list
"""
import argparse
import dataclasses
import json
import sys

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import (ModelConfig, ParallelConfig, ServeConfig,
                          TrainConfig, get_config)
from repro.distributed.sharding import fsdp_extend_tree, sanitize_tree
from repro.launch.mesh import make_production_mesh

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

# skip ledger (see DESIGN.md §Arch-applicability)
LONG_OK = {"jamba-v0.1-52b", "mamba2-1.3b"}       # sub-quadratic families
ENCODER_ONLY = {"hubert-xlarge"}                  # no decode step


def runnable(arch: str, shape: str) -> tuple[bool, str]:
    if shape in ("decode_32k", "long_500k") and arch in ENCODER_ONLY:
        return False, "encoder-only: no decode step"
    if shape == "long_500k" and arch not in LONG_OK:
        return False, "pure full-attention: 500k decode needs sub-quadratic"
    return True, ""


def input_specs(cfg: ModelConfig, shape: str):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    info = SHAPES[shape]
    B, S = info["batch"], info["seq"]
    kind = info["kind"]
    tb = (B, S) if kind != "decode" else (B, 1)
    specs = {}
    if cfg.embed_inputs:
        specs["tokens"] = jax.ShapeDtypeStruct(tb, jnp.int32)
    else:
        specs["embeds"] = jax.ShapeDtypeStruct(tb + (cfg.d_model,),
                                               jnp.bfloat16)
    if cfg.pos_dims == 3:
        specs["positions"] = jax.ShapeDtypeStruct(tb + (3,), jnp.int32)
    if kind == "train":
        specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    return specs


def _shardings(mesh, tree_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_specs(pcfg, specs, axis_sizes):
    from repro.models.model import batch_axes as _ba
    batch_axes = _ba(pcfg)
    raw = {k: P(*((tuple(a for a in batch_axes if a),)
                  + (None,) * (v.ndim - 1)))
           for k, v in specs.items()}
    return sanitize_tree(raw, specs, axis_sizes)


def lower_cell(arch: str, shape: str, *, multi_pod: bool = False,
               overrides: dict | None = None, microbatch: int = 4):
    """Returns (lowered, meta) for one cell."""
    from repro.models import model as M
    from repro.serving import decode
    from repro.training import optimizer as opt
    from repro.training import train_step as ts

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    jax.set_mesh(mesh)   # ambient mesh: activation constraints apply
    info = SHAPES[shape]
    kind = info["kind"]
    pcfg = ParallelConfig(
        pod_axis="pod" if multi_pod else None,
        remat="full" if kind == "train" else "none",
        seq_shard_decode=(kind in ("decode",)),
        param_dtype="float32" if kind == "train" else "bfloat16",
        compute_dtype="bfloat16",
    )
    if overrides:
        pcfg = dataclasses.replace(pcfg, **overrides)

    axis_sizes = dict(mesh.shape)
    params_shape = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0), pcfg.param_dtype))
    pspec = M.param_specs(cfg, pcfg, params_shape)
    if kind == "train":   # FSDP/ZeRO-3: params + moments sharded over data
        pspec = fsdp_extend_tree(pspec, params_shape, axis_sizes,
                                 pcfg.data_axis)
    pspec = sanitize_tree(pspec, params_shape, axis_sizes)
    psh = _shardings(mesh, pspec)
    specs = input_specs(cfg, shape)
    bsh = _shardings(mesh, batch_specs(pcfg, specs, axis_sizes))

    if kind == "train":
        tcfg = TrainConfig(seq_len=info["seq"], global_batch=info["batch"],
                           microbatch=microbatch)
        opt_shape = jax.eval_shape(lambda: opt.init_opt_state(params_shape))
        osh = {"mu": psh, "nu": psh, "step": NamedSharding(mesh, P())}
        step, _, jit_step = ts.make_train_step(cfg, pcfg, tcfg, mesh)
        fn = jit_step(psh, osh, bsh)
        lowered = fn.lower(params_shape, opt_shape, specs)
    elif kind == "prefill":
        cache_shape = jax.eval_shape(
            lambda: M.init_cache(cfg, info["batch"], info["seq"]))
        cspec = M.cache_specs(
            cfg, dataclasses.replace(pcfg, seq_shard_decode=True),
            cache_shape)
        csh = _shardings(mesh, sanitize_tree(cspec, cache_shape,
                                             axis_sizes))

        def step(params, batch):
            return decode.prefill(cfg, pcfg, params, batch)

        fn = jax.jit(step, in_shardings=(psh, bsh),
                     out_shardings=(None, csh))
        lowered = fn.lower(params_shape, specs)
    else:   # decode
        cache_shape = jax.eval_shape(
            lambda: M.init_cache(cfg, info["batch"], info["seq"]))
        cspec = M.cache_specs(cfg, pcfg, cache_shape)
        csh = _shardings(mesh, sanitize_tree(cspec, cache_shape,
                                             axis_sizes))

        def step(params, batch, cache):
            return decode.decode_step(cfg, pcfg, params, batch, cache)

        fn = jax.jit(step, in_shardings=(psh, bsh, csh),
                     out_shardings=(None, csh), donate_argnums=(2,))
        lowered = fn.lower(params_shape, specs, cache_shape)

    meta = dict(arch=arch, shape=shape, kind=kind,
                multi_pod=multi_pod, mesh=str(mesh.shape),
                microbatch=microbatch if kind == "train" else 0,
                params=cfg.param_count(),
                active_params=cfg.active_param_count())
    return lowered, meta


def analyse(lowered, meta, want_hlo=False):
    from repro.roofline.hlo_parse import collective_summary, program_totals
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # older jax: one dict per program
        cost = cost[0] if cost else {}
    txt = compiled.as_text()
    coll = collective_summary(txt)
    prog = program_totals(txt)
    out = dict(meta)
    out["memory"] = {
        k: getattr(mem, k) for k in
        ("argument_size_in_bytes", "output_size_in_bytes",
         "temp_size_in_bytes", "alias_size_in_bytes",
         "generated_code_size_in_bytes")}
    out["cost"] = {k: v for k, v in cost.items()
                   if k in ("flops", "bytes accessed") or
                   k.startswith("bytes accessed")}
    out["collectives"] = coll
    out["program"] = prog   # loop-aware per-device dot FLOPs / bytes
    if want_hlo:
        out["hlo"] = txt
    return out


def emit_result(result: dict, out_path: str | None) -> str:
    """Shared JSON emission for the dry-run entrypoints."""
    js = json.dumps(result, indent=1)
    print(js)
    if out_path:
        with open(out_path, "w") as f:
            f.write(js)
    return js


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=False)
    ap.add_argument("--shape", default="train_4k", choices=SHAPES)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--opt", default=None,
                    help="JSON dict of ParallelConfig overrides")
    ap.add_argument("--microbatch", type=int, default=4,
                    help="gradient-accumulation microbatches (train cells)")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    if args.list:
        from repro.configs import ARCH_IDS
        for a in ARCH_IDS:
            for s in SHAPES:
                ok, why = runnable(a, s)
                print(f"{a:24s} {s:12s} {'RUN' if ok else 'SKIP: ' + why}")
        return 0

    ok, why = runnable(args.arch, args.shape)
    if not ok:
        emit_result(dict(arch=args.arch, shape=args.shape, skipped=why),
                    args.out)
        return 0

    overrides = json.loads(args.opt) if args.opt else None
    lowered, meta = lower_cell(args.arch, args.shape,
                               multi_pod=args.multi_pod,
                               overrides=overrides,
                               microbatch=args.microbatch)
    emit_result(analyse(lowered, meta), args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
