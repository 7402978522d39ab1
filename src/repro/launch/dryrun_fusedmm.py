import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " \
    + os.environ.get("XLA_FLAGS", "")
os.environ["JAX_PLATFORMS"] = "cpu"   # a compile-only tool: never take a chip

"""Dry-run the PAPER'S kernels on the production mesh: distributed FusedMM
at p=256, dispatched through the unified repro.core.api registry — ANY
registered algorithm, no per-family branching here.

  PYTHONPATH=src python -m repro.launch.dryrun_fusedmm \
      [--algo auto|d15|s15|d25|s25] [--c 16] \
      [--elision auto|none|reuse|fused] \
      [--m 1048576] [--r 256] [--nnz-row 32] [--out out.json]

This is the roofline cell most representative of the paper's contribution;
the perf loop (EXPERIMENTS.md §Perf) iterates algo / c / elision through
`sweep_dryrun --fusedmm`.
"""
import argparse
import json
import sys

import numpy as np

from repro.core import api, costmodel, sparse
from repro.launch.mesh import make_production_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", default="auto",
                    choices=["auto"] + sorted(api.ALGORITHMS))
    ap.add_argument("--c", type=int, default=None,
                    help="replication factor (default: cost-model best)")
    ap.add_argument("--elision", default="auto",
                    choices=["auto", "none", "reuse", "fused"])
    ap.add_argument("--m", type=int, default=1 << 20)
    ap.add_argument("--r", type=int, default=256)
    ap.add_argument("--nnz-row", type=int, default=32)
    ap.add_argument("--row-tile", type=int, default=256)
    ap.add_argument("--nz-block", type=int, default=256)
    ap.add_argument("--mtx", default=None,
                    help="Matrix Market file to dry-run instead of the "
                         "Erdos-Renyi generator (repro.core.mtx)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    mesh = make_production_mesh()          # 16 x 16 = 256 chips
    devices = np.asarray(mesh.devices).reshape(-1)
    r = args.r
    if args.mtx:
        from repro.core.mtx import load_mtx
        rows, cols, vals, (m, n) = load_mtx(args.mtx)
    else:
        m = n = args.m
        rows, cols, vals = sparse.erdos_renyi(m, n, args.nnz_row, seed=0)
    nnz = len(vals)

    from repro.launch.dryrun import analyse, emit_result
    try:
        prob = api.make_problem(rows, cols, vals, (m, n), r,
                                algorithm=args.algo, c=args.c,
                                devices=devices, row_tile=args.row_tile,
                                nz_block=args.nz_block)
        elision = prob.resolve_elision(args.elision)
    except ValueError as e:
        # structurally infeasible cell (divisibility, or an elision the
        # family does not support): a skip record, not a crash
        emit_result(dict(algo=args.algo, elision=args.elision, m=m, r=r,
                         skipped=str(e)), args.out)
        return 0
    lowered = prob.lower_fusedmm(elision)

    # the cost-model grid is full rank: every registry-declared
    # (family, elision) cell has exactly one Table-III row
    cm_name = costmodel.ELISION_COST_NAME[(prob.alg.name, elision)]
    paper_words = costmodel.words_fusedmm(cm_name, p=prob.p, c=prob.c,
                                          n=n, r=r, nnz=nnz).words
    meta = dict(arch=f"paper-fusedmm-{prob.alg.name}", shape=elision,
                kind="serve", multi_pod=False, mesh=str(mesh.shape),
                microbatch=0, params=nnz, active_params=nnz,
                algo=prob.alg.name, c=prob.c, phi=prob.phi,
                paper_words=paper_words)
    emit_result(analyse(lowered, meta), args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
