"""Paper application 1: ALS collaborative filtering with batched-CG FusedMM.

  PYTHONPATH=src python examples/als_collaborative_filtering.py [--distributed]

With --distributed every kernel call (SpMM right-hand sides, FusedMM CG
matvecs, SDDMM loss) runs through the unified repro.core.api on the
cost-model-chosen algorithm, with Session-cached replication in the CG
loop.  On a single device the distributed path degenerates to a 1x1
grid — same math, same entrypoint.
"""
import sys

from repro.apps.als import run_als, run_als_distributed
from repro.launch import compile_cache

if __name__ == "__main__":
    compile_cache.enable()
    distributed = "--distributed" in sys.argv[1:]
    runner = run_als_distributed if distributed else run_als
    A, B, hist = runner(m=2048, n=2048, nnz_per_row=12, r=32, rounds=3,
                        cg_iters=10)
    print("loss history:", [round(h, 1) for h in hist])
    assert hist[-1] < hist[0]
    print("OK: every CG matvec ran as one FusedMM call"
          + (" through repro.core.api" if distributed else ""))
