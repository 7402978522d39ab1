"""Distributed embedding training on the sampled loss (SGD).

  PYTHONPATH=src python examples/train_embedding.py [--mtx path/to/file.mtx]

Every SGD step runs one distributed SDDMM forward and its dual-primitive
backward (SpMM + SpMM-transpose on the same grid) through the
``jax.custom_vjp`` rules of repro.core.grads; an api.Session replays the
forward's fiber replication in the backward, so no dense factor is
gathered twice per step.  With ``--mtx`` the ratings matrix is loaded
from a Matrix Market file (the bundled ``tests/fixtures/tiny.mtx`` works)
instead of the seeded Erdos-Renyi generator.
"""
import sys

from repro.apps.als import train_embedding_distributed
from repro.launch import compile_cache

if __name__ == "__main__":
    compile_cache.enable()
    args = sys.argv[1:]
    kw = dict(m=512, n=512, nnz_per_row=8, r=16, steps=25, lr=0.05)
    if "--mtx" in args:
        from repro.core.mtx import load_mtx
        rows, cols, vals, (m, n) = load_mtx(args[args.index("--mtx") + 1])
        kw.update(m=m, n=n, rows=rows, cols=cols, vals=vals)
    X, Y, hist = train_embedding_distributed(**kw)
    print("loss history:", [round(h, 2) for h in hist])
    assert hist[-1] < hist[0]
    print("OK: sampled-loss SGD through the distributed dual-primitive VJPs")
