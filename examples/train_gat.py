"""Distributed GAT layer training (paper §VI-E, made differentiable).

  PYTHONPATH=src python examples/train_gat.py

Trains a single-head GAT layer by SGD: per step, the score SDDMM and the
aggregation SpMM (with differentiable attention values) run as
distributed primitives, and their backwards are the dual primitives on
the same grid — SpMM/SpMM-transpose for the SDDMM, SDDMM for the SpMM's
values-gradient (repro.core.grads).  The row softmax sits between the
kernels on completed rows, in both passes (Fig. 9's no-fusion barrier).
"""
import jax
import numpy as np

from repro.apps import gat
from repro.launch import compile_cache

if __name__ == "__main__":
    compile_cache.enable()
    n, d = 512, 16
    graphP = gat.make_dist_graph(n, 6, d, seed=0)
    rng = np.random.default_rng(0)
    H = rng.standard_normal((n, d)).astype(np.float32)
    # regression target: a "teacher" layer's output
    teacher = gat.init_gat_layer(jax.random.PRNGKey(7), d, d)
    target = np.asarray(gat.gat_layer_distributed(graphP, H, teacher))
    params, hist = gat.train_gat_distributed(graphP, H, target, steps=25,
                                             lr=0.1, seed=1)
    print("loss history:", [round(h, 4) for h in hist])
    assert hist[-1] < hist[0]
    print("OK: GAT layer trained through the distributed dual-primitive VJPs")
