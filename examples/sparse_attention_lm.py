"""Beyond-paper: LM attention as the paper's SDDMM->softmax->SpMM pattern.

A sliding-window + global-token causal mask makes long-context attention a
sparse-kernel problem; at seq=1024 with a 128-token window the mask holds
~3% of the dense score matrix, and phi = nnz/(S*hd) tells you which of the
paper's distributed algorithms to use for it.

  PYTHONPATH=src python examples/sparse_attention_lm.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import costmodel
from repro.core.sparse_attention import (build_causal_block_mask,
                                         dense_reference, sparsity_stats,
                                         sparse_attention_head)
from repro.launch import compile_cache

if __name__ == "__main__":
    compile_cache.enable()
    seq, hd = 1024, 64
    mask = build_causal_block_mask(seq, block=64, window_blocks=2,
                                   global_blocks=1)
    stats = sparsity_stats(mask, seq, hd)
    print(f"mask: {stats['nnz']} nnz = {stats['fraction']:.1%} of dense, "
          f"phi={stats['phi']:.2f}")

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((seq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((seq, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((seq, hd)), jnp.float32)
    out = sparse_attention_head(q, k, v, mask)
    want = dense_reference(q, k, v, np.asarray(mask.to_dense()))
    err = float(jnp.abs(out - want).max())
    print(f"sparse vs dense-masked reference: max err {err:.2e}")
    assert err < 1e-4

    ranking = costmodel.select_algorithm(p=256, n=seq, r=hd,
                                         nnz=stats["nnz"])
    best = next(iter(ranking))
    print("best distributed algorithm for this attention layer at p=256:",
          best)
