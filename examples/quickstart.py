"""Quickstart: the paper's kernels in five minutes (single device).

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import costmodel, sparse
from repro.kernels import ops
from repro.launch import compile_cache

compile_cache.enable()

# 1. build a sparse matrix S (Erdos-Renyi, like the paper's weak scaling)
m = n = 2048
r = 64
rows, cols, vals = sparse.erdos_renyi(m, n, nnz_per_row=8, seed=0)
S = sparse.pack_row_tiled(rows, cols, vals, (m, n))
print(f"S: {m}x{n}, nnz={len(vals)}, phi=nnz/(n*r)={len(vals)/(n*r):.3f}")

# 2. dense embeddings
rng = np.random.default_rng(0)
A = jnp.asarray(rng.standard_normal((m, r)), jnp.float32)
B = jnp.asarray(rng.standard_normal((n, r)), jnp.float32)

# 3. the three kernels (Pallas, interpret mode on CPU)
R = ops.sddmm(A, B, S)                   # R = S * (A @ B^T)
Y = ops.spmm(R, B)                       # Y = R @ B
F, R2 = ops.fusedmm(A, B, S)             # fused: same as the two above
print("fused == sddmm;spmm:",
      bool(jnp.allclose(F, Y, rtol=1e-4, atol=1e-4)))

# 4. which distributed algorithm would the paper pick at p=256?
ranking = costmodel.select_algorithm(p=256, n=n, r=r, nnz=len(vals))
print("algorithm ranking at p=256 (words/proc):")
for name, cost in ranking.items():
    print(f"  {name:28s} c*={cost.c:3d}  words={cost.words:,.0f}")

# 5. the unified distributed entrypoint: every algorithm family behind
# one signature, dispatched by the same cost model (repro.core.api)
from repro.core import api
prob = api.make_problem(rows, cols, vals, (m, n), r)     # algorithm="auto"
print(f"auto dispatch on {len(jax.devices())} device(s): "
      f"{prob.alg.name} c={prob.c} elision={prob.resolve_elision()}")
out, _ = prob.fusedmm(A, B)
print("api fusedmm == local fused:",
      bool(np.allclose(out, np.asarray(F), rtol=1e-3, atol=1e-3)))
