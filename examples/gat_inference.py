"""Paper application 2: GAT forward pass via the r=2-SDDMM score trick.

  PYTHONPATH=src python examples/gat_inference.py [--distributed|--serve]

With --distributed the score SDDMM and aggregation SpMM run through the
unified repro.core.api (cost-model-chosen algorithm), with the row
softmax between them applied on completed rows (paper Fig. 9).

With --serve the layer is DEPLOYED into the serving pool and queried by
several concurrent clients, each asking for a different node set: the
continuous batcher coalesces every client's edge-score query into one
union-of-patterns SDDMM round per tick (all clients share the deployed
A*/B* operands), and the answers match the full distributed forward
bitwise on the queried rows (docs/serving.md).
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import gat
from repro.launch import compile_cache

if __name__ == "__main__":
    compile_cache.enable()
    distributed = "--distributed" in sys.argv[1:]
    serve = "--serve" in sys.argv[1:]
    n, d, heads = 8192, 64, 4
    rng = np.random.default_rng(0)
    H = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    layers = [gat.init_gat_layer(jax.random.PRNGKey(i), d, d)
              for i in range(2)]
    if serve:
        from repro import serving
        pool = serving.SessionPool(capacity=4)
        rows, cols, _ = gat.graph_coo(n, nnz_per_row=16, seed=0)
        dep = gat.gat_deploy_layer(pool, rows, cols, n, np.asarray(H),
                                   layers[0], n_heads=heads)
        engine = serving.ServingEngine(pool, max_batch=32)
        print(f"deployed head 0 on {dep.problem.alg.name} "
              f"(p={dep.problem.p})")
        # several clients queue score queries; ONE coalesced round
        clients = [rng.choice(n, size=64, replace=False)
                   for _ in range(6)]
        phase1 = [gat.gat_submit_scores(engine, dep, ids)
                  for ids in clients]
        report = engine.tick()
        print(f"scores: {report['requests']} client queries -> "
              f"{report['rounds']} coalesced round(s)")
        aggs = [gat.gat_submit_aggregate(engine, dep, ids,
                                         ticket.result())
                for ids, (ticket, _) in zip(clients, phase1)]
        engine.tick()
        out0 = aggs[0].result()[np.unique(clients[0])]
        print("client 0 head-0 rows:", out0.shape, "finite:",
              bool(np.isfinite(out0).all()))
        print("pool:", pool.stats())
    elif distributed:
        graph = gat.make_dist_graph(n, nnz_per_row=16, r=d // heads,
                                    seed=0)
        print(f"distributed on {graph.alg.name} (c={graph.c})")
        out = gat.gat_forward_distributed(graph, H, layers, n_heads=heads)
        print("GAT output:", out.shape, "finite:",
              bool(np.isfinite(np.asarray(out)).all()))
    else:
        S = gat.make_graph(n, nnz_per_row=16, seed=0)
        out = gat.gat_forward(S, H, layers, n_heads=heads)
        print("GAT output:", out.shape, "finite:",
              bool(np.isfinite(np.asarray(out)).all()))
