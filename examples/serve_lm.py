"""Serving demo: concurrent sparse-attention queries, one round per tick.

  PYTHONPATH=src python examples/serve_lm.py

A block-causal attention mask is deployed ONCE into the Session pool;
then several concurrent "clients" — each owning a disjoint block of
query rows with its own Q projection — submit attention-score queries
(``<Q_i, K_j>`` at the mask's positions) plus a value-aggregation
request.  The continuous batcher coalesces every client's score query
into ONE union-of-patterns SDDMM round per tick — disjoint query rows
let different Q operands share the round — so the expensive phase costs
one distributed round no matter how many clients arrive; aggregations
group by their sample-values key (per-client softmaxed attention stays
per-client here, the deployed-values case batches fully —
docs/serving.md).

The greedy LM decode demo that used to live here is still available as
the local path: ``python examples/serve_lm.py --decode``.
"""
import sys

import numpy as np
import jax
import jax.numpy as jnp

from repro.launch import compile_cache


def decode_demo():
    from repro.config import ParallelConfig
    from repro.configs import llama32_1b
    from repro.models import model as M
    from repro.serving import decode
    cfg = llama32_1b.reduced()
    pcfg = ParallelConfig(compute_dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
    out = decode.greedy_generate(cfg, pcfg, params, {"tokens": prompts},
                                 steps=16)
    print("generated:", out.shape)
    print(np.asarray(out[:2]))


def serving_demo():
    from repro import serving

    seq, d, n_clients, block = 256, 32, 8, 32
    rng = np.random.default_rng(0)

    # block-causal mask: token i attends within its block and the one
    # before it — the local-attention sparsity pattern, as a graph
    rows, cols = [], []
    for i in range(seq):
        b = i // block
        lo = max(0, (b - 1) * block)
        js = np.arange(lo, i + 1)
        rows.append(np.full(len(js), i))
        cols.append(js)
    rows = np.concatenate(rows).astype(np.int64)
    cols = np.concatenate(cols).astype(np.int64)

    K = rng.standard_normal((seq, d)).astype(np.float32)
    V = rng.standard_normal((seq, d)).astype(np.float32)
    pool = serving.SessionPool(capacity=4)
    dep = pool.deploy(rows, cols, np.ones(len(rows), np.float32),
                      (seq, seq), d, operands={"K": K, "V": V})
    engine = serving.ServingEngine(pool, max_batch=64)
    print(f"deployed block-causal mask ({len(rows)} positions) on "
          f"{dep.problem.alg.name}, p={dep.problem.p}")

    # each client: its own rows (disjoint blocks) and its own Q
    tickets = []
    for cl in range(n_clients):
        q_rows = np.arange(cl * block, (cl + 1) * block)
        sel = np.isin(rows, q_rows)
        Q = np.zeros((seq, d), np.float32)
        Q[q_rows] = rng.standard_normal((block, d)).astype(np.float32)
        t = engine.submit_score(dep, rows[sel], cols[sel], Q, "K")
        tickets.append((cl, q_rows, sel, Q, t))
    report = engine.tick()
    print(f"scores: {report['requests']} client queries -> "
          f"{report['rounds']} coalesced round(s)")

    # per-client softmax, then everyone's attn @ V in one batched round
    agg = []
    for cl, q_rows, sel, Q, t in tickets:
        from repro.apps.gat import row_softmax_coo
        scale = np.float32(1.0 / np.sqrt(d))
        attn = row_softmax_coo(rows[sel], t.result() * scale, seq)
        vals = np.zeros(len(rows), np.float32)
        vals[sel] = attn
        agg.append((q_rows, engine.submit_aggregate(dep, V, vals=vals)))
    report = engine.tick()
    print(f"aggregation: {len(agg)} requests -> "
          f"{report['rounds']} round(s)")
    for q_rows, t in agg[:2]:
        out = t.result()[q_rows]
        print(f"  client rows {q_rows[0]}..{q_rows[-1]}: "
              f"out {out.shape}, finite={bool(np.isfinite(out).all())}")
    print("engine:", {k: v for k, v in engine.stats().items()
                      if k in ("rounds", "served")})
    print("pool:", pool.stats())


if __name__ == "__main__":
    compile_cache.enable()
    if "--decode" in sys.argv[1:]:
        decode_demo()
    else:
        serving_demo()
