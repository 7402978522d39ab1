"""Graph Attention Network forward pass (paper §VI-E).

Single-head GAT layer over adjacency S:

    e_ij  = LeakyReLU( <a1, W h_i> + <a2, W h_j> )   at nnz(S)
    Shat  = row_softmax(e)
    h'_i  = sigma( sum_j Shat_ij (W h)_j )

The paper notes the additive score is "a slight modification of Eq. 1 with
an identical communication pattern to SDDMM": with augmented embeddings
A* = [u, 1] and B* = [1, v] the dot <A*_i, B*_j> = u_i + v_j, so the score
computation IS an r=2 SDDMM through the repro kernels, and the aggregation
is an SpMM — per the paper, local kernel fusion is NOT applicable because
the softmax needs completed rows (noted in Fig. 9).  This is an
*application-level* barrier, distinct from the per-family elision matrix
of docs/algorithms.md: even on d15, whose FusedMM has a true fused cell,
GAT must run the two kernels separately — the elision grid applies to
FusedMM calls (ALS's matvecs), not to sddmm;softmax;spmm pipelines.

The distributed path (`gat_layer_distributed`) runs the score SDDMM and
the aggregation SpMM through `repro.core.api` on any registered
algorithm.  Between the two kernels the row softmax is applied on
*completed rows*, exactly as Fig. 9 requires: the sampled scores are
collected into the problem's home COO order (each row's nonzeros
complete — in the 1.5D sparse-shifting layout each processor's home
block already holds full rows; host assembly generalizes this to all
four families), softmaxed per row, and re-injected as the SpMM's sample
values.

The trainable path (`gat_layer_trainable` / `train_gat_distributed`)
is the same pipeline through the differentiable `repro.core.grads`
entrypoints: the score SDDMM's backward is the dual SpMM pair, the
aggregation SpMM takes the softmaxed attention as a differentiable
*values* input (its backward is the dual SDDMM on the adjacency
pattern), and `jax.grad` flows end-to-end to the layer parameters.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api, grads, sparse
from repro.distributed import elastic, faults
from repro.kernels import ops
from repro.training import checkpoint


def leaky_relu(x, slope=0.2):
    return jnp.where(x >= 0, x, slope * x)


def attention_scores(S_ones: sparse.RowTiledCOO, u, v):
    """e_ij = u_i + v_j at nonzeros, via the r=2 SDDMM trick."""
    A_star = jnp.stack([u, jnp.ones_like(u)], axis=1)     # (m, 2)
    B_star = jnp.stack([jnp.ones_like(v), v], axis=1)     # (n, 2)
    return ops.sddmm(A_star, B_star, S_ones)


def row_softmax(S: sparse.RowTiledCOO) -> sparse.RowTiledCOO:
    """Softmax over each row's nonzero values (sparse, numerically safe)."""
    rows = S.rows_global().reshape(-1)
    vals = S.vals.reshape(-1)
    mask = vals != 0
    neg = jnp.full((S.shape[0],), -1e30, jnp.float32)
    rmax = neg.at[rows].max(jnp.where(mask, vals, -1e30))
    ex = jnp.where(mask, jnp.exp(vals - rmax[rows]), 0.0)
    rsum = jnp.zeros((S.shape[0],), jnp.float32).at[rows].add(ex)
    out = ex / jnp.maximum(rsum[rows], 1e-30)
    return S.with_vals(out.reshape(S.vals.shape))


@dataclasses.dataclass
class GATParams:
    W: jax.Array       # (d_in, d_out)
    a1: jax.Array      # (d_out,)
    a2: jax.Array      # (d_out,)


def init_gat_layer(key, d_in, d_out):
    k1, k2, k3 = jax.random.split(key, 3)
    return GATParams(
        W=jax.random.normal(k1, (d_in, d_out)) * (1.0 / np.sqrt(d_in)),
        a1=jax.random.normal(k2, (d_out,)) * 0.1,
        a2=jax.random.normal(k3, (d_out,)) * 0.1)


def gat_layer(S_ones: sparse.RowTiledCOO, H, p: GATParams,
              n_heads: int = 1, activation=jax.nn.elu):
    """Multi-head = independent heads on column slices of W, concatenated."""
    d_out = p.W.shape[1] // n_heads
    outs = []
    for h in range(n_heads):
        Wh = H @ p.W[:, h * d_out:(h + 1) * d_out]
        u = Wh @ p.a1[h * d_out:(h + 1) * d_out]
        v = Wh @ p.a2[h * d_out:(h + 1) * d_out]
        e = attention_scores(S_ones, u, v)
        e = e.with_vals(jnp.where(e.vals != 0, leaky_relu(e.vals), 0.0))
        Shat = row_softmax(e)
        outs.append(ops.spmm(Shat, Wh, m=S_ones.shape[0]))
    return activation(jnp.concatenate(outs, axis=1))


def gat_forward(S_ones, H0, layers, n_heads=1):
    H = H0
    for p in layers:
        H = gat_layer(S_ones, H, p, n_heads=n_heads)
    return H


def graph_coo(n_nodes, nnz_per_row, seed=0):
    """ER adjacency + self loops (standard GAT practice), unit values."""
    rows, cols, _ = sparse.erdos_renyi(n_nodes, n_nodes, nnz_per_row,
                                       seed=seed)
    rows = np.concatenate([rows, np.arange(n_nodes, dtype=np.int32)])
    cols = np.concatenate([cols, np.arange(n_nodes, dtype=np.int32)])
    key = np.unique(rows.astype(np.int64) * n_nodes + cols)
    rows = (key // n_nodes).astype(np.int32)
    cols = (key % n_nodes).astype(np.int32)
    return rows, cols, np.ones(len(rows), np.float32)


def make_graph(n_nodes, nnz_per_row, seed=0, row_tile=128, nz_block=128):
    rows, cols, vals = graph_coo(n_nodes, nnz_per_row, seed=seed)
    return sparse.pack_row_tiled(rows, cols, vals, (n_nodes, n_nodes),
                                 row_tile=row_tile, nz_block=nz_block)


# ---------------------------------------------------------------------------
# Distributed path: score SDDMM + aggregation SpMM through repro.core.api,
# row softmax on completed rows in between (paper Fig. 9)
# ---------------------------------------------------------------------------

def make_dist_graph(n_nodes, nnz_per_row, r, *, algorithm="auto", c=None,
                    devices=None, seed=0, row_tile=32,
                    nz_block=32) -> api.DistProblem:
    """Adjacency as a DistProblem; ``r`` is the per-head output width the
    aggregation SpMM will run at (must obey the family's r-divisibility)."""
    rows, cols, vals = graph_coo(n_nodes, nnz_per_row, seed=seed)
    return api.make_problem(rows, cols, vals, (n_nodes, n_nodes), r,
                            algorithm=algorithm, c=c, devices=devices,
                            row_tile=row_tile, nz_block=nz_block)


def row_softmax_coo(rows, vals, n_rows):
    """Numerically-safe softmax over each row's nonzeros, COO layout.

    Operates on completed rows: every nonzero of a row must be present
    (the api's home-COO assembly guarantees this for all four families).
    """
    vals = np.asarray(vals, np.float64)
    rmax = np.full(n_rows, -np.inf)
    np.maximum.at(rmax, rows, vals)
    ex = np.exp(vals - np.where(np.isfinite(rmax), rmax, 0.0)[rows])
    rsum = np.zeros(n_rows)
    np.add.at(rsum, rows, ex)
    return (ex / np.maximum(rsum[rows], 1e-30)).astype(np.float32)


def gat_layer_distributed(graphP: api.DistProblem, H, p: GATParams,
                          n_heads: int = 1, activation=jax.nn.elu):
    """Distributed single layer, mirroring gat_layer head for head.

    Per head: (1) score SDDMM via the augmented r=2 trick, zero-padded to
    the family's minimum feasible width (padding columns contribute 0 to
    every dot product); (2) LeakyReLU + row softmax on the completed-row
    COO; (3) aggregation SpMM with the softmaxed attention as the sample
    values.  No local fusion — the softmax barrier between the kernels is
    exactly why (Fig. 9).
    """
    H = np.asarray(H, np.float32)
    n = graphP.m
    d_out = p.W.shape[1] // n_heads
    mult = graphP.alg.min_r_multiple(graphP.grid)
    r_score = max(2, ((2 + mult - 1) // mult) * mult)
    scoreP = graphP.with_r(r_score)
    aggP = graphP if graphP.r == d_out else graphP.with_r(d_out)
    W = np.asarray(p.W)
    a1, a2 = np.asarray(p.a1), np.asarray(p.a2)
    outs = []
    for h in range(n_heads):
        Wh = H @ W[:, h * d_out:(h + 1) * d_out]
        u = Wh @ a1[h * d_out:(h + 1) * d_out]
        v = Wh @ a2[h * d_out:(h + 1) * d_out]
        A_star = np.zeros((n, r_score), np.float32)
        B_star = np.zeros((n, r_score), np.float32)
        A_star[:, 0], A_star[:, 1] = u, 1.0
        B_star[:, 0], B_star[:, 1] = 1.0, v
        e = scoreP.sddmm(A_star, B_star).values()      # completed rows
        e = np.asarray(leaky_relu(e))
        attn = row_softmax_coo(graphP.rows, e, n)
        outs.append(aggP.with_values(attn).spmm(Wh))
    return activation(jnp.concatenate([jnp.asarray(o) for o in outs],
                                      axis=1))


def gat_forward_distributed(graphP: api.DistProblem, H0, layers,
                            n_heads: int = 1):
    H = H0
    for p in layers:
        H = gat_layer_distributed(graphP, H, p, n_heads=n_heads)
    return H


# ---------------------------------------------------------------------------
# Query mode: the same layer served through repro.serving, many clients'
# node queries coalesced per tick (docs/serving.md)
# ---------------------------------------------------------------------------

def gat_deploy_layer(pool, rows, cols, n_nodes, H, p: GATParams, *,
                     head: int = 0, n_heads: int = 1,
                     algorithm: str = "auto", c=None, devices=None,
                     comm: str = "dense", row_tile: int = 32,
                     nz_block: int = 32):
    """Deploy one GAT head for serving: graph + precomputed operands.

    At inference the parameters are frozen, so everything stationary is
    computed once and deployed with the graph: ``Wh`` (the projected
    embeddings the aggregation SpMM consumes) and the augmented score
    operands ``A* = [u, 1]`` / ``B* = [1, v]`` whose r=2 SDDMM yields
    the additive attention logits.  Every client query then moves only
    coordinates and attention values — the deployment's Session serves
    the operand replication from cache tick after tick.
    """
    H = np.asarray(H, np.float32)
    d_out = p.W.shape[1] // n_heads
    W = np.asarray(p.W)[:, head * d_out:(head + 1) * d_out]
    a1 = np.asarray(p.a1)[head * d_out:(head + 1) * d_out]
    a2 = np.asarray(p.a2)[head * d_out:(head + 1) * d_out]
    Wh = H @ W
    u, v = Wh @ a1, Wh @ a2
    A_star = np.zeros((n_nodes, 2), np.float32)
    B_star = np.zeros((n_nodes, 2), np.float32)
    A_star[:, 0], A_star[:, 1] = u, 1.0
    B_star[:, 0], B_star[:, 1] = 1.0, v
    return pool.deploy(rows, cols, np.ones(len(rows), np.float32),
                       (n_nodes, n_nodes), d_out,
                       operands={"A": A_star, "B": B_star, "Wh": Wh},
                       algorithm=algorithm, c=c, devices=devices,
                       comm=comm, row_tile=row_tile, nz_block=nz_block)


def gat_query_edges(deployment, node_ids):
    """The deployed graph's edges leaving ``node_ids`` (host COO order) —
    a served query's score pattern."""
    prob = deployment.problem
    node_ids = np.unique(np.asarray(node_ids).reshape(-1))
    mask = np.isin(prob.rows, node_ids)
    if not mask.any():
        raise ValueError("queried nodes have no outgoing edges")
    return prob.rows[mask], prob.cols[mask], mask


def gat_submit_scores(engine, deployment, node_ids, *,
                      arrival: float = 0.0):
    """Phase 1 of a served GAT query: queue the edge-score SDDMM for the
    edges leaving ``node_ids``.  All clients' phase-1 tickets share the
    deployed ``A``/``B`` operands, so a tick's worth of them coalesces
    into ONE union-of-patterns round."""
    erows, ecols, _ = gat_query_edges(deployment, node_ids)
    ticket = engine.submit_score(deployment, erows, ecols, "A", "B",
                                 arrival=arrival)
    return ticket, erows


def gat_submit_aggregate(engine, deployment, node_ids, scores, *,
                         arrival: float = 0.0):
    """Phase 2: LeakyReLU + row softmax on the completed queried rows
    (the Fig. 9 barrier, now per client), then the aggregation SpMM
    with the client's attention as a per-request values override (zero
    outside the queried rows — a row of the SpMM output reads only its
    own row's values, so the queried rows are exact)."""
    prob = deployment.problem
    erows, _, mask = gat_query_edges(deployment, node_ids)
    e = np.asarray(leaky_relu(jnp.asarray(np.asarray(scores))))
    attn = row_softmax_coo(erows, e, prob.m)
    vals = np.zeros(prob.nnz, np.float32)
    vals[mask] = attn
    return engine.submit_aggregate(deployment, deployment.operand("Wh"),
                                   vals=vals, arrival=arrival)


def gat_layer_served(engine, deployment, node_ids,
                     activation=jax.nn.elu):
    """Single-client convenience: run both phases through the engine
    (one tick each) and return the layer output rows for ``node_ids``.
    Matches :func:`gat_layer_distributed`'s rows bitwise for a one-head
    layer — same padded score width, same softmax, same aggregation."""
    node_ids = np.unique(np.asarray(node_ids).reshape(-1))
    t_score, _ = gat_submit_scores(engine, deployment, node_ids)
    engine.tick()
    t_agg = gat_submit_aggregate(engine, deployment, node_ids,
                                 t_score.result())
    engine.tick()
    return activation(jnp.asarray(t_agg.result()[node_ids]))


# ---------------------------------------------------------------------------
# Trainable path: the same pipeline through the differentiable
# repro.core.grads entrypoints, so jax.grad flows end-to-end
# ---------------------------------------------------------------------------

def segment_softmax(rows, vals, n_rows):
    """Differentiable row softmax over COO values (completed rows)."""
    rows = jnp.asarray(rows)
    rmax = jax.ops.segment_max(vals, rows, num_segments=n_rows)
    rmax = jnp.where(jnp.isfinite(rmax), rmax, 0.0)
    ex = jnp.exp(vals - rmax[rows])
    rsum = jax.ops.segment_sum(ex, rows, num_segments=n_rows)
    return ex / jnp.maximum(rsum[rows], 1e-30)


def gat_layer_trainable(graphP: api.DistProblem, H, W, a1, a2,
                        n_heads: int = 1, activation=jax.nn.elu,
                        session: api.Session | None = None):
    """Differentiable distributed GAT layer (jax.grad-able in W/a1/a2/H).

    Mirrors :func:`gat_layer_distributed` kernel for kernel, but every
    distributed call goes through :mod:`repro.core.grads`: the score
    SDDMM's backward is the dual SpMM pair, and the aggregation SpMM
    takes the softmaxed attention as a *differentiable values* input —
    its backward is the dual SDDMM on the adjacency pattern (this is
    where the gradient w.r.t. the attention scores flows).  The row
    softmax between the kernels runs on completed rows in the home COO
    order, exactly as the forward-only path does (paper Fig. 9: no
    local fusion across the softmax barrier, in either pass).
    """
    H = jnp.asarray(H, jnp.float32)
    n = graphP.m
    d_out = W.shape[1] // n_heads
    mult = graphP.alg.min_r_multiple(graphP.grid)
    r_score = max(2, ((2 + mult - 1) // mult) * mult)
    scoreP = graphP.with_r(r_score)
    aggP = graphP if graphP.r == d_out else graphP.with_r(d_out)
    outs = []
    for h in range(n_heads):
        Wh = H @ W[:, h * d_out:(h + 1) * d_out]
        u = Wh @ a1[h * d_out:(h + 1) * d_out]
        v = Wh @ a2[h * d_out:(h + 1) * d_out]
        A_star = jnp.zeros((n, r_score)).at[:, 0].set(u).at[:, 1].set(1.0)
        B_star = jnp.zeros((n, r_score)).at[:, 0].set(1.0).at[:, 1].set(v)
        e = grads.sddmm(scoreP, A_star, B_star, session=session)
        e = leaky_relu(e)
        attn = segment_softmax(graphP.rows, e, n)
        outs.append(grads.spmm(aggP, attn, Wh, session=session))
    return activation(jnp.concatenate(outs, axis=1))


def train_gat_distributed(graphP: api.DistProblem, H, target, *,
                          d_out: int | None = None, steps: int = 20,
                          lr: float = 0.05, n_heads: int = 1, seed: int = 0,
                          session: api.Session | None = None,
                          monitor=None, ckpt_dir: str | None = None,
                          ckpt_every: int = 5, max_retries: int = 2,
                          verbose: bool = True):
    """Gradient-based training of one distributed GAT layer.

    Minimizes the MSE between the layer output and ``target`` by SGD on
    (W, a1, a2), every kernel of every step a distributed primitive on
    ``graphP``'s grid.  Returns ((W, a1, a2), loss history); the history
    must be decreasing for any sane (lr, steps).

    Robustness wiring mirrors ``train_embedding_distributed``
    (docs/robustness.md): steps run under ``run_step_resilient`` with
    the typed retryable set — ``TransientFault`` invalidates the
    Session's replication for this grid and retries; ``DeviceLost``
    re-plans ``graphP`` onto a degraded mesh.  ``monitor`` times steps
    for straggler flagging; ``ckpt_dir`` checkpoints (W, a1, a2) plus
    the problem's :meth:`api.DistProblem.meta_dict` every ``ckpt_every``
    steps and resumes from the latest committed step, rebuilding packs
    via :func:`api.problem_from_meta` on whatever mesh is available.
    """
    H = jnp.asarray(H, jnp.float32)
    target = jnp.asarray(target, jnp.float32)
    session = session if session is not None else api.Session()
    d_in = H.shape[1]
    d_out = d_out if d_out is not None else target.shape[1]
    p0 = init_gat_layer(jax.random.PRNGKey(seed), d_in, d_out)
    params = (jnp.asarray(p0.W), jnp.asarray(p0.a1), jnp.asarray(p0.a2))

    def make_grad(prob):
        def loss_fn(params):
            W, a1, a2 = params
            out = gat_layer_trainable(prob, H, W, a1, a2, n_heads=n_heads,
                                      session=session)
            return jnp.mean((out - target) ** 2)
        return jax.value_and_grad(loss_fn)

    grad_fn = make_grad(graphP)

    start = 0
    if ckpt_dir is not None:
        last = checkpoint.latest_step(ckpt_dir)
        if last is not None:
            meta = checkpoint.load_manifest(ckpt_dir, last).get("meta")
            if meta is not None:
                # resume onto the mesh of the problem the caller handed
                # us — not the process's full device set
                devs = list(np.asarray(
                    graphP.grid.mesh.devices).reshape(-1))
                graphP = api.problem_from_meta(
                    meta, graphP.rows, graphP.cols, graphP.vals,
                    devices=devs)
                grad_fn = make_grad(graphP)
            tree = checkpoint.restore(
                ckpt_dir, last, {"W": params[0], "a1": params[1],
                                 "a2": params[2]})
            params = tuple(jnp.asarray(tree[k]) for k in ("W", "a1", "a2"))
            start = last
            if verbose:
                print(f"gat: resumed step {last} on "
                      f"{graphP.alg.name} p={graphP.p}")

    def on_failure(attempt, e):
        nonlocal graphP, grad_fn
        session.invalidate(graphP)
        if isinstance(e, faults.DeviceLost):
            graphP = api.degrade(graphP, e.rank)
            grad_fn = make_grad(graphP)
            if verbose:
                print(f"gat: lost rank {e.rank} -> re-planned onto "
                      f"{graphP.alg.name} p={graphP.p}")

    hist = []
    for it in range(start, steps):
        def step(params):
            if monitor is not None:
                return monitor.timed(it, grad_fn, params)
            return grad_fn(params)

        val, gparams = elastic.run_step_resilient(
            step, None, None, params,
            max_retries=max_retries, on_failure=on_failure)
        params = tuple(p - lr * g for p, g in zip(params, gparams))
        hist.append(float(val))
        if verbose:
            print(f"gat[{graphP.alg.name}] step {it}: loss {val:.5f}")
        if ckpt_dir is not None and (it + 1) % ckpt_every == 0:
            checkpoint.save(ckpt_dir, it + 1,
                            {"W": np.asarray(params[0]),
                             "a1": np.asarray(params[1]),
                             "a2": np.asarray(params[2])},
                            meta=graphP.meta_dict())
    return params, hist
