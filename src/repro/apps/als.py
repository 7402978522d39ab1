"""Collaborative filtering via Alternating Least Squares (paper §VI-E).

Batched-CG formulation of Zhao & Canny [1]: solving the per-row normal
equations (B_Omega_i^T B_Omega_i + lambda I) a_i = B_Omega_i^T c_i for ALL
rows at once.  The batched matvec

    y_i = sum_{j in Omega_i} <x_i, b_j> b_j + lambda x_i

is exactly FusedMMA(mask, X, B) + lambda X — the paper's key observation —
so every CG iteration is one FusedMM call through the repro kernels.

Two paths share the math:

* the single-device path (`run_als`) calls the local Pallas kernels;
* the distributed path (`run_als_distributed`) runs every kernel through
  `repro.core.api` — any registered algorithm, `algorithm="auto"` by
  default — and threads an `api.Session` through the CG loop, so the
  fiber replication of the *stationary* factor matrix is paid once per
  solve instead of once per iteration (the paper's replication-reuse
  elision extended across iterations).

`train_embedding_distributed` is the gradient-based sibling: SGD on the
sampled loss through the differentiable `repro.core.grads` entrypoints,
where each step's backward is the dual SpMM/SpMM-transpose pair on the
same grid and the Session replays the forward's replication.
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


@dataclasses.dataclass
class ALSProblem:
    S: sparse.RowTiledCOO        # mask/ratings (m x n), vals = ratings
    St: sparse.RowTiledCOO       # transpose pack (n x m)
    mask: sparse.RowTiledCOO     # S with vals=1 at nonzeros
    maskt: sparse.RowTiledCOO
    m: int
    n: int
    r: int
    reg: float = 0.1


def make_problem(m, n, nnz_per_row, r, seed=0, reg=0.1,
                 row_tile=128, nz_block=128) -> ALSProblem:
    rows, cols, vals = sparse.erdos_renyi(m, n, nnz_per_row, seed=seed)
    vals = np.abs(vals) + 0.5          # positive "ratings"
    ones = np.ones_like(vals)
    S = sparse.pack_row_tiled(rows, cols, vals, (m, n), row_tile=row_tile,
                              nz_block=nz_block)
    St = sparse.pack_row_tiled(cols, rows, vals, (n, m), row_tile=row_tile,
                               nz_block=nz_block)
    mask = S.with_vals(jnp.where(S.vals != 0, 1.0, 0.0))
    maskt = St.with_vals(jnp.where(St.vals != 0, 1.0, 0.0))
    return ALSProblem(S, St, mask, maskt, m, n, r, reg)


def fusedmm_matvec(mask, X, B, reg, m):
    """y = FusedMM(mask, X, B) + reg*X — one CG matvec for all rows."""
    out, _ = ops.fusedmm(X, B, mask, m=m)
    return out + reg * X


def cg_solve(mask, B, rhs, reg, m, iters=10):
    """Batched CG on the ALS normal equations (all rows at once)."""
    X = jnp.zeros_like(rhs)
    R = rhs - fusedmm_matvec(mask, X, B, reg, m)
    P = R
    rs = jnp.sum(R * R, axis=1, keepdims=True)
    for _ in range(iters):
        AP = fusedmm_matvec(mask, P, B, reg, m)
        alpha = rs / jnp.maximum(jnp.sum(P * AP, axis=1, keepdims=True),
                                 1e-12)
        X = X + alpha * P
        R = R - alpha * AP
        rs_new = jnp.sum(R * R, axis=1, keepdims=True)
        P = R + (rs_new / jnp.maximum(rs, 1e-12)) * P
        rs = rs_new
    return X


def als_round(prob: ALSProblem, A, B, cg_iters=10):
    """One ALS round: optimize A given B, then B given A."""
    rhs_a = ops.spmm(prob.S, B, m=prob.m)                  # SpMMA(C, B)
    A = cg_solve(prob.mask, B, rhs_a, prob.reg, prob.m, cg_iters)
    rhs_b = ops.spmm(prob.St, A, m=prob.n)                 # SpMMB(C, A)
    B = cg_solve(prob.maskt, A, rhs_b, prob.reg, prob.n, cg_iters)
    return A, B


def loss(prob: ALSProblem, A, B):
    """|| C - SDDMM(A, B, mask) ||_F^2 on observed entries."""
    pred = ops.sddmm(A, B, prob.mask)
    return float(jnp.sum((prob.S.vals - pred.vals) ** 2))


# ---------------------------------------------------------------------------
# Distributed path: every kernel call through the unified repro.core.api
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DistALSProblem:
    """Ratings + mask problems in both orientations, one grid.

    A-solve matvecs run FusedMM on `mask`; B-solve matvecs on `mask_t`
    (the normal equations of the transposed system).  `ratings` /
    `ratings_t` supply the right-hand sides via SpMM.
    """
    ratings: api.DistProblem
    ratings_t: api.DistProblem
    mask: api.DistProblem
    mask_t: api.DistProblem
    m: int
    n: int
    r: int
    reg: float = 0.1


def make_dist_problem(m, n, nnz_per_row, r, *, algorithm="auto", c=None,
                      devices=None, seed=0, reg=0.1, row_tile=32,
                      nz_block=32) -> DistALSProblem:
    """Distributed analogue of make_problem: one grid, four plans."""
    rows, cols, vals = sparse.erdos_renyi(m, n, nnz_per_row, seed=seed)
    vals = np.abs(vals) + 0.5
    ratings = api.make_problem(rows, cols, vals, (m, n), r,
                               algorithm=algorithm, c=c, devices=devices,
                               row_tile=row_tile, nz_block=nz_block)
    mask = ratings.with_values(np.ones_like(vals))
    return DistALSProblem(ratings, ratings.transposed(),
                          mask, mask.transposed(), m, n, r, reg)


def dist_fusedmm_matvec(maskP: api.DistProblem, X, B, reg,
                        session: api.Session | None = None,
                        elision: str = "auto"):
    """y = FusedMM(mask, X, B) + reg*X through the unified API."""
    out, _ = maskP.fusedmm(X, B, elision=elision, session=session)
    y = reg * np.asarray(X, np.float32)
    y += out
    return y


def _row_dots(X, Y):
    """Per-row dot products as a column, without an (m, r) temporary."""
    return np.einsum("ij,ij->i", X, Y)[:, None]


def dist_cg_solve(maskP: api.DistProblem, B, rhs, reg, iters=10,
                  session: api.Session | None = None,
                  elision: str = "auto"):
    """Batched CG with every matvec one distributed FusedMM call.

    B is stationary across the whole solve, so with a Session its fiber
    replication happens exactly once (first matvec); the iterate X
    changes every iteration and is replicated fresh — never stale.
    """
    X = np.zeros(np.shape(rhs), np.float32)
    R = np.asarray(rhs, np.float32) - dist_fusedmm_matvec(
        maskP, X, B, reg, session, elision)
    del rhs
    # At real sizes every iterate is gigabytes, so each update makes at
    # most one new array.  X and P are never changed in place: they went
    # to the api, whose Session may keep what it uploaded (on the CPU
    # backend an upload can share the numpy buffer).  R and AP never do.
    P = R.copy()
    rs = _row_dots(R, R)
    for _ in range(iters):
        AP = dist_fusedmm_matvec(maskP, P, B, reg, session, elision)
        alpha = rs / np.maximum(_row_dots(P, AP), 1e-12)
        step = alpha * P
        step += X
        X = step
        AP *= alpha
        R -= AP
        del AP, step
        rs_new = _row_dots(R, R)
        P = (rs_new / np.maximum(rs, 1e-12)) * P
        P += R
        rs = rs_new
    return X


def dist_als_round(dp: DistALSProblem, A, B, cg_iters=10,
                   session: api.Session | None = None,
                   elision: str = "auto"):
    """One distributed ALS round: optimize A given B, then B given A.

    ``elision`` pins the FusedMM strategy of every CG matvec (any cell
    the chosen family implements — see docs/algorithms.md); the default
    "auto" ranks the family's cells by their session-steady-state word
    counts, so the cached loop lands on the cheapest cell for the grid
    (docs/choosing.md's worked ALS example).
    """
    A = dist_cg_solve(dp.mask, B, dp.ratings.spmm(B), dp.reg, cg_iters,
                      session, elision)
    B = dist_cg_solve(dp.mask_t, A, dp.ratings_t.spmm(A), dp.reg,
                      cg_iters, session, elision)
    return A, B


def dist_loss(dp: DistALSProblem, A, B):
    """|| C - SDDMM(A, B, mask) ||_F^2 on observed entries."""
    pred = dp.mask.sddmm(A, B).values()
    return float(np.sum((dp.ratings.vals - pred) ** 2))


def run_als_distributed(m=1024, n=1024, nnz_per_row=8, r=32, rounds=3,
                        cg_iters=10, seed=0, algorithm="auto", c=None,
                        devices=None, elision="auto", verbose=True):
    """End-to-end distributed ALS: the §VI-E application on any
    registered algorithm, with Session-cached replication in the CG loop.
    ``elision`` selects the FusedMM cell for the matvecs ("auto" = the
    cost model's session-aware pick).
    """
    dp = make_dist_problem(m, n, nnz_per_row, r, seed=seed,
                           algorithm=algorithm, c=c, devices=devices)
    rng = np.random.default_rng(seed)
    A = (rng.standard_normal((m, r)) * 0.1).astype(np.float32)
    B = (rng.standard_normal((n, r)) * 0.1).astype(np.float32)
    session = api.Session()
    hist = [dist_loss(dp, A, B)]
    for it in range(rounds):
        A, B = dist_als_round(dp, A, B, cg_iters, session, elision)
        hist.append(dist_loss(dp, A, B))
        if verbose:
            print(f"ALS[{dp.mask.alg.name}] round {it}: "
                  f"loss {hist[-2]:.1f} -> {hist[-1]:.1f}")
    return A, B, hist


# ---------------------------------------------------------------------------
# Query mode: trained factors served through repro.serving — many
# clients' user-item score queries coalesced per tick (docs/serving.md)
# ---------------------------------------------------------------------------

def deploy_factors(pool, rows, cols, vals, shape, U, V, *,
                   algorithm: str = "auto", c=None, devices=None,
                   comm: str = "dense", row_tile: int = 32,
                   nz_block: int = 32):
    """Deploy trained CF factors for serving: the ratings graph plus the
    factor matrices ``U (m, r)`` / ``V (n, r)`` as stationary operands.

    The pool key digests the factors too, so re-deploying after a
    training refresh is a miss (fresh replication), while the identical
    deploy is a hit (warm Session).  Prediction traffic then moves only
    (user, item) coordinate lists.
    """
    U = np.asarray(U, np.float32)
    V = np.asarray(V, np.float32)
    if U.shape[1] != V.shape[1]:
        raise ValueError(f"factor widths differ: {U.shape} vs {V.shape}")
    return pool.deploy(rows, cols, vals, shape, U.shape[1],
                       operands={"U": U, "V": V}, algorithm=algorithm,
                       c=c, devices=devices, comm=comm,
                       row_tile=row_tile, nz_block=nz_block)


def predict_scores(engine, deployment, users, items, *,
                   arrival: float = 0.0):
    """Queue a prediction query: ``score_k = <U_users[k], V_items[k]>``.

    Exactly the paper's CF inference shape — an SDDMM sampled at the
    requested (user, item) pairs against the deployed factors.  Every
    prediction ticket shares the deployed operands, so a tick's worth
    of clients coalesces into ONE union-of-patterns SDDMM round.
    """
    return engine.submit_score(deployment, users, items, "U", "V",
                               arrival=arrival)


def lookup_embeddings(engine, deployment, weights, *,
                      arrival: float = 0.0):
    """Queue an embedding aggregation: ``out = ratings_graph @ weights``
    (``weights (n, w)``) — the neighborhood-lookup shape; all deployed-
    values lookups in a tick ride one batched-RHS SpMM round."""
    return engine.submit_aggregate(deployment, weights, arrival=arrival)


# ---------------------------------------------------------------------------
# Sampled-loss embedding training: SGD through the differentiable
# distributed kernels (repro.core.grads) — the gradient-based sibling of
# the ALS solver above, FusedMM forward AND backward every step
# ---------------------------------------------------------------------------

def sampled_loss(maskP: api.DistProblem, X, Y, targets, reg=0.0,
                 session: api.Session | None = None):
    """0.5 ||SDDMM(mask, X, Y) - targets||^2 on the observed entries.

    The graph-embedding / matrix-completion objective: only the sampled
    predictions ``<x_i, y_j>`` at nnz(mask) enter the loss, so both the
    forward and (via the dual primitives) the backward communicate like
    one SDDMM/SpMM pair — never a dense m x n matrix.
    """
    pred = grads.sddmm(maskP, X, Y, session=session)
    out = 0.5 * jnp.sum((pred - jnp.asarray(targets)) ** 2)
    if reg:
        out = out + 0.5 * reg * (jnp.sum(X * X) + jnp.sum(Y * Y))
    return out


def train_embedding_distributed(m=256, n=256, nnz_per_row=6, r=16,
                                steps=20, lr=0.05, seed=0,
                                algorithm="auto", c=None, devices=None,
                                reg=1e-4, rows=None, cols=None, vals=None,
                                monitor=None, ckpt_dir=None, ckpt_every=5,
                                max_retries=2, verbose=True):
    """End-to-end distributed embedding training by SGD on the sampled
    loss — every step one distributed SDDMM forward plus its dual
    SpMM/SpMM-transpose backward on the same grid, with an
    ``api.Session`` replaying the forward's replication in the backward.

    Pass explicit ``(rows, cols, vals)`` — all three, plus the matching
    ``m``/``n`` — to train on a real matrix (e.g. loaded via
    :func:`repro.core.mtx.load_mtx`); by default a seeded Erdos-Renyi
    ratings matrix is generated.  Returns ``(X, Y, hist)`` with a
    decreasing loss history.

    Robustness wiring (docs/robustness.md): every step runs under
    ``elastic.run_step_resilient`` — a ``TransientFault`` invalidates the
    Session's replication for this grid and retries; a ``DeviceLost``
    re-plans onto a degraded mesh via :func:`api.degrade` before
    retrying.  ``monitor`` (a :class:`elastic.StepMonitor`) times each
    step for straggler flagging.  With ``ckpt_dir`` the factors are
    checkpointed every ``ckpt_every`` steps alongside the problem's
    :meth:`api.DistProblem.meta_dict`, and training resumes from the
    latest committed step — rebuilding the packs via
    :func:`api.problem_from_meta` (same mesh -> pinned family/c; changed
    device count -> cost-model re-dispatch).
    """
    if rows is None:
        if cols is not None or vals is not None:
            raise ValueError("pass rows, cols and vals together")
        rows, cols, vals = sparse.erdos_renyi(m, n, nnz_per_row, seed=seed)
        vals = np.abs(vals) + 0.5
    else:
        if cols is None or vals is None:
            raise ValueError("pass rows, cols and vals together")
        if int(np.max(rows, initial=0)) >= m \
                or int(np.max(cols, initial=0)) >= n:
            raise ValueError(
                f"coordinates exceed shape ({m}, {n}) — pass the "
                "matrix's m/n alongside rows/cols/vals")
    maskP = api.make_problem(rows, cols, np.ones_like(vals, np.float32),
                             (m, n), r, algorithm=algorithm, c=c,
                             devices=devices)
    rng = np.random.default_rng(seed + 1)
    X = jnp.asarray(rng.standard_normal((m, r)) * 0.1, jnp.float32)
    Y = jnp.asarray(rng.standard_normal((n, r)) * 0.1, jnp.float32)
    targets = jnp.asarray(vals, jnp.float32)
    session = api.Session()

    def make_grad(prob):
        return jax.value_and_grad(
            lambda X, Y: sampled_loss(prob, X, Y, targets, reg, session),
            argnums=(0, 1))

    grad_fn = make_grad(maskP)

    start = 0
    if ckpt_dir is not None:
        last = checkpoint.latest_step(ckpt_dir)
        if last is not None:
            meta = checkpoint.load_manifest(ckpt_dir, last).get("meta")
            if meta is not None:
                maskP = api.problem_from_meta(
                    meta, rows, cols, np.ones_like(vals, np.float32),
                    devices=devices)
                grad_fn = make_grad(maskP)
            tree = checkpoint.restore(ckpt_dir, last, {"X": X, "Y": Y})
            X, Y = jnp.asarray(tree["X"]), jnp.asarray(tree["Y"])
            start = last
            if verbose:
                print(f"embed: resumed step {last} on "
                      f"{maskP.alg.name} p={maskP.p}")

    def on_failure(attempt, e):
        nonlocal maskP, grad_fn
        session.invalidate(maskP)
        if isinstance(e, faults.DeviceLost):
            maskP = api.degrade(maskP, e.rank)
            grad_fn = make_grad(maskP)
            if verbose:
                print(f"embed: lost rank {e.rank} -> re-planned onto "
                      f"{maskP.alg.name} p={maskP.p}")

    hist = []
    for it in range(start, steps):
        def step(X, Y):
            if monitor is not None:
                return monitor.timed(it, grad_fn, X, Y)
            return grad_fn(X, Y)

        val, (gx, gy) = elastic.run_step_resilient(
            step, None, None, X, Y,
            max_retries=max_retries, on_failure=on_failure)
        X = X - lr * gx
        Y = Y - lr * gy
        hist.append(float(val))
        if verbose:
            print(f"embed[{maskP.alg.name}] step {it}: loss {val:.3f}")
        if ckpt_dir is not None and (it + 1) % ckpt_every == 0:
            checkpoint.save(ckpt_dir, it + 1,
                            {"X": np.asarray(X), "Y": np.asarray(Y)},
                            meta=maskP.meta_dict())
    return X, Y, hist


def run_als(m=1024, n=1024, nnz_per_row=8, r=32, rounds=3, cg_iters=10,
            seed=0, verbose=True):
    prob = make_problem(m, n, nnz_per_row, r, seed=seed)
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((m, r)) * 0.1, jnp.float32)
    B = jnp.asarray(rng.standard_normal((n, r)) * 0.1, jnp.float32)
    hist = [loss(prob, A, B)]
    for it in range(rounds):
        A, B = als_round(prob, A, B, cg_iters)
        hist.append(loss(prob, A, B))
        if verbose:
            print(f"ALS round {it}: loss {hist[-2]:.1f} -> {hist[-1]:.1f}")
    return A, B, hist
