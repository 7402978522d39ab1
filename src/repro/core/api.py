"""Unified distributed-algorithm API: Algorithm registry, DistProblem,
Session (paper §V + §VI-E applications).

The four executor families (``d15``, ``s15``, ``d25``, ``s25``) implement
the same mathematical procedures — SDDMM, SpMM and FusedMM — with four
different communication schedules.  This module puts them behind ONE
abstraction so applications, launch tooling and benchmarks never branch
per family:

* **Algorithm** — registry entry binding a family's planner and its
  sddmm/spmm/fusedmm executors to a shared signature.  All algorithms
  expose *FusedMMA semantics*: ``fusedmm(S, X, Y) = (S * (X @ Y.T)) @ Y``
  with output ``(m, r)``; where a family's replication-reuse executor is
  the FusedMMB form (d15/d25), the registry runs it on the transpose pack
  with swapped operands — ``FusedMMA(S, X, Y) = FusedMMB(S^T, Y, X)`` —
  so the caller-visible contract never changes.  The elision matrix is
  full rank: every entry declares ``reuse`` and (except s25, where it is
  structurally impossible) ``fused``, each cell backed by a Table-III
  word-count row in ``costmodel`` — docs/algorithms.md tabulates the
  grid with per-cell formulas.
* **DistProblem** — owns the host COO of S, the processor grid, and the
  device-placed packs in every orientation the chosen strategies need
  (built lazily, amortized across calls like the paper's preprocessing).
* **Session** — caches *replication state*: the fiber-all-gathered copy of
  a dense operand.  Within one FusedMM call the paper's replication-reuse
  elision shares a single all-gather between the SDDMM and SpMM rounds;
  the Session extends the same elision **across calls** — ALS's CG loop
  calls FusedMM every iteration with the same stationary factor matrix,
  so its gather is paid once per solve instead of once per iteration.
  Cached calls are bitwise-identical to uncached ones: the executors'
  ``pre_gathered`` paths feed the local kernels the very same operand
  values the in-call all-gather would have produced.

Dispatch: ``make_problem(..., algorithm="auto")`` ranks every feasible
(family, elision, c) by the paper's Table-III bandwidth formulas
(:func:`repro.core.costmodel.choose_algorithm`) — low phi = nnz/(n*r)
selects the sparse-shifting/replicating families, high phi the dense ones.

Results come back host-assembled (numpy) so the contract is uniform
across the four families' on-device layouts; the family modules remain
the layout-aware fast path for callers that keep data device-resident.
"""
from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import dataclasses
import functools
import hashlib
import os
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import common, costmodel, d15, d25, s15, s25
from repro.core.grid import make_grid15, make_grid25
from repro.distributed import faults

__all__ = [
    "ALGORITHMS", "Algorithm", "DistProblem", "Session", "SparseResult",
    "make_problem", "sddmm", "spmm", "spmm_t", "fusedmm", "activate",
    "ElasticProblem", "RetryPolicy", "FaultRecoveryError",
    "problem_from_meta", "degrade", "spmm_batched",
]


def _tracer_active():
    """The active obs tracer, or None.

    Function-scoped import by design (lint rule R1): ``repro.core`` is
    the foundation layer and must stay importable without the obs
    stack; resolving through ``sys.modules`` per call also keeps the
    tests' module-level monkeypatching visible."""
    from repro.obs import tracer as obs_tracer
    return obs_tracer.active()


def _metrics_active():
    """The active obs metrics registry, or None (lazy — see above)."""
    from repro.obs import metrics as obs_metrics
    return obs_metrics.active()


def _span(name: str, **args):
    """A host span on the profiler's clock, ``repro.obs.span`` (lazy —
    see above)."""
    from repro.obs import spans
    return spans.span(name, **args)


# ---------------------------------------------------------------------------
# Results
# ---------------------------------------------------------------------------

def _match_coo(sorted_keys, order, keys):
    """Locate query coordinate keys (r*n + c) in a problem's COO.

    ``(sorted_keys, order)`` come from :meth:`DistProblem.coo_sort`
    (computed once per problem — the coordinates never change).  Returns
    (positions, ok): for each query key, a position into the problem's
    COO order and a mask of keys that actually occur there.  O(q log nnz)
    per call; never materializes a dense matrix.
    """
    if len(order) == 0:
        return (np.zeros(len(keys), np.int64),
                np.zeros(len(keys), bool))
    pos = np.minimum(np.searchsorted(sorted_keys, keys), len(order) - 1)
    idx = order[pos]
    return idx, sorted_keys[pos] == keys

@dataclasses.dataclass
class SparseResult:
    """Sampled (SDDMM-shaped) output in its family's home layout.

    ``raw`` keeps the device-side values exactly as the executor returned
    them (per-phase tuples for d15, fiber-sharded shards for s25, ...);
    ``_triples`` assembles the flat global COO view — O(nnz), never a
    dense matrix — from which ``values``/``to_dense`` derive.
    """
    problem: "DistProblem"
    raw: object
    _triples: Callable[[], tuple]
    _coo: Optional[tuple] = None
    _vals: Optional[np.ndarray] = None

    def to_coo(self):
        """Flat global (rows, cols, vals), padding filtered."""
        if self._coo is None:
            self._coo = self._triples()
        return self._coo

    def to_dense(self) -> np.ndarray:
        """Dense (m, n) matrix with the sampled values scattered in.

        Quadratic in the matrix dimensions — small/debug problems only;
        prefer ``values``/``to_coo`` on production shapes.
        """
        r, c, v = self.to_coo()
        out = np.zeros((self.problem.m, self.problem.n), np.float64)
        np.add.at(out, (r, c), v)
        return out.astype(np.float32)

    def values(self) -> np.ndarray:
        """Values aligned with the problem's host COO (rows, cols) order.

        O(nnz log nnz): the assembled triples are matched to the
        problem's coordinate keys — no dense materialization.
        """
        if self._vals is None:
            prob = self.problem
            r, c, v = self.to_coo()
            sk, order = prob.coo_sort()
            idx, ok = _match_coo(sk, order, r * prob.n + c)
            out = np.bincount(idx[ok], weights=v[ok], minlength=prob.nnz)
            self._vals = out.astype(np.float32)
        return self._vals


# ---------------------------------------------------------------------------
# Algorithm registry
# ---------------------------------------------------------------------------

ALGORITHMS: Dict[str, "Algorithm"] = {}


class Algorithm:
    """Registry entry: one distributed algorithm family behind the shared
    plan/sddmm/spmm/fusedmm signature.  Subclasses adapt layouts only —
    the executors live in their family modules."""

    name: str = ""
    elisions: Tuple[str, ...] = ()       # strategies fusedmm accepts
    auto_elisions: Tuple[str, ...] = ()  # candidates for elision="auto"
    #: the family schedule module (d15/s15/d25/s25) — set per subclass;
    #: typed Any because each module exposes the schedule_* contract
    #: structurally, not through a shared base.
    _sched_mod: Any = None

    # -- grid / feasibility --------------------------------------------------
    def make_grid(self, c: int, devices):
        raise NotImplementedError

    def make_plan(self, prob, orient: str):
        """Build this family's pack for one orientation (host, amortized)."""
        raise NotImplementedError

    def feasible(self, *, m: int, n: int, r: int, p: int, c: int) -> bool:
        return costmodel.family_feasible(self.name, m=m, n=n, r=r, p=p, c=c)

    def min_r_multiple(self, grid) -> int:
        """Smallest multiple the dense operand width r must obey."""
        return 1

    def schedule_events(self, prob, op: str, elision: str = "none"):
        """This family's ordered (point, phase) fault boundaries for one
        ``op`` round — the coordinates ``repro.distributed.faults``
        scripts failures at (each family module exports its own)."""
        return self._sched_mod.schedule_events(prob.grid, op, elision)

    def schedule_words(self, prob, op: str, elision: str = "none",
                       session: Optional["Session"] = None):
        """Modeled per-device wire words for each schedule event.

        Returns ``(point, phase, kind, words)`` tuples aligned 1:1 with
        :meth:`schedule_events` — the live cost-model side of the obs
        tracer (``repro.obs``).  The formulas are impl-exact for dense
        wire formats (including XLA's dead-code elimination of unread
        cycle-closing shifts); ``session`` models the pre-gathered
        (replay) program the executors compile when one is passed.
        Returns None for support-pruned (``comm="sparse"``) packs, whose
        volume is data-dependent — drift is undefined there."""
        plan, pre = self._words_plan(prob, op, elision, session)
        if plan.smeta is not None:
            return None
        return self._sched_mod.schedule_words(prob.grid, plan, op,
                                              elision=elision,
                                              pre_gathered=pre)

    def _words_plan(self, prob, op, elision, session):
        """(plan, pre_gathered) mirroring this family's ``_*_call``
        orientation and Session behavior for one op."""
        raise NotImplementedError

    # -- layouts -------------------------------------------------------------
    def shard_x(self, prob, X):
        """Place an (m, r) operand in this family's X input layout."""
        raise NotImplementedError

    def shard_y(self, prob, Y):
        """Place an (n, r) operand in this family's Y input layout."""
        raise NotImplementedError

    def replicate(self, prob, arr, slot: str):
        """Place an operand in the fiber-replicated (gathered) layout —
        the across-call replication state a Session caches."""
        raise NotImplementedError

    # -- execution (device in, host out) ------------------------------------
    @staticmethod
    def _run(call, *call_args):
        """One call of an executor: ``call`` (a ``_*_call`` adapter)
        places the operands under the span ``api.put``, the executor is
        dispatched, and its ``post`` assembles the host result under
        ``api.assemble``."""
        with _span("api.put"):
            fn, args, kwargs, post = call(*call_args)
        res = fn(*args, **kwargs)
        with _span("api.assemble"):
            return post(res)

    def sddmm(self, prob, X, Y, session=None) -> SparseResult:
        """R = S * (X Y^T) sampled at nnz(S).  ``session`` serves the
        family's fiber replication of the dense operand(s) from the
        across-call cache (d15/s15/d25; s25 replicates nothing)."""
        return self._run(self._sddmm_call, prob, X, Y, session)

    def _sddmm_call(self, prob, X, Y, session):
        raise NotImplementedError

    def spmm(self, prob, Y, vals=None, session=None) -> np.ndarray:
        """out = S(vals) @ Y.  ``vals`` (host COO order) substitutes the
        sample values via the cached structure pack
        (:meth:`DistProblem.injected_plan`); ``session`` serves the
        dense gather where the family has one (s15 only — the other
        families' SpMM replicates nothing inbound)."""
        return self._run(self._spmm_call, prob, Y, vals, session)

    def _spmm_call(self, prob, Y, vals, session):
        raise NotImplementedError

    def lower_sddmm(self, prob, session: Optional["Session"] = None):
        """Lower the family's jitted SDDMM for HLO/wire-word analysis;
        with a ``session``, the pre-gathered (replay) variant."""
        X = np.zeros((prob.m, prob.r), np.float32)
        Y = np.zeros((prob.n, prob.r), np.float32)
        fn, args, kwargs, _ = self._sddmm_call(prob, X, Y, session)
        return fn.lower(*args, **kwargs)

    def lower_spmm(self, prob, session: Optional["Session"] = None):
        """Lower the family's jitted SpMM for HLO/wire-word analysis."""
        Y = np.zeros((prob.n, prob.r), np.float32)
        fn, args, kwargs, _ = self._spmm_call(prob, Y, None, session)
        return fn.lower(*args, **kwargs)

    def spmm_t(self, prob, A, vals=None, session=None) -> np.ndarray:
        """out = S(vals)^T @ A on the SAME grid — the dual of spmm.

        d15/d25 run their native FusedMMB-style executor on S's
        transpose pack; s15/s25 run spmm on the transposed problem.
        Where the executor all-gathers A, the gather is Session-
        replayable — the backward of a training step reuses the
        forward's replication of A this way (repro.core.grads).
        ``vals`` (problem host-COO order) overrides the pack's sample
        values.
        """
        return self._run(self._spmm_t_call, prob, A, vals, session)

    def _spmm_t_call(self, prob, A, vals, session):
        raise NotImplementedError

    def fusedmm(self, prob, X, Y, elision: str,
                session: Optional["Session"]):
        return self._run(self._fusedmm_call, prob, X, Y, elision, session)

    def lower_fusedmm(self, prob, elision: str,
                      session: Optional["Session"] = None):
        """Lower the family's jitted FusedMM for HLO/roofline analysis.

        Passing a ``session`` lowers the Session-replayed variant (the
        pre-gathered program, no in-call fiber all-gather) — what a
        training step's backward dual-FusedMM actually compiles to."""
        X = np.zeros((prob.m, prob.r), np.float32)
        Y = np.zeros((prob.n, prob.r), np.float32)
        fn, args, kwargs, _ = self._fusedmm_call(prob, X, Y, elision,
                                                 session)
        return fn.lower(*args, **kwargs)

    def lower_spmm_t(self, prob, session: Optional["Session"] = None):
        """Lower the jitted SpMM-transpose (the VJP's dual kernel)."""
        A = np.zeros((prob.m, prob.r), np.float32)
        fn, args, kwargs, _ = self._spmm_t_call(prob, A, None, session)
        return fn.lower(*args, **kwargs)

    def _fusedmm_call(self, prob, X, Y, elision, session):
        raise NotImplementedError


def register(cls):
    alg = cls()
    ALGORITHMS[alg.name] = alg
    return cls


def _put(arr, sharding):
    """Upload host rows straight to their shards: no whole copy of the
    array passes through the default device first."""
    a = np.asarray(arr, np.float32)
    with _span("api.upload", bytes=a.nbytes):
        return jax.device_put(a, sharding)


# ---------------------------------------------------------------------------
# 1.5D dense shifting
# ---------------------------------------------------------------------------

@register
class _D15(Algorithm):
    name = "d15"
    elisions = ("none", "reuse", "fused")
    auto_elisions = ("none", "reuse", "fused")
    _sched_mod = d15

    def make_grid(self, c, devices):
        return make_grid15(c, devices=devices)

    def make_plan(self, prob, orient):
        kw = dict(row_tile=prob.row_tile, nz_block=prob.nz_block,
                  comm=prob.comm, compress=prob.compress)
        if orient == "normal":
            return d15.plan_d15(prob.grid, prob.rows, prob.cols, prob.vals,
                                prob.m, prob.n, prob.r, **kw)
        return d15.plan_d15(prob.grid, prob.cols, prob.rows, prob.vals,
                            prob.n, prob.m, prob.r, transpose=True, **kw)

    def shard_x(self, prob, X):
        g = prob.grid
        return _put(X, g.sharding((g.layer, g.fiber)))

    shard_y = shard_x   # same layout, different row count

    def replicate(self, prob, arr, slot):
        g = prob.grid
        return _put(arr, g.sharding(g.layer))

    def _words_plan(self, prob, op, elision, session):
        pre = session is not None
        if op == "spmm":
            return prob.plan("normal"), False   # nothing inbound replicated
        if op == "spmm_t":
            return prob.transposed().plan("transpose"), pre
        if op == "fusedmm" and elision == "reuse":
            return prob.plan("transpose"), pre
        return prob.plan("normal"), pre

    def _sddmm_call(self, prob, X, Y, session):
        plan = prob.plan("normal")
        if session is not None:
            a, pre = session.replicate(prob, X, "x"), True
        else:
            a, pre = self.shard_x(prob, X), False

        def post(rv):
            return SparseResult(prob, rv,
                                lambda: plan.meta.block_meta.to_triples(
                                    plan.rows_local, plan.cols, rv,
                                    plan.tile_base))

        return (d15.sddmm_d15, (prob.grid, plan, a, self.shard_y(prob, Y)),
                dict(pre_gathered=pre), post)

    def _spmm_call(self, prob, Y, vals, session):
        # B shifts and the output reduce-scatters: nothing inbound is
        # replicated, so there is no gather for a session to serve
        plan = prob.injected_plan("normal", vals)
        return (d15.spmma_d15, (prob.grid, plan, self.shard_y(prob, Y)),
                {}, common.fetch)

    def _spmm_t_call(self, prob, A, vals, session):
        # native FusedMMB-half: spmmb on S's transpose pack — which is
        # the TRANSPOSED problem's "transpose" orientation (this
        # problem's own "transpose" plan packs (S^T)^T for the reuse
        # cell).  The AG of A is Session-replayable (pre_gathered),
        # unlike a transposed spmma whose output reduce-scatter could
        # never be elided.
        plan = prob.transposed().injected_plan("transpose", vals)
        if session is not None:
            a, pre = session.replicate(prob, A, "x"), True
        else:
            a, pre = self.shard_x(prob, A), False
        return (d15.spmmb_d15, (prob.grid, plan, a),
                dict(pre_gathered=pre), common.fetch)

    def _fusedmm_call(self, prob, X, Y, elision, session):
        grid = prob.grid
        if elision == "reuse":
            # FusedMMA(S, X, Y) = FusedMMB(S^T, Y, X): Y takes the
            # replicated slot, X the shifting slot, on the S^T pack.
            plan = prob.plan("transpose")
            a_host, slot = Y, "y"
            b = self.shard_x(prob, X)
        else:
            plan = prob.plan("normal")
            a_host, slot = X, "x"
            b = self.shard_y(prob, Y)
        if session is not None:
            a, pre = session.replicate(prob, a_host, slot), True
        else:
            a, pre = (self.shard_x if slot == "x" else self.shard_y)(
                prob, a_host), False

        def post(res):
            out, rvals = res
            return common.fetch(out), SparseResult(
                prob, rvals, lambda: plan.meta.block_meta.to_triples(
                    plan.rows_local, plan.cols, rvals, plan.tile_base))

        return (d15.fusedmm_d15, (grid, plan, a, b),
                dict(elision=elision, pre_gathered=pre), post)


# ---------------------------------------------------------------------------
# 1.5D sparse shifting
# ---------------------------------------------------------------------------

@register
class _S15(Algorithm):
    name = "s15"
    elisions = ("none", "reuse", "fused")
    auto_elisions = ("fused", "reuse", "none")
    _sched_mod = s15

    def make_grid(self, c, devices):
        return make_grid15(c, devices=devices)

    def make_plan(self, prob, orient):
        assert orient == "normal", "s15 keeps S stationary-by-row"
        return s15.plan_s15(prob.grid, prob.rows, prob.cols, prob.vals,
                            prob.m, prob.n, prob.r,
                            row_tile=prob.row_tile, nz_block=prob.nz_block,
                            comm=prob.comm, compress=prob.compress)

    def min_r_multiple(self, grid):
        return grid.p

    def shard_x(self, prob, X):
        g = prob.grid
        return _put(X, g.sharding(None, (g.layer, g.fiber)))

    shard_y = shard_x

    def replicate(self, prob, arr, slot):
        g = prob.grid
        return _put(arr, g.sharding(None, g.layer))

    def _rvals_triples(self, prob, plan, rv):
        return lambda: plan.meta.block_meta.to_triples(
            plan.rows_local, plan.cols, common.fetch(rv), plan.tile_base)

    def _words_plan(self, prob, op, elision, session):
        pre = session is not None
        if op == "spmm_t":
            # the A gather lands in the single-gather (B) slot of the
            # transposed problem's plan, same as _spmm_t_call
            return prob.transposed().plan("normal"), (False, pre)
        if op == "spmm":
            return prob.plan("normal"), (False, pre)
        return prob.plan("normal"), (pre, pre)

    def _sddmm_call(self, prob, X, Y, session):
        plan = prob.plan("normal")
        if session is not None:
            a = session.replicate(prob, X, "x")
            b = session.replicate(prob, Y, "y")
            pre = (True, True)
        else:
            a, b = self.shard_x(prob, X), self.shard_y(prob, Y)
            pre = (False, False)

        def post(rv):
            return SparseResult(prob, rv,
                                self._rvals_triples(prob, plan, rv))

        return (s15.sddmm_s15, (prob.grid, plan, a, b),
                dict(pre_gathered=pre), post)

    def _spmm_call(self, prob, Y, vals, session):
        plan = prob.injected_plan("normal", vals)
        if session is not None:
            b, pre = session.replicate(prob, Y, "y"), True
        else:
            b, pre = self.shard_y(prob, Y), False
        return (s15.spmma_s15, (prob.grid, plan, b),
                dict(pre_gathered=pre),
                lambda slabs: s15.assemble_spmm_out(prob.grid, plan, slabs))

    def _spmm_t_call(self, prob, A, vals, session):
        # S stays stationary-by-row, so the transpose runs on the S^T
        # problem (same grid); the column-slab gather of A is Session-
        # replayable — same layout the forward replicated A in.
        tp = prob.transposed()
        plan = tp.injected_plan("normal", vals)
        if session is not None:
            a, pre = session.replicate(tp, A, "x"), True
        else:
            a, pre = self.shard_x(tp, A), False
        return (s15.spmma_s15, (tp.grid, plan, a), dict(pre_gathered=pre),
                lambda slabs: s15.assemble_spmm_out(tp.grid, plan, slabs))

    def _fusedmm_call(self, prob, X, Y, elision, session):
        grid = prob.grid
        plan = prob.plan("normal")
        if session is not None:
            a = session.replicate(prob, X, "x")
            b = session.replicate(prob, Y, "y")
            pre = (True, True)
        else:
            a, b = self.shard_x(prob, X), self.shard_y(prob, Y)
            pre = (False, False)

        def post(res):
            slabs, rvals = res
            return (s15.assemble_spmm_out(grid, plan, slabs),
                    SparseResult(prob, rvals,
                                 self._rvals_triples(prob, plan, rvals)))

        return (s15.fusedmm_s15, (grid, plan, a, b),
                dict(elision=elision, pre_gathered=pre), post)


# ---------------------------------------------------------------------------
# 2.5D dense replicating
# ---------------------------------------------------------------------------

@register
class _D25(Algorithm):
    name = "d25"
    elisions = ("none", "reuse", "fused")
    auto_elisions = ("fused", "reuse", "none")
    _sched_mod = d25

    def make_grid(self, c, devices):
        return make_grid25(c, devices=devices)

    def make_plan(self, prob, orient):
        kw = dict(row_tile=prob.row_tile, nz_block=prob.nz_block,
                  comm=prob.comm, compress=prob.compress)
        if orient == "normal":
            return d25.plan_d25(prob.grid, prob.rows, prob.cols, prob.vals,
                                prob.m, prob.n, prob.r, **kw)
        return d25.plan_d25(prob.grid, prob.cols, prob.rows, prob.vals,
                            prob.n, prob.m, prob.r, transpose=True, **kw)

    def min_r_multiple(self, grid):
        return grid.G

    def shard_x(self, prob, X):
        # the replicated-slot layout; the shifting operand is skewed via
        # d25.skew_b at the call sites below
        g = prob.grid
        return _put(X, g.sharding((g.row, g.fiber), g.col))

    def replicate(self, prob, arr, slot):
        g = prob.grid
        return _put(arr, g.sharding(g.row, g.col))

    def _words_plan(self, prob, op, elision, session):
        pre = session is not None
        if op == "spmm":
            return prob.plan("normal"), False   # Cannon-shifts, no gather
        if op == "spmm_t":
            return prob.transposed().plan("transpose"), pre
        if op == "fusedmm" and elision == "reuse":
            return prob.plan("transpose"), pre
        return prob.plan("normal"), pre

    def _sddmm_call(self, prob, X, Y, session):
        plan = prob.plan("normal")
        if session is not None:
            a, pre = session.replicate(prob, X, "x"), True
        else:
            a, pre = self.shard_x(prob, X), False

        def post(rv):
            return SparseResult(prob, rv,
                                lambda: plan.meta.block_meta.to_triples(
                                    plan.rows_local, plan.cols,
                                    common.fetch(rv), plan.tile_base))

        return (d25.sddmm_d25,
                (prob.grid, plan, a,
                 d25.skew_b(prob.grid, np.asarray(Y, np.float32))),
                dict(pre_gathered=pre), post)

    def _spmm_call(self, prob, Y, vals, session):
        # B Cannon-shifts and the output reduce-scatters: no inbound
        # replication for a session to serve
        plan = prob.injected_plan("normal", vals)
        return (d25.spmma_d25,
                (prob.grid, plan,
                 d25.skew_b(prob.grid, np.asarray(Y, np.float32))),
                {}, common.fetch)

    def _spmm_t_call(self, prob, A, vals, session):
        # native FusedMMB-half on the Cannon grid (see _D15._spmm_t_call
        # for why the transposed problem's "transpose" orientation is
        # S's own transpose pack)
        plan = prob.transposed().injected_plan("transpose", vals)
        if session is not None:
            a, pre = session.replicate(prob, A, "x"), True
        else:
            a, pre = self.shard_x(prob, A), False
        return (d25.spmmb_d25, (prob.grid, plan, a),
                dict(pre_gathered=pre),
                lambda out: d25.unskew_out(prob.grid, plan, out))

    def _fusedmm_call(self, prob, X, Y, elision, session):
        grid = prob.grid
        if elision == "reuse":
            plan = prob.plan("transpose")
            a_host, slot = Y, "y"
            b = d25.skew_b(grid, np.asarray(X, np.float32))
        else:
            plan = prob.plan("normal")
            a_host, slot = X, "x"
            b = d25.skew_b(grid, np.asarray(Y, np.float32))
        if session is not None:
            a, pre = session.replicate(prob, a_host, slot), True
        else:
            a, pre = self.shard_x(prob, a_host), False

        def post(res):
            out, rvals = res
            triples = lambda: plan.meta.block_meta.to_triples(  # noqa: E731
                plan.rows_local, plan.cols, common.fetch(rvals),
                plan.tile_base)
            if elision == "reuse":
                return (d25.unskew_out(grid, plan, out),
                        SparseResult(prob, rvals, triples))
            return common.fetch(out), SparseResult(prob, rvals, triples)

        return (d25.fusedmm_d25, (grid, plan, a, b),
                dict(elision=elision, pre_gathered=pre), post)


# ---------------------------------------------------------------------------
# 2.5D sparse replicating
# ---------------------------------------------------------------------------

@register
class _S25(Algorithm):
    name = "s25"
    # "fused" is structurally impossible here (docs/algorithms.md): the
    # cross-fiber partial-sum reduction separates the SDDMM and SpMM
    # halves, and the stationary S ships no structure to elide.
    elisions = ("none", "reuse")
    auto_elisions = ("reuse", "none")
    _sched_mod = s25

    def make_grid(self, c, devices):
        return make_grid25(c, devices=devices)

    def make_plan(self, prob, orient):
        assert orient == "normal", "s25 replicates the structure"
        return s25.plan_s25(prob.grid, prob.rows, prob.cols, prob.vals,
                            prob.m, prob.n, prob.r,
                            row_tile=prob.row_tile, nz_block=prob.nz_block,
                            comm=prob.comm, compress=prob.compress)

    def min_r_multiple(self, grid):
        return grid.G * grid.c

    def shard_x(self, prob, X):
        return s25.skew_dense(prob.grid, np.asarray(X, np.float32),
                              along="row")

    def shard_y(self, prob, Y):
        return s25.skew_dense(prob.grid, np.asarray(Y, np.float32),
                              along="col")

    # nothing dense is replicated: Session caching is a no-op here
    def replicate(self, prob, arr, slot):
        return self.shard_x(prob, arr) if slot == "x" \
            else self.shard_y(prob, arr)

    def _rvals_triples(self, prob, plan, rv):
        def triples():
            g = prob.grid
            G, nb = g.G, plan.rows_local.shape[3]
            vals = common.fetch(rv)
            full = vals.reshape(G, G, nb, vals.shape[-1])
            return plan.meta.block_meta.to_triples(
                np.asarray(plan.rows_local)[:, :, 0],
                np.asarray(plan.cols)[:, :, 0], full,
                np.asarray(plan.tile_base)[:, :, 0])
        return triples

    def _words_plan(self, prob, op, elision, session):
        del elision, session            # Session-inert, values-only fiber
        if op == "spmm_t":
            return prob.transposed().plan("normal"), False
        return prob.plan("normal"), False

    def _sddmm_call(self, prob, X, Y, session):
        # nothing dense is replicated: session accepted and ignored
        plan = prob.plan("normal")

        def post(rv):
            return SparseResult(prob, rv,
                                self._rvals_triples(prob, plan, rv))

        return (s25.sddmm_s25,
                (prob.grid, plan, self.shard_x(prob, X),
                 self.shard_y(prob, Y)), {}, post)

    def _spmm_call(self, prob, Y, vals, session):
        plan = prob.injected_plan("normal", vals)
        return (s25.spmma_s25, (prob.grid, plan, self.shard_y(prob, Y)),
                {}, lambda out: s25.unskew_out(prob.grid, plan, out))

    def _spmm_t_call(self, prob, A, vals, session):
        # spmm on the transposed problem (structure re-replicated on the
        # same grid); nothing dense is replicated, so there is no gather
        # for a Session to replay — session is accepted and ignored.
        tp = prob.transposed()
        plan = tp.injected_plan("normal", vals)
        return (s25.spmma_s25, (tp.grid, plan, self.shard_y(tp, A)), {},
                lambda out: s25.unskew_out(tp.grid, plan, out))

    def _fusedmm_call(self, prob, X, Y, elision, session):
        grid = prob.grid
        plan = prob.plan("normal")
        a, b = self.shard_x(prob, X), self.shard_y(prob, Y)

        def post(res):
            out, rvals = res
            return (s25.unskew_out(grid, plan, out),
                    SparseResult(prob, rvals,
                                 self._rvals_triples(prob, plan, rvals)))

        return (s25.fusedmm_s25, (grid, plan, a, b),
                dict(elision=elision), post)


# ---------------------------------------------------------------------------
# DistProblem
# ---------------------------------------------------------------------------

_COST_NAME = costmodel.ELISION_COST_NAME


@dataclasses.dataclass
class DistProblem:
    """A packed sparse matrix + dense layouts bound to one algorithm/grid.

    Plans (the amortized host-side packing of S, and of S^T where a
    strategy needs it) are built lazily per orientation and cached, so
    repeated kernel calls — ALS's CG loop, GAT's per-layer sweeps — pay
    planning once, exactly like the paper's preprocessing."""
    alg: Algorithm
    #: the family grid (Grid15/Grid25) — structural (``.p``/``.L``/
    #: ``.G`` reads), no shared base class
    grid: Any
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    m: int
    n: int
    r: int
    row_tile: int = 32
    nz_block: int = 32
    #: wire format for the dense-operand movements: "dense" ships full
    #: fibers/chunks, "sparse" support-prunes each channel at plan time
    #: (crossover-guarded per channel; bitwise-identical results either
    #: way).  Resolved from "auto" in :func:`make_problem`.
    comm: str = "dense"
    #: optional payload compression for the PRUNED sends ("bf16" or
    #: None); dense-mode channels ignore it.
    compress: Optional[str] = None
    _plans: dict = dataclasses.field(default_factory=dict)
    _derived_r: dict = dataclasses.field(default_factory=dict)
    _posmaps: dict = dataclasses.field(default_factory=dict)
    _coo_sort: Optional[tuple] = None
    _ones: Optional["DistProblem"] = None
    _transposed: Optional["DistProblem"] = None

    # -- metadata ------------------------------------------------------------
    @property
    def nnz(self) -> int:
        return int(len(self.vals))

    @property
    def phi(self) -> float:
        return self.nnz / (self.n * self.r)

    @property
    def p(self) -> int:
        return self.grid.p

    @property
    def c(self) -> int:
        return self.grid.c

    # -- planning ------------------------------------------------------------
    def plan(self, orient: str = "normal"):
        if orient not in self._plans:
            self._plans[orient] = self.alg.make_plan(self, orient)
        return self._plans[orient]

    def _posmap(self, orient: str):
        """Pack-slot -> host-COO-position map for one orientation.

        Built once per orientation by planning a position-coded copy of
        the problem (entry i carries value i+1; padding slots stay 0) —
        packing is deterministic in the coordinates, so the map is valid
        for ANY value vector on this structure."""
        if orient not in self._posmaps:
            posvals = np.arange(1, self.nnz + 1, dtype=np.float32)
            # packing is deterministic in the coordinates and identical
            # across comm modes, so the position plan skips the (pure
            # overhead here) support-set construction
            tmp = dataclasses.replace(
                self, vals=posvals, comm="dense", compress=None,
                _plans={}, _posmaps={},
                _derived_r={}, _ones=None, _transposed=None)
            pv = self.alg.make_plan(tmp, orient).vals

            def to_idx(a):
                return np.asarray(a).astype(np.int64)

            self._posmaps[orient] = (tuple(to_idx(a) for a in pv)
                                     if isinstance(pv, tuple) else
                                     to_idx(pv))
        return self._posmaps[orient]

    def injected_plan(self, orient: str, vals=None):
        """This orientation's plan with ``vals`` (host COO order)
        substituted into the value slots — the s25 family's "attractive
        property" (only values move between calls, the structure is
        packed once) generalized to every family.  The hot path of the
        backward pass: cotangent-valued sparse operands reuse the cached
        structure pack instead of re-planning per training step.

        Falls back to a full re-pack above 2^24 nonzeros, where float32
        position coding would alias."""
        if vals is None:
            return self.plan(orient)
        vals = np.asarray(vals, np.float32)
        if self.nnz >= (1 << 24):
            return self.with_values(vals).plan(orient)
        base = self.plan(orient)
        pos = self._posmap(orient)
        lookup = np.concatenate([np.zeros(1, np.float32), vals])

        def inject(pos_arr, old_dev):
            return jax.device_put(lookup[pos_arr], old_dev.sharding)

        if isinstance(base.vals, tuple):
            new_vals = tuple(inject(p, o)
                             for p, o in zip(pos, base.vals))
        else:
            new_vals = inject(pos, base.vals)
        return dataclasses.replace(base, vals=new_vals)

    def coo_sort(self):
        """(sorted coordinate keys, argsort order) — cached; coordinates
        are immutable for a problem's lifetime."""
        if self._coo_sort is None:
            key = self.rows.astype(np.int64) * self.n + self.cols
            if np.any(key[1:] < key[:-1]):
                order = np.argsort(key, kind="stable")
                key = key[order]
            else:                      # generators emit row-major COO
                order = np.arange(len(key))
            self._coo_sort = (key, order)
        return self._coo_sort

    # -- derived problems ----------------------------------------------------
    def with_values(self, vals: np.ndarray) -> "DistProblem":
        """Same structure, new sample values (e.g. softmaxed attention).

        Packing is deterministic in the coordinates, so the derived
        problem's blocks line up with this one's.  The derived problem
        re-packs on first use (values are baked into the device packs);
        value-churn-heavy callers that keep ONE problem and vary values
        per call (the backward passes, spmm with ``vals=``) should go
        through :meth:`injected_plan` instead, which reuses this
        problem's cached structure pack."""
        vals = np.asarray(vals, np.float32)
        assert vals.shape == self.rows.shape
        return dataclasses.replace(self, vals=vals, _plans={},
                                   _derived_r={}, _posmaps=self._posmaps,
                                   _ones=None, _transposed=None)

    def ones(self) -> "DistProblem":
        """The unit-valued problem on S's pattern (cached).

        The sampling mask: ``ones().sddmm(X, Y)`` yields the raw dots
        ``<x_i, y_j>`` at nnz(S) — what the backward of a values-
        differentiable SpMM needs (repro.core.grads)."""
        if self._ones is None:
            if bool(np.all(self.vals == 1.0)):
                self._ones = self
            else:
                self._ones = self.with_values(np.ones_like(self.vals))
        return self._ones

    def with_r(self, r: int) -> "DistProblem":
        """Same sparse matrix, different dense-operand width.

        Derived problems are cached by width, so repeated callers (e.g.
        GAT deriving score/aggregation widths once per layer) reuse one
        set of packs instead of re-planning every call."""
        if r == self.r:
            return self
        if r not in self._derived_r:
            mult = self.alg.min_r_multiple(self.grid)
            if r % mult:
                raise ValueError(f"r={r} must be a multiple of {mult} "
                                 f"for {self.alg.name} on this grid")
            self._derived_r[r] = dataclasses.replace(
                self, r=r, _plans={}, _derived_r={}, _posmaps={},
                _ones=None, _transposed=None)
        return self._derived_r[r]

    def with_pattern(self, rows, cols, vals=None, *, m: int | None = None,
                     n: int | None = None) -> "DistProblem":
        """A *different* sparse pattern on the SAME grid and algorithm —
        the serving tick's union-of-patterns entry point (docs/serving.md).

        The derived problem shares this problem's grid **object**, family,
        wire format and tiling knobs, so Session replication state — which
        is keyed by the grid identity plus operand content — carries over:
        the deployed factor matrices' fiber gathers, paid once per
        deployed graph, serve every per-tick query pattern's SDDMM
        directly.  Packs and posmaps are rebuilt lazily for the new
        structure (host-side packing, O(nnz) of the query pattern).
        ``vals=None`` installs unit samples (the SDDMM mask).  The shape
        defaults to this problem's ``(m, n)``; a different shape is
        validated against the family's feasibility rules."""
        m = self.m if m is None else int(m)
        n = self.n if n is None else int(n)
        rows = np.asarray(rows)
        cols = np.asarray(cols)
        if rows.ndim != 1 or rows.shape != cols.shape:
            raise ValueError("pattern rows/cols must be matching 1-D "
                             f"arrays, got {rows.shape} / {cols.shape}")
        if len(rows) == 0:
            raise ValueError("empty query pattern")
        vals = (np.ones(len(rows), np.float32) if vals is None
                else np.asarray(vals, np.float32))
        if vals.shape != rows.shape:
            raise ValueError(f"vals length {vals.shape} != pattern "
                             f"length {rows.shape}")
        if (int(rows.min()) < 0 or int(rows.max()) >= m
                or int(cols.min()) < 0 or int(cols.max()) >= n):
            raise ValueError(f"pattern coordinates outside ({m}, {n})")
        if (m, n) != (self.m, self.n) and not self.alg.feasible(
                m=m, n=n, r=self.r, p=self.p, c=self.c):
            raise ValueError(f"{self.alg.name} infeasible for pattern "
                             f"shape ({m}, {n}) on this grid")
        return dataclasses.replace(
            self, rows=rows, cols=cols, vals=vals, m=m, n=n,
            _plans={}, _derived_r={}, _posmaps={}, _coo_sort=None,
            _ones=None, _transposed=None)

    def spmm_batched(self, Ys, vals=None,
                     session: Optional["Session"] = None,
                     pad_to: int | None = None) -> List[np.ndarray]:
        """One SpMM round over column-concatenated right-hand sides.

        ``Ys`` is a sequence of ``(n, r_i)`` host arrays.  They are
        concatenated along columns, zero-padded up to the smallest
        feasible width (the summed widths rounded up to the family's
        r-multiple — or ``pad_to``, a caller-supplied bucket that bounds
        the set of compiled widths a long-running server accumulates),
        executed as ONE :meth:`spmm` at that width on the width-derived
        problem, and split back per request.  An SpMM's output columns
        are independent — ``out[:, j]`` consumes only ``Y[:, j]``, the
        nonzero accumulation order never depends on the dense width, and
        padding columns are zero and dropped — so the batched round is
        **bitwise-identical** to running each RHS alone (the serving
        batcher's parity contract, docs/serving.md).  ``vals`` /
        ``session`` exactly as for :meth:`spmm`."""
        Ys = [np.asarray(Y, np.float32) for Y in Ys]
        if not Ys:
            return []
        for Y in Ys:
            if Y.ndim != 2 or Y.shape[0] != self.n:
                raise ValueError(f"every RHS must be (n={self.n}, r_i), "
                                 f"got {Y.shape}")
        widths = [Y.shape[1] for Y in Ys]
        mult = self.alg.min_r_multiple(self.grid)
        r_tot = -(-max(sum(widths), 1) // mult) * mult
        if pad_to is not None:
            if pad_to < r_tot or pad_to % mult:
                raise ValueError(f"pad_to={pad_to} must be a multiple of "
                                 f"{mult} and >= {r_tot}")
            r_tot = pad_to
        cat = np.zeros((self.n, r_tot), np.float32)
        off = 0
        for Y, w in zip(Ys, widths):
            cat[:, off:off + w] = Y
            off += w
        prob = self if r_tot == self.r else self.with_r(r_tot)
        out = prob.spmm(cat, vals=vals, session=session)
        outs, off = [], 0
        for w in widths:
            outs.append(out[:, off:off + w])
            off += w
        return outs

    def transposed(self) -> "DistProblem":
        """The S^T problem on the same grid (for SpMMB-style updates).

        Cached: the backward pass hits this every training step, and the
        structure never changes — combined with :meth:`injected_plan`,
        the transpose pack is planned exactly once per problem."""
        if self._transposed is None:
            if not self.alg.feasible(m=self.n, n=self.m, r=self.r,
                                     p=self.p, c=self.c):
                raise ValueError(f"{self.alg.name} infeasible for the "
                                 f"transposed shape ({self.n}, {self.m})")
            tp = dataclasses.replace(self, rows=self.cols,
                                     cols=self.rows, m=self.n, n=self.m,
                                     _plans={}, _derived_r={},
                                     _posmaps={}, _coo_sort=None,
                                     _ones=None, _transposed=None)
            tp._transposed = self
            self._transposed = tp
        return self._transposed

    # -- elastic recovery ----------------------------------------------------
    def replan(self, *, devices=None, algorithm: str = "auto",
               c: int | None = None) -> "DistProblem":
        """Re-plan this problem from its host COO onto a (possibly
        different) device set — the elastic-recovery path after device
        loss.  ``algorithm="auto"`` re-runs the Table-III cost-model
        dispatch on the new mesh (family, elision candidates and
        ``optimal_c`` may all change with p); a family name pins it.
        ``devices=None`` re-plans on this problem's own mesh (not the
        process's full device set).  Packs, posmaps and derived problems
        are rebuilt lazily on first use, exactly as for a fresh
        problem."""
        if devices is None:
            devices = list(np.asarray(self.grid.mesh.devices).reshape(-1))
        return make_problem(self.rows, self.cols, self.vals,
                            (self.m, self.n), self.r, algorithm=algorithm,
                            c=c, devices=devices, row_tile=self.row_tile,
                            nz_block=self.nz_block, comm=self.comm,
                            compress=self.compress)

    def coo_digest(self) -> str:
        """Content digest of the host COO (structure + values) — ties a
        checkpoint's pack metadata to the matrix it was planned for."""
        h = hashlib.blake2b(digest_size=16)
        h.update(np.ascontiguousarray(self.rows.astype(np.int64)))
        h.update(np.ascontiguousarray(self.cols.astype(np.int64)))
        h.update(np.ascontiguousarray(self.vals.astype(np.float32)))
        h.update(np.int64([self.m, self.n, self.r]).tobytes())
        return h.hexdigest()

    def meta_dict(self) -> dict:
        """JSON-able Session/pack metadata for distributed checkpoints:
        enough to rebuild an equivalent problem (same mesh -> identical
        family/c/packs; degraded mesh -> cost-model re-dispatch) via
        :func:`problem_from_meta`."""
        return dict(family=self.alg.name, p=self.p, c=self.c, m=self.m,
                    n=self.n, r=self.r, nnz=self.nnz,
                    row_tile=self.row_tile, nz_block=self.nz_block,
                    comm=self.comm, compress=self.compress,
                    coo_digest=self.coo_digest())

    # -- elision resolution --------------------------------------------------
    def resolve_elision(self, elision: str = "auto",
                        session: Optional["Session"] = None) -> str:
        """Resolve ``elision="auto"``: rank this family's candidate
        strategies by their Table-III words at the problem's (p, c, phi).

        Without a Session the per-call :func:`costmodel.words_fusedmm`
        ranks the cells; with one, the *steady-state*
        :func:`costmodel.words_fusedmm_cached` does — it credits each
        cell the share of its replication term the Session elides (the
        stationary operand's all-gather, paid once per cache fill
        instead of once per call).  This is why a Session can flip the
        choice: d15's "reuse" drops to its shift words alone and
        overtakes "fused" at large c, while on s15 "fused" keeps its
        4*phi/c-vs-6*phi/c shift advantage and wins either way.  An
        explicit elision is validated against the registry entry and
        returned unchanged.
        """
        if elision != "auto":
            if elision not in self.alg.elisions:
                raise ValueError(f"{self.alg.name} supports "
                                 f"{self.alg.elisions}, got {elision!r}")
            return elision
        cost_fn = (costmodel.words_fusedmm_cached if session is not None
                   else costmodel.words_fusedmm)

        def words(el):
            cost = cost_fn(
                _COST_NAME[(self.alg.name, el)], p=self.p, c=self.c,
                n=self.n, r=self.r, nnz=self.nnz)
            return cost.words

        return min(self.alg.auto_elisions, key=words)

    # -- the shared-signature executors --------------------------------------
    def sddmm(self, X, Y, session: Optional["Session"] = None
              ) -> SparseResult:
        """R = S * (X @ Y.T) sampled at nnz(S); X (m, r), Y (n, r).

        ``session`` serves the dense operands' fiber replication from
        the across-call cache (bitwise-identical; d15/d25 gather X,
        s15 gathers both, s25 nothing)."""
        faults.guard("sddmm", self)
        tr = _tracer_active()
        with _span("api.sddmm", family=self.alg.name, elision="none"):
            if tr is None:
                return self.alg.sddmm(self, X, Y, session=session)
            with tr.round(self, "sddmm", session=session):
                return self.alg.sddmm(self, X, Y, session=session)

    def spmm(self, Y, vals=None,
             session: Optional["Session"] = None) -> np.ndarray:
        """out = S(vals) @ Y, host-assembled (m, r); Y is (n, r).

        ``vals`` (host COO order, None -> own values) substitutes the
        sample values through the cached structure pack — O(nnz) value
        injection, no re-planning (:meth:`injected_plan`).  ``session``
        serves s15's column-slab gather of Y; the other families' SpMM
        replicates nothing inbound."""
        faults.guard("spmm", self)
        tr = _tracer_active()
        with _span("api.spmm", family=self.alg.name, elision="none"):
            if tr is None:
                return self.alg.spmm(self, Y, vals=vals, session=session)
            with tr.round(self, "spmm", session=session):
                return self.alg.spmm(self, Y, vals=vals, session=session)

    def spmm_t(self, A, vals=None, session: Optional["Session"] = None
               ) -> np.ndarray:
        """out = S(vals)^T @ A, host-assembled (n, r); A is (m, r).

        ``vals`` (this problem's host-COO order, None -> own values)
        overrides the sample values — the backward of a training step
        runs this with the forward's sampled intermediate as the sparse
        operand (repro.core.grads).  ``session`` replays a cached fiber
        replication of A where the family gathers one (d15/d25/s15)."""
        faults.guard("spmm_t", self)
        tr = _tracer_active()
        with _span("api.spmm_t", family=self.alg.name, elision="none"):
            if vals is not None:
                vals = np.asarray(vals, np.float32)
            A = np.asarray(A, np.float32)
            if tr is None:
                return self.alg.spmm_t(self, A, vals=vals, session=session)
            with tr.round(self, "spmm_t", session=session):
                return self.alg.spmm_t(self, A, vals=vals, session=session)

    def fusedmm(self, X, Y, elision: str = "auto",
                session: Optional["Session"] = None):
        """out = (S * (X @ Y.T)) @ Y, host-assembled (m, r).

        Returns (out, SparseResult of the intermediate R).  ``elision``
        must be one of this family's registry-declared cells (or
        "auto"); see the module-level :func:`fusedmm` for the full
        matrix and docs/algorithms.md for the per-cell word counts."""
        el = self.resolve_elision(elision, session)
        faults.guard("fusedmm", self, elision=el)
        tr = _tracer_active()
        with _span("api.fusedmm", family=self.alg.name, elision=el):
            if tr is None:
                return self.alg.fusedmm(self, X, Y, el, session)
            with tr.round(self, "fusedmm", elision=el, session=session):
                return self.alg.fusedmm(self, X, Y, el, session)

    def lower_fusedmm(self, elision: str = "auto",
                      session: Optional["Session"] = None):
        return self.alg.lower_fusedmm(self, self.resolve_elision(elision),
                                      session=session)

    def lower_spmm_t(self, session: Optional["Session"] = None):
        """Lower the dual SpMM-transpose program (the VJP's Ybar kernel);
        with a ``session``, the pre-gathered (replay) variant."""
        return self.alg.lower_spmm_t(self, session=session)

    def lower_sddmm(self, session: Optional["Session"] = None):
        """Lower the jitted SDDMM program (wire-word measurement)."""
        return self.alg.lower_sddmm(self, session=session)

    def lower_spmm(self, session: Optional["Session"] = None):
        """Lower the jitted SpMM program (wire-word measurement)."""
        return self.alg.lower_spmm(self, session=session)

    def schedule_words(self, op: str, elision: str = "auto",
                       session: Optional["Session"] = None):
        """Modeled per-device wire words for each of :func:`schedule_events`'
        (point, phase) boundaries of one ``op`` round — the live
        cost-model side of ``repro.obs`` span drift.  None for
        support-pruned wire formats (data-dependent volume)."""
        el = (self.resolve_elision(elision, session)
              if op == "fusedmm" else "none")
        return self.alg.schedule_words(self, op, el, session=session)


# ---------------------------------------------------------------------------
# Session: across-call replication reuse
# ---------------------------------------------------------------------------

_FP_CHUNK = 4 << 20        # bytes one pool worker hashes and sums at a time
_FP_SERIAL = 8 << 20       # operands smaller than this stay on the caller
_fp_pool = None
_fp_pool_lock = threading.Lock()


def _fp_workers() -> concurrent.futures.ThreadPoolExecutor:
    """The shared thread pool of the chunked fingerprints, made on first
    use.  hashlib and numpy's reductions release the GIL on large
    buffers, so the chunks run on as many cores as there are workers."""
    global _fp_pool
    with _fp_pool_lock:
        if _fp_pool is None:
            _fp_pool = concurrent.futures.ThreadPoolExecutor(
                min(8, os.cpu_count() or 1),
                thread_name_prefix="session-fingerprint")
        return _fp_pool


def _chunk_fp(chunk: np.ndarray, digest: bool, total: bool):
    """(16-byte blake2b, float64 sum) of one contiguous flat chunk; each
    part None where not wanted."""
    return (hashlib.blake2b(chunk.view(np.uint8), digest_size=16).digest()
            if digest else None,
            float(chunk.sum(dtype=np.float64)) if total else None)


def _fingerprints(arr, *, digest: bool = True, total: bool = True):
    """``(digest, total)`` of an operand's contents: a 128-bit blake2b hex
    digest over every byte in C order, and the float64 sum the Session's
    memo re-checks a numpy operand by; each None where not wanted.

    Both are read straight from the operand's buffer (a non-contiguous
    operand is copied once, contiguous).  Below ``_FP_SERIAL`` bytes one
    pass on the caller's thread; from there, fixed ``_FP_CHUNK``-byte
    chunks on the shared pool, the digest a blake2b over the shape, the
    dtype and the chunks' digests in order, the sum the chunks' sums
    added in order.  Both depend on the contents alone, never on the
    number of workers.  The digest runs in an ``api.digest`` span."""
    a = np.ascontiguousarray(np.asarray(arr))
    flat = a.reshape(-1)
    if a.nbytes < _FP_SERIAL:
        chunks = [flat]
    else:
        per = _FP_CHUNK // a.itemsize
        chunks = [flat[i:i + per] for i in range(0, flat.size, per)]
    span = (_span("api.digest", bytes=a.nbytes, chunks=len(chunks))
            if digest else contextlib.nullcontext())
    with span:
        if len(chunks) == 1:
            d, s = _chunk_fp(chunks[0], digest, total)
            return (d.hex() if digest else None), s
        parts = list(_fp_workers().map(
            functools.partial(_chunk_fp, digest=digest, total=total),
            chunks))
    hexdigest = None
    if digest:
        h = hashlib.blake2b(repr((a.shape, str(a.dtype))).encode(),
                            digest_size=16)
        for d, _ in parts:
            h.update(d)
        hexdigest = h.hexdigest()
    return hexdigest, (sum(s for _, s in parts) if total else None)


class Session:
    """Caches fiber-replicated dense operands across executor calls.

    Keyed by operand CONTENT (grid, family, slot, shape, dtype, byte
    digest), so the stationary factor of an iterative solver hits the
    cache on every iteration while the iterate itself misses and is
    replicated fresh — never stale, and in-place mutation of a cached
    numpy operand (``B *= 0.9``) re-replicates automatically.  Content
    keying is what lets a training step's BACKWARD replay the gathers its
    forward performed: the cotangent path hands the executors *new array
    objects* carrying the same stationary operand values (they round-trip
    through jax tracing in ``repro.core.grads``), and identity-based
    keying would miss every one of them.  Cached and uncached calls are
    bitwise-identical (the kernels consume the same values either way).

    The cache is LRU-bounded: families that gather *both* operands (s15)
    replicate the changing iterate through the session too, and without
    eviction every iterate's device copy would stay pinned for the
    session's lifetime.  The stationary operand is hit on every call and
    therefore never ages out.

    The digest is a 128-bit blake2b over the operand's own buffer, with
    no copy of a contiguous operand; from 8 MiB it is a hash tree of
    blake2b over fixed 4 MiB chunks, hashed on a small shared thread
    pool (``_fingerprints``).  The memo's float64 sum is taken the same
    way, in the same pass where both are needed."""

    def __init__(self, max_entries: int = 16):
        self._cache = collections.OrderedDict()
        self._id_memo = collections.OrderedDict()
        self._max_entries = max_entries
        self.hits = 0
        self.misses = 0

    @staticmethod
    def _key(problem: "DistProblem", arr, slot: str, total: bool = False):
        """(content key, the operand's float64 sum if ``total``)."""
        # comm mode is part of the key: replication state cached for a
        # dense-wire problem is never served to a sparse-wire one (the
        # pre-gathered layouts coincide today, but the key must not bake
        # that implementation detail in)
        a = np.asarray(arr)
        digest, s = _fingerprints(a, total=total)
        return (id(problem.grid), problem.alg.name, problem.comm, slot,
                a.shape, str(a.dtype), digest), s

    @staticmethod
    def _cheap_fp(arr, total: Optional[float] = None):
        # mutation check for numpy operands on the id fast path; jax
        # arrays are immutable, so identity alone is sound for them.
        # ``total`` is the operand's sum where the digest's pass took it
        if isinstance(arr, np.ndarray):
            if total is None:
                total = _fingerprints(arr, digest=False)[1]
            return (arr.shape, str(arr.dtype), total)
        return None

    def _content_key(self, problem: "DistProblem", arr, slot: str):
        """Content key with an identity fast path: the iterating caller
        (ALS's CG loop) passes the SAME host array object every call,
        so the full digest — a device sync for jax operands — is paid
        once, not per hit; the memo verifies numpy operands by a cheap
        sum fingerprint so in-place mutation still re-keys.  An operand
        the memo does not hold gets its digest and sum in one pass.
        The memo holds only WEAK references (no operand pinning) and
        evicts LRU per entry; an id is validated by dereferencing the
        weakref, so id recycling after gc cannot alias a dead entry."""
        with _span("api.session_key") as span:
            memo_k = (id(problem.grid), problem.alg.name, problem.comm,
                      slot, id(arr))
            memo = self._id_memo.get(memo_k)
            live = memo is not None and memo[0]() is arr
            fp = self._cheap_fp(arr) if live else None
            hit = live and memo[2] == fp
            span.set_metadata(hit=hit)
            if hit:
                self._id_memo.move_to_end(memo_k)
                return memo[1]
            numpy_op = not live and isinstance(arr, np.ndarray)
            key, total = self._key(problem, arr, slot, total=numpy_op)
            if not live:
                fp = self._cheap_fp(arr, total)
            try:
                ref = weakref.ref(arr)
            except TypeError:
                return key                 # un-weakref-able: no memo
            self._id_memo[memo_k] = (ref, key, fp)
            while len(self._id_memo) > 4 * self._max_entries:
                self._id_memo.popitem(last=False)
            return key

    def replicate(self, problem: "DistProblem", arr, slot: str):
        key = self._content_key(problem, arr, slot)
        hit = self._cache.get(key)
        if hit is not None:
            self._cache.move_to_end(key)
            self.hits += 1
            return hit
        rep = problem.alg.replicate(problem, arr, slot)
        self._cache[key] = rep
        self.misses += 1
        while len(self._cache) > self._max_entries:
            self._cache.popitem(last=False)
        return rep

    def invalidate(self, problem: "DistProblem") -> int:
        """Drop every cached replication bound to ``problem``'s grid.

        The recovery path after an executor fault: a failed collective
        leaves no trustworthy device state, and after a re-mesh the old
        grid's entries could never be consumed again anyway (keys lead
        with the grid identity).  Returns the number of evicted entries.
        """
        gid = id(problem.grid)
        doomed = [k for k in self._cache if k[0] == gid]
        for k in doomed:
            del self._cache[k]
        for k in [k for k in self._id_memo if k[0] == gid]:
            del self._id_memo[k]
        return len(doomed)

    def stats(self) -> dict:
        """Cache-health counters: ``hits``/``misses`` since construction
        plus current LRU ``entries`` and the ``capacity`` bound — what
        ``bench_dist`` surfaces per training-step row so a mis-keyed
        session (0 hits) is visible in the benchmark artifact."""
        return dict(hits=self.hits, misses=self.misses,
                    entries=len(self._cache),
                    capacity=self._max_entries)

    def clear(self):
        self._cache.clear()
        self._id_memo.clear()

    def __len__(self):
        return len(self._cache)


# ---------------------------------------------------------------------------
# Construction + module-level conveniences
# ---------------------------------------------------------------------------

def make_problem(rows, cols, vals, shape: Tuple[int, int], r: int, *,
                 algorithm: str = "auto", c: int | None = None,
                 devices=None, row_tile: int = 32,
                 nz_block: int = 32, comm: str = "dense",
                 compress: Optional[str] = None) -> DistProblem:
    """Build a DistProblem, dispatching the algorithm by the cost model.

    algorithm="auto" ranks every feasible (family, elision, c) by the
    paper's Table-III bandwidth formulas; a family name pins the family
    and picks its best feasible c (or the caller's explicit ``c``).

    ``comm`` selects the wire format for the dense-operand movements:
    "dense" (the Table-III baseline), "sparse" (support-pruned sends,
    bitwise-identical results), or "auto" — prune when S's row/column
    support density clears :data:`costmodel.SPARSE_CROSSOVER`
    (:func:`costmodel.choose_comm`; docs/choosing.md).  ``compress``
    ("bf16" or None) additionally halves the pruned payloads with
    error-feedback handled by the training loop (lossy — NOT
    bitwise-identical; comm="sparse" alone is exact).
    """
    m, n = shape
    if comm not in ("auto", "dense", "sparse"):
        raise ValueError(f"comm must be 'auto'|'dense'|'sparse', "
                         f"got {comm!r}")
    if compress not in (None, "bf16"):
        raise ValueError(f"compress must be None or 'bf16', "
                         f"got {compress!r}")
    if comm == "auto":
        comm = costmodel.choose_comm(rows, cols, m, n)
    devices = list(devices) if devices is not None else list(jax.devices())
    p = len(devices)
    families = costmodel.FAMILIES if algorithm == "auto" else (algorithm,)
    if algorithm != "auto" and algorithm not in ALGORITHMS:
        raise ValueError(f"unknown algorithm {algorithm!r}; registered: "
                         f"{sorted(ALGORITHMS)}")
    choice = costmodel.choose_algorithm(m=m, n=n, nnz=len(vals), r=r, p=p,
                                        c=c, families=families)
    alg = ALGORITHMS[choice.family]
    grid = alg.make_grid(choice.c, devices)
    return DistProblem(alg, grid, np.asarray(rows), np.asarray(cols),
                       np.asarray(vals, np.float32), m, n, r,
                       row_tile=row_tile, nz_block=nz_block,
                       comm=comm, compress=compress)


def sddmm(problem: DistProblem, X, Y,
          session: Optional[Session] = None) -> SparseResult:
    """Distributed SDDMM: ``R = S * (X @ Y.T)`` sampled at nnz(S).

    Shapes: ``X (m, r)``, ``Y (n, r)`` host arrays (any dtype castable
    to float32); returns a :class:`SparseResult` holding the sampled
    values in the family's home device layout, with ``values()`` /
    ``to_coo()`` / ``to_dense()`` host views.  Every family honors the
    same signature.  ``session`` serves the operands' fiber replication
    from the across-call cache, bitwise-identically — a training step's
    backward then replays the forward's gathers (repro.core.grads).
    """
    return problem.sddmm(X, Y, session=session)


def spmm(problem: DistProblem, Y, vals=None,
         session: Optional[Session] = None) -> np.ndarray:
    """Distributed SpMM: ``out = S(vals) @ Y``, host-assembled ``(m, r)``.

    ``Y`` is ``(n, r)``; the result is a numpy float32 array regardless
    of the family's on-device layout (slab-stacked for s15, skewed
    chunks for s25, ... — assembly is the registry entry's job).
    ``vals`` (host COO order) substitutes the sample values via O(nnz)
    injection into the cached structure pack; ``session`` serves s15's
    gather of Y (the other families' SpMM replicates nothing inbound).
    """
    return problem.spmm(Y, vals=vals, session=session)


def spmm_t(problem: DistProblem, A, vals=None,
           session: Optional[Session] = None) -> np.ndarray:
    """Distributed SpMM-transpose: ``out = S(vals)^T @ A``, ``(n, r)``.

    The dual of :func:`spmm` on the same grid — d15/d25 run their native
    FusedMMB-style executor on the transpose pack (AG of ``A``
    Session-replayable), s15/s25 run spmm on the transposed problem.
    ``vals`` overrides the sample values in the problem's host COO
    order; this is how every backward pass applies a cotangent-valued
    sparse matrix without re-building a DistProblem by hand
    (:mod:`repro.core.grads`).
    """
    return problem.spmm_t(A, vals=vals, session=session)


def spmm_batched(problem: DistProblem, Ys, vals=None,
                 session: Optional[Session] = None,
                 pad_to: int | None = None) -> List[np.ndarray]:
    """One SpMM round over many right-hand sides — the serving batcher's
    aggregation primitive.  See :meth:`DistProblem.spmm_batched`."""
    return problem.spmm_batched(Ys, vals=vals, session=session,
                                pad_to=pad_to)


def fusedmm(problem: DistProblem, X, Y, elision: str = "auto",
            session: Optional[Session] = None):
    """Distributed FusedMM with *FusedMMA semantics* on every family:

        ``out = (S * (X @ Y.T)) @ Y``

    ``X (m, r)``, ``Y (n, r)`` -> ``(out (m, r) numpy, SparseResult R)``
    where ``R`` is the sampled intermediate.  Families whose
    replication-reuse executor is the FusedMMB form (d15/d25) run it on
    the transpose pack with swapped operands transparently.

    ``elision`` selects the communication-eliding strategy; each family
    honors exactly the cells its registry entry declares
    (docs/algorithms.md matrix):

    =======  ==============================  =========================
    family   elisions                        notes
    =======  ==============================  =========================
    d15      none, reuse, fused              fused = true local fusion
    s15      none, reuse, fused              fused = one-structure-pass
    d25      none, reuse, fused              fused = one-structure-pass
    s25      none, reuse                     fused structurally
                                             impossible
    =======  ==============================  =========================

    ``elision="auto"`` ranks the declared cells by the Table-III word
    counts at the problem's (p, c, phi) — steady-state (cached) counts
    when a ``session`` is passed (docs/choosing.md).  An undeclared
    elision raises ``ValueError``.  ``session`` caches the stationary
    operand's fiber replication across calls, bitwise-identically.
    """
    return problem.fusedmm(X, Y, elision=elision, session=session)


# ---------------------------------------------------------------------------
# Elastic recovery: typed retry, backoff, degrade-and-re-plan
# ---------------------------------------------------------------------------

class FaultRecoveryError(RuntimeError):
    """Recovery budget exhausted: carries the per-attempt fault history
    so post-mortems see every coordinate that fired."""

    def __init__(self, msg: str, history: Optional[list] = None):
        super().__init__(msg)
        self.history = history or []


@dataclasses.dataclass
class RetryPolicy:
    """Typed retry/backoff policy for the elastic executors.

    Exponential backoff with *deterministic, seedable* jitter: the delay
    sequence is a pure function of ``seed``, so a recovery trace replays
    exactly (and tests inject ``sleep`` to run instantly).  The first
    retry fires after ~``base_delay``; each subsequent delay multiplies
    by ``factor`` and is capped at ``max_delay``; jitter stretches each
    delay by up to ``jitter`` fractionally (decorrelates retry storms
    across ranks without sacrificing replayability — seed by rank)."""
    max_retries: int = 3
    base_delay: float = 0.0          # seconds; 0 disables sleeping
    factor: float = 2.0
    max_delay: float = 2.0
    jitter: float = 0.25
    seed: int = 0
    sleep: Callable[[float], None] = time.sleep

    def delays(self):
        """The policy's full backoff schedule (len == max_retries)."""
        rng = np.random.default_rng(self.seed)
        d = self.base_delay
        for _ in range(self.max_retries):
            yield min(d, self.max_delay) * (1.0 + self.jitter
                                            * float(rng.uniform()))
            d = d * self.factor if d else 0.0


def problem_from_meta(meta: dict, rows, cols, vals, *,
                      devices=None) -> DistProblem:
    """Rebuild a checkpointed problem from its :meth:`DistProblem.meta_dict`.

    The host COO is supplied by the caller (checkpoints store metadata,
    not the matrix) and verified against the saved content digest — a
    mismatched matrix raises ``ValueError`` rather than silently
    producing wrong packs.  On a mesh with the checkpoint's device count
    the saved (family, c) is pinned, so the rebuilt packs are identical;
    on a different (degraded) mesh the cost model re-dispatches
    ``algorithm="auto"``."""
    devices = list(devices) if devices is not None else list(jax.devices())
    prob = make_problem(rows, cols, vals, (meta["m"], meta["n"]),
                        meta["r"],
                        algorithm=(meta["family"]
                                   if len(devices) == meta["p"] else "auto"),
                        c=meta["c"] if len(devices) == meta["p"] else None,
                        devices=devices, row_tile=meta["row_tile"],
                        nz_block=meta["nz_block"],
                        comm=meta.get("comm", "dense"),
                        compress=meta.get("compress"))
    digest = prob.coo_digest()
    if digest != meta["coo_digest"]:
        raise ValueError(
            f"checkpointed problem metadata does not match the supplied "
            f"COO (digest {digest} != saved {meta['coo_digest']}) — "
            f"wrong matrix for this checkpoint")
    return prob


def degrade(problem: DistProblem, lost_rank: Optional[int] = None, *,
            devices=None, algorithm: str = "auto") -> DistProblem:
    """Re-plan ``problem`` onto a degraded mesh after device loss.

    Drops ``lost_rank`` (flat schedule-order index) from the problem's
    device list — or takes an explicit surviving ``devices`` — then
    picks the **largest device count the cost model can dispatch**: the
    planners' divisibility constraints rarely admit p-1 (64x64 blocks
    don't split 7 ways), so the mesh shrinks to the nearest feasible
    size, exactly like a pod losing a slice.  Raises ``ValueError`` with
    the constraint trail if no device count <= the survivors works."""
    if devices is None:
        devs = list(np.asarray(problem.grid.mesh.devices).reshape(-1))
        if lost_rank is not None:
            if not 0 <= lost_rank < len(devs):
                raise ValueError(f"lost_rank {lost_rank} outside the "
                                 f"mesh's {len(devs)} devices")
            devs = devs[:lost_rank] + devs[lost_rank + 1:]
    else:
        devs = list(devices)
    errors = []
    for p_new in range(len(devs), 0, -1):
        try:
            return problem.replan(devices=devs[:p_new],
                                  algorithm=algorithm)
        except ValueError as e:
            errors.append(f"p={p_new}: {e}")
    raise ValueError("no feasible degraded mesh for "
                     f"({problem.m}x{problem.n}, r={problem.r}) on "
                     f"{len(devs)} surviving devices:\n  "
                     + "\n  ".join(errors))


class ElasticProblem:
    """Fault-tolerant facade over a :class:`DistProblem`.

    Mirrors the four executor entrypoints; every call runs under the
    typed retry loop:

    * :class:`repro.distributed.faults.TransientFault` / a runtime
      device failure (``faults.retryable``) -> invalidate the Session
      entries bound to the problem's grid (a failed collective leaves
      no trustworthy replication state), back off per
      :class:`RetryPolicy`, retry the round on the same mesh;
    * :class:`repro.distributed.faults.DeviceLost` -> additionally drop
      the lost rank and re-plan the problem from host COO onto the
      largest feasible degraded mesh (:func:`degrade` — cost-model
      re-dispatched), then retry there;
    * anything else (caller bugs, compile and out-of-memory failures)
      propagates immediately — retrying it can never succeed.

    Results are host-assembled in problem COO order, so a recovered call
    is **bitwise-identical** to a fault-free one on the same mesh, and
    value-identical after a re-mesh wherever the accumulations are exact
    (docs/robustness.md spells out the guarantee).  ``recoveries``
    records every handled fault; :class:`FaultRecoveryError` (with that
    history) is raised when ``policy.max_retries`` is exhausted.
    """

    def __init__(self, problem: DistProblem,
                 session: Optional[Session] = None,
                 policy: Optional[RetryPolicy] = None):
        self.problem = problem
        self.session = session
        self.policy = policy or RetryPolicy()
        self.recoveries: List[dict] = []

    def _run(self, label: str, fn):
        attempt = 0
        delays = self.policy.delays()
        while True:
            try:
                return fn(self.problem)
            except faults.RETRYABLE as e:
                e = faults.unwrap(e)   # typed fault may be XLA-laundered
                if not faults.retryable(e):
                    raise              # compile / memory failure: no retry
                attempt += 1
                rec = dict(op=label, attempt=attempt, error=repr(e),
                           family=self.problem.alg.name,
                           p=self.problem.p,
                           coord=getattr(e, "coord", None))
                self.recoveries.append(rec)
                reg = _metrics_active()
                if reg is not None:
                    reg.inc("elastic.faults", 1, op=label,
                            kind=type(e).__name__)
                    reg.inc("elastic.retries", 1, op=label)
                if self.session is not None:
                    rec["evicted"] = self.session.invalidate(self.problem)
                if attempt > self.policy.max_retries:
                    if reg is not None:
                        reg.inc("elastic.exhausted", 1, op=label)
                    raise FaultRecoveryError(
                        f"{label} failed after {attempt} attempts "
                        f"(budget {self.policy.max_retries}): {e}",
                        history=list(self.recoveries)) from e
                if isinstance(e, faults.DeviceLost):
                    self.problem = degrade(self.problem, e.rank)
                    rec["remeshed_to_p"] = self.problem.p
                    rec["family_after"] = self.problem.alg.name
                    if reg is not None:
                        reg.inc("elastic.degrades", 1, op=label)
                        reg.gauge("elastic.p", self.problem.p)
                delay = next(delays, self.policy.max_delay)
                if delay:
                    self.policy.sleep(delay)

    # -- the shared-signature executors, resiliently -------------------------
    def sddmm(self, X, Y) -> SparseResult:
        return self._run("sddmm",
                         lambda p: p.sddmm(X, Y, session=self.session))

    def spmm(self, Y, vals=None) -> np.ndarray:
        return self._run("spmm", lambda p: p.spmm(Y, vals=vals,
                                                  session=self.session))

    def spmm_t(self, A, vals=None) -> np.ndarray:
        return self._run("spmm_t",
                         lambda p: p.spmm_t(A, vals=vals,
                                            session=self.session))

    def fusedmm(self, X, Y, elision: str = "auto"):
        return self._run("fusedmm",
                         lambda p: p.fusedmm(X, Y, elision=elision,
                                             session=self.session))

    def spmm_batched(self, Ys, vals=None, pad_to: int | None = None):
        return self._run(
            "spmm_batched",
            lambda p: p.spmm_batched(Ys, vals=vals, session=self.session,
                                     pad_to=pad_to))

    # -- derived-problem rounds, resiliently ---------------------------------
    def run_round(self, label: str, fn):
        """Run one serving round under the typed retry loop.

        ``fn(problem)`` receives the CURRENT deployment problem — after a
        ``DeviceLost`` the facade degrades ``self.problem`` onto the
        surviving mesh and calls ``fn`` again with the re-planned
        problem, so ``fn`` must derive any per-round state (a
        :meth:`DistProblem.with_pattern` union problem, a width-derived
        batch problem) from its argument rather than close over a
        pre-fault derivation.  This is the hook the serving engine's
        score ticks use: the union-of-patterns problem is rebuilt on the
        degraded grid each retry, keeping answers bitwise-correct across
        the re-mesh (tests/dist_scripts/check_serving.py)."""
        return self._run(label, fn)


# ---------------------------------------------------------------------------
# Local-kernel routing (repro.kernels.ops)
# ---------------------------------------------------------------------------

class _Router:
    """Routes ops.sddmm/spmm/fusedmm calls on a bound RowTiledCOO pack to
    the active DistProblem.  Only exact pack identity routes; traced
    arguments and mismatched shapes fall through to the local kernels."""

    def __init__(self, problem: DistProblem, pack):
        self.problem, self.pack = problem, pack

    def _traced(self, *arrs) -> bool:
        return any(isinstance(a, jax.core.Tracer) for a in arrs)

    def _sample(self, result: SparseResult):
        """Re-inject a distributed result into the bound pack's slots —
        O(nnz log nnz) coordinate matching, no dense materialization."""
        S = self.pack
        prob = self.problem
        vals_prob = result.values()            # problem COO order
        key = (np.asarray(S.rows_global()).reshape(-1).astype(np.int64)
               * prob.n + np.asarray(S.cols).reshape(-1))
        sk, order = prob.coo_sort()
        idx, ok = _match_coo(sk, order, key)
        out = np.zeros(key.shape[0], np.float32)
        out[ok] = vals_prob[idx[ok]]
        # padding entries point at (tile_base, 0), which may collide with
        # a real nonzero — mask them back to zero
        vals_pack = np.asarray(S.vals)
        out = np.where(vals_pack.reshape(-1) != 0, out, 0.0)
        return S.with_vals(jnp.asarray(out.reshape(vals_pack.shape)))

    def sddmm(self, A, B, S):
        if S is not self.pack or self._traced(A, B, S.vals):
            return NotImplemented
        return self._sample(self.problem.sddmm(np.asarray(A),
                                               np.asarray(B)))

    def spmm(self, S, B, m):
        if S is not self.pack or self._traced(B, S.vals) \
                or m != self.problem.m:
            return NotImplemented
        return jnp.asarray(self.problem.spmm(np.asarray(B)))

    def fusedmm(self, A, B, S, m):
        if S is not self.pack or self._traced(A, B, S.vals) \
                or m != self.problem.m:
            return NotImplemented
        out, r = self.problem.fusedmm(np.asarray(A), np.asarray(B))
        return jnp.asarray(out), self._sample(r)


@contextlib.contextmanager
def activate(problem: DistProblem, local_pack):
    """Route ``repro.kernels.ops`` calls on ``local_pack`` through the
    distributed problem while the context is live (mesh-active mode).

    Calls must be eager (outside jit) to route; traced calls fall through
    to the local Pallas/ref kernels unchanged."""
    from repro.kernels import ops
    prev = ops._DIST_ROUTER
    ops._DIST_ROUTER = _Router(problem, local_pack)
    try:
        yield
    finally:
        ops._DIST_ROUTER = prev
