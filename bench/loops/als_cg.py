"""Distributed CG-ALS half-rounds back to back, as ``dist_als_round``
makes them.

A half-round solves one factor given the other: the right-hand side is
one ``DistProblem.spmm`` of the ratings with the fixed factor, then
``repro.apps.als.dist_cg_solve`` runs ``cg_iters`` CG iterations on the
mask, one distributed FusedMM matvec each (plus the one of the starting
residual), with one ``api.Session`` for the whole window.  Sides
alternate: A given B, then B given A.  The factors start from the seed.

The check compares, for half-rounds drawn from the seed, the right-hand
side and every matvec's FusedMM output with the reference given the same
operands, and the host CG arithmetic of ``dist_cg_solve`` with its
float64 replay from the matvecs the program made (``reference.
cg_replay``): every matvec operand after the first, and the solved
factor.  A reference CG from scratch is not compared with the solved
factor: CG's float32 rounding, amplified by the conditioning of the
normal equations over the steps, makes two sound float32 solvers differ
by about as much as one at the next precision below.

Traffic parameters (``traffic/<name>.json``):

    elision            the api's ``elision`` argument of every matvec
    check_half_rounds  how many completed half-rounds, drawn from the
                       seed, the check compares with the reference
"""
from __future__ import annotations

import numpy as np

from bench import reference, work
from bench.loops import common


class Loop:
    UNIT = "half_rounds"

    def __init__(self, config: dict, traffic: dict, devices, seed: int):
        self.config, self.traffic = config, traffic
        self.devices, self.seed = devices, seed
        self.rows, self.cols, self.vals, self.m, self.n = common.graph(config)
        self.r, self.reg = config["r"], config["reg"]
        self.iters = config["cg_iters"]
        gen = common.rng(seed, "factors")
        self.factors = [common.uniform(gen, (self.m, self.r), 0.1),
                        common.uniform(gen, (self.n, self.r), 0.1)]
        self.halves = 0
        self.sample = common.Reservoir(seed, traffic["check_half_rounds"])
        #: half-round -> (side, fixed, rhs, [(operand, FusedMM out)], x)
        self.kept = {}
        self.dp = self.session = None

    # -- set-up --------------------------------------------------------------
    def plan(self):
        from repro.apps import als
        from repro.core import api
        ratings = common.make_problem(self.config, self.rows, self.cols,
                                      self.vals, self.m, self.n,
                                      self.devices)
        mask = ratings.with_values(np.ones_like(self.vals))
        self.dp = als.DistALSProblem(ratings, ratings.transposed(), mask,
                                     mask.transposed(), self.m, self.n,
                                     self.r, self.reg)
        for p in (self.dp.ratings_t, self.dp.mask, self.dp.mask_t):
            p.plan("normal")
        self.session = api.Session()

    def _half(self, side: int, fixed, session, iters: int, calls=None):
        """One half-round; ``calls``, where given, receives each matvec's
        operand and FusedMM output."""
        import jax
        from repro.apps import als
        ratings, mask = ((self.dp.ratings, self.dp.mask) if side == 0 else
                         (self.dp.ratings_t, self.dp.mask_t))
        with jax.profiler.TraceAnnotation("bench.rhs_spmm"):
            rhs = ratings.spmm(fixed)
        with jax.profiler.TraceAnnotation("bench.cg_solve"):
            x = als.dist_cg_solve(
                mask if calls is None else _Recorder(mask, calls), fixed,
                rhs, self.reg, iters, session, self.traffic["elision"])
        return rhs, x

    def warm(self):
        """Each side's SpMM and matvec programs, with a Session of its own
        so that the window's Session starts empty."""
        from repro.core import api
        warm = api.Session()
        for side in (0, 1):
            self._half(side, self.factors[1 - side], warm, 0)

    def describe(self) -> dict:
        return dict(common.describe(self.dp.mask),
                    elision=self.dp.mask.resolve_elision(
                        self.traffic["elision"], self.session),
                    nnz=self.dp.mask.nnz)

    # -- the window ----------------------------------------------------------
    def step(self) -> dict:
        k, side = self.halves, self.halves % 2
        self.halves += 1
        fixed = self.factors[1 - side]
        keep, drop = self.sample.offer()
        self.kept.pop(drop, None)
        calls = [] if keep else None
        rhs, x = self._half(side, fixed, self.session, self.iters, calls)
        self.factors[side] = x
        if keep:
            self.kept[k] = (side, fixed, rhs, calls, x)
        return {"half_rounds": 1, "matvecs": self.iters + 1, "spmms": 1}

    def counters(self) -> dict:
        return {"session_" + k: v for k, v in self.session.stats().items()}

    def work(self, counters: dict) -> dict:
        total, halves = work.Work(0.0, 0.0), counters["half_rounds"]
        for side, count in ((0, (halves + 1) // 2), (1, halves // 2)):
            rows, cols, m, n = ((self.rows, self.cols, self.m, self.n)
                                if side == 0 else
                                (self.cols, self.rows, self.n, self.m))
            dtype = self.config["dtype"]
            total = total + (
                work.count("spmm", rows, cols, m, n, self.r, dtype)
                + work.count("fusedmm", rows, cols, m, n, self.r, dtype)
                * (self.iters + 1)) * count
        return {"flops": total.flops, "bytes": total.bytes}

    # -- the check -----------------------------------------------------------
    def finish(self):
        self.factors = []
        self.dp = self.session = None

    def _coo(self, side: int, high: bool):
        """The reference's (ratings, mask) of one side."""
        rows, cols, m = ((self.rows, self.cols, self.m) if side == 0
                         else (self.cols, self.rows, self.n))
        return tuple(reference.Coo(rows, cols, v, m, device=self.devices[0],
                                   high=high)
                     for v in (self.vals, np.ones_like(self.vals)))

    def _reference(self, high: bool, solve: bool = False):
        """Each sampled half-round's right-hand side and matvec outputs,
        from the same fixed factor and matvec operands; with ``solve``,
        also the CG replay's gap of the reference's own solve from that
        right-hand side, in the program's place."""
        coo, outs = {}, {}
        for k, (side, fixed, _, calls, _) in self.kept.items():
            if side not in coo:
                coo[side] = self._coo(side, high)
            ratings, mask = coo[side]
            rhs = np.asarray(ratings.spmm(fixed))
            cg = None
            if solve:
                x, made = mask.cg(fixed, rhs, self.reg, self.iters)
                cg = reference.cg_err(rhs, made, x, self.reg)
                del x, made
            outs[k] = (rhs, [np.asarray(mask.fusedmm(P, fixed)[0])
                             for P, _ in calls], cg)
        return outs

    def _numbers(self, got: dict) -> dict:
        want = self.want
        return {"rhs_err": max(reference.row_err(got[k][0], want[k][0])
                               for k in got),
                "matvec_err": max(reference.row_err(g, w) for k in got
                                  for g, w in zip(got[k][1], want[k][1],
                                                  strict=True)),
                "cg_err": max(got[k][2] for k in got)}

    def check(self) -> dict:
        """The sampled half-rounds against the reference: the widest gap
        of a row of the right-hand side, of a matvec's FusedMM output, and
        of the CG's operands and solved factor from their replay."""
        self.want = self._reference(high=False)
        return self._numbers({
            k: (rhs, [out for _, out in calls],
                reference.cg_err(rhs, calls, x, self.reg))
            for k, (_, _, rhs, calls, x) in self.kept.items()})

    def check_control(self) -> dict:
        return self._numbers(self._reference(high=True, solve=True))


class _Recorder:
    """Stands for a DistProblem in ``dist_cg_solve``, which calls only its
    ``fusedmm``: each call goes to the program's own, and its operand and
    output are kept (references only; CG never changes them in place)."""

    def __init__(self, problem, calls: list):
        self.problem, self.calls = problem, calls

    def fusedmm(self, X, Y, **kw):
        out, rv = self.problem.fusedmm(X, Y, **kw)
        self.calls.append((X, out))
        return out, rv
