"""Back-to-back FusedMM rounds through the public api, as a graph
embedding or attention loop makes them.

One round is one ``DistProblem.fusedmm(X, Y, elision=...)`` call, host
operands in and the host ``(m, r)`` output back.  Before each round the
host makes that round's operands, ``X_k = a_k X_0`` and ``Y_k = b_k Y_0``
with scalars drawn from the seed, under the span ``bench.update``: every
round sees new content, so no cache keyed by content can turn rounds
into repeats, and a round's operands can be made again for the check.

Traffic parameters (``traffic/<name>.json``):

    elision       the api's ``elision`` argument
    scale_range   [lo, hi): the range of a_k and b_k
    check_rounds  how many completed rounds, drawn from the seed, the
                  check compares with the reference
"""
from __future__ import annotations

import numpy as np

from bench import reference, work
from bench.loops import common


class Loop:
    UNIT = "rounds"

    def __init__(self, config: dict, traffic: dict, devices, seed: int):
        self.config, self.traffic = config, traffic
        self.devices, self.seed = devices, seed
        self.rows, self.cols, self.vals, self.m, self.n = common.graph(config)
        self.r = config["r"]
        rng = common.rng(seed, "operands")
        self.X0 = common.uniform(rng, (self.m, self.r))
        self.Y0 = common.uniform(rng, (self.n, self.r))
        self._scales = common.rng(seed, "scales")
        self.scales = []              # (a_k, b_k) of every round made
        self._bufs = [(np.empty_like(self.X0), np.empty_like(self.Y0))
                      for _ in range(2)]
        self.sample = common.Reservoir(seed, traffic["check_rounds"])
        self.results = {}             # round -> (out, SparseResult), kept
        self.prob = None

    # -- set-up --------------------------------------------------------------
    def plan(self):
        self.prob = common.make_problem(self.config, self.rows, self.cols,
                                        self.vals, self.m, self.n,
                                        self.devices)

    def warm(self):
        """A round from each operand buffer: the first write to a buffer
        faults in its pages, which would otherwise cost each of the
        window's first two rounds about half a round."""
        for X, Y in self._bufs:
            np.copyto(X, self.X0)
            np.copyto(Y, self.Y0)
            self.prob.fusedmm(X, Y, elision=self.traffic["elision"])

    def describe(self) -> dict:
        return dict(common.describe(self.prob),
                    elision=self.prob.resolve_elision(
                        self.traffic["elision"]),
                    nnz=self.prob.nnz)

    # -- the window ----------------------------------------------------------
    def operands(self, k: int):
        """Round k's operands, into one of two buffers that take turns."""
        a, b = self.scales[k]
        X, Y = self._bufs[k % 2]
        np.multiply(self.X0, np.float32(a), out=X)
        np.multiply(self.Y0, np.float32(b), out=Y)
        return X, Y

    def step(self) -> dict:
        import jax
        k = len(self.scales)
        self.scales.append(tuple(
            self._scales.uniform(*self.traffic["scale_range"], 2)))
        keep, drop = self.sample.offer()
        self.results.pop(drop, None)
        with jax.profiler.TraceAnnotation("bench.update"):
            X, Y = self.operands(k)
        with jax.profiler.TraceAnnotation("bench.fusedmm"):
            result = self.prob.fusedmm(X, Y,
                                       elision=self.traffic["elision"])
        if keep:
            self.results[k] = result
        return {"rounds": 1}

    def counters(self) -> dict:
        return {}

    def work(self, counters: dict) -> dict:
        w = work.count("fusedmm", self.rows, self.cols, self.m, self.n,
                       self.r, self.config["dtype"]) * counters["rounds"]
        return {"flops": w.flops, "bytes": w.bytes}

    # -- the check -----------------------------------------------------------
    def finish(self):
        """Assemble the sampled rounds' sampled values (the api's own host
        assembly) and free the program's state."""
        self.got = {k: (out, rv.values())
                    for k, (out, rv) in self.results.items()}
        self.results = self.prob = None

    def _reference(self, high: bool):
        ref = reference.Coo(self.rows, self.cols, self.vals, self.m,
                            device=self.devices[0], high=high)
        outs = {}
        for k in self.got:
            a, b = self.scales[k]
            out, r = ref.fusedmm(self.X0 * np.float32(a),
                                 self.Y0 * np.float32(b))
            outs[k] = (np.asarray(out), np.asarray(r))
        return outs

    def _numbers(self, got: dict) -> dict:
        return {"out_err": max(reference.row_err(got[k][0], self.want[k][0])
                               for k in got),
                "r_err": max(reference.row_err(got[k][1], self.want[k][1])
                             for k in got)}

    def check(self) -> dict:
        """The sampled rounds against the reference: the widest gap of an
        output row, and of a sampled value, each against the reference's
        own size (``reference.row_err``)."""
        self.want = self._reference(high=False)
        return self._numbers(self.got)

    def check_control(self) -> dict:
        """The control in the program's place, after :meth:`check`."""
        return self._numbers(self._reference(high=True))
