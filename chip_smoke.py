"""Bring-up smoke of the main path on a TPU: one chip, or four with --chips 4.

    python chip_smoke.py              # one v5e chip
    python chip_smoke.py --chips 4    # a v5e:2x2 host

One chip drives the user entry points once at real size and checks every
answer against a plain COO reference (XLA gathers and scatter-adds in
nonzero chunks on the device, no Pallas):

* kernels  -- ``api.make_problem`` on the Graph500 R-MAT graph of scale 22
              (A/B/C = 0.57/0.19/0.19, edge factor 16: 4,194,304 vertices,
              ~67M edges), r=128 f32; ``sddmm``, ``spmm`` and ``fusedmm``.
* als      -- one ``dist_als_round`` on a 2^22 x 2^22 ER mask with 16
              nonzeros per row, r=128, cg_iters=3; the loss must fall, and
              one ``dist_fusedmm_matvec`` is checked.
* serving  -- a ``ServingEngine`` deployment of the R-MAT graph answers 8
              score and 2 aggregate requests with no elastic retry.

GAT training is not a phase: its ``jax.pure_callback`` bridge
(``core/grads.py``) deadlocks on a TPU (ROADMAP 2d, 3c).

``--chips 4`` runs only the distributed FusedMM of every registered
family on the R-MAT graph over the four chips (elision "none" and the
family's cheapest cell) against the same reference, and prints each
device's memory.

Every phase prints one JSON line: seconds, compiles, error against the
reference (max |got - ref| / max |ref|), whether the lowered program
holds a Pallas kernel (``tpu_custom_call``), and memory:
``device_peak_bytes`` (per device) and ``host_peak_bytes`` are the
phase's own peaks of ``bytes_in_use`` and resident set size, sampled
every 0.1 s; ``process_peak_bytes`` holds the cumulative peaks of the
whole process so far (``peak_bytes_in_use``, ``ru_maxrss``), which
short spikes between samples cannot hide.  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero before any phase.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import resource
import sys
import threading
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
SCALE = 22            # Graph500 R-MAT scale (and ALS log2 size)
TOL = 1e-4            # error bound relative to the reference's max |value|
PHASE_LIMIT_S = 600   # wall-clock bound per phase: a hang fails the run


def require_tpu(count: int):
    """The first ``count`` TPU devices; exits non-zero on any other
    platform or too few chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU device(s), found "
                 f"{len(devices)} x {devices[0].platform}")
    return devices[:count]


# ---------------------------------------------------------------------------
# measurement and reference
# ---------------------------------------------------------------------------

_COMPILES = [0]
_REF_S = [0.0]        # seconds spent in the reference, per process


def _count_compiles():
    import jax
    from jax._src import dispatch

    def on_event(event, duration, **kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            _COMPILES[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)


def _device_bytes(devices, key="bytes_in_use"):
    return [(d.memory_stats() or {}).get(key) for d in devices]


def _host_rss() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


class Peaks:
    """Peaks of each device's ``bytes_in_use`` and of the host's resident
    set over a ``with`` block, sampled every ``period`` seconds."""

    def __init__(self, devices, period=0.1):
        self.devices, self.period = devices, period
        self.device, self.host = _device_bytes(devices), _host_rss()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _sample(self):
        self.device = [None if b is None else max(p or 0, b) for p, b in
                       zip(self.device, _device_bytes(self.devices))]
        self.host = max(self.host, _host_rss())

    def _run(self):
        while not self._stop.wait(self.period):
            self._sample()

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self._sample()


def measured(name, devices, fn, *args, **kw):
    """Run one phase under the wall-clock bound; returns its record."""
    watchdog = threading.Timer(PHASE_LIMIT_S, lambda: (
        print(f"chip_smoke: phase {name} exceeded {PHASE_LIMIT_S}s",
              file=sys.stderr, flush=True), os._exit(124)))
    gc.collect()                      # the last phase's arrays
    watchdog.start()
    c0, r0, t0 = _COMPILES[0], _REF_S[0], time.perf_counter()
    try:
        with Peaks(devices) as peaks:
            rec = fn(*args, **kw)
    finally:
        watchdog.cancel()
    rec = dict(phase=name, seconds=time.perf_counter() - t0,
               ref_seconds=_REF_S[0] - r0, compiles=_COMPILES[0] - c0,
               device_peak_bytes=peaks.device, host_peak_bytes=peaks.host,
               process_peak_bytes=dict(
                   device=_device_bytes(devices, "peak_bytes_in_use"),
                   host=resource.getrusage(
                       resource.RUSAGE_SELF).ru_maxrss * 1024), **rec)
    print(json.dumps(rec), flush=True)
    return rec


def timed(fn):
    """Wall seconds of the reference, counted apart from the phase's."""
    @functools.wraps(fn)
    def run(*args, **kw):
        t0 = time.perf_counter()
        try:
            return fn(*args, **kw)
        finally:
            _REF_S[0] += time.perf_counter() - t0
    return run


def rel_err(got, want, chunk=1 << 20) -> float:
    """max |got - want| / max |want|, in cache-sized chunks."""
    got = np.asarray(got, np.float32).reshape(-1)
    want = np.asarray(want, np.float32).reshape(-1)
    assert got.shape == want.shape, (got.shape, want.shape)
    diff = ref = 0.0
    for lo in range(0, len(want), chunk):
        w = want[lo:lo + chunk]
        diff = max(diff, float(np.max(np.abs(got[lo:lo + chunk] - w))))
        ref = max(ref, float(np.max(np.abs(w))))
    return diff / max(ref, 1e-30)


@functools.lru_cache(maxsize=None)
def _ref_steps():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def dots(r, c, v, X, Y):
        return v * jnp.sum(X[r] * Y[c], axis=-1)

    @functools.partial(jax.jit, donate_argnums=0)
    def scatter(acc, r, c, v, Y):
        return acc.at[r].add(v[:, None] * Y[c])

    return dots, scatter


def _chunks(rows, cols, vals, chunk):
    nnz = len(rows)
    for lo in range(0, nnz, chunk):
        hi = min(lo + chunk, nnz)
        pad = chunk - (hi - lo)
        yield lo, hi, tuple(np.pad(np.asarray(a[lo:hi]), (0, pad))
                            for a in (rows, cols, vals))


@timed
def ref_sddmm(rows, cols, vals, X, Y, chunk=1 << 20):
    """vals * <X[rows], Y[cols]> per nonzero, chunked on the device."""
    import jax.numpy as jnp
    dots, _ = _ref_steps()
    X, Y = jnp.asarray(X, jnp.float32), jnp.asarray(Y, jnp.float32)
    out = np.empty(len(rows), np.float32)
    for lo, hi, (r, c, v) in _chunks(rows, cols, vals, chunk):
        out[lo:hi] = np.asarray(dots(r, c, v.astype(np.float32), X, Y))[
            :hi - lo]
    return out


@timed
def ref_spmm(rows, cols, vals, Y, m, chunk=1 << 20):
    """out[rows] += vals * Y[cols], chunked scatter-adds on the device."""
    import jax.numpy as jnp
    _, scatter = _ref_steps()
    Y = jnp.asarray(Y, jnp.float32)
    acc = jnp.zeros((m, Y.shape[1]), jnp.float32)
    for _, _, (r, c, v) in _chunks(rows, cols, vals, chunk):
        acc = scatter(acc, r, c, v.astype(np.float32), Y)
    return np.asarray(acc)


def has_kernel(lowered) -> bool:
    return "tpu_custom_call" in lowered.as_text()


# ---------------------------------------------------------------------------
# phases (the test suite runs each at a tiny size on the CPU)
# ---------------------------------------------------------------------------

def rmat_graph(scale: int, seed: int = 0):
    """The Graph500 R-MAT graph (edge factor 16) as host COO."""
    from repro.core import sparse
    rows, cols, vals = sparse.rmat(scale, edge_factor=16, seed=seed)
    return rows, cols, vals, 1 << scale


def dense_pair(m, n, r, seed):
    """Two seeded dense operands, uniform in [-1, 1)."""
    rng = np.random.default_rng(seed)
    out = []
    for rows in (m, n):
        x = rng.random((rows, r), np.float32)
        x *= 2
        x -= 1
        out.append(x)
    return tuple(out)


def plan(graph, r, devices, nz_block=128):
    """The graph's DistProblem with its normal pack planned (host)."""
    from repro.core import api
    rows, cols, vals, n = graph
    t0 = time.perf_counter()
    prob = api.make_problem(rows, cols, vals, (n, n), r, devices=devices,
                            nz_block=nz_block)
    prob.plan("normal")
    return prob, time.perf_counter() - t0


def planned(graph, devices, r=128):
    """:func:`plan`, printing the host planning time as its own line."""
    prob, planning = plan(graph, r, devices)
    print(json.dumps(dict(phase="plan", family=prob.alg.name,
                          host_planning_s=planning)), flush=True)
    return prob


def phase_kernels(prob, seed=1):
    """SDDMM, SpMM and FusedMM through the api against the reference."""
    X, Y = dense_pair(prob.m, prob.n, prob.r, seed)
    rows, cols, vals = prob.rows, prob.cols, prob.vals
    want_r = ref_sddmm(rows, cols, vals, X, Y)
    want_spmm = ref_spmm(rows, cols, vals, Y, prob.m)
    want_f = ref_spmm(rows, cols, want_r, Y, prob.m)
    op_s = {}

    def op(name, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        op_s[name] = time.perf_counter() - t0
        return out

    err = dict(sddmm=rel_err(op("sddmm", prob.sddmm, X, Y).values(),
                             want_r),
               spmm=rel_err(op("spmm", prob.spmm, Y), want_spmm))
    out, rv = op("fusedmm", prob.fusedmm, X, Y, elision="none")
    err.update(fusedmm=rel_err(out, want_f),
               fusedmm_r=rel_err(rv.values(), want_r))
    return dict(family=prob.alg.name, nnz=prob.nnz, op_seconds=op_s,
                max_err=err,
                tpu_custom_call=has_kernel(prob.lower_fusedmm("none")),
                ok=max(err.values()) <= TOL)


def phase_als(log2_n, r, devices, cg_iters=3, seed=0):
    """One distributed ALS round on an ER mask, plus one checked matvec."""
    from repro.apps import als
    n = 1 << log2_n
    t0 = time.perf_counter()
    dp = als.make_dist_problem(n, n, 16, r, devices=devices, seed=seed,
                               nz_block=128)
    planning = time.perf_counter() - t0
    A, B = dense_pair(n, n, r, seed)
    A *= 0.1
    B *= 0.1
    loss0 = als.dist_loss(dp, A, B)
    t0 = time.perf_counter()
    A, B = als.dist_als_round(dp, A, B, cg_iters=cg_iters)
    round_s = time.perf_counter() - t0
    loss1 = als.dist_loss(dp, A, B)
    got = als.dist_fusedmm_matvec(dp.mask, A, B, dp.reg)
    m = dp.mask
    ones = np.ones(m.nnz, np.float32)
    want = ref_spmm(m.rows, m.cols, ref_sddmm(m.rows, m.cols, ones, A, B),
                    B, n) + dp.reg * A
    err = rel_err(got, want)
    return dict(family=m.alg.name, nnz=m.nnz, host_setup_s=planning,
                round_seconds=round_s, loss=[loss0, loss1], max_err=err,
                tpu_custom_call=has_kernel(m.lower_fusedmm("none")),
                ok=bool(np.isfinite(loss1) and loss1 < loss0
                        and err <= TOL))


def phase_serving(graph, r, devices, n_scores=8, pairs=256, seed=2):
    """One deployment of the graph answers score and aggregate tickets."""
    from repro.apps import als
    from repro.serving.pool import SessionPool
    from repro.serving.server import ServingEngine
    rows, cols, vals, n = graph
    U, V = dense_pair(n, n, r, seed)
    pool = SessionPool(capacity=1)
    dep = als.deploy_factors(pool, rows, cols, vals, (n, n), U, V,
                             devices=devices, nz_block=128)
    engine = ServingEngine(pool)
    rng = np.random.default_rng(seed)
    scores = []
    for _ in range(n_scores):
        u, i = rng.integers(0, n, pairs), rng.integers(0, n, pairs)
        scores.append((als.predict_scores(engine, dep, u, i), u, i))
    Ws = [rng.standard_normal((n, r // 2), np.float32) for _ in range(2)]
    aggs = [als.lookup_embeddings(engine, dep, W) for W in Ws]
    engine.run_until_drained()
    err = max(rel_err(t.result(), np.sum(U[u] * V[i], axis=1))
              for t, u, i in scores)
    want = ref_spmm(rows, cols, vals, np.concatenate(Ws, axis=1), n)
    err_agg = max(rel_err(t.result(), want[:, k * (r // 2):
                                               (k + 1) * (r // 2)])
                  for k, t in enumerate(aggs))
    tickets = [t for t, _, _ in scores] + aggs
    done = all(t.done for t in tickets)
    retries = len(dep.elastic.recoveries)
    return dict(family=dep.problem.alg.name, tickets=len(tickets),
                completed=done, elastic_recoveries=retries,
                max_err=dict(score=err, aggregate=err_agg),
                tpu_custom_call=has_kernel(dep.problem.lower_spmm()),
                ok=done and retries == 0 and max(err, err_agg) <= TOL)


def phase_dist_fusedmm(graph, r, devices, seed=1):
    """Distributed FusedMM of every family at p=len(devices): elision
    "none" and the family's cheapest cell, against one reference.  Each
    cell also prints its own line as it completes."""
    from repro.core import api, costmodel
    rows, cols, vals, n = graph
    X, Y = dense_pair(n, n, r, seed)
    want_r = ref_sddmm(rows, cols, vals, X, Y)
    want = ref_spmm(rows, cols, want_r, Y, n)
    p = len(devices)
    recs = []
    for family in sorted(api.ALGORITHMS):
        cs = [c for c in range(1, p + 1) if p % c == 0 and
              costmodel.family_feasible(family, m=n, n=n, r=r, p=p, c=c)]
        if not cs:
            continue
        # the middle feasible c: a grid that both replicates and shifts
        prob = api.make_problem(rows, cols, vals, (n, n), r,
                                algorithm=family, c=cs[(len(cs) - 1) // 2],
                                devices=devices, nz_block=128)
        for elision in sorted({"none", prob.resolve_elision("auto")}):
            t0 = time.perf_counter()
            with Peaks(devices) as peaks:
                out, rv = prob.fusedmm(X, Y, elision=elision)
            seconds = time.perf_counter() - t0     # first call: plans too
            err = max(rel_err(out, want), rel_err(rv.values(), want_r))
            recs.append(dict(family=family, c=prob.c, elision=elision,
                             seconds=seconds, max_err=err, ok=err <= TOL,
                             device_peak_bytes=peaks.device,
                             bytes_in_use=_device_bytes(devices),
                             tpu_custom_call=has_kernel(
                                 prob.lower_fusedmm(elision))))
            print(json.dumps(dict(phase="dist_fusedmm_cell", **recs[-1])),
                  flush=True)
        del prob, out, rv             # rv refers to prob: free both now
    return dict(cells=recs, ok=bool(recs) and all(c["ok"] for c in recs))


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    sys.path.insert(0, SRC)
    from repro.launch import compile_cache
    compile_cache.enable()
    _count_compiles()

    t0 = time.perf_counter()
    graph = rmat_graph(SCALE)
    print(json.dumps(dict(phase="generate",
                          graph=f"rmat{graph[3].bit_length() - 1}",
                          nnz=len(graph[0]),
                          seconds=time.perf_counter() - t0)), flush=True)
    recs = []
    if args.chips == 4:
        recs.append(measured("dist_fusedmm", devices, phase_dist_fusedmm,
                             graph, 128, devices))
    else:
        # one phase's arrays at a time: host (40 GiB) and device memory
        # hold no more than that
        recs.append(measured("kernels", devices, phase_kernels,
                             planned(graph, devices)))
        recs.append(measured("serving", devices, phase_serving, graph, 128,
                             devices))
        del graph                     # ALS draws its own ratings
        recs.append(measured("als", devices, phase_als, SCALE, 128,
                             devices))
    failed = [r["phase"] for r in recs if not r["ok"]]
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
