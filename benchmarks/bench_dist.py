"""Distributed-algorithm benchmarks through the unified repro.core.api.

Sweeps algorithm x elision x replication-caching (Session on/off) on
Erdos-Renyi inputs over the 8-device host mesh, timing the full
FusedMM path (device kernels + host assembly — the api contract).  The
session rows measure the across-call replication-reuse elision: the
second-and-later calls of an iterative solver, with the stationary
operand's fiber gather served from cache.

Writes ``BENCH_dist.json`` so the perf trajectory of the distributed
layer is machine-readable from PR to PR.
"""
import numpy as np

from benchmarks import common
from repro import obs
from repro.core import api, costmodel, sparse

JSON_PATH = "BENCH_dist.json"

M = N = 1024
R = 64
NNZ_ROW = 8


def run(out, json_path=JSON_PATH):
    rows, cols, vals, X, Y = sparse.random_problem(M, N, R, NNZ_ROW,
                                                   seed=0)
    records = []
    # one sweep-wide registry + tracer: every timed cell also runs one
    # traced round, so each row carries its live cost-model drift
    # (schedule_words vs compiled-HLO wire words; docs/observability.md)
    metrics_reg = obs.MetricsRegistry()
    tracer = obs.Tracer(registry=metrics_reg)

    for name in sorted(api.ALGORITHMS):
        prob = api.make_problem(rows, cols, vals, (M, N), R,
                                algorithm=name)
        for elision in prob.alg.elisions:
            # modeled per-processor comm words (Table-III grid row) so
            # the elision win is machine-readable even where the 8-host-
            # device wall times are compile-bound; session rows get the
            # steady-state (cached) model per docs/choosing.md
            cm_kw = dict(p=prob.p, c=prob.c, n=N, r=R, nnz=prob.nnz)
            cm_name = costmodel.ELISION_COST_NAME[(name, elision)]
            model_words = {
                False: costmodel.words_fusedmm(cm_name, **cm_kw).words,
                True: costmodel.words_fusedmm_cached(cm_name,
                                                     **cm_kw).words}
            # uncached: every call pays the full gather
            t_plain = common.timeit(
                lambda: prob.fusedmm(X, Y, elision=elision)[0], iters=2)
            # session-cached steady state: fill once, then time hits
            sess = api.Session()
            prob.fusedmm(X, Y, elision=elision, session=sess)
            t_cached = common.timeit(
                lambda: prob.fusedmm(X, Y, elision=elision,
                                     session=sess)[0], iters=2)
            out(common.csv_line(
                f"dist.{name}.{elision}", t_plain,
                f"c={prob.c};cached_ratio={t_cached / t_plain:.2f}"))
            for cached, t in ((False, t_plain), (True, t_cached)):
                with obs.trace(tracer):
                    prob.fusedmm(X, Y, elision=elision,
                                 session=sess if cached else None)
                rnd = tracer.rounds[-1]
                metrics_reg.gather("session", sess.stats(), family=name,
                                   elision=elision)
                hits = metrics_reg.value("session.hits", family=name,
                                         elision=elision) or 0.0
                miss = metrics_reg.value("session.misses", family=name,
                                         elision=elision) or 0.0
                records.append(dict(
                    name=name, elision=elision, session_cached=cached,
                    c=prob.c, m=M, n=N, r=R, nnz=prob.nnz,
                    phi=prob.phi, seconds=t,
                    model_words=model_words[cached],
                    schedule_words=rnd.modeled_words,
                    measured_words=(rnd.measured_words or {}).get(
                        "total"),
                    drift=rnd.drift,
                    session_hit_rate=hits / max(hits + miss, 1.0)))

        t_sddmm = common.timeit(lambda: prob.sddmm(X, Y).to_dense(),
                                iters=2)
        t_spmm = common.timeit(lambda: prob.spmm(Y), iters=2)
        out(common.csv_line(f"dist.{name}.sddmm", t_sddmm, f"c={prob.c}"))
        out(common.csv_line(f"dist.{name}.spmm", t_spmm, f"c={prob.c}"))
        drifts = {}
        with obs.trace(tracer):
            prob.sddmm(X, Y)
            drifts["sddmm"] = tracer.rounds[-1].drift
            prob.spmm(Y)
            drifts["spmm"] = tracer.rounds[-1].drift
        records.append(dict(name=name, elision=None, kernel="sddmm",
                            session_cached=False, c=prob.c, m=M, n=N,
                            r=R, nnz=prob.nnz, phi=prob.phi,
                            seconds=t_sddmm, drift=drifts["sddmm"]))
        records.append(dict(name=name, elision=None, kernel="spmm",
                            session_cached=False, c=prob.c, m=M, n=N,
                            r=R, nnz=prob.nnz, phi=prob.phi,
                            seconds=t_spmm, drift=drifts["spmm"]))

    # --- training-step rows: fwd-only vs fwd+bwd vs session-reused ---
    # Per registry cell, the extended cost model's per-step words
    # (words_fusedmm / words_trainstep) — the backward is the dual
    # primitive on the same cell, so these are exact model sums, checked
    # against measured HLO wire words by dist_scripts/check_grad_costs.
    # One wall-timed jax.grad step per family (the auto-resolved cell)
    # keeps the compile cost bounded.
    import jax
    import jax.numpy as jnp
    from repro.core import grads
    from repro.distributed.elastic import StepMonitor

    for name in sorted(api.ALGORITHMS):
        prob = api.make_problem(rows, cols, vals, (M, N), R,
                                algorithm=name)
        cm_kw = dict(p=prob.p, c=prob.c, n=N, r=R, nnz=prob.nnz)
        timed_el = prob.resolve_elision("auto")
        for elision in prob.alg.elisions:
            cm_name = costmodel.ELISION_COST_NAME[(name, elision)]
            words_fwd = costmodel.words_fusedmm(cm_name, **cm_kw).words
            words_step = costmodel.words_trainstep(cm_name, **cm_kw).words
            words_step_sess = costmodel.words_trainstep(
                cm_name, session=True, **cm_kw).words
            rec = dict(name=name, elision=elision, kind="trainstep",
                       c=prob.c, m=M, n=N, r=R, nnz=prob.nnz,
                       phi=prob.phi, model_words_fwd=words_fwd,
                       model_words_fwdbwd=words_step,
                       model_words_fwdbwd_session=words_step_sess)
            if elision == timed_el:
                sess = api.Session()

                def step(X, Y):
                    g = jax.grad(lambda X, Y: jnp.sum(
                        grads.fusedmm(prob, X, Y, elision=elision,
                                      session=sess)))(X, Y)
                    return g

                Xj, Yj = jnp.asarray(X), jnp.asarray(Y)
                step(Xj, Yj)                      # fill session + compile
                # timed steps run under the straggler monitor so the
                # bench records which steps blew past the rolling median
                # (the production cordon signal, docs/robustness.md)
                mon = StepMonitor(straggler_factor=3.0)
                steps = iter(range(1 << 20))
                rec["seconds"] = common.timeit(
                    lambda: mon.timed(next(steps), step, Xj, Yj),
                    iters=2)
                rec["straggler_steps"] = list(mon.flagged)
                # cache health for the step: a mis-keyed session shows
                # up as hits=0 right here in the artifact
                rec["session_stats"] = sess.stats()
                out(common.csv_line(
                    f"dist.{name}.{elision}.trainstep", rec["seconds"],
                    f"c={prob.c};words_fwdbwd={words_step:.0f};"
                    f"session={words_step_sess:.0f};"
                    f"stragglers={len(mon.flagged)};"
                    f"session_hits={rec['session_stats']['hits']}"))
            records.append(rec)

    # --- comm-mode rows: dense vs support-pruned wire words per cell ---
    # Measured (compiled-HLO) and modeled words for both wire formats,
    # on the ER problem (near-full supports: the crossover keeps most
    # channels dense) and a seeded power-law problem (skewed supports:
    # pruning beats the dense Table-III optimum outright).  The bf16
    # rows cast the pruned payloads to half width; on this CPU mesh
    # XLA's float-normalization legalizes the bf16 collectives back to
    # f32 (docs/algorithms.md), so their measured words match "sparse"
    # here and halve only on backends with native bf16 collectives.
    from repro.roofline.hlo_parse import collective_summary

    def wire_words(lowered):
        txt = lowered.compile().as_text()
        return collective_summary(txt)["total_wire_bytes"] / 4

    pl_scale = 9
    problems = [
        ("er", rows, cols, vals, (M, N)),
        ("powerlaw",
         *sparse.powerlaw_problem(pl_scale, R, edge_factor=8, seed=1)[:3],
         (1 << pl_scale, 1 << pl_scale)),
    ]
    for gen, grows, gcols, gvals, (gm, gn) in problems:
        rho_row, rho_col = costmodel.support_density(grows, gcols, gm, gn)
        for name in sorted(api.ALGORITHMS):
            probs = {
                co: api.make_problem(grows, gcols, gvals, (gm, gn), R,
                                     algorithm=name, comm=co)
                for co in ("dense", "sparse")}
            prob_bf16 = api.make_problem(grows, gcols, gvals, (gm, gn), R,
                                         algorithm=name, comm="sparse",
                                         compress="bf16")
            ck = dict(p=probs["dense"].p, c=probs["dense"].c, n=gn, r=R,
                      nnz=len(gvals))
            for elision in probs["dense"].alg.elisions:
                cm_name = costmodel.ELISION_COST_NAME[(name, elision)]
                model = {
                    "dense": costmodel.words_fusedmm(cm_name, **ck).words,
                    "sparse": costmodel.words_fusedmm_sparse(
                        cm_name, m=gm, rho_row=rho_row, rho_col=rho_col,
                        **ck).words}
                meas = {co: wire_words(pr.lower_fusedmm(elision=elision))
                        for co, pr in probs.items()}
                meas["sparse_bf16"] = wire_words(
                    prob_bf16.lower_fusedmm(elision=elision))
                records.append(dict(
                    kind="comm", generator=gen, name=name,
                    elision=elision, c=probs["dense"].c, m=gm, n=gn, r=R,
                    nnz=len(gvals), rho_row=rho_row, rho_col=rho_col,
                    measured_words=meas, model_words=model))
                out(common.csv_line(
                    f"dist.comm.{gen}.{name}.{elision}",
                    meas["sparse"] / max(meas["dense"], 1.0),
                    f"dense={meas['dense']:.0f};"
                    f"sparse={meas['sparse']:.0f};"
                    f"bf16={meas['sparse_bf16']:.0f}"))

    path = common.emit_json(json_path, records,
                            meta=dict(bench="dist", m=M, n=N, r=R,
                                      nnz_row=NNZ_ROW))
    out(f"# wrote {path}")
    arts = obs.write_artifacts(".", "dist", registry=metrics_reg)
    out(f"# wrote {arts['metrics']}")


if __name__ == "__main__":
    run(print)
