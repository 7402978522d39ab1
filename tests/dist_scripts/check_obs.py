"""Traced 8-device smoke with the cost-model drift gate (docs/observability.md).

Four guarantees:

1. **Drift gate** — one traced dense cell per executor family (sddmm,
   spmm, and the auto-resolved fusedmm elision, plain and +session):
   every round's measured/modeled wire-word ratio must land inside
   [0.99, 1.01].  The model is impl-exact, so the expected drift is
   exactly 1.0; the band only absorbs future backend-legalization noise.

2. **Span accounting** — per-event modeled words sum to the round's
   modeled total, and event spans align 1:1 with ``schedule_events``.

3. **Zero-cost parity** — the traced FusedMM result is bitwise-identical
   to the untraced call on the same mesh.

4. **Registry surface** — one smoke pass through the instrumented
   subsystems (executor rounds, Session, SessionPool/serving tick,
   ElasticProblem retry) populates the registry, and its snapshot
   JSON-round-trips exactly.

Writes METRICS_smoke.json (the CI observability artifact) and prints
ALL OBS OK.
"""
import json
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import numpy as np
import jax

from repro import obs, serving
from repro.apps import als
from repro.core import api, sparse
from repro.distributed import faults

assert len(jax.devices()) == 8

m = n = 64
r = 16
nnz_row = 4
DRIFT_BAND = (0.99, 1.01)

rng = np.random.default_rng(0)
rows, cols, _ = sparse.erdos_renyi(m, n, nnz_row, seed=0)
vals = rng.integers(1, 5, rows.shape[0]).astype(np.float32)
X = rng.integers(-3, 4, (m, r)).astype(np.float32)
Y = rng.integers(-3, 4, (n, r)).astype(np.float32)

reg = obs.MetricsRegistry()
tracer = obs.Tracer(registry=reg)

# --- 1+2. drift gate + span accounting: every family, dense comm ------------
for name in sorted(api.ALGORITHMS):
    prob = api.make_problem(rows, cols, vals, (m, n), r,
                            algorithm=name, c=2)
    el = prob.resolve_elision("auto")
    sess = api.Session()
    with obs.trace(tracer):
        prob.sddmm(X, Y)
        prob.spmm(Y)
        prob.fusedmm(X, Y, elision=el)
        prob.fusedmm(X, Y, elision=el, session=sess)
        prob.fusedmm(X, Y, elision=el, session=sess)   # cached round
    reg.gather("session", sess.stats(), family=name)
    for rnd in tracer.rounds[-5:]:
        tag = (f"{name}.{rnd.op}"
               + (f"[{rnd.elision}]" if rnd.op == "fusedmm" else "")
               + ("+sess" if rnd.session else ""))
        assert rnd.comm == "dense" and rnd.p == 8, tag
        events = prob.alg.schedule_events(prob, rnd.op, rnd.elision)
        assert [(e.point, e.phase) for e in rnd.events] == events, tag
        assert rnd.modeled_words is not None, tag
        ev_sum = sum(e.words for e in rnd.events if e.words is not None)
        assert abs(ev_sum - rnd.modeled_words) < 1e-6, (
            f"{tag}: event words {ev_sum} != round model "
            f"{rnd.modeled_words}")
        assert rnd.measured_words is not None, tag
        assert rnd.drift is not None, tag
        assert DRIFT_BAND[0] <= rnd.drift <= DRIFT_BAND[1], (
            f"{tag}: cost-model drift {rnd.drift:.6f} outside "
            f"{DRIFT_BAND} (modeled={rnd.modeled_words:.0f} "
            f"measured={rnd.measured_words['total']:.0f})")
        print(f"{tag:28s} modeled={rnd.modeled_words:8.0f} "
              f"measured={rnd.measured_words['total']:8.0f} "
              f"drift={rnd.drift:.4f}")

# --- 3. traced result is bitwise-identical to the untraced call -------------
prob = api.make_problem(rows, cols, vals, (m, n), r, algorithm="d15", c=2)
base = np.asarray(prob.fusedmm(X, Y, elision="fused")[0])
with obs.trace(tracer):
    got = np.asarray(prob.fusedmm(X, Y, elision="fused")[0])
assert np.array_equal(base, got), "tracing changed the FusedMM result"
print("traced-vs-untraced fusedmm: bitwise identical")

# --- 4a. elastic-retry metrics under an injected transient fault ------------
plan = faults.FaultPlan.scripted(
    faults.FaultSpec(op="sddmm", point="*", rank=1, phase=-1, round=0))
with obs.collect(reg), faults.inject(plan):
    ep = api.ElasticProblem(prob)
    ep.sddmm(X, Y)
assert reg.value("elastic.retries", op="sddmm") == 1
assert reg.value("elastic.faults", op="sddmm",
                 kind="TransientFault") == 1
print("elastic retry metrics ok")

# --- 4b. serving tick latency + pool/session series -------------------------
U = rng.standard_normal((m, r)).astype(np.float32)
V = rng.standard_normal((n, r)).astype(np.float32)
pool = serving.SessionPool(capacity=2)
dep = als.deploy_factors(pool, rows, cols, vals, (m, n), U, V)
eng = serving.ServingEngine(pool, max_batch=8)
with obs.collect(reg):
    for _ in range(2):
        eng.submit_score(dep, rng.integers(0, m, 8),
                         rng.integers(0, n, 8), "U", "V")
    eng.run_until_drained()
assert (reg.histogram("serving.tick_seconds") or {}).get("count"), \
    "serving tick latency series missing"
assert reg.value("serving.pool.hits") is not None
assert reg.value("serving.pool.session.hits") is not None
print("serving metrics ok")

# --- registry snapshot round-trips; required series present -----------------
for series in ("session.hits", "serving.pool.hits", "elastic.retries",
               "costmodel.drift"):
    assert any(s["name"] == series for s in reg.series()), \
        f"registry missing {series}"
snap = reg.snapshot()
assert obs.MetricsRegistry.from_snapshot(
    json.loads(json.dumps(snap))).snapshot() == snap, \
    "metrics snapshot does not round-trip"

# --- metrics artifact -------------------------------------------------------
paths = obs.write_artifacts(".", "smoke", registry=reg)
json.load(open(paths["metrics"]))        # the artifact must be valid JSON
print("wrote", paths["metrics"], f"({len(reg.series())} metric series)")
print(obs.round_summary(tracer))
print("ALL OBS OK")
