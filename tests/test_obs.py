"""Observability layer: metrics registry, tracer spans, zero-cost off.

Fast tier (1 device): the multi-device traced smoke with the drift gate
lives in tests/dist_scripts/check_obs.py (slow tier).
"""
import dataclasses
import gzip
import json

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import api, sparse
from repro.obs import metrics as obs_metrics
from repro.obs import tracer as obs_tracer


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = obs.MetricsRegistry()
    reg.inc("a")
    reg.inc("a", 2.5)
    reg.gauge("g", 7.0, family="d15")
    reg.gauge("g", 9.0, family="d15")
    for v in (0.001, 0.01, 0.5, 2.0):
        reg.observe("h", v)
    assert reg.value("a") == 3.5
    assert reg.value("g", family="d15") == 9.0
    h = reg.histogram("h")
    assert h["count"] == 4 and h["min"] == 0.001 and h["max"] == 2.0
    assert h["mean"] == pytest.approx(2.511 / 4)


def test_registry_labels_are_distinct_series():
    reg = obs.MetricsRegistry()
    reg.inc("rounds", op="sddmm")
    reg.inc("rounds", op="spmm")
    reg.inc("rounds", op="sddmm")
    assert reg.value("rounds", op="sddmm") == 2
    assert reg.value("rounds", op="spmm") == 1
    assert reg.value("rounds") is None          # unlabeled series absent


def test_registry_type_conflict_raises():
    reg = obs.MetricsRegistry()
    reg.inc("x")
    with pytest.raises(TypeError):
        reg.gauge("x", 1.0)
    with pytest.raises(TypeError):
        reg.observe("x", 1.0)


def test_registry_gather_skips_non_numeric():
    reg = obs.MetricsRegistry()
    reg.gather("s", dict(hits=3, rate=0.5, name="d15", nested=dict(a=1),
                         flag=True))
    assert reg.value("s.hits") == 3.0
    assert reg.value("s.rate") == 0.5
    assert reg.value("s.name") is None
    assert reg.value("s.nested") is None
    assert reg.value("s.flag") is None          # bools are identity, not data


def test_registry_snapshot_json_round_trip():
    reg = obs.MetricsRegistry()
    reg.inc("c", 3, op="fusedmm")
    reg.gauge("drift", 1.0, family="s25")
    reg.observe("lat", 0.25)
    reg.observe("lat", 4000.0)
    reg.observe("empty_never", 1.0, tag="x")
    blob = reg.to_json()
    back = obs.MetricsRegistry.from_snapshot(json.loads(blob))
    assert back.snapshot() == reg.snapshot()
    assert back.to_json() == blob
    # and a snapshot of a registry holding an EMPTY histogram round-trips
    reg2 = obs.MetricsRegistry()
    reg2._get("h", "histogram", {})
    back2 = obs.MetricsRegistry.from_snapshot(reg2.snapshot())
    assert back2.snapshot() == reg2.snapshot()


def test_registry_merge_adds_counters_and_labels():
    a, b = obs.MetricsRegistry(), obs.MetricsRegistry()
    a.inc("n", 1, mode="x")
    b.inc("n", 2, mode="x")
    b.observe("h", 1.0)
    out = obs.MetricsRegistry()
    out.merge(a, run=0)
    out.merge(b, run=0)
    assert out.value("n", mode="x", run=0) == 3
    assert out.histogram("h", run=0)["count"] == 1


def test_collect_context_arms_and_restores():
    assert obs_metrics.active() is None
    with obs.collect() as reg:
        assert obs_metrics.active() is reg
        with obs.collect() as inner:
            assert obs_metrics.active() is inner
        assert obs_metrics.active() is reg
    assert obs_metrics.active() is None


# ---------------------------------------------------------------------------
# schedule_words contract (1-device degenerate grids)
# ---------------------------------------------------------------------------

def _problem(**kw):
    rows, cols, vals, X, Y = sparse.random_problem(64, 64, 8, 4, seed=0)
    prob = api.make_problem(rows, cols, vals, (64, 64), 8,
                            devices=jax.devices()[:1], **kw)
    return prob, X, Y


@pytest.mark.parametrize("name", sorted(api.ALGORITHMS))
def test_schedule_words_aligns_with_schedule_events(name):
    prob, _, _ = _problem(algorithm=name)
    for op in ("sddmm", "spmm", "spmm_t"):
        ev = prob.alg.schedule_events(prob, op)
        words = prob.schedule_words(op)
        assert words is not None
        assert [(p, t) for p, t, _, _ in words] == ev
        for _, _, kind, w in words:
            assert w >= 0.0
            assert kind in (None, "all-gather", "reduce-scatter",
                            "collective-permute")
    for el in prob.alg.elisions:
        ev = prob.alg.schedule_events(prob, "fusedmm", el)
        words = prob.schedule_words("fusedmm", el)
        assert [(p, t) for p, t, _, _ in words] == ev


def test_schedule_words_none_for_sparse_wire():
    prob, _, _ = _problem(algorithm="d15", comm="sparse")
    assert prob.schedule_words("sddmm") is None


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_trace_records_round_and_event_spans():
    prob, X, Y = _problem(algorithm="d15")
    with obs.collect() as reg, obs.trace(measure_wire=False) as tr:
        prob.sddmm(X, Y)
        prob.fusedmm(X, Y, elision="fused")
    assert [r.op for r in tr.rounds] == ["sddmm", "fusedmm"]
    r0 = tr.rounds[0]
    assert r0.family == "d15" and r0.comm == "dense" and r0.p == 1
    # the round's duration is measured; its events carry the schedule's
    # coordinates and modeled words, and no timing of their own
    assert r0.dur > 0
    words = prob.schedule_words("sddmm")
    assert [(e.point, e.phase, e.kind, e.words) for e in r0.events] == [
        tuple(w) for w in words]
    assert {f.name for f in dataclasses.fields(obs.EventSpan)} == {
        "point", "phase", "kind", "words"}
    assert r0.modeled_words == sum(e.words for e in r0.events)
    # metrics fed live
    assert reg.value("executor.rounds", op="sddmm", family="d15") == 1
    h = reg.histogram("executor.round_seconds", op="fusedmm", family="d15")
    assert h["count"] == 1 and h["max"] == tr.rounds[1].dur


def test_trace_is_bitwise_identical_and_counts_rounds():
    prob, X, Y = _problem(algorithm="s15")
    base = prob.fusedmm(X, Y, elision="none")
    with obs.trace(measure_wire=False) as tr:
        traced = prob.fusedmm(X, Y, elision="none")
        traced2 = prob.fusedmm(X, Y, elision="none")
    assert np.array_equal(base[0], traced[0])
    assert np.array_equal(base[1].values(), traced[1].values())
    assert [r.round for r in tr.rounds] == [0, 1]


def test_trace_survives_unlowerable_measurement():
    # measure_wire=True on a 1-device grid must not break tracing even
    # if lowering fails — measurement errors degrade to measured=None
    prob, X, Y = _problem(algorithm="d25")
    with obs.trace() as tr:
        prob.spmm(Y)
    assert len(tr.rounds) == 1


def test_traced_error_round_is_recorded_and_reraised():
    prob, X, Y = _problem(algorithm="d15")
    with obs.trace(measure_wire=False) as tr:
        with pytest.raises(ValueError):
            prob.fusedmm(X, Y, elision="nonsense")
    # elision validation fails before the round hook: nothing recorded
    assert tr.rounds == []
    with obs.trace(measure_wire=False) as tr:
        with pytest.raises(TypeError):
            with tr.round(prob, "sddmm"):
                raise TypeError("boom")
    assert tr.rounds[0].error == "TypeError"
    assert tr.rounds[0].drift is None


# ---------------------------------------------------------------------------
# Zero-cost when disabled (the faults.guard discipline)
# ---------------------------------------------------------------------------

def test_disabled_tracer_is_never_touched(monkeypatch):
    """With no tracer armed the executors must not construct spans,
    call any Tracer method, or change results — the disabled path is
    one `active() is None` check, like faults.guard."""
    prob, X, Y = _problem(algorithm="d15")
    base = prob.sddmm(X, Y).values()

    def explode(*a, **kw):
        raise AssertionError("obs hook ran while disabled")

    monkeypatch.setattr(obs_tracer.Tracer, "round", explode)
    monkeypatch.setattr(obs_tracer.Tracer, "_finish", explode)
    assert obs_tracer.active() is None
    got = prob.sddmm(X, Y).values()      # would raise if obs were touched
    assert np.array_equal(base, got)


def test_disabled_metrics_skip_instrumented_sites(monkeypatch):
    from repro.distributed.elastic import StepMonitor
    monkeypatch.setattr(obs.MetricsRegistry, "observe",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            AssertionError("metrics while disabled")))
    assert obs_metrics.active() is None
    mon = StepMonitor()
    assert mon.observe(0, 1.0) is False  # no registry: no metric calls


def test_trace_context_restores_previous():
    assert obs_tracer.active() is None
    with obs.trace(measure_wire=False) as tr:
        assert obs_tracer.active() is tr
    assert obs_tracer.active() is None


# ---------------------------------------------------------------------------
# Host spans in a jax.profiler trace
# ---------------------------------------------------------------------------

def _profiled(tmp_path, fn, perfetto=False):
    """Run ``fn`` under a jax.profiler session; returns (its result, the
    program's host spans as (name, start_ns, end_ns, stats) by start)."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path), create_perfetto_trace=perfetto):
        got = fn()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(("api.", "als."))]
    return got, sorted(spans, key=lambda sp: (sp[1], -sp[2]))


def _inside(outer, spans, name):
    """The spans called ``name`` that lie within the span ``outer``."""
    return [sp for sp in spans if sp[0] == name
            and outer[1] <= sp[1] and sp[2] <= outer[2]]


def test_profiler_spans_nest_in_an_api_round(tmp_path):
    """A s15 FusedMM with a Session: the round holds the operand
    placement (the Session's content keys and the uploads) and then the
    assembly (the wait for the device and the copy back), with the
    uploaded and fetched bytes as stats."""
    prob, X, Y = _problem(algorithm="s15")
    sess = api.Session()
    (out, _), spans = _profiled(
        tmp_path, lambda: prob.fusedmm(X, Y, elision="fused", session=sess))
    (rnd,) = [sp for sp in spans if sp[0] == "api.fusedmm"]
    assert rnd[3] == {"family": "s15", "elision": "fused"}
    (put,) = _inside(rnd, spans, "api.put")
    (asm,) = _inside(rnd, spans, "api.assemble")
    assert put[2] <= asm[1]
    keys = _inside(put, spans, "api.session_key")
    assert [k[3]["hit"] for k in keys] == [0, 0]     # a new Session misses
    ups = _inside(put, spans, "api.upload")
    assert sorted(u[3]["bytes"] for u in ups) == sorted([X.nbytes, Y.nbytes])
    (wait,) = _inside(asm, spans, "api.wait")
    (fetch,) = _inside(asm, spans, "api.fetch")
    assert wait[2] <= fetch[1]
    assert fetch[3]["bytes"] == out.nbytes           # p = 1: the whole output
    # the sampled values stay on the device: nothing else is fetched
    assert len([sp for sp in spans if sp[0] == "api.fetch"]) == 1


def _cg(prob, X, Y, session):
    """Two CG steps of the normal equations on the problem's pattern as a
    mask (positive definite)."""
    from repro.apps import als
    mask = prob.with_values(np.ones_like(prob.vals))
    return als.dist_cg_solve(mask, Y, X, 0.1, iters=2, session=session)


def test_profiler_spans_of_a_cg_solve(tmp_path):
    """``dist_cg_solve`` opens ``als.cg_host`` once per CG step, the
    starting residual's included, each after that step's FusedMM round;
    the Session's second key of the fixed factor hits its memo."""
    prob, X, Y = _problem(algorithm="s15")
    sess = api.Session()
    _, spans = _profiled(tmp_path, lambda: _cg(prob, X, Y, sess))
    rounds = [sp for sp in spans if sp[0] == "api.fusedmm"]
    host = [sp for sp in spans if sp[0] == "als.cg_host"]
    assert len(rounds) == len(host) == 3
    for rnd, h, nxt in zip(rounds, host, rounds[1:] + [None]):
        assert rnd[2] <= h[1] and (nxt is None or h[2] <= nxt[1])
        assert not _inside(h, spans, "api.put")
    keys = [k for k in spans if k[0] == "api.session_key"]
    hits = [k[3]["hit"] for k in keys]
    assert len(hits) == 6 and hits[1] == 0 and hits[3] == hits[5] == 1
    # the digest runs once per memo miss, inside that key, over the
    # operand's bytes (X and the iterates share one shape)
    digests = [sp for sp in spans if sp[0] == "api.digest"]
    assert len(digests) == hits.count(0)
    for k in keys:
        inner = _inside(k, spans, "api.digest")
        assert len(inner) == (1 - k[3]["hit"])
        assert all(d[3]["bytes"] == X.nbytes and d[3]["chunks"] == 1
                   for d in inner)


def test_profiler_spans_leave_results_bitwise_identical(tmp_path):
    prob, X, Y = _problem(algorithm="s15")

    def work():
        out, rv = prob.fusedmm(X, Y, elision="fused",
                               session=api.Session())
        return out, rv.values(), _cg(prob, X, Y, api.Session())

    base = work()
    traced, spans = _profiled(tmp_path, work)
    assert spans
    for a, b in zip(base, traced, strict=True):
        assert np.isfinite(a).all() and np.array_equal(a, b)


def test_chrome_trace_structure_and_artifacts(tmp_path):
    """The profiler's Perfetto export is Chrome trace-event JSON with the
    program's spans as complete events, nested on one thread's track;
    ``write_artifacts`` writes the metrics snapshot beside it."""
    prob, X, Y = _problem(algorithm="d15")
    with obs.collect() as reg, obs.trace(measure_wire=False) as tr:
        _profiled(tmp_path / "trace", lambda: prob.sddmm(X, Y), perfetto=True)
    (path,) = (tmp_path / "trace").glob("plugins/profile/*/perfetto_trace"
                                        ".json.gz")
    evs = json.load(gzip.open(path))["traceEvents"]
    xs = {e["name"]: e for e in evs if e.get("ph") == "X"
          and e["name"].startswith("api.")}
    assert set(xs) >= {"api.sddmm", "api.put", "api.upload",
                       "api.assemble"}
    rnd = xs["api.sddmm"]
    assert rnd["args"] == {"family": "d15", "elision": "none"}
    for e in xs.values():
        assert e["tid"] == rnd["tid"]
        assert rnd["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= rnd["ts"] + rnd["dur"] + 1e-3
    # the tracer's round duration is measured around the same call
    assert 0 < tr.rounds[0].dur
    paths = obs.write_artifacts(str(tmp_path), "t", registry=reg)
    assert set(paths) == {"metrics"}
    assert paths["metrics"].endswith("METRICS_t.json")
    metrics_blob = json.load(open(paths["metrics"]))
    assert obs.MetricsRegistry.from_snapshot(
        metrics_blob).snapshot() == reg.snapshot()
    assert not hasattr(obs, "chrome_trace")


def test_round_summary_renders():
    prob, X, Y = _problem(algorithm="s25")
    with obs.trace(measure_wire=False) as tr:
        prob.fusedmm(X, Y, elision="reuse")
    txt = obs.round_summary(tr)
    assert "s25.fusedmm[reuse]" in txt and "drift" in txt
