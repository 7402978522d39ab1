"""Unified distributed-algorithm API: registry, dispatch, parity (1 device).

The multi-device versions of these checks live in
tests/dist_scripts/check_api.py / check_apps_dist.py (slow tier); here
every registered algorithm degenerates onto a single-device grid, which
exercises the full plan/execute/assemble path and the dispatch logic
cheaply on every PR.
"""
import concurrent.futures
import inspect

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import api, costmodel, d15, d25, s15, s25, sparse
from repro.kernels import ops, ref


def _problem_data(m=64, n=64, r=8, k=4, seed=0):
    rows, cols, vals, X, Y = sparse.random_problem(m, n, r, k, seed=seed)
    Sd = np.zeros((m, n), np.float32)
    Sd[rows, cols] = vals
    return rows, cols, vals, X, Y, Sd


def _dev1():
    # other fast-tier modules (dryrun) force a huge host device count at
    # import time; the single-device degenerate grids are pinned here
    return jax.devices()[:1]


def _make(rows, cols, vals, shape, r, **kw):
    return api.make_problem(rows, cols, vals, shape, r, devices=_dev1(),
                            **kw)


# the full registry-declared (family, elision) grid: parametrizing the
# parity tests over it makes a registry entry that claims an elision it
# cannot execute fail fast, cell by cell
ELISION_CELLS = sorted((name, el) for name in costmodel.FAMILIES
                       for el in api.ALGORITHMS[name].elisions)


def test_registry_has_all_four_families():
    assert set(api.ALGORITHMS) == set(costmodel.FAMILIES)
    for name, alg in api.ALGORITHMS.items():
        assert alg.name == name
        assert alg.elisions, name


def test_registry_matrix_full_rank():
    """Every family exposes reuse; every family but s25 exposes fused
    (s25's fused cell is structurally impossible — docs/algorithms.md);
    every declared cell has a Table-III cost row and auto candidates are
    declared cells."""
    cells = set(costmodel.FAMILY_ELISION.values())
    for name, alg in api.ALGORITHMS.items():
        assert "none" in alg.elisions and "reuse" in alg.elisions, name
        assert ("fused" in alg.elisions) == (name != "s25"), name
        for el in alg.elisions:
            assert (name, el) in cells, (name, el)
        assert set(alg.auto_elisions) <= set(alg.elisions), name


def test_uniform_auto_elision_default():
    """Satellite: every family fusedmm entrypoint defaults to "auto"."""
    for fn in (d15.fusedmm_d15, s15.fusedmm_s15, d25.fusedmm_d25,
               s25.fusedmm_s25):
        sig = inspect.signature(fn)
        assert sig.parameters["elision"].default == "auto", fn


def test_choose_algorithm_regime_rule():
    """Low phi -> sparse families; high phi -> dense families (Fig. 6)."""
    kw = dict(m=1 << 16, n=1 << 16, r=128, p=64)
    lo = costmodel.choose_algorithm(nnz=int(0.02 * kw["n"] * kw["r"]), **kw)
    hi = costmodel.choose_algorithm(nnz=int(4.0 * kw["n"] * kw["r"]), **kw)
    assert lo.family.startswith("s")
    assert hi.family.startswith("d")


def test_choose_algorithm_respects_feasibility():
    # r=2 rules out s15 (needs r % p == 0) and s25/d25 at 4 procs
    ch = costmodel.choose_algorithm(m=64, n=64, nnz=256, r=2, p=4)
    assert ch.family == "d15"
    with pytest.raises(ValueError):
        costmodel.choose_algorithm(m=63, n=63, nnz=64, r=2, p=4)
    # pinned c filters candidates
    ch = costmodel.choose_algorithm(m=64, n=64, nnz=256, r=8, p=4, c=4)
    assert ch.c == 4


def test_family_feasible():
    assert costmodel.family_feasible("d15", m=64, n=64, r=2, p=8, c=2)
    assert not costmodel.family_feasible("s15", m=64, n=64, r=2, p=8, c=2)
    assert costmodel.family_feasible("d25", m=64, n=64, r=4, p=8, c=2)
    assert not costmodel.family_feasible("d25", m=64, n=64, r=4, p=8, c=4)


@pytest.mark.parametrize("name", sorted(costmodel.FAMILIES))
def test_api_parity_vs_ref(name):
    """Same problem through every registered algorithm == kernels/ref."""
    rows, cols, vals, X, Y, Sd = _problem_data()
    prob = _make(rows, cols, vals, Sd.shape, X.shape[1],
                 algorithm=name)
    wantR = np.asarray(ref.sddmm_dense(jnp.asarray(X), jnp.asarray(Y),
                                       jnp.asarray(Sd)))
    np.testing.assert_allclose(prob.sddmm(X, Y).to_dense(), wantR,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(prob.spmm(Y),
                               np.asarray(ref.spmm_dense(Sd, Y)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("comm", ("dense", "sparse"))
@pytest.mark.parametrize("name,el", ELISION_CELLS)
def test_fusedmm_parity_per_cell(name, el, comm):
    """Every registry-declared (family, elision) cell executes and
    matches the dense oracle — a declared-but-unimplemented cell fails
    exactly here.  Parametrized over the wire format: comm="sparse"
    plans and runs the support-pruned program through the same cells
    (degenerate single-device channels here; the multi-device pruning is
    tests/dist_scripts/check_comm_sparse.py)."""
    rows, cols, vals, X, Y, Sd = _problem_data()
    prob = _make(rows, cols, vals, Sd.shape, X.shape[1], algorithm=name,
                 comm=comm)
    wantR = np.asarray(ref.sddmm_dense(jnp.asarray(X), jnp.asarray(Y),
                                       jnp.asarray(Sd)))
    want_out, _ = ref.fusedmm_dense(X, Y, Sd)
    out, R = prob.fusedmm(X, Y, elision=el)
    np.testing.assert_allclose(out, want_out, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(R.to_dense(), wantR, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("name,el", ELISION_CELLS)
def test_comm_sparse_bitwise_vs_dense(name, el):
    """comm="sparse" is bitwise-identical to comm="dense" at every
    registry cell (the executors prune only input-operand movements;
    every accumulation keeps its order)."""
    rows, cols, vals, X, Y, Sd = _problem_data(seed=4)
    pd = _make(rows, cols, vals, Sd.shape, 8, algorithm=name)
    ps = _make(rows, cols, vals, Sd.shape, 8, algorithm=name,
               comm="sparse")
    od, Rd = pd.fusedmm(X, Y, elision=el)
    os_, Rs = ps.fusedmm(X, Y, elision=el)
    np.testing.assert_array_equal(od, os_)
    np.testing.assert_array_equal(Rd.values(), Rs.values())
    np.testing.assert_array_equal(pd.spmm_t(X), ps.spmm_t(X))


def test_comm_mode_plumbing():
    """comm/compress validate, resolve from "auto" via the support
    densities, survive the meta_dict round-trip, and key the Session."""
    rows, cols, vals, X, Y, _ = _problem_data(seed=12)
    with pytest.raises(ValueError, match="comm"):
        _make(rows, cols, vals, (64, 64), 8, comm="nope")
    with pytest.raises(ValueError, match="compress"):
        _make(rows, cols, vals, (64, 64), 8, compress="fp4")
    auto = _make(rows, cols, vals, (64, 64), 8, comm="auto")
    assert auto.comm == costmodel.choose_comm(rows, cols, 64, 64)
    prob = _make(rows, cols, vals, (64, 64), 8, algorithm="d15",
                 comm="sparse", compress="bf16")
    meta = prob.meta_dict()
    assert meta["comm"] == "sparse" and meta["compress"] == "bf16"
    back = api.problem_from_meta(meta, rows, cols, vals,
                                 devices=_dev1())
    assert back.comm == "sparse" and back.compress == "bf16"
    # derived problems inherit the wire format
    assert prob.transposed().comm == "sparse"
    assert prob.with_values(vals * 2).comm == "sparse"
    # sessions key on comm: same operand under each mode -> two entries
    dense = _make(rows, cols, vals, (64, 64), 8, algorithm="d15")
    sess = api.Session()
    sess.replicate(dense, X, "x")
    sess.replicate(prob, X, "x")
    assert sess.stats() == dict(hits=0, misses=2, entries=2, capacity=16)
    sess.replicate(prob, X, "x")
    assert sess.stats()["hits"] == 1


def test_undeclared_elision_rejected():
    rows, cols, vals, X, Y, _ = _problem_data()
    prob = _make(rows, cols, vals, (64, 64), 8, algorithm="s25")
    with pytest.raises(ValueError, match="supports"):
        prob.fusedmm(X, Y, elision="fused")


@pytest.mark.parametrize("name,el", ELISION_CELLS)
def test_session_caching_bitwise(name, el):
    """Cached replication == uncached, bit for bit, at every cell."""
    rows, cols, vals, X, Y, _ = _problem_data(seed=2)
    prob = _make(rows, cols, vals, (64, 64), 8, algorithm=name)
    sess = api.Session()
    base, _ = prob.fusedmm(X, Y, elision=el)
    one, _ = prob.fusedmm(X, Y, elision=el, session=sess)
    two, _ = prob.fusedmm(X, Y, elision=el, session=sess)
    np.testing.assert_array_equal(base, one)
    np.testing.assert_array_equal(base, two)


def test_sparse_result_values_without_dense():
    """values()/to_coo assemble O(nnz) and match the dense view."""
    rows, cols, vals, X, Y, Sd = _problem_data(seed=5)
    wantR = Sd * (X @ Y.T)
    for name in sorted(costmodel.FAMILIES):
        prob = _make(rows, cols, vals, (64, 64), 8, algorithm=name)
        res = prob.sddmm(X, Y)
        np.testing.assert_allclose(res.values(), wantR[rows, cols],
                                   rtol=2e-4, atol=2e-4, err_msg=name)
        r, c, v = res.to_coo()
        back = np.zeros((64, 64), np.float32)
        np.add.at(back, (r, c), v)
        np.testing.assert_allclose(back, wantR, rtol=2e-4, atol=2e-4,
                                   err_msg=name)


def test_session_lru_bound():
    """The cache evicts cold iterates; the hot operand stays correct."""
    rows, cols, vals, X, Y, _ = _problem_data(seed=6)
    prob = _make(rows, cols, vals, (64, 64), 8, algorithm="s15")
    base, _ = prob.fusedmm(X, Y, elision="reuse")
    sess = api.Session(max_entries=3)
    rng = np.random.default_rng(9)
    for _ in range(8):
        it = rng.standard_normal((64, 8)).astype(np.float32)
        prob.fusedmm(it, Y, elision="reuse", session=sess)
    assert len(sess) <= 3
    out, _ = prob.fusedmm(X, Y, elision="reuse", session=sess)
    np.testing.assert_array_equal(base, out)


def test_session_aware_elision_ranking():
    """With a Session the steady-state (cached) word counts rank the
    cells; on the degenerate single-device grid (c=1, no replication to
    cache) "fused" wins everywhere it exists — fewest shift words."""
    rows, cols, vals, _, _, _ = _problem_data()
    for name in ("d15", "s15", "d25"):
        prob = _make(rows, cols, vals, (64, 64), 8, algorithm=name)
        assert prob.resolve_elision("auto") == "fused", name
        assert prob.resolve_elision("auto", api.Session()) == "fused", name
    s25p = _make(rows, cols, vals, (64, 64), 8, algorithm="s25")
    assert s25p.resolve_elision("auto") == "reuse"
    assert s25p.resolve_elision("auto", api.Session()) == "reuse"


@pytest.mark.parametrize("name", sorted(costmodel.FAMILIES))
def test_spmm_t_parity_and_vals_injection(name):
    """spmm_t == S^T @ A on every family, with cotangent-style value
    injection and a Session-replayed (bitwise-identical) path."""
    rows, cols, vals, X, Y, Sd = _problem_data(seed=7)
    prob = _make(rows, cols, vals, Sd.shape, 8, algorithm=name)
    g = np.random.default_rng(11).standard_normal((64, 8)).astype(
        np.float32)
    np.testing.assert_allclose(prob.spmm_t(g), Sd.T @ g, rtol=2e-4,
                               atol=2e-4)
    v2 = (np.arange(len(vals)) * 0.01).astype(np.float32)
    S2 = np.zeros(Sd.shape, np.float32)
    S2[rows, cols] = v2
    base = prob.spmm_t(g, vals=v2)
    np.testing.assert_allclose(base, S2.T @ g, rtol=2e-4, atol=2e-4)
    sess = api.Session()
    np.testing.assert_array_equal(base, prob.spmm_t(g, vals=v2,
                                                    session=sess))
    np.testing.assert_array_equal(base, prob.spmm_t(g, vals=v2,
                                                    session=sess))


@pytest.mark.parametrize("name", sorted(costmodel.FAMILIES))
def test_injected_values_bitwise_vs_repack(name):
    """spmm with ``vals=`` injects values into the cached structure pack
    and must be BITWISE identical to a full re-pack via with_values —
    the backward pass's hot path rides on this."""
    rows, cols, vals, X, Y, Sd = _problem_data(seed=9)
    prob = _make(rows, cols, vals, Sd.shape, 8, algorithm=name)
    v2 = np.random.default_rng(13).standard_normal(len(vals)).astype(
        np.float32)
    want = prob.with_values(v2).spmm(Y)
    got = prob.spmm(Y, vals=v2)
    np.testing.assert_array_equal(want, got)
    # structure planned once: injection must not add plan cache entries
    n_plans = len(prob._plans)
    prob.spmm(Y, vals=v2 * 2.0)
    assert len(prob._plans) == n_plans
    # transposed() is cached, and round-trips to the original
    assert prob.transposed() is prob.transposed()
    assert prob.transposed().transposed() is prob


@pytest.mark.parametrize("name", sorted(costmodel.FAMILIES))
def test_sddmm_spmm_session_bitwise(name):
    """The session paths of the single-kernel entrypoints are
    bitwise-identical to the plain paths."""
    rows, cols, vals, X, Y, Sd = _problem_data(seed=10)
    prob = _make(rows, cols, vals, Sd.shape, 8, algorithm=name)
    sess = api.Session()
    base = prob.sddmm(X, Y).values()
    np.testing.assert_array_equal(base,
                                  prob.sddmm(X, Y, session=sess).values())
    np.testing.assert_array_equal(base,
                                  prob.sddmm(X, Y, session=sess).values())
    base_s = prob.spmm(Y)
    np.testing.assert_array_equal(base_s, prob.spmm(Y, session=sess))


def test_session_content_keyed_replay():
    """The Session hits on CONTENT, not identity: a copy of a cached
    operand (what the backward pass hands the executors after a jax
    round-trip) replays the replication instead of re-gathering."""
    rows, cols, vals, X, Y, _ = _problem_data(seed=8)
    prob = _make(rows, cols, vals, (64, 64), 8, algorithm="d15")
    sess = api.Session()
    prob.fusedmm(X, Y, elision="reuse", session=sess)
    misses = sess.misses
    out2, _ = prob.fusedmm(X.copy(), Y.copy(), elision="reuse",
                           session=sess)
    assert sess.misses == misses and sess.hits >= 1
    base, _ = prob.fusedmm(X, Y, elision="reuse")
    np.testing.assert_array_equal(base, out2)
    # mutation changes the content digest -> transparent re-replication
    Ymut = Y.copy()
    prob.fusedmm(X, Ymut, elision="reuse", session=sess)
    Ymut *= 0.5
    out_mut, _ = prob.fusedmm(X, Ymut, elision="reuse", session=sess)
    want, _ = prob.fusedmm(X, Ymut, elision="reuse")
    np.testing.assert_array_equal(want, out_mut)


@pytest.mark.parametrize("nbytes", [
    api._FP_SERIAL - 256,                  # one serial pass
    api._FP_SERIAL,                        # the first chunked size
    3 * api._FP_CHUNK + 5 * 256,           # four chunks, the last short
])
def test_session_fingerprints_over_sizes(nbytes, monkeypatch):
    """The Session's content key and memo sum, read from the operand's
    buffer serially or in chunks on the pool: equal contents key alike
    whatever their layout or the pool's size; one changed element, a
    shape or a dtype re-keys."""
    rows, cols, vals, _, _, _ = _problem_data(seed=13)
    prob = _make(rows, cols, vals, (64, 64), 8, algorithm="d15")
    rng = np.random.default_rng(nbytes)
    A = rng.standard_normal((nbytes // 256, 64)).astype(np.float32)
    assert A.nbytes == nbytes
    sess = api.Session()
    key = sess._content_key(prob, A, "x")
    assert key[-1] == api._fingerprints(A)[0]
    # a content-equal copy, a non-contiguous view of the same values and
    # a Fortran-ordered copy key alike
    assert api.Session()._content_key(prob, A.copy(), "x") == key
    wide = np.repeat(A, 2, axis=1)
    assert api.Session()._content_key(prob, wide[:, ::2], "x") == key
    assert api.Session()._content_key(prob, np.asfortranarray(A),
                                      "x") == key
    # the same bytes under another shape or dtype key apart
    for other in (A.reshape(-1, 32), A.view(np.int32)):
        assert api.Session()._content_key(prob, other, "x") != key
    # the digest's pass and the memo's own take the same sum
    digest, total = api._fingerprints(A)
    assert api._fingerprints(A, digest=False) == (None, total)
    assert np.isclose(total, A.sum(dtype=np.float64), rtol=1e-9)
    # neither depends on the pool's size
    for workers in (1, 4):
        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            monkeypatch.setattr(api, "_fp_pool", pool)
            assert api._fingerprints(A) == (digest, total)
    monkeypatch.undo()
    # one element of the last chunk, changed in place: the memo's sum
    # moves and the key follows
    fp = api.Session._cheap_fp(A)
    A[-1, -1] += 1.0
    assert api.Session._cheap_fp(A) != fp
    assert sess._content_key(prob, A, "x") != key
    assert sess._content_key(prob, A, "x") == api.Session()._content_key(
        prob, A.copy(), "x")


def test_with_values_and_transposed():
    rows, cols, vals, X, Y, Sd = _problem_data()
    prob = _make(rows, cols, vals, (64, 64), 8, algorithm="d15")
    ones = prob.with_values(np.ones_like(vals))
    want = (Sd != 0).astype(np.float32) @ Y
    np.testing.assert_allclose(ones.spmm(Y), want, rtol=2e-4, atol=2e-4)
    probT = prob.transposed()
    np.testing.assert_allclose(probT.spmm(X), Sd.T @ X, rtol=2e-4,
                               atol=2e-4)


def test_with_r_validates_divisibility():
    rows, cols, vals, _, _, Sd = _problem_data()
    prob = _make(rows, cols, vals, (64, 64), 8, algorithm="s15")
    assert prob.with_r(4).r == 4      # p=1: every width is feasible
    # the divisibility rule itself (multi-device grids are slow-tier)
    fake = type("G", (), {"p": 8, "G": 2, "c": 2})()
    assert api.ALGORITHMS["s15"].min_r_multiple(fake) == 8
    assert api.ALGORITHMS["d25"].min_r_multiple(fake) == 2
    assert api.ALGORITHMS["s25"].min_r_multiple(fake) == 4
    assert api.ALGORITHMS["d15"].min_r_multiple(fake) == 1


def test_ops_routing_when_mesh_active():
    """kernels/ops routes through the api while a problem is active."""
    rows, cols, vals, X, Y, Sd = _problem_data(seed=3)
    S = sparse.pack_row_tiled(rows, cols, vals, (64, 64), row_tile=32,
                              nz_block=32)
    prob = _make(rows, cols, vals, (64, 64), 8, algorithm="d15")
    Xj, Yj = jnp.asarray(X), jnp.asarray(Y)
    local_R = ops.sddmm(Xj, Yj, S)
    local_out = ops.spmm(S, Yj, m=64)
    local_f, local_fR = ops.fusedmm(Xj, Yj, S, m=64)
    with api.activate(prob, S):
        routed_R = ops.sddmm(Xj, Yj, S)
        routed_out = ops.spmm(S, Yj, m=64)
        routed_f, routed_fR = ops.fusedmm(Xj, Yj, S, m=64)
        # a different pack falls through to the local kernels
        other = sparse.pack_row_tiled(rows, cols, vals, (64, 64),
                                      row_tile=32, nz_block=32)
        ops.spmm(other, Yj, m=64)
        # an explicit backend request always wins over routing
        ref_out = ops.spmm(S, Yj, m=64, backend="ref")
        np.testing.assert_allclose(np.asarray(ref_out), Sd @ Y,
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(routed_R.to_dense()),
                               np.asarray(local_R.to_dense()),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(routed_out),
                               np.asarray(local_out), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(routed_f),
                               np.asarray(local_f), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(routed_fR.to_dense()),
                               np.asarray(local_fR.to_dense()),
                               rtol=2e-3, atol=2e-3)
    assert ops._DIST_ROUTER is None    # context restored


def test_distributed_als_single_device():
    from repro.apps import als
    _, _, hist = als.run_als_distributed(m=128, n=128, nnz_per_row=6,
                                         r=16, rounds=2, cg_iters=8,
                                         devices=_dev1(), verbose=False)
    assert hist[-1] < 0.3 * hist[0], hist


def test_distributed_gat_matches_local():
    from repro.apps import gat
    n, d, seed = 96, 16, 3
    S = gat.make_graph(n, 4, seed=seed, row_tile=32, nz_block=32)
    gp = gat.make_dist_graph(n, 4, d, seed=seed, devices=_dev1())
    rng = np.random.default_rng(seed)
    H = rng.standard_normal((n, d)).astype(np.float32)
    p = gat.init_gat_layer(jax.random.PRNGKey(0), d, d)
    want = np.asarray(gat.gat_layer(S, jnp.asarray(H), p))
    got = np.asarray(gat.gat_layer_distributed(gp, H, p))
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)
