"""Per-kernel validation: Pallas (interpret) vs pure-jnp oracle.

Sweeps shapes/dtypes and runs hypothesis property tests on the kernel
invariants (duality, linearity, masking).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep: fall back to the deterministic sampler
    from _propcheck import given, settings, strategies as st

from repro.core import sparse
from repro.kernels import ops, ref


def make_problem(m, n, r, nnz_per_row, seed, dtype=jnp.float32,
                 row_tile=128, nz_block=64):
    rng = np.random.default_rng(seed)
    rows, cols, vals = sparse.erdos_renyi(m, n, nnz_per_row, seed=seed)
    S = sparse.pack_row_tiled(rows, cols, vals, (m, n),
                              row_tile=row_tile, nz_block=nz_block)
    A = jnp.asarray(rng.standard_normal((m, r)), dtype)
    B = jnp.asarray(rng.standard_normal((n, r)), dtype)
    Sd = np.zeros((m, n), np.float32)
    Sd[rows, cols] = vals
    return S, A, B, jnp.asarray(Sd)


SHAPES = [
    (128, 128, 64, 4),
    (256, 128, 128, 8),
    (512, 384, 128, 8),
    (384, 512, 256, 2),
    (128, 640, 32, 16),
]


@pytest.mark.parametrize("m,n,r,k", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sddmm_matches_oracle(m, n, r, k, dtype):
    S, A, B, Sd = make_problem(m, n, r, k, seed=m + r, dtype=dtype)
    got = ops.sddmm(A, B, S).to_dense().astype(jnp.float32)
    want = ref.sddmm_dense(A.astype(jnp.float32), B.astype(jnp.float32), Sd)
    tol = 2e-5 if dtype == jnp.float32 else 0.12 * np.sqrt(r) / 8
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("m,n,r,k", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmm_matches_oracle(m, n, r, k, dtype):
    S, A, B, Sd = make_problem(m, n, r, k, seed=2 * m + r, dtype=dtype)
    got = ops.spmm(S, B).astype(jnp.float32)
    want = Sd @ B.astype(jnp.float32)
    tol = 2e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("m,n,r,k", SHAPES[:3])
def test_fusedmm_matches_composition(m, n, r, k):
    S, A, B, Sd = make_problem(m, n, r, k, seed=3 * m + r)
    got_out, got_R = ops.fusedmm(A, B, S)
    # fused == explicit SDDMM followed by explicit SpMM
    R2 = ops.sddmm(A, B, S)
    out2 = ops.spmm(R2, B)
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(out2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_R.vals), np.asarray(R2.vals),
                               rtol=2e-5, atol=2e-5)
    # ... and matches the dense oracle
    want_out, _ = ref.fusedmm_dense(A, B, Sd)
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(want_out),
                               rtol=2e-3, atol=2e-3)


def test_spmmb_via_transpose_pack():
    """SpMMB(S, A) == SpMMA(S^T, A): the paper stores a transposed copy."""
    m, n, r = 256, 384, 64
    rng = np.random.default_rng(7)
    rows, cols, vals = sparse.erdos_renyi(m, n, 6, seed=7)
    St = sparse.pack_row_tiled(cols, rows, vals, (n, m), row_tile=128,
                               nz_block=64)
    A = jnp.asarray(rng.standard_normal((m, r)), jnp.float32)
    Sd = np.zeros((m, n), np.float32)
    Sd[rows, cols] = vals
    got = ops.spmm(St, A)
    np.testing.assert_allclose(np.asarray(got), Sd.T @ np.asarray(A),
                               rtol=2e-4, atol=2e-4)


def test_empty_rows_are_zero():
    """Row tiles with no nonzeros must produce exact zeros."""
    m, n, r = 512, 128, 64
    rows = np.array([0, 1, 2], np.int32)       # only tile 0 touched
    cols = np.array([5, 6, 7], np.int32)
    vals = np.ones(3, np.float32)
    S = sparse.pack_row_tiled(rows, cols, vals, (m, n), row_tile=128,
                              nz_block=64)
    B = jnp.ones((n, r), jnp.float32)
    out = np.asarray(ops.spmm(S, B))
    assert np.all(out[128:] == 0.0)
    assert np.all(out[:3] == 1.0)


# ---------------------------------------------------------------------------
# Property-based tests (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**30),
       m=st.sampled_from([128, 256]),
       n=st.sampled_from([128, 256]),
       r=st.sampled_from([32, 64, 128]),
       k=st.integers(1, 12))
def test_property_sddmm_equals_masked_gemm(seed, m, n, r, k):
    S, A, B, Sd = make_problem(m, n, r, k, seed=seed)
    got = ops.sddmm(A, B, S).to_dense()
    want = Sd * (A @ B.T)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-5, atol=5e-5)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**30), alpha=st.floats(-2, 2))
def test_property_spmm_linearity(seed, alpha):
    """SpMM(alpha*S, B) == alpha * SpMM(S, B) (linearity in values)."""
    S, A, B, Sd = make_problem(256, 128, 64, 4, seed=seed)
    lhs = ops.spmm(S.with_vals(S.vals * alpha), B)
    rhs = alpha * ops.spmm(S, B)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**30))
def test_property_sddmm_mask_idempotent(seed):
    """SDDMM with vals=1 then re-sample == same sample values scaled."""
    S, A, B, Sd = make_problem(128, 128, 32, 4, seed=seed)
    ones = S.with_vals(jnp.where(S.vals != 0, 1.0, 0.0).astype(jnp.float32))
    R1 = ops.sddmm(A, B, ones)
    R2 = ops.sddmm(A, B, R1)  # samples (A B^T) again, scaled by R1
    want = np.asarray(R1.vals) ** 2 / np.where(np.asarray(ones.vals) == 0, 1,
                                               np.asarray(ones.vals))
    np.testing.assert_allclose(np.asarray(R2.vals), want, rtol=2e-4,
                               atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**30))
def test_property_fused_equals_unfused(seed):
    S, A, B, Sd = make_problem(256, 256, 64, 6, seed=seed)
    fused_out, fused_R = ops.fusedmm(A, B, S)
    R = ops.sddmm(A, B, S)
    unfused = ops.spmm(R, B)
    np.testing.assert_allclose(np.asarray(fused_out), np.asarray(unfused),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# VMEM tiling knobs: r_tile / blocks_per_step (see DESIGN.md)
# ---------------------------------------------------------------------------

TILINGS = [  # (r_tile, blocks_per_step) against r=128 packs with group=4
    (128, 1),   # whole-r residency, single block per step (baseline)
    (64, 1),    # r tiled into 2 VMEM slabs
    (32, 2),    # 4 slabs x 2-block steps
    (32, 4),    # 4 slabs x 4-block steps
]


def make_tiled_problem(m, n, k, seed, dtype, r=128):
    rng = np.random.default_rng(seed)
    rows, cols, vals = sparse.erdos_renyi(m, n, k, seed=seed)
    S = sparse.pack_row_tiled(rows, cols, vals, (m, n), row_tile=64,
                              nz_block=32, group=4)
    A = jnp.asarray(rng.standard_normal((m, r)), dtype)
    B = jnp.asarray(rng.standard_normal((n, r)), dtype)
    return S, A, B


@pytest.mark.parametrize("r_tile,bps", TILINGS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sddmm_tiling_equivalence(r_tile, bps, dtype):
    S, A, B = make_tiled_problem(256, 192, 6, seed=11, dtype=dtype)
    got = ops.sddmm(A, B, S, r_tile=r_tile, blocks_per_step=bps).vals
    want = ref.sddmm(A, B, S).vals
    tol = 2e-4 if dtype == jnp.float32 else 0.12 * np.sqrt(128) / 8
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("r_tile,bps", TILINGS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmm_tiling_equivalence(r_tile, bps, dtype):
    S, A, B = make_tiled_problem(256, 192, 6, seed=13, dtype=dtype)
    got = ops.spmm(S, B, r_tile=r_tile, blocks_per_step=bps)
    want = ref.spmm(S, B)
    tol = 2e-4 if dtype == jnp.float32 else 0.2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("r_tile,bps", TILINGS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fusedmm_tiling_equivalence(r_tile, bps, dtype):
    """Covers both fused paths: single-phase (r_tile==r) and two-phase."""
    S, A, B = make_tiled_problem(256, 192, 6, seed=17, dtype=dtype)
    got_out, got_R = ops.fusedmm(A, B, S, r_tile=r_tile, blocks_per_step=bps)
    want_out, want_R = ref.fusedmm(A, B, S)
    tol = 2e-3 if dtype == jnp.float32 else 0.5
    np.testing.assert_allclose(np.asarray(got_out, np.float32),
                               np.asarray(want_out, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got_R.vals, np.float32),
                               np.asarray(want_R.vals, np.float32),
                               rtol=tol, atol=tol)


def test_grouped_pack_feasibility():
    """group=g packing must make every blocks_per_step dividing g legal."""
    from repro.core import costmodel
    rows, cols, vals = sparse.erdos_renyi(512, 256, 3, seed=5)
    S = sparse.pack_row_tiled(rows, cols, vals, (512, 256), row_tile=64,
                              nz_block=32, group=4)
    assert S.nblocks % 4 == 0
    tb = np.asarray(S.tile_base)
    for g in (2, 4):
        groups = tb.reshape(-1, g)
        assert (groups == groups[:, :1]).all()
    assert costmodel.groupable_blocks_per_step(tb, S.nz_block, cap=4) == 4
    # and the matrix survives the padding round-trip
    dense = np.zeros((512, 256), np.float32)
    dense[rows, cols] = vals
    np.testing.assert_array_equal(np.asarray(S.to_dense()), dense)


def test_choose_tiling_respects_vmem_budget():
    from repro.core import costmodel
    budget = 8 * 1024 * 1024
    t = costmodel.choose_tiling(r=8192, nb=64, k=256, row_tile=256,
                                vmem_budget=budget)
    assert 8192 % t.r_tile == 0 and t.r_tile < 8192
    assert t.r_tile % costmodel.LANES == 0      # whole-lane DMA slabs
    assert costmodel.kernel_vmem_bytes(
        r_tile=t.r_tile, chunk=costmodel.chunk_nnz(256),
        row_tile=256) <= budget
    # small problems keep full-r residency
    t2 = costmodel.choose_tiling(r=128, nb=8, k=32, row_tile=64)
    assert t2.r_tile == 128


def test_packer_roundtrip():
    """pack_row_tiled must preserve the matrix exactly."""
    rows, cols, vals = sparse.erdos_renyi(384, 256, 5, seed=3)
    S = sparse.pack_row_tiled(rows, cols, vals, (384, 256), row_tile=128,
                              nz_block=32)
    dense = np.zeros((384, 256), np.float32)
    dense[rows, cols] = vals
    np.testing.assert_array_equal(np.asarray(S.to_dense()), dense)
    # row-window invariant
    rg = np.asarray(S.rows_global())
    base = np.asarray(S.tile_base)[:, None]
    mask = np.asarray(S.vals) != 0
    assert np.all((rg >= base)[mask] & (rg < base + S.row_tile)[mask])
    # tile bases non-decreasing (Pallas revisit requirement)
    assert np.all(np.diff(np.asarray(S.tile_base)) >= 0)


# ---------------------------------------------------------------------------
# TPU-semantics interpreter: uninitialized VMEM reads as NaN, so a kernel
# that accumulates into a buffer it never initialized fails here on CPU
# ---------------------------------------------------------------------------

NAN_CASES = [  # (r, r_tile, blocks_per_step)
    (128, 128, 1),    # whole width, one block per step
    (256, 128, 1),    # two r slabs
    (128, 128, 2),    # two blocks per step
]


def _nan_problem(r, bps):
    """A 64x48 pack whose row window [16, 32) holds no nonzero."""
    rng = np.random.default_rng(r + bps)
    rows, cols, vals = sparse.erdos_renyi(64, 48, 4, seed=r + bps)
    keep = (rows < 16) | (rows >= 32)
    S = sparse.pack_row_tiled(rows[keep], cols[keep], vals[keep], (64, 48),
                              row_tile=16, nz_block=16, group=bps)
    A = jnp.asarray(rng.standard_normal((64, r)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((48, r)), jnp.float32)
    return S, A, B


@pytest.mark.parametrize("kernel", ["sddmm", "spmm", "fusedmm"])
@pytest.mark.parametrize("r,r_tile,bps", NAN_CASES)
def test_kernel_initializes_accumulators(kernel, r, r_tile, bps):
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.fusedmm import fusedmm_pallas
    from repro.kernels.sddmm import sddmm_pallas
    from repro.kernels.spmm import spmm_pallas

    S, A, B = _nan_problem(r, bps)
    pack = (S.tile_base, S.rows_local, S.cols, S.vals)
    kw = dict(row_tile=S.row_tile, r_tile=r_tile, blocks_per_step=bps,
              interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
    if kernel == "sddmm":
        got = [sddmm_pallas(*pack, A, B, **kw)]
        want = [ref.sddmm(A, B, S).vals]
    elif kernel == "spmm":
        got = [spmm_pallas(*pack, B, m=64, **kw)]
        want = [ref.spmm(S, B)]
    else:
        got = list(fusedmm_pallas(*pack, A, B, m=64, **kw))
        out, R = ref.fusedmm(A, B, S)
        want = [out, R.vals]
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, np.asarray(w), rtol=1e-4, atol=1e-4)
    if kernel != "sddmm":
        assert np.all(np.asarray(got[0])[16:32] == 0.0)   # empty window
