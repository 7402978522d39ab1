"""Public jit'd wrappers over the Pallas kernels with ref fallback.

``backend="pallas"`` runs the Pallas kernels (compiled on TPU, interpret
mode on the CPU backend, refused on any other); ``backend="ref"`` uses the
pure-jnp oracles.  The distributed algorithms in ``repro.core`` call these
for every local kernel invocation, so flipping the backend flips the whole
system.

Tiling knobs (see DESIGN.md): every wrapper accepts ``r_tile`` (width of
the embedding slab a grid step moves through VMEM) and ``blocks_per_step``
(nonzero blocks merged per grid step).  When left ``None`` they default via
``costmodel.choose_tiling`` — VMEM-budget-driven for ``r_tile``; pack-stat-
driven for ``blocks_per_step`` when the pack structure is concrete (inside
jit-traced callers the structure is abstract, so the default stays 1 and
planners pass explicit values chosen at plan time).
"""
from __future__ import annotations

import jax

from repro.core import costmodel
from repro.core.sparse import RowTiledCOO
from repro.kernels import ref as _ref
from repro.kernels.sddmm import sddmm_pallas
from repro.kernels.spmm import spmm_pallas
from repro.kernels.fusedmm import fusedmm_pallas

_DEFAULT_BACKEND = "pallas"

# Distributed routing hook, set by `repro.core.api.activate(problem, S)`:
# while a mesh-bound DistProblem is active, eager calls on its registered
# pack run the distributed algorithm instead of the local kernel.  The
# router returns NotImplemented for anything it does not own (other
# packs, traced values, mismatched shapes), which falls through to the
# local path unchanged.  An explicit ``backend=`` argument always wins
# over routing, preserving the ref-oracle escape hatch.
_DIST_ROUTER = None


def _interpret() -> bool:
    """Compile natively on TPU, interpret on CPU, refuse anything else."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"no Pallas kernels for backend {backend!r}; "
                           f"use backend='ref'")
    return backend == "cpu"


def set_default_backend(backend: str) -> None:
    global _DEFAULT_BACKEND
    assert backend in ("pallas", "ref")
    _DEFAULT_BACKEND = backend


def _resolve_tiling(S: RowTiledCOO, r: int,
                    r_tile: int | None, blocks_per_step: int | None):
    """Fill unset knobs from the cost model; never inspects traced data."""
    concrete = not isinstance(S.tile_base, jax.core.Tracer)
    derived_bps = False
    if r_tile is None or blocks_per_step is None:
        t = costmodel.choose_tiling(
            r=r, nb=S.nblocks, k=S.nz_block, row_tile=S.row_tile,
            tile_base=S.tile_base if concrete else None)
        if r_tile is None:
            r_tile = t.r_tile
        if blocks_per_step is None:
            blocks_per_step = t.blocks_per_step
            derived_bps = True   # choose_tiling already proved feasibility
    if blocks_per_step > 1 and concrete and not derived_bps:
        # merging blocks is only sound when every aligned group shares one
        # row window — a silently wrong answer otherwise, so refuse here.
        # (Traced packs can't be checked; planners validate at plan time.)
        feasible = costmodel.groupable_blocks_per_step(
            S.tile_base, S.nz_block, cap=blocks_per_step)
        if S.nblocks % blocks_per_step or feasible % blocks_per_step:
            raise ValueError(
                f"blocks_per_step={blocks_per_step} infeasible for this "
                f"pack (nblocks={S.nblocks}, largest groupable step "
                f"{feasible}); repack with pack_row_tiled(..., "
                f"group={blocks_per_step})")
    return r_tile, blocks_per_step


def sddmm(A: jax.Array, B: jax.Array, S: RowTiledCOO,
          backend: str | None = None, *, r_tile: int | None = None,
          blocks_per_step: int | None = None) -> RowTiledCOO:
    """R = S * (A @ B.T) sampled at nnz(S); returns S with new values."""
    if _DIST_ROUTER is not None and backend is None:
        routed = _DIST_ROUTER.sddmm(A, B, S)
        if routed is not NotImplemented:
            return routed
    backend = backend or _DEFAULT_BACKEND
    if backend == "ref":
        return _ref.sddmm(A, B, S)
    r_tile, bps = _resolve_tiling(S, B.shape[-1],
                                  r_tile, blocks_per_step)
    vals = sddmm_pallas(S.tile_base, S.rows_local, S.cols,
                        S.vals, A, B, row_tile=S.row_tile, r_tile=r_tile,
                        blocks_per_step=bps, interpret=_interpret())
    return S.with_vals(vals)


def spmm(S: RowTiledCOO, B: jax.Array, m: int | None = None,
         backend: str | None = None, *, r_tile: int | None = None,
         blocks_per_step: int | None = None) -> jax.Array:
    """out = S @ B (shape (m, r))."""
    m = m if m is not None else S.shape[0]
    if _DIST_ROUTER is not None and backend is None:
        routed = _DIST_ROUTER.spmm(S, B, m)
        if routed is not NotImplemented:
            return routed
    backend = backend or _DEFAULT_BACKEND
    if backend == "ref":
        return _ref.spmm(S, B, m)
    r_tile, bps = _resolve_tiling(S, B.shape[-1],
                                  r_tile, blocks_per_step)
    return spmm_pallas(S.tile_base, S.rows_local, S.cols,
                       S.vals, B, row_tile=S.row_tile, m=m, r_tile=r_tile,
                       blocks_per_step=bps, interpret=_interpret())


def fusedmm(A: jax.Array, B: jax.Array, S: RowTiledCOO,
            m: int | None = None, backend: str | None = None, *,
            r_tile: int | None = None, blocks_per_step: int | None = None):
    """FusedMMA: out = SDDMM(A,B,S) @ B; returns (out, R)."""
    m = m if m is not None else S.shape[0]
    if _DIST_ROUTER is not None and backend is None:
        routed = _DIST_ROUTER.fusedmm(A, B, S, m)
        if routed is not NotImplemented:
            return routed
    backend = backend or _DEFAULT_BACKEND
    if backend == "ref":
        return _ref.fusedmm(A, B, S, m)
    r_tile, bps = _resolve_tiling(S, B.shape[-1],
                                  r_tile, blocks_per_step)
    out, r_vals = fusedmm_pallas(S.tile_base, S.rows_local,
                                 S.cols, S.vals, A, B,
                                 row_tile=S.row_tile, m=m, r_tile=r_tile,
                                 blocks_per_step=bps,
                                 interpret=_interpret())
    return out, S.with_vals(r_vals)
