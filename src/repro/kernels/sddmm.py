"""Pallas TPU kernel for blocked SDDMM over RowTiledCOO.

TPU adaptation (DESIGN.md §1-2): nonzeros are pre-sorted by row and
chunked into blocks of ``nz_block`` entries confined to a
``row_tile``-row window of A.  The grid is ``(chunks, r // r_tile)``
with the slab axis minor.  Per grid step the kernel DMAs the rows of B
named by the chunk's nonzeros out of HBM, and for each step of the chunk
its ``(row_tile, r_tile)`` window of A; ``A_window @ B_rows.T`` on the
MXU, masked by the step's one-hot, gives the slab's partial sampled
dots.  The chunk's ``(1, C)`` output block stays resident across the
consecutive slab steps: it is zeroed at slab 0 and accumulates in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.costmodel import chunk_nnz
from repro.kernels import dma


def _sddmm_kernel(base_ref, cols_ref, rows_ref, vals_ref, a_hbm, b_hbm,
                  out_ref, b_rows, a_win, cur, sems, *, row_tile, n,
                  slabs_per_row):
    j = pl.program_id(1)
    q = a_win.shape[0]

    @pl.when(j == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    dma.gather_rows(b_hbm, cols_ref, b_rows, sems.at[1], j * q,
                    slabs_per_row)
    cur[0] = -1

    def step(base, hit):
        @pl.when(base != cur[0])
        def _load():
            dma.copy(dma.window(a_hbm, base, row_tile, j * q, a_win), a_win,
                     sems.at[0])
            cur[0] = base

        out_ref[...] += vals_ref[...] * dma.window_dots(
            hit(rows_ref[...], row_tile), a_win, b_rows)

    dma.for_each_step(base_ref, n, step)


@functools.partial(jax.jit,
                   static_argnames=("row_tile", "r_tile", "blocks_per_step",
                                    "interpret"))
def sddmm_pallas(tile_base: jax.Array, rows_local: jax.Array,
                 cols: jax.Array, vals: jax.Array, A: jax.Array,
                 B: jax.Array, *, row_tile: int, r_tile: int | None = None,
                 blocks_per_step: int = 1, interpret=False) -> jax.Array:
    """Returns new sampled values, shape (nblocks, nz_block).

    ``tile_base`` holds each block's first window row.
    """
    nb, k = rows_local.shape
    r = A.shape[-1]
    bps = blocks_per_step
    r_tile = r if r_tile is None else r_tile
    assert r % r_tile == 0, (r, r_tile)
    assert nb % bps == 0, (nb, bps)
    n = bps * k
    c = chunk_nnz(n)
    rp, r_tile, (Ap, Bp) = dma.lane_pad(r, r_tile, A.astype(jnp.float32),
                                        B.astype(jnp.float32))
    u = dma.slab_width(r_tile)
    chunked = dma.chunk_operands(tile_base, rows_local, cols, vals, bps)
    chunks = chunked[1].shape[0]
    out = pl.pallas_call(
        functools.partial(_sddmm_kernel, row_tile=row_tile, n=n,
                          slabs_per_row=rp // u),
        grid=(chunks, rp // r_tile),
        in_specs=dma.chunk_specs(n, lambda i, j: i) + [dma.HBM, dma.HBM],
        out_specs=pl.BlockSpec((None, 1, c), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((chunks, 1, c), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((r_tile // u, c, u), jnp.float32),         # B rows
            pltpu.VMEM((r_tile // u, row_tile, u), jnp.float32),  # A window
            pltpu.SMEM((1,), jnp.int32),                          # its base
            pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*chunked, Ap, Bp.reshape(-1, u))
    return dma.unchunk(out, nb, k).astype(vals.dtype)
