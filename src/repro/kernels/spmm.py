"""Pallas TPU kernel for blocked SpMM (S @ B) over RowTiledCOO.

The scatter-add of CPU/GPU SpMM is restructured as a one-hot matmul so it
runs on the MXU: per grid step the kernel DMAs the rows of B named by the
chunk's nonzeros out of HBM, and for each step of the chunk accumulates

    out_window += where(onehot(rows_local), vals, 0)  @  B[cols]
                   (row_tile x C)                        (C x r_tile)

Tiling (DESIGN.md §1-2): the grid is ``(r // r_tile, chunks)`` with the
chunk axis minor.  B, the window table and the output stay in HBM, so
VMEM holds only the ``(C, r_tile)`` gathered rows and one
``(row_tile, r_tile)`` accumulator, and SMEM only the chunk's column
ids and window bases.  Row-sorted packs visit each window in consecutive
steps: the accumulator is zeroed on entry and written back on exit, and
windows no block visits are written as zeros, so every output row is
written once per r slab.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.costmodel import chunk_nnz
from repro.kernels import dma


def _spmm_kernel(base_ref, cols_ref, rows_ref, vals_ref, b_hbm, out_hbm,
                 b_rows, acc, cur, sems, *, row_tile, n, slabs_per_row):
    j, i = pl.program_id(0), pl.program_id(1)
    q = acc.shape[0]

    def out_window(b):
        return dma.window(out_hbm, b, row_tile, j * q, acc)

    @pl.when(i == 0)
    def _sweep():
        cur[0] = -row_tile

    dma.gather_rows(b_hbm, cols_ref, b_rows, sems.at[1], j * q,
                    slabs_per_row)

    def step(base, hit):
        dma.enter_window(base, cur, acc, out_window, sems.at[0])
        dma.scatter_rows(acc, hit(rows_ref[...], row_tile), vals_ref[...],
                         b_rows)

    dma.for_each_step(base_ref, n, step)

    @pl.when(i == pl.num_programs(1) - 1)
    def _flush():
        dma.leave_last_window(cur, acc, out_window, sems.at[0],
                              out_hbm.shape[0])


@functools.partial(jax.jit,
                   static_argnames=("row_tile", "m", "r_tile",
                                    "blocks_per_step", "interpret"))
def spmm_pallas(tile_base: jax.Array, rows_local: jax.Array,
                cols: jax.Array, vals: jax.Array, B: jax.Array, *,
                row_tile: int, m: int, r_tile: int | None = None,
                blocks_per_step: int = 1, interpret=False) -> jax.Array:
    """Returns out (m, r) = S @ B accumulated in f32, cast to B.dtype.

    ``tile_base`` holds each block's first window row (non-decreasing).
    """
    nb, k = rows_local.shape
    r = B.shape[-1]
    bps = blocks_per_step
    r_tile = r if r_tile is None else r_tile
    assert m % row_tile == 0, (m, row_tile)
    assert r % r_tile == 0, (r, r_tile)
    assert nb % bps == 0, (nb, bps)
    n = bps * k
    rp, r_tile, (Bp,) = dma.lane_pad(r, r_tile, B.astype(jnp.float32))
    u = dma.slab_width(r_tile)
    chunked = dma.chunk_operands(tile_base, rows_local, cols, vals, bps)
    out = pl.pallas_call(
        functools.partial(_spmm_kernel, row_tile=row_tile, n=n,
                          slabs_per_row=rp // u),
        grid=(rp // r_tile, chunked[1].shape[0]),
        in_specs=dma.chunk_specs(n, lambda j, i: i) + [dma.HBM],
        out_specs=dma.HBM,
        out_shape=jax.ShapeDtypeStruct((m, rp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((r_tile // u, chunk_nnz(n), u), jnp.float32),  # B
            pltpu.VMEM((r_tile // u, row_tile, u), jnp.float32),  # window
            pltpu.SMEM((1,), jnp.int32),                          # its base
            pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*chunked, Bp.reshape(-1, u))
    return out[:, :r].astype(B.dtype)
