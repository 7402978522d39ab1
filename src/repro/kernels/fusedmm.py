"""Pallas TPU kernel for the local FusedMM (SDDMM + SpMM, fused).

This is the paper's "local kernel fusion" primitive [11] adapted to TPU:
for each nonzero the sampled dot product is computed and the scaled row
of B aggregated into the output window from *one* DMA of that row — the
intermediate R never travels to HBM between two kernels.  The sampled
values are still emitted (a ``(1, C)`` block per chunk) because
applications such as GAT attention need them.

    dots   = rowsum(A[rows] * B[cols])                  (MXU, masked)
    coeff  = vals * dots
    out   += where(onehot(rows_local), coeff, 0) @ B[cols]   (MXU)

Tiling (DESIGN.md §1-2): with ``r_tile == r`` one grid axis sweeps the
chunks, holding ``(C, r)`` gathered rows, one A window and one output
window in VMEM.  The SDDMM coefficient needs the whole width before any
SpMM contribution, so for ``r_tile < r`` the kernel is the SDDMM kernel
followed by the SpMM kernel: R round-trips through HBM once, 3 words per
nonzero against the dense slab traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.costmodel import chunk_nnz
from repro.kernels import dma
from repro.kernels.sddmm import sddmm_pallas
from repro.kernels.spmm import spmm_pallas


def _fusedmm_kernel(base_ref, cols_ref, rows_ref, vals_ref, a_hbm, b_hbm,
                    out_hbm, rv_ref, b_rows, a_win, acc, cur, sems,
                    *, row_tile, n, slabs_per_row):
    i = pl.program_id(0)

    def out_window(b):
        return dma.window(out_hbm, b, row_tile, 0, acc)

    @pl.when(i == 0)
    def _sweep():
        cur[0] = -row_tile

    dma.gather_rows(b_hbm, cols_ref, b_rows, sems.at[1], 0, slabs_per_row)
    rv_ref[...] = jnp.zeros_like(rv_ref)

    def step(base, hit):
        def load_a():
            dma.copy(dma.window(a_hbm, base, row_tile, 0, a_win), a_win,
                     sems.at[0])

        dma.enter_window(base, cur, acc, out_window, sems.at[0], load_a)
        h = hit(rows_ref[...], row_tile)
        coeff = vals_ref[...] * dma.window_dots(h, a_win, b_rows)
        rv_ref[...] += coeff
        dma.scatter_rows(acc, h, coeff, b_rows)

    dma.for_each_step(base_ref, n, step)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        dma.leave_last_window(cur, acc, out_window, sems.at[0],
                              out_hbm.shape[0])


@functools.partial(jax.jit,
                   static_argnames=("row_tile", "m", "r_tile",
                                    "blocks_per_step", "interpret"))
def fusedmm_pallas(tile_base: jax.Array, rows_local: jax.Array,
                   cols: jax.Array, vals: jax.Array, A: jax.Array,
                   B: jax.Array, *, row_tile: int, m: int,
                   r_tile: int | None = None, blocks_per_step: int = 1,
                   interpret=False):
    """Returns (out (m,r) f32->B.dtype, r_vals (nblocks, nz_block))."""
    nb, k = rows_local.shape
    r = B.shape[-1]
    bps = blocks_per_step
    r_tile = r if r_tile is None else r_tile
    assert m % row_tile == 0, (m, row_tile)
    assert r % r_tile == 0, (r, r_tile)
    assert nb % bps == 0, (nb, bps)
    tiling = dict(row_tile=row_tile, r_tile=r_tile, blocks_per_step=bps,
                  interpret=interpret)
    if r_tile < r:
        r_vals = sddmm_pallas(tile_base, rows_local, cols, vals, A, B,
                              **tiling)
        return spmm_pallas(tile_base, rows_local, cols, r_vals, B, m=m,
                           **tiling), r_vals

    n = bps * k
    c = chunk_nnz(n)
    rp, _, (Ap, Bp) = dma.lane_pad(r, r, A.astype(jnp.float32),
                                   B.astype(jnp.float32))
    u = dma.slab_width(rp)
    chunked = dma.chunk_operands(tile_base, rows_local, cols, vals, bps)
    chunks = chunked[1].shape[0]
    out, r_vals = pl.pallas_call(
        functools.partial(_fusedmm_kernel, row_tile=row_tile, n=n,
                          slabs_per_row=rp // u),
        grid=(chunks,),
        in_specs=dma.chunk_specs(n, lambda i: i) + [dma.HBM] * 2,
        out_specs=[dma.HBM,
                   pl.BlockSpec((None, 1, c), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((m, rp), jnp.float32),
                   jax.ShapeDtypeStruct((chunks, 1, c), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((rp // u, c, u), jnp.float32),             # B rows
            pltpu.VMEM((rp // u, row_tile, u), jnp.float32),      # A window
            pltpu.VMEM((rp // u, row_tile, u), jnp.float32),      # out window
            pltpu.SMEM((1,), jnp.int32),                          # its base
            pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*chunked, Ap, Bp.reshape(-1, u))
    return (out[:, :r].astype(B.dtype),
            dma.unchunk(r_vals, nb, k).astype(vals.dtype))
