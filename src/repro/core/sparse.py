"""Static-shape sparse formats for TPU-friendly SDDMM / SpMM / FusedMM.

XLA requires static shapes, so every distributed block of the sparse matrix
``S`` is packed to a fixed nonzero capacity.  Padding entries carry
``val = 0`` and point at row/col 0, so:

  * SpMM contributions from padding vanish (0 * B[0] adds nothing),
  * SDDMM outputs at padding are 0 (sample value multiplies the dot).

Two layouts:

``PaddedCOO``      -- flat (rows, cols, vals) triple, 3 words per nonzero,
                      exactly the paper's COO cyclic-shift payload.
``RowTiledCOO``    -- PaddedCOO additionally sorted by row and chunked into
                      nonzero blocks of ``nz_block`` entries whose rows all
                      fall inside one ``row_tile``-row window.  This is the
                      TPU adaptation: it lets the local SpMM kernel turn
                      scatter-add into a one-hot matmul on the MXU.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PaddedCOO:
    """A fixed-capacity COO block of an (m x n) sparse matrix."""

    rows: jax.Array  # int32[cap]
    cols: jax.Array  # int32[cap]
    vals: jax.Array  # float[cap]  (0.0 at padding)
    shape: Tuple[int, int] = dataclasses.field(metadata=dict(static=True))

    @property
    def capacity(self) -> int:
        return self.rows.shape[0]

    def to_dense(self) -> jax.Array:
        out = jnp.zeros(self.shape, self.vals.dtype)
        return out.at[self.rows, self.cols].add(self.vals)

    def with_vals(self, vals: jax.Array) -> "PaddedCOO":
        return PaddedCOO(self.rows, self.cols, vals, self.shape)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowTiledCOO:
    """Row-sorted, tile-aligned COO for the one-hot-matmul local kernels.

    Nonzeros are sorted by row and split into blocks of ``nz_block``
    entries.  Block ``b`` only touches rows in
    ``[tile_base[b], tile_base[b] + row_tile)``; ``rows_local`` stores the
    offset within that window.  Padding entries have ``vals == 0`` and
    ``rows_local == 0``.
    """

    rows_local: jax.Array  # int32[nblocks, nz_block] in [0, row_tile)
    cols: jax.Array        # int32[nblocks, nz_block]
    vals: jax.Array        # float[nblocks, nz_block]
    tile_base: jax.Array   # int32[nblocks] multiples of row_tile
    shape: Tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    row_tile: int = dataclasses.field(metadata=dict(static=True))

    @property
    def nblocks(self) -> int:
        return self.rows_local.shape[0]

    @property
    def nz_block(self) -> int:
        return self.rows_local.shape[1]

    def rows_global(self) -> jax.Array:
        return self.rows_local + self.tile_base[:, None]

    def to_dense(self) -> jax.Array:
        out = jnp.zeros(self.shape, self.vals.dtype)
        return out.at[self.rows_global().reshape(-1),
                      self.cols.reshape(-1)].add(self.vals.reshape(-1))

    def with_vals(self, vals: jax.Array) -> "RowTiledCOO":
        return RowTiledCOO(self.rows_local, self.cols, vals,
                           self.tile_base, self.shape, self.row_tile)

    def to_padded_coo(self) -> PaddedCOO:
        return PaddedCOO(self.rows_global().reshape(-1),
                         self.cols.reshape(-1),
                         self.vals.reshape(-1), self.shape)


# ---------------------------------------------------------------------------
# Packing (numpy, amortized preprocessing -- mirrors the paper's reorder step)
# ---------------------------------------------------------------------------

def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def pack_coo(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
             shape: Tuple[int, int], capacity: int | None = None,
             pad_multiple: int = 8) -> PaddedCOO:
    """Pack raw COO triplets into a PaddedCOO with static capacity."""
    nnz = int(rows.shape[0])
    cap = capacity if capacity is not None else _round_up(max(nnz, 1), pad_multiple)
    if nnz > cap:
        raise ValueError(f"nnz={nnz} exceeds capacity={cap}")
    r = np.zeros(cap, np.int32)
    c = np.zeros(cap, np.int32)
    v = np.zeros(cap, np.float32)
    r[:nnz], c[:nnz], v[:nnz] = rows, cols, vals
    return PaddedCOO(jnp.asarray(r), jnp.asarray(c), jnp.asarray(v), shape)


def pack_row_tiled(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                   shape: Tuple[int, int], *, row_tile: int = 256,
                   nz_block: int = 256, nblocks: int | None = None,
                   group: int = 1) -> RowTiledCOO:
    """Sort by row, then emit nz blocks confined to row_tile windows.

    A block is flushed (padded) whenever it fills up or the next nonzero
    falls outside the current row window.  Window boundaries are aligned to
    multiples of ``row_tile`` so ``tile_base`` can double as a BlockSpec
    index.

    ``group > 1`` pads every window's run of blocks (and the total block
    count) to a multiple of ``group``, so the kernels may merge any
    ``blocks_per_step`` dividing ``group`` — each aligned group then shares
    one ``tile_base`` window (the precondition checked by
    ``costmodel.groupable_blocks_per_step``).
    """
    # clamp to the largest divisor of the row count (kernel window blocking
    # requires row_tile | m)
    row_tile = min(row_tile, shape[0])
    while shape[0] % row_tile:
        row_tile -= 1
    rows, cols, vals = np.asarray(rows), np.asarray(cols), np.asarray(vals)
    key = rows.astype(np.int64) * shape[1] + cols
    if np.any(key[1:] < key[:-1]):    # generators already emit row-major
        order = np.argsort(key, kind="stable")
        rows, cols, vals = rows[order], cols[order], vals[order]
    del key
    rows = rows.astype(np.int64)

    # one pass, vectorized: every nonzero's window, its rank inside the
    # window, and from those its (block, slot); each window's run of
    # blocks is padded to a multiple of ``group``
    win = rows // row_tile
    first = np.flatnonzero(np.diff(win, prepend=-1))   # windows are sorted
    wins = win[first]
    count = np.diff(first, append=len(win))
    run = -(-count // nz_block)
    run = -(-run // group) * group
    start = np.cumsum(run) - run                    # first block per window
    # flat slot of every nonzero: its window's first slot plus its rank
    shift = np.repeat((start * nz_block - first), count)
    flat = np.arange(len(rows)) + shift
    nb = int(run.sum())
    target = nblocks if nblocks is not None else max(nb, 1)
    target = _round_up(target, group)
    if nb > target:
        raise ValueError(f"needs {nb} blocks > target {target}")
    # Padding blocks inherit the last real base so the sequence of output
    # tiles stays non-decreasing (the kernels visit windows in order).
    bases = np.repeat(wins * row_tile, run).astype(np.int32)
    pad_base = bases[-1] if nb else 0
    bases = np.concatenate(
        [bases, np.full(target - nb, pad_base, np.int32)])
    rl = np.zeros((target, nz_block), np.int32)
    cl = np.zeros((target, nz_block), np.int32)
    vl = np.zeros((target, nz_block), np.float32)
    rl.reshape(-1)[flat] = rows - win * row_tile
    cl.reshape(-1)[flat] = cols
    vl.reshape(-1)[flat] = vals
    return RowTiledCOO(jnp.asarray(rl), jnp.asarray(cl), jnp.asarray(vl),
                       jnp.asarray(bases), shape, row_tile)


# ---------------------------------------------------------------------------
# Random sparse matrix generators (paper's workloads)
# ---------------------------------------------------------------------------

def erdos_renyi(m: int, n: int, nnz_per_row: int, seed: int = 0,
                dtype=np.float32):
    """Erdos-Renyi random sparse matrix, ~nnz_per_row nonzeros per row.

    Matches the paper's weak-scaling generator (CombBLAS ER): each row draws
    ``nnz_per_row`` columns uniformly (with possible duplicates removed).
    Returns (rows, cols, vals) numpy COO, deduplicated & sorted.
    """
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(m, dtype=np.int64), nnz_per_row)
    cols = rng.integers(0, n, size=rows.shape[0], dtype=np.int64)
    key = rows * n + cols
    key = np.unique(key)
    rows = (key // n).astype(np.int32)
    cols = (key % n).astype(np.int32)
    vals = rng.standard_normal(rows.shape[0]).astype(dtype)
    return rows, cols, vals


def random_problem(m: int, n: int, r: int, nnz_per_row: int, *,
                   seed: int = 0, scale: float = 1.0):
    """One seeded (rows, cols, vals, X, Y) problem bundle.

    The Erdos-Renyi sparse matrix plus matching dense operands
    ``X (m, r)`` / ``Y (n, r)`` in float32 — the setup every benchmark,
    test and dist_script needs.  Deterministic in ``seed`` alone (the
    dense operands draw from ``seed + 1``, preserving the historical
    streams of ``benchmarks/common.er_problem``), so two call sites with
    the same arguments see the same problem.  ``scale`` shrinks the
    dense entries for iterative-solver initializations.
    """
    rows, cols, vals = erdos_renyi(m, n, nnz_per_row, seed=seed)
    rng = np.random.default_rng(seed + 1)
    X = (rng.standard_normal((m, r)) * scale).astype(np.float32)
    Y = (rng.standard_normal((n, r)) * scale).astype(np.float32)
    return rows, cols, vals, X, Y


_RMAT_CHUNK = 1 << 20       # edges one thread generates at a time


def rmat(scale: int, edge_factor: int = 16, seed: int = 0,
         a: float = 0.57, b: float = 0.19, c: float = 0.19,
         dtype=np.float32):
    """RMAT power-law generator — surrogate for SuiteSparse web/social graphs.

    Level ``l`` of edge ``e`` draws the ``l * ne + e``-th uniform of
    ``default_rng(seed)``; threads fill chunks of edges from copies of
    the stream advanced to their offsets, so the graph depends on the
    seed alone.
    """
    n = 1 << scale
    ne = n * edge_factor
    rows = np.zeros(ne, np.int64)
    cols = np.zeros(ne, np.int64)

    def stream(offset):
        bits = np.random.PCG64(seed)
        bits.advance(offset)
        return np.random.Generator(bits)

    def fill(lo):
        hi = min(lo + _RMAT_CHUNK, ne)
        for lvl in range(scale):
            r = stream(lvl * ne + lo).random(hi - lo)
            # quadrant q = how many of a, a+b, a+b+c are <= r: q is 1 or 3
            # for the row high bit (down), 2 or 3 for the column's (right)
            q = ((r >= a).view(np.uint8) + (r >= a + b).view(np.uint8)
                 + (r >= a + b + c).view(np.uint8))
            rows[lo:hi] |= np.left_shift(q & 1, lvl, dtype=np.int64)
            cols[lo:hi] |= np.left_shift(q >> 1, lvl, dtype=np.int64)

    with concurrent.futures.ThreadPoolExecutor(os.cpu_count()) as pool:
        list(pool.map(fill, range(0, ne, _RMAT_CHUNK)))
    key = np.unique(rows * n + cols)
    rng = stream(scale * ne)
    rows = (key // n).astype(np.int32)
    cols = (key % n).astype(np.int32)
    vals = rng.standard_normal(rows.shape[0]).astype(dtype)
    return rows, cols, vals


def powerlaw_problem(scale: int, r: int, *, edge_factor: int = 16,
                     seed: int = 0, a: float = 0.57, b: float = 0.19,
                     c: float = 0.19):
    """One seeded power-law (rows, cols, vals, X, Y) problem bundle.

    The RMAT surrogate for the paper's headline web/social matrices
    (m = n = 2**scale), unpermuted so the degree skew — many empty or
    near-empty rows and columns, a few dense hubs — survives into the
    per-device packs.  This is the regime where ``comm="sparse"``
    support pruning beats the dense Table-III optimum outright: the
    row/col supports cover only a fraction of each fiber slab.  Same
    bundle contract as :func:`random_problem` (dense operands draw from
    ``seed + 1``), so benchmarks and dist_scripts can swap generators.
    """
    rows, cols, vals = rmat(scale, edge_factor, seed=seed, a=a, b=b, c=c)
    m = n = 1 << scale
    rng = np.random.default_rng(seed + 1)
    X = rng.standard_normal((m, r)).astype(np.float32)
    Y = rng.standard_normal((n, r)).astype(np.float32)
    return rows, cols, vals, X, Y


def random_permute(rows: np.ndarray, cols: np.ndarray, m: int, n: int,
                   seed: int = 0):
    """Random row+col permutation for load balance (paper §VI)."""
    rng = np.random.default_rng(seed)
    pr = rng.permutation(m).astype(np.int32)
    pc = rng.permutation(n).astype(np.int32)
    return pr[rows], pc[cols]


def block_sparse_mask(seq: int, block: int, window_blocks: int,
                      global_blocks: int = 1):
    """Block-sparse attention mask (sliding window + global) as COO blocks.

    Returns (rows, cols) of *block* indices for a lower-triangular
    sliding-window + global-token pattern over seq/block block rows.
    Used by the block-sparse FusedMM attention path.
    """
    nb = seq // block
    rows, cols = [], []
    for i in range(nb):
        lo = max(0, i - window_blocks + 1)
        for j in range(lo, i + 1):
            rows.append(i); cols.append(j)
        for j in range(min(global_blocks, lo)):
            rows.append(i); cols.append(j)
    return np.asarray(rows, np.int32), np.asarray(cols, np.int32)
