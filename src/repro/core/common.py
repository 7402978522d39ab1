"""Shared planner utilities for the distributed sparse algorithms.

Planners run once on the host (numpy) — the analogue of the paper's
amortized preprocessing — and produce static-shape, device-placed pytrees
that the jitted shard_map executors consume repeatedly.

Two planner-level decisions feed the VMEM-tiled kernels (see DESIGN.md):

* packs are padded per *phase* (1.5D dense-shifting) or per *device*
  (traveling packs) rather than to one global ``nbmax``, so a phase with
  few nonzero blocks no longer pays for the densest phase;
* each pack carries a static :class:`repro.core.costmodel.Tiling`
  (``r_tile``/``blocks_per_step``) chosen at plan time from the concrete
  block structure, which the executors thread into every local kernel call.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import costmodel
from repro.core.sparse import RowTiledCOO, pack_row_tiled

def extract_block(rows, cols, vals, r0, r1, c0, c1):
    """Nonzeros of S falling in the [r0,r1) x [c0,c1) block, rebased."""
    msk = (rows >= r0) & (rows < r1) & (cols >= c0) & (cols < c1)
    return rows[msk] - r0, cols[msk] - c0, vals[msk]


def block_partition(rows, cols, vals, row_size, col_size, n_col_blocks):
    """Group nonzeros by (row-block, col-block) in one O(nnz log nnz) pass.

    Returns {(bu, bj): (rows_rebased, cols_rebased, vals)}.  Replaces
    per-block full-array masking, which is O(nnz * blocks) — prohibitive
    for production-scale planning (millions of nnz x thousands of blocks).
    """
    bid = (rows // row_size).astype(np.int64) * n_col_blocks \
        + (cols // col_size)
    order = np.argsort(bid, kind="stable")
    rows, cols, vals, bid = (rows[order], cols[order], vals[order],
                             bid[order])
    uniq, starts = np.unique(bid, return_index=True)
    ends = np.append(starts[1:], len(bid))
    out = {}
    for u, s, e in zip(uniq, starts, ends):
        bu, bj = int(u) // n_col_blocks, int(u) % n_col_blocks
        out[(bu, bj)] = (rows[s:e] - bu * row_size,
                         cols[s:e] - bj * col_size, vals[s:e])
    return out


def pack_block_list(blocks, shape, row_tile, nz_block, group: int = 1):
    """Pack a list of COO blocks to RowTiled arrays with a common nblocks.

    blocks: list of (rows, cols, vals) numpy triples, all logical `shape`.
    The common block count is the max over *this list only* — callers that
    used to stack every phase into one array now call this once per phase,
    so each phase is padded to its own densest device, not the global max.
    Returns stacked numpy arrays (N, nb, k), (N, nb, k), (N, nb, k), (N, nb).
    """
    packs = [pack_row_tiled(r, c, v, shape, row_tile=row_tile,
                            nz_block=nz_block, group=group)
             for (r, c, v) in blocks]
    nbmax = max(p.nblocks for p in packs)
    nbmax = ((nbmax + group - 1) // group) * group
    rl = np.zeros((len(packs), nbmax, nz_block), np.int32)
    cl = np.zeros((len(packs), nbmax, nz_block), np.int32)
    vl = np.zeros((len(packs), nbmax, nz_block), np.float32)
    tb = np.zeros((len(packs), nbmax), np.int32)
    for i, p in enumerate(packs):
        nb = p.nblocks
        rl[i, :nb] = np.asarray(p.rows_local)
        cl[i, :nb] = np.asarray(p.cols)
        vl[i, :nb] = np.asarray(p.vals)
        tb[i, :nb] = np.asarray(p.tile_base)
        tb[i, nb:] = tb[i, nb - 1] if nb else 0   # keep bases monotone
    return rl, cl, vl, tb


def plan_tiling(tile_base: np.ndarray, *, r: int, k: int,
                row_tile: int) -> costmodel.Tiling:
    """Choose the kernel tiling for a stacked pack at plan time (host)."""
    nb = tile_base.shape[-1]
    return costmodel.choose_tiling(r=r, nb=nb, k=k,
                                   row_tile=row_tile, tile_base=tile_base)


def merge_tilings(tilings) -> costmodel.Tiling:
    """Conservative merge across phases: knobs every phase supports."""
    tilings = list(tilings)
    r_tile = tilings[0].r_tile
    bps = tilings[0].blocks_per_step
    for t in tilings[1:]:
        r_tile = math.gcd(r_tile, t.r_tile)
        bps = math.gcd(bps, t.blocks_per_step)
    return costmodel.Tiling(r_tile=r_tile, blocks_per_step=bps)


def coo_of(rows_local, cols, vals, tile_base, shape, row_tile) -> RowTiledCOO:
    """Assemble a RowTiledCOO inside traced code from raw arrays."""
    return RowTiledCOO(rows_local, cols, vals, tile_base, shape, row_tile)


def fetch(x) -> np.ndarray:
    """An executor's device result copied to the host.

    ``np.asarray`` alone would first wait for the device to finish it;
    here the wait and the copy are two host spans on the profiler's
    clock, ``api.wait`` and ``api.fetch`` (with its ``bytes``), so a
    trace tells them apart.  ``repro.obs`` is imported lazily (lint rule
    R1)."""
    from repro.obs import spans
    with spans.span("api.wait"):
        jax.block_until_ready(x)
    with spans.span("api.fetch", bytes=x.nbytes):
        return np.asarray(x)


def choose_row_tile(height: int, want: int = 256) -> int:
    """Largest divisor of `height` that is <= want (prefers multiples of 8)."""
    t = min(want, height)
    while height % t:
        t -= 1
    return t


# ---------------------------------------------------------------------------
# Support-pruned communication (comm="sparse")
# ---------------------------------------------------------------------------
#
# A dense *input* operand movement (fiber all-gather, traveling A/B chunk)
# only needs to deliver the rows the receiver's nonzeros actually read —
# the pack's row/col support.  The planners precompute, per channel, the
# per-(device, offset/phase) send and receive index sets, padded to a
# static width; the executors replace the dense collective with one
# ``ppermute`` of the packed rows per offset, scattered into a zero
# buffer at the receiver.  Rows outside the support stay zero but are
# never read by the local kernels, so results are bitwise-identical to
# the dense schedule.  Traveling *accumulators* (SpMMB/FusedMMB outputs,
# partial-dot buffers) and reduce-scatters are never pruned: they carry
# partial sums whose exact FP addition order must be preserved.

@dataclasses.dataclass(frozen=True)
class SparseMeta:
    """Static per-plan record of which channels ship pruned (and how wide).

    ``gather``/``gather_b`` — the fiber all-gather(s) of a dense operand;
    ``shift``/``shift_b`` — the traveling dense input chunks.  A flag is
    False when the channel does not exist on this grid (c == 1, L == 1)
    or when the crossover heuristic found the support too dense to win
    (``costmodel.SPARSE_CROSSOVER``); the executor then keeps the dense
    schedule for that channel.  ``wg``/``wg_b`` are the padded per-offset
    gather widths, ``ws``/``ws_b`` the per-phase padded shift widths —
    the exact payload heights shipped, which the nnz-dependent cost
    model is asserted against at 1.00x.
    """
    gather: bool = False
    gather_b: bool = False
    shift: bool = False
    shift_b: bool = False
    wg: int = 0
    wg_b: int = 0
    ws: Tuple[int, ...] = ()
    ws_b: Tuple[int, ...] = ()
    compress: object = None     # None | "bf16" — wire format of pruned sends


def pad_sets(sets: np.ndarray, width: int, fill: int) -> np.ndarray:
    """Stack an object-array of sorted index sets into (..., width) int32.

    Senders pad with 0 (a junk row that the receiver drops); receivers
    pad with an out-of-bounds index (scatter ``mode="drop"``).
    """
    sets = np.asarray(sets, dtype=object)
    out = np.full(sets.shape + (width,), fill, np.int32)
    for idx in np.ndindex(sets.shape):
        s = np.asarray(sets[idx], np.int32)
        out[idx][:s.shape[0]] = s
    return out


def _wire(x, compress):
    if compress == "bf16":
        from repro.training import compression
        return compression.to_bf16(x)
    return x


def _unwire(x, dtype, compress):
    # NB: on the CPU test backend XLA's float-normalization legalizes
    # bf16 collectives to f32 (converts fused at the sender), so host
    # meshes see the bf16 *rounding* but not the byte saving; backends
    # with native bf16 collectives ship the half-width payload.
    if compress == "bf16":
        from repro.training import compression
        return compression.from_bf16(x, dtype)
    return x


def pruned_permute(x, send_idx, recv_idx, perm, axis_name, out_rows, *,
                   out=None, compress=None):
    """One support-pruned send: ship ``x[send_idx]``, scatter at ``recv_idx``.

    ``send_idx``/``recv_idx`` are equal-width per-device index vectors
    (aligned element-wise by the planner); receiver padding points at
    ``out_rows`` (out of bounds) and is dropped.  Returns a dense
    ``(out_rows, x.shape[1])`` buffer — zeros (or ``out``) outside the
    support.
    """
    payload = _wire(x[send_idx, :], compress)
    arrived = _unwire(jax.lax.ppermute(payload, axis_name, perm),
                      x.dtype, compress)
    if out is None:
        out = jnp.zeros((out_rows, x.shape[1]), x.dtype)
    return out.at[recv_idx, :].set(arrived, mode="drop")


def pruned_gather_rows(x, send_tuple, recv_tuple, axis_name, size, *,
                       compress=None):
    """Support-pruned row-tiled fiber all-gather: (slot, r) -> (slot*size, r).

    The own slab lands whole (free); every other slab arrives as one
    pruned ppermute per offset d, placed at absolute row indices.
    """
    slot = x.shape[0]
    idx = jax.lax.axis_index(axis_name)
    out = jnp.zeros((slot * size, x.shape[1]), x.dtype)
    out = jax.lax.dynamic_update_slice(out, x, (idx * slot, 0))
    for d in range(1, size):
        perm = [(i, (i + d) % size) for i in range(size)]
        out = pruned_permute(x, send_tuple[d - 1], recv_tuple[d - 1], perm,
                             axis_name, slot * size, out=out,
                             compress=compress)
    return out


def pruned_gather_cols(x, send_tuple, recv_idx, axis_name, size, *,
                       compress=None):
    """Support-pruned column-slab fiber all-gather: (m, w) -> (m, w*size).

    Slabs are full-height, so the receiver's row support ``recv_idx`` is
    one set per device (the union over its resident blocks), independent
    of the source — senders ship ``x[recv's rows]`` per offset.
    """
    m, w = x.shape
    idx = jax.lax.axis_index(axis_name)
    out = jnp.zeros((m, w * size), x.dtype)
    out = jax.lax.dynamic_update_slice(out, x, (0, idx * w))
    for d in range(1, size):
        perm = [(i, (i + d) % size) for i in range(size)]
        payload = _wire(x[send_tuple[d - 1], :], compress)
        arrived = _unwire(jax.lax.ppermute(payload, axis_name, perm),
                          x.dtype, compress)
        slab = jnp.zeros((m, w), x.dtype).at[recv_idx, :].set(
            arrived, mode="drop")
        out = jax.lax.dynamic_update_slice(out, slab,
                                           (0, ((idx - d) % size) * w))
    return out


@dataclasses.dataclass(frozen=True, eq=False)   # identity semantics:
# numpy arrays inside static pytree metadata must not be __eq__-compared
class BlockMeta:
    """Host-side metadata to reassemble stacked sparse outputs densely.

    ``row_offsets``/``col_offsets`` carry one entry per stacked block; for
    per-phase packs (1.5D dense shifting) the *leading* axis is the phase
    and the block arrays arrive as a tuple with one stacked array per
    phase (ragged block counts across phases are fine).
    """
    row_offsets: np.ndarray  # (...,) global row offset per block
    col_offsets: np.ndarray  # (...,) global col offset per block
    shape: Tuple[int, int]

    def to_triples(self, rows_local, cols, vals, tile_base,
                   row_tile=None):
        """Flat global COO (rows, cols, vals) of the stacked blocks.

        Padding entries (vals == 0) are filtered out.  This is the
        layout-independent view the api layer assembles results through;
        unlike a dense scatter it is O(nnz), so it scales to the sparse
        sizes the library targets.
        """
        parts = []
        if isinstance(rows_local, (tuple, list)):   # per-phase ragged packs
            for t in range(len(rows_local)):
                parts.append(self._triples_of(
                    rows_local[t], cols[t], vals[t], tile_base[t],
                    self.row_offsets[t], self.col_offsets[t]))
        else:
            parts.append(self._triples_of(rows_local, cols, vals,
                                          tile_base, self.row_offsets,
                                          self.col_offsets))
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]),
                np.concatenate([p[2] for p in parts]))

    def to_dense(self, rows_local, cols, vals, tile_base, row_tile=None):
        """Scatter stacked (..., nb, k) block arrays into a dense matrix."""
        r, c, v = self.to_triples(rows_local, cols, vals, tile_base)
        out = np.zeros(self.shape, np.float64)
        np.add.at(out, (r, c), v)
        return out.astype(np.float32)

    @staticmethod
    def _triples_of(rows_local, cols, vals, tile_base, row_off, col_off):
        rl = np.asarray(rows_local)
        cl = np.asarray(cols)
        vl = np.asarray(vals)
        tb = np.asarray(tile_base)
        flat_ro = np.asarray(row_off).reshape(-1).astype(np.int64)
        flat_co = np.asarray(col_off).reshape(-1).astype(np.int64)
        rl = rl.reshape(-1, *rl.shape[-2:])
        cl = cl.reshape(-1, *cl.shape[-2:])
        vl = vl.reshape(-1, *vl.shape[-2:])
        tb = tb.reshape(-1, tb.shape[-1])
        r = (rl.astype(np.int64) + tb[:, :, None]
             + flat_ro[:, None, None]).reshape(-1)
        c = (cl.astype(np.int64) + flat_co[:, None, None]).reshape(-1)
        v = vl.reshape(-1)
        keep = v != 0
        return r[keep], c[keep], v[keep]
