"""Chunked operands and DMA helpers shared by the three Pallas kernels.

Mosaic has no general row gather, and at real sizes neither the dense
operand B (``n_b`` rows) fits VMEM nor the window table ``tile_base``
(``nb`` entries) fits SMEM.  The kernels therefore leave B, A and the
output in HBM (``memory_space=pl.ANY``) and move exactly what one grid
step needs (DESIGN.md §1-2):

* a *step* is ``n = blocks_per_step * nz_block`` nonzeros of one row
  window; a grid step consumes a *chunk* of ``C = lcm(n, 128)``
  nonzeros, i.e. ``C // n`` steps (``chunk_nnz``);
* every per-chunk operand is laid out ``(chunks, 1, width)`` and blocked
  ``(None, 1, width)``, lane-dense and within the (8, 128) block rule:
  column ids and step window bases go to SMEM, window-local row ids and
  sample values to VMEM as ``(1, C)`` rows;
* dense operands move in *slabs* of ``u = min(r_tile, 128)`` lanes,
  ``q = r_tile // u`` slabs per grid step.  B is viewed as rows of one
  slab each, so :func:`gather_rows` fetches a nonzero's slab with one
  row DMA whatever the tiled HBM layout of B; VMEM buffers are
  ``(q, rows, u)``;
* a step's one-hot is masked to its own lanes of the chunk, and
  :func:`copy` moves the ``row_tile``-row windows of A or of the output.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.costmodel import LANES, chunk_nnz

HIGHEST = jax.lax.Precision.HIGHEST
HBM = pl.BlockSpec(memory_space=pl.ANY)


def slab_width(r_tile: int) -> int:
    """Lanes per slab; an r_tile above the lane width is whole slabs."""
    u = min(r_tile, LANES)
    assert r_tile % u == 0, (r_tile, LANES)
    return u


def lane_pad(r: int, r_tile: int, *dense):
    """Pad a width below or between lane multiples up to one (zeros add
    nothing to dots or sums), so full-width slabs stay lane-aligned.
    Returns (padded r, padded r_tile, padded arrays)."""
    if r % LANES == 0 or r_tile != r:
        return r, r_tile, dense
    rp = -(-r // LANES) * LANES
    return rp, rp, tuple(jnp.pad(x, ((0, 0), (0, rp - r))) for x in dense)


def chunk_operands(tile_base, rows_local, cols, vals, bps: int):
    """(bases, cols, rows, vals) regrouped per chunk of ``C`` nonzeros.

    Steps are padded to a whole chunk with empty steps in the last
    window (value 0), which contribute nothing.
    """
    nb, k = rows_local.shape
    n = bps * k
    c = chunk_nnz(n)
    steps = nb // bps
    pad = -steps % (c // n)
    bases = tile_base.reshape(steps, bps)[:, 0].astype(jnp.int32)
    bases = jnp.pad(bases, (0, pad), mode="edge")
    chunks = (steps + pad) * n // c

    def lay(x, dtype):
        x = jnp.pad(x.reshape(-1).astype(dtype), (0, pad * n))
        return x.reshape(chunks, 1, c)

    return (bases.reshape(chunks, 1, c // n), lay(cols, jnp.int32),
            lay(rows_local, jnp.int32), lay(vals, jnp.float32))


def chunk_specs(n: int, chunk):
    """BlockSpecs for :func:`chunk_operands`; ``chunk`` maps grid ids to
    the chunk index."""
    c = chunk_nnz(n)

    def spec(width, space=None):
        kw = {} if space is None else dict(memory_space=space)
        return pl.BlockSpec((None, 1, width),
                            lambda *g: (chunk(*g), 0, 0), **kw)

    return [spec(c // n, pltpu.SMEM), spec(c, pltpu.SMEM), spec(c), spec(c)]


def unchunk(x, nb: int, k: int):
    """(chunks, 1, C) per-nonzero values back to the (nb, k) pack."""
    return x.reshape(-1)[:nb * k].reshape(nb, k)


def window(hbm, base, row_tile: int, slab0, buf):
    """The HBM slabs of rows [base, base + row_tile) matching ``buf``'s
    (q, row_tile, u) slabs, starting at slab ``slab0``."""
    q, _, u = buf.shape
    base = pl.multiple_of(base, math.gcd(row_tile, 8))   # bases are aligned
    return [hbm.at[pl.ds(base, row_tile), pl.ds((slab0 + t) * u, u)]
            for t in range(q)]


def copy(srcs, dsts, sem):
    """Blocking DMA of matching slab lists (a window to or from VMEM)."""
    if not isinstance(srcs, list):
        srcs = [srcs.at[t] for t in range(len(dsts))]
    if not isinstance(dsts, list):
        dsts = [dsts.at[t] for t in range(len(srcs))]
    copies = [pltpu.make_async_copy(s, d, sem) for s, d in zip(srcs, dsts)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def gather_rows(src, cols_ref, buf, sem, slab0, slabs_per_row: int):
    """buf[t, k] = slab ``slab0 + t`` of row ``cols_ref[0, k]`` of B.

    ``src`` is B viewed as one slab per row, ``slabs_per_row`` rows per
    row of B.
    """
    q, n, _ = buf.shape

    def row(k, t, c):
        return pltpu.make_async_copy(
            src.at[pl.ds(c * slabs_per_row + slab0 + t, 1)],
            buf.at[t, pl.ds(k, 1)], sem)

    def start(k, carry):
        c = cols_ref[0, k]
        for t in range(q):
            row(k, t, c).start()
        return carry

    def wait(k, carry):
        row(0, 0, 0).wait()       # every row copy moves the same bytes
        return carry

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n * q, wait, 0)


def for_each_step(base_ref, n: int, body):
    """Run ``body(base, hit)`` for every step of the chunk in order.

    ``hit(rows, row_tile)`` is the step's ``(row_tile, C)`` one-hot: entry
    (t, k) is set where lane k belongs to this step and ``rows[0, k] ==
    t``.
    """
    c = base_ref.shape[-1] * n

    def step(s, carry):
        def hit(rows, row_tile):
            shape = (row_tile, c)
            lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            return (row == rows) & (lane >= s * n) & (lane < (s + 1) * n)

        body(base_ref[0, s], hit)
        return carry

    jax.lax.fori_loop(0, base_ref.shape[-1], step, 0)


def zero_windows(acc, out_window, start, stop, sem):
    """Write the zeroed ``acc`` to every window from row ``start`` up to
    row ``stop`` (both window-aligned)."""
    row_tile = acc.shape[1]

    def body(w, carry):
        copy(acc, out_window(start + w * row_tile), sem)
        return carry

    jax.lax.fori_loop(0, (stop - start) // row_tile, body, 0)


def enter_window(base, cur, acc, out_window, sem, on_enter=None):
    """Make ``acc`` the accumulator of window ``base``.

    Packs keep ``tile_base`` non-decreasing, so a window's steps are
    consecutive: entering a new window flushes the previous one (``cur``
    holds its base, ``-row_tile`` before the first) to HBM, zeroes
    ``acc`` and writes those zeros to the windows skipped in between.
    Every window of the output is written once per sweep and nothing is
    read back from it, so no output buffer needs initial contents.
    """
    @pl.when(base != cur[0])
    def _enter():
        @pl.when(cur[0] >= 0)
        def _flush():
            copy(acc, out_window(cur[0]), sem)

        acc[...] = jnp.zeros_like(acc)
        zero_windows(acc, out_window, cur[0] + acc.shape[1], base, sem)
        if on_enter is not None:
            on_enter()
        cur[0] = base


def leave_last_window(cur, acc, out_window, sem, m: int):
    """Flush the sweep's last window and zero the windows after it."""
    copy(acc, out_window(cur[0]), sem)
    acc[...] = jnp.zeros_like(acc)
    zero_windows(acc, out_window, cur[0] + acc.shape[1], m, sem)


def window_dots(hit, a_win, b_rows):
    """(1, C) sampled dots <A[base + rows[k]], B[cols[k]]> on the step's
    lanes, zero elsewhere.

    ``a_win @ b_rows.T`` (summed over slabs) scores every window row
    against every gathered row on the MXU; the one-hot keeps each lane's
    own row.
    """
    scores = sum(jax.lax.dot_general(
        a_win[t], b_rows[t], (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32) for t in range(a_win.shape[0]))
    return jnp.sum(jnp.where(hit, scores, 0.0), axis=0, keepdims=True)


def scatter_rows(acc, hit, coeff, b_rows):
    """acc += coeff[k] * B[cols[k]] added at window row rows[k], per
    slab — the one-hot matmul of DESIGN.md §1."""
    w = jnp.where(hit, coeff, 0.0)
    for t in range(acc.shape[0]):
        acc[t] += jnp.dot(w, b_rows[t], precision=HIGHEST,
                          preferred_element_type=jnp.float32)
