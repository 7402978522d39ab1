"""1.5D dense-shifting, dense-replicating algorithms (paper Algorithm 1).

Grid: ("layer" = p/c, "fiber" = c).  The sparse matrix S is STATIONARY
(block (u, j) lives on device (u, j % c)), one dense matrix is REPLICATED
along the fiber (all-gather input / reduce-scatter output), the other dense
matrix PROPAGATES via cyclic shifts within each layer.

Block schedule: A row-block i lives on device (i // c, i % c).  B row-block
j starts on device (j // c, j % c); after t shifts device (u, v) holds
B block ((u - t) mod L) * c + v.  The planner materializes, for every
(device, phase), the row-tiled pack of the S block the local kernel needs
— padded per *phase*, so a sparse phase no longer pays the densest phase's
block count — plus a static kernel tiling chosen from the pack statistics.

Comm/compute overlap (see DESIGN.md): every phase loop is Python-unrolled
with a double-buffered carry — the cyclic ``ppermute`` of the *next* B
shard is issued before the local kernel consumes the current one, so shift
latency hides behind SDDMM/SpMM/FusedMM compute.  Where the traveling
buffer itself accumulates kernel output (SpMMB, FusedMMB), the *next*
phase's local contribution is instead precomputed from stationary data
while the current shift is in flight.  ``overlap=False`` reproduces the
serial compute-then-shift schedule (numerically identical; kept for A/B
benchmarking and the equivalence tests).

Modes (unified, per the paper's SpMM<->SDDMM conversion):
  sddmm_d15   : R = S * (A @ B.T)          A replicated-in, B shifts
  spmma_d15   : A = S @ B                  A replicated-out, B shifts
  spmmb_d15   : B = S.T @ A                A replicated-in, B shifts+accum
  fusedmm_d15 : FusedMM, elision in {"auto", "none", "reuse", "fused"}
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import common, costmodel
from repro.core.grid import Grid15
from repro.kernels import ops


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PlanD15:
    """Device-placed per-(device, phase) packs of S (and S^T).

    Each field is a tuple with one stacked array per phase; block counts
    may differ across phases (per-phase padding).
    """
    rows_local: Tuple[jax.Array, ...]   # T x (L, c, nb_t, k) int32
    cols: Tuple[jax.Array, ...]
    vals: Tuple[jax.Array, ...]
    tile_base: Tuple[jax.Array, ...]    # T x (L, c, nb_t)
    m: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    r: int = dataclasses.field(metadata=dict(static=True))
    row_tile: int = dataclasses.field(metadata=dict(static=True))
    transpose: bool = dataclasses.field(metadata=dict(static=True))
    tiling: costmodel.Tiling = dataclasses.field(metadata=dict(static=True))
    # host-only metadata (not traced):
    meta: object = dataclasses.field(metadata=dict(static=True))
    # comm="sparse" support indices: (gather_send, gather_recv,
    # shift_send, shift_recv), each a tuple of (L, c, w) int32 arrays
    # (per fiber offset / per phase); empty for dense plans.
    sup: tuple = ()
    smeta: object = dataclasses.field(default=None,
                                      metadata=dict(static=True))

    @property
    def block_shape(self) -> Tuple[int, int]:
        # (rows of the replicated/gathered matrix, rows of one B block)
        if self.transpose:
            return (self.nB, self.cmA)
        return (self.cmA, self.nB)

    @property
    def cmA(self):
        return self.meta.cmA

    @property
    def nB(self):
        return self.meta.nB


@dataclasses.dataclass(frozen=True, eq=False)
class MetaD15:
    cmA: int
    nB: int
    block_meta: common.BlockMeta


def plan_d15(grid: Grid15, rows, cols, vals, m: int, n: int, r: int, *,
             transpose: bool = False, row_tile: int = 256,
             nz_block: int = 256, group: int = 1, comm: str = "dense",
             compress=None) -> PlanD15:
    """Pack S for the 1.5D dense-shifting schedule (host, amortized).

    transpose=True packs S^T blocks (needed by replication-reuse FusedMM
    and by SpMMB — the paper stores both copies, §IV-B).  ``group`` pads
    window runs so ``blocks_per_step`` up to ``group`` stays feasible.

    comm="sparse" additionally derives, from the same block structure,
    the per-device support index sets that let the executors prune the
    fiber all-gather (rows of the replicated operand any resident block
    reads) and the traveling B chunks (per-phase column support of the
    resident block) — see docs/algorithms.md "Sparse communication".
    """
    L, c, p = grid.L, grid.c, grid.p
    assert m % p == 0 and n % p == 0, (m, n, p)
    mA, nB = m // p, n // p
    cmA = c * mA
    blk_shape = (nB, cmA) if transpose else (cmA, nB)
    row_tile = common.choose_row_tile(blk_shape[0], row_tile)

    part = common.block_partition(np.asarray(rows), np.asarray(cols),
                                  np.asarray(vals), cmA, nB, p)
    empty = (np.zeros(0, np.int32), np.zeros(0, np.int32),
             np.zeros(0, np.float32))
    sh5 = grid.sharding("layer", "fiber")
    rls, cls, vls, tbs, tilings = [], [], [], [], []
    row_off = np.zeros((L, L, c), np.int64)   # (phase, layer, fiber)
    col_off = np.zeros((L, L, c), np.int64)
    sparse_comm = comm == "sparse"
    # comm="sparse" support sets, in pre-swap coordinates: the gathered
    # operand T is always indexed by S's row axis (block-local, [0,cmA))
    # and the traveling B chunk by S's col axis ([0,nB)), regardless of
    # pack orientation — transpose only relabels which pack field holds
    # which axis.
    a_sets = [[set() for _ in range(c)] for _ in range(L)]
    b_sets = [[[np.zeros(0, np.int64)] * c for _ in range(L)]
              for _ in range(L)]
    for t in range(L):                        # dense operand fed to kernels
        blocks = []
        for u in range(L):
            for v in range(c):
                j = ((u - t) % L) * c + v
                br, bc, bv = part.get((u, j), empty)
                if sparse_comm:
                    a_sets[u][v].update(np.unique(br).tolist())
                    b_sets[t][u][v] = np.unique(bc)
                if transpose:
                    br, bc = bc, br
                    row_off[t, u, v], col_off[t, u, v] = j * nB, u * cmA
                else:
                    row_off[t, u, v], col_off[t, u, v] = u * cmA, j * nB
                blocks.append((br, bc, bv))
        rl, cl, vl, tb = common.pack_block_list(blocks, blk_shape, row_tile,
                                                nz_block, group=group)
        tilings.append(common.plan_tiling(tb, r=r,
                                          k=nz_block, row_tile=row_tile))
        shp = (L, c) + rl.shape[1:]
        rls.append(jax.device_put(rl.reshape(shp), sh5))
        cls.append(jax.device_put(cl.reshape(shp), sh5))
        vls.append(jax.device_put(vl.reshape(shp), sh5))
        tbs.append(jax.device_put(tb.reshape((L, c) + tb.shape[1:]), sh5))

    meta = MetaD15(cmA, nB, common.BlockMeta(
        row_off, col_off, (n, m) if transpose else (m, n)))
    sup, smeta = ((), None) if not sparse_comm else _sparse_sup(
        grid, a_sets, b_sets, mA, nB, sh5, compress)
    return PlanD15(tuple(rls), tuple(cls), tuple(vls), tuple(tbs),
                   m, n, r, row_tile, transpose,
                   common.merge_tilings(tilings), meta, sup, smeta)


def _sparse_sup(grid: Grid15, a_sets, b_sets, mA, nB, sh, compress):
    """Pad + align the comm="sparse" support sets into device arrays.

    Gather channel (offset d, device (u, v)): as a *sender* it ships the
    slab-local rows of its own A slab that receiver (u, (v+d)%c)'s
    support touches; as a *receiver* it scatters at the absolute rows of
    its support falling in sender (v-d)%c's slab.  Shift channel (phase
    t >= 1): the home layer of the chunk device (u, v) consumes at phase
    t is (u-t)%L, so sender i ships to (i+t)%L the column support of the
    receiver's phase-t resident block.  Per-channel crossover: if the
    padded support words are not under SPARSE_CROSSOVER x the dense
    words, the channel stays dense (flag off).
    """
    L, c = grid.L, grid.c
    cmA = c * mA
    cross = costmodel.SPARSE_CROSSOVER
    g_send, g_recv, wg, gather = (), (), 0, False
    if c > 1:
        a_sorted = [[np.array(sorted(a_sets[u][v]), np.int64)
                     for v in range(c)] for u in range(L)]
        send_sets = np.empty((c - 1, L, c), object)
        recv_sets = np.empty((c - 1, L, c), object)
        w = 1
        for d in range(1, c):
            for u in range(L):
                for v in range(c):
                    rcv = a_sorted[u][(v + d) % c]
                    send_sets[d - 1, u, v] = (
                        rcv[(rcv >= v * mA) & (rcv < (v + 1) * mA)] - v * mA)
                    own = a_sorted[u][v]
                    sv = (v - d) % c
                    recv_sets[d - 1, u, v] = \
                        own[(own >= sv * mA) & (own < (sv + 1) * mA)]
                    w = max(w, send_sets[d - 1, u, v].size)
        gather = w <= cross * mA
        if gather:
            wg = w
            g_send = tuple(jax.device_put(
                common.pad_sets(send_sets[d], wg, 0), sh)
                for d in range(c - 1))
            g_recv = tuple(jax.device_put(
                common.pad_sets(recv_sets[d], wg, cmA), sh)
                for d in range(c - 1))
    s_send, s_recv, ws, shift = (), (), (), False
    if L > 1:
        widths, sends, recvs = [], [], []
        for t in range(1, L):
            ssend = np.empty((L, c), object)
            srecv = np.empty((L, c), object)
            w = 1
            for i in range(L):
                for v in range(c):
                    ssend[i, v] = b_sets[t][(i + t) % L][v]
                    srecv[i, v] = b_sets[t][i][v]
                    w = max(w, srecv[i, v].size)
            widths.append(w)
            sends.append(ssend)
            recvs.append(srecv)
        shift = sum(widths) <= cross * (L - 1) * nB
        if shift:
            ws = tuple(widths)
            s_send = tuple(jax.device_put(
                common.pad_sets(sends[i], ws[i], 0), sh)
                for i in range(L - 1))
            s_recv = tuple(jax.device_put(
                common.pad_sets(recvs[i], ws[i], nB), sh)
                for i in range(L - 1))
    sup = (g_send, g_recv, s_send, s_recv)
    return sup, common.SparseMeta(gather=gather, shift=shift, wg=wg, ws=ws,
                                  compress=compress)


def _s(s, t):
    """Phase-t local pack (drop the (layer, fiber) unit dims)."""
    return tuple(x[t][0, 0] for x in s)


def _coo(plan: PlanD15, s_t):
    rl, cl, vl, tb = s_t
    return common.coo_of(rl, cl, vl, tb, plan.block_shape, plan.row_tile)


def _shift(x, axis_name, size):
    return jax.lax.ppermute(x, axis_name,
                            [(i, (i + 1) % size) for i in range(size)])


def _exec(grid: Grid15, plan: PlanD15, body, A, B, out_specs,
          a_spec=None):
    """Common shard_map/jit harness; S packs enter with (layer,fiber) dims.

    ``a_spec`` overrides the spec of the first dense operand — the
    pre-gathered (Session-cached) paths pass ``P(layer)``, i.e. rows split
    over the layer axis only and replicated along the fiber.  The plan's
    comm="sparse" support indices ride along as a fourth body argument
    (an empty pytree for dense plans).
    """
    mesh, lay, fib = grid.mesh, grid.layer, grid.fiber
    s_spec = P(lay, fib)
    s_pack = (plan.rows_local, plan.cols, plan.vals, plan.tile_base)
    s_specs = jax.tree_util.tree_map(lambda _: s_spec, s_pack)
    sup_specs = jax.tree_util.tree_map(lambda _: s_spec, plan.sup)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(s_specs, a_spec if a_spec is not None else P((lay, fib)),
                  P((lay, fib)), sup_specs),
        out_specs=out_specs, check_vma=False)
    return fn(s_pack, A, B, plan.sup)


def _sq_sup(sup):
    """Drop the (layer, fiber) unit dims of the per-device support sets."""
    return jax.tree_util.tree_map(lambda x: x[0, 0], sup)


def _gather_T(plan: PlanD15, A_loc, sup, fib, c):
    """Fiber replication of the stationary operand, pruned when planned."""
    sm = plan.smeta
    if sm is None or not sm.gather:
        return jax.lax.all_gather(A_loc, fib, tiled=True)
    return common.pruned_gather_rows(A_loc, sup[0], sup[1], fib, c,
                                     compress=sm.compress)


def _shift_sparse(plan: PlanD15) -> bool:
    return plan.smeta is not None and plan.smeta.shift


def _b_chunks(plan: PlanD15, B_loc, sup, lay, L, barrier=False):
    """Per-phase B input chunks via support-pruned direct sends.

    Phase t's chunk ships straight from its home layer ((u-t) mod L for
    receiver u) instead of riding the dense ring: one ppermute of the
    receiver's per-phase column support, scattered into zeros.  Phase 0
    is the local chunk (free).  ``barrier=True`` re-sends from an
    optimization-barrier'd source — the "none" cell's honest second
    round, which XLA would otherwise CSE against round 1 (the payloads
    are syntactically identical; compare s15's re-gather idiom).
    """
    src = jax.lax.optimization_barrier(B_loc) if barrier else B_loc
    chunks = [B_loc]
    for t in range(1, L):
        perm = [(i, (i + t) % L) for i in range(L)]
        chunks.append(common.pruned_permute(
            src, sup[2][t - 1], sup[3][t - 1], perm, lay, plan.nB,
            compress=plan.smeta.compress))
    return chunks


def replicated_spec(grid: Grid15) -> P:
    """Sharding spec of a pre-gathered dense operand (see Session)."""
    return P(grid.layer)


def _phase_shift(n_phases: int, start: int = 0):
    out = []
    for t in range(start, start + n_phases):
        out += [("phase", t), ("shift", t)]
    return out


def schedule_events(grid: Grid15, op: str, elision: str = "none"):
    """Ordered (point, phase) fault boundaries of one executor round.

    Mirrors this family's wire schedule (repro.distributed.faults): an
    optional fiber all-gather, L phase/shift pairs per structure pass
    (two passes for the unfused/reuse FusedMM cells), and a terminal
    reduce-scatter where the output is replicated-out.
    """
    L = grid.L
    if op == "sddmm":
        return [("gather", 0)] + _phase_shift(L)
    if op == "spmm":
        return _phase_shift(L) + [("reduce", L - 1)]
    if op == "spmm_t":                       # spmmb: AG in, B accumulates
        return [("gather", 0)] + _phase_shift(L)
    if op == "fusedmm":
        if elision == "reuse":               # FusedMMB: single AG, 2 passes
            return [("gather", 0)] + _phase_shift(2 * L)
        if elision == "fused":               # one structure pass
            return [("gather", 0)] + _phase_shift(L) + [("reduce", L - 1)]
        return ([("gather", 0)] + _phase_shift(2 * L)
                + [("reduce", 2 * L - 1)])
    raise ValueError(f"unknown op {op!r}")


# Every d15 schedule event legalizes to at most one collective kind —
# no multi-collective expansions (contract read by the static
# conformance verifier; s25 declares the one real entry).
WIRE_EXPANSIONS: dict = {}


def schedule_words(grid: Grid15, plan: PlanD15, op: str,
                   elision: str = "none", pre_gathered: bool = False):
    """Impl-exact per-device wire words for each schedule event.

    Returns ``(point, phase, kind, words)`` tuples aligned 1:1 with
    :func:`schedule_events` — ``kind`` names the HLO collective the
    event compiles to (None for compute phases).  The formulas mirror
    the executors exactly, including XLA's dead-code elimination: a
    cycle-closing shift whose result no consumer reads costs 0 words.
    Dense-wire plans only (the obs layer defines cost-model drift for
    comm="dense"); per-event sums are asserted at 1.00x against the
    compiled HLO by tests/dist_scripts/check_obs.py.
    """
    L, c, p = grid.L, grid.c, grid.p
    ag = 0.0 if pre_gathered else float((c - 1) * (plan.m // p) * plan.r)
    rs = float((c - 1) * (plan.m // p) * plan.r)
    sh = float((plan.n // p) * plan.r)
    if op in ("sddmm", "spmm"):
        dead = {L - 1}              # result of the cycle-closing shift
    elif op == "spmm_t":
        dead = set()                # the traveling buffer IS the output
    elif op == "fusedmm":
        el = resolve_elision(elision, plan.transpose)
        # "none": round-1's last shift feeds round 2; only the very last
        # dies.  "reuse"/"fused": round 1 (or the single round) discards
        # its final B position; reuse's round-2 output travels home live.
        dead = {2 * L - 1} if el == "none" else {L - 1}
    else:
        raise ValueError(f"unknown op {op!r}")
    out = []
    for point, t in schedule_events(grid, op, elision):
        if point == "gather":
            out.append((point, t, "all-gather", ag))
        elif point == "reduce":
            out.append((point, t, "reduce-scatter", rs))
        elif point == "shift":
            out.append((point, t, "collective-permute",
                        0.0 if t in dead else sh))
        else:
            out.append((point, t, None, 0.0))
    return out


def resolve_elision(elision: str, transpose: bool) -> str:
    """Resolve the uniform ``"auto"`` default *for the pack in hand*.

    A plan is already committed to an orientation, so only the elisions
    that orientation supports are candidates: a transpose pack admits
    replication reuse (FusedMMB) alone, and for a normal pack local
    fusion beats the unoptimized sequence at every c (Table III: n*r/c
    vs 2*n*r/c replication words, identical shift words), so "auto"
    never resolves to "none".  The cross-orientation, phi-aware ranking
    — which may *choose* to build the transpose pack — lives one level
    up in ``repro.core.api.DistProblem.resolve_elision``.
    """
    if elision != "auto":
        return elision
    return "reuse" if transpose else "fused"


def _sddmm_phases(plan, T, B0, s, L, lay, overlap, swap=False, chunks=None):
    """L SDDMM phases against a shifting B; returns (vals list, B home).

    Overlapped: the shift of B for phase t+1 is issued before the phase-t
    kernel, so it has no consumer inside the phase and hides behind it.
    ``chunks`` (comm="sparse") supplies the per-phase B chunks from
    support-pruned direct sends instead of the dense ring — the kernels
    read identical values (supported rows) so results are bitwise equal.
    """
    tk = plan.tiling.kernel_kwargs()
    vals_out = []
    if chunks is not None:
        for t in range(L):
            coo = _coo(plan, _s(s, t))
            args = (chunks[t], T) if swap else (T, chunks[t])
            vals_out.append(ops.sddmm(*args, coo, **tk).vals)
        return vals_out, B0
    B_cur = B0
    B_nxt = _shift(B0, lay, L) if overlap else None
    for t in range(L):
        coo = _coo(plan, _s(s, t))
        args = (B_cur, T) if swap else (T, B_cur)
        vals_out.append(ops.sddmm(*args, coo, **tk).vals)
        if overlap:
            B_cur = B_nxt
            if t + 1 < L:
                B_nxt = _shift(B_nxt, lay, L)
        else:
            B_cur = _shift(B_cur, lay, L)
    return vals_out, B_cur


# ---------------------------------------------------------------------------
# Unified Algorithm 1: SDDMM / SpMMA / SpMMB
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("overlap", "pre_gathered"))
def sddmm_d15(grid: Grid15, plan: PlanD15, A, B, overlap: bool = True,
              pre_gathered: bool = False):
    """R = S * (A @ B.T); returns per-phase vals, T x (L, c, nb_t, k).

    pre_gathered=True: A arrives already fiber-replicated (sharding
    ``replicated_spec(grid)``) and the all-gather is skipped — the
    across-call replication reuse of ``repro.core.api.Session``."""
    lay, fib, L = grid.layer, grid.fiber, grid.L

    def body(s, A_loc, B_loc, sup):
        sup = _sq_sup(sup)
        T = A_loc if pre_gathered \
            else _gather_T(plan, A_loc, sup, fib, grid.c)    # (c m/p, r)
        chunks = _b_chunks(plan, B_loc, sup, lay, L) \
            if _shift_sparse(plan) else None
        r_vals, _ = _sddmm_phases(plan, T, B_loc, s, L, lay, overlap,
                                  chunks=chunks)
        return tuple(v[None, None] for v in r_vals)

    return _exec(grid, plan, body, A, B,
                 tuple(P(lay, fib) for _ in range(L)),
                 a_spec=replicated_spec(grid) if pre_gathered else None)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("overlap",))
def spmma_d15(grid: Grid15, plan: PlanD15, B, overlap: bool = True):
    """A = S @ B with A replicated as output, reduce-scattered at the end."""
    lay, fib, L, c = grid.layer, grid.fiber, grid.L, grid.c
    tk = plan.tiling.kernel_kwargs()

    def body(s, _unused, B_loc, sup):
        sup = _sq_sup(sup)
        T = jnp.zeros((plan.cmA, plan.r), jnp.float32)
        if _shift_sparse(plan):
            chunks = _b_chunks(plan, B_loc, sup, lay, L)
            for t in range(L):
                T = T + ops.spmm(_coo(plan, _s(s, t)), chunks[t],
                                 m=plan.cmA, **tk)
            return jax.lax.psum_scatter(T, fib, scatter_dimension=0,
                                        tiled=True)
        B_cur = B_loc
        B_nxt = _shift(B_loc, lay, L) if overlap else None
        for t in range(L):
            T = T + ops.spmm(_coo(plan, _s(s, t)), B_cur, m=plan.cmA, **tk)
            if overlap:
                B_cur = B_nxt
                if t + 1 < L:
                    B_nxt = _shift(B_nxt, lay, L)
            else:
                B_cur = _shift(B_cur, lay, L)
        return jax.lax.psum_scatter(T, fib, scatter_dimension=0, tiled=True)

    dummy = jnp.zeros((grid.p, 1), jnp.float32)  # placeholder A slot
    return _exec(grid, plan, body, dummy, B, P((lay, fib)))


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("overlap", "pre_gathered"))
def spmmb_d15(grid: Grid15, plan: PlanD15, A, overlap: bool = True,
              pre_gathered: bool = False):
    """B = S.T @ A: A replicated-in; the shifting B buffer accumulates.

    The traveling buffer is an accumulator, so its shift depends on the
    local kernel; overlap instead precomputes the *next* phase's local
    contribution (stationary S^T against the gathered T) while the shift
    is in flight — only the cheap add serializes with communication.

    pre_gathered=True: A arrives already fiber-replicated (sharding
    ``replicated_spec(grid)``) and the all-gather is skipped — this is
    how a training step's backward transpose-SpMM replays the forward's
    replication of A through an ``api.Session`` (repro.core.grads).
    """
    assert plan.transpose, "spmmb_d15 needs a transpose-packed plan"
    lay, fib, L = grid.layer, grid.fiber, grid.L
    tk = plan.tiling.kernel_kwargs()

    def body(s, A_loc, B0, sup):
        # only the gather is prunable here: the traveling B buffer IS
        # the output accumulator — its FP addition order must be exact
        T = A_loc if pre_gathered \
            else _gather_T(plan, A_loc, _sq_sup(sup), fib, grid.c)
        B_cur = B0
        if overlap:
            contrib = ops.spmm(_coo(plan, _s(s, 0)), T, m=plan.nB, **tk)
            for t in range(L):
                B_cur = _shift(B_cur + contrib, lay, L)
                if t + 1 < L:
                    contrib = ops.spmm(_coo(plan, _s(s, t + 1)), T,
                                       m=plan.nB, **tk)
        else:
            for t in range(L):
                B_cur = B_cur + ops.spmm(_coo(plan, _s(s, t)), T,
                                         m=plan.nB, **tk)
                B_cur = _shift(B_cur, lay, L)
        return B_cur   # full cycle: home again

    zeros = jnp.zeros((plan.n, plan.r), jnp.float32)
    zeros = jax.device_put(zeros, grid.sharding((lay, fib)))
    return _exec(grid, plan, body, A, zeros, P((lay, fib)),
                 a_spec=replicated_spec(grid) if pre_gathered else None)


# ---------------------------------------------------------------------------
# FusedMM with the paper's three strategies
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("elision", "overlap", "pre_gathered"))
def fusedmm_d15(grid: Grid15, plan: PlanD15, A, B, elision: str = "auto",
                overlap: bool = True, pre_gathered: bool = False):
    """FusedMM on the 1.5D dense-shifting grid.

    elision="auto"  : resolve via the cost model (see resolve_elision)
    elision="none"  : FusedMMA, SDDMM then SpMMA (2 rounds, AG + RS)
    elision="reuse" : FusedMMB on the S^T pack (2 rounds, single AG)
    elision="fused" : FusedMMA via the fused local kernel (1 round, AG + RS)

    pre_gathered=True: the first dense operand arrives already replicated
    along the fiber (sharding ``replicated_spec(grid)``) and the all-gather
    is skipped — the across-call replication reuse exploited by
    ``repro.core.api.Session``.  Numerically identical to the gathered
    path: the local kernels consume the same T values either way.

    Returns (out_dense, per-phase R_vals tuple).
    """
    elision = resolve_elision(elision, plan.transpose)
    lay, fib, L = grid.layer, grid.fiber, grid.L
    tk = plan.tiling.kernel_kwargs()
    r_specs = tuple(P(lay, fib) for _ in range(L))
    a_spec = replicated_spec(grid) if pre_gathered else None

    def gather(A_loc, sup):
        if pre_gathered:
            return A_loc
        return _gather_T(plan, A_loc, sup, fib, grid.c)

    if elision == "none":
        assert not plan.transpose

        def body(s, A_loc, B_loc, sup):
            sup = _sq_sup(sup)
            T = gather(A_loc, sup)
            if _shift_sparse(plan):
                chunks = _b_chunks(plan, B_loc, sup, lay, L)
                r_vals, _ = _sddmm_phases(plan, T, B_loc, s, L, lay,
                                          overlap, chunks=chunks)
                # honest two-launch baseline: B ships again for round 2
                chunks = _b_chunks(plan, B_loc, sup, lay, L, barrier=True)
                T2 = jnp.zeros((plan.cmA, plan.r), jnp.float32)
                for t in range(L):
                    R_t = _coo(plan, _s(s, t)).with_vals(r_vals[t])
                    T2 = T2 + ops.spmm(R_t, chunks[t], m=plan.cmA, **tk)
                out = jax.lax.psum_scatter(T2, fib, scatter_dimension=0,
                                           tiled=True)
                return out, tuple(v[None, None] for v in r_vals)
            r_vals, B_cur = _sddmm_phases(plan, T, B_loc, s, L, lay, overlap)
            T2 = jnp.zeros((plan.cmA, plan.r), jnp.float32)
            B_nxt = _shift(B_cur, lay, L) if overlap else None
            for t in range(L):
                R_t = _coo(plan, _s(s, t)).with_vals(r_vals[t])
                T2 = T2 + ops.spmm(R_t, B_cur, m=plan.cmA, **tk)
                if overlap:
                    B_cur = B_nxt
                    if t + 1 < L:
                        B_nxt = _shift(B_nxt, lay, L)
                else:
                    B_cur = _shift(B_cur, lay, L)
            out = jax.lax.psum_scatter(T2, fib, scatter_dimension=0,
                                       tiled=True)
            return out, tuple(v[None, None] for v in r_vals)

        return _exec(grid, plan, body, A, B, (P((lay, fib)), r_specs),
                     a_spec=a_spec)

    if elision == "reuse":
        # FusedMMB: replicate A once; it serves the SDDMM *and* the SpMMB.
        assert plan.transpose, "reuse needs a transpose-packed plan"

        def body(s, A_loc, B_loc, sup):
            sup = _sq_sup(sup)
            T = gather(A_loc, sup)                           # single AG
            chunks = _b_chunks(plan, B_loc, sup, lay, L) \
                if _shift_sparse(plan) else None
            # sampled <B_j, A_i> on the S^T layout
            r_vals, _ = _sddmm_phases(plan, T, B_loc, s, L, lay, overlap,
                                      swap=True, chunks=chunks)
            out_cur = jnp.zeros((plan.nB, plan.r), jnp.float32)
            if overlap:
                contrib = ops.spmm(
                    _coo(plan, _s(s, 0)).with_vals(r_vals[0]), T,
                    m=plan.nB, **tk)
                for t in range(L):
                    out_cur = _shift(out_cur + contrib, lay, L)
                    if t + 1 < L:
                        contrib = ops.spmm(
                            _coo(plan, _s(s, t + 1)).with_vals(r_vals[t + 1]),
                            T, m=plan.nB, **tk)
            else:
                for t in range(L):
                    Rt = _coo(plan, _s(s, t)).with_vals(r_vals[t])
                    out_cur = out_cur + ops.spmm(Rt, T, m=plan.nB, **tk)
                    out_cur = _shift(out_cur, lay, L)
            # out home after full cycle
            return out_cur, tuple(v[None, None] for v in r_vals)

        return _exec(grid, plan, body, A, B, (P((lay, fib)), r_specs),
                     a_spec=a_spec)

    if elision == "fused":
        assert not plan.transpose

        def body(s, A_loc, B_loc, sup):
            sup = _sq_sup(sup)
            T = gather(A_loc, sup)
            T2 = jnp.zeros((plan.cmA, plan.r), jnp.float32)
            r_vals = []
            chunks = _b_chunks(plan, B_loc, sup, lay, L) \
                if _shift_sparse(plan) else None
            B_cur = B_loc
            B_nxt = _shift(B_loc, lay, L) \
                if overlap and chunks is None else None
            for t in range(L):
                B_t = chunks[t] if chunks is not None else B_cur
                contrib, R_t = ops.fusedmm(T, B_t, _coo(plan, _s(s, t)),
                                           m=plan.cmA, **tk)
                T2 = T2 + contrib
                r_vals.append(R_t.vals)
                if chunks is not None:
                    pass
                elif overlap:
                    B_cur = B_nxt
                    if t + 1 < L:
                        B_nxt = _shift(B_nxt, lay, L)
                else:
                    B_cur = _shift(B_cur, lay, L)
            out = jax.lax.psum_scatter(T2, fib, scatter_dimension=0,
                                       tiled=True)
            return out, tuple(v[None, None] for v in r_vals)

        return _exec(grid, plan, body, A, B, (P((lay, fib)), r_specs),
                     a_spec=a_spec)

    raise ValueError(f"unknown elision {elision!r}")
