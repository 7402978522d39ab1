"""2.5D sparse-replicating algorithms (paper §V-D).

Grid: ("row" = G, "col" = G, "fiber" = c), p = G^2 c.  The sparse matrix is
STATIONARY and structure-replicated along the fiber; only its VALUES move
along the fiber (all-gather / reduce-scatter), since the coordinates never
change between calls — the paper's "attractive property".  Both dense
matrices propagate within each layer, split into r-chunks of width r/(Gc):

  device (x, y, z) holds, at phase t,
    S block (x, y):            (m/G, n/G)  structure replicated over z,
                               values fiber-sharded by nonzero-block
    A chunk A[X_x, w_{k_t,z}]: (m/G, r/(Gc))  travels along the col axis
    B chunk B[Y_y, w_{k_t,z}]: (n/G, r/(Gc))  travels along the row axis
  with Cannon alignment k_t = (x + y + t) mod G.

SDDMM: each phase adds the partial dots over the resident r-chunk into a
layer-local accumulator; after the round the partials are summed across the
fiber (reduce-scatter to the home value shards) and scaled by the original
sample values.  SpMM: output chunks travel along the col axis (taking A's
schedule) and accumulate R @ B contributions from every column block.
FusedMM admits no dense-*replication* elision here (nothing dense is
replicated) — the fiber traffic is values-only: AG + RS + AG, i.e. the
paper's 3*phi*nr*(c-1)/p term.  It does admit B-chunk *reuse*
(elision="reuse"): the SpMM round replays the B r-chunks cached during
the SDDMM round instead of shifting them a second time, cutting the
dense-chunk trips from 4 to 3.  Local kernel fusion is structurally
impossible (the cross-fiber partial-sum reduction separates the two
halves); docs/algorithms.md carries the full argument.

Comm/compute overlap (see DESIGN.md): the Cannon loops are Python-unrolled
with double-buffered carries — the r-chunk shifts for the next phase are
issued before the local kernel consumes the current chunks.  In the SpMM
round the traveling output accumulates kernel results, so its own shift
trails the kernel; the next contribution is instead precomputed from the
double-buffered incoming B chunk while the output chunk is in flight.

Transpose / backward plumbing: s25 needs no FusedMMB-style executor —
SpMM^T runs spmma_s25 on the TRANSPOSED problem (S^T structure
replicated on the same grid; registry `_S25._spmm_t_call`), and because
nothing dense is replicated here, a training step's Session replay
elides nothing: the backward ships identical words with or without one
(costmodel.SESSION_BWD_ELIDED["s25"] == 0, asserted bitwise by
tests/dist_scripts/check_grad_costs.py).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import common, costmodel
from repro.core.grid import Grid25
from repro.kernels import ops


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PlanS25:
    rows_local: jax.Array   # (G, G, c, nb, k) — identical across z
    cols: jax.Array         # (G, G, c, nb, k)
    vals: jax.Array         # (G, G, c, nb/c, k) — fiber-sharded by block
    tile_base: jax.Array    # (G, G, c, nb)
    m: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    r: int = dataclasses.field(metadata=dict(static=True))
    row_tile: int = dataclasses.field(metadata=dict(static=True))
    tiling: costmodel.Tiling = dataclasses.field(metadata=dict(static=True))
    meta: object = dataclasses.field(metadata=dict(static=True))
    sup: tuple = ()             # comm="sparse" support index arrays
    smeta: object = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def mS(self):
        return self.meta.mS

    @property
    def nS(self):
        return self.meta.nS

    @property
    def rc(self):
        return self.meta.rc


@dataclasses.dataclass(frozen=True, eq=False)
class MetaS25:
    mS: int   # m/G
    nS: int   # n/G
    rc: int   # r/(Gc)
    block_meta: common.BlockMeta


def plan_s25(grid: Grid25, rows, cols, vals, m: int, n: int, r: int, *,
             row_tile: int = 256, nz_block: int = 256, group: int = 1,
             comm: str = "dense", compress=None) -> PlanS25:
    """Pack the stationary S block per layer position (host, amortized).

    comm="sparse": the stationary block (x, y) reads its A r-chunks only
    at its row support and its B r-chunks only at its column support —
    both constant across phases, since only the chunk's column window
    changes.  Each phase's chunk ships directly from its home position,
    pruned to the receiver's support.  The fiber value traffic (the
    3*phi term) and the traveling output chunks stay dense.
    """
    G, c, p = grid.G, grid.c, grid.p
    assert m % G == 0 and n % G == 0 and r % (G * c) == 0
    mS, nS, rc = m // G, n // G, r // (G * c)
    row_tile = common.choose_row_tile(mS, row_tile)

    blocks, row_off, col_off = [], [], []
    rsup = np.empty((G, G), object)
    csup = np.empty((G, G), object)
    for x in range(G):
        for y in range(G):
            br, bc, bv = common.extract_block(
                rows, cols, vals, x * mS, (x + 1) * mS, y * nS, (y + 1) * nS)
            rsup[x, y], csup[x, y] = np.unique(br), np.unique(bc)
            blocks.append((br, bc, bv))
            row_off.append(x * mS), col_off.append(y * nS)
    rl, cl, vl, tb = common.pack_block_list(blocks, (mS, nS), row_tile,
                                            nz_block, group=group)
    nb = rl.shape[1]
    if nb % c:                       # pad so the value shards split evenly
        pad = c - nb % c
        rl = np.pad(rl, ((0, 0), (0, pad), (0, 0)))
        cl = np.pad(cl, ((0, 0), (0, pad), (0, 0)))
        vl = np.pad(vl, ((0, 0), (0, pad), (0, 0)))
        tb = np.pad(tb, ((0, 0), (0, pad)), mode="edge")
        nb += pad
    k = rl.shape[-1]
    tiling = common.plan_tiling(tb, r=rc, k=nz_block,
                                row_tile=row_tile)
    # replicate structure across z; shard values by nonzero-block across z
    rl_g = np.broadcast_to(rl[:, None], (G * G, c, nb, k)).reshape(
        G, G, c, nb, k)
    cl_g = np.broadcast_to(cl[:, None], (G * G, c, nb, k)).reshape(
        G, G, c, nb, k)
    tb_g = np.broadcast_to(tb[:, None], (G * G, c, nb)).reshape(G, G, c, nb)
    vl_g = vl.reshape(G, G, c, nb // c, k)
    sh = grid.sharding("row", "col", "fiber")
    meta = MetaS25(mS, nS, rc, common.BlockMeta(
        np.array(row_off).reshape(G, G), np.array(col_off).reshape(G, G),
        (m, n)))
    sup, smeta = ((), None) if comm != "sparse" else _sparse_sup(
        grid, rsup, csup, mS, nS, sh, compress)
    return PlanS25(
        jax.device_put(rl_g, sh), jax.device_put(cl_g, sh),
        jax.device_put(vl_g, sh), jax.device_put(tb_g, sh),
        m, n, r, row_tile, tiling, meta, sup, smeta)


def _sparse_sup(grid: Grid25, rsup, csup, mS, nS, sh, compress):
    """Pad + align the comm="sparse" support sets into device arrays.

    Chunks are full-height within their layer block, so the support is
    receiver-determined and phase-constant: at phase t device (x, y, z)
    receives its A chunk from grid-col (y+t) % G pruned to rsup[x, y],
    and its B chunk from grid-row (x+t) % G pruned to csup[x, y].  One
    channel per traveling operand, each with its own crossover.
    """
    G, c = grid.G, grid.c
    cross = costmodel.SPARSE_CROSSOVER

    def channel(sup2, height, sender):
        w = max(1, max(sup2[x, y].size for x in range(G) for y in range(G)))
        if G == 1 or w > cross * height:
            return (), (), 0, False
        send = []
        for t in range(1, G):
            s_t = np.empty((G, G, c), object)
            for x in range(G):
                for y in range(G):
                    for z in range(c):
                        s_t[x, y, z] = sup2[sender(x, y, t)]
            send.append(jax.device_put(common.pad_sets(s_t, w, 0), sh))
        recv = np.empty((G, G, c), object)
        for x in range(G):
            for y in range(G):
                for z in range(c):
                    recv[x, y, z] = sup2[x, y]
        recv = jax.device_put(common.pad_sets(recv, w, height), sh)
        return tuple(send), (recv,), w, True

    a_send, a_recv, wa, sa = channel(
        rsup, mS, lambda x, y, t: (x, (y - t) % G))
    b_send, b_recv, wb, sb = channel(
        csup, nS, lambda x, y, t: ((x - t) % G, y))
    sup = (a_send, a_recv, b_send, b_recv)
    return sup, common.SparseMeta(shift=sa, shift_b=sb,
                                  ws=(wa,) if sa else (),
                                  ws_b=(wb,) if sb else (),
                                  compress=compress)


def skew_dense(grid: Grid25, X: np.ndarray, along: str) -> jax.Array:
    """Pre-skew a dense matrix into Cannon start chunks.

    along="row": X = A (rows follow the grid-row coordinate x)
    along="col": X = B (rows follow the grid-col coordinate y)
    Returns stacked (G, G, c, rows/G, r/(Gc)) device-placed array.
    """
    G, c = grid.G, grid.c
    nrows, r = X.shape
    blk, rc = nrows // G, r // (G * c)
    out = np.zeros((G, G, c, blk, rc), X.dtype)
    for x in range(G):
        for y in range(G):
            for z in range(c):
                k = (x + y) % G
                w0 = (k * c + z) * rc
                row0 = (x if along == "row" else y) * blk
                out[x, y, z] = X[row0:row0 + blk, w0:w0 + rc]
    return jax.device_put(out, grid.sharding("row", "col", "fiber"))


def unskew_out(grid: Grid25, plan: PlanS25, stacked) -> np.ndarray:
    """Reassemble A-shaped outputs whose chunks ended in skewed-home spots."""
    G, c = grid.G, grid.c
    mS, rc = plan.mS, plan.rc
    stacked = np.asarray(stacked)
    out = np.zeros((plan.m, plan.r), np.float32)
    for x in range(G):
        for y in range(G):
            for z in range(c):
                k = (x + y) % G
                w0 = (k * c + z) * rc
                out[x * mS:(x + 1) * mS, w0:w0 + rc] += stacked[x, y, z]
    return out


def _coo(plan, rl, cl, vl, tb):
    return common.coo_of(rl, cl, vl, tb, (plan.mS, plan.nS), plan.row_tile)


def _shift_back(x, axis_name, size):
    return jax.lax.ppermute(x, axis_name,
                            [(i, (i - 1) % size) for i in range(size)])


def _exec(grid: Grid25, plan: PlanS25, body, A_sk, B_sk, out_specs):
    s_spec = P(grid.row, grid.col, grid.fiber)
    sup_specs = jax.tree_util.tree_map(lambda _: s_spec, plan.sup)
    fn = jax.shard_map(
        body, mesh=grid.mesh,
        in_specs=((s_spec,) * 4, s_spec, s_spec, sup_specs),
        out_specs=out_specs, check_vma=False)
    s_pack = (plan.rows_local, plan.cols, plan.vals, plan.tile_base)
    return fn(s_pack, A_sk, B_sk, plan.sup)


def _sq_sup(sup):
    """Per-device view of the support arrays (drop grid dims)."""
    return jax.tree_util.tree_map(lambda x: x[0, 0, 0], sup)


def _a_sparse(plan) -> bool:
    return plan.smeta is not None and plan.smeta.shift


def _b_sparse(plan) -> bool:
    return plan.smeta is not None and plan.smeta.shift_b


def _r_chunks(grid, plan, X0, send, recv, axis_name, out_rows,
              barrier=False):
    """Per-phase r-chunks via direct pruned sends from each chunk's home.

    Phase t's chunk sits t positions up the travel axis, so one ppermute
    with perm i -> (i-t) % G replaces the dense ring hop; the payload is
    the receiver's (phase-constant) support.  barrier=True keeps a
    replay round (FusedMM "none") out of XLA's CSE.
    """
    G = grid.G
    src = jax.lax.optimization_barrier(X0) if barrier else X0
    chunks = [X0]
    for t in range(1, G):
        perm = [(i, (i - t) % G) for i in range(G)]
        chunks.append(common.pruned_permute(
            src, send[t - 1], recv[0], perm, axis_name, out_rows,
            compress=plan.smeta.compress))
    return chunks


def _sddmm_round(grid, plan, s, A0, B0, sup=()):
    """Cannon round over r-chunks; returns layer-partial dots (nb, k).

    The A/B chunk shifts for phase t+1 are issued before the phase-t
    kernel; the partial accumulator stays local (fiber-reduced later).
    Also returns ``bchunks``, the per-phase resident B chunks — local
    references, free unless a caller consumes them (the "reuse"
    B-chunk-replay schedule feeds them to the SpMM round, eliding B's
    second trip around the grid).  comm="sparse" replaces either ring
    with per-phase direct pruned sends (see _r_chunks).
    """
    G = grid.G
    tk = plan.tiling.kernel_kwargs()
    rl, cl, _, tb = s
    partial = jnp.zeros(rl.shape, jnp.float32)
    ones = jnp.ones(rl.shape, jnp.float32)
    achunks = bchunks_in = None
    if _a_sparse(plan):
        achunks = _r_chunks(grid, plan, A0, sup[0], sup[1], grid.col,
                            plan.mS)
    if _b_sparse(plan):
        bchunks_in = _r_chunks(grid, plan, B0, sup[2], sup[3], grid.row,
                               plan.nS)
    A_cur, B_cur = A0, B0
    bchunks = []
    if G > 1:
        if achunks is None:
            A_nxt = _shift_back(A_cur, grid.col, G)
        if bchunks_in is None:
            B_nxt = _shift_back(B_cur, grid.row, G)
    for t in range(G):
        bchunks.append(B_cur)
        dots = ops.sddmm(A_cur, B_cur, _coo(plan, rl, cl, ones, tb),
                         **tk).vals
        partial = partial + dots
        nt = t + 1 if t + 1 < G else 0
        if achunks is not None:
            A_cur = achunks[nt]
        elif G > 1:
            A_cur = A_nxt
            if t + 1 < G:
                A_nxt = _shift_back(A_nxt, grid.col, G)
        else:
            A_cur = _shift_back(A_cur, grid.col, G)
        if bchunks_in is not None:
            B_cur = bchunks_in[nt]
        elif G > 1:
            B_cur = B_nxt
            if t + 1 < G:
                B_nxt = _shift_back(B_nxt, grid.row, G)
        else:
            B_cur = _shift_back(B_cur, grid.row, G)
    return partial, A_cur, B_cur, bchunks


@functools.partial(jax.jit, static_argnums=(0,))
def sddmm_s25(grid: Grid25, plan: PlanS25, A_sk, B_sk):
    """R = S * (A @ B.T); values end fiber-sharded at home (nb/c, k)."""
    fib = grid.fiber

    def body(s, A_loc, B_loc, sup):
        s = tuple(x[0, 0, 0] for x in s)
        partial, _, _, _ = _sddmm_round(grid, plan, s,
                                        A_loc[0, 0, 0], B_loc[0, 0, 0],
                                        _sq_sup(sup))
        # sum partials over the fiber, back to home value shards
        mine = jax.lax.psum_scatter(partial, fib, scatter_dimension=0,
                                    tiled=True)
        return (s[2] * mine)[None, None, None]

    return _exec(grid, plan, body, A_sk, B_sk,
                 P(grid.row, grid.col, grid.fiber))


def _spmm_round(grid, plan, s, B0, sup=(), barrier=False):
    """Cannon round for SpMM: the traveling output accumulates, so its
    shift trails the kernel; the next contribution is precomputed from the
    double-buffered incoming B chunk while the output is in flight.
    comm="sparse" replaces the B ring with direct pruned sends (the
    traveling output keeps its dense, order-preserving shifts)."""
    G = grid.G
    tk = plan.tiling.kernel_kwargs()
    rl, cl, vals, tb = s
    coo = _coo(plan, rl, cl, vals, tb)
    out_cur = jnp.zeros((plan.mS, plan.rc), jnp.float32)
    chunks = _r_chunks(grid, plan, B0, sup[2], sup[3], grid.row, plan.nS,
                       barrier=barrier) if _b_sparse(plan) else None
    contrib = ops.spmm(coo, B0, m=plan.mS, **tk)
    if chunks is None:
        B_nxt = _shift_back(B0, grid.row, G) if G > 1 else None
    for t in range(G):
        out_cur = _shift_back(out_cur + contrib, grid.col, G)
        if t + 1 < G:
            B_in = chunks[t + 1] if chunks is not None else B_nxt
            contrib = ops.spmm(coo, B_in, m=plan.mS, **tk)
            if chunks is None and t + 2 < G:
                B_nxt = _shift_back(B_nxt, grid.row, G)
    return out_cur


def _spmm_round_cached(grid, plan, s, bchunks):
    """SpMM round replaying the B r-chunks cached during the SDDMM round
    (the "reuse" elision): B's second trip around the grid is elided and
    only the traveling output shifts.  B's round-2 schedule coincides
    with its round-1 schedule (period G), so the kernel operands are
    value-identical to :func:`_spmm_round` — bitwise-identical output."""
    G = grid.G
    tk = plan.tiling.kernel_kwargs()
    coo = _coo(plan, *s)
    out_cur = jnp.zeros((plan.mS, plan.rc), jnp.float32)
    contrib = ops.spmm(coo, bchunks[0], m=plan.mS, **tk)
    for t in range(G):
        out_cur = _shift_back(out_cur + contrib, grid.col, G)
        if t + 1 < G:
            contrib = ops.spmm(coo, bchunks[t + 1], m=plan.mS, **tk)
    return out_cur


@functools.partial(jax.jit, static_argnums=(0,))
def spmma_s25(grid: Grid25, plan: PlanS25, B_sk):
    """A = S @ B; output chunks end in skewed-home layout."""
    G, fib = grid.G, grid.fiber

    def body(s, _A, B_loc, sup):
        rl, cl, vshard, tb = tuple(x[0, 0, 0] for x in s)
        vals = jax.lax.all_gather(vshard, fib, tiled=True)   # (nb, k)
        out = _spmm_round(grid, plan, (rl, cl, vals, tb), B_loc[0, 0, 0],
                          _sq_sup(sup))
        return out[None, None, None]

    dummy = jnp.zeros((grid.G, grid.G, grid.c, 1, 1), jnp.float32)
    return _exec(grid, plan, body, dummy, B_sk,
                 P(grid.row, grid.col, grid.fiber))


def resolve_elision(elision: str) -> str:
    """Resolve the uniform ``"auto"`` default: B-chunk "reuse" beats the
    unoptimized round at every (p, c, phi) — same fiber value traffic,
    one fewer dense-chunk trip (3 vs 4 Table-III units)."""
    if elision != "auto":
        return elision
    return "reuse"


def schedule_events(grid: Grid25, op: str, elision: str = "none"):
    """Ordered (point, phase) fault boundaries of one executor round.

    s25 replicates the *structure*, never a dense operand — no gather
    events.  Each round is G phase/shift pairs of traveling dense
    chunks; the SDDMM half ends in the cross-fiber partial-sum
    reduce-scatter (the very barrier that makes "fused" impossible
    here), and FusedMM chains both halves (repro.distributed.faults).
    """
    G = grid.G

    def passes(n, start=0):
        out = []
        for t in range(start, start + n * G):
            out += [("phase", t), ("shift", t)]
        return out

    if op == "sddmm":
        return passes(1) + [("reduce", G - 1)]
    if op in ("spmm", "spmm_t"):     # spmm_t = spmm on the S^T problem
        return passes(1)
    if op == "fusedmm":              # SDDMM pass, RS barrier, SpMM pass
        return passes(1) + [("reduce", G - 1)] + passes(1, start=G)
    raise ValueError(f"unknown op {op!r}")


# FusedMM's reduce event carries the partial-sum reduce-scatter AND the
# value re-broadcast: it legalizes to two HLO collectives (RS + AG),
# splitting the event's 2*fiber words evenly.  The static conformance
# verifier (repro.analysis.conformance) reads this to expand the event
# before matching the compiled collective sequence.
WIRE_EXPANSIONS: dict = {
    ("fusedmm", "reduce"): ("reduce-scatter", "all-gather"),
}


def schedule_words(grid: Grid25, plan: PlanS25, op: str,
                   elision: str = "none", pre_gathered: bool = False):
    """Impl-exact per-device wire words for each schedule event.

    Aligned 1:1 with :func:`schedule_events`; see d15.schedule_words for
    the contract.  s25 replicates no dense operand, so ``pre_gathered``
    changes nothing; the fiber traffic is values-only.  SpMM's opening
    value all-gather has no event of its own in the fault schedule — its
    words ride the first phase span; FusedMM's reduce event carries both
    the partial-sum reduce-scatter AND the value re-broadcast (RS + AG).
    """
    del pre_gathered   # nothing dense is replicated here (Session-inert)
    G, c = grid.G, grid.c
    nb, k = plan.rows_local.shape[-2:]
    fiber = float((c - 1) * (nb // c) * k)
    a_ch = float(plan.mS * plan.rc)    # A chunk / traveling output chunk
    b_ch = float(plan.nS * plan.rc)
    if op == "sddmm":
        # both dense chunks die on the cycle-closing hop
        def shift_w(t):
            return (a_ch + b_ch) if t < G - 1 else 0.0
    elif op in ("spmm", "spmm_t"):
        # the output chunk accumulates (always travels); B's last hop dies
        def shift_w(t):
            return a_ch + (b_ch if t < G - 1 else 0.0)
    elif op == "fusedmm":
        el = resolve_elision(elision)
        if el == "none":
            # round 1: B home feeds round 2 (all hops live), A's last dies;
            # round 2: output always travels, B's last hop dies
            def shift_w(t):
                if t < G:
                    return b_ch + (a_ch if t < G - 1 else 0.0)
                return a_ch + (b_ch if t - G < G - 1 else 0.0)
        else:   # reuse: round 2 replays cached B chunks — output only
            def shift_w(t):
                if t < G:
                    return (a_ch + b_ch) if t < G - 1 else 0.0
                return a_ch
    else:
        raise ValueError(f"unknown op {op!r}")
    out = []
    for point, t in schedule_events(grid, op, elision):
        if point == "reduce":
            out.append((point, t, "reduce-scatter",
                        2 * fiber if op == "fusedmm" else fiber))
        elif point == "phase" and t == 0 and op in ("spmm", "spmm_t"):
            out.append((point, t, "all-gather", fiber))
        elif point == "shift":
            out.append((point, t, "collective-permute", float(shift_w(t))))
        else:
            out.append((point, t, None, 0.0))
    return out


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("elision",))
def fusedmm_s25(grid: Grid25, plan: PlanS25, A_sk, B_sk,
                elision: str = "auto"):
    """FusedMMA on the 2.5D sparse-replicating grid.

    elision="auto" : resolves to "reuse" (see resolve_elision)
    elision="none" : A and B travel in the SDDMM round, out and B in the
                     SpMM round — 4 dense-chunk trips.
    elision="reuse": the SpMM round replays the B r-chunks cached
                     locally during the SDDMM round (B's two schedules
                     coincide, period G), eliding B's second trip: 3
                     dense-chunk trips, bitwise-identical output.
    elision="fused": structurally impossible — rejected.  Per-phase dots
                     cover only the resident r/(Gc) chunk, and the
                     partial sums must cross the fiber (RS + AG) before
                     any SpMM can consume them; with S stationary there
                     is no structure communication to elide either (the
                     paper's "no elision possible", docs/algorithms.md).

    Fiber traffic in every cell is values-only: AG(vals) happens
    implicitly by computing partials, RS reduces them home, AG
    re-broadcasts the final values for the SpMM round — the
    3*phi*nr*(c-1)/p term of Table III.
    Returns (out chunks (G,G,c,mS,rc) skewed-home, R values fiber-sharded).
    """
    elision = resolve_elision(elision)
    if elision not in ("none", "reuse"):
        raise ValueError(f"s25 supports ('none', 'reuse'), got "
                         f"{elision!r} (local fusion is structurally "
                         f"impossible here — see docs/algorithms.md)")
    G, fib = grid.G, grid.fiber

    def body(s, A_loc, B_loc, sup):
        s = tuple(x[0, 0, 0] for x in s)
        sup = _sq_sup(sup)
        rl, cl, vshard, tb = s
        partial, A_home, B_home, bchunks = _sddmm_round(grid, plan, s,
                                                        A_loc[0, 0, 0],
                                                        B_loc[0, 0, 0],
                                                        sup)
        mine = jax.lax.psum_scatter(partial, fib, scatter_dimension=0,
                                    tiled=True)                  # RS
        r_mine = vshard * mine
        r_vals = jax.lax.all_gather(r_mine, fib, tiled=True)     # AG
        if elision == "reuse":
            out = _spmm_round_cached(grid, plan, (rl, cl, r_vals, tb),
                                     bchunks)
        else:
            # barrier: the replay's pruned sends are syntactically
            # identical to round 1's — keep them out of XLA's CSE so the
            # unoptimized baseline is priced honestly.
            out = _spmm_round(grid, plan, (rl, cl, r_vals, tb), B_home,
                              sup, barrier=True)
        return out[None, None, None], r_mine[None, None, None]

    return _exec(grid, plan, body, A_sk, B_sk,
                 (P(grid.row, grid.col, grid.fiber),
                  P(grid.row, grid.col, grid.fiber)))
