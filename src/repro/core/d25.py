"""2.5D dense-replicating algorithms (paper Algorithm 2).

Grid: ("row" = G, "col" = G, "fiber" = c) with p = G^2 c.  Each fiber layer
runs a concurrent Cannon pass on its G x G grid: the sparse matrix S shifts
along grid rows, dense matrix B shifts along grid columns, and dense matrix
A is replicated along the fiber (all-gather input / reduce-scatter output).

Blocking (device (x, y, z)):
  A block (i = x*c + z, y):  (m/(Gc), r/G)   -> fiber AG gives T = A[X_x, W_y]
  S block (x, j_t):          (m/G,  n/(Gc))  travels along the row axis
  B block (j_t, y):          (n/(Gc), r/G)   travels along the column axis
with the Cannon alignment j_t = ((x + y + t) mod G)*c + z.  The planner
pre-skews S and B (the paper's "initial shift", done for free at fill time).

SDDMM sample values accumulate inside the traveling S pack (partial dots
over each visited column slice W_y) and are scaled by the original values
once the pack returns home — so only 3 words per nonzero ever move.

Comm/compute overlap (see DESIGN.md): the Cannon loops are Python-unrolled
with a double-buffered carry — the ``ppermute`` of the next phase's S pack
and B block is issued before the local kernel runs on the current ones.
The accumulating buffers (traveling partial dots / FusedMMB output) still
serialize their own small shift behind the kernel that feeds them, but the
dense-block and coordinate shifts all hide behind compute.
``overlap=False`` reproduces the serial schedule (numerically identical).
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
class PlanD25:
    rows_local: jax.Array   # (G, G, c, nb, k)
    cols: jax.Array
    vals: jax.Array
    tile_base: jax.Array    # (G, G, c, nb)
    m: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    r: int = dataclasses.field(metadata=dict(static=True))
    row_tile: int = dataclasses.field(metadata=dict(static=True))
    transpose: bool = dataclasses.field(metadata=dict(static=True))
    tiling: costmodel.Tiling = dataclasses.field(metadata=dict(static=True))
    meta: object = dataclasses.field(metadata=dict(static=True))
    sup: tuple = ()             # comm="sparse" support index arrays
    smeta: object = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def block_shape(self):
        if self.transpose:
            return (self.meta.nS, self.meta.mS)
        return (self.meta.mS, self.meta.nS)


@dataclasses.dataclass(frozen=True, eq=False)
class MetaD25:
    mS: int    # m/G   (S block rows, T rows)
    nS: int    # n/(Gc) (S block cols, B block rows)
    mA: int    # m/(Gc) (A block rows at rest)
    rW: int    # r/G   (dense column-slice width)
    block_meta: common.BlockMeta


def plan_d25(grid: Grid25, rows, cols, vals, m: int, n: int, r: int, *,
             transpose: bool = False, row_tile: int = 256,
             nz_block: int = 256, group: int = 1, comm: str = "dense",
             compress=None) -> PlanD25:
    """Pack S pre-skewed for the Cannon schedule (host, amortized).

    comm="sparse": device (x, y, z) only ever touches S blocks
    (x, g*c + z) — the fiber all-gather of A needs just the union of
    their (pre-swap) row supports, and the B chunk consumed at phase t
    just the column support of the block resident that phase, so both
    channels ship pruned (docs/algorithms.md "Sparse communication").
    The traveling COO pack, the partial-dot buffer, the traveling
    output chunks and the reduce-scatter stay dense — they carry the
    accumulation order.
    """
    G, c, p = grid.G, grid.c, grid.p
    assert m % (G * c) == 0 and n % (G * c) == 0 and r % G == 0
    mS, nS, mA, rW = m // G, n // (G * c), m // (G * c), r // G
    blk_shape = (nS, mS) if transpose else (mS, nS)
    row_tile = common.choose_row_tile(blk_shape[0], row_tile)

    blocks, row_off, col_off = [], [], []
    for x in range(G):
        for y in range(G):
            for z in range(c):
                j = ((x + y) % G) * c + z          # Cannon pre-skew
                r0, r1 = x * mS, (x + 1) * mS
                c0, c1 = j * nS, (j + 1) * nS
                br, bc, bv = common.extract_block(rows, cols, vals,
                                                  r0, r1, c0, c1)
                if transpose:
                    br, bc = bc, br
                    row_off.append(c0), col_off.append(r0)
                else:
                    row_off.append(r0), col_off.append(c0)
                blocks.append((br, bc, bv))
    rl, cl, vl, tb = common.pack_block_list(blocks, blk_shape, row_tile,
                                            nz_block, group=group)
    tiling = common.plan_tiling(tb, r=rW,
                                k=nz_block, row_tile=row_tile)
    sh = grid.sharding("row", "col", "fiber")
    shp = (G, G, c) + rl.shape[1:]
    meta = MetaD25(mS, nS, mA, rW, common.BlockMeta(
        np.array(row_off).reshape(G, G, c),
        np.array(col_off).reshape(G, G, c),
        (n, m) if transpose else (m, n)))
    sup, smeta = ((), None) if comm != "sparse" else _sparse_sup(
        grid, rows, cols, vals, meta, sh, compress)
    return PlanD25(
        jax.device_put(rl.reshape(shp), sh),
        jax.device_put(cl.reshape(shp), sh),
        jax.device_put(vl.reshape(shp), sh),
        jax.device_put(tb.reshape((G, G, c) + tb.shape[1:]), sh),
        m, n, r, row_tile, transpose, tiling, meta, sup, smeta)


def _sparse_sup(grid: Grid25, rows, cols, vals, meta, sh, compress):
    """Pad + align the comm="sparse" support sets into device arrays.

    Supports are in *pre-swap* coordinates — the gathered operand T is
    always indexed by S's row axis ([0, mS)) and the traveling B chunk
    by S's col axis ([0, nS)) — so one support set serves both pack
    orientations.  Gather: per offset d along the fiber, sender z ships
    the slab-local rows of receiver (z+d)%c's union support (which
    depends on (x, z) only).  Shift: phase t's B chunk is shipped
    directly from its home grid-row (x+t)%G, pruned to the column
    support of the block the receiver holds that phase.
    """
    G, c = grid.G, grid.c
    mS, nS, mA = meta.mS, meta.nS, meta.mA
    cross = costmodel.SPARSE_CROSSOVER
    part = common.block_partition(np.asarray(rows), np.asarray(cols),
                                  np.asarray(vals), mS, nS, G * c)
    empty = np.zeros(0, np.int64)
    ub_rows = {k: np.unique(v[0]) for k, v in part.items()}
    ub_cols = {k: np.unique(v[1]) for k, v in part.items()}

    g_send, g_recv, wg, gather = (), (), 0, False
    if c > 1:
        ra = [[np.unique(np.concatenate(
            [ub_rows.get((x, g * c + z), empty) for g in range(G)]))
            for z in range(c)] for x in range(G)]
        send_sets = np.empty((c - 1, G, G, c), object)
        recv_sets = np.empty((c - 1, G, G, c), object)
        w = 1
        for d in range(1, c):
            for x in range(G):
                for y in range(G):
                    for z in range(c):
                        rcv = ra[x][(z + d) % c]
                        send_sets[d - 1, x, y, z] = (
                            rcv[(rcv >= z * mA) & (rcv < (z + 1) * mA)]
                            - z * mA)
                        own = ra[x][z]
                        zs = (z - d) % c
                        recv_sets[d - 1, x, y, z] = \
                            own[(own >= zs * mA) & (own < (zs + 1) * mA)]
                        w = max(w, send_sets[d - 1, x, y, z].size)
        gather = w <= cross * mA
        if gather:
            wg = w
            g_send = tuple(jax.device_put(
                common.pad_sets(send_sets[d], wg, 0), sh)
                for d in range(c - 1))
            g_recv = tuple(jax.device_put(
                common.pad_sets(recv_sets[d], wg, mS), sh)
                for d in range(c - 1))

    s_send, s_recv, ws, shift = (), (), (), False
    if G > 1:
        widths, sends, recvs = [], [], []
        for t in range(1, G):
            ssend = np.empty((G, G, c), object)
            srecv = np.empty((G, G, c), object)
            w = 1
            for x in range(G):
                for y in range(G):
                    for z in range(c):
                        ssend[x, y, z] = ub_cols.get(
                            ((x - t) % G, ((x + y) % G) * c + z), empty)
                        srecv[x, y, z] = ub_cols.get(
                            (x, ((x + y + t) % G) * c + z), empty)
                        w = max(w, srecv[x, y, z].size)
            widths.append(w)
            sends.append(ssend)
            recvs.append(srecv)
        shift = sum(widths) <= cross * (G - 1) * nS
        if shift:
            ws = tuple(widths)
            s_send = tuple(jax.device_put(
                common.pad_sets(sends[i], ws[i], 0), sh)
                for i in range(G - 1))
            s_recv = tuple(jax.device_put(
                common.pad_sets(recvs[i], ws[i], nS), sh)
                for i in range(G - 1))
    sup = (g_send, g_recv, s_send, s_recv)
    return sup, common.SparseMeta(gather=gather, shift=shift, wg=wg, ws=ws,
                                  compress=compress)


def skew_b(grid: Grid25, B: np.ndarray) -> jax.Array:
    """Pre-skew B into its Cannon start position: (G, G, c, n/(Gc), r/G)."""
    G, c = grid.G, grid.c
    n, r = B.shape
    nS, rW = n // (G * c), r // G
    out = np.zeros((G, G, c, nS, rW), B.dtype)
    for x in range(G):
        for y in range(G):
            for z in range(c):
                j = ((x + y) % G) * c + z
                out[x, y, z] = B[j * nS:(j + 1) * nS, y * rW:(y + 1) * rW]
    return jax.device_put(out, grid.sharding("row", "col", "fiber"))


def unskew_out(grid: Grid25, plan: PlanD25, stacked) -> np.ndarray:
    """Invert the skew for B-shaped outputs (FusedMMB): -> (n, r)."""
    G, c = grid.G, grid.c
    nS, rW = plan.meta.nS, plan.meta.rW
    stacked = common.fetch(stacked)
    out = np.zeros((plan.n, plan.r), np.float32)
    for x in range(G):
        for y in range(G):
            for z in range(c):
                j = ((x + y) % G) * c + z
                out[j * nS:(j + 1) * nS, y * rW:(y + 1) * rW] = \
                    stacked[x, y, z]
    return out


def _coo(plan, rl, cl, vl, tb):
    return common.coo_of(rl, cl, vl, tb, plan.block_shape, plan.row_tile)


def _shift_back(x, axis_name, size):
    """Move the buffer at position i to position i-1 (Cannon advance)."""
    return jax.lax.ppermute(x, axis_name,
                            [(i, (i - 1) % size) for i in range(size)])


def _exec(grid: Grid25, plan: PlanD25, body, A, B_sk, out_specs,
          a_spec=None):
    """``a_spec`` overrides the replicated-operand spec — the pre-gathered
    (Session-cached) paths pass ``P(row, col)``: rows split over the grid
    row axis only, replicated along the fiber."""
    mesh = grid.mesh
    rw, cl_ax, fib = grid.row, grid.col, grid.fiber
    s_spec = P(rw, cl_ax, fib)
    sup_specs = jax.tree_util.tree_map(lambda _: s_spec, plan.sup)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=((s_spec,) * 4,
                  a_spec if a_spec is not None else P((rw, fib), cl_ax),
                  s_spec, sup_specs),
        out_specs=out_specs, check_vma=False)
    s_pack = (plan.rows_local, plan.cols, plan.vals, plan.tile_base)
    return fn(s_pack, A, B_sk, plan.sup)


def replicated_spec(grid: Grid25) -> P:
    """Sharding spec of a pre-gathered dense operand (see Session)."""
    return P(grid.row, grid.col)


def schedule_events(grid: Grid25, op: str, elision: str = "none"):
    """Ordered (point, phase) fault boundaries of one executor round.

    Cannon schedule: an optional fiber all-gather of the replicated
    operand, G phase/shift pairs per structure pass (two passes for the
    unfused/reuse FusedMM cells), and a terminal fiber reduce-scatter
    where the output is replicated-out (repro.distributed.faults).
    """
    G = grid.G

    def passes(n, start=0):
        out = []
        for t in range(start, start + n * G):
            out += [("phase", t), ("shift", t)]
        return out

    if op == "sddmm":
        return [("gather", 0)] + passes(1)
    if op == "spmm":
        return passes(1) + [("reduce", G - 1)]
    if op == "spmm_t":                       # spmmb on the S^T pack
        return [("gather", 0)] + passes(1)
    if op == "fusedmm":
        if elision == "reuse":
            return [("gather", 0)] + passes(2)
        if elision == "fused":               # one structure pass
            return [("gather", 0)] + passes(1) + [("reduce", G - 1)]
        return [("gather", 0)] + passes(2) + [("reduce", 2 * G - 1)]
    raise ValueError(f"unknown op {op!r}")


# A d25 Cannon shift multiplexes several channels but they are all
# collective-permutes — no schedule event legalizes to more than one
# collective kind (contract read by the static conformance verifier;
# s25 declares the one real entry).
WIRE_EXPANSIONS: dict = {}


def schedule_words(grid: Grid25, plan: PlanD25, op: str,
                   elision: str = "none", pre_gathered: bool = False):
    """Impl-exact per-device wire words for each schedule event.

    Aligned 1:1 with :func:`schedule_events`; see d15.schedule_words for
    the contract.  A Cannon shift event multiplexes up to three channels
    — the partial/value payload (nb*k), the coordinate structure
    (2*nb*k + tile map), and the dense B chunk (nS*rW) — whose liveness
    differs per cell (an accumulating buffer always travels; a carry
    whose final position nothing reads is DCE'd).
    """
    G, c = grid.G, grid.c
    meta = plan.meta
    nb, k = plan.rows_local.shape[-2:]
    e = float(nb * k)
    b = float(nb) if plan.row_tile < plan.block_shape[0] else 0.0
    chunk = float(meta.nS * meta.rW)
    ag = 0.0 if pre_gathered else float((c - 1) * meta.mA * meta.rW)
    rs = float((c - 1) * meta.mS * meta.rW / c)
    if op == "sddmm":
        # traveling partial always moves; struct + B die on the last hop
        def shift_w(t):
            return e + ((2 * e + b + chunk) if t < G - 1 else 0.0)
    elif op == "spmm":
        def shift_w(t):
            return (3 * e + b + chunk) if t < G - 1 else 0.0
    elif op == "spmm_t":
        # spmmb: the output chunk travels every hop; the structure carry
        # dies after feeding the last contribution
        def shift_w(t):
            return chunk + ((3 * e + b) if t < G - 1 else 0.0)
    elif op == "fusedmm":
        el = resolve_elision(elision, plan.transpose)
        if el == "none":
            # round 1 hands struct AND B to round 2 (all hops live)
            def shift_w(t):
                if t < G:
                    return 3 * e + b + chunk
                return (3 * e + b + chunk) if t < 2 * G - 1 else 0.0
        elif el == "fused":
            # single structure pass: partial, ORIGINAL values, structure
            # and the B chunk all travel; the final hop brings the
            # partial home alone
            def shift_w(t):
                return e + ((3 * e + b + chunk) if t < G - 1 else 0.0)
        else:   # reuse: struct feeds round 2; output travels home live
            def shift_w(t):
                if t < G:
                    return 3 * e + b + (chunk if t < G - 1 else 0.0)
                return chunk + ((3 * e + b) if t - G < G - 1 else 0.0)
    else:
        raise ValueError(f"unknown op {op!r}")
    out = []
    for point, t in schedule_events(grid, op, elision):
        if point == "gather":
            out.append((point, t, "all-gather", ag))
        elif point == "reduce":
            out.append((point, t, "reduce-scatter", rs))
        elif point == "shift":
            out.append((point, t, "collective-permute", float(shift_w(t))))
        else:
            out.append((point, t, None, 0.0))
    return out


def resolve_elision(elision: str, transpose: bool) -> str:
    """Resolve the uniform ``"auto"`` default *for the pack in hand*:
    reuse iff transpose-packed (FusedMMB), the one-structure-pass
    "fused" schedule otherwise — it beats the plain Cannon FusedMMA at
    every (p, c, phi): same AG/RS, strictly fewer shift words
    (Table III extension: 4*phi+1 vs 6*phi+2).  The cross-orientation
    ranking lives in ``repro.core.api.DistProblem.resolve_elision``."""
    if elision != "auto":
        return elision
    return "reuse" if transpose else "fused"


def _sq(args):
    return tuple(x[0, 0, 0] for x in args)


def _sq_sup(sup):
    """Per-device view of the support arrays (drop grid dims)."""
    return jax.tree_util.tree_map(lambda x: x[0, 0, 0], sup)


def _gather_T(plan, A_loc, sup, fib, c):
    """Fiber all-gather of the replicated operand, pruned when won."""
    sm = plan.smeta
    if sm is None or not sm.gather:
        return jax.lax.all_gather(A_loc, fib, tiled=True)
    return common.pruned_gather_rows(A_loc, sup[0], sup[1], fib, c,
                                     compress=sm.compress)


def _shift_sparse(plan) -> bool:
    return plan.smeta is not None and plan.smeta.shift


def _b_chunks(grid, plan, B0, sup, G, barrier=False):
    """Per-phase B chunks via direct pruned sends from each chunk's home.

    Phase t's chunk lives at grid-row (x+t) % G, so one ppermute with
    perm i -> (i-t) % G replaces the dense ring hop, shipping only the
    column support of the receiver's phase-t resident block.  barrier=
    True keeps a replay round (FusedMM "none") out of XLA's CSE — the
    re-sends are syntactically identical to round 1's otherwise.
    """
    src = jax.lax.optimization_barrier(B0) if barrier else B0
    chunks = [B0]
    for t in range(1, G):
        perm = [(i, (i - t) % G) for i in range(G)]
        chunks.append(common.pruned_permute(
            src, sup[2][t - 1], sup[3][t - 1], perm, grid.row,
            plan.meta.nS, compress=plan.smeta.compress))
    return chunks


def _sddmm_round(grid, plan, T, s, B0, overlap=True, chunks=None):
    """Cannon round accumulating partial dots in the traveling S pack.

    For a normal pack the kernel samples <T_i, B_j>; for a transpose pack
    the roles of the dense args swap.  The coordinate and B shifts are
    issued double-buffered ahead of the kernel; the partial-dot buffer
    lags one kernel behind (it needs the dots before it can travel).
    Returns (pack home w/ partial dots, B home, structs, bchunks) where
    ``structs``/``bchunks`` are the per-phase resident structure tuples
    and B chunks — local references, free unless a caller consumes them
    (the "fused" one-structure-pass schedule replays both in round 2).
    """
    G = grid.G
    tk = plan.tiling.kernel_kwargs()
    rl, cl, vl, tb = s
    partial = jnp.zeros_like(vl)
    ones = jnp.ones_like(vl)
    struct = (rl, cl, tb)
    structs, bchunks = [], []
    B_cur = B0 if chunks is None else chunks[0]
    if overlap and G > 1:
        nxt = tuple(_shift_back(x, grid.col, G) for x in struct)
        if chunks is None:
            B_nxt = _shift_back(B_cur, grid.row, G)
    for t in range(G):
        rl_c, cl_c, tb_c = struct
        structs.append(struct)
        bchunks.append(B_cur)
        coo = _coo(plan, rl_c, cl_c, ones, tb_c)
        if plan.transpose:
            dots = ops.sddmm(B_cur, T, coo, **tk).vals
        else:
            dots = ops.sddmm(T, B_cur, coo, **tk).vals
        partial = _shift_back(partial + dots, grid.col, G)
        if overlap and G > 1:
            struct = nxt
            if t + 1 < G:
                nxt = tuple(_shift_back(x, grid.col, G) for x in nxt)
        else:
            struct = tuple(_shift_back(x, grid.col, G) for x in struct)
        if chunks is not None:            # comm="sparse": direct sends
            B_cur = chunks[t + 1] if t + 1 < G else chunks[0]
        elif overlap and G > 1:
            B_cur = B_nxt
            if t + 1 < G:
                B_nxt = _shift_back(B_nxt, grid.row, G)
        else:
            B_cur = _shift_back(B_cur, grid.row, G)
    rl, cl, tb = struct
    return (rl, cl, partial, tb), B_cur, structs, bchunks


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("overlap", "pre_gathered"))
def sddmm_d25(grid: Grid25, plan: PlanD25, A, B_sk, overlap: bool = True,
              pre_gathered: bool = False):
    """R = S * (A @ B.T); values return to skewed-home layout.

    pre_gathered=True: A arrives already fiber-replicated (sharding
    ``replicated_spec(grid)``) and the all-gather is skipped — the
    across-call replication reuse of ``repro.core.api.Session``."""
    fib = grid.fiber

    def body(s, A_loc, B_loc, sup):
        s = _sq(s)
        sup = _sq_sup(sup)
        B0 = B_loc[0, 0, 0]
        T = A_loc if pre_gathered \
            else _gather_T(plan, A_loc, sup, fib, grid.c)
        chunks = _b_chunks(grid, plan, B0, sup, grid.G) \
            if _shift_sparse(plan) else None
        (rl, cl, partial, tb), _, _, _ = _sddmm_round(grid, plan, T, s, B0,
                                                      overlap, chunks)
        return (s[2] * partial)[None, None, None]

    return _exec(grid, plan, body, A, B_sk,
                 P(grid.row, grid.col, grid.fiber),
                 a_spec=replicated_spec(grid) if pre_gathered else None)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("overlap",))
def spmma_d25(grid: Grid25, plan: PlanD25, B_sk, overlap: bool = True):
    """A = S @ B, output replicated along fiber then reduce-scattered."""
    G, fib = grid.G, grid.fiber
    tk = plan.tiling.kernel_kwargs()

    def body(s, _A, B_loc, sup):
        sparse_b = _shift_sparse(plan)
        chunks = _b_chunks(grid, plan, B_loc[0, 0, 0], _sq_sup(sup), G) \
            if sparse_b else None
        cur = _sq(s) + (() if sparse_b else (B_loc[0, 0, 0],))
        if overlap and G > 1:
            nxt = _advance(grid, cur, G) if not sparse_b else \
                tuple(_shift_back(x, grid.col, G) for x in cur)
        T2 = jnp.zeros((plan.meta.mS, plan.meta.rW), jnp.float32)
        for t in range(G):
            rl, cl, vl, tb = cur[:4]
            B_cur = chunks[t] if sparse_b else cur[4]
            T2 = T2 + ops.spmm(_coo(plan, rl, cl, vl, tb), B_cur,
                               m=plan.meta.mS, **tk)
            if overlap and G > 1:
                cur = nxt
                if t + 1 < G:
                    nxt = _advance(grid, nxt, G) if not sparse_b else \
                        tuple(_shift_back(x, grid.col, G) for x in nxt)
            elif sparse_b:
                cur = tuple(_shift_back(x, grid.col, G) for x in cur)
            else:
                cur = _advance(grid, cur, G)
        out = jax.lax.psum_scatter(T2, fib, scatter_dimension=0, tiled=True)
        return out

    dummy = jnp.zeros((grid.G * grid.c, grid.G), jnp.float32)
    return _exec(grid, plan, body, dummy, B_sk,
                 P((grid.row, grid.fiber), grid.col))


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("overlap", "pre_gathered"))
def spmmb_d25(grid: Grid25, plan: PlanD25, A, overlap: bool = True,
              pre_gathered: bool = False):
    """B = S.T @ A on the Cannon grid (transpose pack): AG(A) in, the
    output travels home with the propagated buffer — the FusedMMB second
    round standalone, needed by the backward transpose-SpMMs of a
    training step (repro.core.grads).

    The traveling output accumulates, so its shift trails the kernel;
    overlap precomputes the next contribution from the double-buffered
    traveling structure while the output chunk is in flight (the same
    schedule as fusedmm_d25's "reuse" SpMM round).  pre_gathered=True:
    A arrives already fiber-replicated (``replicated_spec(grid)``) and
    the all-gather is skipped — the Session replay path.

    Returns output chunks stacked (G, G, c, nS, rW) in skewed-home
    layout; reassemble with :func:`unskew_out`.
    """
    assert plan.transpose, "spmmb_d25 needs a transpose-packed plan"
    G, fib = grid.G, grid.fiber
    tk = plan.tiling.kernel_kwargs()

    def body(s, A_loc, _B, sup):
        s = _sq(s)
        T = A_loc if pre_gathered \
            else _gather_T(plan, A_loc, _sq_sup(sup), fib, grid.c)
        out_cur = jnp.zeros((plan.meta.nS, plan.meta.rW), jnp.float32)
        struct = s
        contrib = ops.spmm(_coo(plan, *struct), T, m=plan.meta.nS, **tk)
        if overlap and G > 1:
            nxt = tuple(_shift_back(x, grid.col, G) for x in struct)
        for t in range(G):
            out_cur = _shift_back(out_cur + contrib, grid.row, G)
            if t + 1 < G:
                if overlap:
                    contrib = ops.spmm(_coo(plan, *nxt), T,
                                       m=plan.meta.nS, **tk)
                    if t + 2 < G:
                        nxt = tuple(_shift_back(x, grid.col, G)
                                    for x in nxt)
                else:
                    struct = tuple(_shift_back(x, grid.col, G)
                                   for x in struct)
                    contrib = ops.spmm(_coo(plan, *struct), T,
                                       m=plan.meta.nS, **tk)
        return out_cur[None, None, None]

    dummy = jnp.zeros((grid.G, grid.G, grid.c, 1, 1), jnp.float32)
    return _exec(grid, plan, body, A, dummy,
                 P(grid.row, grid.col, grid.fiber),
                 a_spec=replicated_spec(grid) if pre_gathered else None)


def _advance(grid, cur, G):
    """Cannon advance of a (struct..., B) carry: pack along col, B along row."""
    *struct, B = cur
    return tuple(_shift_back(x, grid.col, G) for x in struct) \
        + (_shift_back(B, grid.row, G),)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("elision", "overlap", "pre_gathered"))
def fusedmm_d25(grid: Grid25, plan: PlanD25, A, B_sk, elision: str = "auto",
                overlap: bool = True, pre_gathered: bool = False):
    """FusedMM on the 2.5D dense-replicating grid.

    elision="auto" : resolve via the cost model (see resolve_elision)
    elision="none" : FusedMMA — AG(A) + 2 Cannon rounds + RS(out).
                     Requires a normal pack.  Returns (out (m,r), R_vals).
    elision="reuse": FusedMMB — single AG(A), output travels home with the
                     propagated buffer (no reduce-scatter).  Requires a
                     transpose pack.  Returns (out stacked skewed, R_vals).
    elision="fused": one-structure-pass FusedMMA — round 2 replays the
                     per-phase structure AND B chunks cached locally
                     during the SDDMM round (both schedules have period
                     G), so only the final sample values travel: the
                     shift term drops from 6*phi+2 to 4*phi+1 Table-III
                     units.  True local-kernel fusion is impossible on
                     this grid (per-phase dots cover only the resident
                     r/G column slice — docs/algorithms.md), but the
                     communication signature of local fusion is
                     achieved.  Requires a normal pack; same returns and
                     bitwise-identical outputs to "none".

    pre_gathered=True: A arrives already fiber-replicated (sharding
    ``replicated_spec(grid)``) and the all-gather is skipped — the
    across-call replication reuse exploited by ``repro.core.api.Session``.
    """
    elision = resolve_elision(elision, plan.transpose)
    G, fib = grid.G, grid.fiber
    tk = plan.tiling.kernel_kwargs()
    a_spec = replicated_spec(grid) if pre_gathered else None

    def gather(A_loc, sup):
        if pre_gathered:
            return A_loc
        return _gather_T(plan, A_loc, sup, fib, grid.c)

    if elision == "none":
        assert not plan.transpose

        def body(s, A_loc, B_loc, sup):
            s = _sq(s)
            sup = _sq_sup(sup)
            B0 = B_loc[0, 0, 0]
            T = gather(A_loc, sup)
            sparse_b = _shift_sparse(plan)
            chunks = _b_chunks(grid, plan, B0, sup, G) if sparse_b else None
            (rl, cl, partial, tb), B_home, _, _ = _sddmm_round(
                grid, plan, T, s, B0, overlap, chunks)
            r_vals = s[2] * partial
            # Round 2 re-ships the chunks; the barrier keeps the replay's
            # (syntactically identical) sends out of XLA's CSE so the
            # two-launch baseline is priced honestly.
            chunks2 = _b_chunks(grid, plan, B0, sup, G, barrier=True) \
                if sparse_b else None
            T2 = jnp.zeros((plan.meta.mS, plan.meta.rW), jnp.float32)
            cur = (rl, cl, r_vals, tb) + (() if sparse_b else (B_home,))
            if overlap and G > 1:
                nxt = _advance(grid, cur, G) if not sparse_b else \
                    tuple(_shift_back(x, grid.col, G) for x in cur)
            for t in range(G):
                rl_c, cl_c, vl_c, tb_c = cur[:4]
                B_cur = chunks2[t] if sparse_b else cur[4]
                T2 = T2 + ops.spmm(_coo(plan, rl_c, cl_c, vl_c, tb_c),
                                   B_cur, m=plan.meta.mS, **tk)
                if overlap and G > 1:
                    cur = nxt
                    if t + 1 < G:
                        nxt = _advance(grid, nxt, G) if not sparse_b else \
                            tuple(_shift_back(x, grid.col, G) for x in nxt)
                elif sparse_b:
                    cur = tuple(_shift_back(x, grid.col, G) for x in cur)
                else:
                    cur = _advance(grid, cur, G)
            out = jax.lax.psum_scatter(T2, fib, scatter_dimension=0,
                                       tiled=True)
            return out, r_vals[None, None, None]

        return _exec(grid, plan, body, A, B_sk,
                     (P((grid.row, grid.fiber), grid.col),
                      P(grid.row, grid.col, grid.fiber)),
                     a_spec=a_spec)

    if elision == "fused":
        assert not plan.transpose

        def body(s, A_loc, B_loc, sup):
            s = _sq(s)
            sup = _sq_sup(sup)
            B0 = B_loc[0, 0, 0]
            T = gather(A_loc, sup)
            chunks = _b_chunks(grid, plan, B0, sup, G) \
                if _shift_sparse(plan) else None
            (rl, cl, partial, tb), _, structs, bchunks = _sddmm_round(
                grid, plan, T, s, B0, overlap, chunks)
            r_vals = s[2] * partial
            # Round 2 replays the cached structure and B chunks; only the
            # final values travel (same col-axis schedule as the pack
            # advance in "none", so kernel operands are value-identical).
            T2 = jnp.zeros((plan.meta.mS, plan.meta.rW), jnp.float32)
            vals_cur = r_vals
            if overlap and G > 1:
                vals_nxt = _shift_back(vals_cur, grid.col, G)
            for t in range(G):
                rl_c, cl_c, tb_c = structs[t]
                T2 = T2 + ops.spmm(_coo(plan, rl_c, cl_c, vals_cur, tb_c),
                                   bchunks[t], m=plan.meta.mS, **tk)
                if overlap and G > 1:
                    vals_cur = vals_nxt
                    if t + 1 < G:
                        vals_nxt = _shift_back(vals_nxt, grid.col, G)
                else:
                    vals_cur = _shift_back(vals_cur, grid.col, G)
            out = jax.lax.psum_scatter(T2, fib, scatter_dimension=0,
                                       tiled=True)
            return out, r_vals[None, None, None]

        return _exec(grid, plan, body, A, B_sk,
                     (P((grid.row, grid.fiber), grid.col),
                      P(grid.row, grid.col, grid.fiber)),
                     a_spec=a_spec)

    if elision == "reuse":
        assert plan.transpose

        def body(s, A_loc, B_loc, sup):
            s = _sq(s)
            sup = _sq_sup(sup)
            B0 = B_loc[0, 0, 0]
            T = gather(A_loc, sup)                           # single AG
            chunks = _b_chunks(grid, plan, B0, sup, G) \
                if _shift_sparse(plan) else None
            (rl, cl, partial, tb), _, _, _ = _sddmm_round(grid, plan, T, s,
                                                          B0, overlap,
                                                          chunks)
            r_vals = s[2] * partial
            out_cur = jnp.zeros((plan.meta.nS, plan.meta.rW), jnp.float32)
            # the output travels and accumulates, so its shift trails the
            # kernel; the *next* contribution is precomputed from the
            # double-buffered traveling structure while it is in flight
            struct = (rl, cl, r_vals, tb)
            contrib = ops.spmm(_coo(plan, *struct), T, m=plan.meta.nS, **tk)
            if overlap and G > 1:
                nxt = tuple(_shift_back(x, grid.col, G) for x in struct)
            for t in range(G):
                out_cur = _shift_back(out_cur + contrib, grid.row, G)
                if t + 1 < G:
                    if overlap:
                        contrib = ops.spmm(_coo(plan, *nxt), T,
                                           m=plan.meta.nS, **tk)
                        if t + 2 < G:
                            nxt = tuple(_shift_back(x, grid.col, G)
                                        for x in nxt)
                    else:
                        struct = tuple(_shift_back(x, grid.col, G)
                                       for x in struct)
                        contrib = ops.spmm(_coo(plan, *struct), T,
                                           m=plan.meta.nS, **tk)
            return out_cur[None, None, None], r_vals[None, None, None]

        return _exec(grid, plan, body, A, B_sk,
                     (P(grid.row, grid.col, grid.fiber),
                      P(grid.row, grid.col, grid.fiber)),
                     a_spec=a_spec)

    raise ValueError(f"unknown elision {elision!r}")
