"""1.5D sparse-shifting, dense-replicating algorithms (paper §V-B).

Grid: ("layer" = p/c, "fiber" = c).  The DENSE matrices are stationary,
column-split across layer positions and replicated (all-gathered) along the
fiber; the SPARSE matrix propagates: row-blocks of S cyclically shift
within each layer, carrying partially-accumulated sample values (3 words
per nonzero — rows, cols, value — exactly the paper's COO payload).

Layout: device (u, v) at rest holds
  A[:, W_u,v], B[:, W_u,v]   column slices of width r/p
  S row-block b = u*c + v    (height m/p), row-tiled pack

After the fiber all-gather each device holds the full-height slices
A[:, W_u], B[:, W_u] of width r*c/p.  A nonzero's dot product accumulates
as its block visits every layer position u (covering all r columns); the
block returns home after a full cycle, where the partial dots are scaled
by the original sample values.  The SpMM round shifts the (now final)
values again, emitting per-phase output slabs out[rows(b_t), W_u].

Because phi = nnz/(nr) is low exactly when this layout wins (paper Fig. 6),
the shifted payload (3*nnz/p words/phase) is tiny compared to the dense
blocks the d15 algorithm would shift.

Comm/compute overlap (see DESIGN.md): the propagation loops are
Python-unrolled with double-buffered carries — the coordinate shift for
the next phase is issued before the local kernel consumes the current
pack, so the (already tiny) payload transfer hides entirely behind the
SDDMM/SpMM compute.  The partial-dot buffer lags one kernel behind, as it
must include the current phase's dots before traveling.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import common, costmodel
from repro.core.grid import Grid15
from repro.kernels import ops


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PlanS15:
    rows_local: jax.Array   # (L, c, nb, k) int32 — one home block per device
    cols: jax.Array
    vals: jax.Array         # original sample values (stay home)
    tile_base: jax.Array    # (L, c, nb)
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
    def rc(self):
        return self.meta.rc  # r*c/p: gathered dense slice width


@dataclasses.dataclass(frozen=True, eq=False)
class MetaS15:
    mS: int
    rc: int
    block_meta: common.BlockMeta


def plan_s15(grid: Grid15, rows, cols, vals, m: int, n: int, r: int, *,
             row_tile: int = 256, nz_block: int = 256, group: int = 1,
             comm: str = "dense", compress=None) -> PlanS15:
    """Pack one home row-block per device (host, amortized).

    comm="sparse": the dense column slabs are full-height, and device
    (u, v) only ever reads the rows/cols its resident blocks touch —
    blocks b = v (mod c), the same set for every layer position u.  The
    planner records those two unions (A rows, B cols) so the fiber
    all-gathers ship only supported rows.  The COO propagation is the
    sparse payload itself and always stays as-is.
    """
    L, c, p = grid.L, grid.c, grid.p
    assert m % p == 0 and r % p == 0, (m, r, p)
    mS = m // p
    row_tile = common.choose_row_tile(mS, row_tile)
    sparse_comm = comm == "sparse"
    a_sets = [set() for _ in range(c)]   # absolute A rows read at fiber v
    b_sets = [set() for _ in range(c)]   # B cols read at fiber v
    blocks, row_off = [], []
    for u in range(L):
        for v in range(c):
            b = u * c + v
            br, bc, bv = common.extract_block(rows, cols, vals,
                                              b * mS, (b + 1) * mS, 0, n)
            if sparse_comm:
                a_sets[v].update((np.unique(br) + b * mS).tolist())
                b_sets[v].update(np.unique(bc).tolist())
            blocks.append((br, bc, bv))
            row_off.append(b * mS)
    rl, cl, vl, tb = common.pack_block_list(blocks, (mS, n), row_tile,
                                            nz_block, group=group)
    tiling = common.plan_tiling(tb, r=r * c // p, k=nz_block,
                                row_tile=row_tile)
    sh = grid.sharding("layer", "fiber")
    shp = (L, c) + rl.shape[1:]
    meta = MetaS15(mS, r * c // p, common.BlockMeta(
        np.array(row_off).reshape(L, c), np.zeros((L, c), np.int64), (m, n)))
    sup, smeta = ((), None) if not sparse_comm else _sparse_sup(
        grid, a_sets, b_sets, m, n, sh, compress)
    return PlanS15(
        jax.device_put(rl.reshape(shp), sh),
        jax.device_put(cl.reshape(shp), sh),
        jax.device_put(vl.reshape(shp), sh),
        jax.device_put(tb.reshape((L, c) + tb.shape[1:]), sh),
        m, n, r, row_tile, tiling, meta, sup, smeta)


def _sparse_sup(grid: Grid15, a_sets, b_sets, m, n, sh, compress):
    """Pad + align the comm="sparse" support sets into device arrays.

    Slabs are full-height, so the support is receiver-determined: per
    offset d the sender at fiber v ships rows R[(v+d) % c] of its own
    column slab and scatters arrivals at its constant R[v].  One channel
    per dense operand (A rows / B cols); per-channel crossover against
    the dense slab height.
    """
    L, c = grid.L, grid.c
    cross = costmodel.SPARSE_CROSSOVER

    def grid_sets(pick):
        out = np.empty((L, c), object)
        for u in range(L):
            for v in range(c):
                out[u, v] = pick(v)
        return out

    def channel(sets, height):
        sorted_ = [np.array(sorted(sets[v]), np.int64) for v in range(c)]
        w = max(1, max(s.size for s in sorted_))
        if c == 1 or w > cross * height:
            return (), (), 0, False
        send = tuple(
            jax.device_put(common.pad_sets(
                grid_sets(lambda v: sorted_[(v + d) % c]), w, 0), sh)
            for d in range(1, c))
        recv = jax.device_put(common.pad_sets(
            grid_sets(lambda v: sorted_[v]), w, height), sh)
        return send, (recv,), w, True

    a_send, a_recv, wa, ga = channel(a_sets, m)
    b_send, b_recv, wb, gb = channel(b_sets, n)
    sup = (a_send, a_recv, b_send, b_recv)
    return sup, common.SparseMeta(gather=ga, gather_b=gb, wg=wa, wg_b=wb,
                                  compress=compress)


def _coo(plan, rl, cl, vl, tb):
    return common.coo_of(rl, cl, vl, tb, (plan.mS, plan.n), plan.row_tile)


def _shift(x, axis_name, size):
    return jax.lax.ppermute(x, axis_name,
                            [(i, (i + 1) % size) for i in range(size)])


def _shift_tuple(xs, axis_name, size):
    return tuple(_shift(x, axis_name, size) for x in xs)


def _exec(grid: Grid15, plan: PlanS15, body, A, B, out_specs,
          a_spec=None, b_spec=None):
    """``a_spec``/``b_spec`` override the dense-operand specs — the
    pre-gathered (Session-cached) paths pass ``P(None, layer)``: column
    slabs split over the layer axis, replicated along the fiber."""
    mesh, lay, fib = grid.mesh, grid.layer, grid.fiber
    s_spec = P(lay, fib)
    sup_specs = jax.tree_util.tree_map(lambda _: s_spec, plan.sup)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=((s_spec,) * 4,
                  a_spec if a_spec is not None else P(None, (lay, fib)),
                  b_spec if b_spec is not None else P(None, (lay, fib)),
                  sup_specs),
        out_specs=out_specs, check_vma=False)
    s_pack = (plan.rows_local, plan.cols, plan.vals, plan.tile_base)
    return fn(s_pack, A, B, plan.sup)


def replicated_spec(grid: Grid15) -> P:
    """Sharding spec of a pre-gathered dense operand (see Session)."""
    return P(None, grid.layer)


def schedule_events(grid: Grid15, op: str, elision: str = "none"):
    """Ordered (point, phase) fault boundaries of one executor round.

    s15 fiber-gathers dense *column slabs* (one gather event per dense
    operand) and shifts the sparse structure through L phases; the
    "fused" cell ships the structure once (one propagation round), the
    other cells twice.  There is no terminal reduce — the output comes
    home as phase-stacked slabs (repro.distributed.faults).
    """
    L = grid.L

    def passes(n, start=0):
        out = []
        for t in range(start, start + n * L):
            out += [("phase", t), ("shift", t)]
        return out

    if op == "sddmm":
        return [("gather", 0), ("gather", 1)] + passes(1)
    if op in ("spmm", "spmm_t"):     # spmm_t = spmm on the S^T problem
        return [("gather", 0)] + passes(1)
    if op == "fusedmm":
        head = [("gather", 0), ("gather", 1)]
        if elision == "fused":
            return head + passes(1)
        if elision == "none":
            # B's honest re-gather happens BETWEEN the propagation
            # rounds (the SpMM half gathers afresh), so its event sits
            # there — the emitted HLO order, which the static
            # conformance verifier pins (repro.analysis.conformance)
            return (head + passes(1) + [("gather", 2)]
                    + passes(1, start=L))
        return head + passes(2)      # reuse: replayed, no re-gather
    raise ValueError(f"unknown op {op!r}")


# No s15 schedule event legalizes to more than one collective kind —
# a shift's three payloads are all collective-permutes (contract read
# by the static conformance verifier; s25 declares the one real entry).
WIRE_EXPANSIONS: dict = {}


def schedule_words(grid: Grid15, plan: PlanS15, op: str,
                   elision: str = "none",
                   pre_gathered=(False, False)):
    """Impl-exact per-device wire words for each schedule event.

    Aligned 1:1 with :func:`schedule_events`; see d15.schedule_words for
    the contract.  The COO propagation decomposes per shift event into a
    partial/value payload (nb*k words) and a structure payload
    (2*nb*k + tile-map words); ``tile_base`` only travels when the pack
    has more than one row tile per block (row_tile < mS) — with a single
    tile the kernels never read it and XLA prunes its shift chain.
    """
    L, c, p = grid.L, grid.c, grid.p
    nb, k = plan.rows_local.shape[-2:]
    e = float(nb * k)
    b = float(nb) if plan.row_tile < plan.mS else 0.0
    ga = float((c - 1) * plan.m * (plan.r // p))
    gb = float((c - 1) * plan.n * (plan.r // p))
    pre_a, pre_b = pre_gathered
    if op == "sddmm":
        gathers = [0.0 if pre_a else ga, 0.0 if pre_b else gb]

        def shift_w(t):
            return e + ((2 * e + b) if t < L - 1 else 0.0)
    elif op in ("spmm", "spmm_t"):
        gathers = [0.0 if pre_b else gb]

        def shift_w(t):
            return (3 * e + b) if t < L - 1 else 0.0
    elif op == "fusedmm":
        el = "fused" if elision == "auto" else elision
        gathers = [0.0 if pre_a else ga, 0.0 if pre_b else gb]
        if el == "none":
            gathers.append(gb)   # honest re-gather, never session-elided
        if el == "fused":
            # single structure pass: the partial, the ORIGINAL values
            # (the SpMM half samples R = vals * partial in-flight) and
            # the structure all travel together; the final shift brings
            # the partial home alone
            def shift_w(t):
                return e + ((3 * e + b) if t < L - 1 else 0.0)
        else:
            # none/reuse: round-1's final struct shift feeds round 2's
            # full-pack propagation, so only the very last shift dies
            def shift_w(t):
                return (3 * e + b) if t < 2 * L - 1 else 0.0
    else:
        raise ValueError(f"unknown op {op!r}")
    out, gi = [], iter(gathers)
    for point, t in schedule_events(grid, op, elision):
        if point == "gather":
            out.append((point, t, "all-gather", next(gi)))
        elif point == "shift":
            out.append((point, t, "collective-permute", float(shift_w(t))))
        else:
            out.append((point, t, None, 0.0))
    return out


def _sddmm_round(grid, plan, T_A, T_B, s, L, lay):
    """One propagation round accumulating partial sampled dots.

    s = (rl, cl, vals, tb) local pack; returns the pack home again with
    partial dot products in the values slot (UNSCALED by original vals),
    plus the per-phase resident structures ``structs`` (local references,
    no extra communication — dead code unless a caller consumes them, as
    the "fused" one-structure-pass schedule does).  The coordinate shifts
    are double-buffered ahead of the kernel; the partial buffer trails
    one kernel behind.
    """
    u = jax.lax.axis_index(lay)
    tk = plan.tiling.kernel_kwargs()
    rl, cl, _, tb = s
    partial = jnp.zeros_like(s[2])
    ones = jnp.ones_like(partial)

    struct = (rl, cl, tb)
    structs = []
    nxt = _shift_tuple(struct, lay, L) if L > 1 else None
    for t in range(L):
        blk = (u - t) % L                       # layer-row of resident block
        off = (blk * grid.c + jax.lax.axis_index(grid.fiber)) * plan.mS
        a_slice = jax.lax.dynamic_slice(
            T_A, (off, 0), (plan.mS, plan.rc))
        rl_c, cl_c, tb_c = struct
        structs.append(struct)
        dots = ops.sddmm(a_slice, T_B,
                         _coo(plan, rl_c, cl_c, ones, tb_c), **tk).vals
        partial = _shift(partial + dots, lay, L)
        if L > 1:
            struct = nxt
            if t + 1 < L:
                nxt = _shift_tuple(nxt, lay, L)
        else:
            struct = _shift_tuple(struct, lay, L)
    rl, cl, tb = struct
    return (rl, cl, partial, tb), structs


def _spmm_round(grid, plan, T_B, s, L, lay):
    """Propagation round for SpMMA: emits per-phase output slabs."""
    tk = plan.tiling.kernel_kwargs()
    cur = s
    nxt = _shift_tuple(cur, lay, L) if L > 1 else None
    slabs = []
    for t in range(L):
        rl, cl, vals, tb = cur
        slabs.append(ops.spmm(_coo(plan, rl, cl, vals, tb), T_B,
                              m=plan.mS, **tk))
        if L > 1:
            cur = nxt
            if t + 1 < L:
                nxt = _shift_tuple(nxt, lay, L)
        else:
            cur = _shift_tuple(cur, lay, L)
    return jnp.stack(slabs)  # (L, mS, rc) — slab t covers rows of block b_t


def _spmm_round_cached(grid, plan, T_B, vals0, structs, L, lay):
    """SpMM propagation round replaying locally cached structure.

    The "fused" one-structure-pass elision: the SDDMM round already
    marched every block's coordinates through this device (``structs``,
    period-L schedule — round-2 phase t re-encounters round-1 phase t's
    block), so only the final sample values travel: 1 word/nnz/phase
    instead of the 3-word COO pack.  Kernel operands are value-identical
    to :func:`_spmm_round`, hence bitwise-identical slabs.
    """
    tk = plan.tiling.kernel_kwargs()
    vals_cur = vals0
    vals_nxt = _shift(vals_cur, lay, L) if L > 1 else None
    slabs = []
    for t in range(L):
        rl, cl, tb = structs[t]
        slabs.append(ops.spmm(_coo(plan, rl, cl, vals_cur, tb), T_B,
                              m=plan.mS, **tk))
        if L > 1:
            vals_cur = vals_nxt
            if t + 1 < L:
                vals_nxt = _shift(vals_nxt, lay, L)
        else:
            vals_cur = _shift(vals_cur, lay, L)
    return jnp.stack(slabs)


def _gather_cols(x, fib):
    """All-gather column slices along the fiber: (n, r/p) -> (n, rc/p)."""
    return jax.lax.all_gather(x, fib, axis=1, tiled=True)


def _sq_sup(sup):
    """Per-device view of the support arrays (drop (layer, fiber) dims)."""
    return jax.tree_util.tree_map(lambda x: x[0, 0], sup)


def _gather_side(plan, x, sup, fib, c, side):
    """Fiber all-gather of one dense operand, support-pruned when won."""
    sm = plan.smeta
    on = sm is not None and (sm.gather if side == 0 else sm.gather_b)
    if not on:
        return _gather_cols(x, fib)
    send, recv = sup[2 * side], sup[2 * side + 1][0]
    return common.pruned_gather_cols(x, send, recv, fib, c,
                                     compress=sm.compress)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("pre_gathered",))
def sddmm_s15(grid: Grid15, plan: PlanS15, A, B,
              pre_gathered: tuple = (False, False)):
    """R = S * (A @ B.T); R values return to home-block layout.

    pre_gathered=(a, b): the corresponding dense operand arrives already
    fiber-replicated (sharding ``replicated_spec(grid)``) and its
    all-gather is skipped — the ``repro.core.api.Session`` reuse path."""
    lay, fib, L = grid.layer, grid.fiber, grid.L
    pre_a, pre_b = pre_gathered

    def body(s, A_loc, B_loc, sup):
        s = tuple(x[0, 0] for x in s)
        sup = _sq_sup(sup)
        T_A = A_loc if pre_a else _gather_side(plan, A_loc, sup, fib,
                                               grid.c, 0)
        T_B = B_loc if pre_b else _gather_side(plan, B_loc, sup, fib,
                                               grid.c, 1)
        (rl, cl, partial, tb), _ = _sddmm_round(grid, plan, T_A, T_B, s,
                                                L, lay)
        vals = s[2] * partial            # scale by original samples (home)
        return vals[None, None]

    rspec = replicated_spec(grid)
    return _exec(grid, plan, body, A, B, P(lay, fib),
                 a_spec=rspec if pre_a else None,
                 b_spec=rspec if pre_b else None)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("pre_gathered",))
def spmma_s15(grid: Grid15, plan: PlanS15, B, pre_gathered: bool = False):
    """A = S @ B; output slabs stacked by phase: (L, c, T, mS, rc/p).

    pre_gathered=True: B's column slices arrive already fiber-replicated
    (sharding ``replicated_spec(grid)``) and the all-gather is skipped —
    the backward transpose-SpMM of a training step replays the forward's
    gather through an ``api.Session`` this way (repro.core.grads).
    """
    lay, fib, L = grid.layer, grid.fiber, grid.L

    def body(s, _A, B_loc, sup):
        s = tuple(x[0, 0] for x in s)
        T_B = B_loc if pre_gathered else _gather_side(
            plan, B_loc, _sq_sup(sup), fib, grid.c, 1)
        slabs = _spmm_round(grid, plan, T_B, s, L, lay)
        return slabs[None, None]

    dummy = jnp.zeros((1, grid.p), jnp.float32)
    return _exec(grid, plan, body, dummy, B, P(lay, fib),
                 b_spec=replicated_spec(grid) if pre_gathered else None)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("elision", "pre_gathered"))
def fusedmm_s15(grid: Grid15, plan: PlanS15, A, B, elision: str = "auto",
                pre_gathered: tuple = (False, False)):
    """FusedMMA = SpMMA(SDDMM(A,B,S), B) with sparse shifting.

    elision="auto" : resolves to "fused" (always cheapest here; see
    docs/choosing.md)
    elision="fused": one-structure-pass — the SpMM round replays the
    per-phase coordinate structure cached locally during the SDDMM round
    (the schedules coincide, period L), so only the final sample values
    travel in round 2: the 6*phi/c shift term drops to 4*phi/c.  The
    single fiber all-gather of "reuse" is retained.  True local-kernel
    fusion is impossible here — each phase's gathered slices span only
    r*c/p of the r columns, so per-phase dots are partial (docs/
    algorithms.md) — but the *communication* signature of local fusion
    (structure shipped once, not twice) is achieved.
    elision="reuse": the fiber all-gathers of the dense column slices are
    performed ONCE and serve both rounds (paper's replication reuse).
    elision="none": B is re-gathered between the rounds, emulating two
    independent kernel launches (the unoptimized baseline).

    pre_gathered=(a, b): the corresponding dense operand arrives already
    fiber-replicated (sharding ``replicated_spec(grid)``) and its
    all-gather is skipped — the across-call replication reuse exploited by
    ``repro.core.api.Session``.

    Returns (slabs (L,c,T,mS,rc/p), R_vals (L,c,nb,k)).
    """
    if elision == "auto":
        elision = "fused"
    lay, fib, L = grid.layer, grid.fiber, grid.L
    pre_a, pre_b = pre_gathered

    def body(s, A_loc, B_loc, sup):
        s = tuple(x[0, 0] for x in s)
        sup = _sq_sup(sup)
        T_A = A_loc if pre_a else _gather_side(plan, A_loc, sup, fib,
                                               grid.c, 0)
        T_B = B_loc if pre_b else _gather_side(plan, B_loc, sup, fib,
                                               grid.c, 1)
        (rl, cl, partial, tb), structs = _sddmm_round(grid, plan, T_A, T_B,
                                                      s, L, lay)
        r_vals = s[2] * partial
        if elision == "fused":
            slabs = _spmm_round_cached(grid, plan, T_B, r_vals, structs,
                                       L, lay)
            return slabs[None, None], r_vals[None, None]
        if elision == "none":
            # Unoptimized baseline: replicate B again for the SpMM, as two
            # independent kernel launches would.  NOTE: a naive duplicate
            # all-gather gets CSE'd by XLA — the compiler applies the
            # paper's replication reuse automatically within one program
            # (an observation we report in EXPERIMENTS.md).  To price the
            # two-launch baseline honestly we re-derive the local slice
            # from the gathered buffer and re-gather it, which XLA cannot
            # structurally merge.
            v_idx = jax.lax.axis_index(fib)
            w = T_B.shape[1] // grid.c
            B_back = jax.lax.dynamic_slice_in_dim(T_B, v_idx * w, w, axis=1)
            T_B = _gather_side(plan, B_back, sup, fib, grid.c, 1)
        slabs = _spmm_round(grid, plan, T_B, (rl, cl, r_vals, tb), L, lay)
        return slabs[None, None], r_vals[None, None]

    rspec = replicated_spec(grid)
    return _exec(grid, plan, body, A, B, (P(lay, fib), P(lay, fib)),
                 a_spec=rspec if pre_a else None,
                 b_spec=rspec if pre_b else None)


def assemble_spmm_out(grid: Grid15, plan: PlanS15, slabs) -> np.ndarray:
    """Host-side reassembly of phase-stacked SpMM slabs into (m, r)."""
    L, c = grid.L, grid.c
    slabs = np.asarray(slabs)
    out = np.zeros((plan.m, plan.r), np.float32)
    w = plan.r * c // grid.p
    for u in range(L):
        for v in range(c):
            for t in range(L):
                b = ((u - t) % L) * c + v
                out[b * plan.mS:(b + 1) * plan.mS,
                    u * w:(u + 1) * w] = slabs[u, v, t]
    return out
