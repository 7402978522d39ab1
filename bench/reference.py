"""Plain COO reference of the benchmark's operations, and its control.

Copied from the reference of ``chip_smoke.py``: XLA gathers and
scatter-adds over the nonzeros in fixed chunks on one device, no Pallas
and nothing imported from the program.  Every product is a float32
multiply at ``Precision.HIGHEST``, as the configurations state.

``high=True`` gives the control: every product of two floats is computed
as XLA's ``Precision.HIGH`` does on the MXU, from bfloat16 halves in three
passes (``hi*hi + hi*lo + lo*hi``, the ``lo*lo`` term dropped), with sums
in float32.  It is the next precision below the configurations' own, and
the comparison that decides ``correct`` must fail it.

``cg_replay`` redoes the CG arithmetic of ALS in float64 on the host,
step by step, from the matvecs a solver made: a solver's own arithmetic
is compared given its matvecs, and the matvecs on their own.
"""
from __future__ import annotations

import functools

import numpy as np

CHUNK = 1 << 20        # nonzeros per device step


@functools.lru_cache(maxsize=None)
def _steps(high: bool):
    import jax
    import jax.numpy as jnp

    def bf16(x):
        # rounds to bfloat16 in a float32 array; a convert pair could be
        # dropped by the compiler as excess precision, this cannot
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def split(x):
        hi = bf16(x)
        return hi, bf16(x - hi)

    def mul(a, b):
        if not high:
            return a * b
        (ah, al), (bh, bl) = split(a), split(b)
        return ah * bh + (ah * bl + al * bh)

    def rowdot(a, b):
        return jnp.sum(mul(a, b), axis=1, keepdims=True)

    @jax.jit
    def dots(r, c, v, X, Y):
        return v * jnp.sum(mul(X[r], Y[c]), axis=-1)

    @functools.partial(jax.jit, donate_argnums=0)
    def scatter(acc, r, c, v, Y):
        return acc.at[r].add(mul(v[:, None], Y[c]))

    @jax.jit
    def cg_step(X, R, P, rs, out, reg):
        """One step of ``dist_cg_solve``'s CG, given the FusedMM output
        ``out`` of the operand ``P``."""
        AP = out + mul(reg, P)
        alpha = rs / jnp.maximum(rowdot(P, AP), 1e-12)
        X = X + mul(alpha, P)
        R = R - mul(alpha, AP)
        rs_new = rowdot(R, R)
        return X, R, R + mul(rs_new / jnp.maximum(rs, 1e-12), P), rs_new

    return dots, scatter, rowdot, cg_step


class Coo:
    """One sparse matrix held on the reference's device: the sampled
    dot products (SDDMM), the product with a dense matrix (SpMM), their
    fusion, and the batched conjugate gradients of ALS."""

    def __init__(self, rows, cols, vals, m: int, device=None,
                 high: bool = False, chunk: int = CHUNK):
        import jax
        self.m, self.high, self.chunk = m, high, chunk
        self.device = device or jax.devices()[0]
        self.nnz = len(rows)
        self._rows = [self._put(a, np.int32)
                      for a in self._chunked(np.asarray(rows))]
        self._cols = [self._put(a, np.int32)
                      for a in self._chunked(np.asarray(cols))]
        self.vals = self._put(vals)

    def _chunked(self, a):
        """``a`` in chunks of ``chunk``, the last one padded with zeros:
        one program serves every chunk."""
        for lo in range(0, self.nnz, self.chunk):
            part = a[lo:lo + self.chunk]
            yield np.pad(part, (0, self.chunk - len(part)))

    def _put(self, a, dtype=np.float32):
        import jax
        if not isinstance(a, jax.Array):
            a = np.asarray(a, dtype)
        return jax.device_put(a, self.device)

    def _vals(self, vals):
        """The nonzeros' values in the chunks of the coordinates."""
        import jax.numpy as jnp
        v = jnp.pad(self._put(vals), (0, -self.nnz % self.chunk))
        return v.reshape(-1, self.chunk)

    def _run(self, fn):
        import jax
        with jax.default_matmul_precision("highest"):
            return fn(*_steps(self.high))

    def sddmm(self, X, Y, vals=None):
        """vals * <X[rows], Y[cols]> per nonzero, as a device array."""
        import jax.numpy as jnp
        v = self._vals(self.vals if vals is None else vals)
        X, Y = self._put(X), self._put(Y)

        def go(dots, *_):
            parts = [dots(r, c, v[k], X, Y) for k, (r, c)
                     in enumerate(zip(self._rows, self._cols))]
            return jnp.concatenate(parts)[:self.nnz]
        return self._run(go)

    def spmm(self, Y, vals=None):
        """out[rows] += vals * Y[cols], as a device (m, r) array."""
        import jax.numpy as jnp
        v = self._vals(self.vals if vals is None else vals)
        Y = self._put(Y)

        def go(_, scatter, *__):
            acc = jnp.zeros((self.m, Y.shape[1]), jnp.float32)
            for k, (r, c) in enumerate(zip(self._rows, self._cols)):
                acc = scatter(acc, r, c, v[k], Y)
            return acc
        return self._run(go)

    def fusedmm(self, X, Y):
        """(out, sampled values) of FusedMM: ``spmm(sddmm(X, Y), Y)``."""
        Y = self._put(Y)
        r = self.sddmm(X, Y)
        return self.spmm(Y, r), r

    def cg(self, B, rhs, reg: float, iters: int):
        """Batched CG on ``(S_mask(B) + reg I) x_i = rhs_i`` for every row
        at once, in ``dist_cg_solve``'s order: from x = 0, the starting
        residual's matvec included, each matvec this matrix's FusedMM of
        the operand with B plus ``reg`` times the operand.  Returns the
        solved X and each matvec's (operand, FusedMM output), on the host.
        """
        import jax.numpy as jnp
        B, reg = self._put(B), jnp.float32(reg)
        calls = []

        def fusedmm(P):
            out, _ = self.fusedmm(P, B)
            calls.append((np.asarray(P), np.asarray(out)))
            return out

        def go(_, __, rowdot, cg_step):
            X = jnp.zeros((self.m, B.shape[1]), jnp.float32)
            R = self._put(rhs) - fusedmm(X)       # reg * 0 is 0
            P, rs = R, rowdot(R, R)
            for _ in range(iters):
                X, R, P, rs = cg_step(X, R, P, rs, fusedmm(P), reg)
            return np.asarray(X)
        return self._run(go), calls


def cg_replay(rhs, calls, reg: float):
    """``dist_cg_solve``'s arithmetic redone in float64, step by step,
    from its matvecs as they were made: each step takes the operand that
    was passed and the FusedMM output that came back.  Returns the
    operand each later matvec should have been passed, and the solved X.
    """
    def f64(a):
        return np.asarray(a, np.float64)

    def rowdot(a, b):
        return np.einsum("ij,ij->i", a, b)[:, None]

    X0, out0 = (f64(a) for a in calls[0])
    R = f64(rhs) - (out0 + reg * X0)
    X, want = X0, [R]
    rs = rowdot(R, R)
    for P, out in calls[1:]:
        P = f64(P)
        AP = f64(out) + reg * P
        alpha = rs / np.maximum(rowdot(P, AP), 1e-12)
        X = X + alpha * P
        R = R - alpha * AP
        rs_new = rowdot(R, R)
        want.append(R + rs_new / np.maximum(rs, 1e-12) * P)
        rs = rs_new
    return want[:-1], X


def cg_err(rhs, calls, x, reg: float) -> float:
    """The widest gap of a CG solve from its float64 replay
    (``cg_replay``): over every operand after the first and the solved
    factor, by ``row_err``."""
    want, want_x = cg_replay(rhs, calls, reg)
    got = [P for P, _ in calls[1:]]
    return max([row_err(x, want_x)]
               + [row_err(g, w) for g, w in zip(got, want, strict=True)])


def row_err(got, want) -> float:
    """Worst row: ``max_i |got_i - want_i| / max(|want_i|, median)``,
    with Euclidean row norms and the median over the reference's nonzero
    rows, so that a row whose reference is all but zero reads against a
    typical one."""
    got = np.asarray(got, np.float32).reshape(len(want), -1)
    want = np.asarray(want, np.float32).reshape(len(want), -1)
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} against {want.shape}")
    gap = np.linalg.norm(got - want, axis=1)
    norm = np.linalg.norm(want, axis=1)
    nonzero = norm[norm > 0]
    floor = float(np.median(nonzero)) if len(nonzero) else 1.0
    return float(np.max(gap / np.maximum(norm, floor), initial=0.0))
