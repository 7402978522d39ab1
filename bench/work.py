"""The algorithmic work of the sparse kernels, from the matrix alone.

Counts depend on the COO coordinates and ``(m, n, r, dtype)`` only: how
a program packs, tiles, pads, distributes or elides never enters them,
so every implementation is held to the same work.

* Operations: 2 nnz r for SDDMM, 2 nnz r for SpMM, 4 nnz r for FusedMM.
* Bytes, the compulsory traffic: the row and column indices (int32) and
  the values, once per nonzero; the rows of X and of Y that some
  nonzero touches (distinct rows and columns), once each; and the output
  as the api returns it (``m r`` for the dense output, ``nnz`` for the
  sampled values).
"""
from __future__ import annotations

import dataclasses

import numpy as np

INDEX_BYTES = 4
OPS = ("sddmm", "spmm", "fusedmm")


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)


def count(op: str, rows, cols, m: int, n: int, r: int,
          dtype="float32") -> Work:
    """Work of one call of ``op`` on the (m, n) matrix with nonzeros at
    ``(rows, cols)`` and dense operands of width ``r``."""
    if op not in OPS:
        raise ValueError(f"op must be one of {OPS}, got {op!r}")
    item = np.dtype(dtype).itemsize
    nnz = len(rows)
    x_rows = len(np.unique(rows)) if op != "spmm" else 0
    y_rows = len(np.unique(cols))
    out = (m * r if op != "sddmm" else 0) + (nnz if op != "spmm" else 0)
    flops = (4 if op == "fusedmm" else 2) * nnz * r
    moved = (nnz * (2 * INDEX_BYTES + item)
             + (x_rows + y_rows) * r * item + out * item)
    return Work(float(flops), float(moved))


def least_seconds(work: Work, peak: dict, chips: int):
    """The least time ``chips`` chips need for ``work``, and which bound
    sets it: ``"compute"`` or ``"bandwidth"``."""
    compute = work.flops / (chips * peak["flops_per_s"])
    bandwidth = work.bytes / (chips * peak["hbm_bytes_per_s"])
    if compute >= bandwidth:
        return compute, "compute"
    return bandwidth, "bandwidth"
