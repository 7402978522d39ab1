"""Sharding hygiene: divisibility sanitizing and FSDP extension.

``sanitize`` drops any PartitionSpec entry whose mesh-axis product does not
divide the corresponding array dimension (odd vocab sizes like 50280 or
batch=1 decode simply fall back to replication on that dim — exactly what
a production launcher must do rather than crash).

``fsdp_extend`` implements ZeRO-3/FSDP via GSPMD: each parameter (and its
optimizer moments) additionally shards one free, divisible dimension over
the data axis; the partitioner inserts the per-layer all-gathers.  Without
this, f32 params + Adam moments of the 52B/72B architectures are 39+ GB
per chip — with it they drop to ~2.5 GB (see EXPERIMENTS.md §Dry-run).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
from jax.sharding import PartitionSpec as P


def _axes_size(entry, axis_sizes: Dict[str, int]) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        return math.prod(axis_sizes.get(a, 1) for a in entry if a)
    return axis_sizes.get(entry, 1)


def sanitize_spec(spec: P, shape, axis_sizes: Dict[str, int]) -> P:
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        size = _axes_size(entry, axis_sizes)
        out.append(entry if size > 0 and dim % size == 0 else None)
    return P(*out)


def sanitize_tree(spec_tree, shape_tree, axis_sizes: Dict[str, int]):
    return jax.tree.map(
        lambda s, x: sanitize_spec(s, x.shape, axis_sizes),
        spec_tree, shape_tree,
        is_leaf=lambda v: isinstance(v, P))


def fsdp_extend_spec(spec: P, shape, axis_sizes: Dict[str, int],
                     data_axis: str, min_size: int = 2 ** 16) -> P:
    """Shard one free dim over the data axis (largest divisible dim)."""
    if math.prod(shape) < min_size:      # skip small tensors (norms, biases)
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    dsize = axis_sizes.get(data_axis, 1)
    best, best_dim = None, 0
    for i, (dim, entry) in enumerate(zip(shape, entries)):
        if entry is None and dim % dsize == 0 and dim > best_dim:
            best, best_dim = i, dim
    if best is not None:
        entries[best] = data_axis
    return P(*entries)


def fsdp_extend_tree(spec_tree, shape_tree, axis_sizes, data_axis):
    return jax.tree.map(
        lambda s, x: fsdp_extend_spec(s, x.shape, axis_sizes, data_axis),
        spec_tree, shape_tree,
        is_leaf=lambda v: isinstance(v, P))
