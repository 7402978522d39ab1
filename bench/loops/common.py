"""What the loops share: the configured graph, seeded streams, and the
program's problem as a configuration states it."""
from __future__ import annotations

import zlib

import numpy as np

#: what the program runs: float32 operands, every product at
#: ``Precision.HIGHEST`` (the kernels have no other path today)
DTYPE, PRECISION = "float32", "highest"


def rng(seed: int, stream: str) -> np.random.Generator:
    """A stream of its own for each use of the run's seed; any whole
    number is a seed."""
    return np.random.default_rng([seed % 2 ** 64,
                                  zlib.crc32(stream.encode())])


def uniform(gen: np.random.Generator, shape, scale: float = 1.0):
    """float32 uniform in [-scale, scale), made in place."""
    x = gen.random(shape, np.float32)
    x *= 2 * scale
    x -= scale
    return x


class Reservoir:
    """A sample of ``k`` units drawn from the seed, uniform over however
    many the window completes, decided as each unit starts, so that only
    the kept units' answers are held (reservoir sampling)."""

    def __init__(self, seed: int, k: int):
        self.k, self.seen, self.kept = k, 0, []
        self._gen = rng(seed, "sample")

    def offer(self):
        """(keep the next unit?, the kept unit it replaces or None)."""
        i, self.seen = self.seen, self.seen + 1
        if len(self.kept) < self.k:
            self.kept.append(i)
            return True, None
        j = int(self._gen.integers(0, i + 1))
        if j >= self.k:
            return False, None
        out, self.kept[j] = self.kept[j], i
        return True, out


def graph(spec: dict):
    """(rows, cols, vals, m, n) of the configuration's matrix.  The matrix is
    the deployment's data: it comes from the configuration's own seed,
    never the run's, so every run has the same shapes and programs."""
    from repro.core import sparse
    if spec["generator"] == "rmat":
        rows, cols, vals = sparse.rmat(
            spec["scale"], spec["edge_factor"], seed=spec["graph_seed"],
            a=spec["a"], b=spec["b"], c=spec["c"])
        m = n = 1 << spec["scale"]
    elif spec["generator"] == "erdos_renyi":
        m, n = spec["m"], spec["n"]
        rows, cols, vals = sparse.erdos_renyi(m, n, spec["nnz_per_row"],
                                              seed=spec["graph_seed"])
    else:
        raise ValueError(f"unknown generator {spec['generator']!r}")
    if spec["values"] == "ones":
        vals = np.ones_like(vals)
    elif spec["values"] == "ratings":       # as repro.apps.als makes them
        vals = np.abs(vals) + np.float32(0.5)
    else:
        raise ValueError(f"unknown values {spec['values']!r}")
    return rows, cols, vals, m, n


def make_problem(config: dict, rows, cols, vals, m: int, n: int, devices):
    """The program's DistProblem as the configuration states it, with its
    normal pack planned."""
    from repro.core import api
    if (config["dtype"], config["precision"]) != (DTYPE, PRECISION):
        raise ValueError(f"the program runs {DTYPE} at {PRECISION}, the "
                         f"configuration states {config['dtype']} at "
                         f"{config['precision']}")
    prob = api.make_problem(rows, cols, vals, (m, n), config["r"],
                            algorithm=config["algorithm"], devices=devices,
                            row_tile=config["row_tile"],
                            nz_block=config["nz_block"])
    prob.plan("normal")
    return prob


def describe(prob) -> dict:
    return {"family": prob.alg.name, "p": prob.p, "c": prob.c}
