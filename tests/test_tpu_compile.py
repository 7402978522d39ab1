"""Ahead-of-time compiles of the three Pallas kernels for a v5e chip.

Interpret mode on the CPU accepts tilings and memory footprints that the
TPU compiler refuses, so the kernels are compiled here for a *described*
v5e chip at real widths: r=128 float32, the block count of the Graph500
R-MAT graph of scale 22 packed at row_tile = nz_block = 32 (~2M blocks),
and 2^22 rows of B.  The two-phase FusedMM needs r above one 128-lane
slab; at r=256 it runs on 2^21 rows, since at 2^22 the program (A, B,
the output and B re-laid out in 128-lane rows) needs 19.1 GB.  Nothing
runs; a refusal or an over-budget program fails the test.  The topology
is described inside a fixture, never at import, so only the worker that
runs this file loads the TPU compiler.
"""
import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.fusedmm import fusedmm_pallas
from repro.kernels.sddmm import sddmm_pallas
from repro.kernels.spmm import spmm_pallas

HBM_BYTES = 16 * 1024 ** 3          # one v5e chip
N_B = 1 << 22                       # rows of A and B (scale-22 graph)
NB = 1 << 21                        # nonzero blocks of the scale-22 pack
K = 32
ROW_TILE = 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(one_chip, kernel, r, n_b=N_B, **kw):
    pack = (_spec((NB,), jnp.int32, one_chip),
            _spec((NB, K), jnp.int32, one_chip),
            _spec((NB, K), jnp.int32, one_chip),
            _spec((NB, K), jnp.float32, one_chip))
    dense = _spec((n_b, r), jnp.float32, one_chip)
    kw = dict(row_tile=ROW_TILE, **kw)
    if kernel is spmm_pallas:
        fn = functools.partial(kernel, m=n_b, **kw)
        args = pack + (dense,)
    else:
        if kernel is fusedmm_pallas:
            kw["m"] = n_b
        fn = functools.partial(kernel, **kw)
        args = pack + (dense, dense)
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kernel,r,tiling", [
    (sddmm_pallas, 128, {}),
    (spmm_pallas, 128, {}),
    (spmm_pallas, 128, dict(blocks_per_step=4)),
    (fusedmm_pallas, 128, {}),                       # single-phase
    (fusedmm_pallas, 256, dict(r_tile=128, n_b=N_B // 2)),   # two-phase
], ids=["sddmm", "spmm", "spmm-bps4", "fusedmm", "fusedmm-two-phase"])
def test_kernel_compiles_for_v5e(one_chip, kernel, r, tiling):
    compiled = _compile(one_chip, kernel, r, **tiling)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= HBM_BYTES, used
