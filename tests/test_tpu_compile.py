"""Compile rehearsals: the Pallas kernels, compiled by Mosaic for v5e.

Every other kernel test runs the Pallas interpreter, which knows no
tiling rule and no VMEM limit.  Here each kernel of the main path is
compiled for a described (not attached) TPU v5e at the paper's widths
with ``interpret=False``, so a block shape Mosaic refuses or a block
that overflows VMEM fails here, at no chip time.  Nothing runs.

The topology is described inside a module fixture, never at import:
only one process may load the TPU compiler library, and under
pytest-xdist every worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.dantzig import DantzigConfig
from repro.core.solver_dispatch import select_solver
from repro.kernels.dantzig_fused import (
    AdmmState,
    dantzig_fused_pallas,
    pick_block_k,
)
from repro.kernels.gram import gram_pallas

ITERS = 20  # the loop trip count does not change what Mosaic allocates


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    def make(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


@pytest.mark.parametrize("n,d", [(1000, 200), (2000, 1024)])
def test_gram_compiles(shape, n, d):
    text = _compiled_text(
        lambda x, mu: gram_pallas(x, mu, interpret=False),
        shape(n, d), shape(d))
    assert "tpu_custom_call" in text


def test_gram_vmapped_over_machines_compiles(shape):
    """The simulation vmaps the gram kernel over its m = 10 machines."""
    m, n, d = 10, 1000, 200
    text = _compiled_text(
        jax.vmap(lambda x, mu: gram_pallas(x, mu, interpret=False)),
        shape(m, n, d), shape(m, d))
    assert "tpu_custom_call" in text


def _fused(shape, d, k, block_k, state_io):
    """Compile one fused launch as the dispatch would issue it."""
    ops = (shape(d, d), shape(d, d), shape(d), shape(d, k), shape(k),
           shape(k))
    if not state_io:
        return _compiled_text(
            lambda a, q, inv, b, lam, rho: dantzig_fused_pallas(
                a, q, inv, b, lam, rho, iters=ITERS, block_k=block_k,
                interpret=False),
            *ops)
    return _compiled_text(
        lambda a, q, inv, b, lam, rho, *st: dantzig_fused_pallas(
            a, q, inv, b, lam, rho, iters=ITERS, block_k=block_k,
            tol=1e-3, state=AdmmState(*st), return_info=True,
            interpret=False),
        *ops, *(shape(d, k) for _ in range(4)))


@pytest.mark.parametrize("state_io", [False, True], ids=["fixed", "state"])
@pytest.mark.parametrize("d", [200, 512, 1024])
def test_fused_kernel_compiles_at_picked_block(shape, d, state_io):
    """Every CLIME batch (k = d) that dispatch routes to a fused path
    compiles at the block ``pick_block_k`` chose; where none fits, the
    dispatch must have picked scan instead."""
    k = d
    cfg = DantzigConfig(fused=True, tol=1e-3 if state_io else None)
    choice = select_solver(cfg, d, k)
    bk = pick_block_k(d, k, state_io=state_io)
    if bk is None:
        assert choice.kind == "scan"
        return
    assert choice.block_k == bk
    assert bk == k or bk % 128 == 0
    assert "tpu_custom_call" in _fused(shape, d, k, bk, state_io)


def test_fused_blocked_state_kernel_compiles_over_many_blocks(shape):
    """The state kernel's per-block iteration counts compile for any
    number of blocks (a (1, 1) block of them used to be refused)."""
    d, k = 256, 1000
    assert "tpu_custom_call" in _fused(shape, d, k, 128, state_io=True)

