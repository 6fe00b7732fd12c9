"""Solver dispatch rules: scan vs fused vs fused-blocked selection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dantzig import DantzigConfig, solve_dantzig, solve_dantzig_scan
from repro.core.solver_dispatch import (
    DEFAULT_VMEM_BUDGET,
    SolverChoice,
    backend_vmem_budget,
    select_solver,
    fused_block_vmem_bytes,
)
from repro.core import solver_dispatch
from repro.kernels import dantzig_fused
from repro.kernels.dantzig_fused import CHIP_VMEM_BYTES, chip_vmem_bytes
from repro.stats.synthetic import ar1_covariance


def test_scan_selected_when_fused_off():
    assert select_solver(DantzigConfig(), 64, 64) == SolverChoice("scan")
    assert select_solver(DantzigConfig(fused=False), 2048, 2048).kind == "scan"


def test_fused_single_block_for_small_shapes():
    choice = select_solver(DantzigConfig(fused=True), 256, 64)
    assert choice == SolverChoice("fused", 64)
    assert fused_block_vmem_bytes(256, 64) <= DEFAULT_VMEM_BUDGET


def test_fused_blocked_for_wide_batches():
    choice = select_solver(DantzigConfig(fused=True), 768, 4096)
    assert choice.kind == "fused_blocked"
    assert 0 < choice.block_k < 4096 and choice.block_k % 128 == 0
    assert fused_block_vmem_bytes(768, choice.block_k) <= DEFAULT_VMEM_BUDGET


def test_scan_fallback_when_operands_exceed_vmem():
    # A + Q alone are 2 * 4096^2 * 4 B = 128 MiB, double-buffered 256 MiB
    assert select_solver(DantzigConfig(fused=True), 4096, 8).kind == "scan"


def test_explicit_block_k_override():
    choice = select_solver(DantzigConfig(fused=True, block_k=16), 64, 64)
    assert choice == SolverChoice("fused_blocked", 16)
    # override is clamped to the batch width
    choice = select_solver(DantzigConfig(fused=True, block_k=999), 64, 8)
    assert choice == SolverChoice("fused", 8)


def test_dispatch_entry_matches_scan_and_squeezes():
    d = 30
    a = jnp.asarray(ar1_covariance(d, 0.6), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(0), (d,))
    cfg_scan = DantzigConfig(max_iters=200, adapt_rho=False)
    cfg_fused = DantzigConfig(max_iters=200, adapt_rho=False, fused=True)
    out_scan = solve_dantzig(a, b, 0.1, cfg_scan)
    out_fused = solve_dantzig(a, b, 0.1, cfg_fused)
    assert out_scan.shape == out_fused.shape == (d,)
    np.testing.assert_allclose(np.asarray(out_scan), np.asarray(out_fused),
                               atol=1e-4)
    # the shim in core.dantzig and the dispatch entry are the same path
    out_direct = solver_dispatch.solve_dantzig(a, b, 0.1, cfg_scan)
    np.testing.assert_array_equal(np.asarray(out_scan), np.asarray(out_direct))


def test_output_dtype_uniform_across_paths():
    """b.dtype out on BOTH paths: toggling cfg.fused never changes it."""
    d = 16
    a = jnp.asarray(ar1_covariance(d, 0.5), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(2), (d, 2)).astype(jnp.bfloat16)
    for fused in (False, True):
        cfg = DantzigConfig(max_iters=50, adapt_rho=False, fused=fused)
        assert solve_dantzig(a, b, 0.1, cfg).dtype == jnp.bfloat16


def test_scan_accepts_warm_rho_seed():
    """rho0 seeds the adaptive state; a converged solve is insensitive."""
    d, k = 24, 5
    a = jnp.asarray(ar1_covariance(d, 0.5), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (d, k))
    base = solve_dantzig_scan(a, b, 0.1, DantzigConfig(max_iters=1200))
    warm = solve_dantzig(a, b, 0.1, DantzigConfig(max_iters=1200),
                         rho=jnp.full((k,), 2.0))
    np.testing.assert_allclose(np.asarray(base), np.asarray(warm), atol=5e-4)


class _FakeChip:
    def __init__(self, kind):
        self.device_kind = kind


def test_backend_budgets_drive_selection(monkeypatch):
    """The backend parameter is live: it resolves the fast-memory budget."""
    # cpu mirrors the target chip so interpreter-validated shapes pick
    # the path they will pick on the chip
    assert backend_vmem_budget("cpu") == DEFAULT_VMEM_BUDGET
    # the active backend is the default (this suite runs on cpu)
    assert backend_vmem_budget() == backend_vmem_budget(
        jax.default_backend())
    # tpu reads the attached chip's device_kind ...
    monkeypatch.setattr(dantzig_fused.jax, "devices",
                        lambda *_: [_FakeChip("TPU v5 lite")])
    assert backend_vmem_budget("tpu") == DEFAULT_VMEM_BUDGET
    cfg = DantzigConfig(fused=True)
    assert select_solver(cfg, 256, 64, backend="tpu").kind == "fused"
    # ... and a chip with no VMEM figure is an error, not a default
    monkeypatch.setattr(dantzig_fused.jax, "devices",
                        lambda *_: [_FakeChip("TPU v2")])
    with pytest.raises(ValueError, match="TPU v2"):
        backend_vmem_budget("tpu")
    # so is a backend the model does not know
    with pytest.raises(ValueError, match="wasm"):
        backend_vmem_budget("wasm")


def test_backend_budget_exact_values():
    """The budget constants are part of the dispatch contract.

    v5e has 128 MiB of VMEM per core (its compiler's own figure); the
    blocking model plans for 3/4 of it and CPU mirrors the chip.  A
    change here silently reroutes every shape's scan/fused/
    fused_blocked decision, so the exact numbers are pinned, not just
    their ordering.
    """
    assert CHIP_VMEM_BYTES["TPU v5 lite"] == 128 * 2**20
    assert chip_vmem_bytes("cpu") == 128 * 2**20
    assert backend_vmem_budget("cpu") == DEFAULT_VMEM_BUDGET == 96 * 2**20


def test_gpu_scan_fallback_boundary():
    """GPU has no VMEM model and raises; under a tight explicit budget the
    dispatch fuses small d, tiles mid d in 128-column blocks, and bails
    where A + Q alone bust the budget."""
    with pytest.raises(ValueError, match="gpu"):
        select_solver(DantzigConfig(fused=True), 64, 8, backend="gpu")
    budget = fused_block_vmem_bytes(256, 128)
    cfg = DantzigConfig(fused=True, vmem_budget=budget)
    # d=256, 64 columns: the whole batch fits one block
    assert select_solver(cfg, 256, 64) == SolverChoice("fused", 64)
    # 512 columns: tiled at the 128-lane granularity
    assert select_solver(cfg, 256, 512) == SolverChoice("fused_blocked", 128)
    # d=512: A + Q alone exceed the budget -- not even one column fits,
    # and the fallback ignores any explicit block_k override
    assert select_solver(cfg, 512, 1).kind == "scan"
    assert select_solver(cfg._replace(block_k=1), 512, 1).kind == "scan"


def test_state_io_footprint_drives_gpu_selection():
    """The adaptive kernel's larger footprint shrinks the block.

    ``cfg.tol`` routes to the adaptive kernel, whose streamed-in/out
    ADMM state costs eight more (d, block_k) buffers -- under a tight
    budget that is visible as a smaller block for the SAME shape.  An
    explicit ``state_io`` overrides the cfg derivation.
    """
    d, k = 256, 1024
    budget = fused_block_vmem_bytes(d, 512)
    fixed = select_solver(DantzigConfig(fused=True, vmem_budget=budget), d, k)
    adaptive = select_solver(
        DantzigConfig(fused=True, tol=1e-4, vmem_budget=budget), d, k)
    assert fixed == SolverChoice("fused_blocked", 512)
    assert adaptive.kind == "fused_blocked"
    assert adaptive.block_k < fixed.block_k
    assert select_solver(DantzigConfig(fused=True, vmem_budget=budget), d, k,
                         state_io=True) == adaptive


def test_cfg_vmem_budget_overrides_backend():
    """DantzigConfig.vmem_budget wins over any backend derivation."""
    # a budget too small for even one column at d=256 forces scan on
    # every backend
    tiny = DantzigConfig(fused=True, vmem_budget=100_000)
    assert select_solver(tiny, 256, 64).kind == "scan"
    assert select_solver(tiny, 256, 64, backend="cpu").kind == "scan"
    # a budget big enough for one block keeps the whole batch fused
    # even where the backend budget would have tiled or bailed
    huge = DantzigConfig(fused=True, vmem_budget=2**30)
    assert select_solver(huge, 256, 512) == SolverChoice("fused", 512)
    # and the end-to-end solve under an explicit budget stays exact
    d = 32
    a = jnp.asarray(ar1_covariance(d, 0.6), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(3), (d, 6))
    base = solve_dantzig(a, b, 0.1,
                         DantzigConfig(max_iters=150, adapt_rho=False))
    for budget in (100_000, 2**26):
        cfg = DantzigConfig(max_iters=150, adapt_rho=False, fused=True,
                            vmem_budget=budget)
        np.testing.assert_allclose(
            np.asarray(solve_dantzig(a, b, 0.1, cfg)), np.asarray(base),
            atol=1e-4)


def test_clime_forwards_warm_rho():
    from repro.core.clime import solve_clime_columns

    d = 32
    a = jnp.asarray(ar1_covariance(d, 0.6), jnp.float32)
    cols = jnp.asarray([0, 5, 31])
    cfg = DantzigConfig(max_iters=400, adapt_rho=False, fused=True)
    cold = solve_clime_columns(a, cols, 0.1, cfg)
    warm = solve_clime_columns(a, cols, 0.1, cfg,
                               rho=jnp.ones((3,), jnp.float32))
    np.testing.assert_allclose(np.asarray(cold), np.asarray(warm), atol=1e-6)
