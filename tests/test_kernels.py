"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Kernels run under interpret=True on CPU (the kernel body itself is
executed); on a TPU host the same tests exercise the Mosaic path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.gram import gram_pallas
from repro.kernels.soft_threshold import soft_threshold_pallas


@pytest.mark.parametrize("n,d", [(8, 8), (32, 16), (100, 50), (257, 130), (64, 200)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_matches_ref(n, d, dtype):
    key = jax.random.PRNGKey(n * 1000 + d)
    x = jax.random.normal(key, (n, d)).astype(dtype)
    mu = jnp.mean(x.astype(jnp.float32), axis=0).astype(dtype)
    out = gram_pallas(x, mu, block_n=32, block_d=16, interpret=True)
    expected = ref.gram_ref(x.astype(jnp.float32), mu.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 0.35
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("blocks", [(8, 8), (16, 64), (128, 128)])
def test_gram_block_shapes(blocks):
    bn, bd = blocks
    x = jax.random.normal(jax.random.PRNGKey(0), (48, 24))
    mu = jnp.mean(x, axis=0)
    out = gram_pallas(x, mu, block_n=bn, block_d=bd, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.gram_ref(x, mu)),
                               rtol=1e-4, atol=1e-3)


def test_gram_padding_rows_are_neutral():
    # n not a multiple of block: padded rows must contribute zero
    x = jax.random.normal(jax.random.PRNGKey(1), (13, 8))
    mu = jnp.mean(x, axis=0)
    out = gram_pallas(x, mu, block_n=8, block_d=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.gram_ref(x, mu)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", [(16,), (7,), (4, 36), (130, 600), (1, 1)])
@pytest.mark.parametrize("t", [0.0, 0.05, 1.5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_soft_threshold_matches_ref(shape, t, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(42), shape) * 2).astype(dtype)
    out = soft_threshold_pallas(x, t, block_r=8, block_c=16, interpret=True)
    expected = ref.soft_threshold_ref(x, jnp.asarray(t, dtype))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        rtol=1e-3, atol=1e-3,
    )


def test_soft_threshold_in_solver_path():
    """The kernel-enabled Dantzig solve agrees with the jnp path."""
    from repro.core.dantzig import DantzigConfig, solve_dantzig
    from repro.stats.synthetic import ar1_covariance

    d = 24
    a = jnp.asarray(ar1_covariance(d, 0.6), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(7), (d,))
    x_plain = solve_dantzig(a, b, 0.1, DantzigConfig(max_iters=300, use_kernel=False))
    x_kern = solve_dantzig(a, b, 0.1, DantzigConfig(max_iters=300, use_kernel=True))
    np.testing.assert_allclose(np.asarray(x_plain), np.asarray(x_kern),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fused Dantzig/CLIME ADMM solve (SSPerf-A2)
# ---------------------------------------------------------------------------

from repro.core.dantzig import DantzigConfig, kkt_violation, solve_dantzig  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.dantzig_fused import dantzig_fused_pallas  # noqa: E402
from repro.stats.synthetic import ar1_covariance  # noqa: E402


@pytest.mark.parametrize("d,k,iters", [(16, 1, 50), (64, 4, 200), (40, 16, 120),
                                       (128, 8, 80)])
def test_dantzig_fused_matches_oracle(d, k, iters):
    a = jnp.asarray(ar1_covariance(d, 0.7), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(d + k), (d, k))
    lam = 0.1
    evals, q = jnp.linalg.eigh(a)
    inv = 1.0 / (evals**2 + 1.0)
    out_k = dantzig_fused_pallas(a, q, inv, b, lam, iters=iters, interpret=True)
    out_r = ref.dantzig_fused_ref(a, q, inv, b, lam, iters=iters)
    # f32 accumulation-order drift grows with iteration count
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=5e-4 * (iters / 50), rtol=1e-3)


def test_dantzig_fused_matches_scan_solver():
    """The kernel and the lax.scan solver share hyperparams -> same sol."""
    d = 48
    a = jnp.asarray(ar1_covariance(d, 0.8), jnp.float32)
    # realistic CLIME right-hand sides (unit vectors) -- bounded solutions
    b = jnp.eye(d)[:, ::12]
    lam = 0.08
    out_k = ops.dantzig_fused(a, b, lam, iters=300)
    out_s = solve_dantzig(a, b, lam, DantzigConfig(max_iters=300, adapt_rho=False))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_s),
                               atol=5e-3, rtol=5e-3)
    # and both are near-feasible
    assert float(jnp.max(kkt_violation(a, b, out_k, lam))) < 0.05


def test_dantzig_fused_single_rhs_squeeze():
    d = 32
    a = jnp.asarray(ar1_covariance(d, 0.5), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(4), (d,))
    out = ops.dantzig_fused(a, b, 0.2, iters=200)
    assert out.shape == (d,)
    assert float(jnp.max(kkt_violation(a, b, out, 0.2))) < 0.02


# ---------------------------------------------------------------------------
# blocked grid: fused-vs-scan parity sweep (incl. non-multiple tail block)
# ---------------------------------------------------------------------------

from repro.core.solver_dispatch import select_solver  # noqa: E402
from repro.kernels.dantzig_fused import (  # noqa: E402
    fused_block_vmem_bytes, pick_block_k,
)


def _scan_reference(a, b, lam, iters):
    """Scan solver with the fused kernel's hyperparams (fixed rho=1)."""
    return solve_dantzig(a, b, lam,
                         DantzigConfig(max_iters=iters, adapt_rho=False))


@pytest.mark.parametrize("d,k", [(64, 1), (256, 64), (300, 7)])
def test_fused_blocked_parity_sweep(d, k):
    """Fused (auto-blocked) matches scan to 1e-4 max-abs on any shape."""
    a = jnp.asarray(ar1_covariance(d, 0.6), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(d * 31 + k), (d, k)) * 0.5
    lam, iters = 0.1, 200
    out_f = ops.dantzig_fused(a, b, lam, iters=iters)
    out_s = _scan_reference(a, b, lam, iters)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_s), atol=1e-4)
    # both near-feasible: the fused path obeys the same KKT bound
    kkt_f = float(jnp.max(kkt_violation(a, b, out_f, lam)))
    kkt_s = float(jnp.max(kkt_violation(a, b, out_s, lam)))
    assert kkt_f < max(2 * kkt_s, 5e-2)


def test_fused_explicit_blocking_with_tail_is_exact():
    """Forcing a tail block (k % block_k != 0) changes nothing: columns
    are independent and the pad columns are inert."""
    d, k = 48, 10
    a = jnp.asarray(ar1_covariance(d, 0.7), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(5), (d, k))
    one_block = ops.dantzig_fused(a, b, 0.1, iters=150)
    tail_blocked = ops.dantzig_fused(a, b, 0.1, iters=150, block_k=4)
    # bitwise under the interpreter; Mosaic may differ in the last ulp
    np.testing.assert_allclose(np.asarray(one_block), np.asarray(tail_blocked),
                               atol=1e-6, rtol=0)


def test_fused_blocked_past_single_block_vmem():
    """A shape whose single-block footprint exceeds the budget still
    matches the scan solver once the dispatch tiles it over the grid in
    128-column blocks (the only width Mosaic accepts below the batch)."""
    d, k, iters = 256, 384, 25
    budget = fused_block_vmem_bytes(d, 128)
    assert fused_block_vmem_bytes(d, k) > budget
    bk = pick_block_k(d, k, budget)
    assert bk == 128  # must be tiled, lane-aligned
    choice = select_solver(DantzigConfig(fused=True, vmem_budget=budget), d, k)
    assert choice.kind == "fused_blocked" and choice.block_k == bk
    a = jnp.asarray(ar1_covariance(d, 0.5), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(6), (d, k)) * 0.3
    out_f = ops.dantzig_fused(a, b, 0.15, iters=iters, vmem_budget=budget)
    out_s = _scan_reference(a, b, 0.15, iters)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_s), atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_output_dtype_matches_rhs(dtype):
    """ops.dantzig_fused returns b.dtype (it used to pin float32)."""
    d = 32
    a = jnp.asarray(ar1_covariance(d, 0.5), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(7), (d, 3)).astype(dtype)
    out = ops.dantzig_fused(a, b, 0.1, iters=100)
    assert out.dtype == dtype
    if dtype == jnp.float32:
        # parity with the scan path, which also returns f32 here
        out_s = _scan_reference(a, b, 0.1, 100)
        assert out_s.dtype == out.dtype
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_s), atol=1e-4)
    else:
        # values agree with the f32 solve up to bf16 resolution
        out32 = ops.dantzig_fused(a, b.astype(jnp.float32), 0.1, iters=100)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(out32), atol=2e-2)


def test_fused_per_column_rho_operand():
    """rho is a (k,) operand: per-column values match the oracle and a
    second rho value reuses the compiled kernel (no retrace)."""
    d, k = 40, 6
    a = jnp.asarray(ar1_covariance(d, 0.6), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(8), (d, k))
    evals, q = jnp.linalg.eigh(a)
    inv = 1.0 / (evals**2 + 1.0)
    rhos = jnp.linspace(0.5, 2.0, k)
    out = dantzig_fused_pallas(a, q, inv, b, 0.1, rhos, iters=120,
                               block_k=4, interpret=True)
    out_ref = ref.dantzig_fused_ref(a, q, inv, b, 0.1, rho=rhos, iters=120)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-4, rtol=1e-3)
    n_compiled = dantzig_fused_pallas._cache_size()
    dantzig_fused_pallas(a, q, inv, b, 0.1, rhos * 1.5, iters=120,
                         block_k=4, interpret=True)
    assert dantzig_fused_pallas._cache_size() == n_compiled


# ---------------------------------------------------------------------------
# trace pins via repro.analysis: launch count + VMEM conformance
# ---------------------------------------------------------------------------

from repro.analysis import VmemConformance, count_eqns  # noqa: E402


def test_fused_blocked_trace_conforms_to_vmem_model():
    """The traced BlockMappings of a tiled launch satisfy the analytic
    footprint model -- and a deliberately tiny budget trips the contract
    with the offending launch located in the report."""
    d, k = 48, 10
    a = jnp.asarray(ar1_covariance(d, 0.7), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(9), (d, k))
    jaxpr = jax.make_jaxpr(
        lambda a, b: ops.dantzig_fused(a, b, 0.1, iters=50, block_k=4))(a, b)
    assert count_eqns(jaxpr, "pallas_call") == 1
    assert VmemConformance().check(jaxpr) == []
    violations = VmemConformance(budget=1024).check(jaxpr)
    assert violations, "1 KiB budget must trip the conformance contract"
    assert any("pallas_call" in site for v in violations for site in v.sites)


def test_vmem_conformance_flags_an_unreadable_launch():
    """A fused launch whose block mappings cannot be read is itself a
    violation: the contract never passes by skipping it."""
    from types import SimpleNamespace as NS

    launch = NS(primitive=NS(name="pallas_call"),
                params={"name": "fused_admm"}, outvars=[])
    violations = VmemConformance().check(NS(eqns=[launch]))
    assert len(violations) == 1
    assert "could not read" in violations[0].message


def test_tol_mode_state_kernel_trace_conforms_to_vmem_model():
    """tol-mode launches the state-I/O kernel (10 operands): the checker
    must pick up state_io=True and still conform."""
    d, k = 32, 6
    a = jnp.asarray(ar1_covariance(d, 0.5), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(10), (d, k))
    cfg = DantzigConfig(max_iters=60, adapt_rho=False, fused=True, tol=1e-3)
    from repro.core.solver_dispatch import solve_dantzig_full

    jaxpr = jax.make_jaxpr(
        lambda a, b: solve_dantzig_full(a, b, 0.1, cfg))(a, b)
    assert count_eqns(jaxpr, "pallas_call") == 1
    assert VmemConformance().check(jaxpr) == []
