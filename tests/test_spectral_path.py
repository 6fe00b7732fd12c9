"""Pins for the single-factorization contract and the lambda-path fold.

* eigh-count regression: the jaxpr of a jitted ``worker_debiased`` (and
  of a whole lambda-path sweep) contains EXACTLY ONE ``eigh`` -- the
  direction solve, the CLIME solve, and every grid point share the
  worker's SpectralFactor.
* fold parity: ``solve_dantzig_path`` matches L independent
  ``solve_dantzig`` calls to 1e-5 on the scan, fused, and fused_blocked
  dispatch paths.
* factor-acceptance: every solver entry point takes a SpectralFactor
  in place of the raw matrix and returns the same solution.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import check_entry, count_eqns
from repro.core import path as rpath
from repro.core import pipeline, slda
from repro.core.clime import solve_clime, solve_clime_columns
from repro.core.dantzig import (
    DantzigConfig,
    SpectralFactor,
    solve_dantzig_scan,
    spectral_factor,
)
from repro.core.pipeline import BinaryHead, MulticlassHead
from repro.core.solver_dispatch import solve_dantzig
from repro.kernels import ops as kops
from repro.stats.synthetic import ar1_covariance


def _ar1(d, rho=0.6):
    return jnp.asarray(ar1_covariance(d, rho), jnp.float32)


# ---------------------------------------------------------------------------
# eigh-count regression (the tentpole's contract, pinned structurally)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [False, True])
def test_worker_debiased_traces_exactly_one_eigh(fused):
    """Direction solve + CLIME solve = ONE factorization, on both paths."""
    cfg = DantzigConfig(max_iters=30, adapt_rho=False, fused=fused)
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 12))
    y = jax.random.normal(jax.random.PRNGKey(1), (44, 12))

    def worker(x, y):
        return pipeline.worker_debiased(
            BinaryHead(), x, y, lam=0.1, lam_prime=0.1, cfg=cfg)

    jaxpr = jax.make_jaxpr(worker)(x, y)
    assert count_eqns(jaxpr, "eigh") == 1


def test_multiclass_worker_traces_exactly_one_eigh():
    cfg = DantzigConfig(max_iters=30, adapt_rho=False, fused=True)
    x = jax.random.normal(jax.random.PRNGKey(2), (60, 10))
    labels = jax.random.randint(jax.random.PRNGKey(3), (60,), 0, 3)

    def worker(x, labels):
        return pipeline.worker_debiased(
            MulticlassHead(3), x, labels, lam=0.1, lam_prime=0.1, cfg=cfg)

    jaxpr = jax.make_jaxpr(worker)(x, labels)
    assert count_eqns(jaxpr, "eigh") == 1


@pytest.mark.parametrize("fused", [False, True])
def test_lambda_path_sweep_traces_exactly_one_eigh(fused):
    """An entire L-point sweep (direction path + CLIME) = ONE eigh."""
    cfg = DantzigConfig(max_iters=30, adapt_rho=False, fused=fused)
    lams = jnp.linspace(0.05, 0.4, 6)
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 12))
    y = jax.random.normal(jax.random.PRNGKey(5), (44, 12))

    def sweep(x, y):
        return rpath.worker_debiased_path(
            BinaryHead(), x, y, lams=lams, lam_prime=0.1, cfg=cfg)

    jaxpr = jax.make_jaxpr(sweep)(x, y)
    assert count_eqns(jaxpr, "eigh") == 1


def test_solve_with_factor_traces_zero_eigh():
    """A solve handed a factor never re-factorizes."""
    a = _ar1(16)
    factor = spectral_factor(a)
    b = jax.random.normal(jax.random.PRNGKey(6), (16, 2))
    for fused in (False, True):
        cfg = DantzigConfig(max_iters=20, adapt_rho=False, fused=fused)
        jaxpr = jax.make_jaxpr(
            lambda f, b: solve_dantzig(f, b, 0.1, cfg))(factor, b)
        assert count_eqns(jaxpr, "eigh") == 0, f"fused={fused}"


def test_adaptive_worker_traces_one_eigh():
    """tol-mode (the while_loop kernel) keeps the one-eigh contract."""
    cfg = DantzigConfig(max_iters=50, adapt_rho=False, fused=True, tol=1e-3)
    x = jax.random.normal(jax.random.PRNGKey(20), (40, 12))
    y = jax.random.normal(jax.random.PRNGKey(21), (44, 12))

    def worker(x, y):
        return pipeline.worker_debiased(
            BinaryHead(), x, y, lam=0.1, lam_prime=0.1, cfg=cfg)

    jaxpr = jax.make_jaxpr(worker)(x, y)
    assert count_eqns(jaxpr, "eigh") == 1


def test_adaptive_sweep_traces_one_eigh_and_one_launch_per_solve():
    """With tol-mode on, an ENTIRE folded sweep still traces ONE eigh
    and ONE kernel launch for the direction fold (plus exactly one for
    the shared CLIME solve) -- the early exit lives INSIDE the kernel,
    it does not fragment the launch."""
    cfg = DantzigConfig(max_iters=50, adapt_rho=False, fused=True, tol=1e-3)
    lams = jnp.linspace(0.05, 0.4, 6)
    x = jax.random.normal(jax.random.PRNGKey(22), (40, 12))
    y = jax.random.normal(jax.random.PRNGKey(23), (44, 12))

    def sweep(x, y):
        return rpath.worker_debiased_path(
            BinaryHead(), x, y, lams=lams, lam_prime=0.1, cfg=cfg)

    jaxpr = jax.make_jaxpr(sweep)(x, y)
    assert count_eqns(jaxpr, "eigh") == 1
    assert count_eqns(jaxpr, "pallas_call") == 2
    # the registered contract set agrees (incl. dtype + VMEM conformance)
    violations = check_entry("path.worker_debiased_path", jaxpr,
                             {"pallas_calls": 2})
    assert violations == [], violations

    # warm re-sweep: threading rho AND full state changes neither count
    res = sweep(x, y)

    def resweep(x, y, rho, state):
        return rpath.worker_debiased_path(
            BinaryHead(), x, y, lams=lams, lam_prime=0.1, cfg=cfg,
            rho_beta=rho, state_beta=state)

    jaxpr = jax.make_jaxpr(resweep)(x, y, res.rho_beta, res.state_beta)
    assert count_eqns(jaxpr, "eigh") == 1
    assert count_eqns(jaxpr, "pallas_call") == 2


# ---------------------------------------------------------------------------
# lambda-path fold parity: one wide launch == L independent launches
# ---------------------------------------------------------------------------


PATH_CFGS = [
    ("scan", DantzigConfig(max_iters=200, adapt_rho=False)),
    ("fused", DantzigConfig(max_iters=200, adapt_rho=False, fused=True)),
    ("fused_blocked",
     DantzigConfig(max_iters=200, adapt_rho=False, fused=True, block_k=4)),
]


@pytest.mark.parametrize("name,cfg", PATH_CFGS, ids=[c[0] for c in PATH_CFGS])
def test_solve_dantzig_path_matches_sequential(name, cfg):
    d, k, L = 40, 3, 5
    a = _ar1(d)
    b = jax.random.normal(jax.random.PRNGKey(7), (d, k)) * 0.4
    lams = jnp.linspace(0.05, 0.4, L)
    res = rpath.solve_dantzig_path(a, b, lams, cfg)
    assert res.beta.shape == (L, d, k)
    assert res.kkt.shape == (L, k) and res.rho.shape == (L, k)
    for i in range(L):
        seq = solve_dantzig(a, b, float(lams[i]), cfg)
        np.testing.assert_allclose(
            np.asarray(res.beta[i]), np.asarray(seq), atol=1e-5,
            err_msg=f"{name} lambda[{i}]")


def test_solve_dantzig_path_vector_rhs_squeezes():
    d, L = 24, 4
    a = _ar1(d)
    b = jax.random.normal(jax.random.PRNGKey(8), (d,)) * 0.4
    lams = jnp.linspace(0.1, 0.4, L)
    cfg = DantzigConfig(max_iters=150, adapt_rho=False, fused=True)
    res = rpath.solve_dantzig_path(a, b, lams, cfg)
    assert res.beta.shape == (L, d)
    assert res.kkt.shape == (L,)
    for i in range(L):
        np.testing.assert_allclose(
            np.asarray(res.beta[i]),
            np.asarray(solve_dantzig(a, b, float(lams[i]), cfg)), atol=1e-5)


def test_worker_path_matches_single_lambda_worker():
    """Each grid point of the folded worker sweep reproduces the
    single-lambda pipeline (same CLIME radius)."""
    cfg = DantzigConfig(max_iters=150, adapt_rho=False, fused=True)
    lams = jnp.linspace(0.08, 0.4, 4)
    x = jax.random.normal(jax.random.PRNGKey(9), (80, 20))
    y = jax.random.normal(jax.random.PRNGKey(10), (90, 20)) + 0.5
    res = rpath.worker_debiased_path(
        BinaryHead(), x, y, lams=lams, lam_prime=0.2, cfg=cfg)
    for i in range(4):
        bt, bh, _ = pipeline.worker_debiased(
            BinaryHead(), x, y, lam=float(lams[i]), lam_prime=0.2, cfg=cfg)
        np.testing.assert_allclose(
            np.asarray(res.beta_hat[i]), np.asarray(bh), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(res.beta_tilde[i]), np.asarray(bt), atol=1e-5)


def test_path_warm_rho_carry_shape_and_reuse():
    """PathResult.rho threads back in as the next sweep's warm start."""
    d, k, L = 24, 2, 3
    a = _ar1(d)
    b = jax.random.normal(jax.random.PRNGKey(11), (d, k)) * 0.4
    lams = jnp.linspace(0.1, 0.4, L)
    cfg = DantzigConfig(max_iters=300, adapt_rho=False, fused=True)
    first = rpath.solve_dantzig_path(a, b, lams, cfg)
    again = rpath.solve_dantzig_path(a, b, lams, cfg, rho=first.rho)
    # fixed-rho fused path with the same (scalar-equal) warm values:
    # identical solves
    np.testing.assert_allclose(
        np.asarray(first.beta), np.asarray(again.beta), atol=1e-6)
    # scan path adapts rho and reports the adapted values; a converged
    # solve is insensitive to the (different) warm trajectory
    scan_cfg = DantzigConfig(max_iters=1200)
    res = rpath.solve_dantzig_path(a, b, lams, scan_cfg)
    assert res.rho.shape == (L, k)
    warm = rpath.solve_dantzig_path(a, b, lams, scan_cfg, rho=res.rho)
    np.testing.assert_allclose(
        np.asarray(res.beta), np.asarray(warm.beta), atol=5e-4)


def test_lambda_selection_helpers():
    d, L = 30, 5
    a = _ar1(d)
    b = jax.random.normal(jax.random.PRNGKey(12), (d,)) * 0.5
    # a grid reaching down to a radius the iteration budget can't close
    lams = jnp.asarray([1e-5, 0.1, 0.2, 0.3, 0.4])
    cfg = DantzigConfig(max_iters=300, adapt_rho=False, fused=True)
    res = rpath.solve_dantzig_path(a, b, lams, cfg)
    # 300 iterations bring lam 0.2 and 0.3 to a violation of 2-4e-4 and
    # leave 1e-5, 0.1 and 0.4 above 1e-3: tol falls in that gap
    tol = 5e-4
    feasible = [i for i in range(L) if float(res.kkt[i]) <= tol]
    assert feasible and len(feasible) < L, res.kkt  # tol splits the grid
    idx = int(rpath.select_by_kkt(res, tol=tol))
    # rule: the smallest tol-feasible radius
    assert float(res.kkt[idx]) <= tol
    assert float(res.lam[idx]) == min(float(res.lam[i]) for i in feasible)
    # nothing feasible -> fall back to the smallest violation
    idx_none = int(rpath.select_by_kkt(res, tol=1e-9))
    assert idx_none == int(jnp.argmin(res.kkt))
    picked = rpath.take_lambda(res.beta, idx)
    assert picked.shape == (d,)
    # validation scoring picks the argmax of the supplied score
    scores_idx, scores = rpath.select_by_validation(
        res.beta, lambda beta: -jnp.sum(jnp.abs(beta)))
    assert scores.shape == (L,)
    assert int(scores_idx) == int(jnp.argmax(scores))


def test_binary_face_path_and_validation_tuning():
    key = jax.random.PRNGKey(13)
    d = 20
    x = jax.random.normal(key, (100, d))
    y = jax.random.normal(jax.random.fold_in(key, 1), (100, d)) + 0.6
    lams = jnp.linspace(0.08, 0.5, 4)
    cfg = DantzigConfig(max_iters=150, adapt_rho=False, fused=True)
    res = slda.debiased_local_estimator_path(x, y, lams, 0.2, cfg)
    assert res.beta_tilde.shape == (4, d, 1)
    z = jnp.concatenate([
        jax.random.normal(jax.random.fold_in(key, 2), (40, d)),
        jax.random.normal(jax.random.fold_in(key, 3), (40, d)) + 0.6])
    labels = jnp.concatenate([jnp.zeros(40, jnp.int32), jnp.ones(40, jnp.int32)])
    idx, errors = slda.tune_lambda_validation(res, z, labels)
    assert errors.shape == (4,)
    assert float(errors[int(idx)]) == float(jnp.min(errors))
    # a separable draw should classify well at the tuned lambda
    assert float(jnp.min(errors)) < 0.45


# ---------------------------------------------------------------------------
# factor-acceptance across entry points
# ---------------------------------------------------------------------------


def test_every_entry_point_accepts_a_factor():
    d = 32
    a = _ar1(d)
    factor = spectral_factor(a)
    assert isinstance(factor, SpectralFactor) and factor.d == d
    b = jax.random.normal(jax.random.PRNGKey(14), (d, 3)) * 0.4
    for fused in (False, True):
        cfg = DantzigConfig(max_iters=150, adapt_rho=False, fused=fused)
        np.testing.assert_allclose(
            np.asarray(solve_dantzig(factor, b, 0.1, cfg)),
            np.asarray(solve_dantzig(a, b, 0.1, cfg)), atol=1e-5)
    # scan implementation directly
    np.testing.assert_allclose(
        np.asarray(solve_dantzig_scan(factor, b, 0.1,
                                      DantzigConfig(max_iters=150))),
        np.asarray(solve_dantzig_scan(a, b, 0.1,
                                      DantzigConfig(max_iters=150))),
        atol=1e-5)
    # kernel wrapper directly
    np.testing.assert_allclose(
        np.asarray(kops.dantzig_fused(factor, b, 0.1, iters=150)),
        np.asarray(kops.dantzig_fused(a, b, 0.1, iters=150)), atol=1e-5)
    # CLIME entry points
    cols = jnp.asarray([0, 7, 31])
    cfg = DantzigConfig(max_iters=150, adapt_rho=False)
    np.testing.assert_allclose(
        np.asarray(solve_clime_columns(factor, cols, 0.1, cfg)),
        np.asarray(solve_clime_columns(a, cols, 0.1, cfg)), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(solve_clime(factor, 0.1, cfg)),
        np.asarray(solve_clime(a, 0.1, cfg)), atol=1e-5)


def test_factor_is_a_pytree_under_jit():
    a = _ar1(12)
    factor = jax.jit(spectral_factor)(a)
    recon = factor.q @ jnp.diag(factor.evals) @ factor.q.T
    np.testing.assert_allclose(np.asarray(recon), np.asarray(a), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(factor.inv_eig),
        1.0 / (np.asarray(factor.evals) ** 2 + 1.0), rtol=1e-6)
