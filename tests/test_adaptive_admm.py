"""Convergence-adaptive ADMM: early exit, warm starts, pad neutrality.

The DESIGN.md §7 contract, pinned:

  * tol-mode solutions match the fixed-500 baseline to <= 1e-4 on
    every dispatch path (scan / fused / fused_blocked), including
    SpectralFactor-fed calls, while executing strictly fewer
    iterations;
  * a solve resumed from a previous solve's :class:`AdmmState`
    converges in strictly fewer iterations than the cold solve;
  * padded tail columns (b = 0, lam = 1, rho = 1, zero state) report
    zero residual immediately and never hold a block's while_loop
    open;
  * the default config (tol=None) keeps the fixed-iteration schedule
    bit-exact -- the adaptive machinery is strictly opt-in.
"""

from collections import Counter
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import count_executions, find_eqns
from repro.core import path as rpath
from repro.core.clime import solve_clime_columns
from repro.core.pipeline import BinaryHead
from repro.core.dantzig import (
    AdmmState,
    DantzigConfig,
    _mm,
    soft_threshold,
    solve_dantzig_scan,
)
from repro.core.solver_dispatch import (
    select_solver,
    solve_dantzig,
    solve_dantzig_full,
)
from repro.kernels import ops as kops
from repro.kernels.dantzig_fused import (
    fused_block_vmem_bytes,
    pick_block_k,
)
from repro.kernels.spectral import spectral_factor
from repro.stats.synthetic import ar1_covariance

# the benchmark's converging operating point: CLIME columns on AR(0.4)
D, LAM, TOL = 64, 0.3, 2e-4
FIXED = 500


def _factor(d=D, ar=0.4):
    return spectral_factor(jnp.asarray(ar1_covariance(d, ar), jnp.float32))


def _clime_b(d=D, k=None):
    return jnp.eye(d, dtype=jnp.float32)[:, : (k or d)]


ADAPTIVE_CFGS = [
    ("scan", DantzigConfig(max_iters=FIXED, adapt_rho=False, tol=TOL)),
    ("fused", DantzigConfig(max_iters=FIXED, adapt_rho=False, fused=True,
                            tol=TOL)),
    ("fused_blocked",
     DantzigConfig(max_iters=FIXED, adapt_rho=False, fused=True, block_k=16,
                   tol=TOL)),
]


# ---------------------------------------------------------------------------
# tol-mode parity vs fixed-500
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,cfg", ADAPTIVE_CFGS,
                         ids=[c[0] for c in ADAPTIVE_CFGS])
def test_tol_mode_matches_fixed_500(name, cfg):
    factor = _factor()
    b = _clime_b()
    fixed = solve_dantzig(factor, b, LAM, cfg._replace(tol=None))
    res = solve_dantzig_full(factor, b, LAM, cfg)
    assert int(res.iters.max()) < FIXED, name  # it actually exited early
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(fixed),
                               atol=1e-4, err_msg=name)
    # the narrow entry point honors cfg.tol identically
    np.testing.assert_array_equal(
        np.asarray(solve_dantzig(factor, b, LAM, cfg)),
        np.asarray(res.beta))


@pytest.mark.parametrize("name,cfg", ADAPTIVE_CFGS,
                         ids=[c[0] for c in ADAPTIVE_CFGS])
def test_tol_mode_factor_fed_matches_matrix_fed(name, cfg):
    a = jnp.asarray(ar1_covariance(D, 0.4), jnp.float32)
    b = _clime_b(k=8)
    np.testing.assert_allclose(
        np.asarray(solve_dantzig(spectral_factor(a), b, LAM, cfg)),
        np.asarray(solve_dantzig(a, b, LAM, cfg)), atol=1e-5, err_msg=name)


def test_tol_mode_scan_with_adaptive_rho():
    """The while_loop early exit composes with residual balancing."""
    factor = _factor()
    b = _clime_b(k=8)
    cfg = DantzigConfig(max_iters=FIXED, tol=TOL)  # adapt_rho defaults on
    fixed = solve_dantzig(factor, b, LAM, cfg._replace(tol=None))
    res = solve_dantzig_full(factor, b, LAM, cfg)
    assert int(res.iters.max()) < FIXED
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(fixed),
                               atol=1e-4)


@pytest.mark.parametrize("fused", [False, True])
def test_cap_is_exactly_max_iters_when_check_every_does_not_divide(fused):
    """A non-converging tol-mode solve stops at max_iters, not at the
    next check_every multiple (the final chunk is clamped)."""
    factor = _factor()
    b = jax.random.normal(jax.random.PRNGKey(7), (D, 4)) * 0.5
    cfg = DantzigConfig(max_iters=100, adapt_rho=False, fused=fused,
                        tol=1e-12, check_every=30)
    res = solve_dantzig_full(factor, b, 0.05, cfg)
    assert int(res.iters.max()) == 100
    # and the clamped trajectory equals a straight 100-iteration run
    fixed = solve_dantzig(factor, b, 0.05, cfg._replace(tol=None))
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(fixed),
                               atol=1e-6)


def test_squeeze_contract_in_tol_mode():
    factor = _factor()
    b = _clime_b(k=1)[:, 0]
    cfg = DantzigConfig(max_iters=FIXED, adapt_rho=False, fused=True, tol=TOL)
    res = solve_dantzig_full(factor, b, LAM, cfg)
    assert res.beta.shape == (D,)
    assert res.iters.shape == ()
    assert res.state.z.shape == (D,)
    np.testing.assert_allclose(
        np.asarray(res.beta),
        np.asarray(solve_dantzig(factor, _clime_b(k=1), LAM, cfg)[:, 0]),
        atol=1e-6)


# ---------------------------------------------------------------------------
# warm starts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,cfg", ADAPTIVE_CFGS,
                         ids=[c[0] for c in ADAPTIVE_CFGS])
def test_resumed_solve_iterates_strictly_less_than_cold(name, cfg):
    factor = _factor()
    b = _clime_b()
    cold = solve_dantzig_full(factor, b, LAM, cfg)
    resumed = solve_dantzig_full(factor, b, LAM, cfg, state=cold.state,
                                 rho=cold.rho)
    assert int(resumed.iters.max()) < int(cold.iters.max()), name
    np.testing.assert_allclose(np.asarray(resumed.beta),
                               np.asarray(cold.beta), atol=1e-3)


def test_state_is_a_resumable_pytree():
    factor = _factor()
    b = _clime_b(k=8)
    cfg = DantzigConfig(max_iters=200, adapt_rho=False, fused=True, tol=TOL)
    res = solve_dantzig_full(factor, b, LAM, cfg)
    assert isinstance(res.state, AdmmState)
    assert all(leaf.shape == (D, 8) for leaf in res.state)
    # flows through jit like any pytree operand
    resumed = jax.jit(
        lambda s: solve_dantzig_full(factor, b, LAM, cfg, state=s).beta
    )(res.state)
    np.testing.assert_allclose(np.asarray(resumed), np.asarray(res.beta),
                               atol=1e-3)


def test_fixed_mode_with_state_runs_exact_iteration_count():
    """tol=None + warm state = exactly max_iters more iterations."""
    factor = _factor()
    b = _clime_b(k=4)
    cfg = DantzigConfig(max_iters=100, adapt_rho=False, fused=True)
    cold = solve_dantzig_full(factor, b, LAM, cfg)
    assert int(cold.iters.max()) == 100
    resumed = solve_dantzig_full(factor, b, LAM, cfg, state=cold.state)
    assert int(resumed.iters.max()) == 100
    # 100 + 100 resumed == 200 straight (same trajectory, fixed rho)
    straight = solve_dantzig_full(
        factor, b, LAM, cfg._replace(max_iters=200))
    np.testing.assert_allclose(np.asarray(resumed.beta),
                               np.asarray(straight.beta), atol=1e-6)


def test_clime_entry_point_forwards_state():
    factor = _factor()
    cols = jnp.asarray([0, 5, 33])
    cfg = DantzigConfig(max_iters=FIXED, adapt_rho=False, fused=True, tol=TOL)
    cold = solve_clime_columns(factor, cols, LAM, cfg)
    rhs = jnp.zeros((D, 3), jnp.float32).at[cols, jnp.arange(3)].set(1.0)
    full = solve_dantzig_full(factor, rhs, LAM, cfg)
    warm = solve_clime_columns(factor, cols, LAM, cfg, state=full.state)
    np.testing.assert_allclose(np.asarray(warm), np.asarray(cold), atol=1e-3)


# ---------------------------------------------------------------------------
# pad-column neutrality under early exit
# ---------------------------------------------------------------------------


def test_pad_columns_never_hold_a_block_open():
    """d=300, k=7 with block_k=4: the remainder tail (one pad column in
    the second block) must not pin its block at max_iters."""
    d, k = 300, 7
    factor = _factor(d=d)
    b = _clime_b(d=d, k=k)
    cfg = DantzigConfig(max_iters=FIXED, adapt_rho=False, fused=True,
                        tol=TOL, block_k=4)
    res = solve_dantzig_full(factor, b, LAM, cfg)
    assert int(res.iters.max()) < FIXED  # neither block ran out the cap
    # and the tail block (3 real columns + 1 pad) agrees with the
    # unblocked solve of the same columns
    whole = solve_dantzig_full(factor, b, LAM, cfg._replace(block_k=None))
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(whole.beta),
                               atol=1e-4)


def test_pure_pad_block_exits_after_one_chunk():
    """A block made ENTIRELY of pad columns stops at the first check."""
    d, k = 48, 5
    factor = _factor(d=d)
    b = _clime_b(d=d, k=k)
    check_every = 10
    cfg = DantzigConfig(max_iters=FIXED, adapt_rho=False, fused=True,
                        tol=TOL, check_every=check_every, block_k=4)
    # blocks: [4 real], [1 real + 3 pad] -- per-block counts surface
    # through kops.dantzig_fused directly
    res = kops.dantzig_fused(
        factor, b, LAM, iters=FIXED, tol=TOL, check_every=check_every,
        block_k=4, return_info=True)
    assert res.iters.shape == (2,)
    assert int(res.iters.max()) < FIXED
    # solving ONLY pad-equivalent columns (b = 0) exits after one chunk
    zero = kops.dantzig_fused(
        factor, jnp.zeros((d, 4), jnp.float32), 1.0, iters=FIXED, tol=TOL,
        check_every=check_every, rho=1.0, return_info=True)
    assert int(zero.iters.max()) == check_every
    np.testing.assert_array_equal(np.asarray(zero.beta),
                                  np.zeros((d, 4), np.float32))


# ---------------------------------------------------------------------------
# path continuation
# ---------------------------------------------------------------------------


def test_path_resweep_warm_iters_below_cold():
    factor = _factor(d=96)
    b = _clime_b(d=96, k=8)
    lams = jnp.linspace(0.25, 0.55, 5)
    cfg = DantzigConfig(max_iters=FIXED, adapt_rho=False, fused=True,
                        tol=TOL, block_k=8)
    cold = rpath.solve_dantzig_path(factor, b, lams, cfg)
    assert cold.state.z.shape == (5, 96, 8)
    assert cold.iters.shape == (5, 8)
    warm = rpath.solve_dantzig_path(factor, b, lams, cfg,
                                    state=cold.state, rho=cold.rho)
    assert int(warm.iters.sum()) < int(cold.iters.sum())
    np.testing.assert_allclose(np.asarray(warm.beta), np.asarray(cold.beta),
                               atol=1e-3)


def test_seed_path_state_maps_nearest_lambda():
    state = AdmmState(*(jnp.arange(3, dtype=jnp.float32)[:, None, None]
                        * jnp.ones((3, 4, 2)) for _ in range(4)))
    lams_from = jnp.asarray([0.1, 0.2, 0.3])
    lams_to = jnp.asarray([0.1, 0.22, 0.31, 0.05])
    seeded = rpath.seed_path_state(state, lams_from, lams_to)
    np.testing.assert_array_equal(
        np.asarray(seeded.z[:, 0, 0]), np.asarray([0.0, 1.0, 2.0, 0.0]))


def test_worker_path_state_carry_round_trips():
    # the cold sweep of this draw converges at 330 iterations: the cap
    # sits above it, so both solves end at tol, not at the cap
    cfg = DantzigConfig(max_iters=400, adapt_rho=False, fused=True, tol=TOL)
    lams = jnp.linspace(0.2, 0.5, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (120, 30))
    y = jax.random.normal(jax.random.PRNGKey(3), (130, 30)) + 0.4
    res = rpath.worker_debiased_path(
        BinaryHead(), x, y, lams=lams, lam_prime=0.3, cfg=cfg)
    assert res.state_beta.z.shape == (4, 30, 1)
    assert res.iters.shape == (4, 1)
    assert int(res.iters.max()) < cfg.max_iters
    again = rpath.worker_debiased_path(
        BinaryHead(), x, y, lams=lams, lam_prime=0.3, cfg=cfg,
        rho_beta=res.rho_beta, state_beta=res.state_beta)
    assert int(again.iters.sum()) < int(res.iters.sum())
    np.testing.assert_allclose(np.asarray(again.beta_tilde),
                               np.asarray(res.beta_tilde), atol=1e-3)


# ---------------------------------------------------------------------------
# VMEM model + selection
# ---------------------------------------------------------------------------


def test_state_io_footprint_is_larger_and_budgeted():
    d = 256
    budget = fused_block_vmem_bytes(d, 512)  # a budget that binds
    bk_plain = pick_block_k(d, 4096, budget)
    bk_state = pick_block_k(d, 4096, budget, state_io=True)
    assert bk_state < bk_plain  # state I/O pays for itself in block size
    assert fused_block_vmem_bytes(d, bk_state, state_io=True) <= budget
    assert fused_block_vmem_bytes(d, bk_plain, state_io=True) \
        > budget  # the fixed kernel's sizing would have blown VMEM


def test_select_solver_derives_state_io_from_tol():
    d, k = 256, 4096
    budget = fused_block_vmem_bytes(d, 512)
    plain = select_solver(DantzigConfig(fused=True, vmem_budget=budget), d, k)
    adaptive = select_solver(
        DantzigConfig(fused=True, tol=1e-4, vmem_budget=budget), d, k)
    assert adaptive.kind == plain.kind == "fused_blocked"
    assert adaptive.block_k < plain.block_k
    assert select_solver(DantzigConfig(fused=True, vmem_budget=budget), d, k,
                         state_io=True) == adaptive


def test_default_config_stays_on_the_fixed_kernel_bit_exact():
    """tol=None end to end == the pre-adaptive fixed path, bitwise."""
    factor = _factor()
    b = _clime_b(k=8)
    cfg = DantzigConfig(max_iters=150, adapt_rho=False, fused=True)
    base = solve_dantzig(factor, b, LAM, cfg)
    via_full = solve_dantzig_full(factor, b, LAM, cfg)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(via_full.beta))
    assert int(via_full.iters.max()) == 150


# ---------------------------------------------------------------------------
# residual balancing evaluated only on the adapting iterations
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",))
def _every_iteration_scan(factor, b, lam, cfg, rho0=None, state0=None):
    """The scan solver as it was when every iteration evaluated the
    residual-balancing statistics and masked them with ``i %
    adapt_every == 0``: the yardstick the chunked schedule must match.
    Returns ``(beta, rho, (z, w, u1, u2), iters)`` for a (d, k) ``b``."""
    d, k = b.shape
    a, q = factor.sigma, factor.q
    inv_eig = factor.inv_eig[:, None]
    lam = jnp.broadcast_to(jnp.asarray(lam, a.dtype), (k,))[None, :]

    def solve_m(v):
        return _mm(q, inv_eig * _mm(q.T, v))

    zeros = jnp.zeros((d, k), a.dtype)
    rho = (jnp.full((k,), cfg.rho, a.dtype) if rho0 is None
           else jnp.broadcast_to(jnp.asarray(rho0, a.dtype), (k,)))
    init = (zeros,) * 4 if state0 is None else tuple(state0)
    init = init + (rho,)
    alpha = cfg.alpha

    def body(state, i):
        z0, w0, u1_0, u2_0, rho = state
        beta = solve_m(_mm(a, z0 + b - u1_0) + (w0 - u2_0))
        ab = _mm(a, beta)
        ab_r = alpha * ab + (1.0 - alpha) * (z0 + b)
        beta_r = alpha * beta + (1.0 - alpha) * w0
        z = jnp.clip(ab_r - b + u1_0, -lam, lam)
        w = soft_threshold(beta_r + u2_0, 1.0 / rho[None, :], cfg.use_kernel)
        u1 = u1_0 + ab_r - z - b
        u2 = u2_0 + beta_r - w
        if not cfg.adapt_rho:
            return (z, w, u1, u2, rho), None
        r_pri = jnp.sqrt(jnp.sum((ab - z - b) ** 2 + (beta - w) ** 2, axis=0))
        s_dual = rho * jnp.sqrt(
            jnp.sum(_mm(a, z - z0) ** 2 + (w - w0) ** 2, axis=0))
        up = r_pri > cfg.rho_mu * s_dual
        down = s_dual > cfg.rho_mu * r_pri
        do_adapt = (i % cfg.adapt_every) == 0
        scale = jnp.where(do_adapt & up, cfg.rho_tau,
                          jnp.where(do_adapt & down, 1.0 / cfg.rho_tau, 1.0))
        return (z, w, u1 / scale[None, :], u2 / scale[None, :],
                rho * scale), None

    if cfg.tol is None:
        state, _ = jax.lax.scan(body, init, jnp.arange(cfg.max_iters))
        iters = jnp.int32(cfg.max_iters)
    else:
        def chunk_body(carry):
            it, state, _ = carry
            n = jnp.minimum(jnp.int32(cfg.check_every), cfg.max_iters - it)

            def inner(j, c):
                state, _, _ = c
                new, _ = body(state, it + j)
                return new, new[0] - state[0], new[1] - state[1]

            state, dz, dw = jax.lax.fori_loop(
                0, n, inner, (state, zeros, zeros))
            z, w, u1, u2, rho = state
            beta = solve_m(_mm(a, z + b - u1) + (w - u2))
            ab = _mm(a, beta)
            r_pri = jnp.maximum(jnp.max(jnp.abs(ab - z - b)),
                                jnp.max(jnp.abs(beta - w)))
            s_dual = jnp.max(rho[None, :] * jnp.max(
                jnp.abs(_mm(a, dz) + dw), axis=0, keepdims=True))
            return it + n, state, jnp.maximum(r_pri, s_dual)

        iters, state, _ = jax.lax.while_loop(
            lambda c: jnp.logical_and(c[0] < cfg.max_iters, c[2] > cfg.tol),
            chunk_body, (jnp.int32(0), init, jnp.asarray(jnp.inf, a.dtype)))
    z, w, u1, u2, rho = state
    return w, rho, (z, w, u1, u2), iters


_SCHEDULES = {
    "divisible": DantzigConfig(max_iters=60),
    "tail": DantzigConfig(max_iters=67),
    "adapt_every_1": DantzigConfig(max_iters=45, adapt_every=1),
    "adapt_every_7_tail_1": DantzigConfig(max_iters=50, adapt_every=7),
    "adapt_every_7_tail_3": DantzigConfig(max_iters=52, adapt_every=7),
    "shorter_than_adapt_every": DantzigConfig(max_iters=6),
    "fixed_rho": DantzigConfig(max_iters=67, adapt_rho=False),
    # early exit at the file's converging operating point, checking
    # on iterations that do not line up with the adapting ones
    "tol": DantzigConfig(max_iters=FIXED, tol=TOL, check_every=4),
    "tol_adapt_every_7": DantzigConfig(max_iters=FIXED, adapt_every=7,
                                       tol=TOL, check_every=10),
    "tol_fixed_rho": DantzigConfig(max_iters=FIXED, adapt_rho=False,
                                   tol=TOL, check_every=7),
    # the cap: the final chunk is clamped at max_iters
    "tol_capped": DantzigConfig(max_iters=95, tol=1e-12, check_every=10),
}
_STARTS = ["cold", "rho0", "state0"]


@pytest.mark.parametrize("start", _STARTS)
@pytest.mark.parametrize("schedule", sorted(_SCHEDULES))
def test_adapting_only_residual_matches_every_iteration(schedule, start):
    """The residual product runs only on the iterations that adapt rho;
    beta, rho, the state and the iteration count are those of the
    schedule that evaluated it on every iteration."""
    cfg = _SCHEDULES[schedule]
    factor, b, lam = _factor(), _clime_b(k=6), LAM
    kwargs = {}
    if start == "rho0":
        kwargs["rho0"] = jnp.asarray([1 / 64, 1 / 8, 1.0, 2.0, 16.0, 64.0])
    elif start == "state0":
        warm = solve_dantzig_scan(factor, b, lam, DantzigConfig(max_iters=23),
                                  return_info=True)[1]
        kwargs["state0"] = warm
    beta, rho, state, iters = solve_dantzig_scan(
        factor, b, lam, cfg, return_rho=True, return_info=True, **kwargs)
    ref_beta, ref_rho, ref_state, ref_iters = _every_iteration_scan(
        factor, b, lam, cfg, kwargs.get("rho0"),
        None if start != "state0" else tuple(kwargs["state0"]))
    np.testing.assert_array_equal(np.asarray(rho), np.asarray(ref_rho))
    if start == "rho0" and cfg.adapt_rho:  # rho was balanced up and down
        assert rho[0] > kwargs["rho0"][0] and rho[-1] < kwargs["rho0"][-1]
    assert int(iters) == int(ref_iters)
    np.testing.assert_allclose(np.asarray(beta), np.asarray(ref_beta),
                               rtol=0, atol=1e-6)
    for got, want in zip(state, ref_state):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-6)
    # the narrow returns carry the same answer
    np.testing.assert_array_equal(
        np.asarray(solve_dantzig_scan(factor, b, lam, cfg, **kwargs)),
        np.asarray(beta))
    np.testing.assert_array_equal(
        np.asarray(solve_dantzig_scan(factor, b, lam, cfg, return_rho=True,
                                      **kwargs)[1]), np.asarray(rho))


# where each (d,d)x(d,k) product sits: an adapting step holds five, a
# plain step four; the tail's adapting step runs outside the chunk loop
# and its plain steps in a loop of their own, at the chunk loop's depth
_PRODUCT_SITES = {
    "default": (DantzigConfig(), {("jit", "scan"): 5,
                                  ("jit", "scan", "scan"): 4}),
    "tail": (DantzigConfig(max_iters=67), {("jit",): 5, ("jit", "scan"): 9,
                                           ("jit", "scan", "scan"): 4}),
    "adapt_every_7": (DantzigConfig(max_iters=50, adapt_every=7),
                      {("jit",): 5, ("jit", "scan"): 5,
                       ("jit", "scan", "scan"): 4}),
    "adapt_every_1": (DantzigConfig(max_iters=45, adapt_every=1),
                      {("jit", "scan"): 5}),
    "fixed_rho": (DantzigConfig(max_iters=67, adapt_rho=False),
                  {("jit", "scan"): 4}),
}


@pytest.mark.parametrize("schedule", sorted(_PRODUCT_SITES))
def test_residual_product_runs_once_per_adapting_iteration(schedule):
    """The adapting step, with the residual's fifth product, sits in the
    chunk loop and the four-product plain steps in its inner loop, so
    the residual runs once per ``adapt_every`` iterations (the count
    itself is the ``dantzig.solve_dantzig_scan`` trace contract).  At
    the default config that is 60 per 600-iteration solve, where the
    every-iteration schedule ran 600."""
    cfg, want = _PRODUCT_SITES[schedule]
    factor, b = _factor(d=16), _clime_b(d=16, k=4)
    jaxpr = jax.make_jaxpr(
        lambda f, b: solve_dantzig_scan(f, b, LAM, cfg))(factor, b)
    sites = Counter(s.path for s in find_eqns(jaxpr, "dot_general", (16, 4)))
    assert sites == want
    if schedule == "default":
        assert count_executions(jaxpr, "dot_general", (16, 4)) == 4 * 600 + 60
        every = jax.make_jaxpr(
            lambda f, b: _every_iteration_scan(f, b, LAM, cfg))(factor, b)
        assert count_executions(every, "dot_general", (16, 4)) == 5 * 600


@pytest.mark.parametrize("batched", [False, True], ids=["single", "vmap"])
@pytest.mark.parametrize("schedule", ["tol", "tol_adapt_every_7"])
def test_early_exit_step_holds_five_products(schedule, batched):
    """The early exit's step evaluates the residual on every iteration
    and masks it, as the every-iteration schedule did: five products.
    Under ``vmap`` the exit batches the iteration index, and a
    ``lax.cond`` choosing between the two steps would run both (nine)."""
    cfg = _SCHEDULES[schedule]
    factor, b = _factor(d=16), _clime_b(d=16, k=4)

    def solve(f, b):
        return solve_dantzig_scan(f, b, LAM, cfg)

    if batched:
        solve = jax.vmap(solve)
        factor, b = jax.tree.map(lambda x: jnp.stack([x, x]), (factor, b))
    jaxpr = jax.make_jaxpr(solve)(factor, b)
    sites = Counter(s.path for s in find_eqns(jaxpr, "dot_general"))
    # the inner loop's step, and the chunk's residual check
    assert sites == {("jit", "while", "while"): 5, ("jit", "while"): 5}
