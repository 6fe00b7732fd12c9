"""Dantzig-type l1 solver via two-block ADMM with a cached spectral factor.

Solves   min ||beta||_1   s.t.  ||A beta - b||_inf <= lam
for PSD ``A`` (a sample covariance).  This is the primitive behind both
the sparse-LDA estimator (eq. 3.1, ``b = mu_d``) and every CLIME column
(eq. 3.3, ``b = e_j``).

The paper's reference solvers (parametric simplex / FastCLIME) are
branchy, pivot-based LP codes -- a poor fit for XLA/TPU.  We adapt the
algorithm to the hardware.  A first attempt (linearized ADMM) needs a
step size ~ 1/sigma_max(A)^2 and crawls on ill-conditioned covariances
(AR(0.8) at d=40 has cond ~ 81; KKT violation 0.18 after 1.5k iters).
Instead we use *exact* two-block ADMM on the splitting

    min ||w||_1 + I_{B_inf(lam)}(z)
    s.t.  A beta - z = b,     beta - w = 0

whose beta-subproblem is the linear solve (A^2 + I) beta = A(z+b-u1) +
(w-u2).  ``A`` is symmetric, so with one eigendecomposition A = Q L Q^T
(cached; O(d^3) once) the solve is Q diag(1/(L^2+1)) Q^T v -- two
matmuls.  Every iteration is therefore a handful of (d,d)x(d,k)
matmuls + clip + shrink: fixed shapes, MXU-shaped, batchable over many
right-hand sides (CLIME batches the model-axis shard of columns).
Empirically this reaches KKT 1e-3 where the linearized variant sat at
0.18 (same iteration count).  (The linearized variant also needed a
power-iteration estimate of sigma_max(A) for its step size; the exact
splitting has no such tuning knob, so that helper is gone with it.)

The cached factor is rho- and lam-independent, so it is shared across
EVERY solve on a machine: pass a
:class:`~repro.kernels.spectral.SpectralFactor` (from
:func:`~repro.kernels.spectral.spectral_factor`) in place of ``a`` to
any solver entry point and the O(d^3) eigendecomposition is skipped --
the pipeline factorizes Sigma_hat once and threads the factor through
the direction solve, the CLIME columns, and whole lambda-path sweeps
(:mod:`repro.core.path`).

Extras, all fixed-shape and `lax.scan`-able:
  * over-relaxation (alpha ~ 1.7),
  * residual-balancing adaptive rho -- free here because the cached
    factor (A^2+I) does not depend on rho; only the scaled duals and
    the shrink threshold rescale.  On the fixed schedule the residuals
    (one more (d,d) product) are evaluated only on the iterations that
    adapt rho, one in ``cfg.adapt_every``: it runs as chunks of one
    adapting step and ``adapt_every - 1`` plain ones.  The early exit
    below evaluates them on every iteration and masks all but those,
  * residual-gated early exit (``cfg.tol``): the fixed ``lax.scan``
    becomes a bounded ``lax.while_loop`` over ``cfg.check_every``-
    iteration chunks that stops once the batch's max scaled residual
    drops below ``tol`` (same residual definitions as the fused
    kernel -- DESIGN.md §7), and full-state warm starts (``state0`` /
    the returned :class:`~repro.kernels.dantzig_fused.AdmmState`)
    that resume a solve instead of restarting from zero.  The default
    ``cfg.tol=None`` keeps the historical fixed-iteration scan --
    bit-exact with the pre-adaptive golden pins.

Dispatch rules: :func:`solve_dantzig` is a thin shim over
:func:`repro.core.solver_dispatch.solve_dantzig`, which picks between

  * ``scan``           -- this module's ``lax.scan`` path: the default
    (``cfg.fused=False``, the only path with adaptive rho), and the
    fallback whenever A + Q cannot fit VMEM at all;
  * ``fused``          -- whole batch in one VMEM-resident Pallas call
    (``cfg.fused=True`` and the (d, k) footprint fits the budget);
  * ``fused_blocked``  -- ``cfg.fused=True`` with the column batch
    tiled over a Pallas grid (block size from ``pick_block_k``, or the
    explicit ``cfg.block_k`` override).

The selection happens at trace time from static shapes; per-column
``rho`` is a traced operand on the fused paths, so warm rho estimates
never recompile.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis import ExecutionBudget, Param, trace_contract
from repro.kernels import ops as kops
from repro.kernels.dantzig_fused import AdmmState  # noqa: F401  (re-export)
from repro.kernels.spectral import (  # noqa: F401  (re-exported API)
    SpectralFactor,
    spectral_factor,
)


class DantzigConfig(NamedTuple):
    """Solver knobs (static under jit)."""

    max_iters: int = 600
    rho: float = 1.0
    # over-relaxation coefficient (1.0 disables; 1.5-1.8 typical)
    alpha: float = 1.7
    # residual-balancing: rho *= / /= rho_tau when residuals differ by
    # more than rho_mu x; adapt every `adapt_every` iterations.
    adapt_rho: bool = True
    rho_mu: float = 10.0
    rho_tau: float = 2.0
    adapt_every: int = 10
    # use the Pallas soft-threshold kernel for the shrink step
    use_kernel: bool = False
    # run the WHOLE solve in the fused VMEM-resident Pallas kernel
    # (kernels/dantzig_fused.py; fixed rho, no adaptation).  Wide
    # batches are tiled over a Pallas grid automatically -- see the
    # dispatch rules in the module docstring.
    fused: bool = False
    # explicit columns-per-grid-step override for the fused kernel
    # (None = size the block to the VMEM budget)
    block_k: int | None = None
    # fast-memory budget in bytes for the fused kernel's blocking model
    # (None = derive from the active backend, see
    # repro.kernels.dantzig_fused.backend_vmem_budget)
    vmem_budget: int | None = None
    # residual-gated early exit (DESIGN.md §7): stop once the batch's
    # max scaled primal/dual residual drops below `tol`, checking every
    # `check_every` iterations, capped at `max_iters`.  None (default)
    # keeps the historical fixed-`max_iters` schedule bit-exact -- the
    # mode the golden pre-refactor pins require.  `tol` is static:
    # changing it recompiles (it gates trace-time control flow).
    tol: float | None = None
    check_every: int = 10


def soft_threshold(x: jnp.ndarray, t: jnp.ndarray, use_kernel: bool = False) -> jnp.ndarray:
    """Elementwise shrink.  Kernel path used on 2D batched CLIME updates."""
    if use_kernel:
        return kops.soft_threshold(x, t)
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


def _mm(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """An ADMM matmul in full f32.  A TPU's default f32 matmul is one
    bf16 pass, whose ~4e-3 relative error sits above the residual
    tolerances and moves the fixed point of an ill-conditioned A."""
    return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)


class DantzigState(NamedTuple):
    z: jnp.ndarray  # (d, k) box-constrained copy of A beta - b
    w: jnp.ndarray  # (d, k) sparse copy of beta
    u1: jnp.ndarray  # scaled dual for A beta - z = b
    u2: jnp.ndarray  # scaled dual for beta - w = 0
    rho: jnp.ndarray  # (k,) per-problem penalty


def solve_dantzig(
    a: jnp.ndarray | SpectralFactor,
    b: jnp.ndarray,
    lam: jnp.ndarray | float,
    cfg: DantzigConfig = DantzigConfig(),
    *,
    rho: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Solve a (batch of) Dantzig problems sharing the same matrix ``a``.

    Thin shim over :func:`repro.core.solver_dispatch.solve_dantzig`
    (kept here so every historical import site keeps working); see the
    module docstring for the dispatch rules.

    Args:
      a:   (d, d) PSD matrix, or its :class:`SpectralFactor`.
      b:   (d,) or (d, k) right-hand side(s).
      lam: scalar or (k,) per-problem box radius.
      rho: optional scalar or (k,) per-column ADMM penalty override.
    Returns:
      beta with the same trailing shape as ``b`` (the sparse ADMM copy,
      exactly sparse thanks to the shrink step).
    """
    from repro.core import solver_dispatch  # deferred: avoids import cycle

    return solver_dispatch.solve_dantzig(a, b, lam, cfg, rho=rho)


@trace_contract(
    "dantzig.solve_dantzig_scan",
    contracts=(
        # four (d,d)x(d,k) products per iteration, and the residual's
        # fifth only on the ceil(max_iters / adapt_every) adapting ones
        ExecutionBudget("dot_general", exact=Param("products"),
                        out_shape=Param("rhs")),
    ),
)
@partial(jax.jit, static_argnames=("cfg", "return_rho", "return_info"))
def solve_dantzig_scan(
    a: jnp.ndarray | SpectralFactor,
    b: jnp.ndarray,
    lam: jnp.ndarray | float,
    cfg: DantzigConfig = DantzigConfig(),
    rho0: jnp.ndarray | None = None,
    *,
    return_rho: bool = False,
    state0: AdmmState | None = None,
    return_info: bool = False,
) -> jnp.ndarray:
    """The XLA ADMM implementation (adaptive rho lives here).

    ``a`` may be the raw matrix (factorized here) or a
    :class:`SpectralFactor` (the eigendecomposition is reused as-is).
    ``rho0`` optionally seeds the per-problem rho state (scalar or
    (k,)); it defaults to ``cfg.rho``.  With ``return_rho`` the final
    adapted per-problem rho rides along -- the warm estimate that
    lambda-path sweeps carry into their next call.

    ``state0`` optionally resumes the iteration from a previous solve's
    :class:`~repro.kernels.dantzig_fused.AdmmState` (zero cold start
    when None).  With ``cfg.tol`` set the fixed ``lax.scan`` becomes a
    bounded ``lax.while_loop`` over ``cfg.check_every``-iteration
    chunks with the residual-gated early exit of DESIGN.md §7;
    ``return_info`` appends ``(state, iters)`` to the return value:
    ``(beta[, rho], state, iters)`` with ``iters`` the scalar executed
    iteration count.
    """
    squeeze = b.ndim == 1
    if squeeze:
        b = b[:, None]
    d, k = b.shape

    # cached spectral factor of (A^2 + I); rho- and lam-independent.
    factor = a if isinstance(a, SpectralFactor) else spectral_factor(a)
    a = factor.sigma
    q = factor.q
    inv_eig = factor.inv_eig[:, None]

    lam = jnp.broadcast_to(jnp.asarray(lam, a.dtype), (k,))[None, :]

    def solve_m(v):  # (A^2 + I)^{-1} v
        return _mm(q, inv_eig * _mm(q.T, v))

    zeros = jnp.zeros((d, k), a.dtype)
    rho_init = (jnp.full((k,), cfg.rho, a.dtype) if rho0 is None
                else jnp.broadcast_to(jnp.asarray(rho0, a.dtype), (k,)))
    if state0 is None:
        init = DantzigState(
            z=zeros, w=zeros, u1=zeros, u2=zeros, rho=rho_init,
        )
    else:
        s0 = [jnp.asarray(v, a.dtype) for v in state0]
        s0 = [v[:, None] if v.ndim == 1 else v for v in s0]
        init = DantzigState(z=s0[0], w=s0[1], u1=s0[2], u2=s0[3],
                            rho=rho_init)

    alpha = cfg.alpha

    def step(state: DantzigState, adapt) -> DantzigState:
        """One ADMM iteration.  A plain step (``adapt`` False) leaves rho
        and the scaled duals exactly as they are.  Otherwise the step
        also evaluates the residual-balancing statistics, one more (d,d)
        product, and rescales rho and the duals: always for ``adapt``
        True, and where it holds for a traced boolean ``adapt``."""
        z0, w0 = state.z, state.w
        rho = state.rho[None, :]
        beta = solve_m(_mm(a, z0 + b - state.u1) + (w0 - state.u2))
        ab = _mm(a, beta)
        # over-relaxation mixes in the previous constraint copies
        ab_r = alpha * ab + (1.0 - alpha) * (z0 + b)
        beta_r = alpha * beta + (1.0 - alpha) * w0
        z = jnp.clip(ab_r - b + state.u1, -lam, lam)
        w = soft_threshold(beta_r + state.u2, 1.0 / rho, cfg.use_kernel)
        u1 = state.u1 + ab_r - z - b
        u2 = state.u2 + beta_r - w
        if adapt is False:
            return DantzigState(z, w, u1, u2, state.rho)
        # residual balancing (per problem in the batch)
        r_pri = jnp.sqrt(jnp.sum((ab - z - b) ** 2 + (beta - w) ** 2, axis=0))
        s_dual = state.rho * jnp.sqrt(
            jnp.sum(_mm(a, z - z0) ** 2 + (w - w0) ** 2, axis=0)
        )
        up = r_pri > cfg.rho_mu * s_dual
        down = s_dual > cfg.rho_mu * r_pri
        if adapt is not True:
            up, down = adapt & up, adapt & down
        scale = jnp.where(up, cfg.rho_tau, jnp.where(down, 1.0 / cfg.rho_tau, 1.0))
        new_rho = state.rho * scale
        # scaled duals u = y/rho must rescale with rho
        u1 = u1 / scale[None, :]
        u2 = u2 / scale[None, :]
        return DantzigState(z, w, u1, u2, new_rho)

    def plain_steps(state: DantzigState, n: int) -> DantzigState:
        if n == 0:
            return state
        return jax.lax.fori_loop(0, n, lambda _, s: step(s, False), state)

    def step_at(state: DantzigState, i) -> DantzigState:
        """Iteration ``i`` (traced): it adapts when ``i`` is a multiple of
        ``adapt_every``.  The residual is computed on every iteration and
        masked, not chosen by a ``lax.cond``: under ``vmap`` the early
        exit batches ``i``, and a batched ``cond`` runs both steps."""
        if not cfg.adapt_rho:
            return step(state, False)
        return step(state, i % cfg.adapt_every == 0)

    if cfg.tol is None and not cfg.adapt_rho:
        state = plain_steps(init, cfg.max_iters)
        iters = jnp.int32(cfg.max_iters)
    elif cfg.tol is None:
        # the static schedule: iteration i adapts when i % adapt_every
        # == 0, so each chunk is one adapting step and adapt_every - 1
        # plain ones; a tail of max_iters % adapt_every starts at a
        # multiple of adapt_every and so adapts first too.
        every = cfg.adapt_every
        chunks, tail = divmod(cfg.max_iters, every)

        def chunk(state, _):
            return plain_steps(step(state, True), every - 1), None

        state, _ = jax.lax.scan(chunk, init, None, length=chunks)
        if tail:
            state = plain_steps(step(state, True), tail - 1)
        iters = jnp.int32(cfg.max_iters)
    else:
        # residual-gated early exit, mirroring the fused kernel's
        # chunked while_loop (DESIGN.md §7): run `check_every`
        # iterations, then compute the batch's max scaled residual and
        # stop once it drops below tol (capped at exactly max_iters --
        # the final chunk is clamped when check_every does not divide).
        check_every = cfg.check_every

        def chunk_body(carry):
            it, state, _ = carry
            n = jnp.minimum(jnp.int32(check_every), cfg.max_iters - it)

            def inner(j, c):
                state, _, _ = c
                new = step_at(state, it + j)
                return new, new.z - state.z, new.w - state.w

            state, dz, dw = jax.lax.fori_loop(
                0, n, inner, (state, zeros, zeros))
            beta = solve_m(_mm(a, state.z + b - state.u1)
                           + (state.w - state.u2))
            ab = _mm(a, beta)
            r_pri = jnp.maximum(jnp.max(jnp.abs(ab - state.z - b)),
                                jnp.max(jnp.abs(beta - state.w)))
            s_dual = jnp.max(state.rho[None, :]
                             * jnp.max(jnp.abs(_mm(a, dz) + dw), axis=0,
                                       keepdims=True))
            return it + n, state, jnp.maximum(r_pri, s_dual)

        def chunk_cond(carry):
            it, _, res = carry
            return jnp.logical_and(it < cfg.max_iters, res > cfg.tol)

        iters, state, _ = jax.lax.while_loop(
            chunk_cond, chunk_body,
            (jnp.int32(0), init, jnp.asarray(jnp.inf, a.dtype)))

    beta = state.w[:, 0] if squeeze else state.w
    out = (beta,)
    if return_rho:
        out += (state.rho[0] if squeeze else state.rho,)
    if return_info:
        leaves = (state.z, state.w, state.u1, state.u2)
        if squeeze:
            leaves = tuple(v[:, 0] for v in leaves)
        out += (AdmmState(*leaves), iters)
    return out if len(out) > 1 else out[0]


def kkt_violation(a: jnp.ndarray, b: jnp.ndarray, beta: jnp.ndarray, lam) -> jnp.ndarray:
    """Max constraint violation ``max(||A beta - b||_inf - lam, 0)``."""
    if beta.ndim == 1:
        resid = a @ beta - b
        return jnp.maximum(jnp.max(jnp.abs(resid)) - lam, 0.0)
    resid = a @ beta - b
    return jnp.maximum(jnp.max(jnp.abs(resid), axis=0) - lam, 0.0)
