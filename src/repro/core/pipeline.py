"""The ONE worker pipeline behind every estimator entry point.

Algorithm 1's per-machine schedule -- sufficient statistics -> batched
Dantzig solve for the direction block -> CLIME precision columns ->
debias -- used to exist four times (``slda.debiased_local_estimator``,
``distributed._worker_debiased``, the simulated ``one_machine``
closures, ``multiclass.mc_debiased_local``), so improvements like the
blocked fused solver or the pad-and-mask column sharding landed in one
copy and missed the rest.  This module is the single implementation;
everything else is a thin head- or mesh-specific wrapper (see
DESIGN.md §3).

A :class:`DiscriminantHead` turns raw per-machine samples into
``HeadStats(sigma, rhs, aux)`` where ``rhs`` is the (d, K) block of
direction right-hand sides:

  * :class:`BinaryHead` -- the paper's two-sample problem, K = 1,
    ``rhs = (mu1 - mu2)[:, None]`` (eq. 3.1);
  * :class:`MulticlassHead` -- K classes sharing one covariance
    (Chen's multicategory one-shot schedule), ``rhs[:, k] =
    mu_k - mu_bar``; all K directions ride ONE batched solve.

:func:`worker_debiased` then runs the shared schedule:

  * the (d, K) direction block solves in one batched Dantzig call;
  * the CLIME columns solve unsharded (``model_axis=None``) or sharded
    over a mesh model axis with the pad-to-multiple + masked-gather
    scheme (any (d, |model|) pair is exact -- pad columns are clamped
    onto column d-1 and their (cols_per, K) correction rows are masked
    out of the ``all_gather``);
  * the debias correction generalizes the paper's (d,) vector to a
    (d, K) block: ``beta_tilde = beta_hat - Theta^T (Sigma beta_hat -
    rhs)``.

Every solve routes through :mod:`repro.core.solver_dispatch` (scan /
fused / fused_blocked picked from shape + config), and warm per-column
ADMM penalties thread through as ``rho_beta`` (K,) / ``rho_theta``
(columns-per-device,): on the fused paths they are traced operands, so
warm estimates carried across lambda sweeps never recompile.

Sigma_hat is factorized EXACTLY ONCE per worker
(:func:`~repro.kernels.spectral.spectral_factor`, one ``eigh``): the
direction solve and the CLIME columns both consume the same
:class:`~repro.kernels.spectral.SpectralFactor`, halving the O(d^3)
work per machine on every path, including the shard_map mesh paths
(the factorization sits inside the per-device shard function, so each
model-device factorizes its replicated Sigma_hat once).  The invariant
is pinned by the eigh-count jaxpr test in ``tests/test_spectral_path.py``.
Lambda-path sweeps extend the same sharing across an entire grid of
box radii -- see :mod:`repro.core.path`.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core.clime import (
    solve_clime_columns,
    solve_clime_columns_full,
    symmetrize_min,
)
from repro.analysis import (
    DtypePolicy,
    Param,
    PrimitiveBudget,
    VmemConformance,
    trace_contract,
)
from repro.core.dantzig import AdmmState, DantzigConfig
from repro.core.solver_dispatch import solve_dantzig, solve_dantzig_full
from repro.kernels import ops as kops
from repro.kernels.spectral import SpectralFactor, spectral_factor


class HeadStats(NamedTuple):
    """What a head hands the shared pipeline."""

    sigma: jnp.ndarray  # (d, d) pooled within-class covariance
    rhs: jnp.ndarray  # (d, K) direction right-hand sides
    aux: Any  # head-specific stats (SuffStats / MCStats)


@runtime_checkable
class DiscriminantHead(Protocol):
    """Maps raw per-machine samples to :class:`HeadStats`.

    Heads must be hashable (NamedTuples of static fields) so they can
    ride as static arguments under ``jax.jit``.
    """

    def stats(self, *data: jnp.ndarray) -> HeadStats: ...


# ---------------------------------------------------------------------------
# Sufficient statistics (canonical home; slda / multiclass re-export)
# ---------------------------------------------------------------------------


class SuffStats(NamedTuple):
    """Per-machine sufficient statistics of the two-class sample."""

    sigma: jnp.ndarray  # (d, d) pooled intra-class covariance
    mu1: jnp.ndarray  # (d,)
    mu2: jnp.ndarray  # (d,)
    n1: jnp.ndarray  # scalar
    n2: jnp.ndarray  # scalar

    @property
    def mu_d(self) -> jnp.ndarray:
        return self.mu1 - self.mu2


def suff_stats(x: jnp.ndarray, y: jnp.ndarray, use_kernel: bool | None = None) -> SuffStats:
    """Compute (Sigma_hat, mu1, mu2) from class samples X:(n1,d), Y:(n2,d).

    Sigma_hat = [sum (X_i-mu1)(X_i-mu1)^T + sum (Y_i-mu2)(Y_i-mu2)^T] / n

    ``use_kernel=None`` (default) selects the Pallas gram kernel on TPU
    and the jnp path elsewhere -- the CPU interpreter path is for
    correctness tests only, not a performance path.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    n1, n2 = x.shape[0], y.shape[0]
    mu1 = jnp.mean(x, axis=0)
    mu2 = jnp.mean(y, axis=0)
    if use_kernel:
        g1 = kops.gram(x, mu1)
        g2 = kops.gram(y, mu2)
    else:
        xc = x - mu1[None, :]
        yc = y - mu2[None, :]
        g1 = xc.T @ xc
        g2 = yc.T @ yc
    sigma = (g1 + g2) / (n1 + n2)
    return SuffStats(sigma, mu1, mu2, jnp.asarray(n1), jnp.asarray(n2))


class MCStats(NamedTuple):
    sigma: jnp.ndarray  # (d, d) pooled within-class covariance
    means: jnp.ndarray  # (K, d) class means
    counts: jnp.ndarray  # (K,)


def mc_suff_stats(x: jnp.ndarray, labels: jnp.ndarray, num_classes: int) -> MCStats:
    """x: (n, d), labels: (n,) in [0, K) -> pooled stats.

    Within-class scatter via the one-hot trick (static shapes, no sort).
    """
    n, d = x.shape
    onehot = jax.nn.one_hot(labels, num_classes, dtype=x.dtype)  # (n, K)
    counts = jnp.sum(onehot, axis=0)  # (K,)
    sums = onehot.T @ x  # (K, d)
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    centered = x - means[labels]  # (n, d)
    sigma = centered.T @ centered / n
    return MCStats(sigma, means, counts)


def mc_direction_rhs(stats: MCStats) -> jnp.ndarray:
    """(d, K) Dantzig right-hand sides ``mu_k - mu_bar`` (shared mu_bar)."""
    mu_bar = jnp.mean(stats.means, axis=0)
    return (stats.means - mu_bar[None, :]).T


# ---------------------------------------------------------------------------
# Heads
# ---------------------------------------------------------------------------


class BinaryHead(NamedTuple):
    """The paper's two-sample head: K = 1, rhs = mu1 - mu2."""

    use_kernel: bool | None = None

    def stats(self, x: jnp.ndarray, y: jnp.ndarray) -> HeadStats:
        s = suff_stats(x, y, self.use_kernel)
        return HeadStats(s.sigma, s.mu_d[:, None], s)


class MulticlassHead(NamedTuple):
    """K-class shared-covariance head: rhs[:, k] = mu_k - mu_bar."""

    num_classes: int

    def stats(self, x: jnp.ndarray, labels: jnp.ndarray) -> HeadStats:
        s = mc_suff_stats(x, labels, self.num_classes)
        return HeadStats(s.sigma, mc_direction_rhs(s), s)


# ---------------------------------------------------------------------------
# The shared worker schedule
# ---------------------------------------------------------------------------


def debias(
    sigma: jnp.ndarray,
    rhs: jnp.ndarray,
    beta_hat: jnp.ndarray,
    theta_hat: jnp.ndarray,
) -> jnp.ndarray:
    """beta_tilde = beta_hat - Theta^T (Sigma beta_hat - rhs)  (eq. 3.4).

    Shapes broadcast: (d,)/(d, K) ``rhs``/``beta_hat`` both work.
    """
    resid = sigma @ beta_hat - rhs
    return beta_hat - theta_hat.T @ resid


class WorkerSolves(NamedTuple):
    """One machine's round-zero heavy lifting, reusable across rounds.

    Everything downstream of the two ADMM solves -- the debias
    correction of the one-shot schedule AND every refinement round of
    :mod:`repro.core.rounds` -- is closed-form in these fields, so a
    T-round run pays the eigendecomposition and both solves exactly
    once.  The warm-carry fields (``rho_*`` / ``state_*`` /
    ``iters_*``) are populated only by ``full=True`` solves
    (:func:`worker_solves`); the narrow mode leaves them ``None`` and
    keeps the historical solver kernels bit-exact.
    """

    stats: HeadStats
    beta_hat: jnp.ndarray  # (d, K) biased local direction block
    theta: jnp.ndarray  # (d, cols) CLIME block ((d, d) unsharded)
    valid: jnp.ndarray | None  # (cols,) non-pad mask (sharded paths only)
    rho_beta: jnp.ndarray | None  # warm carries of the two solves
    rho_theta: jnp.ndarray | None
    state_beta: AdmmState | None
    state_theta: AdmmState | None
    iters_beta: jnp.ndarray | None  # executed ADMM iterations per column
    iters_theta: jnp.ndarray | None
    # the worker's ONE factorization, shared by both solves; carried so
    # streaming refits can snapshot it without a second eigh
    factor: "SpectralFactor | None" = None


def worker_solves(
    head: DiscriminantHead,
    *data: jnp.ndarray,
    lam,
    lam_prime,
    cfg: DantzigConfig = DantzigConfig(),
    model_axis: str | None = None,
    model_axis_size: int = 1,
    rho_beta: jnp.ndarray | None = None,
    rho_theta: jnp.ndarray | None = None,
    state_beta: AdmmState | None = None,
    state_theta: AdmmState | None = None,
    symmetrize: bool = False,
    full: bool = False,
) -> WorkerSolves:
    """Run one machine's ADMM solves (direction block + CLIME columns).

    The expensive, round-independent part of Algorithm 1's worker
    schedule: sufficient statistics, ONE eigendecomposition, the (d, K)
    direction solve and the CLIME column block.  :func:`worker_debiased`
    composes this with one :func:`apply_correction`;
    :mod:`repro.core.rounds` reuses the same result across T refinement
    rounds.

    ``symmetrize`` applies the CLIME symmetrization (eq. 3.3,
    ``theta_ij <- the smaller-magnitude of theta_ij / theta_ji``) to the
    full (d, d) Theta_hat.  It requires the UNSHARDED path: a
    model-axis device owns only its column block, and eq. 3.3 pairs
    ``theta_ij`` with ``theta_ji`` across blocks, so symmetrizing under
    sharding would need an extra (d, d) all-to-all gather -- exactly
    the communication the column sharding avoids.  ``model_axis`` +
    ``symmetrize`` therefore raises.

    ``full=False`` (the default) issues the narrow dispatched solves --
    bit-identical to the historical pipeline, the mode the golden
    pre-refactor pins require.  ``full=True`` routes both solves
    through :func:`~repro.core.solver_dispatch.solve_dantzig_full` and
    populates the warm-carry fields (final rho, resumable
    :class:`AdmmState`, executed iteration counts) -- the mode
    multi-round drivers and iteration-count benchmarks use.
    """
    if symmetrize and model_axis is not None:
        raise ValueError(
            "symmetrize=True needs the full (d, d) Theta_hat on one "
            "device; the model-axis-sharded path would need an extra "
            "(d, d) gather to pair theta_ij with theta_ji (eq. 3.3). "
            "Run with model_axis=None to symmetrize.")
    with jax.named_scope("slda.stats"):
        hs = head.stats(*data)
    return solves_from_stats(
        hs, lam=lam, lam_prime=lam_prime, cfg=cfg, model_axis=model_axis,
        model_axis_size=model_axis_size, rho_beta=rho_beta,
        rho_theta=rho_theta, state_beta=state_beta, state_theta=state_theta,
        symmetrize=symmetrize, full=full)


def solves_from_stats(
    hs: HeadStats,
    *,
    lam,
    lam_prime,
    cfg: DantzigConfig = DantzigConfig(),
    model_axis: str | None = None,
    model_axis_size: int = 1,
    rho_beta: jnp.ndarray | None = None,
    rho_theta: jnp.ndarray | None = None,
    state_beta: AdmmState | None = None,
    state_theta: AdmmState | None = None,
    symmetrize: bool = False,
    full: bool = False,
) -> WorkerSolves:
    """The solve body of :func:`worker_solves`, from pre-built statistics.

    Factored out so the sufficient statistics can come from somewhere
    OTHER than one machine's raw sample pass: the streaming serving
    loop (:mod:`repro.core.streaming`) accumulates chunk-merged
    :class:`HeadStats` and re-solves through this exact body, so the
    served estimator is the pipeline's estimator by construction.
    """
    # ONE eigendecomposition per worker: the direction solve and every
    # CLIME column share this factor (it is rho- and lam-independent).
    with jax.named_scope("slda.spectral"):
        factor = spectral_factor(hs.sigma)
    d = hs.rhs.shape[0]
    with jax.named_scope("slda.clime"):
        if model_axis is None:
            cols = jnp.arange(d)
            valid = None
        else:
            size = model_axis_size
            idx = jax.lax.axis_index(model_axis)
            cols_per = -(-d // size)  # ceil: pad d to a multiple of size
            cols = idx * cols_per + jnp.arange(cols_per)
            valid = cols < d
            cols = jnp.minimum(cols, d - 1)
    if full:
        with jax.named_scope("slda.direction"):
            dir_res = solve_dantzig_full(factor, hs.rhs, lam, cfg,
                                         rho=rho_beta, state=state_beta)
        with jax.named_scope("slda.clime"):
            theta_res = solve_clime_columns_full(
                factor, cols, lam_prime, cfg, rho=rho_theta,
                state=state_theta)
        beta_hat, theta = dir_res.beta, theta_res.beta
        carries = dict(
            rho_beta=dir_res.rho, rho_theta=theta_res.rho,
            state_beta=dir_res.state, state_theta=theta_res.state,
            iters_beta=dir_res.iters, iters_theta=theta_res.iters)
    else:
        with jax.named_scope("slda.direction"):
            beta_hat = solve_dantzig(factor, hs.rhs, lam, cfg, rho=rho_beta,
                                     state=state_beta)
        with jax.named_scope("slda.clime"):
            theta = solve_clime_columns(
                factor, cols, lam_prime, cfg, rho=rho_theta,
                state=state_theta)
        carries = dict(rho_beta=None, rho_theta=None, state_beta=None,
                       state_theta=None, iters_beta=None, iters_theta=None)
    if symmetrize:
        with jax.named_scope("slda.clime"):
            theta = symmetrize_min(theta)
    return WorkerSolves(stats=hs, beta_hat=beta_hat, theta=theta,
                        valid=valid, factor=factor, **carries)


def apply_correction(
    theta: jnp.ndarray,
    valid: jnp.ndarray | None,
    resid: jnp.ndarray,
    model_axis: str | None = None,
) -> jnp.ndarray:
    """Assemble the (d, K) debias correction ``Theta^T resid``.

    The correction must use ALL d CLIME columns (Theorem 4.5's
    one-round guarantee is exact only then), so on the sharded path
    (``model_axis`` set, ``valid`` the non-pad mask from
    :func:`worker_solves`) each device contributes its (cols, K) slice,
    pad rows are masked to zero, and one intra-machine ``all_gather``
    over the model axis reassembles the full vector -- global column j
    lands at row j, pad columns sit at rows >= d and are dropped.  The
    gather carries the ``slda.gather`` scope where the axis spans more
    than one device; on a one-device axis it moves nothing.
    """
    if model_axis is None:
        return theta.T @ resid
    corr_slice = jnp.where(valid[:, None], theta.T @ resid, 0.0)

    def gather():  # (size * cols_per, K), device i's block at [i*cols_per, ...)
        return jax.lax.all_gather(corr_slice, model_axis, axis=0, tiled=True)

    if jax.lax.axis_size(model_axis) == 1:
        return gather()[: resid.shape[0]]
    with jax.named_scope("slda.gather"):
        gathered = gather()
    return gathered[: resid.shape[0]]


@trace_contract(
    "pipeline.worker_debiased",
    contracts=(
        # one SpectralFactor per worker: refinement and the lambda path
        # both reuse it, so a second eigh is always a regression
        PrimitiveBudget("eigh", exact=1),
        # fused cfg: direction solve + CLIME block = exactly 2 launches;
        # scan cfg: none (a third launch means the factor stopped folding)
        PrimitiveBudget("pallas_call", exact=Param("pallas_calls")),
        # the unsharded worker communicates nothing
        PrimitiveBudget("psum", exact=0),
        PrimitiveBudget("all_gather", exact=0),
        DtypePolicy(),
        VmemConformance(),
    ),
)
def worker_debiased(
    head: DiscriminantHead,
    *data: jnp.ndarray,
    lam,
    lam_prime,
    cfg: DantzigConfig = DantzigConfig(),
    model_axis: str | None = None,
    model_axis_size: int = 1,
    rho_beta: jnp.ndarray | None = None,
    rho_theta: jnp.ndarray | None = None,
    state_beta: AdmmState | None = None,
    state_theta: AdmmState | None = None,
    symmetrize: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, HeadStats]:
    """One machine's full debiased estimate of the (d, K) direction block.

    Args:
      head: the discriminant head (static under jit).
      data: the head's raw samples -- ``(x, y)`` for :class:`BinaryHead`,
        ``(x, labels)`` for :class:`MulticlassHead`.
      lam / lam_prime: Dantzig / CLIME box radii.
      model_axis: if set, this call must be inside shard_map over that
        mesh axis; the d CLIME columns shard across it with
        ``model_axis_size`` devices (pad-and-mask, exact for any d).
      rho_beta / rho_theta: optional warm per-column ADMM penalties for
        the direction / CLIME solves (traced on the fused paths).
      state_beta / state_theta: optional warm ADMM states for the same
        two solves (leaves (d, K) / (d, columns-per-device)) -- a
        re-solve resumes from them instead of restarting from zero,
        riding exactly like the warm rho (DESIGN.md §7).
      symmetrize: apply eq. 3.3's CLIME symmetrization to Theta_hat
        before debiasing (unsharded paths only -- see
        :func:`worker_solves`; default False preserves the historical
        raw-column debias bit-for-bit).

    Returns ``(beta_tilde, beta_hat, stats)`` with (d, K) blocks.

    The schedule decomposes as :func:`worker_solves` (suff stats + one
    eigh + both ADMM solves) followed by one closed-form
    :func:`apply_correction`; multi-round refinement
    (:mod:`repro.core.rounds`, DESIGN.md §8) reuses the same solves and
    re-applies the correction around the master's aggregate.
    """
    ws = worker_solves(
        head, *data, lam=lam, lam_prime=lam_prime, cfg=cfg,
        model_axis=model_axis, model_axis_size=model_axis_size,
        rho_beta=rho_beta, rho_theta=rho_theta,
        state_beta=state_beta, state_theta=state_theta,
        symmetrize=symmetrize,
    )
    resid = ws.stats.sigma @ ws.beta_hat - ws.stats.rhs  # (d, K)
    correction = apply_correction(ws.theta, ws.valid, resid, model_axis)
    return ws.beta_hat - correction, ws.beta_hat, ws.stats
