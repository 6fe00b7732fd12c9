"""Binary sparse-LDA estimation, debiasing and aggregation primitives.

The per-machine computations of Algorithm 1:

  * pooled intra-class covariance  Sigma_hat (Pallas gram kernel)
  * local Dantzig-type sparse LDA  beta_hat           (eq. 3.1)
  * CLIME precision estimate       Theta_hat          (eq. 3.2)
  * debiased estimator             beta_tilde         (eq. 3.4)
  * hard threshold                 HT(., t)           (eq. 3.5)

The worker schedule itself (suff stats -> Dantzig -> CLIME -> debias)
lives ONCE in :mod:`repro.core.pipeline`; this module is the binary
(K=1) face of it -- :func:`debiased_local_estimator` is a thin wrapper
over ``pipeline.worker_debiased(BinaryHead(), ...)`` -- plus the
master-side aggregation and the two baselines the paper compares
against (centralized SLDA, naive averaging -- assembled in
:mod:`repro.core.distributed`).

Lambda tuning (the paper's lam ∝ sqrt(log d / n) with grid-tuned
constants) goes through :func:`debiased_local_estimator_path`: the
whole grid solves in ONE folded launch sharing ONE eigendecomposition
(:mod:`repro.core.path`), and :func:`tune_lambda_validation` picks the
operating point by held-out misclassification.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import classifier, path, pipeline
from repro.core import rounds as _rounds
from repro.core.dantzig import DantzigConfig
from repro.core.pipeline import BinaryHead, SuffStats, suff_stats  # noqa: F401
from repro.core.solver_dispatch import solve_dantzig

__all__ = [
    "SuffStats",
    "suff_stats",
    "local_slda",
    "debias",
    "debiased_local_estimator",
    "debiased_local_estimator_path",
    "multi_round_slda",
    "tune_lambda_validation",
    "hard_threshold",
    "aggregate",
    "centralized_slda",
]


def local_slda(
    stats: SuffStats, lam: float, cfg: DantzigConfig = DantzigConfig()
) -> jnp.ndarray:
    """Biased local estimator beta_hat (eq. 3.1)."""
    return solve_dantzig(stats.sigma, stats.mu_d, lam, cfg)


def debias(
    stats: SuffStats,
    beta_hat: jnp.ndarray,
    theta_hat: jnp.ndarray,
) -> jnp.ndarray:
    """beta_tilde = beta_hat - Theta_hat^T (Sigma_hat beta_hat - mu_d)  (eq. 3.4)."""
    return pipeline.debias(stats.sigma, stats.mu_d, beta_hat, theta_hat)


def debiased_local_estimator(
    x: jnp.ndarray,
    y: jnp.ndarray,
    lam: float,
    lam_prime: float | None = None,
    cfg: DantzigConfig = DantzigConfig(),
    symmetrize: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full worker-side pipeline: returns (beta_tilde, beta_hat).

    ``symmetrize`` debiases with the eq.-3.3-symmetrized Theta_hat
    (unsharded full-CLIME path only; default False keeps the
    historical raw-column debias bit-for-bit -- the golden pins).
    """
    beta_tilde, beta_hat, _ = pipeline.worker_debiased(
        BinaryHead(), x, y,
        lam=lam, lam_prime=lam if lam_prime is None else lam_prime, cfg=cfg,
        symmetrize=symmetrize,
    )
    return beta_tilde[:, 0], beta_hat[:, 0]


@functools.partial(jax.jit, static_argnames=("rounds", "cfg", "comm",
                                             "compression", "faults",
                                             "staleness", "aggregation"))
def multi_round_slda(
    xs: jnp.ndarray,
    ys: jnp.ndarray,
    lam: float,
    lam_prime: float,
    t: float,
    rounds: int = 3,
    cfg: DantzigConfig = DantzigConfig(),
    compression: "_rounds.Compression | None" = None,
    faults: "_rounds.FaultSchedule | None" = None,
    staleness: int = 0,
    aggregation: "_rounds.Aggregation | None" = None,
    comm: "_rounds.CommPlan | None" = None,
) -> jnp.ndarray:
    """T-round refined distributed estimator on stacked machine draws.

    The large-m face (DESIGN.md §8): xs (m, n1, d) / ys (m, n2, d) ->
    beta_bar (d,) after ``rounds`` O(d) communication rounds, all
    sharing one set of per-machine solves (``rounds=1`` is the paper's
    one-shot aggregate).  ``comm`` (a hashable
    :class:`~repro.core.transport.CommPlan`, DESIGN.md §13) carries
    the whole comms config -- per-direction codecs / bit-budget
    schedule (DESIGN.md §10), fault schedule / staleness / aggregation
    (DESIGN.md §11); the legacy ``compression`` / ``faults`` /
    ``staleness`` / ``aggregation`` kwargs remain as deprecation
    shims.  Mesh twin:
    :func:`repro.core.distributed.distributed_slda_shardmap` with
    the same ``rounds=`` / ``comm=`` knobs.
    """
    beta_bar, _ = _rounds.simulate_multi_round(
        BinaryHead(), (xs, ys), lam=lam, lam_prime=lam_prime,
        rounds=rounds, cfg=cfg, comm=comm, compression=compression,
        faults=faults, staleness=staleness, aggregation=aggregation)
    with jax.named_scope("slda.aggregate"):
        return hard_threshold(beta_bar[:, 0], t)


def debiased_local_estimator_path(
    x: jnp.ndarray,
    y: jnp.ndarray,
    lams: jnp.ndarray,
    lam_prime: float | None = None,
    cfg: DantzigConfig = DantzigConfig(),
    rho_beta: jnp.ndarray | None = None,
    state_beta: "path.AdmmState | None" = None,
    symmetrize: bool = False,
) -> path.WorkerPathResult:
    """The worker pipeline at EVERY lambda in ``lams``, in one launch.

    One eigendecomposition + one folded direction launch + one CLIME
    solve serve the whole grid (vs L launches and L+1 eigh's run
    naively); see :mod:`repro.core.path`.  ``lam_prime=None`` pins the
    CLIME radius to the middle of the grid (a lambda-independent
    choice keeps Theta_hat shared across the sweep).  ``rho_beta`` /
    ``state_beta`` accept the warm carries from a previous sweep's
    result (with ``cfg.tol`` set, a resumed sweep exits in fewer
    iterations -- DESIGN.md §7).  Returns the full
    :class:`~repro.core.path.WorkerPathResult` ((L, d, 1) blocks;
    squeeze the trailing axis for the paper's vectors).
    """
    lams = jnp.asarray(lams)
    if lam_prime is None:
        lam_prime = lams[lams.shape[0] // 2]
    return path.worker_debiased_path(
        BinaryHead(), x, y, lams=lams, lam_prime=lam_prime, cfg=cfg,
        rho_beta=rho_beta, state_beta=state_beta, symmetrize=symmetrize,
    )


def tune_lambda_validation(
    result: path.WorkerPathResult,
    z_val: jnp.ndarray,
    labels_val: jnp.ndarray,
):
    """Pick lambda by held-out misclassification of the Fisher rule.

    ``result.stats.aux`` carries the worker's (mu1, mu2), so the rule
    needs only the validation draw.  Returns ``(idx, error_rates)``;
    the tuned estimator is ``result.beta_tilde[idx, :, 0]`` (use
    :func:`repro.core.path.take_lambda` under jit).
    """
    s = result.stats.aux

    def err(beta_block):  # (d, 1) -> scalar error rate
        return classifier.misclassification_rate(
            z_val, labels_val, beta_block[:, 0], s.mu1, s.mu2)

    errors = jax.vmap(err)(result.beta_tilde)  # (L,)
    return jnp.argmin(errors), errors


def hard_threshold(beta: jnp.ndarray, t) -> jnp.ndarray:
    """HT(beta, t)_j = beta_j * 1(|beta_j| > t)."""
    t = jnp.asarray(t, beta.dtype)
    return jnp.where(jnp.abs(beta) > t, beta, jnp.zeros_like(beta))


def aggregate(beta_tildes: jnp.ndarray, t) -> jnp.ndarray:
    """Master-side aggregation (eq. 3.5): mean over machines + HT."""
    return hard_threshold(jnp.mean(beta_tildes, axis=0), t)


def centralized_slda(
    x: jnp.ndarray, y: jnp.ndarray, lam: float, cfg: DantzigConfig = DantzigConfig()
) -> jnp.ndarray:
    """Centralized baseline: pool everything, solve (3.1) once (m=1, n=N)."""
    stats = suff_stats(x, y)
    return local_slda(stats, lam, cfg)
