"""Multi-class distributed sparse LDA (the paper's stated future work).

Extension of Algorithm 1 to K classes sharing one covariance
(Chen's multicategory one-shot schedule):

  * discriminant directions  beta_k* = Theta* (mu_k - mu_bar), where
    mu_bar is the grand mean of class means -- all K directions solve
    Dantzig problems with the SAME matrix Sigma_hat, so the whole
    multi-class estimation is ONE batched solve (the k directions ride
    the same (d,d) x (d,K) MXU matmuls the CLIME columns use);
  * debiasing reuses the single CLIME estimate Theta_hat:
      beta_tilde_k = beta_hat_k - Theta_hat^T (Sigma_hat beta_hat_k - mu_dk);
  * aggregation stays one round: each machine uplinks a (d, K) block
    (still O(dK) bytes, no covariance travels);
  * classification: argmax_k (Z - mu_k/2)^T beta_k + log pi_k (equal
    priors by default), reducing to the paper's rule at K=2.

The worker schedule lives ONCE in :mod:`repro.core.pipeline`
(:func:`mc_debiased_local` wraps ``pipeline.worker_debiased`` with a
:class:`~repro.core.pipeline.MulticlassHead`), so every solve routes
through :mod:`repro.core.solver_dispatch` -- ``cfg.fused`` dispatches
the batched (d, K) direction solve and the CLIME columns to the
(blocked) fused Pallas kernel exactly as the binary path does.  Mesh
execution is :func:`repro.core.distributed.distributed_mc_slda_shardmap`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import classifier
from repro.core import path as _path
from repro.core import pipeline
from repro.core import rounds as _rounds
from repro.core.dantzig import DantzigConfig
from repro.core.pipeline import (  # noqa: F401
    MCStats,
    MulticlassHead,
    mc_direction_rhs,
    mc_suff_stats,
)
from repro.core.slda import hard_threshold
from repro.core.solver_dispatch import solve_dantzig

__all__ = [
    "MCStats",
    "mc_suff_stats",
    "mc_direction_rhs",
    "local_mc_slda",
    "mc_debias",
    "mc_debiased_local",
    "mc_debiased_local_path",
    "mc_multi_round_slda",
    "simulated_distributed_mc_slda",
    "simulated_naive_mc_slda",
    "centralized_mc_slda",
    "mc_classify",
]


def local_mc_slda(
    stats: MCStats, lam, cfg: DantzigConfig = DantzigConfig()
) -> jnp.ndarray:
    """Batched estimation of all K directions: returns (d, K)."""
    return solve_dantzig(stats.sigma, mc_direction_rhs(stats), lam, cfg)


def mc_debias(stats: MCStats, beta_hat: jnp.ndarray, theta_hat: jnp.ndarray) -> jnp.ndarray:
    """beta_tilde_k = beta_hat_k - Theta^T (Sigma beta_hat_k - mu_dk)."""
    return pipeline.debias(stats.sigma, mc_direction_rhs(stats), beta_hat, theta_hat)


def mc_debiased_local(
    x: jnp.ndarray,
    labels: jnp.ndarray,
    num_classes: int,
    lam: float,
    lam_prime: float | None = None,
    cfg: DantzigConfig = DantzigConfig(),
    symmetrize: bool = False,
) -> tuple[jnp.ndarray, MCStats]:
    """Full worker-side pipeline: returns (beta_tilde (d, K), stats).

    ``symmetrize`` debiases with the eq.-3.3-symmetrized Theta_hat
    (unsharded full-CLIME path only; default False keeps the
    historical raw-column debias).
    """
    beta_tilde, _, hs = pipeline.worker_debiased(
        MulticlassHead(num_classes), x, labels,
        lam=lam, lam_prime=lam if lam_prime is None else lam_prime, cfg=cfg,
        symmetrize=symmetrize,
    )
    return beta_tilde, hs.aux


def mc_multi_round_slda(
    xs: jnp.ndarray,
    labels: jnp.ndarray,
    num_classes: int,
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
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """T-round refined K-class estimator on stacked machine draws.

    The large-m face (DESIGN.md §8): xs (m, n, d) / labels (m, n) ->
    (beta_bar (d, K), means (K, d)) after ``rounds`` O(dK)
    communication rounds sharing one set of per-machine solves.
    ``comm`` (a hashable :class:`~repro.core.transport.CommPlan`,
    DESIGN.md §13) carries the whole comms config; the legacy
    ``compression`` / ``faults`` / ``staleness`` / ``aggregation``
    kwargs remain as deprecation shims (DESIGN.md §10/§11).
    """
    return simulated_distributed_mc_slda(
        xs, labels, num_classes, lam, lam_prime, t, cfg, rounds,
        compression, faults, staleness, aggregation, comm)


def mc_debiased_local_path(
    x: jnp.ndarray,
    labels: jnp.ndarray,
    num_classes: int,
    lams: jnp.ndarray,
    lam_prime: float | None = None,
    cfg: DantzigConfig = DantzigConfig(),
    rho_beta: jnp.ndarray | None = None,
    state_beta: "_path.AdmmState | None" = None,
    symmetrize: bool = False,
) -> _path.WorkerPathResult:
    """All K directions at EVERY lambda in one folded launch.

    The K-class analogue of
    :func:`repro.core.slda.debiased_local_estimator_path`: the K*L
    direction columns ride one blocked fused call, and one
    eigendecomposition + one CLIME solve serve the whole sweep (see
    :mod:`repro.core.path`).  ``lam_prime=None`` pins the CLIME radius
    to the middle of the grid.  Returns the (L, d, K)-blocked
    :class:`~repro.core.path.WorkerPathResult`.
    """
    lams = jnp.asarray(lams)
    if lam_prime is None:
        lam_prime = lams[lams.shape[0] // 2]
    return _path.worker_debiased_path(
        MulticlassHead(num_classes), x, labels,
        lams=lams, lam_prime=lam_prime, cfg=cfg, rho_beta=rho_beta,
        state_beta=state_beta, symmetrize=symmetrize,
    )


@functools.partial(jax.jit, static_argnames=("num_classes", "cfg", "rounds",
                                             "compression", "faults",
                                             "staleness", "aggregation",
                                             "comm"))
def simulated_distributed_mc_slda(
    xs: jnp.ndarray,
    labels: jnp.ndarray,
    num_classes: int,
    lam: float,
    lam_prime: float,
    t: float,
    cfg: DantzigConfig = DantzigConfig(),
    rounds: int = 1,
    compression: "_rounds.Compression | None" = None,
    faults: "_rounds.FaultSchedule | None" = None,
    staleness: int = 0,
    aggregation: "_rounds.Aggregation | None" = None,
    comm: "_rounds.CommPlan | None" = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """xs: (m, n, d), labels: (m, n) -> (beta_bar (d, K), means (K, d)).

    The vmap axis is the machine; the master aggregation is one mean of
    (d, K) blocks per round + hard threshold -- the multi-class
    analogue of the paper's schedule (``rounds=1`` one-shot, T > 1
    refined around the aggregate, DESIGN.md §8).  ``comm`` (a hashable
    :class:`~repro.core.transport.CommPlan`, DESIGN.md §13) carries
    the whole comms config; the legacy ``compression`` / ``faults`` /
    ``staleness`` / ``aggregation`` kwargs remain as deprecation shims
    (DESIGN.md §10/§11).  Mesh-executed twin:
    :func:`repro.core.distributed.distributed_mc_slda_shardmap`.
    """
    beta_bar, ws = _rounds.simulate_multi_round(
        MulticlassHead(num_classes), (xs, labels),
        lam=lam, lam_prime=lam_prime, rounds=rounds, cfg=cfg,
        comm=comm, compression=compression, faults=faults,
        staleness=staleness, aggregation=aggregation)
    with jax.named_scope("slda.aggregate"):
        return (hard_threshold(beta_bar, t),
                jnp.mean(ws.stats.aux.means, axis=0))


@functools.partial(jax.jit, static_argnames=("num_classes", "cfg"))
def simulated_naive_mc_slda(
    xs: jnp.ndarray,
    labels: jnp.ndarray,
    num_classes: int,
    lam: float,
    cfg: DantzigConfig = DantzigConfig(),
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Baseline: average the biased local estimators (no debias/HT)."""

    def one_machine(x, lab):
        stats = mc_suff_stats(x, lab, num_classes)
        return local_mc_slda(stats, lam, cfg), stats.means

    betas, means = jax.vmap(one_machine)(xs, labels)
    return jnp.mean(betas, axis=0), jnp.mean(means, axis=0)


def centralized_mc_slda(
    x: jnp.ndarray,
    labels: jnp.ndarray,
    num_classes: int,
    lam: float,
    cfg: DantzigConfig = DantzigConfig(),
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Centralized baseline: pool everything, one batched solve (m=1, n=N)."""
    stats = mc_suff_stats(x, labels, num_classes)
    return local_mc_slda(stats, lam, cfg), stats.means


def mc_classify(
    z: jnp.ndarray,
    beta: jnp.ndarray,
    means: jnp.ndarray,
    priors: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """z: (n, d), beta: (d, K), means: (K, d) -> predicted class (n,).

    score_k(Z) = (Z - mu_k / 2)^T beta_k + log pi_k; ``priors=None``
    means equal priors (the + log pi_k term is a constant shift and
    drops out of the argmax).  At K=2 the equal-prior rule reduces to
    the paper's Fisher rule up to the shared mu_bar shift.  The score
    computation is shared with the serving hot path through
    :func:`repro.core.classifier.classify_scores` (bit-identical to
    the pre-dedup inline form, pinned by the parity tests).
    """
    return jnp.argmax(classifier.classify_scores(z, beta, means, priors),
                      axis=-1)
