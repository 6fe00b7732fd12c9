"""The plain reference fit, and the comparison that decides ``correct``.

A straightforward ``jax.numpy`` implementation of the fit the cells
time (Tian & Gu 2016, Algorithm 1 with T refinement rounds); it imports
nothing of the program and takes nothing it made.  The ADMM follows the
schedule the configuration states step for step -- the same splitting,
over-relaxation, residual balancing and order of operations as the
program's scan solver -- because after a fixed 600 iterations the
answer depends on the schedule, not only on the problem.  A fault in
that schedule itself is therefore not caught here; the CPU test against
the program (``bench/tests/test_bench_reference.py``) is the second
witness.  Per machine:

1. the pooled within-class covariance Sigma and the mean difference
   mu_d, by centering and one matrix product per class;
2. one symmetric eigendecomposition Sigma = Q diag(e) Q^T;
3. the Dantzig direction (b = mu_d) and the d CLIME columns (b = e_j),
   each by the configuration's ADMM schedule on the splitting
   ``Sigma beta - z = b, beta - w = 0`` (over-relaxed, with residual-
   balanced penalty), whose beta-step is ``Q diag(1/(e^2+1)) Q^T v``;
4. the debiased estimate around an anchor,
   ``anchor - Theta^T (Sigma anchor - mu_d)``.

The rounds average the machines' debiased estimates and re-anchor every
machine at the average; the result is the raw aggregate before the hard
threshold.

A precision names how matrix products are computed: "highest"
(float32, six bf16 passes on a TPU), or lower, emulated so that they
read the same on any backend: "bf16x3" (three bf16 passes, what
``Precision.HIGH`` does on a TPU) and "bf16x1" (one bf16 pass with
float32 accumulation, what a TPU does for float32 operands by default).
A configuration states one precision for the ADMM's products and one or
more that it admits for the Gram and debias products; a fit is held to
the nearest of the references at the precisions it admits.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_CONTEXT = {"highest": "highest", "bf16x3": "high", "bf16x1": "default"}


class Schedule(NamedTuple):
    """The ADMM schedule the configuration states."""

    max_iters: int
    rho: float
    alpha: float
    adapt_every: int
    rho_mu: float
    rho_tau: float


def _split(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _bf16_dot(a, b):
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def matmul(a, b, precision: str):
    """``a @ b`` in float32 at the named precision."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    if precision == "bf16x1":
        return _bf16_dot(a_hi, b_hi)
    if precision == "bf16x3":
        return (_bf16_dot(a_hi, b_hi) + _bf16_dot(a_hi, b_lo)
                + _bf16_dot(a_lo, b_hi))
    raise ValueError(f"unknown precision {precision!r}")


def admm(sigma, q, evals, b, lam, sched: Schedule, precision: str):
    """The configuration's ADMM schedule for ``min |beta|_1`` s.t.
    ``|Sigma beta - b|_inf <= lam``, for every column of ``b``."""
    mm = partial(matmul, precision=precision)
    d, k = b.shape
    inv = (1.0 / (evals * evals + 1.0))[:, None]
    alpha = sched.alpha

    def step(carry, i):
        z, w, u1, u2, rho = carry
        beta = mm(q, inv * mm(q.T, mm(sigma, z + b - u1) + (w - u2)))
        ab = mm(sigma, beta)
        ab_r = alpha * ab + (1.0 - alpha) * (z + b)
        beta_r = alpha * beta + (1.0 - alpha) * w
        z_new = jnp.clip(ab_r - b + u1, -lam, lam)
        v = beta_r + u2
        w_new = jnp.sign(v) * jnp.maximum(jnp.abs(v) - 1.0 / rho[None, :], 0.0)
        u1 = u1 + ab_r - z_new - b
        u2 = u2 + beta_r - w_new
        primal = jnp.sqrt(jnp.sum((ab - z_new - b) ** 2
                                  + (beta - w_new) ** 2, axis=0))
        dual = rho * jnp.sqrt(jnp.sum(mm(sigma, z_new - z) ** 2
                                      + (w_new - w) ** 2, axis=0))
        on = (i % sched.adapt_every) == 0
        scale = jnp.where(on & (primal > sched.rho_mu * dual), sched.rho_tau,
                          jnp.where(on & (dual > sched.rho_mu * primal),
                                    1.0 / sched.rho_tau, 1.0))
        return (z_new, w_new, u1 / scale[None, :], u2 / scale[None, :],
                rho * scale), None

    zeros = jnp.zeros((d, k), jnp.float32)
    init = (zeros, zeros, zeros, zeros, jnp.full((k,), sched.rho, jnp.float32))
    (_, w, _, _, _), _ = jax.lax.scan(step, init, jnp.arange(sched.max_iters))
    return w


class Precision(NamedTuple):
    """How the products of a fit are computed."""

    stats: str  # the Gram products of Sigma and the debias products
    admm: str  # every product of the ADMM solves


def machine(x, y, lam, sched: Schedule, precision: Precision):
    """One machine: (Sigma, mu_d, beta_hat, Theta) from its two classes.

    The statistics and the solves are two programs, so that references
    that differ only in the statistics' precision share one compiled
    solve.
    """
    sigma, mu_d = _stats(x, y, precision.stats)
    return (sigma, mu_d) + _solve(sigma, mu_d, lam, sched, precision.admm)


@partial(jax.jit, static_argnames=("precision",))
def _stats(x, y, precision: str):
    mm = partial(matmul, precision=precision)
    mu1, mu2 = jnp.mean(x, axis=0), jnp.mean(y, axis=0)
    xc, yc = x - mu1, y - mu2
    sigma = (mm(xc.T, xc) + mm(yc.T, yc)) / (x.shape[0] + y.shape[0])
    return sigma, (mu1 - mu2)[:, None]


@partial(jax.jit, static_argnames=("sched", "precision"))
def _solve(sigma, mu_d, lam, sched: Schedule, precision: str):
    evals, q = jnp.linalg.eigh(sigma)
    beta_hat = admm(sigma, q, evals, mu_d, lam, sched, precision)
    theta = admm(sigma, q, evals, jnp.eye(sigma.shape[0], dtype=jnp.float32),
                 lam, sched, precision)
    return beta_hat, theta


@partial(jax.jit, static_argnames=("precision",))
def _debias(sigma, mu_d, theta, anchor, precision: str):
    mm = partial(matmul, precision=precision)
    return anchor - mm(theta.T, mm(sigma, anchor) - mu_d)


def fit(xs, ys, lam: float, rounds: int, sched: Schedule,
        precision: Precision) -> np.ndarray:
    """The raw (d,) aggregate after ``rounds`` rounds over the machines
    of ``xs`` (m, n1, d) and ``ys`` (m, n2, d), before the threshold.

    The machines run one after another, so that only one machine's
    CLIME state is on the device at a time.
    """
    with jax.default_matmul_precision(_CONTEXT[precision.admm]):
        bar = aggregate(xs, ys, jnp.float32(lam), rounds, sched, precision)
    return np.asarray(bar, np.float64)


def aggregate(xs, ys, lam, rounds: int, sched: Schedule,
              precision: Precision):
    """:func:`fit` as a traceable function: the raw (d,) aggregate."""
    parts = [machine(xs[i], ys[i], lam, sched, precision)
             for i in range(xs.shape[0])]
    return _rounds(parts, rounds, precision.stats)


def _rounds(parts, rounds: int, stats: str):
    """Average the machines' debiased estimates and re-anchor every
    machine at the average, ``rounds`` times."""
    anchors = [p[2] for p in parts]
    for _ in range(rounds):
        bar = sum(_debias(*p[:2], p[3], a, stats)
                  for p, a in zip(parts, anchors)) / len(parts)
        anchors = [bar] * len(parts)
    return bar[:, 0]


def schedule(config: dict) -> Schedule:
    """The ADMM schedule a configuration file states."""
    return Schedule(**{k: config["solver"][k] for k in Schedule._fields})


def references(config: dict) -> list:
    """The precisions the configuration admits: its ADMM precision with
    each of its precisions for the Gram and debias products."""
    p = config["precision"]
    return [Precision(stats, p["admm"]) for stats in p["stats"]]


def control(config: dict) -> Precision:
    """The control: the ADMM products one precision below the stated
    one (float32 at HIGHEST, six passes, gives three bf16 passes), the
    Gram and debias products at the least the configuration admits."""
    p = config["precision"]
    lower = {"highest": "bf16x3", "bf16x3": "bf16x1"}[p["admm"]]
    return Precision(p["stats"][-1], lower)


def beta_gap(out, raws, t: float) -> float:
    """:func:`threshold_gap` to the nearest of the references' ``raws``."""
    return min(threshold_gap(out, raw, t) for raw in raws)


def threshold_gap(out, raw, t: float) -> float:
    """How far the program's thresholded aggregate ``out`` lies from the
    reference's raw aggregate ``raw``, relative to ``|raw|_2``.

    Where ``out`` keeps a coordinate, the gap there is ``|out_j - raw_j|``.
    Where it zeroes one, the program's raw value lay within ``t`` of 0,
    so the gap is at least ``max(|raw_j| - t, 0)``.  The threshold then
    adds no jump: a coordinate that lies near ``t`` on both sides and is
    kept on one side only adds what its raw values differ by at most.
    """
    out = np.asarray(out, np.float64)
    raw = np.asarray(raw, np.float64)
    gap = np.where(out != 0, np.abs(out - raw),
                   np.maximum(np.abs(raw) - t, 0.0))
    return float(np.linalg.norm(gap) / max(np.linalg.norm(raw), 1e-30))
