"""The benchmark's data: a frozen copy of the §5.1 generator.

The paper's synthetic design (Tian & Gu 2016, §5.1): Sigma*_jk =
rho^|j-k| (AR(1)), mu1 = 0, mu2 = (1,...,1,0,...,0) with ``n_signal``
ones, beta* = Theta* (mu1 - mu2), r = n1 / n.  ``make_problem`` and
``sample_machines`` copy the program's ``repro.stats.synthetic`` as it
stood when this benchmark was written, so that a change to the program
cannot change the benchmark's data; a CPU test pins the two draw for
draw.  ``penalties`` copies the lambda / threshold rule of the chip
smoke test.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Problem(NamedTuple):
    sigma: jnp.ndarray  # (d, d) true covariance
    theta: jnp.ndarray  # (d, d) true precision
    mu1: jnp.ndarray
    mu2: jnp.ndarray
    beta_star: jnp.ndarray  # Theta* (mu1 - mu2)
    chol: jnp.ndarray  # cholesky(sigma), for sampling


def make_problem(d: int, n_signal: int, rho: float,
                 signal: float = 1.0) -> Problem:
    """The §5.1 design at dimension ``d`` (AR(1) covariance)."""
    idx = np.arange(d)
    sigma = rho ** np.abs(idx[:, None] - idx[None, :])
    theta = np.linalg.inv(sigma)
    mu1 = np.zeros(d)
    mu2 = np.zeros(d)
    mu2[:n_signal] = signal
    beta_star = theta @ (mu1 - mu2)
    beta_star[np.abs(beta_star) < 1e-10] = 0.0
    chol = np.linalg.cholesky(sigma)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return Problem(f32(sigma), f32(theta), f32(mu1), f32(mu2),
                   f32(beta_star), f32(chol))


def sample_two_class(key, mu1, mu2, chol, n1: int, n2: int):
    """Draw (X: (n1, d), Y: (n2, d)) from the two Gaussians."""
    k1, k2 = jax.random.split(key)
    d = mu1.shape[0]
    x = mu1 + jax.random.normal(k1, (n1, d)) @ chol.T
    y = mu2 + jax.random.normal(k2, (n2, d)) @ chol.T
    return x, y


def sample_machines(key, problem: Problem, m: int, n1: int, n2: int):
    """Stacked per-machine shards xs: (m, n1, d), ys: (m, n2, d)."""
    keys = jax.random.split(key, m)
    return jax.vmap(lambda k: sample_two_class(
        k, problem.mu1, problem.mu2, problem.chol, n1, n2))(keys)


def penalties(beta_star, d: int, n_per_machine: int, n_total: int,
              lam_coef: float = 0.30, t_coef: float = 0.75):
    """(lambda, t): lambda at the local sample size, t over all N."""
    b1 = float(jnp.sum(jnp.abs(beta_star)))
    lam = lam_coef * math.sqrt(math.log(d) / n_per_machine) * b1
    t = t_coef * math.sqrt(math.log(d) / n_total) * b1
    return lam, t


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any whole number that fits in 64 bits."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must lie in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


class Pool(NamedTuple):
    """``size`` datasets of one cell, each the rows of all held machines."""

    xs: jnp.ndarray  # (size, machines, n1, d)
    ys: jnp.ndarray  # (size, machines, n2, d)
    lam: float
    t: float


def make_pool(seed: int, *, d: int, n_signal: int, rho: float, signal: float,
              r: float, n_per_machine: int, machines: int, machines_held: int,
              size: int, lam_coef: float, t_coef: float) -> Pool:
    """Draw the cell's pool on the default device, in one jitted call.

    Every dataset has the same shapes; the seed changes only the draws.
    """
    problem = make_problem(d, n_signal, rho, signal)
    n1 = int(n_per_machine * r)
    n2 = n_per_machine - n1

    @jax.jit
    def draw(key, mu1, mu2, chol):
        p = problem._replace(mu1=mu1, mu2=mu2, chol=chol)
        keys = jax.random.split(key, size)
        return jax.vmap(lambda k: sample_machines(
            k, p, machines_held, n1, n2))(keys)

    xs, ys = draw(key_from_seed(seed), problem.mu1, problem.mu2, problem.chol)
    lam, t = penalties(problem.beta_star, d, n_per_machine,
                       machines * n_per_machine, lam_coef, t_coef)
    return Pool(xs, ys, lam, t)
