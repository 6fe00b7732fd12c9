"""The work each layer of one fit requires, counted from the shapes.

Counts are of the algorithm, not of one implementation, so that every
implementation of a layer is held to the same number.  Flops count a
multiply-add as two.  Bytes are the compulsory traffic: each input
read once and each output written once, as a kernel that keeps its
working set on chip would move them; so a share of the roofline built
from them cannot pass 100%.
"""

from __future__ import annotations

from typing import NamedTuple

F32 = 4  # bytes


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, other):
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_seconds(self, peak_flops: float, peak_bytes_per_s: float):
        """(least time, the bound that sets it: "compute" or "memory")."""
        compute = self.flops / peak_flops
        memory = self.bytes / peak_bytes_per_s
        return (compute, "compute") if compute >= memory else (memory, "memory")


def gram(n: int, d: int) -> Work:
    """The pooled within-class covariance of n rows: sum of centered
    outer products, 2 n d^2 flops; reads the rows, writes Sigma."""
    return Work(2.0 * n * d * d, F32 * (n * d + d * d))


def admm_iteration(d: int, k: int) -> Work:
    """One ADMM iteration on a (d, k) batch of right-hand sides: the
    beta-step ``Q diag Q^T (Sigma v)`` and the product ``Sigma beta``,
    four (d, d) @ (d, k) products, 8 d^2 k flops.  An iteration moves
    no compulsory bytes: its operands can stay on chip between
    iterations."""
    return Work(8.0 * d * d * k, 0.0)


def admm_solve(d: int, k: int, iters: int) -> Work:
    """A whole solve of ``iters`` iterations: reads Sigma, Q and the
    right-hand sides once, writes the (d, k) solution."""
    it = admm_iteration(d, k)
    return Work(iters * it.flops, F32 * (2 * d * d + 2 * d * k))


def admm(d: int, iters: int) -> Work:
    """One machine's two solves: the direction (k = 1) and the d CLIME
    columns (k = d)."""
    return admm_solve(d, 1, iters) + admm_solve(d, d, iters)


def debias_round(d: int) -> Work:
    """One round's correction ``anchor - Theta^T (Sigma anchor - mu_d)``:
    two (d, d) @ (d, 1) products; reads Sigma and Theta."""
    return Work(4.0 * d * d, F32 * (2 * d * d + 3 * d))


def fit(n: int, d: int, iters: int, rounds: int) -> Work:
    """One machine's share of a fit, leaving out the eigendecomposition
    (whose flops depend on the algorithm that computes it)."""
    out = gram(n, d) + admm(d, iters)
    for _ in range(rounds):
        out = out + debias_round(d)
    return out
