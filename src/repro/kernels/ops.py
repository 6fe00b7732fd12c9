"""Jit'd public wrappers over the Pallas kernels.

On a CPU container the kernels execute under ``interpret=True``
(Pallas interpreter runs the kernel body on the host); on a real TPU
the same call sites compile to Mosaic.  Callers never pass
``interpret`` -- it is derived from the backend *per call* (NOT at
import time: tests and launch scripts may switch the backend via
``jax.config`` after this module is imported).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gram import gram_pallas
from repro.kernels.soft_threshold import soft_threshold_pallas


def _interpret() -> bool:
    """Resolve interpret-vs-Mosaic from the backend active *now*."""
    return jax.default_backend() != "tpu"


def gram(x: jnp.ndarray, mu: jnp.ndarray, **kw) -> jnp.ndarray:
    """Mean-centered Gram matrix (X - mu)^T(X - mu), float32 accumulate."""
    kw.setdefault("interpret", _interpret())
    return gram_pallas(x, mu, **kw)


def soft_threshold(x: jnp.ndarray, t, **kw) -> jnp.ndarray:
    """Fused shrink: sign(x) * max(|x| - t, 0)."""
    kw.setdefault("interpret", _interpret())
    return soft_threshold_pallas(x, t, **kw)


@functools.partial(
    jax.jit, static_argnames=("iters", "alpha", "block_k", "interpret",
                              "tol", "check_every", "return_info")
)
def _dantzig_fused_jit(a, b, lam, rho, state, *, iters, alpha, block_k,
                       interpret, tol, check_every, return_info):
    """Spectral factor (O(d^3), skipped when handed one) + the kernel."""
    from repro.kernels.dantzig_fused import dantzig_fused_pallas
    from repro.kernels.spectral import SpectralFactor, spectral_factor

    if not isinstance(a, SpectralFactor):
        a = spectral_factor(a.astype(jnp.float32))
    out = dantzig_fused_pallas(a, b=b, lam=lam, rho=rho,
                               iters=iters, alpha=alpha, block_k=block_k,
                               interpret=interpret, tol=tol,
                               check_every=check_every, state=state,
                               return_info=return_info)
    if return_info:
        return out._replace(beta=out.beta.astype(b.dtype))
    return out.astype(b.dtype)


def dantzig_fused(a, b, lam, *, iters=500, rho=1.0, alpha=1.7,
                  block_k=None, vmem_budget=None, tol=None, check_every=10,
                  state=None, return_info=False, **kw):
    """Whole Dantzig/CLIME ADMM solve in the blocked VMEM-resident kernel.

    ``a`` is either the raw (d, d) matrix -- factorized here, O(d^3)
    once per trace -- or a :class:`~repro.kernels.spectral.SpectralFactor`
    whose eigendecomposition is reused as-is (the pipeline factorizes
    Sigma_hat exactly once and threads the factor through every solve).

    ``rho`` may be a scalar or a (k,) per-column array (a traced
    operand -- warm per-column estimates do not recompile).  ``block_k``
    of None lets :func:`repro.kernels.dantzig_fused.pick_block_k` size
    the block to ``vmem_budget`` (None = the active backend's budget,
    see :func:`repro.kernels.dantzig_fused.backend_vmem_budget`).

    Convergence-adaptive mode (DESIGN.md §7): a static ``tol`` enables
    the kernel's residual-gated early exit (chunked every
    ``check_every`` iterations, capped at ``iters``); ``state`` resumes
    from a previous solve's
    :class:`~repro.kernels.dantzig_fused.AdmmState`; ``return_info``
    returns the full
    :class:`~repro.kernels.dantzig_fused.FusedSolveResult` (solution +
    state + per-block iteration counts).  Any of the three routes to
    the state-I/O kernel, whose larger VMEM footprint the blocking
    model accounts for.

    Returns a (d, k) sparse solution in ``b``'s dtype (the dispatch
    layer applies the same contract to the scan path, so toggling
    ``cfg.fused`` never changes dtypes), or the ``FusedSolveResult``
    when ``return_info``.
    """
    from repro.kernels.dantzig_fused import (
        backend_vmem_budget, fused_block_vmem_bytes, pick_block_k,
    )
    from repro.kernels.spectral import sigma_of

    interpret = kw.pop("interpret", None)
    if interpret is None:
        interpret = _interpret()
    if kw:
        raise TypeError(f"unexpected keyword arguments: {sorted(kw)}")
    if vmem_budget is None:
        vmem_budget = backend_vmem_budget()
    state_io = tol is not None or state is not None or return_info
    d = sigma_of(a).shape[0]
    squeeze = b.ndim == 1
    if squeeze:
        b = b[:, None]
        if state is not None:
            state = type(state)(*(s[:, None] for s in state))
    if block_k is None:
        block_k = pick_block_k(d, b.shape[1], vmem_budget, state_io=state_io)
        if block_k is None:
            if not interpret:
                raise ValueError(
                    f"dantzig_fused: A and Q at d={d} exceed the "
                    "VMEM budget for any column block; use the scan solver "
                    "(repro.core.solver_dispatch falls back automatically)")
            block_k = b.shape[1]  # interpreter has no VMEM limit
    elif not interpret:
        bk = max(1, min(block_k, b.shape[1]))
        if bk != b.shape[1] and bk % 128:
            raise ValueError(
                f"dantzig_fused: block_k={block_k} must be a multiple of "
                f"128 or the whole batch ({b.shape[1]}) for Mosaic")
        if fused_block_vmem_bytes(d, bk, state_io=state_io) > vmem_budget:
            raise ValueError(
                f"dantzig_fused: block_k={block_k} at d={d} exceeds "
                "the VMEM budget; pass block_k=None to auto-size the block")
    out = _dantzig_fused_jit(a, b, lam, rho, state, iters=iters, alpha=alpha,
                             block_k=block_k, interpret=interpret, tol=tol,
                             check_every=check_every,
                             return_info=return_info)
    if return_info:
        if squeeze:
            out = out._replace(
                beta=out.beta[:, 0],
                state=type(out.state)(*(s[:, 0] for s in out.state)))
        return out
    return out[:, 0] if squeeze else out
