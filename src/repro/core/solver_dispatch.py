"""One dispatch layer for every Dantzig/CLIME solve in the system.

Every solver entry point (:mod:`repro.core.slda`, :mod:`repro.core.clime`,
:mod:`repro.core.distributed`, :mod:`repro.core.path`) routes through
:func:`solve_dantzig` here, which picks the implementation from the
problem shape and config:

``scan``
    The ``lax.scan`` ADMM in :func:`repro.core.dantzig.solve_dantzig_scan`.
    Selected when ``cfg.fused`` is False (it is the only path with
    residual-balancing adaptive rho), or as the fallback when the fused
    kernel cannot fit even one 128-column block in the fast-memory
    budget (d ≳ 2200 at f32 in the fixed kernel, d ≳ 2000 with state
    I/O, under the v5e budget).

``fused``
    The Pallas kernel in :mod:`repro.kernels.dantzig_fused` with the
    whole (d, k) batch in one VMEM-resident grid step.

``fused_blocked``
    The same kernel with the column batch tiled over a Pallas grid;
    chosen when the single-block footprint exceeds the budget.  Block
    size comes from :func:`repro.kernels.dantzig_fused.pick_block_k`
    (override with ``cfg.block_k``).

The fast-memory budget is ``cfg.vmem_budget`` when set, else derived
from the backend (:func:`repro.kernels.dantzig_fused.backend_vmem_budget`):
TPU reads the attached chip's VMEM, CPU mirrors the v5e so shapes
validated under the interpreter pick the chip's path, and any other
backend raises (the fused kernel is a TPU design).

Every entry point accepts either the raw (d, d) matrix or its
:class:`~repro.kernels.spectral.SpectralFactor`; a factor is threaded
to the implementation untouched, so the O(d^3) eigendecomposition
happens exactly once per Sigma_hat no matter how many solves share it.

Convergence-adaptive mode (DESIGN.md §7): ``cfg.tol`` switches every
path -- scan, fused, fused_blocked -- from the fixed-iteration
schedule to the residual-gated early exit, and every entry point
accepts a warm :class:`~repro.kernels.dantzig_fused.AdmmState` to
resume from.  :func:`solve_dantzig_full` exposes the full result
(solution, warm rho, resumable state, executed per-column iteration
counts); the narrower entry points discard what they don't return.
The adaptive fused kernel streams the 4-leaf state in AND out, so its
blocking model uses the larger ``state_io`` footprint in
``fused_block_vmem_bytes``/``pick_block_k``.

The choice is made at trace time from static shapes, so it adds zero
runtime cost and composes with jit/vmap/shard_map.  On non-TPU backends
the fused kernel runs under the Pallas interpreter -- a correctness
path, not a performance one; ``cfg.fused`` still selects it so tests
exercise identical code on every backend.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.analysis import (
    DtypePolicy,
    Param,
    PrimitiveBudget,
    VmemConformance,
    trace_contract,
)
from repro.core import dantzig as _dantzig
from repro.kernels import ops as kops
from repro.kernels.dantzig_fused import (
    DEFAULT_VMEM_BUDGET,
    AdmmState,
    backend_vmem_budget,
    fused_block_vmem_bytes,
    pick_block_k,
)
from repro.kernels.spectral import SpectralFactor  # noqa: F401  (re-export)

__all__ = [
    "SolverChoice",
    "SolveResult",
    "select_solver",
    "solve_dantzig",
    "solve_dantzig_with_rho",
    "solve_dantzig_full",
    "AdmmState",
    "fused_block_vmem_bytes",
    "backend_vmem_budget",
    "DEFAULT_VMEM_BUDGET",
]


class SolverChoice(NamedTuple):
    """Trace-time solver selection for a (d, k) Dantzig batch."""

    kind: str  # "scan" | "fused" | "fused_blocked"
    block_k: int | None = None  # columns per grid step (fused paths)


def select_solver(
    cfg: "_dantzig.DantzigConfig",
    d: int,
    k: int,
    backend: str | None = None,
    state_io: bool | None = None,
) -> SolverChoice:
    """Pick the solver implementation for a (d, k) batch.

    The fast-memory budget is ``cfg.vmem_budget`` when set, else the
    ``backend``'s budget (None = the active ``jax.default_backend()``).
    ``state_io`` selects the adaptive kernel's larger VMEM footprint
    (full ADMM state streamed in and out); None derives it from the
    config -- ``cfg.tol`` routes to the adaptive kernel.
    """
    if not cfg.fused:
        return SolverChoice("scan")
    if state_io is None:
        state_io = cfg.tol is not None
    budget = cfg.vmem_budget
    if budget is None:
        budget = backend_vmem_budget(backend)
    bk = pick_block_k(d, k, budget, state_io=state_io)
    if bk is None:
        # even one column per block cannot fit next to A and Q; an
        # explicit cfg.block_k cannot override infeasibility
        return SolverChoice("scan")
    if cfg.block_k is not None:
        # an override may force FINER blocking but never a block that
        # busts the budget (bk from pick_block_k is the max that fits)
        bk = max(1, min(cfg.block_k, k, bk))
    if bk >= k:
        return SolverChoice("fused", k)
    return SolverChoice("fused_blocked", bk)


class SolveResult(NamedTuple):
    """Everything a dispatched solve can hand back (DESIGN.md §7)."""

    beta: jnp.ndarray  # the sparse solution, trailing shape of b
    rho: jnp.ndarray  # (k,) warm per-problem ADMM penalties
    state: AdmmState  # full final state, resumable via `state=`
    iters: jnp.ndarray  # (k,) int32 executed iterations per column


def solve_dantzig(
    a: "jnp.ndarray | SpectralFactor",
    b: jnp.ndarray,
    lam,
    cfg: "_dantzig.DantzigConfig | None" = None,
    *,
    rho: jnp.ndarray | None = None,
    state: AdmmState | None = None,
    backend: str | None = None,
) -> jnp.ndarray:
    """Solve a (batch of) Dantzig problems via the dispatched implementation.

    Args:
      a:   (d, d) PSD matrix, or its precomputed
           :class:`~repro.kernels.spectral.SpectralFactor` (skips the
           O(d^3) eigendecomposition -- the pipeline shares one factor
           across the direction solve, CLIME, and lambda sweeps).
      b:   (d,) or (d, k) right-hand side(s).
      lam: scalar or (k,) per-problem box radius.
      rho: optional scalar or (k,) per-column ADMM penalty.  On the
           fused paths it is a traced operand (warm per-column
           estimates never recompile); on the scan path it seeds the
           adaptive-rho state in place of ``cfg.rho``.
      state: optional warm :class:`AdmmState` (leaves shaped like
           ``b``) to resume from instead of the zero cold start.
    Returns beta with the same trailing shape as ``b``, in ``b``'s
    dtype on every path (so toggling ``cfg.fused`` never changes the
    output dtype).
    """
    out, _ = solve_dantzig_with_rho(
        a, b, lam, cfg, rho=rho, state=state, backend=backend)
    return out


def solve_dantzig_with_rho(
    a: "jnp.ndarray | SpectralFactor",
    b: jnp.ndarray,
    lam,
    cfg: "_dantzig.DantzigConfig | None" = None,
    *,
    rho: jnp.ndarray | None = None,
    state: AdmmState | None = None,
    backend: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`solve_dantzig` plus the final per-problem rho.

    On the scan path the returned rho is the residual-balanced adapted
    value; on the fused paths (fixed rho) it is the input broadcast to
    (k,).  Either way it is the warm estimate to thread into the next
    solve of a regularization-path sweep.
    """
    if cfg is None:
        cfg = _dantzig.DantzigConfig()
    if cfg.tol is not None or state is not None:
        # the adaptive / warm-started modes carry full state anyway;
        # route through the full solve and discard the extras
        result = solve_dantzig_full(
            a, b, lam, cfg, rho=rho, state=state, backend=backend)
        return result.beta, result.rho
    squeeze = b.ndim == 1
    b2 = b[:, None] if squeeze else b
    d, k = b2.shape
    choice = select_solver(cfg, d, k, backend, state_io=False)
    if choice.kind == "scan":
        out, rho_final = _dantzig.solve_dantzig_scan(
            a, b2, lam, cfg, rho0=rho, return_rho=True)
        out = out.astype(b.dtype)
    else:
        rho_in = cfg.rho if rho is None else rho
        out = kops.dantzig_fused(
            a, b2, lam,
            iters=cfg.max_iters,
            rho=rho_in,
            alpha=cfg.alpha,
            block_k=choice.block_k,
            vmem_budget=cfg.vmem_budget,
        )
        rho_final = jnp.broadcast_to(
            jnp.asarray(rho_in, jnp.float32), (k,))
    if squeeze:
        return out[:, 0], rho_final if rho_final.ndim == 0 else rho_final[0]
    return out, rho_final


@trace_contract(
    "solver_dispatch.solve_dantzig_full",
    contracts=(
        # factor-fed solves must not re-factorize; raw input costs one
        PrimitiveBudget("eigh", exact=Param("eighs")),
        PrimitiveBudget("pallas_call", exact=Param("pallas_calls")),
        DtypePolicy(),
        VmemConformance(),
    ),
)
def solve_dantzig_full(
    a: "jnp.ndarray | SpectralFactor",
    b: jnp.ndarray,
    lam,
    cfg: "_dantzig.DantzigConfig | None" = None,
    *,
    rho: jnp.ndarray | None = None,
    state: AdmmState | None = None,
    backend: str | None = None,
) -> SolveResult:
    """Dispatched solve returning the full :class:`SolveResult`.

    The convergence-adaptive entry point: honors ``cfg.tol`` /
    ``cfg.check_every`` on every path (scan's while_loop, the fused
    kernel's chunked while_loop), resumes from ``state`` when given,
    and returns the final state + executed per-column iteration counts
    next to the solution and warm rho.  With ``cfg.tol=None`` it runs
    exactly ``cfg.max_iters`` iterations (from ``state`` if provided)
    and ``iters`` reports the fixed count.

    Iteration counts are reported at the solver's native granularity
    broadcast to columns: the whole batch shares one count on the scan
    path, each fused grid block shares its block's count.
    """
    if cfg is None:
        cfg = _dantzig.DantzigConfig()
    squeeze = b.ndim == 1
    b2 = b[:, None] if squeeze else b
    d, k = b2.shape
    if state is not None:
        leaves = [jnp.asarray(v) for v in state]
        leaves = [v[:, None] if v.ndim == 1 else v for v in leaves]
        state = AdmmState(*leaves)
    choice = select_solver(cfg, d, k, backend, state_io=True)
    if choice.kind == "scan":
        out, rho_final, fstate, iters = _dantzig.solve_dantzig_scan(
            a, b2, lam, cfg, rho0=rho, return_rho=True,
            state0=state, return_info=True)
        out = out.astype(b.dtype)
        iters_col = jnp.broadcast_to(iters, (k,))
    else:
        rho_in = cfg.rho if rho is None else rho
        fused = kops.dantzig_fused(
            a, b2, lam,
            iters=cfg.max_iters,
            rho=rho_in,
            alpha=cfg.alpha,
            block_k=choice.block_k,
            vmem_budget=cfg.vmem_budget,
            tol=cfg.tol,
            check_every=cfg.check_every,
            state=state,
            return_info=True,
        )
        out = fused.beta.astype(b.dtype)
        fstate = fused.state
        rho_final = jnp.broadcast_to(jnp.asarray(rho_in, jnp.float32), (k,))
        # per-block counts -> per-column (each block's columns share it)
        iters_col = jnp.repeat(fused.iters, choice.block_k or k)[:k]
    if squeeze:
        return SolveResult(
            out[:, 0],
            rho_final if rho_final.ndim == 0 else rho_final[0],
            AdmmState(*(v[:, 0] for v in fstate)),
            iters_col[0],
        )
    return SolveResult(out, rho_final, fstate, iters_col)
