"""Pallas TPU kernel: blocked, grid-parallel fused Dantzig/CLIME ADMM solve.

The per-machine hot loop of the paper is the batched two-block ADMM in
repro.core.dantzig.  Lowered through XLA it re-reads the (d, d) matrix
A, the spectral factor Q and the diagonal (L^2+1)^-1 from HBM on every
one of ~500 iterations -- the dry-run shows the estimator is
memory-bound 107:1 (compute 1.4e-5 s vs memory 1.5e-3 s per solve at
d=256).

TPU adaptation: the columns of a CLIME batch are independent problems
that share only the loop-invariant operands (A, Q, inv).  The kernel
therefore tiles the column batch k over a 1-D Pallas grid:

  grid step i owns columns [i*block_k, (i+1)*block_k) and runs the
  ENTIRE solve for its block in VMEM -- an iteration loop whose body is
  four (d, d) x (d, block_k) MXU matmuls plus clip/shrink on the VPU.

``block_k`` is chosen (see :func:`pick_block_k`) so that
``A + Q + inv + b + out + ADMM state blocks + loop temporaries`` fit
the per-core VMEM budget.  A and Q are re-fetched once per block --
still ~iters x fewer HBM bytes per block than the XLA scan path, which
re-streams them every iteration.  When the whole batch fits, the grid
collapses to a single step and the kernel degenerates to the original
whole-array design.

Tail handling: k is padded up to a multiple of ``block_k`` with
neutral columns (b = 0, lam = 1, rho = 1, zero warm state, whose exact
solution is 0), so *any* (d, k) shape is exact; the wrapper slices the
pad columns off the output.  Columns never interact, so the pad is
mathematically inert, not just approximately so -- and because the
neutral column's residual is exactly zero from the first iteration, a
pad column can never hold a block's convergence gate open.

``rho`` is a per-column (1, k) *operand* rather than a compile-time
scalar: callers (repro.core.clime) can reuse warm per-column rho
estimates across calls without triggering recompilation.  ``iters``
and ``alpha`` remain static.  No adaptive rho inside the kernel (it is
per-column scalar control flow); the exact-ADMM iteration is robust to
a fixed rho (see EXPERIMENTS.md SSPerf-A1).

Convergence-adaptive mode (DESIGN.md §7): with a static ``tol`` the
fixed ``fori_loop`` becomes a bounded ``lax.while_loop`` over chunks of
``check_every`` iterations.  After each chunk the kernel computes the
block's max scaled-ADMM residual IN VMEM (no HBM round trip):

  r_pri  = max_j max(||A beta_j - z_j - b_j||_inf, ||beta_j - w_j||_inf)
  s_dual = max_j rho_j * ||A dz_j + dw_j||_inf

(dz/dw are the last in-chunk iteration deltas of the constraint
copies) and stops the whole block when ``max(r_pri, s_dual) <= tol``,
capped at exactly ``max_iters`` iterations (the final chunk is
clamped when ``check_every`` does not divide it).  The executed
iteration count per block rides out as an extra int32 output, one
(1, 128) lane tile per block.  The adaptive kernel also takes and
returns the full ADMM state ``(z, w, u1, u2)`` (:class:`AdmmState`),
so a solve can RESUME
from an earlier solution -- glmnet-style warm starts across lambda-path
re-sweeps -- instead of restarting from zero.  ``tol=None`` keeps the
original fixed-iteration kernel (bit-exact with the pre-adaptive
golden pins).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spectral import SpectralFactor

# VMEM per TensorCore, keyed by ``jax.Device.device_kind``.  The v5e
# figure is what its compiler reports ("Used ... of 128.00M vmem").
CHIP_VMEM_BYTES = {
    "TPU v5 lite": 128 * 2**20,
}
# The chip these kernels are written for.  The CPU backend runs them in
# the Pallas interpreter, which has no VMEM, so it borrows this chip's
# figures: shapes validated on CPU then pick the scan/fused/
# fused_blocked path they will pick on the chip (DESIGN.md §5).
TARGET_CHIP = "TPU v5 lite"
# The blocking model plans for 3/4 of VMEM; the kernel's scoped limit is
# all of it, so the model may be off by a quarter without a refusal.
_BUDGET_NUM, _BUDGET_DEN = 3, 4
DEFAULT_VMEM_BUDGET = CHIP_VMEM_BYTES[TARGET_CHIP] * _BUDGET_NUM // _BUDGET_DEN

# f32 tiles are (8 sublanes, 128 lanes): a block's last two dims are
# padded up to them in VMEM, and a column block must be a multiple of
# 128 lanes unless it spans the whole batch.
_SUBLANES, _LANES = 8, 128
# Scratch beyond the double-buffered operands, as counts of (d, block_k)
# and (d, d) buffers by ``state_io``: the live values of one ADMM
# iteration, and the bf16 parts a full-f32 MXU product splits its
# operands into.  Fitted as an upper bound to the least scoped VMEM
# limit Mosaic accepts on v5e at (d, block_k) in {(200, 200),
# (256, 512), (512, 256), (1024, 128), (2048, 128)}: the model exceeds
# it by 0.2-52% (fixed) and 1-89% (state I/O).
_TEMPORARIES = {False: (14, 2), True: (22, 5)}
# Mosaic's internal scratch, outside the operand and temporary buffers.
_INTERNAL_SCRATCH = 2**20
# Mosaic unrolls a block's vector ops, so compile time grows with
# d * block_k.  For v5e at d=1024: 3 s (fixed kernel) and 7 s (state
# kernel) at block_k=128, 40 s and 67 s at block_k=1024 and 640.  Blocks
# stay under this many elements (but never under 128 columns); each
# extra grid step re-reads A and Q once, d^2 floats against the
# block's ~8 * iters * d^2 * block_k flops.
_MAX_BLOCK_ELEMS = 2**17

# pallas_call names of the two kernels (trace contracts match on them)
FIXED_KERNEL = "fused_admm"
STATE_KERNEL = "fused_admm_state"


class AdmmState(NamedTuple):
    """The full two-block ADMM state of a (d, k) batch -- a pytree.

    Passing a previous solve's state back in resumes the iteration
    instead of restarting from zero (the warm-start carry of lambda-path
    re-sweeps, riding next to the per-column warm ``rho``).  Leaves may
    carry extra leading axes (e.g. the (L, d, k) per-lambda states of a
    folded path sweep).
    """

    z: jnp.ndarray  # box-constrained copy of A beta - b
    w: jnp.ndarray  # sparse copy of beta (the solution estimate)
    u1: jnp.ndarray  # scaled dual for A beta - z = b
    u2: jnp.ndarray  # scaled dual for beta - w = 0

    @classmethod
    def zeros(cls, d: int, k: int, dtype=jnp.float32) -> "AdmmState":
        z = jnp.zeros((d, k), dtype)
        return cls(z, z, z, z)


class FusedSolveResult(NamedTuple):
    """Adaptive-mode kernel outputs (see DESIGN.md §7)."""

    beta: jnp.ndarray  # (d, k) the sparse ADMM copy w
    state: AdmmState  # full final state, resumable
    iters: jnp.ndarray  # (num_blocks,) int32 executed iterations per block


def chip_vmem_bytes(backend: str | None = None) -> int:
    """VMEM of the chip that ``backend`` (None = the active one) compiles for.

    TPU reads the attached chip's ``device_kind``; CPU stands in for
    :data:`TARGET_CHIP`.  Any other backend, or a chip missing from
    :data:`CHIP_VMEM_BYTES`, raises: a guessed figure would send shapes
    to a kernel the compiler refuses.
    """
    if backend is None:
        backend = jax.default_backend()
    if backend == "tpu":
        kind = jax.devices("tpu")[0].device_kind
    elif backend == "cpu":
        kind = TARGET_CHIP
    else:
        raise ValueError(
            f"no VMEM model for backend {backend!r}: the fused ADMM kernel "
            "is a TPU kernel (CPU runs it in the Pallas interpreter)")
    if kind not in CHIP_VMEM_BYTES:
        raise ValueError(
            f"no VMEM figure for device kind {kind!r}; add it to "
            "CHIP_VMEM_BYTES in repro.kernels.dantzig_fused")
    return CHIP_VMEM_BYTES[kind]


def backend_vmem_budget(backend: str | None = None) -> int:
    """Bytes the blocking model may plan for on ``backend``'s chip."""
    return chip_vmem_bytes(backend) * _BUDGET_NUM // _BUDGET_DEN


def _tile_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of one f32 (rows, cols) buffer after (8, 128) tiling."""
    rows = -(-rows // _SUBLANES) * _SUBLANES
    cols = -(-cols // _LANES) * _LANES
    return 4 * rows * cols


def fused_block_vmem_bytes(d: int, block_k: int, state_io: bool = False) -> int:
    """VMEM footprint of one grid step of the fused kernel.

    Every operand and result block is double-buffered by the Pallas
    pipeline, even A and Q, whose block never moves.  Fixed mode reads
    A, Q (d, d), inv (d, 1), b (d, block_k) and lam, rho (1, block_k),
    and writes out (d, block_k).  ``state_io`` (the adaptive /
    warm-start kernel) also reads and writes the four (d, block_k)
    :class:`AdmmState` leaves and writes a (1, 128) iteration count.
    On top come the :data:`_TEMPORARIES` and Mosaic's internal scratch.
    Each buffer is padded to (8, 128) tiles.
    """
    col = _tile_bytes(d, block_k)
    row = _tile_bytes(1, block_k)
    mat = _tile_bytes(d, d)
    inputs = 2 * mat + _tile_bytes(d, 1) + col + 2 * row
    outputs = col
    if state_io:
        inputs += 4 * col
        outputs += 4 * col + _tile_bytes(1, _LANES)
    n_col, n_mat = _TEMPORARIES[state_io]
    return (2 * (inputs + outputs) + n_col * col + n_mat * mat
            + _INTERNAL_SCRATCH)


def pick_block_k(d: int, k: int, budget: int = DEFAULT_VMEM_BUDGET,
                 state_io: bool = False) -> int | None:
    """Widest column block that fits the VMEM budget and compiles fast.

    Returns ``k`` when the whole batch fits in one block, else the
    largest multiple of 128 below ``k`` that fits (Mosaic accepts no
    other column block), or ``None`` when no such block fits -- callers
    fall back to the XLA scan solver then.  Blocks are also kept under
    :data:`_MAX_BLOCK_ELEMS` elements, which bounds compile time.
    ``state_io`` selects the adaptive kernel's larger footprint (see
    :func:`fused_block_vmem_bytes`).
    """
    widest = max(_LANES, _MAX_BLOCK_ELEMS // d // _LANES * _LANES)
    if k <= widest and fused_block_vmem_bytes(d, k, state_io) <= budget:
        return k
    for bk in range(min(widest, (k - 1) // _LANES * _LANES), 0, -_LANES):
        if fused_block_vmem_bytes(d, bk, state_io) <= budget:
            return bk
    return None


def _matmul(m, x, contract: int = 1):
    """``m @ x`` (``contract=1``) or ``m.T @ x`` (``contract=0``) in full
    f32 -- Mosaic's fp32 contract precision, as the scan solver."""
    return jax.lax.dot_general(
        m, x, (((contract,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _shrink(x, t):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


def _beta_update(a_ref, q_ref, inv, b, z, w, u1, u2):
    """``beta = Q diag(inv) Q^T (A (z + b - u1) + w - u2)`` and ``A beta``.

    A and Q are read from their VMEM refs at each use.  Held as values
    across the loop, and with an explicit ``Q.T``, Mosaic spilled copies
    of them into O(d^2) VMEM scratch, which refused d=2048.
    """
    a, q = a_ref[...], q_ref[...]
    beta = _matmul(q, inv * _matmul(q, _matmul(a, z + b - u1) + (w - u2),
                                    contract=0))
    return beta, _matmul(a, beta)


def _admm_iteration(a_ref, q_ref, inv, b, lam, inv_rho, alpha, z, w, u1, u2):
    """One exact two-block ADMM iteration (identical on every path)."""
    beta, ab = _beta_update(a_ref, q_ref, inv, b, z, w, u1, u2)
    ab_r = alpha * ab + (1.0 - alpha) * (z + b)
    beta_r = alpha * beta + (1.0 - alpha) * w
    z_new = jnp.clip(ab_r - b + u1, -lam, lam)
    w_new = _shrink(beta_r + u2, inv_rho)
    u1 = u1 + ab_r - z_new - b
    u2 = u2 + beta_r - w_new
    return z_new, w_new, u1, u2


def _fused_admm_kernel(a_ref, q_ref, inv_ref, b_ref, lam_ref, rho_ref, out_ref,
                       *, iters: int, alpha: float):
    """Fixed-iteration, cold-start kernel (the golden-pinned fast path).

    ``a_ref`` and ``q_ref`` hold A and its eigenvectors Q, (d, d) and
    VMEM-resident across all iterations.
    """
    inv = inv_ref[...]  # (d, 1) 1/(eig^2 + 1)
    b = b_ref[...]  # (d, block_k) this grid step's column block
    lam = lam_ref[...]  # (1, block_k)
    inv_rho = 1.0 / rho_ref[...]  # (1, block_k) per-column shrink threshold

    zeros = jnp.zeros_like(b)

    def body(_, carry):
        z, w, u1, u2 = carry
        return _admm_iteration(a_ref, q_ref, inv, b, lam, inv_rho, alpha,
                               z, w, u1, u2)

    z, w, u1, u2 = jax.lax.fori_loop(0, iters, body, (zeros, zeros, zeros, zeros))
    out_ref[...] = w


def _fused_admm_state_kernel(a_ref, q_ref, inv_ref, b_ref, lam_ref, rho_ref,
                             z0_ref, w0_ref, u10_ref, u20_ref,
                             w_ref, z_ref, u1_ref, u2_ref, it_ref,
                             *, max_iters: int, alpha: float,
                             tol: float | None, check_every: int):
    """Warm-startable kernel with full state I/O and (optionally) the
    residual-gated early exit (DESIGN.md §7).

    ``tol=None`` runs exactly ``max_iters`` iterations from the given
    state; otherwise the loop runs ``check_every``-iteration chunks
    under a bounded ``lax.while_loop``, stopping the whole block once
    its max scaled residual drops below ``tol`` (capped at exactly
    ``max_iters`` iterations -- the final chunk is clamped).
    """
    inv = inv_ref[...]
    b = b_ref[...]
    lam = lam_ref[...]
    rho = rho_ref[...]  # (1, block_k)
    inv_rho = 1.0 / rho
    state0 = (z0_ref[...], w0_ref[...], u10_ref[...], u20_ref[...])

    if tol is None:
        def body(_, carry):
            z, w, u1, u2 = carry
            return _admm_iteration(
                a_ref, q_ref, inv, b, lam, inv_rho, alpha, z, w, u1, u2)

        z, w, u1, u2 = jax.lax.fori_loop(0, max_iters, body, state0)
        it = jnp.int32(max_iters)
    else:
        def chunk_body(carry):
            it, z, w, u1, u2, _ = carry
            # the final chunk is clamped so the cap is EXACTLY max_iters
            # even when check_every does not divide it
            n = jnp.minimum(jnp.int32(check_every), max_iters - it)

            def body(_, c):
                z, w, u1, u2, _, _ = c
                zn, wn, u1n, u2n = _admm_iteration(
                    a_ref, q_ref, inv, b, lam, inv_rho, alpha, z, w, u1, u2)
                return zn, wn, u1n, u2n, zn - z, wn - w

            zeros = jnp.zeros_like(b)
            z, w, u1, u2, dz, dw = jax.lax.fori_loop(
                0, n, body, (z, w, u1, u2, zeros, zeros))
            # scaled-ADMM residuals of the block, entirely in VMEM:
            # one extra beta solve (4 matmuls) per chunk -- a
            # 1/check_every relative overhead on the chunk's compute.
            beta, ab = _beta_update(a_ref, q_ref, inv, b, z, w, u1, u2)
            r_pri = jnp.maximum(jnp.max(jnp.abs(ab - z - b)),
                                jnp.max(jnp.abs(beta - w)))
            dual_col = jnp.max(jnp.abs(_matmul(a_ref[...], dz) + dw), axis=0,
                               keepdims=True)  # (1, block_k)
            s_dual = jnp.max(rho * dual_col)
            return it + n, z, w, u1, u2, jnp.maximum(r_pri, s_dual)

        def chunk_cond(carry):
            it, _, _, _, _, res = carry
            return jnp.logical_and(it < max_iters, res > tol)

        it, z, w, u1, u2, _ = jax.lax.while_loop(
            chunk_cond, chunk_body,
            (jnp.int32(0), *state0, jnp.float32(jnp.inf)))

    w_ref[...] = w
    z_ref[...] = z
    u1_ref[...] = u1
    u2_ref[...] = u2
    it_ref[...] = jnp.full(it_ref.shape, it, jnp.int32)


def _pad_cols(x: jnp.ndarray, pad: int, value: float = 0.0) -> jnp.ndarray:
    return jnp.pad(x, ((0, 0), (0, pad)), constant_values=value)


@functools.partial(
    jax.jit,
    static_argnames=("iters", "alpha", "block_k", "interpret",
                     "tol", "check_every", "return_info"),
)
def dantzig_fused_pallas(
    a: jnp.ndarray | SpectralFactor,
    q: jnp.ndarray | None = None,
    inv_eig: jnp.ndarray | None = None,
    b: jnp.ndarray | None = None,
    lam: jnp.ndarray | float | None = None,
    rho: jnp.ndarray | float = 1.0,
    *,
    iters: int = 500,
    alpha: float = 1.7,
    block_k: int | None = None,
    interpret: bool = False,
    tol: float | None = None,
    check_every: int = 10,
    state: AdmmState | None = None,
    return_info: bool = False,
) -> jnp.ndarray | FusedSolveResult:
    """Blocked fused ADMM solve.

    Args:
      a, q:    (d, d) f32 matrix and its eigenvectors -- or pass a
               :class:`~repro.kernels.spectral.SpectralFactor` as ``a``
               (with ``q``/``inv_eig`` omitted) and the factor's pieces
               are used as-is; the kernel never re-factorizes.
      inv_eig: (d,) 1/(eig^2 + 1).
      b:       (d, k) right-hand sides.
      lam:     scalar or (k,) per-column box radius.
      rho:     scalar or (k,) per-column fixed ADMM penalty (an operand:
               changing it does NOT recompile).
      block_k: columns per grid step (None = whole batch in one block).
      tol:     static residual tolerance; None = fixed ``iters``
               iterations (bit-exact with the pre-adaptive kernel),
               else the chunked while_loop early exit (DESIGN.md §7).
      check_every: iterations per residual check (adaptive mode only).
      state:   optional :class:`AdmmState` with (d, k) leaves to resume
               from (zero-state cold start when None).
      return_info: also return the final state and per-block iteration
               counts as a :class:`FusedSolveResult`.

    Returns the sparse ADMM copy w: (d, k) f32, or a
    :class:`FusedSolveResult` when ``return_info``.
    """
    if isinstance(a, SpectralFactor):
        if q is not None or inv_eig is not None:
            raise TypeError(
                "dantzig_fused_pallas: pass EITHER a SpectralFactor OR "
                "(a, q, inv_eig), not both")
        a, q, inv_eig = a.sigma, a.q, a.inv_eig
    elif q is None or inv_eig is None:
        raise TypeError(
            "dantzig_fused_pallas: a raw matrix needs q and inv_eig "
            "(or pass a SpectralFactor as the first argument)")
    if b is None:
        raise TypeError("dantzig_fused_pallas: missing right-hand sides b")
    if lam is None:
        raise TypeError("dantzig_fused_pallas: missing box radius lam")
    d, k = b.shape
    if block_k is None:
        block_k = k
    block_k = max(1, min(block_k, k))
    inv2 = inv_eig.reshape(d, 1).astype(jnp.float32)
    lam2 = jnp.broadcast_to(jnp.asarray(lam, jnp.float32), (k,)).reshape(1, k)
    rho2 = jnp.broadcast_to(jnp.asarray(rho, jnp.float32), (k,)).reshape(1, k)
    b2 = b.astype(jnp.float32)

    num_blocks = -(-k // block_k)
    k_pad = num_blocks * block_k
    pad = k_pad - k
    if pad:
        # neutral tail columns: b = 0, lam = 1, rho = 1 (and zero warm
        # state) solve exactly to 0 AND report zero residual from the
        # first chunk, so a pad column never holds a block's
        # while_loop open
        b2 = _pad_cols(b2, pad)
        lam2 = _pad_cols(lam2, pad, 1.0)
        rho2 = _pad_cols(rho2, pad, 1.0)

    a2 = a.astype(jnp.float32)
    q2 = q.astype(jnp.float32)
    # the blocking model plans for part of VMEM; Mosaic may use all of it
    compiler_params = None if interpret else pltpu.CompilerParams(
        vmem_limit_bytes=chip_vmem_bytes())
    shared_specs = [
        pl.BlockSpec((d, d), lambda i: (0, 0)),
        pl.BlockSpec((d, d), lambda i: (0, 0)),
        pl.BlockSpec((d, 1), lambda i: (0, 0)),
        pl.BlockSpec((d, block_k), lambda i: (0, i)),
        pl.BlockSpec((1, block_k), lambda i: (0, i)),
        pl.BlockSpec((1, block_k), lambda i: (0, i)),
    ]

    if tol is None and state is None and not return_info:
        # the original fixed-iteration kernel: smallest VMEM footprint,
        # bit-exact with the pre-adaptive golden pins
        kernel = functools.partial(_fused_admm_kernel, iters=iters,
                                   alpha=alpha)
        out = pl.pallas_call(
            kernel,
            grid=(num_blocks,),
            in_specs=shared_specs,
            out_specs=pl.BlockSpec((d, block_k), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((d, k_pad), jnp.float32),
            interpret=interpret,
            compiler_params=compiler_params,
            name=FIXED_KERNEL,
        )(a2, q2, inv2, b2, lam2, rho2)
        return out[:, :k] if pad else out

    if state is None:
        state = AdmmState.zeros(d, k_pad)
    else:
        leaves = [jnp.asarray(s, jnp.float32) for s in state]
        if pad:
            leaves = [_pad_cols(s, pad) for s in leaves]
        state = AdmmState(*leaves)

    kernel = functools.partial(
        _fused_admm_state_kernel, max_iters=iters, alpha=alpha,
        tol=tol, check_every=check_every)
    col_spec = pl.BlockSpec((d, block_k), lambda i: (0, i))
    # each block's iteration count fills one full (1, 128) lane tile:
    # Mosaic refuses a (1, 1) block of a (1, num_blocks) array
    it_spec = pl.BlockSpec((1, _LANES), lambda i: (0, i))
    w, z, u1, u2, it = pl.pallas_call(
        kernel,
        grid=(num_blocks,),
        in_specs=shared_specs + [col_spec] * 4,
        out_specs=[col_spec] * 4 + [it_spec],
        out_shape=[jax.ShapeDtypeStruct((d, k_pad), jnp.float32)] * 4
        + [jax.ShapeDtypeStruct((1, num_blocks * _LANES), jnp.int32)],
        interpret=interpret,
        compiler_params=compiler_params,
        name=STATE_KERNEL,
    )(a2, q2, inv2, b2, lam2, rho2, *state)
    if pad:
        w, z, u1, u2 = (x[:, :k] for x in (w, z, u1, u2))
    it = it.reshape(num_blocks, _LANES)[:, 0]
    result = FusedSolveResult(w, AdmmState(z, w, u1, u2), it)
    return result if return_info else result.beta
