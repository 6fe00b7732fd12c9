"""Multi-round refinement: past the one-shot m-barrier (DESIGN.md §8).

The paper's one-shot aggregation attains the centralized rate only
while the machine count m stays below Theorem 4.5's threshold; past it
the averaged debiased estimator degrades and the one-shot schedule has
no recourse.  Wang et al.'s EDSL and Lee et al.'s one-shot sparse
regression show the fix: a few extra O(d)-communication rounds recover
the centralized rate under much weaker conditions on m.

The refinement iteration here re-applies each worker's debias
correction AROUND THE MASTER'S AGGREGATE instead of the worker's own
biased estimate.  With anchor_1 = beta_hat (the local estimate), every
round t = 1..T is the SAME closed-form map

    beta_tilde_t^i = anchor_t^i - Theta_hat_i^T (Sigma_hat_i anchor_t^i - rhs_i)
    beta_bar_t     = mean_i beta_tilde_t^i        (ONE pmean of (d, K))
    anchor_{t+1}^i = beta_bar_t                   (replicated post-pmean)

so T = 1 IS the paper's one-shot estimator, bit for bit.  Writing
M = mean_i Theta_i^T Sigma_i, the aggregate error contracts as
``e_t = (I - M) e_{t-1}``: per-machine CLIME/covariance noise makes
``I - Theta_i^T Sigma_i`` small (entrywise <= lam' by the CLIME
constraint), and the FIXED POINT solves ``mean_i Theta_i^T (Sigma_i
beta - rhs_i) = 0`` -- its deviation from beta* averages the m
machines' score noise, i.e. the centralized rate, with no condition
tying m to the one-shot threshold.  The hard threshold stays a
master-side O(dK) postlude, exactly as in eq. 3.5.

Cost accounting (the whole point of the design):

* **Compute.**  Every round reuses the worker's ONE
  :class:`~repro.kernels.spectral.SpectralFactor`, its already-solved
  CLIME block and direction solve (:class:`~repro.core.pipeline.
  WorkerSolves`): a round is two (d, d) x (d, K) matmuls -- ZERO extra
  eigendecompositions, ZERO extra ADMM iterations.
* **Communication.**  One ``pmean`` of a (d, K) block per round over
  the data axes (T rounds = exactly T times the paper's per-round
  budget), plus the intra-machine model-axis ``all_gather`` of the
  correction slice -- inside a machine in the paper's cost model,
  exactly as in the one-shot schedule.  Masked aggregation
  (DESIGN.md §11) adds ONE scalar f32 psum per round (the live
  count); the trimmed mean and the masked compressed path gather
  per-machine blocks/weights instead.
* **Warm re-entry.**  ``collect_info=True`` threads both solves
  through the full dispatched result, so the returned
  :class:`~repro.core.pipeline.WorkerSolves` carries the warm
  rho/:class:`~repro.core.dantzig.AdmmState`/iteration counts.  A
  re-entry (a tuning loop re-running the rounds pipeline after moving
  lambda or t) passes them back and resumes each ADMM solve instead of
  restarting from zero -- with ``cfg.tol`` set, measurably fewer
  iterations (gated by ``benchmarks/multi_round.py``).

The round-loop body itself lives ONCE in :func:`_refinement_rounds`:
the mesh driver (:class:`_MeshRound`, collectives) and the vmap twin
(:class:`_SimRound`, machine-axis reductions) supply only the
axis-specific operations, so the two paths cannot drift -- the fault
and staleness logic of :mod:`repro.core.faults` is written once and
exercised identically by both.  The T (static, small) rounds unroll so
the jaxpr pins can count exactly T (d, K) ``pmean``s and ONE ``eigh``
per worker.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.analysis import (
    AxisPayloadBits,
    CollectiveContract,
    DtypePolicy,
    Param,
    PrimitiveBudget,
    VmemConformance,
    trace_contract,
)
from repro.core import compression as compression_core
from repro.core import faults as faults_core
from repro.core import pipeline
from repro.core import transport as transport_core
from repro.core.compression import Compression
from repro.core.dantzig import AdmmState, DantzigConfig
from repro.core.faults import Aggregation, FaultPlan, FaultSchedule
from repro.core.pipeline import DiscriminantHead, WorkerSolves
from repro.core.transport import CommPlan, Transport, TransportState

__all__ = [
    "refine_step",
    "worker_rounds",
    "simulate_multi_round",
    "simulate_round_loop",
]


def refine_step(ws: WorkerSolves, anchor: jnp.ndarray,
                model_axis: str | None = None) -> jnp.ndarray:
    """One worker's closed-form debias correction around ``anchor``.

    ``beta_tilde = anchor - Theta_hat^T (Sigma_hat anchor - rhs)``:
    round 1 anchors at the worker's own ``beta_hat`` (the paper's
    eq. 3.4), later rounds at the replicated aggregate.  No solver
    runs -- the round reuses the :class:`WorkerSolves` CLIME block
    (sharded blocks reassemble through the same masked intra-machine
    gather as the one-shot path).
    """
    resid = ws.stats.sigma @ anchor - ws.stats.rhs  # (d, K)
    return anchor - pipeline.apply_correction(
        ws.theta, ws.valid, resid, model_axis)


class _MeshRound:
    """One machine's view of a round: collectives aggregate (shard_map)."""

    def __init__(self, ws: WorkerSolves, model_axis: str | None,
                 data_axes: Sequence[str]):
        self.ws = ws
        self.model_axis = model_axis
        self.data_axes = tuple(data_axes)

    def correction(self, anchor):
        return refine_step(self.ws, anchor, self.model_axis)

    def mean(self, x):
        for ax in self.data_axes:
            x = jax.lax.pmean(x, ax)
        return x

    def sum(self, x):
        for ax in self.data_axes:
            x = jax.lax.psum(x, ax)
        return x

    def stack(self, x):
        """Machine-stack a per-machine value: (...) -> (m, ...)."""
        return faults_core.gather_machines(x, self.data_axes)

    def expand(self, w):
        return w  # this machine's scalar weight broadcasts against (d, K)

    def corrupt(self, code, block):
        return faults_core.corrupt_block(code, block)

    def screen(self, agg, block):
        return faults_core.screen_weight(agg, block)

    def broadcast(self, bar):
        return bar  # already this machine's replicated copy

    def agg_zeros(self, anchor):
        return jnp.zeros_like(anchor)

    def ef(self, comp, message, resid, ref):
        return compression_core.ef_step(comp, message, resid, ref)

    def corrupt_payload(self, comp, code, payload):
        return faults_core.corrupt_payload(comp, code, payload)

    def sparse_mean(self, comp, payload, ref):
        return compression_core.sparse_mean_mesh(
            comp, payload, ref, self.data_axes)

    def stack_payload(self, comp, payload):
        return compression_core.gather_payloads(
            comp, payload, self.data_axes)

    def downlink_wire(self, comp, payload, code):
        """The aggregator's broadcast: master-masked psum of the leaves.

        ``code`` is THIS machine's corruption code; only the master's
        survives the mask, so the downlink's fate is the aggregator's
        fault row and every receiver sees the same wire."""
        if code is not None:
            payload = faults_core.corrupt_payload(comp, code, payload)
        return transport_core.psum_broadcast(payload, self.data_axes)


class _SimRound:
    """The vmap twin: machines are a leading axis, reductions are local."""

    def __init__(self, ws: WorkerSolves):
        self.ws = ws
        self.m = ws.beta_hat.shape[0]

    def correction(self, anchor):
        return jax.vmap(refine_step)(self.ws, anchor)

    def mean(self, x):
        return jnp.mean(x, axis=0)  # the round's one "pmean"

    def sum(self, x):
        return jnp.sum(x, axis=0)

    def stack(self, x):
        return x  # the machine axis is already materialized

    def expand(self, w):
        return w.reshape(w.shape + (1, 1))

    def corrupt(self, code, block):
        return jax.vmap(faults_core.corrupt_block)(code, block)

    def screen(self, agg, block):
        return jax.vmap(lambda b: faults_core.screen_weight(agg, b))(block)

    def broadcast(self, bar):
        return jnp.broadcast_to(bar[None], (self.m,) + bar.shape)

    def agg_zeros(self, anchor):
        return jnp.zeros(anchor.shape[1:], anchor.dtype)

    def ef(self, comp, message, resid, ref):
        return jax.vmap(lambda msg, res: compression_core.ef_step(
            comp, msg, res, ref))(message, resid)

    def corrupt_payload(self, comp, code, payload):
        return jax.vmap(lambda c, p: faults_core.corrupt_payload(
            comp, c, p))(code, payload)

    def sparse_mean(self, comp, payload, ref):
        return compression_core.decode_mean(comp, payload, ref)

    def stack_payload(self, comp, payload):
        return payload

    def downlink_wire(self, comp, payload, code):
        """Machine 0 is the aggregator: its fault row corrupts the wire."""
        if code is not None:
            payload = faults_core.corrupt_payload(comp, code[0], payload)
        return payload


def _refinement_rounds(
    drv,
    *,
    rounds: int,
    anchor: jnp.ndarray,
    transport: Transport,
    plan: FaultPlan | None = None,
    state: TransportState | None = None,
    ref: jnp.ndarray | None = None,
    return_all_rounds: bool = False,
):
    """The ONE T-round body both drivers run (DESIGN.md §8/§10/§11/§13).

    ``drv`` supplies the axis-specific operations (mesh collectives vs
    machine-axis reductions); ``transport`` the per-round
    uplink/downlink codecs, aggregation policy, and staleness bound --
    everything else (the anchor/EF-residual/reference iteration, fault
    injection, screening, masked/trimmed aggregation, bounded
    staleness, and the last-good fallback) is written exactly once so
    the mesh and vmap twins cannot drift.

    With a default :class:`CommPlan` (no codecs, no plan, no
    aggregation) the branches reduce LITERALLY to the pre-fault code
    path: the legacy jaxpr (and its golden pins) is reproduced bit for
    bit.  ``ref`` seeds the SHARED delta reference on re-entry (the
    previous *received* aggregate); None starts at zeros, the round-1
    convention.  Both wires encode against this one reference: the
    uplink's per-machine EF residual and the downlink's
    aggregator-held residual ride in/out through ``state``.

    The downlink round close (transport contract, DESIGN.md §13): the
    aggregator EF-encodes the round's aggregate against ``ref``, the
    payload crosses the data axis on the master-masked psum of
    :func:`repro.core.transport.psum_broadcast` (where ``corrupt_payload``
    can hit it), and every machine -- master included -- applies the
    same whole-block finite screen to the same post-wire payload: on a
    corrupted round all of them fall back to ``ref`` together and the
    aggregator's residual drops (the rolled-back anchors regenerate the
    lost step next round), so the master/receiver reference views can
    never diverge and the stream resumes exactly one round delayed.

    Returns ``(bar-or-trajectory, final TransportState)``.
    """
    aggregation = transport.aggregation
    staleness = transport.staleness
    masked = aggregation is not None
    faulted = plan is not None
    if masked:
        aggregation.validate()
        # replicated, so an ALL-dead final round still returns a value
        # every machine agrees on (zeros before any round succeeded)
        last_good = drv.agg_zeros(anchor)
    resid = state.up_residual if state is not None else None
    down_resid = state.down_residual if state is not None else None
    if transport.any_up and resid is None:
        resid = jnp.zeros_like(anchor)
    if transport.any_down and down_resid is None:
        down_resid = drv.agg_zeros(anchor)  # replicated, like the aggregate
    if (transport.any_up or transport.any_down) and ref is None:
        # round-1 reference is zeros (the anchor is still per-machine);
        # afterwards the replicated RECEIVED aggregate -- both wires
        # share it
        ref = drv.agg_zeros(anchor)
    history = [anchor]  # entry j-1 = the round-j anchor
    bars = []
    for t in range(1, rounds + 1):  # static T: the jaxpr shows T rounds
        compression = transport.up(t).comp
        live = code = None
        if faulted:
            live, stale, code = plan.row(t)
        a = history[-1]
        if faulted and staleness > 0 and t > 1:
            a = faults_core.select_anchor(history, stale, t, staleness)
        with jax.named_scope("slda.debias"):
            beta_tilde = drv.correction(a)
        if compression is None:
            wire = drv.corrupt(code, beta_tilde) if faulted else beta_tilde
            if not masked and not faulted:
                bar = drv.mean(wire)  # the legacy bit-exact round
            elif not masked:
                # the fragile baseline under faults: a dropped machine's
                # slot contributes zeros but the divisor stays m, and
                # corrupt payloads reach the mean unscreened
                bar = drv.mean(jnp.where(drv.expand(live) > 0, wire, 0.0))
            else:
                w = drv.screen(aggregation, wire)
                if faulted:
                    w = live * w
                if aggregation.trim > 0:
                    bar, den = faults_core.trimmed_mean(
                        drv.stack(wire), drv.stack(w), aggregation.trim)
                else:
                    # select, never multiply: 0 * NaN would re-poison
                    num = drv.sum(jnp.where(drv.expand(w) > 0, wire, 0.0))
                    den = drv.sum(w)  # the liveness mask on the wire
                    bar = num / jnp.maximum(den, 1.0)
                bar = jnp.where(den > 0, bar, last_good)
        else:
            payload, new_resid = drv.ef(compression, beta_tilde, resid, ref)
            if faulted:
                # a dropped machine computed nothing this round: its EF
                # carry is untouched.  Corruption happens on the WIRE,
                # after the (honest) machine updated its own residual.
                resid = jnp.where(drv.expand(live) > 0, new_resid, resid)
                payload = drv.corrupt_payload(compression, code, payload)
            else:
                resid = new_resid
            if not masked and not faulted:
                bar = drv.sparse_mean(compression, payload, ref)  # legacy
            else:
                stacked = drv.stack_payload(compression, payload)
                w_live = drv.stack(live) if faulted else None
                if masked:
                    # decode RAW: the screen must see poisoned values to
                    # zero the whole machine, not a ref-filled repair
                    dense = compression_core.decode_stack(
                        compression, stacked, ref, screen_nonfinite=False)
                    w = jax.vmap(lambda b: faults_core.screen_weight(
                        aggregation, b))(dense)
                    if w_live is not None:
                        w = w_live * w
                    if aggregation.trim > 0:
                        bar, den = faults_core.trimmed_mean(
                            dense, w, aggregation.trim)
                    else:
                        bar, den = faults_core.masked_mean(dense, w)
                    bar = jnp.where(den > 0, bar, last_good)
                else:
                    # fragile baseline: a dropped machine's missing
                    # payload decodes to the reference (set semantics),
                    # still diluting the mean by the full m
                    dense = compression_core.decode_stack(
                        compression, stacked, ref)
                    keep = (w_live > 0).reshape(w_live.shape + (1, 1))
                    bar = jnp.mean(jnp.where(keep, dense, ref), axis=0)
        # ---- the downlink close (DESIGN.md §13): the aggregate back
        # down the wire, EF-compressed against the SAME reference ----
        down = transport.down(t)
        if down.compressed:
            u = bar + down_resid
            payload = down.encode(u, ref)
            wire = drv.downlink_wire(down.comp, payload, code)
            decoded = down.decode(wire, ref, screen_nonfinite=False)
            # whole-block receiver screen, replicated: a poisoned wire
            # rolls EVERY machine (master included) back to the last
            # received aggregate, so the shared reference never forks
            ok = jnp.all(jnp.isfinite(decoded))
            honest = down.decode(payload, ref, screen_nonfinite=False)
            # delivered: residual = quantization/selection leftovers.
            # rejected: DROP the carry -- receivers roll back to ref, so
            # next round's anchors regenerate the lost step themselves;
            # re-arming with it would deliver the step twice (and a
            # poisoned upstream aggregate would ride the carry forever)
            down_resid = jnp.where(ok, u - honest, jnp.zeros_like(u))
            bar = jnp.where(ok, decoded, ref)
        if transport.any_up or transport.any_down:
            ref = bar  # the received aggregate seeds both wires' deltas
        if masked:
            last_good = bar  # what receivers actually hold
        bars.append(bar)
        history.append(drv.broadcast(bar))
    out = jnp.stack(bars) if return_all_rounds else bars[-1]
    return out, TransportState(
        resid if transport.any_up else None,
        down_resid if transport.any_down else None)


def _check_plan(faults, expect_shape, where: str):
    if faults is None:
        return
    if isinstance(faults, FaultSchedule):
        raise TypeError(
            f"{where} takes a materialized FaultPlan (the faces call "
            "FaultSchedule.plan(m, rounds, staleness)); got a schedule")
    if faults.live.shape != expect_shape:
        raise ValueError(
            f"{where}: FaultPlan leaves must be {expect_shape}, got "
            f"{faults.live.shape}")


@trace_contract(
    "rounds.worker_rounds",
    contracts=(
        # refinement rounds reuse the round-one SpectralFactor
        PrimitiveBudget("eigh", exact=1),
        # the DENSE uplink: one (d, K) f32 psum per dense round over the
        # data axis -- count AND payload are pinned (0 when compressed:
        # a compressed trace must hold NO dense data-axis psum at all)
        CollectiveContract("psum", count=Param("dense_psums"), axis="data",
                           shape=Param("psum_payload"), dtype="float32"),
        # the liveness mask of DESIGN.md §11: one scalar f32 psum (the
        # live count) per masked dense round, nothing on the legacy path
        CollectiveContract("psum", count=Param("live_psums"), axis="data",
                           shape=(), dtype="float32"),
        PrimitiveBudget("psum", exact=Param("total_psums")),
        # intra-machine CLIME reassembly: one model-axis gather per round
        CollectiveContract("all_gather", count=Param("rounds"),
                           axis="model"),
        # the COMPRESSED uplink payload gathers, plus the fault layer's
        # block/weight gathers (0 on the legacy dense path) ...
        CollectiveContract("all_gather", count=Param("data_gathers"),
                           axis="data"),
        # ... and the bits everything moves per link, exactly, split by
        # direction: uplink payloads ride all_gathers, dense uplinks +
        # liveness masks + downlink payloads ride psums -- pinning each
        # primitive family to its analytic schedule total means a
        # hidden dense block in EITHER direction blows its own budget
        AxisPayloadBits("data", exact_bits=Param("data_gather_bits"),
                        prims=("all_gather",)),
        AxisPayloadBits("data", exact_bits=Param("data_psum_bits"),
                        prims=("psum",)),
        AxisPayloadBits("data", exact_bits=Param("data_total_bits")),
        # per-machine screening + decode sanitization are is_finite eqns
        PrimitiveBudget("is_finite", exact=Param("screen_ops")),
        PrimitiveBudget("pallas_call", exact=Param("pallas_calls")),
        DtypePolicy(),
        VmemConformance(),
    ),
)
def worker_rounds(
    head: DiscriminantHead,
    *data: jnp.ndarray,
    lam,
    lam_prime,
    rounds: int = 1,
    cfg: DantzigConfig = DantzigConfig(),
    data_axes: Sequence[str] = ("data",),
    model_axis: str | None = None,
    model_axis_size: int = 1,
    comm: CommPlan | None = None,
    compression: Compression | None = None,
    ef_residual: jnp.ndarray | None = None,
    down_residual: jnp.ndarray | None = None,
    resume_from: jnp.ndarray | None = None,
    faults: FaultPlan | None = None,
    staleness: int = 0,
    aggregation: Aggregation | None = None,
    rho_beta: jnp.ndarray | None = None,
    rho_theta: jnp.ndarray | None = None,
    state_beta: AdmmState | None = None,
    state_theta: AdmmState | None = None,
    collect_info: bool = False,
    return_ef_residual: bool = False,
    return_transport_state: bool = False,
):
    """T-round refined aggregate, from inside shard_map over the mesh.

    Runs :func:`~repro.core.pipeline.worker_solves` ONCE (suff stats,
    one eigh, direction + CLIME ADMM -- warm-startable via the
    ``rho_*`` / ``state_*`` carries of a previous invocation's
    :class:`WorkerSolves`), then ``rounds`` closed-form refinement
    rounds driven by ONE static comms config: ``comm`` (a
    :class:`~repro.core.transport.CommPlan`).  The default plan closes
    each round with one dense (d, K) ``pmean`` over ``data_axes`` --
    bit-identical to the pre-compression path; ``comm.uplink`` moves
    each round's top-k error-feedback payload through
    :func:`~repro.core.compression.sparse_mean_mesh` instead (residual
    seeded by ``ef_residual``), ``comm.downlink`` EF-compresses the
    aggregate's broadcast back down against the same reference
    (aggregator residual seeded by ``down_residual``), and
    ``comm.schedule`` (a :class:`~repro.core.transport.BitBudget`)
    replans both directions per round under a total bit budget.
    ``rounds=1`` dense reproduces the one-shot worker + single
    averaging round of Algorithm 1 exactly.

    The legacy ``compression=`` / ``staleness=`` / ``aggregation=``
    kwargs remain as deprecation shims (mutually exclusive with
    ``comm``); ``comm.faults`` must stay None here -- fault SCHEDULES
    are materialized by the faces, and ``faults`` is THIS machine's
    materialized :class:`~repro.core.faults.FaultPlan` row ((rounds,)
    leaves -- the per-machine liveness operand the faces shard in).
    ``aggregation`` switches the round close to the liveness-masked
    (or trimmed) robust mean of :mod:`repro.core.faults`;
    ``staleness`` bounds how many rounds a straggler's anchor may lag.

    ``resume_from`` re-enters a round stream mid-way: it seeds the
    round-1 anchor AND the shared delta reference with the previous
    received aggregate, so a split T-round run (with the carried
    residuals) matches an uninterrupted one.

    Returns ``(beta_bar, solves)``: the replicated (d, K) aggregate
    (un-thresholded -- the master's hard threshold is the caller's
    O(dK) postlude) and the worker's solves for reuse/warm re-entry.
    ``return_ef_residual`` appends the final uplink error-feedback
    residual (None on a dense uplink); ``return_transport_state``
    appends the full :class:`~repro.core.transport.TransportState`
    (both wires' residuals) for a bit-exact resume.
    """
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    comm = transport_core.resolve_comm(
        comm, compression=compression, staleness=staleness,
        aggregation=aggregation, where="worker_rounds")
    if comm.faults is not None:
        raise TypeError(
            "worker_rounds: CommPlan.faults is a schedule -- the faces "
            "materialize it; pass this machine's FaultPlan row via faults=")
    _check_plan(faults, (rounds,), "worker_rounds")
    ws = pipeline.worker_solves(
        head, *data, lam=lam, lam_prime=lam_prime, cfg=cfg,
        model_axis=model_axis, model_axis_size=model_axis_size,
        rho_beta=rho_beta, rho_theta=rho_theta,
        state_beta=state_beta, state_theta=state_theta,
        full=collect_info,
    )
    anchor = ws.beta_hat if resume_from is None else resume_from
    tr = Transport(comm, anchor.shape[0], anchor.shape[1], rounds)
    with jax.named_scope("slda.aggregate"):
        anchor, tstate = _refinement_rounds(
            _MeshRound(ws, model_axis, data_axes),
            rounds=rounds, anchor=anchor, transport=tr, plan=faults,
            state=TransportState(ef_residual, down_residual),
            ref=resume_from)
    out = [anchor, ws]
    if return_ef_residual:
        out.append(tstate.up_residual)
    if return_transport_state:
        out.append(tstate)
    return tuple(out)


def simulate_round_loop(
    ws: WorkerSolves,
    *,
    rounds: int,
    comm: CommPlan | None = None,
    compression: Compression | None = None,
    ef_residual: jnp.ndarray | None = None,
    down_residual: jnp.ndarray | None = None,
    resume_from: jnp.ndarray | None = None,
    faults: FaultPlan | FaultSchedule | None = None,
    staleness: int = 0,
    aggregation: Aggregation | None = None,
    return_all_rounds: bool = False,
    return_ef_residual: bool = False,
    return_transport_state: bool = False,
):
    """The T refinement rounds alone, on already-computed machine solves.

    ``ws`` is an (m, ...)-stacked :class:`WorkerSolves` (the output of
    :func:`simulate_multi_round`'s vmap).  Splitting the loop from the
    solves lets one set of per-machine solves -- the expensive part --
    drive many round schedules: the compressed-uplink and fault
    benchmarks replay the SAME solves under every
    :class:`Compression` / :class:`~repro.core.faults.FaultSchedule`
    config, so the curves differ only in the uplink and its faults.

    Same shared round body as the mesh path
    (:func:`_refinement_rounds`), with machine-axis reductions where
    the mesh does collectives.  ``comm`` is the one static
    :class:`~repro.core.transport.CommPlan` (its ``faults`` -- a
    hashable :class:`~repro.core.faults.FaultSchedule` -- is
    materialized here against ``m``); the legacy ``compression`` /
    ``faults`` / ``staleness`` / ``aggregation`` kwargs remain as
    deprecation shims, with ``faults`` additionally accepting an
    already-materialized :class:`~repro.core.faults.FaultPlan`
    ((m, rounds) leaves).  ``resume_from`` as in :func:`worker_rounds`.

    Returns ``beta_bar`` (d, K), or the (rounds, d, K) trajectory when
    ``return_all_rounds``; ``return_ef_residual`` appends the final
    (m, d, K) uplink residual (None on a dense uplink) and
    ``return_transport_state`` the full
    :class:`~repro.core.transport.TransportState` for a bit-exact
    resume of both wires.
    """
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    drv = _SimRound(ws)
    if comm is not None and isinstance(faults, FaultSchedule):
        raise TypeError(
            "simulate_round_loop: pass the fault schedule inside "
            "comm=CommPlan(faults=...), not alongside it (a materialized "
            "FaultPlan is data and may ride next to comm)")
    comm = transport_core.resolve_comm(
        comm, compression=compression, staleness=staleness,
        aggregation=aggregation, where="simulate_round_loop")
    plan = faults if faults is not None else comm.faults
    if isinstance(plan, FaultSchedule):
        plan = plan.plan(drv.m, rounds, max(comm.staleness, 1))
    _check_plan(plan, (drv.m, rounds), "simulate_round_loop")
    anchor = (ws.beta_hat if resume_from is None
              else drv.broadcast(resume_from))
    tr = Transport(comm, anchor.shape[1], anchor.shape[2], rounds)
    with jax.named_scope("slda.aggregate"):
        out, tstate = _refinement_rounds(
            drv, rounds=rounds, anchor=anchor, transport=tr, plan=plan,
            state=TransportState(ef_residual, down_residual),
            ref=resume_from, return_all_rounds=return_all_rounds)
    res = [out]
    if return_ef_residual:
        res.append(tstate.up_residual)
    if return_transport_state:
        res.append(tstate)
    return tuple(res) if len(res) > 1 else out


def simulate_multi_round(
    head: DiscriminantHead,
    data: Sequence[jnp.ndarray],
    *,
    lam,
    lam_prime,
    rounds: int = 1,
    cfg: DantzigConfig = DantzigConfig(),
    comm: CommPlan | None = None,
    compression: Compression | None = None,
    ef_residual: jnp.ndarray | None = None,
    faults: FaultPlan | FaultSchedule | None = None,
    staleness: int = 0,
    aggregation: Aggregation | None = None,
    rho_beta: jnp.ndarray | None = None,
    rho_theta: jnp.ndarray | None = None,
    state_beta: AdmmState | None = None,
    state_theta: AdmmState | None = None,
    collect_info: bool = False,
    return_all_rounds: bool = False,
) -> tuple[jnp.ndarray, WorkerSolves]:
    """Single-device twin of :func:`worker_rounds`: machines are vmapped.

    ``data`` holds the head's samples stacked over a leading machine
    axis (``(xs, ys)`` with (m, n, d) leaves for the binary head).
    Identical math to the mesh path: per-machine solves under ``vmap``,
    then the round loop of :func:`simulate_round_loop` -- a machine-axis
    ``mean`` per dense round, or the top-k error-feedback payload mean
    when ``compression`` is set, under the same ``faults`` /
    ``staleness`` / ``aggregation`` fault model as the mesh.  Warm
    carries are the (m, ...)-stacked fields of a previous invocation's
    returned :class:`WorkerSolves`.

    Returns ``(beta_bar, solves)`` with ``beta_bar`` (d, K), or
    (rounds, d, K) -- the whole per-round trajectory -- when
    ``return_all_rounds`` (the error-vs-T benchmark reads every T from
    ONE set of solves).
    """
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    # None carries are empty pytrees: vmap maps only the provided ones
    warms = dict(rho_beta=rho_beta, rho_theta=rho_theta,
                 state_beta=state_beta, state_theta=state_theta)

    def one_machine(args, warm):
        return pipeline.worker_solves(
            head, *args, lam=lam, lam_prime=lam_prime, cfg=cfg,
            full=collect_info, **warm)

    ws = jax.vmap(one_machine)(tuple(data), warms)
    out = simulate_round_loop(
        ws, rounds=rounds, comm=comm, compression=compression,
        ef_residual=ef_residual, faults=faults, staleness=staleness,
        aggregation=aggregation, return_all_rounds=return_all_rounds)
    return out, ws
