"""Algorithm 1 on a JAX device mesh (the paper's distributed schedule).

Mapping (see DESIGN.md §2):

  * the paper's ``m`` machines  <->  the ``("pod", "data")`` mesh axes;
    each data-slice holds an i.i.d. shard of the N samples and runs the
    *entire* worker pipeline locally (suff stats -> beta_hat -> CLIME
    -> debias) with zero communication;
  * the paper's intra-machine CLIME column parallelism  <->  the
    ``"model"`` axis: each model-device solves ceil(d/|model|) Dantzig
    columns (d is padded to a multiple of the axis; pad columns are
    masked out of the gather, so any (d, |model|) pair is exact) and
    produces its slice of the debias correction, then one
    ``all_gather`` over "model" reassembles beta_tilde (this gather is
    *inside* a machine in the paper's cost model);
  * the paper's one-round worker->master send + average  <->  a single
    ``pmean`` of a (d, K) block over ("pod", "data") -- O(dK) bytes per
    link (K=1 for the paper's binary problem), exactly the paper's
    communication budget;
  * the master's hard threshold runs replicated (it is dK cheap ops).

The suff-stats/beta_hat computation is intentionally *replicated*
across the "model" axis instead of sharded: replicating O(n d + d^2)
FLOPs is cheaper than broadcasting Sigma_hat (d^2 bytes) across the
axis, and it keeps the one-round communication claim exact.

The worker schedule itself lives ONCE in :mod:`repro.core.pipeline`;
every entry point here is a head- or mesh-specific wrapper:
``distributed_slda_shardmap`` (binary, K=1) and
``distributed_mc_slda_shardmap`` (K-class, Chen's multicategory
one-shot schedule: each machine uplinks one (d, K) block) share the
same core, as do the single-device simulations below.  That includes
the single-factorization invariant: inside every shard function the
pipeline computes ONE :class:`~repro.kernels.spectral.SpectralFactor`
of the device's replicated Sigma_hat and threads it through both the
direction solve and the CLIME column block -- the mesh paths pay one
eigendecomposition per model-device per round, not two.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis import (
    AxisPayloadBits,
    CollectiveContract,
    DtypePolicy,
    Param,
    PrimitiveBudget,
    VmemConformance,
    trace_contract,
)
from repro.core import rounds as rounds_core, slda
from repro.core import transport as transport_core
from repro.core.compression import Compression
from repro.core.dantzig import DantzigConfig
from repro.core.faults import Aggregation, FaultPlan, FaultSchedule
from repro.core.pipeline import BinaryHead, MulticlassHead
from repro.core.transport import CommPlan


def _materialize_plan(faults, mesh, data_axes, rounds, staleness):
    """Resolve ``faults`` to a full (m, rounds) :class:`FaultPlan`.

    The mesh faces accept either a :class:`FaultSchedule` (materialized
    here against the mesh's machine count) or an already-built plan;
    the per-machine rows then ride into shard_map as ONE extra sharded
    operand per plan leaf (the "liveness operand" of DESIGN.md §11) so
    each machine sees only its own (rounds,) row.
    """
    if faults is None:
        return None
    m = 1
    for ax in data_axes:
        m *= mesh.shape[ax]
    if isinstance(faults, FaultSchedule):
        faults = faults.plan(m, rounds, max(staleness, 1))
    if faults.live.shape != (m, rounds):
        raise ValueError(
            f"FaultPlan leaves must be ({m}, {rounds}) for this mesh, "
            f"got {faults.live.shape}")
    return faults


@trace_contract(
    "distributed.slda_shardmap",
    contracts=(
        PrimitiveBudget("eigh", exact=1),
        # Algorithm 1's dense uplink: one (d, 1) psum per dense round --
        # nothing else crosses the data axis (0 psums when compressed)
        CollectiveContract("psum", count=Param("dense_psums"), axis="data",
                           shape=Param("psum_payload"), dtype="float32"),
        # the DESIGN §11 liveness mask: one scalar f32 psum per masked
        # dense round (0 on the legacy path), and nothing else -- the
        # total psum budget closes the loophole
        CollectiveContract("psum", count=Param("live_psums"), axis="data",
                           shape=(), dtype="float32"),
        PrimitiveBudget("psum", exact=Param("total_psums")),
        CollectiveContract("all_gather", count=Param("rounds"),
                           axis="model"),
        # compressed uplink: the payload gathers, and the exact bits
        # per direction -- uplink payloads on all_gathers, dense psums
        # + liveness masks + downlink payloads on psums (DESIGN.md §13)
        CollectiveContract("all_gather", count=Param("data_gathers"),
                           axis="data"),
        AxisPayloadBits("data", exact_bits=Param("data_gather_bits"),
                        prims=("all_gather",)),
        AxisPayloadBits("data", exact_bits=Param("data_psum_bits"),
                        prims=("psum",)),
        AxisPayloadBits("data", exact_bits=Param("data_total_bits")),
        PrimitiveBudget("is_finite", exact=Param("screen_ops")),
        PrimitiveBudget("pallas_call", exact=Param("pallas_calls")),
        DtypePolicy(),
        VmemConformance(),
    ),
)
def distributed_slda_shardmap(
    mesh: jax.sharding.Mesh,
    x: jnp.ndarray,
    y: jnp.ndarray,
    lam: float,
    lam_prime: float,
    t: float,
    cfg: DantzigConfig = DantzigConfig(),
    data_axes: Sequence[str] = ("data",),
    model_axis: str | None = "model",
    rounds: int = 1,
    comm: CommPlan | None = None,
    compression: Compression | None = None,
    faults: FaultPlan | FaultSchedule | None = None,
    staleness: int = 0,
    aggregation: Aggregation | None = None,
) -> jnp.ndarray:
    """Distributed sparse LDA over a mesh (one-shot, or T-round refined).

    Args:
      x: (N1, d) class-1 samples, shardable over the data axes.
      y: (N2, d) class-2 samples.
      rounds: communication rounds.  1 (default) is the paper's
        one-shot schedule; T > 1 runs T-1 extra refinement rounds
        around the aggregate (DESIGN.md §8) -- each an O(d) ``pmean``
        reusing the round-one solves, no extra eigendecompositions --
        recovering the centralized rate past the one-shot m-barrier.
      comm: the ONE static comms config
        (:class:`~repro.core.transport.CommPlan`, DESIGN.md §13):
        uplink/downlink codecs or a
        :class:`~repro.core.transport.BitBudget` schedule, the fault
        schedule, the staleness bound, and the aggregation policy.
        The default plan moves each round's dense (d, 1) float32
        block, bit-exact vs the legacy path.
      compression / faults / staleness / aggregation: DEPRECATED shims
        for the corresponding :class:`CommPlan` fields (mutually
        exclusive with ``comm``; ``faults`` additionally accepts an
        (m, rounds) :class:`~repro.core.faults.FaultPlan`).  A fault
        schedule is materialized against this mesh's machine count and
        each machine's row rides in as a sharded liveness operand
        (DESIGN.md §11).
    Returns:
      beta_bar: (d,) aggregated sparse discriminant vector (replicated).
    """
    data_axes = tuple(data_axes)
    in_spec = P(data_axes, None)
    model_size = mesh.shape[model_axis] if model_axis is not None else 1
    if comm is not None and faults is not None:
        raise TypeError("distributed_slda_shardmap: pass the fault schedule "
                        "inside comm=CommPlan(faults=...), not alongside it")
    comm = transport_core.resolve_comm(
        comm, compression=compression, staleness=staleness,
        aggregation=aggregation, where="distributed_slda_shardmap")
    plan = _materialize_plan(faults if faults is not None else comm.faults,
                             mesh, data_axes, rounds, comm.staleness)
    worker_comm = comm._replace(faults=None)  # the row is the operand
    plan_args = tuple(plan) if plan is not None else ()
    plan_specs = tuple(P(data_axes, None) for _ in plan_args)

    def shard_fn(xs, ys, *plan_leaves):
        row = (FaultPlan(*(leaf[0] for leaf in plan_leaves))
               if plan_leaves else None)
        # ---- the T communication rounds of Algorithm 1 / DESIGN §8 ----
        beta_bar, _ = rounds_core.worker_rounds(
            BinaryHead(), xs, ys, lam=lam, lam_prime=lam_prime,
            rounds=rounds, cfg=cfg, data_axes=data_axes,
            model_axis=model_axis, model_axis_size=model_size,
            comm=worker_comm, faults=row,
        )
        with jax.named_scope("slda.aggregate"):
            return slda.hard_threshold(beta_bar[:, 0], t)

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(in_spec, in_spec) + plan_specs,
                       out_specs=P(), check_vma=False)
    return fn(x, y, *plan_args)


@trace_contract(
    "distributed.mc_slda_shardmap",
    contracts=(
        PrimitiveBudget("eigh", exact=1),
        # one (d, K) direction psum per DENSE round over the data axis
        # (0 when compressed) ...
        CollectiveContract("psum", count=Param("dense_psums"), axis="data",
                           shape=Param("direction_payload"),
                           dtype="float32"),
        # ... plus exactly one (K, d) class-means psum, and nothing else
        CollectiveContract("psum", count=1, axis="data",
                           shape=Param("means_payload"), dtype="float32"),
        # the liveness-mask scalar psum of masked rounds (DESIGN §11)
        CollectiveContract("psum", count=Param("live_psums"), axis="data",
                           shape=(), dtype="float32"),
        PrimitiveBudget("psum", exact=Param("total_psums")),
        CollectiveContract("all_gather", count=Param("rounds"),
                           axis="model"),
        # compressed uplink: the payload gathers, and the exact bits
        # everything moves over the data axis, split by direction
        # (the one-time means psum counts on the psum side)
        CollectiveContract("all_gather", count=Param("data_gathers"),
                           axis="data"),
        AxisPayloadBits("data", exact_bits=Param("data_gather_bits"),
                        prims=("all_gather",)),
        AxisPayloadBits("data", exact_bits=Param("data_psum_bits"),
                        prims=("psum",)),
        AxisPayloadBits("data", exact_bits=Param("data_total_bits")),
        PrimitiveBudget("is_finite", exact=Param("screen_ops")),
        PrimitiveBudget("pallas_call", exact=Param("pallas_calls")),
        DtypePolicy(),
        VmemConformance(),
    ),
)
def distributed_mc_slda_shardmap(
    mesh: jax.sharding.Mesh,
    x: jnp.ndarray,
    labels: jnp.ndarray,
    num_classes: int,
    lam: float,
    lam_prime: float,
    t: float,
    cfg: DantzigConfig = DantzigConfig(),
    data_axes: Sequence[str] = ("data",),
    model_axis: str | None = "model",
    rounds: int = 1,
    comm: CommPlan | None = None,
    compression: Compression | None = None,
    faults: FaultPlan | FaultSchedule | None = None,
    staleness: int = 0,
    aggregation: Aggregation | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Distributed K-class sparse LDA over a mesh (one-shot or T-round).

    The multiclass analogue of :func:`distributed_slda_shardmap`: each
    data-slice is one machine, the d CLIME columns shard over the model
    axis, and each communication round is one ``pmean`` of a (d, K)
    direction block -- O(dK) bytes per link, the multicategory budget.
    The (K, d) class means ride one extra ``pmean`` once (they are
    round-independent), and ``rounds`` > 1 refines the direction block
    around the aggregate exactly as in the binary driver (DESIGN.md §8).
    ``comm`` is the one static :class:`~repro.core.transport.CommPlan`
    (DESIGN.md §13) -- per-direction codecs / schedule / faults /
    staleness / aggregation exactly as in the binary driver; the
    legacy kwargs remain as deprecation shims.  The one-time means
    pmean stays dense and is NOT fault-masked; it rides the round-1
    uplink in the paper's cost model.

    Args:
      x: (N, d) samples, shardable over the data axes.
      labels: (N,) int labels in [0, num_classes).
    Returns:
      (beta_bar (d, K), means (K, d)), both replicated.
    """
    data_axes = tuple(data_axes)
    model_size = mesh.shape[model_axis] if model_axis is not None else 1
    if comm is not None and faults is not None:
        raise TypeError("distributed_mc_slda_shardmap: pass the fault "
                        "schedule inside comm=CommPlan(faults=...), not "
                        "alongside it")
    comm = transport_core.resolve_comm(
        comm, compression=compression, staleness=staleness,
        aggregation=aggregation, where="distributed_mc_slda_shardmap")
    plan = _materialize_plan(faults if faults is not None else comm.faults,
                             mesh, data_axes, rounds, comm.staleness)
    worker_comm = comm._replace(faults=None)  # the row is the operand
    plan_args = tuple(plan) if plan is not None else ()
    plan_specs = tuple(P(data_axes, None) for _ in plan_args)

    def shard_fn(xs, labs, *plan_leaves):
        row = (FaultPlan(*(leaf[0] for leaf in plan_leaves))
               if plan_leaves else None)
        beta_bar, ws = rounds_core.worker_rounds(
            MulticlassHead(num_classes), xs, labs,
            lam=lam, lam_prime=lam_prime, rounds=rounds, cfg=cfg,
            data_axes=data_axes,
            model_axis=model_axis, model_axis_size=model_size,
            comm=worker_comm, faults=row,
        )
        means = ws.stats.aux.means
        with jax.named_scope("slda.aggregate"):
            for ax in data_axes:
                means = jax.lax.pmean(means, ax)
            return slda.hard_threshold(beta_bar, t), means

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(data_axes, None), P(data_axes)) + plan_specs,
        out_specs=(P(), P()), check_vma=False)
    return fn(x, labels, *plan_args)


def naive_averaged_slda_shardmap(
    mesh: jax.sharding.Mesh,
    x: jnp.ndarray,
    y: jnp.ndarray,
    lam: float,
    cfg: DantzigConfig = DantzigConfig(),
    data_axes: Sequence[str] = ("data",),
) -> jnp.ndarray:
    """Baseline: average the *biased* local estimators (no debias, no HT)."""
    data_axes = tuple(data_axes)

    def shard_fn(xs, ys):
        stats = slda.suff_stats(xs, ys)
        beta_hat = slda.local_slda(stats, lam, cfg)
        for ax in data_axes:
            beta_hat = jax.lax.pmean(beta_hat, ax)
        return beta_hat

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(data_axes, None), P(data_axes, None)),
                       out_specs=P(), check_vma=False)
    return fn(x, y)


# ---------------------------------------------------------------------------
# Single-device simulation (statistical experiments / tests).  Identical
# math; machines are a leading vmap axis instead of mesh shards.  The
# per-machine body is the SAME pipeline.worker_solves schedule the mesh
# runs, driven through the same rounds core (pipeline.worker_debiased's
# one-shot correction is its rounds=1 case).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "rounds", "comm",
                                             "compression", "faults",
                                             "staleness", "aggregation"))
def simulated_debiased_mean(
    xs: jnp.ndarray,
    ys: jnp.ndarray,
    lam: float,
    lam_prime: float,
    cfg: DantzigConfig = DantzigConfig(),
    rounds: int = 1,
    compression: Compression | None = None,
    faults: FaultSchedule | None = None,
    staleness: int = 0,
    aggregation: Aggregation | None = None,
    comm: CommPlan | None = None,
) -> jnp.ndarray:
    """Mean of debiased locals WITHOUT the hard threshold.

    Benchmarks tune the threshold t post hoc over a grid (the paper
    reports grid-tuned best results); exposing the raw mean makes that
    tuning free (HT is O(d)).  ``rounds`` > 1 applies the extra
    refinement rounds around the aggregate (DESIGN.md §8).  ``comm``
    (a hashable :class:`~repro.core.transport.CommPlan` -- static, so
    changing the plan recompiles) carries the whole comms config:
    codecs/schedule (DESIGN.md §10/§13), fault schedule (materialized
    inside the jit), staleness, aggregation (DESIGN.md §11).  The
    legacy ``compression``/``faults``/``staleness``/``aggregation``
    kwargs remain as deprecation shims."""
    beta_bar, _ = rounds_core.simulate_multi_round(
        BinaryHead(), (xs, ys), lam=lam, lam_prime=lam_prime,
        rounds=rounds, cfg=cfg, comm=comm, compression=compression,
        faults=faults, staleness=staleness, aggregation=aggregation)
    return beta_bar[:, 0]


@functools.partial(jax.jit, static_argnames=("cfg", "rounds", "comm",
                                             "compression", "faults",
                                             "staleness", "aggregation"))
def simulated_distributed_slda(
    xs: jnp.ndarray,
    ys: jnp.ndarray,
    lam: float,
    lam_prime: float,
    t: float,
    cfg: DantzigConfig = DantzigConfig(),
    rounds: int = 1,
    compression: Compression | None = None,
    faults: FaultSchedule | None = None,
    staleness: int = 0,
    aggregation: Aggregation | None = None,
    comm: CommPlan | None = None,
) -> jnp.ndarray:
    """xs: (m, n1, d), ys: (m, n2, d) -> aggregated beta_bar (d,)."""
    beta_bar = simulated_debiased_mean(xs, ys, lam, lam_prime, cfg, rounds,
                                       compression, faults, staleness,
                                       aggregation, comm)
    with jax.named_scope("slda.aggregate"):
        return slda.hard_threshold(beta_bar, t)


@functools.partial(jax.jit, static_argnames=("cfg",))
def simulated_naive_averaged_slda(
    xs: jnp.ndarray,
    ys: jnp.ndarray,
    lam: float,
    cfg: DantzigConfig = DantzigConfig(),
) -> jnp.ndarray:
    def one_machine(x, y):
        stats = slda.suff_stats(x, y)
        return slda.local_slda(stats, lam, cfg)

    return jnp.mean(jax.vmap(one_machine)(xs, ys), axis=0)
