"""Representative trace shapes for every contracted entry point.

Each case builds ``(fn, args)`` for :func:`jax.make_jaxpr` plus the
params dict that resolves the entry's :class:`~repro.analysis.contracts.
Param` placeholders.  Tracing never executes the solver, so even the
d=70 remainder sweep is cheap -- but mesh cases DO need the devices
their mesh asks for (``min_devices``); the lint CLI forces an 8-device
host, in-process callers skip what the host cannot mesh.

Importing this module imports the core entry points, which is what
populates the contract registry.
"""

from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compression as compression_core
from repro.core import path as rpath
from repro.core import pipeline, rounds, streaming
from repro.core import transport as transport_core
from repro.core.compression import Compression
from repro.core.dantzig import DantzigConfig
from repro.core.distributed import (
    distributed_mc_slda_shardmap,
    distributed_slda_shardmap,
)
from repro.core.faults import Aggregation, FaultPlan, FaultSchedule
from repro.core.solver_dispatch import solve_dantzig_full
from repro.kernels.spectral import spectral_factor


class Case(NamedTuple):
    entry: str
    name: str
    params: dict
    build: Callable[[], Tuple[Callable, tuple]]
    min_devices: int = 1


_CASES: Dict[str, List[Case]] = {}


def case(entry: str, name: str, params: dict, *, min_devices: int = 1):
    def register(build):
        _CASES.setdefault(entry, []).append(
            Case(entry, name, dict(params), build, min_devices))
        return build
    return register


def cases_for(entry: str) -> List[Case]:
    return list(_CASES.get(entry, []))


def all_cases() -> Dict[str, List[Case]]:
    return {k: list(v) for k, v in _CASES.items()}


def _normal(seed: int, shape) -> jnp.ndarray:
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _spd(d: int, seed: int = 0) -> jnp.ndarray:
    g = _normal(seed, (2 * d, d))
    return g.T @ g / (2 * d) + 0.5 * jnp.eye(d)


SCAN = DantzigConfig(max_iters=40, adapt_rho=False)
FUSED = DantzigConfig(max_iters=40, adapt_rho=False, fused=True)
FUSED_TOL = DantzigConfig(max_iters=40, adapt_rho=False, fused=True,
                          tol=1e-3)


# ---------------------------------------------------------------------------
# pipeline.worker_debiased
# ---------------------------------------------------------------------------

def _worker_debiased_case(cfg):
    def build():
        x, y = _normal(0, (40, 12)), _normal(1, (44, 12))

        def fn(x, y):
            return pipeline.worker_debiased(
                pipeline.BinaryHead(), x, y, lam=0.1, lam_prime=0.1,
                cfg=cfg)
        return fn, (x, y)
    return build


case("pipeline.worker_debiased", "binary-scan-d12",
     {"pallas_calls": 0})(_worker_debiased_case(SCAN))
case("pipeline.worker_debiased", "binary-fused-d12",
     {"pallas_calls": 2})(_worker_debiased_case(FUSED))
case("pipeline.worker_debiased", "binary-fused-tol-d12",
     {"pallas_calls": 2})(_worker_debiased_case(FUSED_TOL))


@case("pipeline.worker_debiased", "multiclass-fused-d10-K3",
      {"pallas_calls": 2})
def _worker_debiased_mc():
    x = _normal(2, (60, 10))
    labels = jax.random.randint(jax.random.PRNGKey(3), (60,), 0, 3)

    def fn(x, labels):
        return pipeline.worker_debiased(
            pipeline.MulticlassHead(3), x, labels, lam=0.1,
            lam_prime=0.1, cfg=FUSED)
    return fn, (x, labels)


# ---------------------------------------------------------------------------
# rounds.worker_rounds (inside a minimal shard_map shell)
# ---------------------------------------------------------------------------

def _comm_params(comm, t_rounds, d, num_cols, extra_bits=0):
    """Collective counts + per-direction exact bits for a fault-free,
    unmasked :class:`~repro.core.transport.CommPlan`.

    Walks the resolved :class:`~repro.core.transport.Transport` round by
    round (a :class:`~repro.core.transport.BitBudget` schedule changes
    codecs per round), applying the DESIGN §10/§13 accounting: a dense
    uplink is one (d, K) f32 psum; a compressed uplink is 2 payload
    all_gathers (3 with int8 scales) + 2 decode-sanitize is_finite; a
    compressed downlink is 2 payload psums (3 with int8 scales) + ONE
    whole-block receiver screen (a dense downlink never touches the
    wire -- the aggregate is already replicated).  ``extra_bits`` covers
    one-off psum payloads like the mc class-means pmean.
    """
    tr = transport_core.Transport(comm, d, num_cols, t_rounds)
    dense_psums = down_psums = data_gathers = screen_ops = 0
    gather_bits, psum_bits = 0, extra_bits
    for t in range(1, t_rounds + 1):
        up, down = tr.up(t), tr.down(t)
        if up.compressed:
            data_gathers += 3 if up.comp.quantize == "int8" else 2
            gather_bits += up.bits(d, num_cols)
            screen_ops += 2
        else:
            dense_psums += 1
            psum_bits += compression_core.dense_uplink_bits(d, num_cols)
        if down.compressed:
            down_psums += 3 if down.comp.quantize == "int8" else 2
            psum_bits += down.bits(d, num_cols)
            screen_ops += 1
    return {
        "rounds": t_rounds,
        "dense_psums": dense_psums,
        "live_psums": 0,
        "total_psums": dense_psums + down_psums,
        "screen_ops": screen_ops,
        "data_gathers": data_gathers,
        "data_gather_bits": gather_bits,
        "data_psum_bits": psum_bits,
        "data_total_bits": gather_bits + psum_bits,
    }


def _round_params(t_rounds, d, num_cols, comp=None, extra_bits=0,
                  down=None):
    """Fixed-codec shorthand over :func:`_comm_params`."""
    return _comm_params(
        transport_core.CommPlan(uplink=comp, downlink=down),
        t_rounds, d, num_cols, extra_bits=extra_bits)


def _masked_round_params(t_rounds, d, num_cols, comp=None, *,
                         faulted=False, trim=False, extra_bits=0,
                         down=None):
    """The DESIGN §11 masked-aggregation counterparts.

    Masked dense rounds close with a (d, K) psum + the scalar liveness
    psum (trimmed mode gathers per-machine blocks + weights instead);
    masked compressed rounds gather the payload as before plus, when a
    fault plan rides along, the per-machine liveness scalar.  Screening
    is one is_finite per round on the dense wire, or (compressed) one
    on the ef_step decode + one on the raw decoded stack.  The downlink
    close is orthogonal to the masking and keeps its
    :func:`_comm_params` accounting."""
    base = _round_params(t_rounds, d, num_cols, comp,
                         extra_bits=extra_bits, down=down)
    scalar_bits = 32  # one f32 liveness scalar per round on the wire
    dl_psums = (0 if down is None
                else t_rounds * (3 if down.quantize == "int8" else 2))
    dl_bits = (0 if down is None
               else t_rounds * compression_core.uplink_bits(
                   down, d, num_cols))
    dl_screens = 0 if down is None else t_rounds
    if comp is None:
        dense_bits = t_rounds * compression_core.dense_uplink_bits(
            d, num_cols)
        if trim:
            # all_gather of the (d, K) block + the weight scalar; the
            # trimmed reduction itself is replicated local math
            base.update({
                "dense_psums": 0, "live_psums": 0,
                "total_psums": dl_psums,
                "data_gathers": 2 * t_rounds,
                "screen_ops": t_rounds + dl_screens,
                "data_gather_bits": dense_bits + t_rounds * scalar_bits,
                "data_psum_bits": extra_bits + dl_bits,
            })
        else:
            base.update({
                "live_psums": t_rounds,
                "total_psums": base["total_psums"] + t_rounds,
                "screen_ops": t_rounds + dl_screens,
                "data_psum_bits":
                    base["data_psum_bits"] + t_rounds * scalar_bits,
            })
    else:
        extra_gathers = t_rounds if faulted else 0
        base.update({
            "data_gathers": base["data_gathers"] + extra_gathers,
            "data_gather_bits":
                base["data_gather_bits"] + extra_gathers * scalar_bits,
        })
    base["data_total_bits"] = (base["data_gather_bits"]
                               + base["data_psum_bits"])
    return base


def _worker_rounds_case(cfg, t_rounds, comp=None, agg=None, faults=False,
                        staleness=0, comm=None):
    def build():
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        x, y = _normal(4, (30, 12)), _normal(5, (30, 12))
        plan = (FaultSchedule(dropout=0.3, seed=0).plan(
            1, t_rounds, max(staleness, 1)) if faults else None)
        plan_args = tuple(plan) if plan is not None else ()
        plan_specs = tuple(P("data", None) for _ in plan_args)

        def shard_fn(xs, ys, *plan_leaves):
            row = (FaultPlan(*(leaf[0] for leaf in plan_leaves))
                   if plan_leaves else None)
            beta, _ = rounds.worker_rounds(
                pipeline.BinaryHead(), xs, ys, lam=0.2, lam_prime=0.2,
                rounds=t_rounds, cfg=cfg, model_axis="model",
                model_axis_size=1, comm=comm, compression=comp,
                faults=row, staleness=staleness, aggregation=agg)
            return beta

        spec = P("data", None)
        fn = jax.shard_map(shard_fn, mesh=mesh,
                           in_specs=(spec, spec) + plan_specs, out_specs=P(),
                           check_vma=False)
        return fn, (x, y) + plan_args
    return build


case("rounds.worker_rounds", "rounds3-mesh1x1-d12",
     {**_round_params(3, 12, 1), "psum_payload": (12, 1),
      "pallas_calls": 0})(_worker_rounds_case(SCAN, 3))
case("rounds.worker_rounds", "rounds3-mesh1x1-d12-top5",
     {**_round_params(3, 12, 1, Compression(5)), "psum_payload": (12, 1),
      "pallas_calls": 0})(_worker_rounds_case(SCAN, 3, Compression(5)))
case("rounds.worker_rounds", "rounds2-mesh1x1-d12-top4-int8",
     {**_round_params(2, 12, 1, Compression(4, "int8")),
      "psum_payload": (12, 1), "pallas_calls": 0})(
    _worker_rounds_case(SCAN, 2, Compression(4, "int8")))
# DESIGN §11 masked aggregation: the liveness scalar psum + one
# screening is_finite per round join the budget
case("rounds.worker_rounds", "rounds3-mesh1x1-d12-masked",
     {**_masked_round_params(3, 12, 1), "psum_payload": (12, 1),
      "pallas_calls": 0})(
    _worker_rounds_case(SCAN, 3, agg=Aggregation()))
case("rounds.worker_rounds", "rounds2-mesh1x1-d12-masked-faulted-stale",
     {**_masked_round_params(2, 12, 1), "psum_payload": (12, 1),
      "pallas_calls": 0})(
    _worker_rounds_case(SCAN, 2, agg=Aggregation(), faults=True,
                        staleness=1))
# trimmed mode trades the psums for per-machine block + weight gathers
case("rounds.worker_rounds", "rounds2-mesh1x1-d12-trimmed",
     {**_masked_round_params(2, 12, 1, trim=True),
      "psum_payload": (12, 1), "pallas_calls": 0})(
    _worker_rounds_case(SCAN, 2, agg=Aggregation(trim=0.1)))
# masked compressed + faults: payload gathers + the liveness gather
case("rounds.worker_rounds", "rounds2-mesh1x1-d12-top4-int8-masked-faulted",
     {**_masked_round_params(2, 12, 1, Compression(4, "int8"),
                             faulted=True),
      "psum_payload": (12, 1), "pallas_calls": 0})(
    _worker_rounds_case(SCAN, 2, Compression(4, "int8"),
                        agg=Aggregation(envelope=1e6), faults=True))
# DESIGN §13 two-way transport: the compressed downlink rides the
# master-masked psum broadcast (values + indices, + scales when int8)
# and adds ONE whole-block receiver screen per round
case("rounds.worker_rounds", "rounds2-mesh1x1-d12-top5-down4-int8",
     {**_round_params(2, 12, 1, Compression(5),
                      down=Compression(4, "int8")),
      "psum_payload": (12, 1), "pallas_calls": 0})(
    _worker_rounds_case(SCAN, 2, comm=transport_core.CommPlan(
        uplink=Compression(5), downlink=Compression(4, "int8"))))


# ---------------------------------------------------------------------------
# distributed faces
# ---------------------------------------------------------------------------

def _slda_face_case(cfg, t_rounds, d, mesh_shape, n_per=30, comp=None,
                    faults=None, staleness=0, agg=None, comm=None):
    def build():
        mesh = jax.make_mesh(mesh_shape, ("data", "model"))
        n = n_per * mesh_shape[0]
        x, y = _normal(6, (n, d)), _normal(7, (n, d))

        def fn(x, y):
            return distributed_slda_shardmap(
                mesh, x, y, 0.2, 0.2, 0.05, cfg, rounds=t_rounds,
                comm=comm, compression=comp, faults=faults,
                staleness=staleness, aggregation=agg)
        return fn, (x, y)
    return build


for _t in (1, 3):
    case("distributed.slda_shardmap", f"scan-rounds{_t}-mesh1x1-d12",
         {**_round_params(_t, 12, 1), "psum_payload": (12, 1),
          "pallas_calls": 0})(
        _slda_face_case(SCAN, _t, 12, (1, 1)))
case("distributed.slda_shardmap", "fused-rounds2-mesh1x1-d12",
     {**_round_params(2, 12, 1), "psum_payload": (12, 1),
      "pallas_calls": 2})(
    _slda_face_case(FUSED, 2, 12, (1, 1)))
# the PR-1 regression shape: d % model_axis != 0 (70 over 4 -> pad 72)
case("distributed.slda_shardmap", "fused-rounds3-mesh2x4-d70-remainder",
     {**_round_params(3, 70, 1), "psum_payload": (70, 1),
      "pallas_calls": 2},
     min_devices=8)(
    _slda_face_case(FUSED, 3, 70, (2, 4)))
# compressed uplinks: the jaxpr moves the (k_top, 1) payload, no dense
# psum, and exactly the declared bits -- one f32 and one int8 config,
# plus the 8-device remainder shape under compression
case("distributed.slda_shardmap", "scan-rounds3-mesh1x1-d12-top5",
     {**_round_params(3, 12, 1, Compression(5)), "psum_payload": (12, 1),
      "pallas_calls": 0})(
    _slda_face_case(SCAN, 3, 12, (1, 1), comp=Compression(5)))
case("distributed.slda_shardmap", "scan-rounds2-mesh1x1-d12-top4-int8",
     {**_round_params(2, 12, 1, Compression(4, "int8")),
      "psum_payload": (12, 1), "pallas_calls": 0})(
    _slda_face_case(SCAN, 2, 12, (1, 1), comp=Compression(4, "int8")))
case("distributed.slda_shardmap",
     "fused-rounds3-mesh2x4-d70-remainder-top16-bf16",
     {**_round_params(3, 70, 1, Compression(16, "bf16")),
      "psum_payload": (70, 1), "pallas_calls": 2},
     min_devices=8)(
    _slda_face_case(FUSED, 3, 70, (2, 4), comp=Compression(16, "bf16")))
# the fault-tolerant face (DESIGN §11): masked aggregation with a
# sharded FaultPlan liveness operand, dense and on the 8-device mesh
case("distributed.slda_shardmap", "scan-rounds3-mesh1x1-d12-masked-faulted",
     {**_masked_round_params(3, 12, 1), "psum_payload": (12, 1),
      "pallas_calls": 0})(
    _slda_face_case(SCAN, 3, 12, (1, 1),
                    faults=FaultSchedule(dropout=0.2, seed=1),
                    staleness=1, agg=Aggregation()))
case("distributed.slda_shardmap", "scan-rounds2-mesh1x1-d12-trimmed",
     {**_masked_round_params(2, 12, 1, trim=True),
      "psum_payload": (12, 1), "pallas_calls": 0})(
    _slda_face_case(SCAN, 2, 12, (1, 1),
                    faults=FaultSchedule(corrupt=0.2, seed=2),
                    agg=Aggregation(trim=0.25)))
# DESIGN §13: compressed downlinks -- dense uplink + compressed
# downlink, both directions compressed, and on the 8-device remainder
# mesh (k < d keeps the (k, 1) downlink psum distinct from the dense
# (d, 1) psum the dense_psums contract counts)
case("distributed.slda_shardmap", "scan-rounds3-mesh1x1-d12-down6",
     {**_round_params(3, 12, 1, down=Compression(6)),
      "psum_payload": (12, 1), "pallas_calls": 0})(
    _slda_face_case(SCAN, 3, 12, (1, 1),
                    comm=transport_core.CommPlan(downlink=Compression(6))))
case("distributed.slda_shardmap", "scan-rounds2-mesh1x1-d12-top5-down4-int8",
     {**_round_params(2, 12, 1, Compression(5),
                      down=Compression(4, "int8")),
      "psum_payload": (12, 1), "pallas_calls": 0})(
    _slda_face_case(SCAN, 2, 12, (1, 1), comm=transport_core.CommPlan(
        uplink=Compression(5), downlink=Compression(4, "int8"))))
case("distributed.slda_shardmap",
     "fused-rounds3-mesh2x4-d70-top16-bf16-down8-int8",
     {**_round_params(3, 70, 1, Compression(16, "bf16"),
                      down=Compression(8, "int8")),
      "psum_payload": (70, 1), "pallas_calls": 2},
     min_devices=8)(
    _slda_face_case(FUSED, 3, 70, (2, 4), comm=transport_core.CommPlan(
        uplink=Compression(16, "bf16"), downlink=Compression(8, "int8"))))
# DESIGN §13 bit-budget schedules: the BitBudget planner re-plans both
# directions per round at trace time; the pinned bits are the REALIZED
# schedule totals (what plan_rounds fit under the budget).  Budgets are
# sized so every planned k_top < d: a k=d downlink would put a (d, 1)
# psum on the wire, which the dense_psums contract's shape filter
# counts (it filters by payload shape before checking dtype)
_TAPER = transport_core.BitBudget(total_bits=1100, mode="taper",
                                  taper=0.5, quantize="int8")
case("distributed.slda_shardmap", "scan-rounds3-mesh1x1-d12-taper1100",
     {**_comm_params(transport_core.CommPlan(schedule=_TAPER), 3, 12, 1),
      "psum_payload": (12, 1), "pallas_calls": 0})(
    _slda_face_case(SCAN, 3, 12, (1, 1),
                    comm=transport_core.CommPlan(schedule=_TAPER)))
_CONST = transport_core.BitBudget(total_bits=1500, mode="constant",
                                  quantize=None, down_fraction=0.25)
case("distributed.slda_shardmap", "scan-rounds2-mesh1x1-d12-const1500",
     {**_comm_params(transport_core.CommPlan(schedule=_CONST), 2, 12, 1),
      "psum_payload": (12, 1), "pallas_calls": 0})(
    _slda_face_case(SCAN, 2, 12, (1, 1),
                    comm=transport_core.CommPlan(schedule=_CONST)))
case("distributed.slda_shardmap", "fused-rounds3-mesh2x4-d70-masked-faulted",
     {**_masked_round_params(3, 70, 1), "psum_payload": (70, 1),
      "pallas_calls": 2},
     min_devices=8)(
    _slda_face_case(FUSED, 3, 70, (2, 4),
                    faults=FaultSchedule(dropout=0.3, straggle=0.2,
                                         corrupt=0.1, corrupt_mode="mix",
                                         seed=3),
                    staleness=2, agg=Aggregation(envelope=1e6)))


def _mc_face_case(cfg, t_rounds, d=10, num_classes=3, comp=None,
                  faults=None, staleness=0, agg=None):
    def build():
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        x = _normal(8, (60, d))
        labels = jax.random.randint(jax.random.PRNGKey(9), (60,), 0,
                                    num_classes)

        def fn(x, labels):
            return distributed_mc_slda_shardmap(
                mesh, x, labels, num_classes, 0.2, 0.2, 0.05, cfg,
                rounds=t_rounds, compression=comp, faults=faults,
                staleness=staleness, aggregation=agg)
        return fn, (x, labels)
    return build


def _mc_params(t_rounds, d=10, num_classes=3, comp=None, masked=False,
               faulted=False):
    # the (K, d) class means ride one dense f32 pmean regardless of the
    # direction compression (and outside the fault mask)
    means_bits = num_classes * d * 32
    maker = (_masked_round_params if masked else _round_params)
    kw = {"faulted": faulted} if masked else {}
    p = maker(t_rounds, d, num_classes, comp, extra_bits=means_bits, **kw)
    return {**p, "total_psums": p["total_psums"] + 1,
            "direction_payload": (d, num_classes),
            "means_payload": (num_classes, d), "pallas_calls": 0}


for _t in (1, 3):
    case("distributed.mc_slda_shardmap", f"scan-rounds{_t}-mesh1x1-d10-K3",
         _mc_params(_t))(_mc_face_case(SCAN, _t))
case("distributed.mc_slda_shardmap", "scan-rounds2-mesh1x1-d10-K3-top3",
     _mc_params(2, comp=Compression(3)))(
    _mc_face_case(SCAN, 2, comp=Compression(3)))
case("distributed.mc_slda_shardmap",
     "scan-rounds2-mesh1x1-d10-K3-masked-faulted",
     _mc_params(2, masked=True, faulted=True))(
    _mc_face_case(SCAN, 2, faults=FaultSchedule(dropout=0.2, seed=4),
                  staleness=1, agg=Aggregation()))


# ---------------------------------------------------------------------------
# path.solve_dantzig_path / path.worker_debiased_path
# ---------------------------------------------------------------------------

@case("path.solve_dantzig_path", "fused-factor-fed-d16-k3-L4",
      {"eighs": 0, "pallas_calls": 1})
def _path_factor_fed():
    a = _spd(16, seed=10)
    factor = spectral_factor(a)
    b = _normal(11, (16, 3))
    lams = jnp.linspace(0.05, 0.4, 4)

    def fn(factor, b):
        return rpath.solve_dantzig_path(factor, b, lams, FUSED)
    return fn, (factor, b)


@case("path.solve_dantzig_path", "scan-raw-d16-k2-L4",
      {"eighs": 1, "pallas_calls": 0})
def _path_raw_scan():
    a = _spd(16, seed=12)
    b = _normal(13, (16, 2))
    lams = jnp.linspace(0.05, 0.4, 4)

    def fn(a, b):
        return rpath.solve_dantzig_path(a, b, lams, SCAN)
    return fn, (a, b)


@case("path.solve_dantzig_path", "fused-tol-raw-d16-k2-L4",
      {"eighs": 1, "pallas_calls": 1})
def _path_raw_fused_tol():
    a = _spd(16, seed=14)
    b = _normal(15, (16, 2))
    lams = jnp.linspace(0.05, 0.4, 4)

    def fn(a, b):
        return rpath.solve_dantzig_path(a, b, lams, FUSED_TOL)
    return fn, (a, b)


def _worker_path_case(cfg):
    def build():
        x, y = _normal(16, (40, 12)), _normal(17, (44, 12))
        lams = jnp.linspace(0.05, 0.4, 6)

        def fn(x, y):
            return rpath.worker_debiased_path(
                pipeline.BinaryHead(), x, y, lams=lams, lam_prime=0.1,
                cfg=cfg)
        return fn, (x, y)
    return build


case("path.worker_debiased_path", "scan-d12-L6",
     {"pallas_calls": 0})(_worker_path_case(SCAN))
case("path.worker_debiased_path", "fused-tol-d12-L6",
     {"pallas_calls": 2})(_worker_path_case(FUSED_TOL))


# ---------------------------------------------------------------------------
# solver_dispatch.solve_dantzig_full
# ---------------------------------------------------------------------------

@case("solver_dispatch.solve_dantzig_full", "fused-factor-fed-d16-k4",
      {"eighs": 0, "pallas_calls": 1})
def _full_factor_fed():
    a = _spd(16, seed=18)
    factor = spectral_factor(a)
    b = _normal(19, (16, 4))

    def fn(factor, b):
        return solve_dantzig_full(factor, b, 0.1, FUSED)
    return fn, (factor, b)


@case("solver_dispatch.solve_dantzig_full", "scan-raw-d16-k4",
      {"eighs": 1, "pallas_calls": 0})
def _full_raw_scan():
    a = _spd(16, seed=20)
    b = _normal(21, (16, 4))

    def fn(a, b):
        return solve_dantzig_full(a, b, 0.1, SCAN)
    return fn, (a, b)


# ---------------------------------------------------------------------------
# dantzig.solve_dantzig_scan (reached through the dispatch layer)
# ---------------------------------------------------------------------------

def _scan_products_case(cfg):
    def build():
        factor = spectral_factor(_spd(16, seed=26))
        b = _normal(27, (16, 4))

        def fn(factor, b):
            return solve_dantzig_full(factor, b, 0.1, cfg)
        return fn, (factor, b)
    return build


# four products per iteration, plus ceil(max_iters / adapt_every)
# residual products (none with fixed rho)
case("dantzig.solve_dantzig_scan", "default-600-d16-k4",
     {"products": 4 * 600 + 60, "rhs": (16, 4)},
     )(_scan_products_case(DantzigConfig()))
case("dantzig.solve_dantzig_scan", "tail-67-d16-k4",
     {"products": 4 * 67 + 7, "rhs": (16, 4)},
     )(_scan_products_case(DantzigConfig(max_iters=67)))
case("dantzig.solve_dantzig_scan", "adapt-every-1-45-d16-k4",
     {"products": 5 * 45, "rhs": (16, 4)},
     )(_scan_products_case(DantzigConfig(max_iters=45, adapt_every=1)))
case("dantzig.solve_dantzig_scan", "adapt-every-7-50-d16-k4",
     {"products": 4 * 50 + 8, "rhs": (16, 4)},
     )(_scan_products_case(DantzigConfig(max_iters=50, adapt_every=7)))
case("dantzig.solve_dantzig_scan", "fixed-rho-40-d16-k4",
     {"products": 4 * 40, "rhs": (16, 4)})(_scan_products_case(SCAN))


# ---------------------------------------------------------------------------
# streaming.classify_batch / streaming.refit_step (the serving runtime)
# ---------------------------------------------------------------------------

@case("streaming.classify_batch", "B32-d16-K3-priors", {})
def _classify_batch_priors():
    z = _normal(22, (32, 16))
    beta = _normal(23, (16, 3))
    means = _normal(24, (3, 16))
    priors = jnp.full((3,), 1.0 / 3.0)

    def fn(z, beta, means, priors):
        return streaming.classify_batch(z, beta, means, priors)
    return fn, (z, beta, means, priors)


@case("streaming.classify_batch", "B8-d12-K2-equal-priors", {})
def _classify_batch_binary():
    z = _normal(25, (8, 12))
    beta = _normal(26, (12, 2))
    means = _normal(27, (2, 12))

    def fn(z, beta, means):
        return streaming.classify_batch(z, beta, means, None)
    return fn, (z, beta, means)


def _refit_stats(d: int = 12):
    x, y = _normal(28, (40, d)), _normal(29, (44, d))
    return streaming.head_stats_of(pipeline.suff_stats(x, y))


def _refit_case(cfg, warm: bool):
    def build():
        stats = _refit_stats()
        if warm:
            carry = streaming.refit_step(stats, 0.1, 0.1, cfg).carry

            def fn(stats, carry):
                return streaming.refit_step(stats, 0.1, 0.1, cfg,
                                            carry=carry)
            return fn, (stats, carry)

        def fn(stats):
            return streaming.refit_step(stats, 0.1, 0.1, cfg)
        return fn, (stats,)
    return build


case("streaming.refit_step", "cold-scan-d12",
     {"pallas_calls": 0})(_refit_case(SCAN, warm=False))
case("streaming.refit_step", "warm-scan-d12",
     {"pallas_calls": 0})(_refit_case(SCAN, warm=True))
case("streaming.refit_step", "cold-fused-tol-d12",
     {"pallas_calls": 2})(_refit_case(FUSED_TOL, warm=False))


__all__ = ["Case", "all_cases", "case", "cases_for"]
