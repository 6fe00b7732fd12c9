"""Fault-tolerant refinement rounds (DESIGN.md §11).

What is pinned here, and why it is the contract that matters:

* the :class:`FaultSchedule` is DETERMINISTIC and seedable -- a chaos
  run is reproducible bit for bit, so a CI failure is a repro recipe;
* masked aggregation is EXACTLY the mean over the live subset -- not
  an approximation of it -- and with no faults it matches the dense
  round to float tolerance (the legacy unmasked path stays bit-exact
  vs the PR 5 goldens, pinned separately by the golden tests of
  test_compression);
* screening is per machine and total: one NaN/Inf coordinate removes
  that machine's whole contribution; out-of-envelope garbage likewise
  (finite garbage is NOT screened without an envelope -- the envelope
  is the opt-in, the trimmed mean the scale-free alternative);
* graceful degradation: an all-screened round returns the last-good
  aggregate, an all-dead stream returns zeros -- NaN never escapes;
* bounded staleness: a straggler's round-t contribution is its
  correction against the round-(t-s) anchor, s clamped to both the
  bound and the available history;
* the mesh path (shard_map, liveness rows as sharded operands) agrees
  with the vmap twin under the same plan -- the shared round body of
  ``rounds._refinement_rounds`` is what makes this structural.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_in_subprocess
from repro.core import rounds as rounds_core
from repro.core.compression import Compression
from repro.core.dantzig import DantzigConfig
from repro.core.faults import (
    CORRUPT_GARBAGE,
    CORRUPT_INF,
    CORRUPT_NAN,
    Aggregation,
    FaultPlan,
    FaultSchedule,
    masked_mean,
    trimmed_mean,
)
from repro.core.pipeline import BinaryHead
from repro.stats import synthetic

CFG = DantzigConfig(max_iters=80)


def _solves(d=16, m=6, seed=0):
    p = synthetic.make_problem(d=d, n_signal=4, rho=0.5)
    xs, ys = synthetic.sample_machines(jax.random.PRNGKey(seed), p, m,
                                       30, 30)
    _, ws = rounds_core.simulate_multi_round(
        BinaryHead(), (xs, ys), lam=0.3, lam_prime=0.3, rounds=1, cfg=CFG)
    return ws


def _plan(m, rounds, live=None, stale=None, corrupt=None):
    z = jnp.zeros((m, rounds))
    zi = jnp.zeros((m, rounds), jnp.int32)
    return FaultPlan(
        live=jnp.asarray(live, jnp.float32) if live is not None else z + 1,
        stale=jnp.asarray(stale, jnp.int32) if stale is not None else zi,
        corrupt=(jnp.asarray(corrupt, jnp.int32)
                 if corrupt is not None else zi))


# ---------------------------------------------------------------------------
# FaultSchedule: deterministic, seedable, rate-faithful
# ---------------------------------------------------------------------------


def test_schedule_deterministic_and_shaped():
    sched = FaultSchedule(dropout=0.3, straggle=0.2, corrupt=0.1,
                          corrupt_mode="mix", seed=11)
    a = sched.plan(40, 5, max_staleness=2)
    b = sched.plan(40, 5, max_staleness=2)
    for la, lb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert a.live.shape == (40, 5)
    assert a.rounds == 5
    assert set(np.unique(np.asarray(a.live))) <= {0.0, 1.0}
    assert np.asarray(a.stale).min() >= 0
    assert np.asarray(a.stale).max() <= 2
    assert np.asarray(a.corrupt).min() >= 0
    assert np.asarray(a.corrupt).max() <= 3
    # a different seed draws a different plan
    c = FaultSchedule(dropout=0.3, straggle=0.2, corrupt=0.1,
                      corrupt_mode="mix", seed=12).plan(40, 5, 2)
    assert not np.array_equal(np.asarray(a.live), np.asarray(c.live))


def test_schedule_rates_approximate_probabilities():
    plan = FaultSchedule(dropout=0.25, seed=3).plan(200, 20)
    rate = 1.0 - float(np.asarray(plan.live).mean())
    assert abs(rate - 0.25) < 0.03


def test_schedule_and_aggregation_validation():
    with pytest.raises(ValueError):
        FaultSchedule(dropout=1.5).validate()
    with pytest.raises(ValueError):
        FaultSchedule(corrupt_mode="bogus").validate()
    with pytest.raises(ValueError):
        Aggregation(trim=0.5).validate()
    with pytest.raises(ValueError):
        Aggregation(envelope=-1.0).validate()


def test_plan_shape_and_type_checks():
    # worker_rounds refuses an unmaterialized schedule (the faces own
    # the plan(m, rounds) call -- a shard can't know m)
    with pytest.raises(TypeError):
        rounds_core._check_plan(FaultSchedule(), (2,), "worker_rounds")
    ws = _solves(m=2)
    with pytest.raises(ValueError):  # machine-count mismatch
        rounds_core.simulate_round_loop(ws, rounds=2, faults=_plan(3, 2))
    with pytest.raises(ValueError):  # round-count mismatch
        rounds_core.simulate_round_loop(ws, rounds=2, faults=_plan(2, 3))


def test_fault_schedule_is_hashable_static():
    a = FaultSchedule(dropout=0.1, seed=2)
    b = FaultSchedule(dropout=0.1, seed=2)
    assert hash(a) == hash(b) and a == b
    assert hash(Aggregation(trim=0.1)) == hash(Aggregation(trim=0.1))


# ---------------------------------------------------------------------------
# masked aggregation == mean over the live subset
# ---------------------------------------------------------------------------


def test_masked_round_is_exact_live_subset_mean():
    ws = _solves(m=6)
    live = [[1.0], [0.0], [1.0], [1.0], [0.0], [1.0]]
    plan = _plan(6, 1, live=live)
    bar = rounds_core.simulate_round_loop(
        ws, rounds=1, faults=plan, aggregation=Aggregation())
    tilde = np.asarray(jax.vmap(rounds_core.refine_step)(ws, ws.beta_hat))
    keep = np.asarray(live)[:, 0] > 0
    expected = tilde[keep].sum(axis=0) / keep.sum()
    np.testing.assert_allclose(np.asarray(bar), expected,
                               rtol=1e-5, atol=1e-7)


def test_masked_nofault_matches_dense_round():
    ws = _solves()
    dense = rounds_core.simulate_round_loop(ws, rounds=3)
    masked = rounds_core.simulate_round_loop(
        ws, rounds=3, aggregation=Aggregation())
    np.testing.assert_allclose(np.asarray(masked), np.asarray(dense),
                               rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# screening: NaN / Inf / envelope, per machine, total
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("code", [CORRUPT_NAN, CORRUPT_INF])
def test_nonfinite_machine_screened_entirely(code):
    ws = _solves(m=5)
    corrupt = np.zeros((5, 1), np.int32)
    corrupt[2, 0] = code
    plan = _plan(5, 1, corrupt=corrupt)
    bar = rounds_core.simulate_round_loop(
        ws, rounds=1, faults=plan, aggregation=Aggregation())
    tilde = np.asarray(jax.vmap(rounds_core.refine_step)(ws, ws.beta_hat))
    keep = np.arange(5) != 2
    expected = tilde[keep].mean(axis=0)
    assert np.isfinite(np.asarray(bar)).all()
    np.testing.assert_allclose(np.asarray(bar), expected,
                               rtol=1e-5, atol=1e-7)


def test_envelope_screens_finite_garbage_only_when_set():
    ws = _solves(m=4)
    corrupt = np.zeros((4, 1), np.int32)
    corrupt[1, 0] = CORRUPT_GARBAGE
    plan = _plan(4, 1, corrupt=corrupt)
    tilde = np.asarray(jax.vmap(rounds_core.refine_step)(ws, ws.beta_hat))
    # with an envelope the +-1e12 garbage machine contributes nothing
    bar = rounds_core.simulate_round_loop(
        ws, rounds=1, faults=plan, aggregation=Aggregation(envelope=1e6))
    keep = np.arange(4) != 1
    np.testing.assert_allclose(np.asarray(bar), tilde[keep].mean(axis=0),
                               rtol=1e-5, atol=1e-7)
    # without one, finite garbage is NOT screened (the masked mean is
    # poisoned in magnitude but stays finite) -- the envelope is opt-in
    bar_no = rounds_core.simulate_round_loop(
        ws, rounds=1, faults=plan, aggregation=Aggregation())
    assert np.isfinite(np.asarray(bar_no)).all()
    assert float(np.abs(np.asarray(bar_no)).max()) > 1e9


def test_all_screened_round_returns_last_good_chaos_pin():
    """The chaos pin: a round where EVERY machine is screened falls
    back to the last-good aggregate; the stream never emits NaN."""
    ws = _solves(m=4)
    # round 1 clean, round 2 all-NaN
    corrupt = np.zeros((4, 2), np.int32)
    corrupt[:, 1] = CORRUPT_NAN
    plan = _plan(4, 2, corrupt=corrupt)
    bars = rounds_core.simulate_round_loop(
        ws, rounds=2, faults=plan, aggregation=Aggregation(),
        return_all_rounds=True)
    bars = np.asarray(bars)
    assert np.isfinite(bars).all()
    np.testing.assert_array_equal(bars[1], bars[0])
    # an ALL-NaN stream returns the zeros init, still no NaN
    all_bad = _plan(4, 2, corrupt=np.full((4, 2), CORRUPT_NAN, np.int32))
    bar = rounds_core.simulate_round_loop(
        ws, rounds=2, faults=all_bad, aggregation=Aggregation())
    np.testing.assert_array_equal(np.asarray(bar),
                                  np.zeros_like(np.asarray(bar)))


# ---------------------------------------------------------------------------
# bounded staleness
# ---------------------------------------------------------------------------


def test_zero_stale_plan_with_bound_is_bit_exact():
    ws = _solves()
    ref = rounds_core.simulate_round_loop(
        ws, rounds=3, faults=_plan(6, 3), aggregation=Aggregation())
    stale = rounds_core.simulate_round_loop(
        ws, rounds=3, faults=_plan(6, 3), staleness=2,
        aggregation=Aggregation())
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(stale))


def test_straggler_uses_round_t_minus_s_anchor():
    ws = _solves(m=3)
    stale = np.zeros((3, 2), np.int32)
    stale[0, 1] = 1  # machine 0 straggles in round 2
    plan = _plan(3, 2, stale=stale)
    bars = rounds_core.simulate_round_loop(
        ws, rounds=2, faults=plan, staleness=1, aggregation=Aggregation(),
        return_all_rounds=True)
    # manual: round 1 as usual; in round 2 machine 0's correction is
    # taken against its ROUND-1 anchor (its own beta_hat), machines
    # 1..2 against the round-1 aggregate
    tilde1 = jax.vmap(rounds_core.refine_step)(ws, ws.beta_hat)
    bar1 = jnp.mean(tilde1, axis=0)
    anchor2 = jnp.broadcast_to(bar1[None], ws.beta_hat.shape)
    fresh = jax.vmap(rounds_core.refine_step)(ws, anchor2)
    tilde2 = fresh.at[0].set(tilde1[0])
    np.testing.assert_allclose(np.asarray(bars[0]), np.asarray(bar1),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(bars[1]),
                               np.asarray(jnp.mean(tilde2, axis=0)),
                               rtol=1e-5, atol=1e-7)


def test_staleness_clamped_to_bound_and_history():
    ws = _solves(m=3)
    deep = np.full((3, 2), 5, np.int32)  # deeper than any history
    plan = _plan(3, 2, stale=deep)
    capped = rounds_core.simulate_round_loop(
        ws, rounds=2, faults=plan, staleness=1, aggregation=Aggregation())
    one = _plan(3, 2, stale=np.ones((3, 2), np.int32))
    expected = rounds_core.simulate_round_loop(
        ws, rounds=2, faults=one, staleness=1, aggregation=Aggregation())
    np.testing.assert_array_equal(np.asarray(capped), np.asarray(expected))


# ---------------------------------------------------------------------------
# masked / trimmed aggregation primitives
# ---------------------------------------------------------------------------


def test_trimmed_mean_matches_numpy_reference():
    key = jax.random.PRNGKey(0)
    stack = jax.random.normal(key, (10, 7, 2))
    w = jnp.ones((10,))
    got, den = trimmed_mean(stack, w, 0.2)  # per-side cut = 2
    srt = np.sort(np.asarray(stack), axis=0)
    np.testing.assert_allclose(np.asarray(got), srt[2:-2].mean(axis=0),
                               rtol=1e-5, atol=1e-7)
    assert float(den) == 10.0


def test_trimmed_mean_dead_machines_do_not_occupy_trim_slots():
    stack = jnp.stack([jnp.full((3, 1), v) for v in
                       (0.0, 1.0, 2.0, 3.0, 100.0, -100.0)])
    w = jnp.asarray([1.0, 1.0, 1.0, 1.0, 1.0, 0.0])  # -100 machine dead
    got, den = trimmed_mean(stack, w, 1.0 / 6.0)  # per-side cut = 1
    # live sorted: 0 1 2 3 100 -> drop 0 and 100 -> mean(1, 2, 3) = 2
    np.testing.assert_allclose(np.asarray(got), np.full((3, 1), 2.0),
                               rtol=1e-6)
    assert float(den) == 5.0


def test_masked_mean_all_dead_returns_zero_count():
    stack = jnp.ones((4, 3, 1)) * jnp.nan
    got, den = masked_mean(stack, jnp.zeros((4,)))
    assert float(den) == 0.0
    np.testing.assert_array_equal(np.asarray(got), np.zeros((3, 1)))


def test_trimmed_round_beats_unscreened_garbage():
    """The trimmed mode is the no-envelope defense: per-coordinate
    trimming discards the garbage machine without knowing its scale."""
    ws = _solves(m=8)
    corrupt = np.zeros((8, 1), np.int32)
    corrupt[3, 0] = CORRUPT_GARBAGE
    plan = _plan(8, 1, corrupt=corrupt)
    trimmed = rounds_core.simulate_round_loop(
        ws, rounds=1, faults=plan, aggregation=Aggregation(trim=0.2))
    untrimmed = rounds_core.simulate_round_loop(
        ws, rounds=1, faults=plan, aggregation=Aggregation())
    clean = rounds_core.simulate_round_loop(ws, rounds=1)
    err_t = float(np.abs(np.asarray(trimmed) - np.asarray(clean)).max())
    err_u = float(np.abs(np.asarray(untrimmed) - np.asarray(clean)).max())
    assert err_t < 1.0 < err_u


# ---------------------------------------------------------------------------
# compression interplay
# ---------------------------------------------------------------------------


def test_compressed_masked_dropout_screens_and_stays_finite():
    ws = _solves(m=6, d=16)
    comp = Compression(5, "int8")
    sched = FaultSchedule(dropout=0.3, corrupt=0.3, corrupt_mode="mix",
                          seed=9)
    bar = rounds_core.simulate_round_loop(
        ws, rounds=3, compression=comp, faults=sched,
        aggregation=Aggregation(envelope=1e6))
    assert np.isfinite(np.asarray(bar)).all()


def test_dropped_machine_ef_residual_carries_unchanged():
    ws = _solves(m=3, d=16)
    comp = Compression(4)
    live = np.ones((3, 1), np.float32)
    live[1, 0] = 0.0  # machine 1 drops the round
    plan = _plan(3, 1, live=live)
    _, resid = rounds_core.simulate_round_loop(
        ws, rounds=1, compression=comp, faults=plan,
        aggregation=Aggregation(), return_ef_residual=True)
    # a dropped machine computed nothing: its EF carry is still zero
    np.testing.assert_array_equal(np.asarray(resid[1]),
                                  np.zeros_like(np.asarray(resid[1])))
    assert float(np.abs(np.asarray(resid[0])).max()) > 0


# ---------------------------------------------------------------------------
# mesh parity (forced host devices, subprocess)
# ---------------------------------------------------------------------------


def test_mesh_masked_faulted_matches_sim_twin():
    """(data=2, model=4) mesh under dropout+staleness+mixed corruption,
    dense AND compressed: the liveness rows ride shard_map as sharded
    operands and the result matches the vmap twin under the SAME
    schedule seed."""
    out = run_in_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np, math
        from repro.core.dantzig import DantzigConfig
        from repro.core.compression import Compression
        from repro.core.distributed import (
            distributed_slda_shardmap, simulated_distributed_slda)
        from repro.core.faults import Aggregation, FaultSchedule
        from repro.stats import synthetic

        cfg = DantzigConfig(max_iters=200)
        m, d = 2, 16
        p = synthetic.make_problem(d=d, n_signal=4, rho=0.5)
        xs, ys = synthetic.sample_machines(jax.random.PRNGKey(5), p, m, 40, 40)
        lam = 0.3 * math.sqrt(math.log(d) / 80) * 4
        t = 0.25 * lam
        sched = FaultSchedule(dropout=0.4, straggle=0.3, corrupt=0.3,
                              corrupt_mode="mix", seed=21)
        agg = Aggregation(envelope=1e6)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        for comp in (None, Compression(5, "int8")):
            sim = simulated_distributed_slda(
                xs, ys, lam, lam, t, cfg, rounds=3, compression=comp,
                faults=sched, staleness=2, aggregation=agg)
            out = distributed_slda_shardmap(
                mesh, xs.reshape(-1, d), ys.reshape(-1, d), lam, lam, t,
                cfg, rounds=3, compression=comp, faults=sched, staleness=2,
                aggregation=agg)
            np.testing.assert_allclose(np.asarray(out), np.asarray(sim),
                                       atol=1e-5)
        print("FAULT_MESH_PARITY_OK")
        """
    )
    assert "FAULT_MESH_PARITY_OK" in out


def test_mesh_compressed_reentry_matches_uninterrupted():
    """Mid-stream re-entry on the MESH path: a T=3 compressed run split
    as 1+2 via ``return_ef_residual`` + ``resume_from`` reproduces the
    uninterrupted stream bit for bit (the sim twin's replay is pinned
    in test_compression)."""
    out = run_in_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np, math
        from jax.sharding import PartitionSpec as P
        from repro.core import rounds as rounds_core
        from repro.core.compression import Compression
        from repro.core.dantzig import DantzigConfig
        from repro.core.pipeline import BinaryHead
        from repro.stats import synthetic

        cfg = DantzigConfig(max_iters=200)
        m, d = 2, 16
        p = synthetic.make_problem(d=d, n_signal=4, rho=0.5)
        xs, ys = synthetic.sample_machines(jax.random.PRNGKey(6), p, m, 40, 40)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        comp = Compression(5, "int8")
        spec = P("data", None)

        def run(t_rounds, resume_from=None, ef_residual=None):
            # the resumed aggregate and residual enter as operands: a
            # shard_map body may not close over an array sharded on the
            # mesh's (explicit) axes
            extra, specs = (), [spec, spec]
            if resume_from is not None:
                extra = (resume_from, ef_residual)
                specs += [P(), P("data", None, None)]

            def shard_fn(x, y, *rest):
                bar, _, resid = rounds_core.worker_rounds(
                    BinaryHead(), x, y, lam=0.3, lam_prime=0.3,
                    rounds=t_rounds, cfg=cfg, model_axis="model",
                    model_axis_size=4, compression=comp,
                    resume_from=rest[0] if rest else None,
                    ef_residual=rest[1][0] if rest else None,
                    return_ef_residual=True)
                return bar, resid[None]

            fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=tuple(specs),
                               out_specs=(P(), P("data", None, None)),
                               check_vma=False)
            return fn(xs.reshape(-1, d), ys.reshape(-1, d), *extra)

        full, _ = run(3)
        half, resid = run(1)
        resumed, _ = run(2, resume_from=jnp.asarray(half),
                         ef_residual=jnp.asarray(resid))
        np.testing.assert_array_equal(np.asarray(full), np.asarray(resumed))
        print("MESH_REENTRY_OK")
        """
    )
    assert "MESH_REENTRY_OK" in out


# ---------------------------------------------------------------------------
# the faces thread the knobs
# ---------------------------------------------------------------------------


def test_faces_accept_fault_knobs():
    from repro.core.multiclass import mc_multi_round_slda
    from repro.core.slda import multi_round_slda

    d, m = 12, 4
    p = synthetic.make_problem(d=d, n_signal=3, rho=0.5)
    xs, ys = synthetic.sample_machines(jax.random.PRNGKey(8), p, m, 24, 24)
    sched = FaultSchedule(dropout=0.3, seed=13)
    bar = multi_round_slda(xs, ys, 0.3, 0.3, 0.05, rounds=2, cfg=CFG,
                           faults=sched, staleness=1,
                           aggregation=Aggregation())
    assert np.isfinite(np.asarray(bar)).all()

    mp = synthetic.make_mc_problem(d=10, num_classes=3, n_signal=3)
    mxs, mlabels = synthetic.sample_mc_machines(
        jax.random.PRNGKey(9), mp, 3, 45)
    beta, means = mc_multi_round_slda(
        mxs, mlabels, 3, 0.3, 0.3, 0.05, rounds=2, cfg=CFG,
        faults=sched, aggregation=Aggregation())
    assert np.isfinite(np.asarray(beta)).all()
    assert np.isfinite(np.asarray(means)).all()


def test_fault_free_faces_bit_exact_vs_legacy():
    """faults=None/aggregation=None is LITERALLY the legacy program:
    the threaded call signature changes nothing about the no-fault
    output (the golden files pin the absolute values)."""
    from repro.core.slda import multi_round_slda

    d, m = 12, 4
    p = synthetic.make_problem(d=d, n_signal=3, rho=0.5)
    xs, ys = synthetic.sample_machines(jax.random.PRNGKey(8), p, m, 24, 24)
    legacy = multi_round_slda(xs, ys, 0.3, 0.3, 0.05, rounds=3, cfg=CFG)
    threaded = multi_round_slda(xs, ys, 0.3, 0.3, 0.05, rounds=3, cfg=CFG,
                                faults=None, staleness=0, aggregation=None)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(threaded))
