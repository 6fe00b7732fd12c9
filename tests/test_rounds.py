"""Multi-round refinement (core/rounds.py, DESIGN.md §8).

Four pin families:

* **Mesh/simulation parity** -- ``distributed_slda_shardmap`` /
  ``distributed_mc_slda_shardmap`` with ``rounds=3`` on an 8-device
  (data=2, model=4) mesh match the single-device vmap simulation to
  1e-5, including ``d % |model| != 0`` remainder columns.
* **Communication/compute structure** -- the jaxpr of a T-round driver
  traces exactly T ``pmean``s of a (d, K) block over the data axis and
  exactly ONE ``eigh`` per worker: refinement rounds are closed-form,
  they re-solve nothing.
* **Statistics** -- in a large-m regime where the one-shot estimator's
  l2 error visibly degrades versus centralized, T=3 refinement rounds
  recover most of the gap; T=1 reproduces the one-shot bit-for-bit.
* **Warm re-entry** -- re-entering the rounds pipeline with the
  returned WorkerSolves carries resumes both ADMM solves in strictly
  fewer executed iterations.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from conftest import run_in_subprocess

from repro.analysis import check_entry, count_eqns
from repro.core import compression as compression_core
from repro.core import pipeline, rounds as rounds_core
from repro.core.dantzig import DantzigConfig
from repro.core.distributed import (
    distributed_slda_shardmap,
    simulated_debiased_mean,
    simulated_distributed_slda,
)
from repro.core.pipeline import BinaryHead
from repro.core.slda import centralized_slda, multi_round_slda
from repro.stats import synthetic


# ---------------------------------------------------------------------------
# jaxpr pins: T pmeans of a (d, K) block, one eigh per worker
# (counter and contracts both come from repro.analysis)
# ---------------------------------------------------------------------------


def test_rounds_trace_T_pmeans_and_one_eigh():
    """T rounds = T (d, K) pmeans over the data axis; the refinement
    rounds reuse the round-one SpectralFactor and CLIME block, so the
    whole T-round worker still traces exactly ONE eigh (pmean lowers to
    a psum; the model-axis gather is all_gather, counted separately)."""
    d = 12
    cfg = DantzigConfig(max_iters=40, adapt_rho=False)
    p = synthetic.make_problem(d=d, n_signal=4, rho=0.5)
    xs, ys = synthetic.sample_machines(jax.random.PRNGKey(0), p, 1, 30, 30)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for t_rounds in (1, 2, 3):
        def fn(x, y, t_rounds=t_rounds):
            return distributed_slda_shardmap(
                mesh, x, y, 0.2, 0.2, 0.05, cfg, rounds=t_rounds)

        jaxpr = jax.make_jaxpr(fn)(xs.reshape(-1, d), ys.reshape(-1, d))
        assert count_eqns(jaxpr, "psum", (d, 1)) == t_rounds
        assert count_eqns(jaxpr, "psum") == t_rounds
        assert count_eqns(jaxpr, "eigh") == 1
        # one intra-machine correction gather per round
        assert count_eqns(jaxpr, "all_gather") == t_rounds
        # and the face's full declared contract set holds on this trace
        # (dense path: every round is one dense psum, no data-axis
        # gathers, and the per-link bits are T dense (d, 1) blocks)
        violations = check_entry(
            "distributed.slda_shardmap", jaxpr,
            {"rounds": t_rounds, "dense_psums": t_rounds,
             "live_psums": 0, "total_psums": t_rounds, "screen_ops": 0,
             "data_gathers": 0,
             "data_gather_bits": 0,
             "data_psum_bits":
                 t_rounds * compression_core.dense_uplink_bits(d, 1),
             "data_total_bits":
                 t_rounds * compression_core.dense_uplink_bits(d, 1),
             "psum_payload": (d, 1), "pallas_calls": 0})
        assert violations == [], violations


def test_mc_rounds_trace_T_direction_pmeans_one_means_pmean():
    """Multiclass: T (d, K) direction pmeans + ONE (K, d) means pmean
    (the class means are round-independent), still one eigh."""
    from repro.core.distributed import distributed_mc_slda_shardmap

    d, K = 10, 3
    cfg = DantzigConfig(max_iters=40, adapt_rho=False)
    problem = synthetic.make_mc_problem(d=d, num_classes=K, n_signal=3)
    xs, labels = synthetic.sample_mc_machines(
        jax.random.PRNGKey(1), problem, 1, 60)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for t_rounds in (1, 3):
        def fn(x, lab, t_rounds=t_rounds):
            return distributed_mc_slda_shardmap(
                mesh, x, lab, K, 0.2, 0.2, 0.05, cfg, rounds=t_rounds)

        jaxpr = jax.make_jaxpr(fn)(
            xs.reshape(-1, d), labels.reshape(-1))
        assert count_eqns(jaxpr, "psum", (d, K)) == t_rounds
        assert count_eqns(jaxpr, "psum", (K, d)) == 1
        assert count_eqns(jaxpr, "eigh") == 1
        violations = check_entry(
            "distributed.mc_slda_shardmap", jaxpr,
            {"rounds": t_rounds, "dense_psums": t_rounds,
             "live_psums": 0, "screen_ops": 0,
             "data_gathers": 0,
             "data_gather_bits": 0,
             "data_psum_bits":
                 t_rounds * compression_core.dense_uplink_bits(d, K)
                 + K * d * 32,  # + the one (K, d) f32 means psum
             "data_total_bits":
                 t_rounds * compression_core.dense_uplink_bits(d, K)
                 + K * d * 32,
             "direction_payload": (d, K),
             "means_payload": (K, d), "total_psums": t_rounds + 1,
             "pallas_calls": 0})
        assert violations == [], violations


# ---------------------------------------------------------------------------
# rounds=1 IS the one-shot estimator
# ---------------------------------------------------------------------------


def test_rounds_one_matches_oneshot_bitwise():
    cfg = DantzigConfig(max_iters=200)
    p = synthetic.make_problem(d=20, n_signal=5, rho=0.5)
    xs, ys = synthetic.sample_machines(jax.random.PRNGKey(2), p, 4, 60, 60)
    legacy = simulated_distributed_slda(xs, ys, 0.2, 0.2, 0.05, cfg)
    one_round = simulated_distributed_slda(
        xs, ys, 0.2, 0.2, 0.05, cfg, rounds=1)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(one_round))
    # the slda face agrees with the distributed simulation
    face = multi_round_slda(xs, ys, 0.2, 0.2, 0.05, rounds=1, cfg=cfg)
    np.testing.assert_allclose(np.asarray(face), np.asarray(legacy),
                               atol=1e-6)


def test_refine_step_is_the_debias_formula():
    """One refine_step around beta_hat == the one-shot debias (eq. 3.4)."""
    cfg = DantzigConfig(max_iters=200)
    p = synthetic.make_problem(d=16, n_signal=4, rho=0.5)
    x, y = synthetic.sample_two_class(jax.random.PRNGKey(3), p, 80, 80)
    ws = pipeline.worker_solves(
        BinaryHead(), x, y, lam=0.2, lam_prime=0.25, cfg=cfg)
    bt_step = rounds_core.refine_step(ws, ws.beta_hat)
    bt_ref, _, _ = pipeline.worker_debiased(
        BinaryHead(), x, y, lam=0.2, lam_prime=0.25, cfg=cfg)
    np.testing.assert_allclose(np.asarray(bt_step), np.asarray(bt_ref),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# mesh parity vs the single-device simulation (subprocess, 8 devices)
# ---------------------------------------------------------------------------


def test_rounds_mesh_8dev_remainder_matches_simulation():
    """Acceptance case: (data=2, model=4) mesh, d=70 (70 % 4 != 0),
    rounds=3: the mesh multi-round output matches the vmap simulation
    to 1e-5 -- every round's correction gather handles the padded
    remainder columns exactly."""
    out = run_in_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np, math
        from repro.core.dantzig import DantzigConfig
        from repro.core.distributed import (
            distributed_slda_shardmap, simulated_distributed_slda)
        from repro.stats import synthetic

        cfg = DantzigConfig(max_iters=300)
        m, d = 2, 70
        p = synthetic.make_problem(d=d, n_signal=6, rho=0.6)
        xs, ys = synthetic.sample_machines(jax.random.PRNGKey(0), p, m, 100, 100)
        lam = 0.3 * math.sqrt(math.log(d) / 200) * 4
        t = 0.25 * lam
        sim = simulated_distributed_slda(xs, ys, lam, lam, t, cfg, rounds=3)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        out = distributed_slda_shardmap(
            mesh, xs.reshape(-1, d), ys.reshape(-1, d), lam, lam, t, cfg,
            rounds=3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(sim), atol=1e-5)
        print("ROUNDS_MESH8_OK")
        """
    )
    assert "ROUNDS_MESH8_OK" in out


def test_mc_rounds_mesh_matches_simulation():
    """Multiclass rounds=2 on a (2, 2) mesh vs the simulation."""
    out = run_in_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np, math
        from repro.core import multiclass as mc
        from repro.core.dantzig import DantzigConfig
        from repro.core.distributed import distributed_mc_slda_shardmap
        from repro.stats import synthetic

        cfg = DantzigConfig(max_iters=300)
        K, m, n, d = 3, 2, 150, 30
        problem = synthetic.make_mc_problem(d=d, num_classes=K, n_signal=4, rho=0.6)
        xs, labels = synthetic.sample_mc_machines(jax.random.PRNGKey(1), problem, m, n)
        lam = 0.3 * math.sqrt(math.log(d) / n) * 4
        t = 0.25 * lam
        sim_b, sim_m = mc.simulated_distributed_mc_slda(
            xs, labels, K, lam, lam, t, cfg, rounds=2)
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        out_b, out_m = distributed_mc_slda_shardmap(
            mesh, xs.reshape(m * n, d), labels.reshape(m * n),
            K, lam, lam, t, cfg, rounds=2)
        np.testing.assert_allclose(np.asarray(out_b), np.asarray(sim_b), atol=1e-5)
        np.testing.assert_allclose(np.asarray(out_m), np.asarray(sim_m), atol=1e-5)
        print("MC_ROUNDS_MESH_OK")
        """,
        devices=4,
    )
    assert "MC_ROUNDS_MESH_OK" in out


# ---------------------------------------------------------------------------
# statistics: refinement recovers past the m-barrier
# ---------------------------------------------------------------------------


def test_rounds_recover_large_m_error():
    """Large-m regime (m=40, n=100, d=60): the one-shot l2 error visibly
    degrades vs centralized; T=3 refinement cuts most of the excess and
    the refined support-recovery F1 stays within 5% of centralized."""
    from benchmarks.common import tuned_metrics

    t_grid = np.geomspace(0.005, 2.0, 25)
    cfg = DantzigConfig(max_iters=300)
    d, m, n = 60, 40, 100
    problem = synthetic.make_problem(d=d, n_signal=8, rho=0.6)
    b1 = float(jnp.sum(jnp.abs(problem.beta_star)))
    lam = 0.3 * math.sqrt(math.log(d) / n) * b1
    lam_c = 0.3 * math.sqrt(math.log(d) / (m * n)) * b1
    xs, ys = synthetic.sample_machines(
        jax.random.PRNGKey(4), problem, m, n // 2, n // 2)
    cent = centralized_slda(xs.reshape(-1, d), ys.reshape(-1, d), lam_c, cfg)
    mc = tuned_metrics(cent, problem.beta_star, t_grid)
    bars, _ = rounds_core.simulate_multi_round(
        BinaryHead(), (xs, ys), lam=lam, lam_prime=lam, rounds=3, cfg=cfg,
        return_all_rounds=True)
    m1 = tuned_metrics(bars[0][:, 0], problem.beta_star, t_grid)
    m3 = tuned_metrics(bars[2][:, 0], problem.beta_star, t_grid)
    # premise: the one-shot is visibly past the barrier
    assert m1["l2"] > 1.5 * mc["l2"], (m1, mc)
    # T=3 cuts at least 30% of the excess error over centralized
    assert m3["l2"] < m1["l2"] - 0.3 * (m1["l2"] - mc["l2"]), (m1, m3, mc)
    # and support recovery stays with the centralized baseline
    assert m3["f1"] >= mc["f1"] - 0.05, (m3, mc)


def test_rounds_param_changes_simulated_mean():
    cfg = DantzigConfig(max_iters=150)
    p = synthetic.make_problem(d=16, n_signal=4, rho=0.5)
    xs, ys = synthetic.sample_machines(jax.random.PRNGKey(5), p, 3, 40, 40)
    r1 = simulated_debiased_mean(xs, ys, 0.2, 0.2, cfg)
    r3 = simulated_debiased_mean(xs, ys, 0.2, 0.2, cfg, rounds=3)
    assert r1.shape == r3.shape == (16,)
    assert float(jnp.max(jnp.abs(r1 - r3))) > 1e-6


# ---------------------------------------------------------------------------
# warm re-entry: carried WorkerSolves state resumes in fewer iterations
# ---------------------------------------------------------------------------


def test_rounds_warm_reentry_fewer_iterations():
    cfg = DantzigConfig(max_iters=800, tol=2e-4, check_every=25)
    p = synthetic.make_problem(d=40, n_signal=5, rho=0.6)
    xs, ys = synthetic.sample_machines(jax.random.PRNGKey(6), p, 3, 150, 150)
    # at lam=0.2 one machine's direction solve of this draw stalls above
    # tol (still at the cap after 6000 iterations); at 0.25 all converge
    lam = 0.25
    cold_bar, cold = rounds_core.simulate_multi_round(
        BinaryHead(), (xs, ys), lam=lam, lam_prime=0.2, rounds=2, cfg=cfg,
        collect_info=True)
    assert cold.iters_beta is not None and cold.iters_theta is not None
    assert int(np.max(cold.iters_beta)) < 800, \
        "cold direction solves must converge below the cap"
    assert int(np.max(cold.iters_theta)) < 800, \
        "cold CLIME solves must converge below the cap"
    cold_total = (int(np.max(cold.iters_beta))
                  + int(np.max(cold.iters_theta)))
    warm_bar, warm = rounds_core.simulate_multi_round(
        BinaryHead(), (xs, ys), lam=lam, lam_prime=0.2, rounds=2, cfg=cfg,
        collect_info=True,
        rho_beta=cold.rho_beta, rho_theta=cold.rho_theta,
        state_beta=cold.state_beta, state_theta=cold.state_theta)
    warm_total = (int(np.max(warm.iters_beta))
                  + int(np.max(warm.iters_theta)))
    assert warm_total < cold_total, (warm_total, cold_total)
    np.testing.assert_allclose(np.asarray(warm_bar), np.asarray(cold_bar),
                               atol=5e-3)


def test_collect_info_default_off_keeps_fields_none():
    cfg = DantzigConfig(max_iters=100)
    p = synthetic.make_problem(d=12, n_signal=3, rho=0.5)
    x, y = synthetic.sample_two_class(jax.random.PRNGKey(7), p, 40, 40)
    ws = pipeline.worker_solves(
        BinaryHead(), x, y, lam=0.2, lam_prime=0.2, cfg=cfg)
    assert ws.iters_beta is None and ws.state_beta is None
    full = pipeline.worker_solves(
        BinaryHead(), x, y, lam=0.2, lam_prime=0.2, cfg=cfg, full=True)
    assert full.iters_beta is not None and full.state_beta is not None
    assert full.theta.shape == (12, 12)
