"""Multiclass LDA on the mesh: shard_map matches the simulation.

``distributed_mc_slda_shardmap`` (data-axis machines, model-axis CLIME
columns, one (d, K) pmean) against ``simulated_distributed_mc_slda``
(same pipeline, vmap machines).  Mesh runs happen in a subprocess with
forced host devices (see ``conftest.run_in_subprocess``).
"""

from conftest import run_in_subprocess as _run_in_subprocess


def test_mc_mesh_8dev_remainder_columns():
    """Acceptance case: 8-device (data=2, model=4) mesh, d=70 (70 % 4 != 0):
    mesh output matches the single-device simulation to 1e-5."""
    out = _run_in_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np, math
        from repro.core import multiclass as mc
        from repro.core.dantzig import DantzigConfig
        from repro.core.distributed import distributed_mc_slda_shardmap
        from repro.stats import synthetic

        cfg = DantzigConfig(max_iters=300)
        K, m, n, d = 3, 2, 200, 70
        problem = synthetic.make_mc_problem(
            d=d, num_classes=K, n_signal=5, rho=0.6, signal=1.2)
        xs, labels = synthetic.sample_mc_machines(
            jax.random.PRNGKey(0), problem, m, n)
        lam = 0.3 * math.sqrt(math.log(d) / n) * 4
        t = 0.25 * lam
        sim_b, sim_m = mc.simulated_distributed_mc_slda(
            xs, labels, K, lam, lam, t, cfg)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        out_b, out_m = distributed_mc_slda_shardmap(
            mesh, xs.reshape(m * n, d), labels.reshape(m * n),
            K, lam, lam, t, cfg)
        assert out_b.shape == (d, K) and out_m.shape == (K, d)
        np.testing.assert_allclose(np.asarray(out_b), np.asarray(sim_b), atol=1e-5)
        np.testing.assert_allclose(np.asarray(out_m), np.asarray(sim_m), atol=1e-5)
        print("MC_MESH8_OK")
        """
    )
    assert "MC_MESH8_OK" in out


def test_mc_mesh_4dev_matches_simulation():
    """Satellite case: 4-device (data=2, model=2) mesh, K=5, to 2e-5.

    The mesh sums its column-sharded CLIME matmuls in another order than
    the simulation, and that f32 rounding compounds over the 300
    adaptive-rho iterations: on this design and seeds 0-3 the gap is
    2-6e-6 at 100 iterations, 2-8e-6 at 200 and 5e-6 to 1.1e-5 at 300,
    on coefficients up to 1.9 (a relative 6e-6, some 50 f32 ulps).
    """
    out = _run_in_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np, math
        from repro.core import multiclass as mc
        from repro.core.dantzig import DantzigConfig
        from repro.core.distributed import distributed_mc_slda_shardmap
        from repro.stats import synthetic

        cfg = DantzigConfig(max_iters=300)
        K, m, n, d = 5, 2, 200, 45
        problem = synthetic.make_mc_problem(
            d=d, num_classes=K, n_signal=4, rho=0.6)
        xs, labels = synthetic.sample_mc_machines(
            jax.random.PRNGKey(0), problem, m, n)
        lam = 0.3 * math.sqrt(math.log(d) / n) * 4
        t = 0.25 * lam
        sim_b, sim_m = mc.simulated_distributed_mc_slda(
            xs, labels, K, lam, lam, t, cfg)
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        out_b, out_m = distributed_mc_slda_shardmap(
            mesh, xs.reshape(m * n, d), labels.reshape(m * n),
            K, lam, lam, t, cfg)
        np.testing.assert_allclose(np.asarray(out_b), np.asarray(sim_b), atol=2e-5)
        np.testing.assert_allclose(np.asarray(out_m), np.asarray(sim_m), atol=1e-5)
        print("MC_MESH4_OK")
        """,
        devices=4,
    )
    assert "MC_MESH4_OK" in out


def test_mc_mesh_fused_solver_path():
    """The padded column sharding composes with the fused Pallas solver
    for a (d, K) block (d=22 over 4 model devices: 6 cols/device, 2 pad)."""
    out = _run_in_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np, math
        from repro.core import multiclass as mc
        from repro.core.dantzig import DantzigConfig
        from repro.core.distributed import distributed_mc_slda_shardmap
        from repro.stats import synthetic

        cfg = DantzigConfig(max_iters=250, adapt_rho=False, fused=True)
        K, m, n, d = 3, 1, 150, 22
        problem = synthetic.make_mc_problem(
            d=d, num_classes=K, n_signal=3, rho=0.6)
        xs, labels = synthetic.sample_mc_machines(
            jax.random.PRNGKey(2), problem, m, n)
        lam = 0.3 * math.sqrt(math.log(d) / n) * 4
        t = 0.25 * lam
        sim_b, _ = mc.simulated_distributed_mc_slda(
            xs, labels, K, lam, lam, t, cfg)
        mesh = jax.make_mesh((1, 4), ("data", "model"))
        out_b, _ = distributed_mc_slda_shardmap(
            mesh, xs.reshape(m * n, d), labels.reshape(m * n),
            K, lam, lam, t, cfg)
        np.testing.assert_allclose(np.asarray(out_b), np.asarray(sim_b), atol=1e-5)
        print("MC_MESH_FUSED_OK")
        """,
        devices=4,
    )
    assert "MC_MESH_FUSED_OK" in out
