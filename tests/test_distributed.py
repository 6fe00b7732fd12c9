"""Model-axis CLIME sharding: remainder columns must never be dropped.

The debias correction ``Theta^T (Sigma beta_hat - mu_d)`` uses all d
CLIME columns; these tests pin the padded+masked sharding against the
unsharded simulation for d NOT a multiple of the model-axis size, and
the gathered correction blocks against the unsharded product.
Mesh runs happen in a subprocess with forced host devices (see
``conftest.run_in_subprocess``).
"""

import math

import pytest

from conftest import run_in_subprocess as _run_in_subprocess


def _rule(d, n):
    lam = 0.3 * math.sqrt(math.log(d) / n) * 4
    return lam, 0.25 * lam


# name: (machines, rows per class, d, signal coordinates, PRNG key,
#        (data, model) mesh, lam, t, DantzigConfig fields, rounds)
REMAINDER_CASES = {
    # 7 % 2 = 1 column must survive
    "d7_size2": (1, 40, 7, 3, 1, (1, 2), 0.2, 0.05,
                 dict(max_iters=200), 1),
    # the acceptance case: d=70, |model|=4 agrees to 1e-5
    "d70_size4": (2, 60, 70, 5, 0, (2, 4), *_rule(70, 120),
                  dict(max_iters=200), 1),
    # the fused Pallas solver path: ceil gives 3 cols/device, 1 pad column
    "fused_d11_size4": (1, 50, 11, 3, 2, (1, 4), 0.15, 0.02,
                        dict(max_iters=250, adapt_rho=False, fused=True), 1),
    # two machines, each over a 2-wide model slice, T=3 rounds: a pmean
    # over the data axis and a gather over the model axis in every round
    "d15_data2_model2_T3": (2, 40, 15, 3, 3, (2, 2), *_rule(15, 80),
                            dict(max_iters=200), 3),
}


@pytest.mark.parametrize("case", sorted(REMAINDER_CASES))
def test_remainder_columns(case):
    """The mesh fit equals the vmap simulation to 1e-5."""
    m, n, d, n_signal, key, mesh, lam, t, cfg, rounds = REMAINDER_CASES[case]
    out = _run_in_subprocess(
        f"""
        import jax, numpy as np
        from repro.core.distributed import (
            distributed_slda_shardmap, simulated_distributed_slda,
        )
        from repro.core.dantzig import DantzigConfig
        from repro.stats import synthetic

        cfg = DantzigConfig(**{cfg!r})
        m, n, d, lam, t, rounds = {m}, {n}, {d}, {lam!r}, {t!r}, {rounds}
        problem = synthetic.make_problem(d=d, n_signal={n_signal})
        xs, ys = synthetic.sample_machines(
            jax.random.PRNGKey({key}), problem, m, n, n)
        sim = simulated_distributed_slda(xs, ys, lam, lam, t, cfg,
                                         rounds=rounds)
        mesh = jax.make_mesh({mesh}, ("data", "model"))
        out = distributed_slda_shardmap(
            mesh, xs.reshape(m * n, d), ys.reshape(m * n, d),
            lam, lam, t, cfg, rounds=rounds)
        np.testing.assert_allclose(np.asarray(out), np.asarray(sim),
                                   atol=1e-5)
        print("REMAINDER_OK")
        """,
        devices=mesh[0] * mesh[1],
    )
    assert "REMAINDER_OK" in out


@pytest.mark.parametrize("d,size", [(15, 2), (16, 2), (15, 4), (16, 4)])
def test_gathered_correction_is_the_unsharded_product(d, size):
    """Each model device's column block, and the correction every device
    gathers from the blocks, equal the unsharded ``Theta`` and
    ``Theta^T resid``: the shares add up to the uncut result."""
    out = _run_in_subprocess(
        f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import pipeline
        from repro.core.dantzig import DantzigConfig
        from repro.stats import synthetic

        d, size = {d}, {size}
        cfg = DantzigConfig(max_iters=100)
        problem = synthetic.make_problem(d=d, n_signal=3)
        xs, ys = synthetic.sample_machines(
            jax.random.PRNGKey(d), problem, 1, 40, 40)
        hs = pipeline.BinaryHead().stats(xs[0], ys[0])
        whole = pipeline.solves_from_stats(hs, lam=0.2, lam_prime=0.2,
                                           cfg=cfg)
        resid = hs.sigma @ whole.beta_hat - hs.rhs
        expected = whole.theta.T @ resid

        def per_device():
            ws = pipeline.solves_from_stats(
                hs, lam=0.2, lam_prime=0.2, cfg=cfg, model_axis="model",
                model_axis_size=size)
            corr = pipeline.apply_correction(ws.theta, ws.valid, resid,
                                             "model")
            return ws.theta[None], corr[None]

        mesh = jax.make_mesh((size,), ("model",))
        blocks, gathered = jax.shard_map(
            per_device, mesh=mesh, in_specs=(),
            out_specs=(P("model"), P("model")), check_vma=False)()
        cols = np.concatenate(list(np.asarray(blocks)), axis=1)
        assert cols.shape[1] == size * -(-d // size) >= d
        np.testing.assert_allclose(cols[:, :d], np.asarray(whole.theta),
                                   atol=1e-6)
        assert np.asarray(gathered).shape == (size, d, 1)
        for corr in np.asarray(gathered):
            np.testing.assert_allclose(corr, np.asarray(expected),
                                       atol=1e-6)
        print("GATHER_OK")
        """,
        devices=size,
    )
    assert "GATHER_OK" in out
