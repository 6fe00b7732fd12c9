"""Pins for the head-parameterized pipeline refactor (PR 2).

Two kinds of pins:

  * numeric -- every binary public API (``debiased_local_estimator``,
    ``simulated_distributed_slda`` & friends, ``distributed_slda_shardmap``
    with remainder columns) must reproduce the PRE-refactor outputs
    stored in ``tests/golden/binary_prerefactor.npz`` (written by
    ``tests/golden/generate_binary_golden.py``; re-pinned on JAX 0.9,
    whose partitionable threefry default changed the synthetic draws);
  * structural -- exactly one implementation of the worker debias
    schedule remains: slda / distributed / multiclass call into
    ``core/pipeline.py``, and no module but the dispatch layer imports
    ``solve_dantzig`` from ``core.dantzig``.
"""

import os

import jax
import numpy as np
import pytest

from conftest import run_in_subprocess

from repro.analysis import imports as import_rules
from repro.core import slda
from repro.core.dantzig import DantzigConfig
from repro.core.distributed import (
    simulated_debiased_mean,
    simulated_distributed_slda,
    simulated_naive_averaged_slda,
)
from repro.stats import synthetic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(REPO, "tests", "golden", "binary_prerefactor.npz")
ATOL = 1e-6  # pre-refactor parity budget (observed: bit-for-bit on CPU)


@pytest.fixture(scope="module")
def golden():
    return np.load(GOLDEN)


def test_local_estimator_matches_prerefactor(golden):
    cfg = DantzigConfig(max_iters=300)
    p40 = synthetic.make_problem(d=40, n_signal=5)
    x, y = synthetic.sample_two_class(jax.random.PRNGKey(10), p40, 200, 200)
    bt, bh = slda.debiased_local_estimator(x, y, 0.2, 0.25, cfg)
    np.testing.assert_allclose(np.asarray(bt), golden["local_beta_tilde"], atol=ATOL)
    np.testing.assert_allclose(np.asarray(bh), golden["local_beta_hat"], atol=ATOL)
    # lam_prime=None defaults to lam, as before the refactor
    bt2, _ = slda.debiased_local_estimator(x, y, 0.2, None, cfg)
    np.testing.assert_allclose(
        np.asarray(bt2), golden["local_beta_tilde_lamdefault"], atol=ATOL)


def test_simulated_paths_match_prerefactor(golden):
    cfg = DantzigConfig(max_iters=300)
    p30 = synthetic.make_problem(d=30, n_signal=4)
    xs, ys = synthetic.sample_machines(jax.random.PRNGKey(11), p30, 3, 100, 100)
    np.testing.assert_allclose(
        np.asarray(simulated_distributed_slda(xs, ys, 0.2, 0.2, 0.05, cfg)),
        golden["sim_dist"], atol=ATOL)
    np.testing.assert_allclose(
        np.asarray(simulated_debiased_mean(xs, ys, 0.2, 0.2, cfg)),
        golden["sim_mean"], atol=ATOL)
    np.testing.assert_allclose(
        np.asarray(simulated_naive_averaged_slda(xs, ys, 0.2, cfg)),
        golden["sim_naive"], atol=ATOL)


def test_fused_solver_path_matches_prerefactor(golden):
    cfg = DantzigConfig(max_iters=250, adapt_rho=False, fused=True)
    p30 = synthetic.make_problem(d=30, n_signal=4)
    xs, ys = synthetic.sample_machines(jax.random.PRNGKey(11), p30, 3, 100, 100)
    np.testing.assert_allclose(
        np.asarray(simulated_distributed_slda(xs, ys, 0.2, 0.2, 0.05, cfg)),
        golden["sim_dist_fused"], atol=ATOL)


def test_shardmap_remainder_matches_prerefactor():
    """d=7 over |model|=2 (d % size != 0): the padded+masked sharding
    through the new core reproduces the pre-refactor mesh output."""
    out = run_in_subprocess(
        """
        import os
        import jax, numpy as np
        from repro.core.dantzig import DantzigConfig
        from repro.core.distributed import distributed_slda_shardmap
        from repro.stats import synthetic

        g = np.load(os.environ['GOLDEN'])
        p7 = synthetic.make_problem(d=7, n_signal=3)
        xs, ys = synthetic.sample_machines(jax.random.PRNGKey(12), p7, 1, 40, 40)
        mesh = jax.make_mesh((1, 2), ("data", "model"))
        out = distributed_slda_shardmap(
            mesh, xs.reshape(-1, 7), ys.reshape(-1, 7), 0.2, 0.2, 0.05,
            DantzigConfig(max_iters=300))
        np.testing.assert_allclose(np.asarray(out), g['mesh_d7'], atol=1e-6)
        print('MESH_GOLDEN_OK')
        """,
        devices=2,
        env_extra={"GOLDEN": GOLDEN},
    )
    assert "MESH_GOLDEN_OK" in out


# ---------------------------------------------------------------------------
# Structural pins -- AST-based import-graph rules from repro.analysis
# (a comment, docstring, or alias rename can no longer flip these)
# ---------------------------------------------------------------------------


def test_single_pipeline_implementation():
    """slda, distributed and multiclass all call into core/pipeline.py --
    directly (worker_debiased / debias) or through the rounds core
    (worker_rounds / simulate_multi_round, themselves thin over
    pipeline.worker_solves + pipeline.apply_correction) -- and the
    sharded-CLIME gather logic lives only in the pipeline."""
    violations = import_rules.pipeline_unification_violations()
    assert violations == [], [v.render() for v in violations]
    violations = import_rules.exclusive_call_violations()
    assert violations == [], [v.render() for v in violations]
    # the positive half of the gather rule: pipeline really does gather
    pipeline_path = import_rules.SRC_ROOT / "repro" / "core" / "pipeline.py"
    import ast

    calls = [n for n in ast.walk(ast.parse(pipeline_path.read_text()))
             if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute)
             and n.func.attr == "all_gather"]
    assert calls, "pipeline.py lost its all_gather call site"


def test_only_dispatch_layer_imports_dantzig_solver():
    """No module but core/solver_dispatch.py reaches around the dispatch
    layer to core.dantzig's solver entry points."""
    violations = import_rules.banned_import_violations()
    assert violations == [], [v.render() for v in violations]
