"""The plain reference, the comparison, and the control that must fail it.

On the chip the control is run at each cell's own size by
``bench/control.py``.  Here the same control is put in the program's
place in a whole run at a size the CPU holds.  At this size the program
reads under 1e-6 (the CPU computes every float32 product in full) and
the control, over a pool of eight datasets, at least 6e-4 on every seed
tried, so the limit here is 1e-4: these tests check the mechanism; the
chip runs set the cells' limits (``bench/limits``).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, reference, run
from bench.tests import tiny

SMALL_LIMIT = 1e-4
SHARE_CELLS = ("highd_d1024_m4.fit_share.1chip", "paper51_m10.fit_share.1chip")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tiny.make_root(str(tmp_path_factory.mktemp("bench_control")))
    for name in os.listdir(os.path.join(path, "bench", "limits")):
        with open(os.path.join(path, "bench", "limits", name), "w") as f:
            json.dump({"beta_gap": {"limit": SMALL_LIMIT}}, f)
    return path


def _control_in_place(config):
    """The reference at the control's precision, in the program's place:
    the same signature as ``distributed_slda_shardmap``."""
    sched = reference.schedule(config)
    precision = reference.control(config)

    def fit(mesh, x, y, lam, lam_prime, t, cfg, rounds=1):
        held = mesh.shape["data"]
        xs = x.reshape(held, -1, x.shape[1])
        ys = y.reshape(held, -1, y.shape[1])
        bar = reference.aggregate(xs, ys, lam, rounds, sched, precision)
        return jnp.where(jnp.abs(bar) > t, bar, 0.0)

    return fit


@pytest.mark.parametrize("cell", SHARE_CELLS)
def test_program_is_correct_at_the_small_limit(root, cell):
    result = run.run_cell(root, cell, 7, 0.2, False, jax.devices())
    assert result["correct"], result["compared"]
    assert result["compared"]["beta_gap"]["value"] < SMALL_LIMIT / 50


@pytest.mark.parametrize("seed", [11, 12, 2 ** 31 + 13])
@pytest.mark.parametrize("cell", SHARE_CELLS)
def test_control_is_not_correct(root, cell, seed, monkeypatch):
    from repro.core import distributed

    with open(os.path.join(root, "bench", "configs",
                           cell.split(".")[0] + ".json")) as f:
        config = json.load(f)
    monkeypatch.setattr(distributed, "distributed_slda_shardmap",
                        _control_in_place(config))
    result = run.run_cell(root, cell, seed, 0.5, False, jax.devices())
    assert not result["correct"], result["compared"]
    assert result["compared"]["beta_gap"]["value"] > 3 * SMALL_LIMIT


def test_control_is_one_precision_below():
    config = {"precision": {"admm": "highest", "stats": ["highest", "bf16x1"]}}
    assert reference.references(config) == [
        reference.Precision("highest", "highest"),
        reference.Precision("bf16x1", "highest")]
    assert reference.control(config) == reference.Precision("bf16x1", "bf16x3")


def test_emulated_precisions():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    err = {p: np.abs(np.asarray(reference.matmul(a, b, p)) - exact).max()
           for p in ("highest", "bf16x3", "bf16x1")}
    assert err["highest"] < 1e-4
    assert err["highest"] < err["bf16x3"] < err["bf16x1"]
    assert err["bf16x1"] > 1e-2  # eight bits of mantissa
    with pytest.raises(ValueError):
        reference.matmul(a, b, "fp8")


def test_threshold_gap():
    raw = np.array([2.0, -1.0, 0.51, 0.49, 0.0])
    t = 0.5
    kept = np.where(np.abs(raw) > t, raw, 0.0)
    assert reference.threshold_gap(kept, raw, t) == 0.0
    # a coordinate on the other side of t, by a hair: a hair of gap
    flip = kept.copy()
    flip[2] = 0.0
    assert reference.threshold_gap(flip, raw, t) == pytest.approx(
        0.01 / np.linalg.norm(raw))
    near = kept.copy()
    near[3] = 0.5001
    assert reference.threshold_gap(near, raw, t) == pytest.approx(
        0.0101 / np.linalg.norm(raw))
    # an answer altered, or dropped, reads its whole size
    wrong = kept.copy()
    wrong[0] = -2.0
    assert reference.threshold_gap(wrong, raw, t) == pytest.approx(
        4.0 / np.linalg.norm(raw))
    assert reference.threshold_gap(np.zeros(5), raw, t) > 0.6
    assert reference.beta_gap(kept, [raw + 1, raw], t) == 0.0


def test_reference_matches_program_on_cpu():
    """On the CPU every product is full float32, so the program and the
    reference agree to rounding at any size."""
    from repro.core.dantzig import DantzigConfig
    from repro.core.distributed import simulated_debiased_mean

    pool = data.make_pool(3, d=24, n_signal=10, rho=0.8, signal=1.0, r=0.5,
                          n_per_machine=40, machines=4, machines_held=4,
                          size=1, lam_coef=0.3, t_coef=0.75)
    sched = reference.Schedule(600, 1.0, 1.7, 10, 10.0, 2.0)
    for rounds in (1, 3):
        raw = reference.fit(pool.xs[0], pool.ys[0], pool.lam, rounds, sched,
                            reference.Precision("highest", "highest"))
        program = simulated_debiased_mean(pool.xs[0], pool.ys[0], pool.lam,
                                          pool.lam, DantzigConfig(), rounds)
        np.testing.assert_allclose(np.asarray(program), raw, atol=1e-5)
