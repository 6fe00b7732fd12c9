"""The frozen data generator matches the program's, draw for draw."""

import math

import jax
import numpy as np
import pytest

from bench import data
from repro.stats import synthetic


@pytest.mark.parametrize("d,n_signal,rho", [(200, 10, 0.8), (37, 5, 0.5)])
def test_problem_matches(d, n_signal, rho):
    ours = data.make_problem(d, n_signal, rho)
    theirs = synthetic.make_problem(d=d, n_signal=n_signal, rho=rho)
    for a, b in zip(ours, theirs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("m,n1,n2", [(1, 500, 500), (4, 7, 9)])
def test_sample_machines_matches_draw_for_draw(m, n1, n2):
    key = jax.random.PRNGKey(123)
    problem = data.make_problem(24, 10, 0.8)
    xs, ys = data.sample_machines(key, problem, m, n1, n2)
    ref = synthetic.make_problem(d=24, n_signal=10, rho=0.8)
    xr, yr = synthetic.sample_machines(key, ref, m, n1, n2)
    np.testing.assert_array_equal(np.asarray(xs), np.asarray(xr))
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(yr))
    assert xs.shape == (m, n1, 24) and ys.shape == (m, n2, 24)


def test_penalties_follow_the_smoke_rule():
    problem = data.make_problem(200, 10, 0.8)
    b1 = float(np.abs(np.asarray(problem.beta_star)).sum())
    lam, t = data.penalties(problem.beta_star, 200, 1000, 10000)
    assert lam == pytest.approx(0.30 * math.sqrt(math.log(200) / 1000) * b1)
    assert t == pytest.approx(0.75 * math.sqrt(math.log(200) / 10000) * b1)


def test_seeds_past_32_bits():
    keys = [data.key_from_seed(s) for s in (0, 2 ** 31 - 1, 2 ** 31 + 5,
                                            2 ** 32 + 5, 2 ** 63)]
    raw = {tuple(np.asarray(jax.random.key_data(k)).tolist()) for k in keys}
    assert len(raw) == len(keys)
    np.testing.assert_array_equal(
        np.asarray(data.key_from_seed(2 ** 32 + 5)),
        np.asarray(data.key_from_seed(2 ** 32 + 5)))
    with pytest.raises(ValueError):
        data.key_from_seed(-1)


def test_pool_shapes_and_seeding():
    kw = dict(d=12, n_signal=3, rho=0.8, signal=1.0, r=0.5, n_per_machine=10,
              machines=4, machines_held=2, size=3, lam_coef=0.3, t_coef=0.75)
    a = data.make_pool(2 ** 31 + 1, **kw)
    b = data.make_pool(2 ** 31 + 1, **kw)
    c = data.make_pool(2 ** 31 + 2, **kw)
    assert a.xs.shape == (3, 2, 5, 12) and a.ys.shape == (3, 2, 5, 12)
    np.testing.assert_array_equal(np.asarray(a.xs), np.asarray(b.xs))
    assert not np.array_equal(np.asarray(a.xs), np.asarray(c.xs))
    assert not np.array_equal(np.asarray(a.xs[0]), np.asarray(a.xs[1]))
    assert (a.lam, a.t) == (b.lam, b.t)
