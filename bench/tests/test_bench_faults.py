"""A whole run of each cell, with the timed path broken underneath.

Each test skips the harness's look for a chip and drives the rest of a
run (:func:`bench.run.run_cell`) at a tiny size on the CPU: set-up, the
closed-loop window, the reference and the comparison.  Unbroken, the run
is correct; with each fault the program can have planted in it, the
run's ``correct`` comes out false.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import tiny

SHARE_CELLS = ("highd_d1024_m4.fit_share.1chip", "paper51_m10.fit_share.1chip")
SECONDS = 0.3


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))


def _run(root, cell, seed=2 ** 31 + 5):
    return run.run_cell(root, cell, seed, SECONDS, False, jax.devices())


@pytest.mark.parametrize("cell", SHARE_CELLS)
def test_unbroken_run_is_correct(root, cell):
    result = _run(root, cell)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) >= {"fit_s", "setup_s"}


def _state_unchanged(a, b, lam, cfg, rho0=None, *, return_rho=False,
                     state0=None, return_info=False):
    """Every ADMM step returns its state unchanged: the zero start."""
    b2 = b if b.ndim == 2 else b[:, None]
    beta = jnp.zeros_like(b2 if b.ndim == 2 else b)
    rho = jnp.full((b2.shape[1],), cfg.rho, jnp.float32)
    out = (beta,)
    if return_rho:
        out += (rho if b.ndim == 2 else rho[0],)
    return out if len(out) > 1 else out[0]


def _half_batch(suff_stats):
    def broken(x, y, use_kernel=None):
        return suff_stats(x[: x.shape[0] // 2], y[: y.shape[0] // 2],
                          use_kernel)
    return broken


def _altered_answer(hard_threshold):
    def broken(beta, t):
        out = hard_threshold(beta, t)
        j = jnp.argmax(jnp.abs(out))
        return out.at[j].set(-out[j])
    return broken


def _plant(monkeypatch, fault):
    from repro.core import dantzig, pipeline, slda

    if fault == "state_unchanged":
        monkeypatch.setattr(dantzig, "solve_dantzig_scan", _state_unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(pipeline, "suff_stats",
                            _half_batch(pipeline.suff_stats))
    elif fault == "answer_altered":
        monkeypatch.setattr(slda, "hard_threshold",
                            _altered_answer(slda.hard_threshold))
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("cell", SHARE_CELLS)
def test_fault_is_not_correct(root, cell, fault, monkeypatch):
    _plant(monkeypatch, fault)
    result = _run(root, cell)
    assert not result["correct"], (fault, result["compared"])
    assert result["failed"] == result["attempted"] > 0
    c = result["compared"]["beta_gap"]
    assert c["value"] > c["limit"]


FOUR_CHIPS = """
import json, sys
import jax
from bench import run
from bench.tests import test_bench_faults as t

root, fault = sys.argv[1], sys.argv[2]
if fault == "exchange_left_out":
    from repro.core import rounds
    rounds._MeshRound.mean = lambda self, x: x
result = run.run_cell(root, "highd_d1024_m4.fit_T3.4chip", 2 ** 31 + 9,
                      t.SECONDS, False, jax.devices())
print(json.dumps(result))
"""


@pytest.mark.parametrize("fault", [None, "exchange_left_out"])
def test_four_chip_cell(root, fault):
    """The four-chip cell on four virtual CPU devices, in a process of its
    own (the device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([tiny.REPO,
                                           os.path.join(tiny.REPO, "src")]))
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS, root, str(fault)],
        capture_output=True, text=True, timeout=600, env=env, cwd=tiny.REPO)
    assert proc.returncode == 0, proc.stderr[-4000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] == (fault is None), result["compared"]


def test_program_default_off_the_stated_schedule_is_refused(root,
                                                            monkeypatch):
    """A default ``DantzigConfig()`` with an early exit or another
    schedule than the configuration states stops the run before set-up."""
    from repro.core import dantzig

    stated = dantzig.DantzigConfig
    monkeypatch.setattr(dantzig, "DantzigConfig",
                        lambda: stated(tol=1e-3))
    with pytest.raises(ValueError, match="departs from the fixed ADMM"):
        _run(root, SHARE_CELLS[0])
    monkeypatch.setattr(dantzig, "DantzigConfig",
                        lambda: stated(max_iters=300))
    with pytest.raises(ValueError, match="departs from the fixed ADMM"):
        _run(root, SHARE_CELLS[0])
