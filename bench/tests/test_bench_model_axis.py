"""The model-axis cell, run whole at a tiny size on four virtual CPU
devices: each of two machines splits its CLIME columns over a 2-device
model slice.  Unbroken, the run is correct; with either fault of the
column split planted in it, the run's ``correct`` comes out false.
"""

import json
import os
import subprocess
import sys

import pytest

from bench.tests import tiny

CELL = "highd_d2048_m4.fit_T3_model2.4chip"
# d odd, so that the second model device holds a pad column
SIZES = dict(d=15, n_per_machine=64, N=256)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))
    file = os.path.join(path, "bench", "configs", "highd_d2048_m4.json")
    with open(file) as f:
        config = json.load(f)
    config.update(SIZES)
    with open(file, "w") as f:
        json.dump(config, f)
    return path


MODEL_AXIS = """
import json, sys
import jax, jax.numpy as jnp
from bench import run
from bench.tests import test_bench_faults as t
from repro.core import pipeline

root, fault = sys.argv[1], sys.argv[2]
if fault == "gather_left_out":
    # each model device keeps its own block of the correction, in place
    def own_block(theta, valid, resid, model_axis=None):
        block = jnp.where(valid[:, None], theta.T @ resid, 0.0)
        full = jnp.zeros((block.shape[0] * jax.lax.axis_size(model_axis),
                          block.shape[1]), block.dtype)
        full = jax.lax.dynamic_update_slice_in_dim(
            full, block, jax.lax.axis_index(model_axis) * block.shape[0], 0)
        return full[: resid.shape[0]]
    pipeline.apply_correction = own_block
elif fault == "block0_twice":
    # every model device solves the columns of block 0
    solve = pipeline.solve_clime_columns
    pipeline.solve_clime_columns = lambda factor, cols, *a, **k: solve(
        factor, jnp.arange(cols.shape[0], dtype=cols.dtype), *a, **k)
result = run.run_cell(root, sys.argv[3], 2 ** 31 + 11, t.SECONDS, False,
                      jax.devices())
print(json.dumps(result))
"""


@pytest.mark.parametrize("fault", [None, "gather_left_out", "block0_twice"])
def test_model_axis_cell(root, fault):
    """In a process of its own: the device count is fixed when JAX
    starts."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([tiny.REPO,
                                           os.path.join(tiny.REPO, "src")]))
    proc = subprocess.run(
        [sys.executable, "-c", MODEL_AXIS, root, str(fault), CELL],
        capture_output=True, text=True, timeout=600, env=env, cwd=tiny.REPO)
    assert proc.returncode == 0, proc.stderr[-4000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["attempted"] > 0
    assert result["correct"] == (fault is None), result["compared"]
    if fault is not None:
        assert result["failed"] == result["attempted"]
