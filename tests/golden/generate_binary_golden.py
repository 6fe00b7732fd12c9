"""Regenerate the binary pre-refactor golden outputs (PR 2 parity pins).

Run from the repo root:

    PYTHONPATH=src python tests/golden/generate_binary_golden.py

The .npz this writes was first produced before the head-parameterized
pipeline refactor, so the parity tests in ``tests/test_pipeline_parity.py``
pinned the refactor against the exact pre-refactor numbers.  It was
re-pinned on JAX 0.9: its default ``jax_threefry_partitionable=True``
draws other synthetic samples from the same keys.  The pins are always
computed on the CPU: the plain f32 path, with the Pallas kernels in the
interpreter.  Re-running it re-bases the pin to the current
implementation -- only do that deliberately.

The shard_map case runs in a subprocess with 2 forced host devices so
the main process keeps its default device count.
"""

import os
import subprocess
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
OUT = os.path.join(HERE, "binary_prerefactor.npz")

BODY = textwrap.dedent(
    """
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import slda
    from repro.core.dantzig import DantzigConfig
    from repro.core.distributed import (
        distributed_slda_shardmap,
        simulated_debiased_mean,
        simulated_distributed_slda,
        simulated_naive_averaged_slda,
    )
    from repro.stats import synthetic

    out = {}
    cfg = DantzigConfig(max_iters=300)

    # --- local debiased estimator (d=40) --------------------------------
    p40 = synthetic.make_problem(d=40, n_signal=5)
    x, y = synthetic.sample_two_class(jax.random.PRNGKey(10), p40, 200, 200)
    bt, bh = slda.debiased_local_estimator(x, y, 0.2, 0.25, cfg)
    out['local_beta_tilde'] = np.asarray(bt)
    out['local_beta_hat'] = np.asarray(bh)
    # default lam_prime=None branch
    bt2, bh2 = slda.debiased_local_estimator(x, y, 0.2, None, cfg)
    out['local_beta_tilde_lamdefault'] = np.asarray(bt2)

    # --- simulated paths (m=3, d=30) ------------------------------------
    p30 = synthetic.make_problem(d=30, n_signal=4)
    xs, ys = synthetic.sample_machines(jax.random.PRNGKey(11), p30, 3, 100, 100)
    out['sim_dist'] = np.asarray(
        simulated_distributed_slda(xs, ys, 0.2, 0.2, 0.05, cfg))
    out['sim_mean'] = np.asarray(
        simulated_debiased_mean(xs, ys, 0.2, 0.2, cfg))
    out['sim_naive'] = np.asarray(
        simulated_naive_averaged_slda(xs, ys, 0.2, cfg))

    # --- fused-solver simulated path -------------------------------------
    cfg_fused = DantzigConfig(max_iters=250, adapt_rho=False, fused=True)
    out['sim_dist_fused'] = np.asarray(
        simulated_distributed_slda(xs, ys, 0.2, 0.2, 0.05, cfg_fused))

    # --- shard_map with remainder columns: d=7 over |model|=2 ------------
    p7 = synthetic.make_problem(d=7, n_signal=3)
    xs7, ys7 = synthetic.sample_machines(jax.random.PRNGKey(12), p7, 1, 40, 40)
    mesh = jax.make_mesh((1, 2), ("data", "model"))
    out['mesh_d7'] = np.asarray(distributed_slda_shardmap(
        mesh, xs7.reshape(-1, 7), ys7.reshape(-1, 7), 0.2, 0.2, 0.05, cfg))

    np.savez(os.environ['GOLDEN_OUT'], **out)
    print('wrote', os.environ['GOLDEN_OUT'])
    """
)


def main():
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(REPO, "src"),
        GOLDEN_OUT=OUT,
        JAX_PLATFORMS="cpu",  # the plain f32 path, kernels interpreted
    )
    res = subprocess.run([sys.executable, "-c", BODY], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    sys.stdout.write(res.stdout)
    sys.stderr.write(res.stderr)
    res.check_returncode()


if __name__ == "__main__":
    main()
