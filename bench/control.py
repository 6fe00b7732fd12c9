#!/usr/bin/env python3
"""The control of a cell's comparison, run at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed it draws the cell's pool as a run does, puts the plain
reference in the program's place with its ADMM products one precision
below the configuration's (:func:`bench.reference.control`: float32 at
HIGHEST gives three bf16 passes), and prints the number a run would
compare, the widest ``beta_gap`` over the pool's datasets, beside the
cell's limit.  The control has to come out above the limit.  The
benchmark's own runs do not run this.  It runs on one chip whatever the
cell asks for, since the reference runs the machines one after another.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import data, manifest, reference  # noqa: E402


def control_gaps(cell: manifest.Cell, seed: int) -> list:
    """The control's ``beta_gap`` on each dataset of the cell's pool."""
    cfg, tr = cell.config, cell.traffic
    sched = reference.schedule(cfg)
    pool = data.make_pool(
        seed, d=cfg["d"], n_signal=cfg["n_signal"], rho=cfg["rho"],
        signal=cfg["signal"], r=cfg["r"], n_per_machine=cfg["n_per_machine"],
        machines=cfg["machines"], machines_held=tr["mesh"]["data"],
        size=tr["pool"], lam_coef=cfg["lambda_coef"], t_coef=cfg["t_coef"])
    gaps = []
    for j in range(tr["pool"]):
        args = (pool.xs[j], pool.ys[j], pool.lam, tr["rounds"], sched)
        raws = [reference.fit(*args, p) for p in reference.references(cfg)]
        low = reference.fit(*args, reference.control(cfg))
        out = np.where(np.abs(low) > pool.t, low, 0.0)
        gaps.append(reference.beta_gap(out, raws, pool.t))
    return gaps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU: JAX's first device is {device.platform}",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    m = manifest.load(ROOT)
    cell = manifest.cell(m, ROOT, args.workload)
    with open(os.path.join(ROOT, "bench", "limits",
                           f"{args.workload}.json")) as f:
        limit = json.load(f)["beta_gap"]["limit"]
    for seed in args.seeds:
        gaps = control_gaps(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": reference.control(cell.config),
                          "beta_gap": max(gaps), "limit": limit,
                          "per_dataset": gaps,
                          "fails": max(gaps) > limit}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
