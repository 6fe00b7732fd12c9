"""A copy of the benchmark at a size the CPU test run can hold.

The cells keep their names, traffic, solver schedule and limits; only
each configuration's dimension and sample sizes shrink.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SIZES = {
    "highd_d1024_m4": dict(d=16, n_per_machine=64, N=256),
    "paper51_m10": dict(d=12, n_per_machine=40, N=400),
}


def make_root(path: str) -> str:
    """``BENCHMARK.json`` and ``bench/`` under ``path``, at tiny sizes."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), path)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, sizes in SIZES.items():
        file = os.path.join(path, "bench", "configs", f"{name}.json")
        with open(file) as f:
            config = json.load(f)
        config.update(sizes)
        with open(file, "w") as f:
            json.dump(config, f)
    return path
