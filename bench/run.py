#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The run loads the cell's files by name,
checks that JAX's first device is a TPU and that there are as many as
the cell asks for, keeps JAX's compilation cache in ``.jax_cache/`` at
the root of the checkout (or where ``JAX_COMPILATION_CACHE_DIR``
says), draws the cell's pool of datasets on the device from the seed,
compiles and warms the fit on every dataset, and then fits in a closed
loop for ``--seconds``: one fit in flight, each timed from dispatch to
``block_until_ready``, the datasets taken in turn.  After the window it
reads the peak device memory, computes the plain reference
(:mod:`bench.reference`) on every dataset the window fitted and compares
every fit with it.  ``--trace 1`` also traces the first fits of the
window and reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are also the last lines of standard
error.  Without a TPU, or with too few, it exits non-zero first.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from bench import data, manifest, peaks, reference, tracing, work  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_MIN_S = 0.05  # the traced part of the window: at least this long
TRACE_MIN_FITS = 3  # ... and at least this many fits


def info(msg: str) -> None:
    print(f"# {msg}", flush=True)


class Setup:
    """Everything a run builds before its window."""

    def __init__(self, cell: manifest.Cell, seed: int, devices):
        from repro.core.dantzig import DantzigConfig
        from repro.core.distributed import distributed_slda_shardmap

        cfg, tr = cell.config, cell.traffic
        self.config = cfg
        data_n, model_n = tr["mesh"]["data"], tr["mesh"]["model"]
        if data_n * model_n != cell.chips:
            raise ValueError(f"{cell.name}: a {data_n}x{model_n} mesh on "
                             f"{cell.chips} chips")
        held = data_n  # one machine per data slice
        if held > cfg.get("machines_held", cfg["machines"]):
            raise ValueError(f"{cell.name}: {held} machines, but the "
                             f"configuration holds at most "
                             f"{cfg.get('machines_held', cfg['machines'])}")
        self.devices = list(devices[:cell.chips])
        self.mesh = Mesh(np.array(self.devices).reshape(data_n, model_n),
                         ("data", "model"))
        self.held, self.rounds, self.d = held, tr["rounds"], cfg["d"]
        self.n = cfg["n_per_machine"]
        self.sched = reference.schedule(cfg)
        default = DantzigConfig()
        if default.tol is not None or any(
                getattr(default, k) != v
                for k, v in self.sched._asdict().items()):
            raise ValueError(f"{cell.name}: the program's default "
                             f"DantzigConfig() departs from the fixed ADMM "
                             f"schedule the configuration states")
        split = {}

        t0 = time.perf_counter()
        with jax.default_device(self.devices[0]):
            self.pool = data.make_pool(
                seed, d=cfg["d"], n_signal=cfg["n_signal"], rho=cfg["rho"],
                signal=cfg["signal"], r=cfg["r"], n_per_machine=self.n,
                machines=cfg["machines"], machines_held=held, size=tr["pool"],
                lam_coef=cfg["lambda_coef"], t_coef=cfg["t_coef"])
        rows = NamedSharding(self.mesh, P("data", None))
        scalar = NamedSharding(self.mesh, P())
        self.inputs = [
            (jax.device_put(self.pool.xs[j].reshape(-1, self.d), rows),
             jax.device_put(self.pool.ys[j].reshape(-1, self.d), rows))
            for j in range(tr["pool"])]
        self.lam = jax.device_put(jnp.float32(self.pool.lam), scalar)
        self.t = jax.device_put(jnp.float32(self.pool.t), scalar)
        jax.block_until_ready((self.inputs, self.pool.xs, self.pool.ys))
        split["data"] = time.perf_counter() - t0

        mesh, rounds = self.mesh, self.rounds
        self.fit = jax.jit(lambda x, y, lam, t: distributed_slda_shardmap(
            mesh, x, y, lam, lam, t, DantzigConfig(), rounds=rounds))
        t0 = time.perf_counter()
        jax.block_until_ready(self.fit(*self.inputs[0], self.lam, self.t))
        split["compile"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        for x, y in self.inputs[1:]:
            jax.block_until_ready(self.fit(x, y, self.lam, self.t))
        split["warm"] = time.perf_counter() - t0
        self.split = split

    def call(self, j: int):
        return self.fit(*self.inputs[j], self.lam, self.t)

    def compiled_programs(self) -> int:
        return self.fit._cache_size()

    def hlo_text(self) -> str:
        """The compiled fit's HLO (the persistent cache holds it)."""
        return self.fit.lower(*self.inputs[0], self.lam,
                              self.t).compile().as_text()


def window(setup: Setup, seconds: float, trace: bool):
    """The closed loop: ``(outs, pool indices, fit seconds, window
    seconds, fits traced or None)``."""
    outs, idx, times = [], [], []
    n = len(setup.inputs)
    traced = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    start = time.perf_counter()
    deadline = start + seconds
    end = t0 = start
    while t0 < deadline:
        j = len(outs) % n
        with jax.profiler.TraceAnnotation("bench.fit"):
            out = setup.call(j)
            out.block_until_ready()
        end = time.perf_counter()
        outs.append(out)
        idx.append(j)
        times.append(end - t0)
        if trace and traced is None and (
                end - start >= TRACE_MIN_S and len(outs) >= TRACE_MIN_FITS):
            jax.profiler.stop_trace()
            traced = len(outs)
        t0 = end
    if trace and traced is None:
        jax.profiler.stop_trace()
        traced = len(outs)
    return outs, idx, times, end - start, traced


def memory_peak(devices) -> int | None:
    """The peak bytes in use on the fullest device, where JAX reports it."""
    used = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    used = [u for u in used if u is not None]
    return max(used) if used else None


def compare(setup: Setup, outs, idx, limits: dict):
    """Every fit of the window against the references on its dataset:
    ``(numbers compared, failed fits, reference seconds)``."""
    t0 = time.perf_counter()
    raws = {}
    with jax.default_device(setup.devices[0]):
        for j in sorted(set(idx)):
            raws[j] = [reference.fit(setup.pool.xs[j], setup.pool.ys[j],
                                     setup.pool.lam, setup.rounds,
                                     setup.sched, precision)
                       for precision in reference.references(setup.config)]
    seconds = time.perf_counter() - t0
    limit = limits["beta_gap"]["limit"]
    gaps = []
    failed = 0
    for out, j in zip(jax.device_get(outs), idx):
        beta = np.asarray(out, np.float64)
        gap = (reference.beta_gap(beta, raws[j], setup.pool.t)
               if np.all(np.isfinite(beta)) else math.inf)
        gaps.append(gap)
        failed += not gap <= limit
    compared = {"beta_gap": {"value": max(gaps) if gaps else math.inf,
                             "limit": limit}}
    return compared, failed, seconds


def per_layer_summary(setup: Setup, kind: str, hlo_text: str) -> dict:
    """The reduced trace with what the metric readers need beside it."""
    devices, host = tracing.load_xplane(TRACE_DIR)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    used = {d.id for d in setup.devices}
    devices = {k: v for k, v in devices.items() if k in used}
    spans = [s for s in host if s.name == "bench.fit"]
    summary = tracing.reduce(devices, host, spans,
                             tracing.parse_hlo(hlo_text))
    iters = setup.sched.max_iters
    summary.update(
        chips=len(setup.devices),
        machines_per_chip=setup.held / len(setup.devices),
        peaks=peaks.peaks(kind),
        admm_work=work.admm(setup.d, iters),
        fit_work=work.fit(setup.n, setup.d, iters, setup.rounds))
    return summary


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             devices) -> dict:
    """Everything of one run after the look for the chips: the result."""
    m = manifest.load(root)
    cell = manifest.cell(m, root, name)
    with open(os.path.join(root, "bench", "limits", f"{name}.json")) as f:
        limits = json.load(f)
    kind = devices[0].device_kind
    if trace:
        peaks.peaks(kind)  # an unknown device fails before the window
    setup = Setup(cell, seed, devices)
    setup_s = time.perf_counter() - T_START
    split = dict(setup.split, start=setup_s - sum(setup.split.values()))
    info("setup_split_s " + json.dumps(split))
    programs = setup.compiled_programs()
    # what set-up built lives on: keep the collector's full passes off it
    gc.collect()
    gc.freeze()
    outs, idx, times, window_s, traced = window(setup, seconds, trace)
    gc.unfreeze()
    if setup.compiled_programs() != programs:
        raise RuntimeError("the fit compiled inside the window")
    mem = memory_peak(setup.devices)
    compared, failed, ref_s = compare(setup, outs, idx, limits)
    info(f"fits {len(outs)} in {window_s:.6f} s; reference {ref_s:.3f} s")
    slowest = sorted(range(len(times)), key=lambda i: -times[i])[:5]
    info("fit_times_s " + json.dumps({
        "min": min(times), "median": statistics.median(times),
        "max": max(times), "slowest": [[i, times[i]] for i in slowest]}))
    dev = {"platform": devices[0].platform, "kind": kind,
           "count": len(setup.devices), "memory_peak_bytes": mem}
    metrics = {}
    result = {"correct": failed == 0 and len(outs) > 0,
              "attempted": len(outs), "failed": failed}
    if not trace:
        values = {"setup_s": setup_s,
                  "fit_s": window_s / len(outs)}
        if len(times) >= 20:
            values["fit_p95_s"] = statistics.quantiles(times, n=20)[18]
            info(f"fit_p95_s over {len(times)} fits")
        for e in cell.end_to_end:
            if e["name"] in values:
                metrics[e["name"]] = {"value": values[e["name"]],
                                      "unit": e["unit"]}
    else:
        hlo_text = setup.hlo_text()
        kernels = hlo_text.count('custom_call_target="tpu_custom_call"')
        info(f"solver {select_solver_kind(setup)} tpu_custom_call {kernels} "
             f"fits_traced {traced}")
        summary = per_layer_summary(setup, kind, hlo_text)
        busy = [v["busy_s"] for v in summary["devices"].values()]
        dev["busy_s"] = sum(busy) / len(busy)
        dev["window_s"] = summary["window_s"]
        info("layers_s " + json.dumps(
            {k: v["layers"] for k, v in summary["devices"].items()}))
        for e in cell.per_layer:
            value = manifest.load_reader(root, e["name"])(summary)
            if value is not None:
                metrics[e["name"]] = {"value": value, "unit": e["unit"]}
        result["breakdown"] = summary["breakdown"]
    result["metrics"] = metrics
    result["device"] = dev
    result["compared"] = compared
    return result


def select_solver_kind(setup: Setup) -> str:
    """The path the program's dispatch picks for the CLIME batch."""
    from repro.core.dantzig import DantzigConfig
    from repro.core.solver_dispatch import select_solver

    choice = select_solver(DantzigConfig(), setup.d, setup.d)
    return choice.kind + (f"/{choice.block_k}" if choice.block_k else "")


def enable_compile_cache(root: str) -> str:
    configured = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = configured or os.path.join(root, ".jax_cache")
    if not configured:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    m = manifest.load(ROOT)
    chips = manifest.cell(m, ROOT, args.workload).chips
    # the TPU runtime logs under /tmp unless told otherwise: keep its
    # logs inside the checkout (read when the backend starts, below)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".tpu_logs"))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's first device is {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"{args.workload} needs {chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    info(f"cache {enable_compile_cache(ROOT)} device {devices[0].device_kind}"
         f" x{len(devices)} seed {args.seed}")
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices)
    for key, c in result["compared"].items():
        print(f"compared {key} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
