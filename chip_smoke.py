#!/usr/bin/env python3
"""Drive the distributed sparse-LDA fit and the serving runtime on a TPU.

    python chip_smoke.py [--seed S]              # one chip
    python chip_smoke.py --chips 4 [--seed S]    # the mesh path on four

One chip (``jax.devices()[0]`` only, however many the host has):

* fit: the paper's §5.1 design (d=200, AR(0.8), 10 signal coordinates,
  N=10,000) through ``simulated_distributed_slda`` at m=10, one-shot
  and T=3 rounds, and the centralized fit through
  ``distributed_slda_shardmap`` on a 1x1 mesh, each with the scan
  solver and with the fused ADMM kernel;
* high-d worker: d=1024, n=512 per machine, m=4, fused kernel;
* serving: ``repro.launch.serve`` at d=200 with 2048-query batches,
  1,000 arriving samples on every tick and a refit every second tick.

``--chips 4`` runs only ``distributed_slda_shardmap`` on a (data=4,
model=1) mesh, one-shot and T=3, and on a (data=2, model=2) mesh
one-shot, each against ``simulated_distributed_slda`` over the same
machines (one machine per data-axis slice: m=4, then m=2).

Every fit is checked against the plain float32 reference -- the same
vmapped simulation with the gram kernel off and the scan solver, at
"highest" matmul precision -- on support F1 against beta* and on the
relative l2 gap, within the limits below.  Each compiled fit must hold
a Mosaic kernel (``tpu_custom_call``): the gram kernel always, the
fused ADMM kernel when the dispatch selected it.  Any failed check
raises.  The last line of stdout is a JSON record of the device, and
it is printed only when every phase passed.  Without a TPU the script
exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.paper_synthetic import SYNTHETIC  # noqa: E402
from repro.core import classifier  # noqa: E402
from repro.core.dantzig import DantzigConfig  # noqa: E402
from repro.core.distributed import (  # noqa: E402
    distributed_slda_shardmap,
    simulated_distributed_slda,
)
from repro.core.pipeline import BinaryHead  # noqa: E402
from repro.core.rounds import simulate_multi_round  # noqa: E402
from repro.core.slda import hard_threshold  # noqa: E402
from repro.core.solver_dispatch import select_solver  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.stats import synthetic  # noqa: E402

# Limits against the highest-precision reference, which runs the same
# ADMM schedule (the fused kernel's fixed penalty where the fit uses it).
# What is left is f32 rounding through 600 iterations, the gram and
# debias products at the backend's default precision, and the hard
# threshold keeping or dropping a coefficient that sits near t.
F1_SLACK = 0.05  # support F1 may trail the reference's by this much
L2_GAP = 0.02  # ||fit - ref||_2 / ||ref||_2
MESH_ATOL = 1e-5  # mesh vs simulation: the same arithmetic on each machine


def _log(msg: str) -> None:
    print(msg, flush=True)


def _problem_data(seed, d, m, n_per_machine):
    """§5.1 design at dimension d; m machines of n_per_machine samples."""
    problem = synthetic.make_problem(d=d, n_signal=SYNTHETIC.n_signal,
                                     rho=SYNTHETIC.rho)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), d)
    n1 = int(n_per_machine * SYNTHETIC.r)
    xs, ys = synthetic.sample_machines(key, problem, m, n1,
                                       n_per_machine - n1)
    b1 = float(jnp.sum(jnp.abs(problem.beta_star)))
    # the benchmarks' lambda rule (fig1_machines) at the local sample size
    lam = 0.30 * math.sqrt(math.log(d) / n_per_machine) * b1
    # the threshold at the same rate over all N samples; 0.75 maximizes
    # the one-shot reference's F1 at the §5.1 design
    t = 0.75 * math.sqrt(math.log(d) / (m * n_per_machine)) * b1
    return problem, xs, ys, lam, t


def _reference(xs, ys, lam, t, rounds, cfg):
    """Plain f32 reference for a fit run with ``cfg``: the vmapped
    simulation with the gram kernel off and the scan solver, running the
    fit's ADMM schedule (a fixed penalty where ``cfg`` fuses)."""
    cfg = cfg._replace(fused=False, adapt_rho=cfg.adapt_rho and not cfg.fused)

    def fit(xs, ys, lam, t):
        beta, _ = simulate_multi_round(
            BinaryHead(use_kernel=False), (xs, ys), lam=lam, lam_prime=lam,
            rounds=rounds, cfg=cfg)
        return hard_threshold(beta[:, 0], t)

    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(jax.jit(fit)(xs, ys, lam, t))


def _run_fit(name, fn, args, *, ref, beta_star, fused, solver):
    """Compile, run and check one fit; prints one line per fit."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    beta = jax.block_until_ready(compiled(*args))
    t_run = time.perf_counter() - t0
    text = compiled.as_text()
    kernels = text.count("tpu_custom_call")
    f1 = float(classifier.f1_score(beta, beta_star))
    f1_ref = float(classifier.f1_score(ref, beta_star))
    gap = float(jnp.linalg.norm(beta - ref)
                / jnp.maximum(jnp.linalg.norm(ref), 1e-30))
    _log(f"[fit] {name}: solver={solver} F1={f1:.4f} (ref {f1_ref:.4f}) "
         f"l2_gap={gap:.3e} mosaic_kernels={kernels} "
         f"compile_s={t_compile:.2f} run_s={t_run:.3f}")
    if not bool(jnp.all(jnp.isfinite(beta))):
        raise AssertionError(f"{name}: non-finite estimate")
    if kernels == 0:
        raise AssertionError(f"{name}: no Mosaic kernel in the compiled fit "
                             "(the gram kernel fell back or was skipped)")
    if fused and "fused_admm" not in text:
        raise AssertionError(f"{name}: the fused ADMM kernel was selected "
                             "but is not in the compiled fit")
    if f1 < f1_ref - F1_SLACK:
        raise AssertionError(f"{name}: F1 {f1:.4f} < reference {f1_ref:.4f} "
                             f"- {F1_SLACK}")
    if gap > L2_GAP:
        raise AssertionError(f"{name}: l2 gap {gap:.3e} > {L2_GAP}")


def _solver_path(cfg, d):
    """The path dispatch picks for the d-column CLIME batch."""
    choice = select_solver(cfg, d, d)
    return f"{choice.kind}" + (f"/{choice.block_k}" if choice.block_k else "")


def fit_phase(device, seed):
    """§5.1: m=10 simulation (T=1, 3) and the centralized 1x1 mesh fit."""
    d, m = SYNTHETIC.d, 10
    problem, xs, ys, lam, t = _problem_data(seed, d, m, SYNTHETIC.N // m)
    mesh = Mesh(np.array([device]).reshape(1, 1), ("data", "model"))
    x_all, y_all = xs.reshape(-1, d), ys.reshape(-1, d)
    lam_c = lam * math.sqrt(1.0 / m)  # the same rule at n = N
    for fused in (False, True):
        cfg = DantzigConfig(fused=fused)
        solver = _solver_path(cfg, d)
        for rounds in (1, 3):
            _run_fit(
                f"sim m={m} T={rounds} fused={fused}",
                lambda xs, ys, lam, t, cfg=cfg, r=rounds:
                    simulated_distributed_slda(xs, ys, lam, lam, t, cfg, r),
                (xs, ys, lam, t),
                ref=_reference(xs, ys, lam, t, rounds, cfg),
                beta_star=problem.beta_star, fused=fused, solver=solver)
        _run_fit(
            f"centralized mesh 1x1 fused={fused}",
            lambda x, y, lam, t, cfg=cfg: distributed_slda_shardmap(
                mesh, x, y, lam, lam, t, cfg),
            (x_all, y_all, lam_c, t),
            ref=_reference(x_all[None], y_all[None], lam_c, t, 1, cfg),
            beta_star=problem.beta_star, fused=fused, solver=solver)


def high_d_phase(seed):
    """p >> n: d=1024, n=512 per machine over m=4, fused kernel."""
    d, m, n = 1024, 4, 512
    problem, xs, ys, lam, t = _problem_data(seed, d, m, n)
    cfg = DantzigConfig(fused=True)
    _run_fit(
        f"high-d d={d} n={n} m={m} fused=True",
        lambda xs, ys, lam, t: simulated_distributed_slda(
            xs, ys, lam, lam, t, cfg),
        (xs, ys, lam, t), ref=_reference(xs, ys, lam, t, 1, cfg),
        beta_star=problem.beta_star, fused=True, solver=_solver_path(cfg, d))


def serving_phase():
    """The serving loop of ``repro.launch.serve`` at d=200."""
    args = serve.build_parser().parse_args(
        ["--d", "200", "--batch", "2048", "--ingest", "500", "--ticks", "6",
         "--refit-every", "2"])
    t0 = time.perf_counter()
    report = serve.serve(args)
    seconds = time.perf_counter() - t0
    refits = len(report.ladder_log) - 1  # the first entry is the initial fit
    _log(f"[serve] d=200 batch=2048 ingest=1000: served={report.served} "
         f"statuses={sorted(set(report.statuses))} refits={refits} "
         f"attempts={[e['attempt'] for e in report.ladder_log]} "
         f"accuracy={report.accuracy:.4f} seconds={seconds:.2f}")
    if set(report.statuses) != {"live"}:
        raise AssertionError(f"serving left 'live': {report.statuses}")
    if not report.all_finite:
        raise AssertionError("serving returned non-finite scores")
    if not all(e["converged"] for e in report.ladder_log):
        raise AssertionError(f"a refit escalated past its first rung: "
                             f"{report.ladder_log}")
    if refits < 2:
        raise AssertionError(f"only {refits} refits published")


def mesh_phase(devices, seed):
    """The mesh path on four chips against the vmapped simulation."""
    d, cfg = SYNTHETIC.d, DantzigConfig()
    for data, model, rounds in ((4, 1, 1), (4, 1, 3), (2, 2, 1)):
        m = data  # one machine per data-axis slice
        _, xs, ys, lam, t = _problem_data(seed, d, m, SYNTHETIC.N // m)
        mesh = Mesh(np.array(devices).reshape(data, model), ("data", "model"))
        rows = NamedSharding(mesh, P("data", None))
        x = jax.device_put(xs.reshape(-1, d), rows)
        y = jax.device_put(ys.reshape(-1, d), rows)
        # each machine's rows sit whole on the devices of its data slice
        slice_of = {dev: i for i, row in enumerate(mesh.devices)
                    for dev in row}
        for shard in x.addressable_shards:
            i = slice_of[shard.device]
            if not bool(jnp.array_equal(shard.data, xs[i])):
                raise AssertionError(f"machine {i}'s rows are not on "
                                     f"{shard.device}")
        fit = jax.jit(lambda x, y, lam, t: distributed_slda_shardmap(
            mesh, x, y, lam, lam, t, cfg, rounds=rounds))
        t0 = time.perf_counter()
        compiled = fit.lower(x, y, lam, t).compile()
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        beta = jax.block_until_ready(compiled(x, y, lam, t))
        t_run = time.perf_counter() - t0
        used = compiled.input_shardings[0][0].device_set
        if used != set(devices):
            raise AssertionError(f"the mesh fit ran on devices {used}")
        sim = jax.block_until_ready(simulated_distributed_slda(
            jax.device_put(xs, devices[0]), jax.device_put(ys, devices[0]),
            lam, lam, t, cfg, rounds))
        diff = float(jnp.max(jnp.abs(jax.device_put(beta, devices[0]) - sim)))
        _log(f"[mesh] data={data} model={model} m={m} T={rounds}: "
             f"max|mesh - sim|={diff:.3e} compile_s={t_compile:.2f} "
             f"run_s={t_run:.3f} nnz={int(jnp.sum(beta != 0))}")
        if not diff <= MESH_ATOL:
            raise AssertionError(f"mesh vs simulation {diff:.3e} > "
                                 f"{MESH_ATOL}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {device.platform}")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, "
                         f"found {len(devices)}")
    _log(f"cache={enable_compile_cache()} device={device.device_kind} "
         f"chips={args.chips} seed={args.seed}")
    if args.chips == 1:
        with jax.default_device(device):
            fit_phase(device, args.seed)
            high_d_phase(args.seed)
            serving_phase()
    else:
        mesh_phase(devices[:args.chips], args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": args.chips}}))


if __name__ == "__main__":
    main()
