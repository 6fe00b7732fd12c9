"""SLDA classify-as-a-service driver (DESIGN.md §12).

``python -m repro.launch.serve --smoke`` streams synthetic two-class
(or ``--classes K``) traffic through :class:`repro.core.streaming.
ServingRuntime`: every tick serves one batched query through the jit'd
hot path, ingests one (screened) data batch into the merged sufficient
statistics, and attempts a model refresh on its schedule.  Chaos flags
drive the deterministic :class:`ServeFaultSchedule` harness::

    python -m repro.launch.serve --smoke --chaos \\
        --corrupt-ingest 0.3 --diverge-refit 0.5 --drop-refresh 0.2

``--chaos`` asserts the graceful-degradation contract inline (finite
scores always; accuracy within the slack of a fault-free run) and
exits nonzero on violation.  ``--ckpt-dir`` snapshots every publish
and ends the run with a restore parity self-check; ``--unprotected``
runs the fragile baseline (no screening, no verdict, no staleness
accounting) for side-by-side degradation demos.
"""

from __future__ import annotations

import argparse
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dantzig import DantzigConfig
from repro.core.pipeline import mc_suff_stats, suff_stats
from repro.core.streaming import (
    ServeFaultSchedule,
    ServingRuntime,
    corrupt_batch_arrays,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.stats.synthetic import (
    make_mc_problem,
    make_problem,
    sample_labeled,
    sample_mc_machines,
    sample_two_class,
)


def _binary_stream(key, problem, n_seed, n_batch, n_query):
    """(seed_aux, per-tick (batch_aux, raw_arrays, queries, labels))."""
    k_seed, k_rest = jax.random.split(key)
    x, y = sample_two_class(k_seed, problem, n_seed, n_seed)

    def tick(k):
        k1, k2 = jax.random.split(k)
        bx, by = sample_two_class(k1, problem, n_batch, n_batch)
        z, lab = sample_labeled(k2, problem, n_query)
        return (bx, by), z, lab

    return suff_stats(x, y), k_rest, tick, lambda arrs: suff_stats(*arrs)


def _mc_stream(key, problem, classes, n_seed, n_batch, n_query):
    k_seed, k_rest = jax.random.split(key)
    xs, labs = sample_mc_machines(k_seed, problem, 1, n_seed * 2)

    def tick(k):
        k1, k2 = jax.random.split(k)
        bx, blab = sample_mc_machines(k1, problem, 1, n_batch * 2)
        z, lab = sample_mc_machines(k2, problem, 1, n_query)
        return (bx[0], blab[0]), z[0], lab[0]

    return (mc_suff_stats(xs[0], labs[0], classes), k_rest, tick,
            lambda arrs: mc_suff_stats(arrs[0], arrs[1], classes))


class ServeReport(NamedTuple):
    """What one serving run saw, tick by tick."""

    served: int  # queries classified
    statuses: list  # the runtime's status at each tick
    all_finite: bool  # every served score was finite
    ladder_log: list  # every refit attempt, the initial fit included
    accuracy: float  # mean per-tick accuracy


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--d", type=int, default=60)
    ap.add_argument("--classes", type=int, default=2)
    ap.add_argument("--batch", type=int, default=256,
                    help="query batch size per tick")
    ap.add_argument("--ingest", type=int, default=60,
                    help="arriving data samples per class per tick")
    ap.add_argument("--ticks", type=int, default=24)
    ap.add_argument("--refit-every", type=int, default=2)
    ap.add_argument("--staleness-bound", type=int, default=2)
    ap.add_argument("--lam", type=float, default=0.1)
    ap.add_argument("--lam-prime", type=float, default=0.2)
    ap.add_argument("--threshold", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (overrides --d/--ticks/--batch)")
    ap.add_argument("--chaos", action="store_true",
                    help="assert the degradation contract inline")
    ap.add_argument("--acc-slack", type=float, default=0.02)
    ap.add_argument("--corrupt-ingest", type=float, default=0.0)
    ap.add_argument("--diverge-refit", type=float, default=0.0)
    ap.add_argument("--drop-refresh", type=float, default=0.0)
    ap.add_argument("--corrupt-mode", default="mix")
    ap.add_argument("--unprotected", action="store_true",
                    help="fragile baseline: no screening/verdict/staleness")
    ap.add_argument("--ckpt-dir", default=None)
    return ap


def serve(args: argparse.Namespace) -> ServeReport:
    """Drive one serving run as configured by :func:`build_parser`."""
    if args.smoke:
        args.d, args.ticks, args.batch, args.ingest = 28, 10, 128, 40

    key = jax.random.PRNGKey(args.seed)
    if args.classes == 2:
        problem = make_problem(d=args.d, n_signal=max(4, args.d // 8),
                               rho=0.5)
        aux0, key, tick_fn, stats_fn = _binary_stream(
            key, problem, 4 * args.ingest, args.ingest, args.batch)
    else:
        # rho=0.5 matches the binary stream's conditioning: the AR(1)
        # default (0.8) needs a far larger ADMM budget at tol=1e-3
        problem = make_mc_problem(d=args.d, num_classes=args.classes,
                                  n_signal=max(4, args.d // 10), rho=0.5)
        aux0, key, tick_fn, stats_fn = _mc_stream(
            key, problem, args.classes, 4 * args.ingest, args.ingest,
            args.batch)

    cfg = DantzigConfig(tol=1e-3)
    rt = ServingRuntime(
        aux0, args.lam, args.lam_prime, args.threshold, cfg=cfg,
        staleness_bound=args.staleness_bound, protect=not args.unprotected,
        ckpt_dir=args.ckpt_dir)
    plan = ServeFaultSchedule(
        args.corrupt_ingest, args.diverge_refit, args.drop_refresh,
        args.corrupt_mode, args.seed).plan(args.ticks)

    # fault-free twin for the chaos contract: same stream, no faults
    ref_acc = None
    if args.chaos:
        ref = ServingRuntime(aux0, args.lam, args.lam_prime, args.threshold,
                             cfg=cfg, staleness_bound=args.staleness_bound)

    accs, statuses, quarantined, t_classify, served = [], [], 0, 0.0, 0
    ref_accs, all_finite = [], True
    for t in range(args.ticks):
        key, kt = jax.random.split(key)
        raw, z, lab = tick_fn(kt)
        t0 = time.perf_counter()
        pred, scores = rt.classify(z)
        pred.block_until_ready()
        t_classify += time.perf_counter() - t0
        served += int(z.shape[0])
        finite = bool(np.isfinite(np.asarray(scores)).all())
        all_finite = all_finite and finite
        accs.append(float(jnp.mean(pred == lab)))
        statuses.append(rt.status)
        if args.chaos:
            ref_pred, _ = ref.classify(z)
            ref_accs.append(float(jnp.mean(ref_pred == lab)))
            if not finite:
                raise SystemExit(f"tick {t}: non-finite served scores")
        faulted = corrupt_batch_arrays(int(plan.corrupt[t]), raw)
        if not rt.ingest_batch(stats_fn(faulted), *faulted):
            quarantined += 1
        if (t + 1) % args.refit_every == 0:
            rt.refresh(drop=bool(plan.drop[t]),
                       inject_diverge=int(plan.diverge[t]))
            if args.chaos:
                ref.ingest_batch(stats_fn(raw), *raw)
                ref.refresh()

    qps = served / max(t_classify, 1e-9)
    counts = {s: statuses.count(s) for s in ("live", "stale", "degraded")}
    print(f"served {served} queries over {args.ticks} ticks "
          f"(d={args.d}, K={args.classes}, protect={not args.unprotected})")
    print(f"sustained qps (classify wall-clock only): {qps:,.0f}")
    print(f"mean accuracy: {np.mean(accs):.4f}  status counts: {counts}  "
          f"quarantined batches: {quarantined}  "
          f"model version: {int(rt.slot.version)}")
    ladder = [e["attempt"] for e in rt.ladder_log if not e["converged"]]
    if ladder:
        print(f"escalations past a failed rung: {ladder}")

    if args.chaos:
        ref_acc = float(np.mean(ref_accs))
        drop = ref_acc - float(np.mean(accs))
        print(f"fault-free twin accuracy: {ref_acc:.4f}  "
              f"(faulted run within {drop:+.4f})")
        if drop > args.acc_slack:
            raise SystemExit(
                f"degradation contract violated: accuracy dropped {drop:.4f} "
                f"> slack {args.acc_slack}")

    if args.ckpt_dir is not None:
        restored = ServingRuntime.restore(
            args.ckpt_dir, aux0, args.lam, args.lam_prime, args.threshold,
            cfg=cfg, staleness_bound=args.staleness_bound)
        key, kq = jax.random.split(key)
        _, z, lab = tick_fn(kq)
        p_live, _ = rt.classify(z)
        p_rest, _ = restored.classify(z)
        if int(restored.slot.version) == int(rt.slot.version) and not bool(
                jnp.all(p_live == p_rest)):
            raise SystemExit("restore parity violated: same slot version, "
                             "different predictions")
        print(f"checkpoint restore OK (version {int(restored.slot.version)})")
    return ServeReport(served, statuses, all_finite, list(rt.ladder_log),
                       float(np.mean(accs)))


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    serve(args)


if __name__ == "__main__":
    main()
