#!/usr/bin/env python3
"""Trace the first fits of one cell and print each of the program's
layers, as its own scopes name them, per fit.

    python3 bench/layers.py --workload <cell> --seed <n>
                            [--record <fixture.json>]

From the root of a checkout, on the chips the cell asks for.  The cell
is built as ``bench/run.py`` builds it (same data, compile cache and
closed loop, the first fits traced the same way), but nothing is
compared with the reference, so a run takes about as long as its
set-up.  The last line of standard output is one JSON object: per fit
on the busiest device, in ms, the window, busy time and idle time, the
layers of :mod:`bench.tracing` (read from jit names and opcodes) and
the scopes of :mod:`bench.scopes` (read from the program's own
``slda.*`` names), with ``program_idle`` and each scope's share of it
(``idle.<scope>``), and the least share of a device's busy time that
carries a scope.  On a program that names no scope only the former are
there.

``--record`` also writes the second traced fit in the form of
``bench/tests/fixtures/trace_paper51_fit.json``, for the reduction's
tests.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import manifest, run, scopes, tracing  # noqa: E402

# The closed loop's length: the traced fits (run.TRACE_MIN_FITS and
# run.TRACE_MIN_S) take under a second in every cell, the three of
# d=1,024 at ~0.26 s each on a v5e; the untraced fits after them are
# not read.
WINDOW_S = 1.0


def fixture(devices, host, spans, hlo, what: str) -> dict:
    """One fit (the second traced) of device 0 in the fixture's form."""
    fit = sorted(spans, key=lambda s: s.start_ns)[min(1, len(spans) - 1)]
    lo, hi = fit.start_ns, fit.start_ns + fit.dur_ns
    trace = devices[min(devices)]
    shift = tracing.clock_shift(trace.modules, [fit])
    modules = [m for m in trace.modules
               if m.start_ns + shift < hi and m.start_ns + m.dur_ns + shift
               > lo]
    first = min(m.start_ns for m in modules)
    last = max(m.start_ns + m.dur_ns for m in modules)

    def inside(ops):
        return [list(o) for o in ops
                if o.start_ns < last and o.start_ns + o.dur_ns > first]

    ops, async_ops = inside(trace.ops), inside(trace.async_ops)
    names = {o[0] for o in ops + async_ops}
    return {
        "what": what,
        "hlo": {k: list(v) for k, v in hlo.items() if k in names},
        "modules": [list(m) for m in modules],
        "ops": ops,
        "async_ops": async_ops,
        "host": [list(h) for h in host
                 if h.start_ns < hi and h.start_ns + h.dur_ns > lo],
        "fit_span": list(fit),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)

    m = manifest.load(ROOT)
    cell = manifest.cell(m, ROOT, args.workload)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".tpu_logs"))
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} TPU chips, found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    run.enable_compile_cache(ROOT)
    setup = run.Setup(cell, args.seed, devices)
    _, _, _, _, traced = run.window(setup, WINDOW_S, trace=True)
    hlo = tracing.parse_hlo(setup.hlo_text())
    found, host = tracing.load_xplane(run.TRACE_DIR)
    shutil.rmtree(run.TRACE_DIR, ignore_errors=True)
    used = {d.id for d in setup.devices}
    found = {k: v for k, v in found.items() if k in used}
    spans = [s for s in host if s.name == "bench.fit"]
    summary = tracing.reduce(found, host, spans, hlo)
    reduced = scopes.reduce(found, spans, hlo)
    fits = summary["fits"]
    devs = summary["devices"].values()
    out = {
        "workload": args.workload, "seed": args.seed, "fits": fits,
        "traced": traced, "device": devices[0].device_kind,
        "window": 1e3 * summary["window_s"] / fits,
        "busy": 1e3 * max(d["busy_s"] for d in devs) / fits,
        "idle": 1e3 * max(d["idle_s"] for d in devs) / fits,
        "layers": {k: 1e3 * max(d["layers"].get(k, 0.0) for d in devs) / fits
                   for k in {k for d in devs for k in d["layers"]}},
        "scopes": scopes.per_fit_ms(reduced, fits),
        "scoped_share": min(reduced[k]["scoped_s"] / d["busy_s"]
                            for k, d in summary["devices"].items()),
    }
    if args.record:
        what = (f"one fit of the {args.workload} cell on a "
                f"{devices[0].device_kind} (jax {jax.__version__}), from "
                f"jax.profiler via bench/layers.py --record, seed "
                f"{args.seed}: the program execution, every device "
                f"operation it ran, its asynchronous operations and the "
                f"host spans open during the fit")
        with open(args.record, "w") as f:
            json.dump(fixture(found, host, spans, hlo, what), f,
                      separators=(",", ":"))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
