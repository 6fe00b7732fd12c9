"""The program's own layer names, read from a profiler trace.

The fit names its layers with ``jax.named_scope("slda.<scope>")``:
``stats``, ``spectral``, ``direction``, ``clime``, ``debias`` and
``aggregate``.  JAX writes the scope into the ``op_name`` of every
operation under it (a transform wraps the scope it maps, as in
``vmap(slda.clime)/jit(solve_dantzig_scan)``), so a device operation's
scope is read as :mod:`bench.tracing` reads its layer.  Scopes may
nest (the rounds' ``slda.aggregate`` holds each round's
``slda.debias``): an operation belongs to its innermost scope.  :func:`reduce` gives, per device, over the window the host
spans ``fit_spans`` cover, with the device timeline moved as
:func:`bench.tracing.clock_shift` moves it:

* ``scopes``: ``{scope: busy seconds}``, the union of the clipped
  intervals of the operations that carry the scope.  Control flow
  counts for none, and an asynchronous collective counts for its scope
  as it counts for the collective layer;
* ``scoped_s``: the union of the intervals of the operations that do
  work and carry a scope, the part of the busy time the scopes cover;
* ``program_idle_s``: the idle time inside the program's executions
  ("XLA Modules"), the device's idle time less the gaps between
  executions, which the host leaves;
* ``scope_idle``: ``{scope: seconds}``, the part of ``program_idle_s``
  in gaps whose nearest operations on either side, in the same
  execution, carry that one scope: the loop and latency overhead inside
  a layer.  The rest of ``program_idle_s`` lies between layers.  An
  operation that carries no scope (a copy XLA adds) is passed over.

:func:`per_fit_ms` turns that into each scope's milliseconds per fit on
the device that spends the most, which is what ``bench/layers.py``
prints.
"""

from __future__ import annotations

import bisect
import re

from bench import tracing

SCOPES = ("stats", "spectral", "direction", "clime", "debias", "aggregate")
SCOPE = re.compile(r"(?:^|[/(])slda\.([a-z_]+)(?=[/)]|$)")


def scope_of(op_name: str) -> str | None:
    """The innermost ``slda.*`` scope of an ``op_name``, or None."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def _shifted(op, shift, lo, hi):
    return tracing.clip([(op.start_ns + shift,
                          op.start_ns + shift + op.dur_ns)], lo, hi)


def program_idle(busy, programs, scoped) -> tuple[float, dict]:
    """The idle time inside the merged intervals ``programs``, and
    ``{scope: part of it}`` in gaps whose nearest scoped operations on
    either side, inside the same interval, carry that scope.  ``busy``
    is the merged work; ``scoped`` holds ``(start, end, scope)``."""
    by_end = sorted((e, sc) for _, e, sc in scoped)
    by_start = sorted((s, sc) for s, _, sc in scoped)
    ends = [e for e, _ in by_end]
    starts = [s for s, _ in by_start]
    idle, per_scope = 0.0, {}
    for lo, hi in programs:
        for g0, g1 in tracing.gaps_between(tracing.clip(busy, lo, hi),
                                           lo, hi):
            idle += g1 - g0
            i = bisect.bisect_right(ends, g0) - 1
            j = bisect.bisect_left(starts, g1)
            if (i >= 0 and j < len(starts) and ends[i] >= lo
                    and starts[j] <= hi and by_end[i][1] == by_start[j][1]):
                scope = by_end[i][1]
                per_scope[scope] = per_scope.get(scope, 0.0) + g1 - g0
    return idle, per_scope


def reduce(devices: dict, fit_spans: list, hlo: dict) -> dict:
    """``{device: {"scopes", "scoped_s", "program_idle_s", "scope_idle"}}``
    of the window the host spans ``fit_spans`` cover (seconds)."""
    fit_spans = sorted(fit_spans, key=lambda s: s.start_ns)
    lo = fit_spans[0].start_ns
    hi = max(s.start_ns + s.dur_ns for s in fit_spans)
    out = {}
    for dev, trace in sorted(devices.items()):
        shift = tracing.clock_shift(trace.modules, fit_spans)
        work, spans, scoped = [], {}, []
        for op in trace.ops:
            opcode, op_name = hlo.get(op.name, ("", ""))
            span = _shifted(op, shift, lo, hi)
            if not span or opcode in tracing.CONTROL_FLOW:
                continue
            work.append(span[0])
            scope = scope_of(op_name)
            if scope is not None:
                spans.setdefault(scope, []).append(span[0])
                scoped.append(span[0] + (scope,))
        for op in trace.async_ops:
            opcode, op_name = hlo.get(op.name, ("", ""))
            span = _shifted(op, shift, lo, hi)
            scope = scope_of(op_name)
            if (span and scope is not None
                    and tracing.layer_of(opcode, "") == "collective"):
                spans.setdefault(scope, []).append(span[0])
        programs = tracing.merge(
            span[0] for m in trace.modules
            if (span := _shifted(m, shift, lo, hi)))
        idle, scope_idle = program_idle(tracing.merge(work), programs,
                                        scoped)
        out[dev] = {
            "scopes": {k: tracing.length(tracing.merge(v)) * 1e-9
                       for k, v in spans.items()},
            "scoped_s": tracing.length(tracing.merge(
                (s, e) for s, e, _ in scoped)) * 1e-9,
            "program_idle_s": idle * 1e-9,
            "scope_idle": {k: v * 1e-9 for k, v in scope_idle.items()},
        }
    return out


def per_fit_ms(reduced: dict, fits: int) -> dict:
    """Each scope's time, ``program_idle`` and each ``idle.<scope>`` in
    milliseconds per fit, on the device that spends the most on it; a
    scope no device ran is left out."""
    out = {}
    for scope in SCOPES:
        for key, part in ((scope, "scopes"), (f"idle.{scope}", "scope_idle")):
            times = [d[part][scope] for d in reduced.values()
                     if scope in d[part]]
            if times:
                out[key] = 1e3 * max(times) / fits
    if reduced:
        out["program_idle"] = 1e3 * max(
            d["program_idle_s"] for d in reduced.values()) / fits
    return out
