"""From a profiler trace to the numbers the per-layer metrics read.

:func:`load_xplane` reads the ``.xplane.pb`` that ``jax.profiler``
writes into plain records: per device, the executions of compiled
programs ("XLA Modules"), the operations they ran ("XLA Ops") and the
asynchronous operations in flight ("Async XLA Ops"); and the host's
spans.  A device operation's event is named by its HLO instruction;
:func:`parse_hlo` maps each instruction of the compiled program to its
opcode and its metadata ``op_name``, the JAX name stack that says which
function of the program it came from.
:func:`reduce` turns one traced window into a summary:

* per device, busy time: the union of the intervals of the operations
  that do work.  Control flow (``while``, ``conditional``, ``call``)
  only encloses other operations, so it counts for none; the time
  between the operations inside a loop is idle.  An asynchronous
  operation in flight (a prefetch, a collective) does no work of its
  own on the core either: it counts only towards the collective layer;
* per device, each layer's time: the union of the intervals of the
  operations whose name stack matches the layer's rule in
  :data:`LAYERS`;
* the operations that took most device time, and the longest idle
  gaps, each named by the deepest host span open in it.

Device and host clocks are aligned before the gaps are named: where a
program starts on the device before the host span that launched it,
the device timeline is moved later by the largest such lead.
"""

from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple


class Op(NamedTuple):
    name: str  # the HLO instruction, e.g. "fusion.12"
    start_ns: float
    dur_ns: float


class HostSpan(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float
    depth: int  # nesting on its thread; deeper spans are more specific


class DeviceTrace(NamedTuple):
    modules: list  # [Op]: one per program execution
    ops: list  # [Op]: the operations the core ran
    async_ops: list | tuple = ()  # [Op]: asynchronous operations in flight


# (layer, pattern searched in the opcode and the op_name).  The first
# rule that matches names an operation's layer.
LAYERS = (
    ("collective", re.compile(
        r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
        r"all-to-all)")),
    ("gram", re.compile(r"gram_pallas")),
    ("eigh", re.compile(r"jit\(eigh\)")),
    ("admm", re.compile(r"solve_dantzig_scan|dantzig_fused|fused_admm")),
)
CONTROL_FLOW = {"while", "conditional", "call"}

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def parse_hlo(text: str) -> dict:
    """``{instruction: (opcode, op_name)}`` of a compiled HLO module."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        rest = m.group(2)
        op = _OPCODE.search(" " + rest)
        name = _OP_NAME.search(rest)
        out[m.group(1)] = (op.group(1) if op else "",
                           name.group(1) if name else "")
    return out


def instruction(event_name: str) -> str:
    """The instruction an "XLA Ops" event is named by:
    ``"%fusion.12 = f32[...] fusion(...)"`` gives ``"fusion.12"``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def load_xplane(path: str):
    """``({device id: DeviceTrace}, [HostSpan])`` of the one
    ``.xplane.pb`` under the directory ``path``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {path}, "
                                f"found {len(files)}")
    data = ProfileData.from_file(files[0])
    devices, host = {}, []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            trace = DeviceTrace([], [], [])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    trace.modules.extend(Op(ev.name, ev.start_ns,
                                            ev.duration_ns)
                                         for ev in line.events)
                elif line.name in ("XLA Ops", "Async XLA Ops"):
                    into = (trace.ops if line.name == "XLA Ops"
                            else trace.async_ops)
                    into.extend(Op(instruction(ev.name), ev.start_ns,
                                   ev.duration_ns) for ev in line.events)
            devices[int(match.group(1))] = trace
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(nest(
                    [(ev.name, ev.start_ns, ev.duration_ns)
                     for ev in line.events]))
    return devices, host


def nest(events) -> list:
    """HostSpans of one thread's ``(name, start, duration)`` events,
    each with its depth under the spans that enclose it."""
    out, open_ends = [], []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while open_ends and open_ends[-1] <= start:
            open_ends.pop()
        out.append(HostSpan(name, start, dur, len(open_ends)))
        open_ends.append(start + dur)
    return out


def layer_of(opcode: str, op_name: str) -> str | None:
    for layer, pattern in LAYERS:
        if pattern.search(opcode) or pattern.search(op_name):
            return layer
    return None


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps_between(busy, lo, hi):
    """The gaps of the merged ``busy`` intervals inside [lo, hi]."""
    out, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def host_activity(host, at_ns: float) -> str:
    """The deepest host span open at ``at_ns``, or "host idle"."""
    best = None
    for span in host:
        if span.start_ns <= at_ns < span.start_ns + span.dur_ns:
            if best is None or span.depth > best.depth:
                best = span
    return best.name if best is not None else "host idle"


def clock_shift(modules, fit_spans) -> float:
    """How far to move a device's timeline so that no program starts
    before the host span that launched it (0 where none does)."""
    leads = [f.start_ns - m.start_ns
             for m, f in zip(sorted(modules, key=lambda m: m.start_ns),
                             fit_spans)]
    return max([0.0] + leads)


def reduce(devices: dict, host: list, fit_spans: list, hlo: dict,
           top: int = 10) -> dict:
    """The summary of the window the host spans ``fit_spans`` cover.

    ``devices`` maps a device id to its :class:`DeviceTrace`; ``hlo`` is
    :func:`parse_hlo` of the program the window ran.
    """
    fit_spans = sorted(fit_spans, key=lambda s: s.start_ns)
    lo = fit_spans[0].start_ns
    hi = max(s.start_ns + s.dur_ns for s in fit_spans)
    per_device = {}
    op_time: dict[str, float] = {}
    all_gaps = []
    for dev, trace in sorted(devices.items()):
        shift = clock_shift(trace.modules, fit_spans)
        work, layers = [], {}
        for op in trace.ops:
            opcode, op_name = hlo.get(op.name, ("", ""))
            span = clip([(op.start_ns + shift,
                          op.start_ns + shift + op.dur_ns)], lo, hi)
            if not span or opcode in CONTROL_FLOW:
                continue
            work.append(span[0])
            layer = layer_of(opcode, op_name)
            if layer is not None:
                layers.setdefault(layer, []).append(span[0])
            key = f"{opcode or op.name}: {op_name}" if op_name else op.name
            op_time[key] = op_time.get(key, 0.0) + length(span)
        for op in trace.async_ops:
            opcode, _ = hlo.get(op.name, ("", ""))
            span = clip([(op.start_ns + shift,
                          op.start_ns + shift + op.dur_ns)], lo, hi)
            if span and layer_of(opcode, "") == "collective":
                layers.setdefault("collective", []).append(span[0])
        busy = merge(work)
        per_device[dev] = {
            "busy_s": length(busy) * 1e-9,
            "idle_s": (hi - lo - length(busy)) * 1e-9,
            "clock_shift_s": shift * 1e-9,
            "layers": {k: length(merge(v)) * 1e-9 for k, v in layers.items()},
        }
        all_gaps += [(e - s, s, e) for s, e in gaps_between(busy, lo, hi)]
    all_gaps.sort(reverse=True)
    gap_time: dict[str, float] = {}
    for size, s, e in all_gaps[:top * 10]:
        what = host_activity(host, (s + e) / 2)
        gap_time[what] = gap_time.get(what, 0.0) + size * 1e-9
    n_dev = max(len(per_device), 1)
    return {
        "window_s": (hi - lo) * 1e-9,
        "fits": len(fit_spans),
        "devices": per_device,
        "breakdown": {
            "device_ops": [[k, v * 1e-9 / n_dev] for k, v in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v] for k, v in sorted(
                gap_time.items(), key=lambda kv: -kv[1])[:top]],
        },
    }
