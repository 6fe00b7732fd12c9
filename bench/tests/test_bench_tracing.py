"""The trace reduction, on hand-made events and on a recorded trace."""

import json
import os

import numpy as np
import pytest

from bench import tracing
from bench.tracing import HostSpan, Op

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_paper51_fit.json")

HLO = """\
HloModule jit_fit

%body (p: f32[4,4]) -> f32[4,4] {
  %p = f32[4,4]{1,0} parameter(0)
  ROOT %fusion.2 = f32[4,4]{1,0:T(8,128)} fusion(f32[4,4]{1,0} %p), kind=kOutput, calls=%fc, metadata={op_name="jit(fit)/jit(solve_dantzig_scan)/while/body/dot_general" source_file="x.py"}
}

ENTRY %main (x: f32[8,4]) -> f32[4] {
  %gram_pallas.1 = f32[4,4]{1,0} custom-call(f32[8,4]{1,0} %x), custom_call_target="tpu_custom_call", metadata={op_name="jit(fit)/jit(gram_pallas)/pallas_call"}
  %custom-call.2 = (f32[4]{0}, f32[4,4]{1,0}) custom-call(f32[4,4]{1,0} %gram_pallas.1), custom_call_target="EighTpu", metadata={op_name="jit(fit)/jit(eigh)/eigh"}
  %while.3 = (s32[], f32[4,4]{1,0:T(8,128)S(1)}) while((s32[], f32[4,4]) %t), condition=%c, body=%body, metadata={op_name="jit(fit)/jit(solve_dantzig_scan)/while"}
  %all-reduce.4 = f32[4]{0} all-reduce(f32[4]{0} %v), replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(fit)/shard_map/pmean"}
  %copy-start.6 = (f32[8,4]{1,0}, f32[8,4]{1,0}, u32[]) copy-start(f32[8,4]{1,0} %x)
  %all-reduce-start.7 = f32[4]{0} all-reduce-start(f32[4]{0} %v), replica_groups={{0,1}}, to_apply=%add
  ROOT %copy.5 = f32[4]{0} copy(f32[4]{0} %all-reduce.4)
}
"""


def test_parse_hlo_names_opcode_and_name_stack():
    hlo = tracing.parse_hlo(HLO)
    assert hlo["fusion.2"] == (
        "fusion", "jit(fit)/jit(solve_dantzig_scan)/while/body/dot_general")
    assert hlo["gram_pallas.1"] == ("custom-call",
                                    "jit(fit)/jit(gram_pallas)/pallas_call")
    assert hlo["while.3"][0] == "while"
    assert hlo["all-reduce.4"][0] == "all-reduce"
    assert hlo["copy.5"] == ("copy", "")
    assert tracing.instruction(
        "%fusion.2 = f32[4,4]{1,0} fusion(f32[4,4]{1,0} %p), kind=kOutput"
    ) == "fusion.2"


@pytest.mark.parametrize("opcode,op_name,layer", [
    ("custom-call", "jit(fit)/jit(gram_pallas)/pallas_call", "gram"),
    ("custom-call", "jit(fit)/jit(eigh)/eigh", "eigh"),
    ("fusion", "jit(fit)/jit(eigh)/jit(_eigh_work)/dot_general", "eigh"),
    ("fusion", "jit(fit)/jit(solve_dantzig_scan)/while/body/mul", "admm"),
    ("custom-call", "jit(fit)/jit(_dantzig_fused_jit)/pallas_call", "admm"),
    ("all-reduce", "jit(fit)/shard_map/pmean", "collective"),
    ("all-gather-start", "", "collective"),
    ("fusion", "jit(fit)/shard_map/dot_general", None),
])
def test_layer_rules(opcode, op_name, layer):
    assert tracing.layer_of(opcode, op_name) == layer


def _synthetic():
    """One fit from t=100 to t=200 on the host; the device starts it at
    t=90 on its own clock, which so reads at least 10 early."""
    ops = [
        Op("gram_pallas.1", 95, 10),  # 105-115 after the shift
        Op("custom-call.2", 110, 20),  # 120-140
        Op("while.3", 130, 60),  # control flow: no time of its own
        Op("fusion.2", 135, 10),  # 145-155
        Op("fusion.2", 150, 10),  # 160-170
        Op("all-reduce.4", 165, 10),  # 175-185
        Op("copy.5", 172, 8),  # 182-190, overlaps the all-reduce by 3
    ]
    modules = [Op("jit_fit", 90, 110)]
    in_flight = [
        Op("copy-start.6", 90, 100),  # a prefetch: no work of the core's
        Op("all-reduce-start.7", 156, 8),  # 166-174: a collective
    ]
    host = [HostSpan("bench.fit", 100, 100, 0),
            HostSpan("block_until_ready", 104, 96, 1)]
    return {0: tracing.DeviceTrace(modules, ops, in_flight)}, host


def test_reduce_synthetic():
    devices, host = _synthetic()
    s = tracing.reduce(devices, host, [host[0]], tracing.parse_hlo(HLO))
    dev = s["devices"][0]
    assert dev["clock_shift_s"] == pytest.approx(10e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    # busy: 105-115, 120-140, 145-155, 160-170, 175-190 = 65
    assert dev["busy_s"] == pytest.approx(65e-9)
    assert dev["idle_s"] == pytest.approx(35e-9)
    assert dev["layers"] == pytest.approx({
        "gram": 10e-9, "eigh": 20e-9, "admm": 20e-9, "collective": 18e-9})
    ops = dict(s["breakdown"]["device_ops"])
    assert not any(k.startswith("while") for k in ops)
    assert ops["fusion: jit(fit)/jit(solve_dantzig_scan)/while/body/"
               "dot_general"] == pytest.approx(20e-9)
    # gaps: 100-105 (bench.fit), 115-120, 140-145, 155-160, 170-175 and
    # 190-200 (block_until_ready, the deeper span)
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"block_until_ready": 30e-9,
                                  "bench.fit": 5e-9})


def test_merge_and_gaps():
    busy = tracing.merge([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert busy == [(1, 4), (5, 8)]
    assert tracing.gaps_between(busy, 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert tracing.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]


def test_nest_depths():
    spans = tracing.nest([("b", 2, 3), ("a", 0, 10), ("c", 3, 1),
                          ("d", 12, 1)])
    depth = {s.name: s.depth for s in spans}
    assert depth == {"a": 0, "b": 1, "c": 2, "d": 0}


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        fx = json.load(f)
    devices = {0: tracing.DeviceTrace([Op(*m) for m in fx["modules"]],
                                      [Op(*o) for o in fx["ops"]],
                                      [Op(*o) for o in fx["async_ops"]])}
    host = [HostSpan(*h) for h in fx["host"]]
    hlo = {k: tuple(v) for k, v in fx["hlo"].items()}
    fit = HostSpan(*fx["fit_span"])
    return devices, host, hlo, fit


def _mask(intervals, lo, hi):
    """A boolean nanosecond timeline of [lo, hi): the brute-force union."""
    mask = np.zeros(int(np.ceil(hi - lo)), bool)
    for s, e in intervals:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            mask[int(round(a - lo)):int(round(b - lo))] = True
    return mask


def test_reduce_recorded_trace(recorded):
    devices, host, hlo, fit = recorded
    s = tracing.reduce(devices, host, [fit], hlo)
    dev = s["devices"][0]
    lo, hi = fit.start_ns, fit.start_ns + fit.dur_ns
    shift = dev["clock_shift_s"] * 1e9
    module = devices[0].modules[0]
    assert module.start_ns + shift == pytest.approx(lo)  # clocks aligned
    ops = devices[0].ops
    assert devices[0].async_ops  # prefetches in flight: not busy time
    work = [(o.start_ns + shift, o.start_ns + o.dur_ns + shift) for o in ops
            if hlo[o.name][0] not in ("while", "conditional", "call")]
    busy = _mask(work, lo, hi).sum()
    assert dev["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-6)
    assert dev["busy_s"] + dev["idle_s"] == pytest.approx(s["window_s"])
    # each layer: the union of its operations' intervals
    by_name = {
        "gram": [o for o in ops if "jit(gram_pallas)" in hlo[o.name][1]],
        "eigh": [o for o in ops if "jit(eigh)" in hlo[o.name][1]
                 and hlo[o.name][0] not in ("while", "conditional")],
        "admm": [o for o in ops if "solve_dantzig_scan" in hlo[o.name][1]
                 and hlo[o.name][0] != "while"],
    }
    for layer, chosen in by_name.items():
        assert chosen, layer
        want = _mask([(o.start_ns + shift, o.start_ns + o.dur_ns + shift)
                      for o in chosen], lo, hi).sum()
        assert dev["layers"][layer] == pytest.approx(want * 1e-9, rel=1e-6)
    kernels = [o for o in by_name["gram"] if o.name.startswith("gram_pallas")]
    assert len(kernels) == 2  # one Mosaic Gram kernel per class
    assert "collective" not in dev["layers"]  # one chip: nothing crosses


def test_breakdown_recorded_trace(recorded):
    devices, host, hlo, fit = recorded
    s = tracing.reduce(devices, host, [fit], hlo)
    ops = s["breakdown"]["device_ops"]
    gaps = s["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert ops[0][0].startswith("custom-call: jit(<lambda>)/jit(eigh)")
    names = {h.name for h in host} | {"host idle"}
    assert {k for k, _ in gaps} <= names
    assert sum(v for _, v in gaps) <= s["devices"][0]["idle_s"] + 1e-12
