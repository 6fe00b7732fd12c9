"""The program's own layer scopes, read from hand-made and recorded
traces."""

import json
import os

import numpy as np
import pytest

from bench import scopes, tracing
from bench.tracing import HostSpan, Op

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")

HLO = """\
HloModule jit_fit

%body (p: f32[4,4]) -> f32[4,4] {
  %p = f32[4,4]{1,0} parameter(0)
  ROOT %fusion.4 = f32[4,4]{1,0} fusion(f32[4,4]{1,0} %p), kind=kOutput, calls=%fc, metadata={op_name="jit(fit)/slda.clime/jit(solve_dantzig_scan)/while/body/dot_general"}
}

ENTRY %main (x: f32[8,4]) -> f32[4] {
  %gram_pallas.1 = f32[4,4]{1,0} custom-call(f32[8,4]{1,0} %x), custom_call_target="tpu_custom_call", metadata={op_name="jit(fit)/slda.stats/jit(gram_pallas)/pallas_call"}
  %custom-call.2 = (f32[4]{0}, f32[4,4]{1,0}) custom-call(f32[4,4]{1,0} %gram_pallas.1), custom_call_target="EighTpu", metadata={op_name="jit(fit)/slda.spectral/jit(eigh)/eigh"}
  %while.3 = (s32[], f32[4,4]{1,0}) while((s32[], f32[4,4]) %t), condition=%c, body=%body, metadata={op_name="jit(fit)/slda.clime/jit(solve_dantzig_scan)/while"}
  %copy.5 = f32[4,4]{1,0} copy(f32[4,4]{1,0} %fusion.4)
  %fusion.6 = f32[4,1]{1,0} fusion(f32[4,4]{1,0} %p), kind=kLoop, calls=%fd, metadata={op_name="jit(fit)/slda.direction/jit(solve_dantzig_scan)/while/body/mul"}
  %all-reduce.7 = f32[4]{0} all-reduce(f32[4]{0} %v), replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(fit)/shard_map/slda.aggregate/pmean"}
  %fusion.8 = f32[4,1]{1,0} fusion(f32[4,4]{1,0} %p), kind=kOutput, calls=%fe, metadata={op_name="jit(fit)/slda.aggregate/slda.debias/dot_general"}
  %all-reduce-start.9 = f32[4]{0} all-reduce-start(f32[4]{0} %v), replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(fit)/slda.aggregate/pmean"}
  %copy-start.10 = (f32[8,4]{1,0}, f32[8,4]{1,0}, u32[]) copy-start(f32[8,4]{1,0} %x)
  ROOT %fusion.11 = f32[4,1]{1,0} fusion(f32[4,4]{1,0} %p), kind=kOutput, calls=%ff, metadata={op_name="jit(fit)/vmap(slda.debias)/dot_general"}
}
"""


def _synthetic():
    """One fit from t=100 to t=200 on the host; the device runs it from
    t=90 to t=195 on its own clock, which so reads 10 early."""
    ops = [
        Op("gram_pallas.1", 95, 10),  # 105-115 after the shift: stats
        Op("custom-call.2", 110, 10),  # 120-130: spectral
        Op("while.3", 125, 40),  # control flow: no time of its own
        Op("fusion.4", 125, 5),  # 135-140: clime
        Op("fusion.4", 133, 5),  # 143-148
        Op("copy.5", 140, 2),  # 150-152: no scope, passed over
        Op("fusion.4", 144, 6),  # 154-160
        Op("fusion.6", 155, 5),  # 165-170: direction
        Op("all-reduce.7", 165, 5),  # 175-180: aggregate
        Op("fusion.8", 172, 4),  # 182-186: debias, inside aggregate
        Op("fusion.11", 178, 4),  # 188-192: debias, under a vmap
    ]
    in_flight = [
        Op("all-reduce-start.9", 160, 15),  # 170-185: aggregate
        Op("copy-start.10", 90, 100),  # a prefetch: no scope's
    ]
    modules = [Op("jit_fit", 90, 105)]
    host = [HostSpan("bench.fit", 100, 100, 0)]
    return {0: tracing.DeviceTrace(modules, ops, in_flight)}, host


def test_scope_of():
    assert scopes.scope_of("jit(f)/slda.clime/jit(g)/while/body/x") == "clime"
    assert scopes.scope_of("jit(f)/vmap(slda.stats)/dot_general") == "stats"
    assert scopes.scope_of("jit(f)/slda.debias") == "debias"
    # nested: the innermost
    assert scopes.scope_of("jit(f)/slda.aggregate/slda.debias/dot") == \
        "debias"
    assert scopes.scope_of("jit(f)/vmap(slda.aggregate/slda.debias)/x") == \
        "debias"
    assert scopes.scope_of("jit(f)/jit(solve_dantzig_scan)/while") is None
    assert scopes.scope_of("jit(f)/xslda.clime/mul") is None
    assert scopes.scope_of("") is None


def test_reduce_synthetic():
    devices, host = _synthetic()
    dev = scopes.reduce(devices, host, tracing.parse_hlo(HLO))[0]
    assert dev["scopes"] == pytest.approx({
        "stats": 10e-9, "spectral": 10e-9, "clime": 16e-9,
        "direction": 5e-9, "aggregate": 15e-9, "debias": 8e-9})
    # busy: 56 of the 100; all but the copy's 2 carry a scope
    assert dev["scoped_s"] == pytest.approx(54e-9)
    # the program runs the whole window: its idle is the device's
    assert dev["program_idle_s"] == pytest.approx(44e-9)
    # clime: 140-143, 148-150 and 152-154 (the copy passed over);
    # debias: 186-188.  The gaps at the edges and between layers: none's
    assert dev["scope_idle"] == pytest.approx({"clime": 7e-9,
                                               "debias": 2e-9})
    summary = tracing.reduce(devices, host, host, tracing.parse_hlo(HLO))
    assert summary["devices"][0]["busy_s"] == pytest.approx(56e-9)
    assert summary["devices"][0]["idle_s"] == pytest.approx(44e-9)


def test_program_idle_stays_inside_one_execution():
    """Two executions of one layer, with the host between them: the gaps
    at their edges are a program's idle, but no layer's."""
    busy = [(2, 8), (22, 28)]
    scoped = [(2, 8, "clime"), (22, 28, "clime")]
    idle, per_scope = scopes.program_idle(busy, [(0, 10), (20, 30)], scoped)
    assert idle == 8 and per_scope == {}
    idle, per_scope = scopes.program_idle(busy, [(0, 30)], scoped)
    assert idle == 18 and per_scope == {"clime": 14}


def test_per_fit_ms_takes_the_busiest_device():
    reduced = {
        0: {"scopes": {"clime": 4e-3, "debias": 1e-4}, "scoped_s": 0.0,
            "program_idle_s": 2e-3, "scope_idle": {"clime": 1e-3}},
        1: {"scopes": {"clime": 6e-3}, "scoped_s": 0.0,
            "program_idle_s": 1e-3, "scope_idle": {}},
    }
    assert scopes.per_fit_ms(reduced, fits=2) == pytest.approx({
        "clime": 3.0, "debias": 0.05, "idle.clime": 0.5,
        "program_idle": 1.0})
    assert scopes.per_fit_ms({}, fits=2) == {}


def _load(name):
    with open(os.path.join(FIXTURES, name)) as f:
        fx = json.load(f)
    devices = {0: tracing.DeviceTrace([Op(*m) for m in fx["modules"]],
                                      [Op(*o) for o in fx["ops"]],
                                      [Op(*o) for o in fx["async_ops"]])}
    host = [HostSpan(*h) for h in fx["host"]]
    hlo = {k: tuple(v) for k, v in fx["hlo"].items()}
    return devices, host, hlo, HostSpan(*fx["fit_span"])


def test_unscoped_program_reads_no_scope():
    """The program before it named its layers: no scope, and the idle
    inside the program still within the device's idle."""
    devices, host, hlo, fit = _load("trace_paper51_fit.json")
    dev = scopes.reduce(devices, [fit], hlo)[0]
    assert dev["scopes"] == {} and dev["scope_idle"] == {}
    assert dev["scoped_s"] == 0.0
    assert scopes.per_fit_ms({0: dev}, 1) == {
        "program_idle": 1e3 * dev["program_idle_s"]}
    summary = tracing.reduce(devices, host, [fit], hlo)
    assert 0 < dev["program_idle_s"] <= summary["devices"][0]["idle_s"]


@pytest.fixture(scope="module")
def scoped():
    devices, host, hlo, fit = _load("trace_paper51_fit_scoped.json")
    summary = tracing.reduce(devices, host, [fit], hlo)["devices"][0]
    return devices, hlo, fit, summary, scopes.reduce(devices, [fit], hlo)[0]


def test_recorded_scopes_cover_the_busy_time(scoped):
    _, _, _, summary, dev = scoped
    assert set(dev["scopes"]) == set(scopes.SCOPES)
    assert dev["scoped_s"] >= 0.97 * summary["busy_s"]
    assert dev["scoped_s"] <= summary["busy_s"] * (1 + 1e-9)


def test_recorded_solves_split_the_admm_layer(scoped):
    _, _, _, summary, dev = scoped
    solves = dev["scopes"]["direction"] + dev["scopes"]["clime"]
    assert solves == pytest.approx(summary["layers"]["admm"], rel=0.01)
    assert dev["scopes"]["spectral"] >= summary["layers"]["eigh"]
    assert dev["scopes"]["stats"] >= summary["layers"]["gram"]


def test_recorded_program_idle(scoped):
    _, _, _, summary, dev = scoped
    assert 0 < dev["program_idle_s"] <= summary["idle_s"]
    assert 0 < sum(dev["scope_idle"].values()) <= dev["program_idle_s"]


def _mask(intervals, lo, hi):
    """A boolean nanosecond timeline of [lo, hi): the brute-force union."""
    mask = np.zeros(int(np.ceil(hi - lo)), bool)
    for s, e in intervals:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            mask[int(round(a - lo)):int(round(b - lo))] = True
    return mask


def test_recorded_scopes_brute_force(scoped):
    devices, hlo, fit, _, dev = scoped
    lo, hi = fit.start_ns, fit.start_ns + fit.dur_ns
    shift = tracing.clock_shift(devices[0].modules, [fit])
    work = [(o.start_ns + shift, o.start_ns + o.dur_ns + shift, o.name)
            for o in devices[0].ops
            if hlo[o.name][0] not in tracing.CONTROL_FLOW]
    busy = _mask([(s, e) for s, e, _ in work], lo, hi)
    program = _mask([(m.start_ns + shift, m.start_ns + m.dur_ns + shift)
                     for m in devices[0].modules], lo, hi)
    for scope in scopes.SCOPES:
        mine = _mask([(s, e) for s, e, n in work
                      if scopes.scope_of(hlo[n][1]) == scope], lo, hi)
        assert dev["scopes"][scope] == pytest.approx(mine.sum() * 1e-9,
                                                     rel=1e-6), scope
    assert dev["program_idle_s"] == pytest.approx(
        (program & ~busy).sum() * 1e-9, rel=1e-6)


def test_recorded_fixture_round_trip():
    """``bench/layers.py --record`` writes one fit that reduces as the
    trace it came from."""
    from bench import layers

    devices, host = _synthetic()
    hlo = tracing.parse_hlo(HLO)
    fx = json.loads(json.dumps(layers.fixture(devices, host, host, hlo,
                                              "synthetic")))
    assert set(fx) == {"what", "hlo", "modules", "ops", "async_ops", "host",
                       "fit_span"}
    again = {0: tracing.DeviceTrace([Op(*m) for m in fx["modules"]],
                                    [Op(*o) for o in fx["ops"]],
                                    [Op(*o) for o in fx["async_ops"]])}
    fit = HostSpan(*fx["fit_span"])
    assert scopes.reduce(again, [fit], {k: tuple(v) for k, v in
                                        fx["hlo"].items()}) == \
        scopes.reduce(devices, host, hlo)
