"""``BENCHMARK.json`` is checked, and each cell's files found by name."""

import copy
import json
import os

import pytest

from bench import manifest
from bench.tests import tiny


@pytest.fixture(scope="module")
def benchmark():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_repo_manifest_is_valid_and_every_file_is_found(benchmark):
    manifest.validate(benchmark, tiny.REPO)
    for w in benchmark["workloads"]:
        cell = manifest.cell(benchmark, tiny.REPO, w["name"])
        assert cell.config["name"] == w["config"]
        assert {"mesh", "rounds", "pool"} <= set(cell.traffic)
        assert cell.traffic["mesh"]["data"] * cell.traffic["mesh"]["model"] \
            == cell.chips
        assert os.path.isfile(os.path.join(tiny.REPO, "bench", "limits",
                                           f"{w['name']}.json"))
        assert {"setup_s", "fit_s"} <= {e["name"] for e in cell.end_to_end}
        assert cell.per_layer
    for e in benchmark["per_layer"]:
        assert callable(manifest.load_reader(tiny.REPO, e["name"]))


def test_command_and_paths_stay_inside(benchmark):
    assert benchmark["paths"] == ["bench"]
    for word in benchmark["command"]:
        assert not word.startswith("/") and ".." not in word
    assert benchmark["command"][1].startswith("bench/")


@pytest.mark.parametrize("name,ok", [
    ("fit_s", True), ("collective_ms.fit4", True), ("_x-1.y", True),
    ("9lives", True), ("a" * 64, True), ("a" * 65, False), (".hidden", False),
    ("-dash", False), ("has space", False), ("a,b", False), ("a/b", False),
    ("muµ", False), ("", False),
])
def test_names(name, ok):
    assert (manifest.NAME.fullmatch(name) is not None) == ok


@pytest.mark.parametrize("unit,ok", [
    ("s", True), ("ms", True), ("%", True), ("tokens/s", True),
    ("us", True), ("µs", False), ("tokens per second", False),
    ("a" * 17, False), ("", False),
])
def test_units(unit, ok):
    assert (manifest.UNIT.fullmatch(unit) is not None) == ok


def _broken(benchmark, edit):
    m = copy.deepcopy(benchmark)
    edit(m)
    return m


@pytest.mark.parametrize("what,edit", [
    ("bad metric name", lambda m: m["end_to_end"][0].update(name="fit s")),
    ("bad unit", lambda m: m["end_to_end"][0].update(unit="seconds each")),
    ("bound too loose", lambda m: m["end_to_end"][0].update(bound=0.3)),
    ("no setup_s", lambda m: m["end_to_end"].pop()),
    ("unknown key", lambda m: m["per_layer"][0].update(why="x")),
    ("missing reader", lambda m: m["per_layer"][0].update(name="no_reader")),
    ("missing traffic",
     lambda m: m["workloads"][0].update(traffic="no_such_mix")),
    ("missing config file",
     lambda m: m["configs"][0].update(file="bench/configs/none.json")),
    ("unknown config", lambda m: m["workloads"][0].update(config="none")),
    ("chips", lambda m: m["workloads"][0].update(chips=2)),
    ("duplicate cell",
     lambda m: m["workloads"].append(dict(m["workloads"][0]))),
    ("moves nothing", lambda m: m["per_layer"][0].update(moves="none")),
    ("run_seconds", lambda m: m.update(run_seconds=52)),
    ("unknown cell in metric",
     lambda m: m["per_layer"][0].update(workloads=["no.such.cell"])),
    ("why on two lines",
     lambda m: m["workloads"][0].update(why="one\ntwo")),
])
def test_broken_manifest_is_refused(benchmark, what, edit):
    with pytest.raises(manifest.ManifestError):
        manifest.validate(_broken(benchmark, edit), tiny.REPO)


def test_unknown_cell_is_refused(benchmark):
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.cell(benchmark, tiny.REPO, "no.such.cell")


def _file(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("what,edit", [
    ("unread key", lambda c: c.update(tol=None)),
    ("unread solver key", lambda c: c["solver"].update(tol=1e-3)),
    ("missing solver key", lambda c: c["solver"].pop("alpha")),
    ("unread precision key", lambda c: c["precision"].update(gram="x")),
    ("missing key", lambda c: c.pop("d")),
    ("N apart from its machines", lambda c: c.update(N=c["N"] + 1)),
    ("another name", lambda c: c.update(name="other")),
])
def test_config_with_an_unread_or_missing_key_is_refused(what, edit):
    config = _file(manifest.config_path(tiny.REPO, "highd_d1024_m4"))
    manifest.check_config(config, "highd_d1024_m4")
    edit(config)
    with pytest.raises(manifest.ManifestError):
        manifest.check_config(config, "highd_d1024_m4")


@pytest.mark.parametrize("what,edit", [
    ("unread key", lambda t: t.update(in_flight=1)),
    ("missing key", lambda t: t.pop("pool")),
    ("unread mesh key", lambda t: t["mesh"].update(pipe=1)),
])
def test_traffic_with_an_unread_or_missing_key_is_refused(what, edit):
    traffic = _file(manifest.traffic_path(tiny.REPO, "fit_share"))
    manifest.check_traffic(traffic, "fit_share")
    edit(traffic)
    with pytest.raises(manifest.ManifestError):
        manifest.check_traffic(traffic, "fit_share")


def test_solver_keys_are_the_schedule_the_reference_reads():
    from bench import reference

    assert set(reference.Schedule._fields) == manifest.SOLVER_KEYS
