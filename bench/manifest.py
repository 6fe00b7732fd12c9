"""``BENCHMARK.json`` and the files it names, loaded and checked.

A cell is found by its name: its configuration in
``bench/configs/<config>.json``, its traffic in
``bench/traffic/<traffic>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  Adding a cell, a configuration, a
traffic mix or a metric adds files and entries; no file changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, NamedTuple

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
# The keys a run reads from a configuration or traffic file, and those
# that only describe it.  Any other key is refused, so that no key looks
# like a setting that no run applies.
CONFIG_READ = {"d", "rho", "n_signal", "signal", "N", "r", "machines",
               "n_per_machine", "lambda_coef", "t_coef", "solver",
               "precision"}
CONFIG_DOC = {"name", "source"}
CONFIG_OPTIONAL = {"machines_held", "deployment", "guarantees", "assumed",
                   "reduced_from_source"}
SOLVER_KEYS = {"max_iters", "rho", "alpha", "adapt_every", "rho_mu",
               "rho_tau"}  # the fields of bench.reference.Schedule
PRECISION_KEYS = {"admm", "stats", "what"}
TRAFFIC_READ = {"mesh", "rounds", "pool"}
TRAFFIC_DOC = {"what"}
MESH_KEYS = {"data", "model"}


class ManifestError(ValueError):
    pass


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict  # the configuration file, as run
    traffic: dict  # the traffic file
    end_to_end: list  # the metric entries this cell reports, trace 0
    per_layer: list  # ... and with trace 1


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise ManifestError(msg)


def _check_name(value: Any, what: str) -> None:
    _check(isinstance(value, str) and NAME.fullmatch(value) is not None,
           f"{what}: {value!r} is not a name (letters, digits, _ . -; "
           f"at most 64; no leading . or -)")


def _check_line(value: Any, what: str) -> None:
    _check(isinstance(value, str) and 1 <= len(value) <= 200
           and "\n" not in value and "\t" not in value,
           f"{what}: 1 to 200 characters on one line, no tab")


def _check_keys(d: Any, required: set, optional: set, what: str) -> None:
    _check(isinstance(d, dict), f"{what}: not an object")
    missing, unread = required - set(d), set(d) - required - optional
    _check(not missing, f"{what}: missing {sorted(missing)}")
    _check(not unread, f"{what}: no run reads {sorted(unread)}")


def check_config(config: dict, name: str) -> None:
    """Raise :class:`ManifestError` where a configuration file holds a
    key that no run reads, or misses one that it does."""
    _check_keys(config, CONFIG_READ | CONFIG_DOC, CONFIG_OPTIONAL,
                f"bench/configs/{name}.json")
    _check(config["name"] == name, f"bench/configs/{name}.json: name")
    _check_keys(config["solver"], SOLVER_KEYS, set(),
                f"bench/configs/{name}.json solver")
    _check_keys(config["precision"], PRECISION_KEYS, set(),
                f"bench/configs/{name}.json precision")
    _check(config["N"] == config["machines"] * config["n_per_machine"],
           f"bench/configs/{name}.json: N is machines x n_per_machine")


def check_traffic(traffic: dict, name: str) -> None:
    """The same for a traffic file."""
    _check_keys(traffic, TRAFFIC_READ, TRAFFIC_DOC,
                f"bench/traffic/{name}.json")
    _check_keys(traffic["mesh"], MESH_KEYS, set(),
                f"bench/traffic/{name}.json mesh")


def config_path(root: str, config: str) -> str:
    return os.path.join(root, "bench", "configs", f"{config}.json")


def traffic_path(root: str, traffic: str) -> str:
    return os.path.join(root, "bench", "traffic", f"{traffic}.json")


def metric_path(root: str, metric: str) -> str:
    return os.path.join(root, "bench", "metrics", f"{metric}.py")


def validate(m: dict, root: str) -> None:
    """Raise :class:`ManifestError` where ``m`` breaks the rules."""
    _check(set(m) == TOP_KEYS, f"top-level keys must be {sorted(TOP_KEYS)}")
    _check(isinstance(m["run_seconds"], int)
           and 1 <= m["run_seconds"] <= 51, "run_seconds: 1 to 51")
    configs = {}
    for c in m["configs"]:
        _check(set(c) == {"name", "source", "file", "reduced", "why"},
               f"config {c.get('name')}: keys")
        _check_name(c["name"], "config name")
        _check_line(c["source"], f"config {c['name']} source")
        _check_line(c["why"], f"config {c['name']} why")
        _check(c["file"] == f"bench/configs/{c['name']}.json",
               f"config {c['name']}: file must be bench/configs/<name>.json")
        _check(os.path.isfile(os.path.join(root, c["file"])),
               f"config {c['name']}: {c['file']} not found")
        for key in c["reduced"]:
            _check_name(key, f"config {c['name']} reduced key")
        _check(c["name"] not in configs, f"config {c['name']} twice")
        configs[c["name"]] = c
    cells = set()
    pairs = set()
    for w in m["workloads"]:
        _check(set(w) == {"name", "config", "traffic", "chips", "why"},
               f"workload {w.get('name')}: keys")
        for key in ("name", "config", "traffic"):
            _check_name(w[key], f"workload {key}")
        _check_line(w["why"], f"workload {w['name']} why")
        _check(w["chips"] in (1, 4), f"workload {w['name']}: chips 1 or 4")
        _check(w["config"] in configs,
               f"workload {w['name']}: no config {w['config']}")
        _check(os.path.isfile(traffic_path(root, w["traffic"])),
               f"workload {w['name']}: bench/traffic/{w['traffic']}.json "
               f"not found")
        _check(w["name"] not in cells, f"workload {w['name']} twice")
        _check((w["config"], w["traffic"]) not in pairs,
               f"workload {w['name']}: config and traffic pair twice")
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
    used = {w["config"] for w in m["workloads"]}
    _check(used == set(configs), "every config is used by some cell")
    names = set()
    e2e = {}
    for kind, entries in (("end_to_end", m["end_to_end"]),
                          ("per_layer", m["per_layer"])):
        for e in entries:
            _check_name(e.get("name"), f"{kind} metric name")
            _check(e["name"] not in names, f"metric {e['name']} twice")
            names.add(e["name"])
            _check(isinstance(e.get("unit"), str)
                   and UNIT.fullmatch(e["unit"]) is not None,
                   f"metric {e['name']}: unit {e.get('unit')!r}")
            _check(e.get("better") in ("lower", "higher"),
                   f"metric {e['name']}: better is lower or higher")
            for cell in e.get("workloads", []):
                _check(cell in cells, f"metric {e['name']}: no cell {cell}")
            if kind == "end_to_end":
                _check(set(e) - {"workloads"}
                       == {"name", "unit", "better", "bound", "source"},
                       f"metric {e['name']}: keys")
                _check(e["source"] in SOURCES_E2E,
                       f"metric {e['name']}: source")
                _check(0 < e["bound"] <= 0.25, f"metric {e['name']}: bound")
                e2e[e["name"]] = e
            else:
                _check(set(e) - {"workloads"} == {
                    "name", "unit", "better", "source", "layer", "moves"},
                    f"metric {e['name']}: keys")
                _check(e["source"] in SOURCES, f"metric {e['name']}: source")
                _check_line(e["layer"], f"metric {e['name']} layer")
                _check(e["moves"] in e2e,
                       f"metric {e['name']}: moves names no end-to-end metric")
                _check(os.path.isfile(metric_path(root, e["name"])),
                       f"metric {e['name']}: bench/metrics/{e['name']}.py "
                       f"not found")
    _check("setup_s" in e2e, "setup_s is an end-to-end metric")


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    validate(m, root)
    return m


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def cell(m: dict, root: str, name: str) -> Cell:
    """The cell ``name`` of the validated manifest ``m``, files loaded."""
    by_name = {w["name"]: w for w in m["workloads"]}
    if name not in by_name:
        raise ManifestError(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    with open(config_path(root, w["config"])) as f:
        config = json.load(f)
    with open(traffic_path(root, w["traffic"])) as f:
        traffic = json.load(f)
    check_config(config, w["config"])
    check_traffic(traffic, w["traffic"])
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        end_to_end=[e for e in m["end_to_end"] if _reports(e, name)],
        per_layer=[e for e in m["per_layer"] if _reports(e, name)])


def load_reader(root: str, metric: str):
    """The ``read(summary)`` function of ``bench/metrics/<metric>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}",
        metric_path(root, metric))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
