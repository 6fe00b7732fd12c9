"""Every operation of the compiled fit carries one layer scope.

The pipeline names its layers with ``jax.named_scope`` (``slda.stats``,
``slda.spectral``, ``slda.direction``, ``slda.clime``, ``slda.debias``,
``slda.aggregate``), and a profiler trace attributes device time to a
layer by reading the innermost scope out of each operation's
``op_name``.  The nestings are each round's ``slda.debias`` inside the
rounds' ``slda.aggregate`` and, on a mesh whose model axis spans more
than one device, the correction's ``slda.gather`` inside the debias.
Here the fit is compiled at a small size and each instruction the
program executes (the entry computation and the loop bodies, not the
inside of fusions) that computes is checked: where JAX gave it an
``op_name`` that ends in a primitive, that name holds a scope, and
holds more only as those nestings.  What XLA adds itself (copies,
bitcasts, rewrites) has no ``op_name``, and a constant JAX hoists is
named by its ``jit(...)`` wrapper alone: both are exempt.
"""

import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core.dantzig import DantzigConfig
from repro.core.distributed import (
    distributed_slda_shardmap,
    simulated_distributed_slda,
)

from conftest import run_in_subprocess

SCOPES = {"stats", "spectral", "direction", "clime", "debias", "aggregate"}
NESTED = (["aggregate", "debias"],  # a round's debias, inside the rounds
          ["aggregate", "debias", "gather"])  # ... and its model gather
D, N, M = 16, 40, 3

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# a transform wraps the scope it maps: "vmap(slda.clime)/jit(...)"
_SCOPE = re.compile(r"(?:^|[/(])slda\.([a-z_]+)(?=[/)]|$)")
# instructions that hold, move or name data and compute nothing
MOVES = {"parameter", "constant", "bitcast", "copy", "tuple",
         "get-tuple-element"}
# what a while, call or conditional runs
_RUNS = re.compile(r"(?:body|condition|to_apply|true_computation|"
                   r"false_computation)=%?([\w.\-]+)|"
                   r"branch_computations=\{([^}]*)\}")


def executed_instructions(text: str) -> list:
    """``(opcode, op_name)`` of every instruction in the computations the
    program runs: the entry and what ``while``, ``call`` and
    ``conditional`` run, leaving out fusion bodies and reducers."""
    computations, entry, current = {}, None, None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = m.group(2)
                computations[current] = []
                entry = current if m.group(1) else entry
        elif line.strip() == "}":
            current = None
        else:
            m = _INSTR.match(line)
            if m:
                rest = m.group(2)
                op = _OPCODE.search(" " + rest)
                name = _OP_NAME.search(rest)
                computations[current].append(
                    (op.group(1) if op else "", name.group(1) if name else "",
                     rest))
    out, todo, seen = [], [entry], set()
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for opcode, op_name, rest in computations[comp]:
            out.append((opcode, op_name))
            if opcode in ("while", "call", "conditional"):
                for m in _RUNS.finditer(rest):
                    todo += re.findall(r"[\w.\-]+", m.group(1) or m.group(2))
    return out


def _mesh_fit(rounds):
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    x = jax.ShapeDtypeStruct((N, D), jnp.float32)
    fn = jax.jit(lambda x, y, lam, t: distributed_slda_shardmap(
        mesh, x, y, lam, lam, t, DantzigConfig(), rounds=rounds))
    return fn.lower(x, x, 0.1, 0.05)


def _simulated_fit():
    xs = jax.ShapeDtypeStruct((M, N, D), jnp.float32)
    return simulated_distributed_slda.lower(xs, xs, 0.1, 0.1, 0.05)


MODEL_AXIS = f"""
import contextlib, json
import jax, jax.numpy as jnp
from repro.core.dantzig import DantzigConfig
from repro.core.distributed import distributed_slda_shardmap

mesh = jax.make_mesh((2, 2), ("data", "model"))
x = jax.ShapeDtypeStruct((2 * {N}, {D - 1}), jnp.float32)


def hlo():
    fn = jax.jit(lambda x, y, lam, t: distributed_slda_shardmap(
        mesh, x, y, lam, lam, t, DantzigConfig(), rounds=3))
    return fn.lower(x, x, 0.1, 0.05).compile().as_text()


scoped = hlo()
named_scope = jax.named_scope
jax.named_scope = lambda name: (contextlib.nullcontext()
                                if name == "slda.gather"
                                else named_scope(name))
print(json.dumps({{"scoped": scoped, "unscoped": hlo()}}))
"""


@functools.lru_cache(maxsize=None)
def model_axis_hlo() -> dict:
    """The fit on a (data 2, model 2) mesh at an odd d, T=3, compiled with
    and without the gather's scope, in a process with four devices."""
    out = run_in_subprocess(MODEL_AXIS, devices=4)
    return json.loads(out.strip().splitlines()[-1])


CASES = {
    "mesh_T1": lambda: _mesh_fit(1).compile().as_text(),
    "mesh_T3": lambda: _mesh_fit(3).compile().as_text(),
    "simulated": lambda: _simulated_fit().compile().as_text(),
    "model_axis_T3": lambda: model_axis_hlo()["scoped"],
}


@functools.lru_cache(maxsize=None)
def compiled(case: str) -> list:
    return executed_instructions(CASES[case]())


def scopes_of(op_name: str) -> list:
    return _SCOPE.findall(op_name)


def from_primitive(op_name: str) -> bool:
    """Whether JAX named the instruction after a primitive it lowered."""
    return bool(op_name) and not op_name.rsplit("/", 1)[-1].startswith(
        "jit(")


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_named_instruction_has_one_scope(case):
    named = [(op, name) for op, name in compiled(case)
             if from_primitive(name) and op not in MOVES]
    assert named
    stray = [(op, name) for op, name in named
             if len(scopes_of(name)) != 1 and scopes_of(name) not in NESTED]
    assert not stray, stray[:10]


@pytest.mark.parametrize("case", sorted(CASES))
def test_all_six_layers_compute(case):
    seen = {scopes_of(name)[-1] for op, name in compiled(case)
            if op not in MOVES and scopes_of(name)}
    assert seen == SCOPES | ({"gather"} if case == "model_axis_T3"
                             else set())


@pytest.mark.parametrize("case", sorted(CASES))
def test_gather_scope_only_on_a_model_axis(case):
    """``slda.gather`` names each round's all-gather over a model axis of
    two devices, and nothing where the axis holds one device (or none)."""
    gathers = [scopes_of(name) for op, name in compiled(case)
               if "slda.gather" in name]
    if case == "model_axis_T3":
        assert gathers == [NESTED[1]] * 3
        assert [op for op, name in compiled(case)
                if "slda.gather" in name] == ["all-gather"] * 3
    else:
        assert gathers == []


def strip_metadata(text: str) -> str:
    """A compiled module's text without what names and locates its
    instructions in the source: the metadata and the stack tables."""
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:.+\n)*", "", text, flags=re.M)
    return re.sub(r",? metadata=\{[^}]*\}", "", text)


def test_gather_scope_changes_no_compiled_code():
    hlo = model_axis_hlo()
    assert hlo["scoped"] != hlo["unscoped"]
    assert strip_metadata(hlo["scoped"]) == strip_metadata(hlo["unscoped"])
    assert "all-gather" in strip_metadata(hlo["scoped"])


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_admm_loop_per_solve(case):
    """Each solve runs one loop over its adapting chunks, which holds the
    one inner loop of plain steps; both carry the solve's scope."""
    loops = [name for op, name in compiled(case)
             if op == "while" and "solve_dantzig_scan" in name]
    outer = [n for n in loops if n.endswith("solve_dantzig_scan)/while")]
    assert sorted(scopes_of(n) for n in outer) == [["clime"], ["direction"]]
    inner = sorted(n for n in loops if n not in outer)
    assert [n.split("/while/body/")[0] for n in inner] == sorted(
        n.removesuffix("/while") for n in outer)


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_eigh_under_spectral(case):
    eighs = [scopes_of(name) for op, name in compiled(case)
             if op == "custom-call" and "jit(eigh)" in name]
    assert eighs == [["spectral"]]
