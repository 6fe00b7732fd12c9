"""The analyzer analyzed: negative cases per contract kind, nested-jaxpr
recursion, AST import-rule units, and the remainder-shape CLI sweep.

Every contract kind must (a) pass on a conforming trace and (b) trip on
a deliberately violating one, reporting the offending eqn path.
"""

import io
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import (
    AxisPayloadBits,
    CollectiveContract,
    DtypePolicy,
    ExecutionBudget,
    Param,
    PrimitiveBudget,
    check_entry,
    count_eqns,
    count_executions,
    find_eqns,
    run_contracts,
    trace_contract,
)
from repro.analysis import cases as cases_mod
from repro.analysis import imports as import_rules
from repro.analysis import lint, registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# walker: nested-jaxpr recursion and located paths
# ---------------------------------------------------------------------------


def test_count_eqns_recurses_into_scan_while_cond_pjit():
    def scan_body(c, _):
        return c, jnp.linalg.eigh(c)[0]

    def while_body(s):
        a, i = s
        return jnp.linalg.eigh(a + 1.0)[1], i + 1

    def f(a):
        c, _ = jax.lax.scan(scan_body, a, jnp.arange(2))
        w, _ = jax.lax.while_loop(lambda s: s[1] < 1, while_body, (a, 0))
        e = jax.lax.cond(a[0, 0] > 0,
                         lambda x: jnp.linalg.eigh(x)[1],
                         lambda x: x, a)
        g = jax.jit(lambda x: jnp.linalg.eigh(x)[1])(a)
        return c, w, e, g

    jaxpr = jax.make_jaxpr(f)(jnp.eye(3))
    # scan body traces once (not per iteration); cond holds one eigh in
    # one branch; while body one; the inner jit one
    assert count_eqns(jaxpr, "eigh") == 4
    outermost = [s.path[0] for s in find_eqns(jaxpr, "eigh")]
    # jax names the pjit primitive "jit"
    for enclosing in ("scan", "while", "cond", "jit"):
        assert enclosing in outermost, (enclosing, outermost)


def test_count_eqns_accepts_closed_and_raw_jaxpr():
    jaxpr = jax.make_jaxpr(lambda a: jnp.linalg.eigh(a))(jnp.eye(3))
    assert count_eqns(jaxpr, "eigh") == count_eqns(jaxpr.jaxpr, "eigh") == 1


def test_count_eqns_out_shape_matcher():
    def f(x):
        return x @ x.T, x.T @ x  # (2,2) and (3,3) dot_generals

    jaxpr = jax.make_jaxpr(f)(jnp.ones((2, 3)))
    assert count_eqns(jaxpr, "dot_general", (2, 2)) == 1
    assert count_eqns(jaxpr, "dot_general", (3, 3)) == 1
    assert count_eqns(jaxpr, "dot_general", (4, 4)) == 0


def test_count_eqns_recurses_into_shard_map():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    fn = jax.shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                       in_specs=(P("data"),), out_specs=P(), check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(jnp.ones((4,)))
    sites = find_eqns(jaxpr, "psum")
    assert len(sites) == 1
    assert "shard_map" in "/".join(sites[0].path)


# ---------------------------------------------------------------------------
# primitive budgets: negative case trips with located sites
# ---------------------------------------------------------------------------


def test_primitive_budget_trips_on_double_eigh():
    def double_eigh(a):
        return jnp.linalg.eigh(a)[1] + jnp.linalg.eigh(a + 1.0)[1]

    jaxpr = jax.make_jaxpr(double_eigh)(jnp.eye(3))
    assert PrimitiveBudget("eigh", exact=1).check(jaxpr) != []
    assert PrimitiveBudget("eigh", max_count=1).check(jaxpr) != []
    assert PrimitiveBudget("eigh", max_count=2).check(jaxpr) == []
    (violation,) = PrimitiveBudget("eigh", exact=1).check(jaxpr)
    assert "found 2" in violation.message
    assert len(violation.sites) == 2
    assert all("eigh" in s for s in violation.sites)


def test_budget_param_resolution_and_missing_param():
    jaxpr = jax.make_jaxpr(lambda a: jnp.linalg.eigh(a))(jnp.eye(3))
    budget = PrimitiveBudget("eigh", exact=Param("eighs"))
    assert run_contracts([budget], jaxpr, {"eighs": 1}) == []
    assert run_contracts([budget], jaxpr, {"eighs": 2}) != []
    (violation,) = run_contracts([budget], jaxpr, {})
    assert "eighs" in violation.message  # missing key is itself reported


# ---------------------------------------------------------------------------
# execution budgets: sites weighted by their enclosing scans' trips
# ---------------------------------------------------------------------------


def _nested_scans(a):
    def inner(c, _):
        return c @ c, None

    def outer(c, _):
        c = c @ c  # once per outer trip
        c, _ = jax.lax.scan(inner, c, None, length=4)
        return c, None

    c, _ = jax.lax.scan(outer, a, None, length=3)
    return jax.jit(lambda x: x @ x)(c)  # once


def test_count_executions_multiplies_nested_scan_lengths():
    jaxpr = jax.make_jaxpr(_nested_scans)(jnp.eye(2))
    assert count_eqns(jaxpr, "dot_general") == 3
    assert count_executions(jaxpr, "dot_general") == 3 + 3 * 4 + 1
    assert count_executions(jaxpr, "dot_general", (5, 5)) == 0


def test_execution_budget_exact_and_unknown_trips():
    jaxpr = jax.make_jaxpr(_nested_scans)(jnp.eye(2))
    budget = ExecutionBudget("dot_general", exact=Param("n"))
    assert run_contracts([budget], jaxpr, {"n": 16}) == []
    (violation,) = run_contracts([budget], jaxpr, {"n": 15})
    assert "runs 16 times" in violation.message
    assert len(violation.sites) == 3

    def in_while(a):
        return jax.lax.while_loop(lambda c: c[0, 0] < 9.0,
                                  lambda c: c @ c + 1.0, a)

    looped = jax.make_jaxpr(in_while)(jnp.eye(2))
    with pytest.raises(ValueError, match="no static trip count"):
        count_executions(looped, "dot_general")
    (violation,) = budget.check(looped, {"n": 1})
    assert "while/dot_general" in violation.message


# ---------------------------------------------------------------------------
# collective contracts: count, payload shape/dtype, mesh axis
# ---------------------------------------------------------------------------


def _trace_shard(body, *args):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(P() for _ in args),
                       out_specs=P(), check_vma=False)
    return jax.make_jaxpr(fn)(*args)


def test_collective_contract_holds_on_conforming_trace():
    jaxpr = _trace_shard(lambda x: jax.lax.psum(x, "data"), jnp.ones((4,)))
    good = CollectiveContract("psum", count=1, axis="data", shape=(4,),
                              dtype="float32")
    assert good.check(jaxpr) == []


def test_collective_contract_trips_on_extra_psum():
    jaxpr = _trace_shard(
        lambda x: jax.lax.psum(x, "data") + jax.lax.psum(2.0 * x, "data"),
        jnp.ones((4,)))
    violations = CollectiveContract("psum", count=1, axis="data",
                                    shape=(4,)).check(jaxpr)
    assert violations and "found 2" in violations[0].message
    assert all("psum" in s for s in violations[0].sites)


def test_collective_contract_trips_on_wrong_payload_shape():
    jaxpr = _trace_shard(lambda x: jax.lax.psum(x, "data"), jnp.ones((4,)))
    violations = CollectiveContract("psum", count=1,
                                    shape=(5,)).check(jaxpr)
    assert violations and "expected exactly 1" in violations[0].message


def test_collective_contract_trips_on_wrong_axis():
    jaxpr = _trace_shard(lambda x: jax.lax.psum(x, "model"), jnp.ones((4,)))
    violations = CollectiveContract("psum", count=1, axis="data",
                                    shape=(4,)).check(jaxpr)
    assert violations and "'data'" in violations[0].message


def test_collective_contract_trips_on_payload_dtype():
    jaxpr = _trace_shard(
        lambda x: jax.lax.psum(x.astype(jnp.bfloat16), "data"),
        jnp.ones((4,)))
    violations = CollectiveContract("psum", count=1, shape=(4,),
                                    dtype="float32").check(jaxpr)
    assert violations and "bfloat16" in violations[0].message


def test_collective_contract_axis_filter_ignores_other_axes():
    """An axis-scoped contract counts ONLY its axis's collectives:
    model-axis traffic neither satisfies nor violates a data-axis pin."""
    jaxpr = _trace_shard(
        lambda x: jax.lax.psum(x, "data") + jax.lax.psum(x, "model"),
        jnp.ones((4,)))
    assert CollectiveContract("psum", count=1, axis="data", shape=(4,),
                              dtype="float32").check(jaxpr) == []
    assert CollectiveContract("psum", count=1, axis="model").check(jaxpr) \
        == []


# ---------------------------------------------------------------------------
# axis payload bits: total traffic over one mesh axis, at wire dtypes
# ---------------------------------------------------------------------------


def test_axis_payload_bits_exact_max_and_axis_scope():
    # one f32 psum of (4,) over the data axis = 128 bits per link
    jaxpr = _trace_shard(lambda x: jax.lax.psum(x, "data"), jnp.ones((4,)))
    assert AxisPayloadBits("data", exact_bits=128).check(jaxpr) == []
    assert AxisPayloadBits("data", max_bits=128).check(jaxpr) == []
    (violation,) = AxisPayloadBits("data", exact_bits=64).check(jaxpr)
    assert "128" in violation.message and violation.sites
    (violation,) = AxisPayloadBits("data", max_bits=100).check(jaxpr)
    assert "128" in violation.message
    # traffic on OTHER axes does not count toward this axis's total
    assert AxisPayloadBits("model", exact_bits=0).check(jaxpr) == []


def test_axis_payload_bits_sums_wire_dtypes():
    """Mixed-dtype gathers over one axis sum at their WIRE widths --
    the contract prices what one link uplinks (the gather operand),
    not the m-times-larger gathered result."""
    def body(x):
        vals = jax.lax.all_gather(x.astype(jnp.bfloat16), "data")
        idx = jax.lax.all_gather(jnp.arange(4, dtype=jnp.int16), "data")
        return vals.sum() + idx.sum()

    jaxpr = _trace_shard(body, jnp.ones((4,)))
    # 4 bf16 values (64 bits) + 4 int16 indices (64 bits)
    assert AxisPayloadBits("data", exact_bits=128).check(jaxpr) == []
    assert AxisPayloadBits("data", exact_bits=256).check(jaxpr) != []


# ---------------------------------------------------------------------------
# dtype policy: silent promotion past the ceiling
# ---------------------------------------------------------------------------


def test_dtype_policy_passes_f32_and_trips_at_bf16_ceiling():
    def f(x):
        return x.astype(jnp.float32) @ x.astype(jnp.float32).T

    jaxpr = jax.make_jaxpr(f)(jnp.ones((3, 3), jnp.bfloat16))
    assert DtypePolicy().check(jaxpr) == []  # f32 ceiling: clean
    violations = DtypePolicy(max_float="bfloat16").check(jaxpr)
    assert violations and "float32" in violations[0].message
    assert violations[0].sites  # offending eqns are located


# ---------------------------------------------------------------------------
# registry: contracts travel with the entry point; breaks are located
# ---------------------------------------------------------------------------


def test_registry_decorator_registers_and_checks():
    @trace_contract("selftest.double_eigh",
                    contracts=(PrimitiveBudget("eigh", exact=1),))
    def double_eigh(a):
        return jnp.linalg.eigh(a)[1] + jnp.linalg.eigh(a + 1.0)[1]

    try:
        assert "selftest.double_eigh" in registry.registered()
        jaxpr = jax.make_jaxpr(double_eigh)(jnp.eye(3))
        violations = check_entry("selftest.double_eigh", jaxpr, {})
        assert len(violations) == 1
        assert violations[0].sites and all(
            "eigh" in s for s in violations[0].sites)
    finally:
        registry.unregister("selftest.double_eigh")


def test_lint_run_api_passes_on_real_entry():
    buf = io.StringIO()
    n = lint.run(["pipeline.worker_debiased"], include_imports=False,
                 out=buf)
    assert n == 0, buf.getvalue()
    assert "[ok] binary-fused-d12" in buf.getvalue()


def test_lint_run_reports_broken_entry():
    @trace_contract("selftest.lint_broken",
                    contracts=(PrimitiveBudget("pallas_call", exact=1),))
    def plain(x):
        return x * 2.0

    @cases_mod.case("selftest.lint_broken", "neg", {})
    def _build():
        return plain, (jnp.ones((2, 2)),)

    try:
        buf = io.StringIO()
        n = lint.run(["selftest.lint_broken"], include_imports=False,
                     out=buf)
        report = buf.getvalue()
        assert n == 1
        assert "[FAIL] neg" in report and "pallas_call" in report
    finally:
        registry.unregister("selftest.lint_broken")
        cases_mod._CASES.pop("selftest.lint_broken", None)


def test_every_registered_entry_has_cases():
    for name in registry.registered():
        assert cases_mod.cases_for(name), f"{name} has no trace cases"


# ---------------------------------------------------------------------------
# AST import-graph rules (units on synthetic trees)
# ---------------------------------------------------------------------------


def _write_tree(root, files):
    for rel, src in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src)


def test_banned_import_rule_flags_both_import_forms(tmp_path):
    _write_tree(tmp_path, {
        "repro/core/dantzig.py": "def solve_dantzig():\n    pass\n",
        "repro/core/solver_dispatch.py":
            "from repro.core.dantzig import solve_dantzig\n",  # allowed
        "repro/core/evil.py":
            "from repro.core.dantzig import solve_dantzig\n",
        "repro/core/sneaky.py":
            "from repro.core import dantzig as dz\n"
            "def f(a, b):\n    return dz.solve_dantzig(a, b)\n",
        "repro/core/innocent.py":
            "# from repro.core.dantzig import solve_dantzig (a comment!)\n"
            "S = 'dantzig.solve_dantzig('\n",
    })
    violations = import_rules.banned_import_violations(tmp_path)
    offenders = {v.sites[0].rsplit(":", 1)[0] for v in violations}
    assert offenders == {str(tmp_path / "repro/core/evil.py"),
                         str(tmp_path / "repro/core/sneaky.py")}


def test_exclusive_call_rule_ignores_comments_and_strings(tmp_path):
    _write_tree(tmp_path, {
        "repro/core/pipeline.py":
            "import jax\ndef g(x):\n"
            "    return jax.lax.all_gather(x, 'model')\n",  # allowed
        "repro/core/rogue.py":
            "import jax\ndef f(x):\n"
            "    return jax.lax.all_gather(x, 'model')\n",
        "repro/core/clean.py":
            "# lax.all_gather( in a comment must not trip\n"
            "DOC = 'lax.all_gather('\n",
    })
    violations = import_rules.exclusive_call_violations(tmp_path)
    assert len(violations) == 1
    assert "rogue" in violations[0].sites[0]


def test_pipeline_unification_rule(tmp_path):
    good = {
        f"repro/core/{leaf}.py":
            "from repro.core import pipeline\n"
            "def run():\n    return pipeline.worker_debiased\n"
        for leaf in ("slda", "distributed", "multiclass")
    }
    good["repro/core/rounds.py"] = (
        "from repro.core import pipeline\n"
        "def step():\n"
        "    return pipeline.worker_solves, pipeline.apply_correction\n")
    _write_tree(tmp_path, good)
    assert import_rules.pipeline_unification_violations(tmp_path) == []
    # break one face: multiclass stops importing the pipeline core
    (tmp_path / "repro/core/multiclass.py").write_text(
        "def run():\n    return 7\n")
    violations = import_rules.pipeline_unification_violations(tmp_path)
    assert violations and any("multiclass" in v.message for v in violations)


def test_structural_rules_hold_on_this_repo():
    assert import_rules.structural_violations() == []


# ---------------------------------------------------------------------------
# remainder-shape sweep (d=70, model axis 4) through the CLI
# ---------------------------------------------------------------------------


def test_remainder_shape_sweep_via_cli():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint",
         "--entry", "distributed.slda_shardmap", "--no-imports"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "[ok] fused-rounds3-mesh2x4-d70-remainder" in proc.stdout
