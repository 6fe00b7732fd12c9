"""Contract types checked against traced jaxprs.

Each contract is a small declarative object with a ``check(jaxpr, params)``
method returning :class:`Violation` records that carry the offending eqn
path.  Numeric fields accept either a literal or :class:`Param`, a named
placeholder resolved against the per-case params dict at check time --
that is how "T rounds means T psums" stays declarative at the decoration
site while the sweep supplies T.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple, Union

import numpy as np

from repro.analysis import walker

# NOTE: jax itself is imported lazily (inside check methods) so that
# `python -m repro.analysis.lint` can force the host device count
# before jax initializes.


class Violation(NamedTuple):
    """One contract breach, with the located eqn paths that triggered it."""

    contract: str
    message: str
    sites: Tuple[str, ...] = ()

    def render(self) -> str:
        lines = [f"{self.contract}: {self.message}"]
        lines.extend(f"    at {s}" for s in self.sites)
        return "\n".join(lines)


class Param(NamedTuple):
    """Placeholder resolved against the case params dict at check time."""

    key: str


class MissingParam(KeyError):
    pass


def resolve(value, params):
    if isinstance(value, Param):
        if not params or value.key not in params:
            raise MissingParam(value.key)
        return params[value.key]
    return value


def _fmt(sites) -> Tuple[str, ...]:
    return tuple(walker.format_site(s) for s in sites)


IntOrParam = Union[int, Param]
ShapeOrParam = Union[Tuple[int, ...], Param]


class PrimitiveBudget(NamedTuple):
    """Bound the number of occurrences of one primitive in the whole trace.

    ``exact`` pins the count; ``max_count``/``min_count`` bound it.  The
    optional ``out_shape`` matcher restricts counting to eqns producing an
    output of that shape (the old rounds-test filter, now standard).
    """

    prim: str
    exact: Optional[IntOrParam] = None
    max_count: Optional[IntOrParam] = None
    min_count: Optional[IntOrParam] = None
    out_shape: Optional[ShapeOrParam] = None

    def describe(self) -> str:
        parts = []
        if self.exact is not None:
            parts.append(f"=={self.exact}")
        if self.max_count is not None:
            parts.append(f"<={self.max_count}")
        if self.min_count is not None:
            parts.append(f">={self.min_count}")
        shape = f" @{self.out_shape}" if self.out_shape is not None else ""
        return f"budget[{self.prim}{shape} {' '.join(parts) or 'any'}]"

    def check(self, jaxpr, params=None) -> list:
        out_shape = resolve(self.out_shape, params)
        sites = walker.find_eqns(jaxpr, self.prim, out_shape)
        n = len(sites)
        violations = []

        def fail(expected: str):
            violations.append(Violation(
                self.describe(),
                f"found {n} `{self.prim}` eqns, expected {expected}",
                _fmt(sites),
            ))

        exact = resolve(self.exact, params)
        if exact is not None and n != exact:
            fail(f"exactly {exact}")
        max_count = resolve(self.max_count, params)
        if max_count is not None and n > max_count:
            fail(f"at most {max_count}")
        min_count = resolve(self.min_count, params)
        if min_count is not None and n < min_count:
            fail(f"at least {min_count}")
        return violations


class ExecutionBudget(NamedTuple):
    """Pin how many times one call runs a primitive: each site counted
    once per trip of its enclosing scans (``walker.count_executions``).
    A matching site under a ``while`` or a ``cond`` has no static trip
    count and is a violation."""

    prim: str
    exact: IntOrParam
    out_shape: Optional[ShapeOrParam] = None

    def describe(self) -> str:
        shape = f" @{self.out_shape}" if self.out_shape is not None else ""
        return f"executions[{self.prim}{shape} =={self.exact}]"

    def check(self, jaxpr, params=None) -> list:
        out_shape = resolve(self.out_shape, params)
        exact = resolve(self.exact, params)
        try:
            n = walker.count_executions(jaxpr, self.prim, out_shape)
        except ValueError as err:
            return [Violation(self.describe(), str(err))]
        if n == exact:
            return []
        return [Violation(
            self.describe(),
            f"`{self.prim}` runs {n} times per call, expected {exact}",
            _fmt(walker.find_eqns(jaxpr, self.prim, out_shape)),
        )]


def _eqn_axes(eqn) -> Tuple[str, ...]:
    """Named mesh axes a collective eqn reduces/gathers over."""
    axes = eqn.params.get("axes", None)
    if axes is None:
        axes = eqn.params.get("axis_name", ())
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


class CollectiveContract(NamedTuple):
    """Pin a collective's count AND its payload shape/dtype per mesh axis.

    The per-round O(d*K) uplink becomes an asserted fact: ``count``
    matching eqns must exist (after the ``shape`` payload filter), and
    every one of them must reduce over ``axis`` and carry ``dtype``.
    """

    prim: str  # "psum" | "all_gather"
    count: IntOrParam
    axis: Optional[str] = None
    shape: Optional[ShapeOrParam] = None
    dtype: Optional[str] = None

    def describe(self) -> str:
        bits = [f"x{self.count}"]
        if self.axis:
            bits.append(f"axis={self.axis}")
        if self.shape is not None:
            bits.append(f"payload={self.shape}")
        if self.dtype:
            bits.append(self.dtype)
        return f"collective[{self.prim} {' '.join(bits)}]"

    def check(self, jaxpr, params=None) -> list:
        shape = resolve(self.shape, params)
        sites = walker.find_eqns(jaxpr, self.prim, shape)
        if self.axis is not None:
            # count only the axis's own collectives: a trace may hold
            # BOTH data-axis and model-axis gathers under separate
            # contracts (the compressed rounds path does)
            sites = [s for s in sites if self.axis in _eqn_axes(s.eqn)]
        count = resolve(self.count, params)
        violations = []
        if len(sites) != count:
            payload = f" with payload {tuple(shape)}" if shape is not None else ""
            axis = f" on axis '{self.axis}'" if self.axis is not None else ""
            violations.append(Violation(
                self.describe(),
                f"found {len(sites)} `{self.prim}` eqns{payload}{axis}, "
                f"expected exactly {count}",
                _fmt(sites),
            ))
        for site in sites:
            if self.dtype is not None:
                want = np.dtype(self.dtype)
                bad = [v for v in site.eqn.outvars
                       if getattr(v.aval, "dtype", want) != want]
                if bad:
                    got = {str(v.aval.dtype) for v in bad}
                    violations.append(Violation(
                        self.describe(),
                        f"`{self.prim}` payload dtype {sorted(got)}, "
                        f"contract requires {want}",
                        _fmt([site]),
                    ))
        return violations


class AxisPayloadBits(NamedTuple):
    """Pin the total per-link bits all collectives move over one mesh axis.

    Sums, over every collective eqn (``prims``) whose named axes include
    ``axis``, the bits of its INPUT operands -- what one device puts on
    the wire: an ``all_gather``'s invar is the per-device shard, a
    ``psum``'s operand is the block each device contributes (``pmean``
    lowers to psum + div, so it is counted at the psum).  ``exact_bits``
    makes the declared uplink budget an asserted property of the lowered
    program: a hidden dense block riding the axis -- whatever primitive
    carries it -- blows the budget and names the eqn.
    """

    axis: str
    exact_bits: Optional[IntOrParam] = None
    max_bits: Optional[IntOrParam] = None
    prims: Tuple[str, ...] = ("psum", "all_gather", "all_to_all",
                              "ppermute")

    def describe(self) -> str:
        parts = []
        if self.exact_bits is not None:
            parts.append(f"=={self.exact_bits}")
        if self.max_bits is not None:
            parts.append(f"<={self.max_bits}")
        return (f"payload_bits[axis={self.axis} "
                f"{' '.join(parts) or 'any'}]")

    @staticmethod
    def _eqn_bits(eqn) -> int:
        bits = 0
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", None)
            dtype = getattr(aval, "dtype", None)
            if shape is None or dtype is None:
                continue
            bits += int(np.prod(shape, dtype=np.int64)) * (
                np.dtype(dtype).itemsize * 8)
        return bits

    def check(self, jaxpr, params=None) -> list:
        sites = []
        total = 0
        for site in walker.iter_eqns(jaxpr):
            if site.eqn.primitive.name not in self.prims:
                continue
            if self.axis not in _eqn_axes(site.eqn):
                continue
            sites.append(site)
            total += self._eqn_bits(site.eqn)
        violations = []

        def fail(expected: str):
            violations.append(Violation(
                self.describe(),
                f"collectives over axis '{self.axis}' move {total} bits "
                f"per link, expected {expected}",
                _fmt(sites),
            ))

        exact = resolve(self.exact_bits, params)
        if exact is not None and total != exact:
            fail(f"exactly {exact}")
        max_bits = resolve(self.max_bits, params)
        if max_bits is not None and total > max_bits:
            fail(f"at most {max_bits}")
        return violations


def _block_dim(dim) -> int:
    """One block dimension as an int (``pl.Blocked`` wraps it)."""
    return int(getattr(dim, "block_size", dim))


class VmemConformance(NamedTuple):
    """Cross-check traced fused-ADMM launches against the VMEM model.

    For every ``pallas_call`` whose kernel name contains
    ``kernel_substr``, read the BlockMappings actually traced, recover
    (d, block_k, state_io), and assert the analytic footprint
    ``fused_block_vmem_bytes(d, block_k, state_io)`` fits the budget and
    that ``block_k`` never exceeds what ``pick_block_k`` would allow.
    A launch whose name or block mappings cannot be read is itself a
    violation: the contract never passes by skipping what it cannot see.
    """

    budget: Optional[IntOrParam] = None  # None -> backend_vmem_budget()
    kernel_substr: str = "fused_admm"

    def describe(self) -> str:
        budget = self.budget if self.budget is not None else "backend"
        return f"vmem[{self.kernel_substr} <= {budget}]"

    @staticmethod
    def _kernel_name(eqn) -> str:
        """The launch's ``name=``, else the kernel function's name."""
        name = eqn.params.get("name")
        if not name:
            info = getattr(eqn.params.get("jaxpr"), "debug_info", None)
            name = getattr(info, "func_name", None)
        if not name:
            raise ValueError("pallas_call carries no kernel name")
        return name

    def check(self, jaxpr, params=None) -> list:
        from repro.kernels.dantzig_fused import (
            STATE_KERNEL,
            backend_vmem_budget,
            fused_block_vmem_bytes,
            pick_block_k,
        )

        budget = resolve(self.budget, params)
        if budget is None:
            budget = backend_vmem_budget()
        violations = []
        for site in walker.find_eqns(jaxpr, "pallas_call"):
            try:
                name = self._kernel_name(site.eqn)
                if self.kernel_substr not in name:
                    continue
                mappings = site.eqn.params["grid_mapping"].block_mappings
                # the last two dims: a vmapped launch prepends a batch dim
                d = _block_dim(mappings[0].block_shape[-2])
                block_k = _block_dim(mappings[3].block_shape[-1])
                k_total = int(mappings[3].array_aval.shape[-1])
                state_io = name == STATE_KERNEL
            except (KeyError, AttributeError, IndexError, TypeError,
                    ValueError) as exc:
                violations.append(Violation(
                    self.describe(),
                    f"could not read the kernel name or block mappings "
                    f"from pallas_call params ({exc!r}); the analyzer "
                    f"needs updating for this jax version",
                    _fmt([site]),
                ))
                continue
            used = fused_block_vmem_bytes(d, block_k, state_io=state_io)
            if used > budget:
                violations.append(Violation(
                    self.describe(),
                    f"fused block (d={d}, block_k={block_k}, "
                    f"state_io={state_io}) needs {used} bytes, "
                    f"budget is {budget}",
                    _fmt([site]),
                ))
            allowed = pick_block_k(d, k_total, budget, state_io=state_io)
            if allowed is not None and block_k > allowed:
                violations.append(Violation(
                    self.describe(),
                    f"traced block_k={block_k} exceeds pick_block_k's "
                    f"choice {allowed} for (d={d}, k={k_total})",
                    _fmt([site]),
                ))
        return violations


class DtypePolicy(NamedTuple):
    """No silent float promotion past ``max_float`` anywhere in the trace.

    Flags every eqn producing a floating value wider than the ceiling --
    which catches both f64 literals leaking in and an explicit
    ``convert_element_type`` promoting the hot path.
    """

    max_float: str = "float32"

    def describe(self) -> str:
        return f"dtype[float <= {self.max_float}]"

    def check(self, jaxpr, params=None) -> list:
        import jax.numpy as jnp

        max_bits = jnp.finfo(jnp.dtype(self.max_float)).bits
        bad_sites = []
        bad_dtypes = set()
        for site in walker.iter_eqns(jaxpr):
            for v in site.eqn.outvars:
                dt = getattr(v.aval, "dtype", None)
                if dt is None or not jnp.issubdtype(dt, jnp.floating):
                    continue
                if jnp.finfo(dt).bits > max_bits:
                    bad_sites.append(site)
                    bad_dtypes.add(str(dt))
                    break
        if not bad_sites:
            return []
        shown = _fmt(bad_sites[:8])
        if len(bad_sites) > 8:
            shown = shown + (f"... and {len(bad_sites) - 8} more",)
        return [Violation(
            self.describe(),
            f"{len(bad_sites)} eqns produce {sorted(bad_dtypes)}, wider "
            f"than the {self.max_float} ceiling",
            shown,
        )]


ContractType = Union[PrimitiveBudget, CollectiveContract,
                     AxisPayloadBits, VmemConformance, DtypePolicy]


def run_contracts(contracts, jaxpr, params: Optional[dict] = None) -> list:
    """Check every contract; a missing case param is itself a violation."""
    violations: list[Violation] = []
    for contract in contracts:
        try:
            violations.extend(contract.check(jaxpr, params))
        except MissingParam as exc:
            violations.append(Violation(
                contract.describe(),
                f"case params missing key {exc.args[0]!r} needed by this "
                f"contract",
            ))
    return violations


def render_report(violations, indent: str = "  ") -> str:
    return "\n".join(
        indent + line
        for v in violations
        for line in v.render().splitlines()
    )


__all__ = [
    "AxisPayloadBits",
    "CollectiveContract",
    "ContractType",
    "DtypePolicy",
    "MissingParam",
    "Param",
    "PrimitiveBudget",
    "Violation",
    "VmemConformance",
    "render_report",
    "resolve",
    "run_contracts",
]
