"""Recursive jaxpr traversal with located eqn paths.

This is the single home of the eqn counter that used to live as two
divergent private copies in ``tests/test_rounds.py`` and
``tests/test_spectral_path.py``.  Traversal descends into every nested
jaxpr a primitive carries in its params -- pjit, scan, while, cond
branches, shard_map bodies, pallas_call kernels -- so a contract holds
for the whole lowered program, not just the top level.
"""

from __future__ import annotations

from typing import Any, Iterator, NamedTuple


class EqnSite(NamedTuple):
    """One equation plus the chain of enclosing primitives that reach it."""

    eqn: Any
    path: tuple[str, ...]  # enclosing primitive names, outermost first


def as_jaxpr(obj):
    """Accept a ClosedJaxpr, a raw Jaxpr, or anything forwarding ``eqns``."""
    if hasattr(obj, "eqns"):
        return obj
    if hasattr(obj, "jaxpr"):
        return obj.jaxpr
    raise TypeError(f"not a jaxpr: {type(obj).__name__}")


def _sub_jaxprs(value) -> Iterator[Any]:
    """Yield every jaxpr reachable from one params value.

    Handles ClosedJaxpr (``.jaxpr``), raw Jaxpr (``.eqns``), and
    tuples/lists of either (cond branches, custom-call sub-jaxprs).
    """
    if hasattr(value, "jaxpr") and hasattr(value.jaxpr, "eqns"):
        yield value.jaxpr
    elif hasattr(value, "eqns"):
        yield value
    elif isinstance(value, (list, tuple)):
        for item in value:
            yield from _sub_jaxprs(item)


def iter_eqns(jaxpr, path: tuple[str, ...] = ()) -> Iterator[EqnSite]:
    """Depth-first walk over every eqn, including nested sub-jaxprs."""
    jaxpr = as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield EqnSite(eqn, path)
        inner = path + (eqn.primitive.name,)
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from iter_eqns(sub, inner)


def _aval_short(var) -> str:
    aval = getattr(var, "aval", None)
    if aval is None:
        return "?"
    short = getattr(aval, "str_short", None)
    return short() if callable(short) else str(aval)


def format_site(site: EqnSite) -> str:
    """Render a located eqn path, e.g. ``shard_map/pjit/eigh -> f32[8,8]``."""
    where = "/".join(site.path + (site.eqn.primitive.name,))
    outs = ",".join(_aval_short(v) for v in site.eqn.outvars)
    return f"{where} -> {outs}"


def find_eqns(jaxpr, prim_name: str, out_shape=None) -> list[EqnSite]:
    """All sites for ``prim_name``; ``out_shape`` keeps only eqns with at
    least one output of that shape (the standard payload matcher)."""
    want = tuple(out_shape) if out_shape is not None else None
    sites = []
    for site in iter_eqns(jaxpr):
        if site.eqn.primitive.name != prim_name:
            continue
        if want is not None and not any(
            getattr(v.aval, "shape", None) == want for v in site.eqn.outvars
        ):
            continue
        sites.append(site)
    return sites


def count_eqns(jaxpr, prim_name: str, out_shape=None) -> int:
    """Count primitive occurrences, descending into nested jaxprs."""
    return len(find_eqns(jaxpr, prim_name, out_shape))


def _iter_executions(jaxpr, trips: int | None = 1,
                    path: tuple[str, ...] = ()) -> Iterator[tuple]:
    """Like :func:`iter_eqns`, with how many times each eqn runs per
    call: ``(site, trips)``, ``trips`` the product of the enclosing
    scans' static lengths, or None under a ``while`` or a ``cond``,
    whose trips the trace does not fix."""
    jaxpr = as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield EqnSite(eqn, path), trips
        name = eqn.primitive.name
        inner = trips
        if name == "scan" and trips is not None:
            inner = trips * eqn.params["length"]
        elif name in ("while", "cond"):
            inner = None
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from _iter_executions(sub, inner, path + (name,))


def count_executions(jaxpr, prim_name: str, out_shape=None) -> int:
    """How many times one call runs ``prim_name``: each site counted once
    per trip of its enclosing scans.  Raises ValueError for a site under
    a ``while`` or a ``cond``, whose trip count is not static."""
    want = tuple(out_shape) if out_shape is not None else None
    total = 0
    for site, trips in _iter_executions(jaxpr):
        if site.eqn.primitive.name != prim_name:
            continue
        if want is not None and not any(
            getattr(v.aval, "shape", None) == want for v in site.eqn.outvars
        ):
            continue
        if trips is None:
            raise ValueError(f"no static trip count at {format_site(site)}")
        total += trips
    return total
