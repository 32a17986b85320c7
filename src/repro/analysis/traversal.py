"""Jaxpr traversal core — the one implementation of "walk every equation,
recursing into sub-jaxprs" that the fedlint rules, the CLI manifest and the
test-suite jaxpr assertions all share.

Before this module the repo carried two hand-rolled copies of the walker
(``tests/test_sparse_round.py``, ``tests/test_dual_wire.py``), each guarding
one invariant.  Copies rot: the PR-4 int8-accumulator wrap and the PR-6
padding-polluted ``alie`` statistics both shipped before their walker
existed.  Everything here is pure structural traversal — no rule logic.

The traversal carries an *equation path* (e.g. ``jit(_normal)/scan/body``)
so a finding deep inside a scanned sub-jaxpr is diagnosable without
re-deriving where it came from.
"""
from __future__ import annotations

from typing import Any, Iterator, List, Tuple

from jax.extend.core import ClosedJaxpr, Jaxpr


def subjaxprs(eqn) -> Iterator[Tuple[str, Jaxpr]]:
    """All sub-jaxprs referenced by ``eqn``'s params, as (label, jaxpr).

    Handles every higher-order primitive layout jax uses: a bare ``Jaxpr``
    or ``ClosedJaxpr`` param (``jit``, ``scan``, ``while``, ``remat``,
    custom derivatives) and tuples/lists of them (``cond`` branches).  The
    label names the param (plus the branch index for sequences) so paths
    stay readable.
    """
    for name, v in eqn.params.items():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for i, sub in enumerate(vs):
            label = name if len(vs) == 1 else f"{name}[{i}]"
            if isinstance(sub, ClosedJaxpr):
                yield label, sub.jaxpr
            elif isinstance(sub, Jaxpr):
                yield label, sub


def _label(eqn) -> str:
    """Display label of an equation in a path: the primitive name, plus the
    jitted function's name when the primitive carries one."""
    name = eqn.params.get("name")
    prim = eqn.primitive.name
    return f"{prim}({name})" if isinstance(name, str) else prim


def iter_eqns(jaxpr: Jaxpr) -> Iterator[Any]:
    """All eqns of ``jaxpr``, recursing into sub-jaxprs (jit, scan, while,
    cond, ...) depth-first.  Accepts a ``Jaxpr`` or ``ClosedJaxpr``."""
    for eqn, _ in iter_eqns_with_path(jaxpr):
        yield eqn


def iter_eqns_with_path(jaxpr: Jaxpr,
                        _path: Tuple[str, ...] = ()
                        ) -> Iterator[Tuple[Any, Tuple[str, ...]]]:
    """Like :func:`iter_eqns` but yields ``(eqn, path)`` where ``path`` is
    the tuple of enclosing higher-order-primitive labels, outermost first
    (``()`` for a top-level equation)."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, _path
        for _, sub in subjaxprs(eqn):
            yield from iter_eqns_with_path(sub, _path + (_label(eqn),))


def format_path(path: Tuple[str, ...]) -> str:
    return "/".join(path) if path else "<top>"


def out_avals(eqn) -> List[Any]:
    """The abstract values of an equation's outputs (skips dropped vars
    without an aval)."""
    avals = []
    for var in eqn.outvars:
        aval = getattr(var, "aval", None)
        if aval is not None:
            avals.append(aval)
    return avals
