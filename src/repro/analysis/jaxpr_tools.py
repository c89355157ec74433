"""The ONE jaxpr-walking toolbox for the contract analyzer and probes.

Before :mod:`repro.analysis` existed, ``subjaxprs`` / ``find_while_body``
/ ``count_prim`` were triplicated across ``tests/_jaxpr_utils.py``,
``tests/_distributed_check.py`` and ``benchmarks/_overlap_child.py``,
and four test files re-derived the reverse transitive-dependency walk
inline.  Every walker lives here now; the jaxpr vocabulary types come
from ``jax.extend.core``.

All walkers recurse through nested jaxprs (``pjit``, ``scan``,
``while``, custom-call bodies) — a probe must see through the session
layer's jit wrapping and the substrate's kernel dispatch.
"""
from __future__ import annotations

from typing import Iterable, Iterator, List, Optional, Set, Tuple

from jax.extend.core import Jaxpr, Literal


def subjaxprs(eqn) -> Iterator[Jaxpr]:
    """Yield every sub-jaxpr referenced by an equation's params."""
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else [v]):
            j = getattr(sub, "jaxpr", sub)
            if isinstance(j, Jaxpr):
                yield j


def find_while_body(jaxpr: Jaxpr) -> Optional[Jaxpr]:
    """First while-loop body jaxpr, searching nested jaxprs depth-first."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            return eqn.params["body_jaxpr"].jaxpr
        for sub in subjaxprs(eqn):
            found = find_while_body(sub)
            if found is not None:
                return found
    return None


def count_prim(jaxpr: Jaxpr, name: str) -> int:
    """Occurrences of a primitive in a jaxpr, including nested jaxprs."""
    cnt = sum(1 for eqn in jaxpr.eqns if eqn.primitive.name == name)
    for eqn in jaxpr.eqns:
        for sub in subjaxprs(eqn):
            cnt += count_prim(sub, name)
    return cnt


def find_prim_eqn(jaxpr: Jaxpr, name: str):
    """First equation of the given primitive, searching nested jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            return eqn
        for sub in subjaxprs(eqn):
            found = find_prim_eqn(sub, name)
            if found is not None:
                return found
    return None


def find_prim_eqns(jaxpr: Jaxpr, name: str) -> List:
    """ALL equations of the given primitive, including nested jaxprs."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            out.append(eqn)
        for sub in subjaxprs(eqn):
            out.extend(find_prim_eqns(sub, name))
    return out


def nonliteral(vs: Iterable) -> Set:
    """The variable (non-``Literal``) subset of an invar/outvar list."""
    return {v for v in vs if not isinstance(v, Literal)}


def transitive_inputs(body: Jaxpr, target_eqn) -> Set:
    """Every variable ``target_eqn`` transitively consumes within ``body``.

    One reverse pass over the body's equations, growing the needed set —
    the shared core of every overlap probe in the repo.  Equations are
    treated atomically (a needed pjit/scan output pulls in all of that
    equation's inputs), which is conservative: it can only ever report
    MORE dependencies, never hide a real edge.
    """
    needed = nonliteral(target_eqn.invars)
    for eqn in reversed(body.eqns):
        if eqn is target_eqn:
            continue
        if any(ov in needed for ov in eqn.outvars):
            needed |= nonliteral(eqn.invars)
    return needed


def eqn_consumes(body: Jaxpr, target_eqn, producer_outvars: Set) -> bool:
    """Does ``target_eqn`` transitively consume any of the given outputs?"""
    return bool(set(producer_outvars) & transitive_inputs(body, target_eqn))


def eqn_needs_ppermute(body: Jaxpr, target_eqn) -> Tuple[Set, bool]:
    """Overlap probe: does ``target_eqn`` (e.g. the psum of the fused dot
    partials) transitively consume any ppermute output of ``body``?

    Returns ``(permute_outs, needs)`` — the set of halo-exchange outputs
    found in the body, and whether the target depends on any of them
    (False == no dependency edge == the reduction may overlap the
    in-flight matvec).
    """
    permute_outs: Set = set()
    for eqn in body.eqns:
        if eqn.primitive.name == "ppermute":
            permute_outs.update(eqn.outvars)
    return permute_outs, eqn_consumes(body, target_eqn, permute_outs)
