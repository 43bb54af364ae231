"""Jaxpr tracing utilities for the trace-lint front end.

A ``TraceTarget`` names one hot-path entry point — a callable plus concrete
example args (real arrays or ShapeDtypeStructs; tracing never needs values).
``trace`` turns it into a ``TraceArtifact``: the closed jaxpr, the abstract
output, and any exception raised during tracing (a trace that *can't* be
built is itself a finding — see trace/recompile_hazard).

Tracing is the whole story here: nothing in this package compiles or runs
a step.  ``jax.make_jaxpr`` on a jitted function yields a single top-level
``jit`` equation whose params carry ``donated_invars`` — that plus a
recursive equation walk is enough for every rule in ``rules_trace``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, Optional, Sequence, Tuple

import jax
from jax.extend import core as jcore


@dataclass(frozen=True)
class TraceTarget:
    """One registered hot-path entry point.

    ``donate`` is the argnums the call site *requests* (the analyzer checks
    the traced jaxpr actually honors them).  ``state_map`` pairs
    ``(arg_index, out_index)`` for carried state whose dtype must be
    preserved across the step (param/opt-state trees under a policy).
    """
    name: str
    fn: Callable
    args: Tuple[Any, ...]
    donate: Tuple[int, ...] = ()
    policy: str = "fp32"
    state_map: Tuple[Tuple[int, int], ...] = ()
    tags: Tuple[str, ...] = ()


@dataclass(frozen=True)
class TraceArtifact:
    target: TraceTarget
    jaxpr: Optional[Any] = None          # jax.extend.core.ClosedJaxpr
    out_shape: Optional[Any] = None      # pytree of ShapeDtypeStruct
    error: Optional[str] = None
    meta: Dict[str, Any] = field(default_factory=dict)


def trace(target: TraceTarget) -> TraceArtifact:
    """Trace one target to (jaxpr, abstract outputs); never raises."""
    import traceback
    try:
        jaxpr = jax.make_jaxpr(target.fn)(*target.args)
        out_shape = jax.eval_shape(target.fn, *target.args)
    except Exception:
        return TraceArtifact(target=target,
                             error=traceback.format_exc(limit=8))
    return TraceArtifact(target=target, jaxpr=jaxpr, out_shape=out_shape)


# --------------------------------------------------------------------------
# equation walking
# --------------------------------------------------------------------------

def _sub_jaxprs(params: Dict[str, Any]) -> Iterator[Any]:
    """Yield every jaxpr nested in an equation's params (scan/cond/jit/...)."""
    for v in params.values():
        leaves = v if isinstance(v, (tuple, list)) else (v,)
        for leaf in leaves:
            if isinstance(leaf, jcore.ClosedJaxpr):
                yield leaf.jaxpr
            elif isinstance(leaf, jcore.Jaxpr):
                yield leaf


def iter_eqns(jaxpr) -> Iterator[Any]:
    """Depth-first over all equations, descending into nested jaxprs.

    Accepts a ClosedJaxpr or raw Jaxpr.
    """
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    for eqn in inner.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def top_jit_eqn(jaxpr):
    """The sole top-level jit equation of a traced jitted fn, or None.

    make_jaxpr of ``jax.jit(f)`` produces exactly one ``jit`` eqn wrapping
    the body; its params hold ``donated_invars`` (leaf-expanded, one bool
    per flattened input).
    """
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    jits = [e for e in inner.eqns if e.primitive.name == "jit"]
    if len(inner.eqns) == len(jits) == 1:
        return jits[0]
    return None


def donated_invars(artifact: TraceArtifact) -> Optional[Tuple[bool, ...]]:
    """Leaf-level donation mask of the target's top-level jit, or None."""
    if artifact.jaxpr is None:
        return None
    eqn = top_jit_eqn(artifact.jaxpr)
    if eqn is None or "donated_invars" not in eqn.params:
        return None
    return tuple(eqn.params["donated_invars"])


def leaf_counts(args: Sequence[Any]) -> Tuple[int, ...]:
    """Flattened-leaf count per positional argument (donation accounting)."""
    return tuple(len(jax.tree_util.tree_leaves(a)) for a in args)
