"""Named regions of the training step, and the table that charges a device
trace's operations to them.

The step program wraps each region of a stage step in a ``jax.named_scope``
of a fixed name (``SCOPES``).  A scope is metadata only: the compiled step
is the same program with or without it.  The compiler keeps each scope in
the ``op_name`` of the instructions it compiles, through ``lax.scan``,
``jax.checkpoint`` and a ``custom_vjp`` backward, and the instruction names
of the compiled program are the names a ``jax.profiler`` trace gives its
device operations.  So device time can be charged to the regions:

* ``register(name, jitted, args)``: a step builder records its jitted
  program and the shapes of its first call's arguments;
* ``op_scopes(name=None)``: compiles that program again at those shapes (a
  compile-cache hit: the executable the step ran) and returns
  ``{instruction name: op_name}`` from its optimized HLO;
* ``scope_path(op_name)``: the scopes an ``op_name`` lies in, outermost
  first, and the pass that ran it: ``forward``, ``remat`` or ``backward``.

| scope | where | covers |
|---|---|---|
| ``embed`` | ``model.embed_tokens`` | token embedding and its gradient |
| ``sil_input`` | ``LMBackend.build_parallel_stage_step`` | the SIL_{k-1} lookup |
| ``layers`` | ``model.forward_groups`` | the layer scan's own bookkeeping |
| ``attention`` | ``model._apply_slot_full`` | norm1, projections, RoPE, residual |
| ``flash`` | ``ops.flash_attention`` | attention core, its remat and backward |
| ``mlp`` | ``model._apply_slot_full`` | norm2, the FFN, residual |
| ``sil_mse`` | ``losses.sil_stage_loss`` | the SIL-MSE head and its gradient |
| ``ce`` | ``LMBackend._objective`` | tied unembedding and chunked CE |
| ``optimizer`` | ``LMBackend`` steps | the optimizer update |
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax

SCOPES = ("embed", "sil_input", "layers", "attention", "flash", "mlp",
          "sil_mse", "ce", "optimizer")

# a name-stack element wrapped by a transformation: ``transpose(jvp(mlp))``;
# ``jit(f)`` wraps a function's name, never a scope
_WRAPPED = re.compile(r"^([\w\-]+)\((.*)\)$", re.S)
_FUNCTIONS = ("jit", "pjit")


def _split(path: str) -> List[str]:
    """``path`` split at the slashes outside parentheses."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(path[start:i])
            start = i + 1
    parts.append(path[start:])
    return parts


def _scopes_in(path: str) -> List[str]:
    out: List[str] = []
    for part in _split(path):
        m = _WRAPPED.match(part)
        if m:
            if m.group(1) not in _FUNCTIONS:
                out += _scopes_in(m.group(2))
        elif part in SCOPES:
            out.append(part)
    return out


def scope_path(op_name: str) -> Tuple[Tuple[str, ...], str]:
    """(scopes in ``op_name``'s path, outermost first; its pass).

    The pass is that of the innermost scope, read from the path up to it:
    ``remat`` where that holds ``rematted_computation`` (the forward a
    checkpointed backward recomputes), ``backward`` where it holds a
    ``transpose(...)``, ``forward`` otherwise.  So all the work of a region
    that runs inside the backward counts there, its own recomputation
    included: the custom VJP of ``flash`` differentiates a checkpointed
    scan.  A path with no scope is read whole.  A scope repeated at once
    (``flash`` opens again inside its own custom VJP) is listed once."""
    parts = _split(op_name)
    found: List[str] = []
    end = len(parts)
    for i, part in enumerate(parts):
        inner = _scopes_in(part)
        if inner:
            found += [n for n in inner if not found or n != found[-1]]
            end = i + 1
    head = "/".join(parts[:end])
    if "rematted_computation" in head:
        pass_ = "remat"
    elif "transpose(" in head:
        pass_ = "backward"
    else:
        pass_ = "forward"
    return tuple(found), pass_


# --------------------------------------------------------------------------
# the compiled program's instruction -> op_name table
# --------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r"\b(?:condition|body|to_apply|calls|true_computation"
                     r"|false_computation)=%?([\w.\-]+)")
_CALLED_LIST = re.compile(r"\b(?:branch_computations|called_computations)"
                          r"=\{([^}]*)\}")
_FUSED = re.compile(r"\bkind=k\w+, calls=%?([\w.\-]+)")


def hlo_op_names(text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of an HLO module's text.

    A fusion whose root the compiler made (a layout ``convert``, say, with
    no ``op_name``) takes that of the last named instruction it fuses.  An
    instruction still without one takes that of the instruction that calls
    its computation (the ``while`` whose body or condition holds it, the
    ``conditional`` whose branch does), and so on outwards; one that finds
    none, such as an unnamed copy in the entry computation, is left out."""
    own: Dict[str, Optional[str]] = {}
    home: Dict[str, str] = {}          # instruction -> its computation
    caller: Dict[str, str] = {}        # computation -> a calling instruction
    fuses: Dict[str, str] = {}         # fusion -> its fused computation
    last_named: Dict[str, str] = {}    # computation -> its last op_name
    comp = ""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                comp = c.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else None
        home[name] = comp
        if op:
            last_named[comp] = op.group(1)
        fused = _FUSED.search(line)
        if fused:
            fuses[name] = fused.group(1)
        for called in _CALLED.findall(line):
            caller.setdefault(called, name)
        for group in _CALLED_LIST.findall(line):
            for called in group.split(","):
                caller.setdefault(called.strip().lstrip("%"), name)
    for name, comp in fuses.items():
        if not own[name] and comp in last_named:
            own[name] = last_named[comp]

    resolved: Dict[str, Optional[str]] = {}

    def resolve(name: str) -> Optional[str]:
        # computations nest a few deep and never call themselves
        if name not in resolved:
            up = caller.get(home[name])
            resolved[name] = own[name] or (resolve(up) if up else None)
        return resolved[name]

    return {n: op for n in own if (op := resolve(n)) is not None}


# --------------------------------------------------------------------------
# the registry of step programs
# --------------------------------------------------------------------------

@dataclass
class _Program:
    jitted: Any
    args: tuple                        # ShapeDtypeStructs; None stays None
    table: Optional[Dict[str, str]] = None


_PROGRAMS: Dict[str, _Program] = {}    # most recently registered last


def _struct(x):
    if not (hasattr(x, "shape") and hasattr(x, "dtype")):
        return x
    # an uncommitted array was lowered with no sharding of its own: so is
    # its struct, or the program compiled here would not be the one the
    # step ran
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding,
                                weak_type=getattr(x, "weak_type", False))


def register(name: str, jitted, args: tuple) -> bool:
    """Record ``jitted`` called with ``args`` as the program ``name``, the
    most recent one.  Only the arguments' shapes, dtypes and shardings are
    kept.  A call under a transformation (tracer arguments) is not the
    program that runs, and registers nothing; returns whether it did."""
    leaves = jax.tree_util.tree_leaves(args)
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return False
    _PROGRAMS.pop(name, None)
    _PROGRAMS[name] = _Program(jitted, jax.tree_util.tree_map(_struct, args))
    return True


def op_scopes(name: Optional[str] = None) -> Dict[str, str]:
    """``{instruction name: op_name}`` of the compiled program ``name``, or
    of the most recently registered one.  Raises ``LookupError`` where no
    such program is registered."""
    if name is None:
        if not _PROGRAMS:
            raise LookupError("no step program is registered")
        name = next(reversed(_PROGRAMS))
    if name not in _PROGRAMS:
        raise LookupError(f"no step program {name!r} is registered")
    prog = _PROGRAMS[name]
    if prog.table is None:
        compiled = prog.jitted.lower(*prog.args).compile()
        prog.table = hlo_op_names(compiled.as_text())
    return prog.table
