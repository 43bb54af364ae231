"""Trace-lint rules: jaxpr-level checks over the registered entry points.

Each rule walks the traced jaxprs from ``entrypoints.artifacts(ctx)`` —
tracing happens once per (arch, precision) context, rules share the cache.

Detection notes that shaped these rules (verified against JAX's actual
lowering, not the docs):

* ``jnp.sum(x, dtype=bfloat16)`` lowers identically to ``jnp.sum(x)`` on a
  bf16 operand — convert-to-f32, f32 reduce, convert back — so a jnp-level
  "bf16 accumulation" is *invisible* in the jaxpr.  What IS visible: a raw
  lax-level reduce whose operand and output are both bf16, and a bf16 scan
  carry fed directly into an ``add`` in the scan body (a running
  accumulator kept in bf16).  Both are warns, not fails: autodiff of any
  bf16 forward mass-produces bf16 ``add_any`` / ``reduce_sum`` for the
  cotangents (fan-out sums, broadcast transposes) — that is inherent to
  bf16 training, while this repo's *deliberate* accumulations (microbatch
  grads, optimizer moments, loss reductions) are all explicitly fp32.  The
  warn aggregates per (target, primitive) so a hand-written bf16 reduce is
  visible without 29 lines of AD noise; bf16-*stored* state likewise flows
  through adds legitimately (a bf16 param update), and the decode cache's
  bf16 carry feeds ``dynamic_update_slice``, not ``add``, staying silent.
* Host transfers inside a jitted region surface as callback primitives
  (``debug_print`` / ``debug_callback`` / ``pure_callback`` /
  ``io_callback``); a plain ``jax.debug.print`` in a scan body is the
  classic accidental one.
* ``donated_invars`` lives on the top-level jit equation's params,
  leaf-expanded in argument order — comparing it against the donation the
  call site *requested* catches donation silently dropped by a wrapper.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from repro.analysis.core import AnalysisContext, Finding, register
from repro.analysis.entrypoints import artifacts
from repro.analysis.trace import donated_invars, iter_eqns, leaf_counts

HOST_CALLBACK_PRIMS = ("debug_print", "debug_callback", "pure_callback",
                       "io_callback", "callback")
REDUCE_PRIMS = ("reduce_sum", "cumsum", "add_any", "reduce_window_sum")
LOW_PRECISION = (jnp.bfloat16, jnp.float16)


def _dtype(aval):
    return getattr(aval, "dtype", None)


def _is_low(dt) -> bool:
    return dt is not None and any(dt == jnp.dtype(t) for t in LOW_PRECISION)


@register("trace/host_transfer",
          "No host callbacks / implicit device-to-host transfers inside "
          "jitted hot-path regions.", tags=("trace",))
def host_transfer(ctx: AnalysisContext) -> List[Finding]:
    out = []
    for name, art in artifacts(ctx).items():
        if art.jaxpr is None:
            continue
        for eqn in iter_eqns(art.jaxpr):
            if eqn.primitive.name in HOST_CALLBACK_PRIMS:
                cb = eqn.params.get("callback", "")
                out.append(Finding(
                    rule="trace/host_transfer", severity="fail", target=name,
                    message=f"{eqn.primitive.name} inside the jitted step "
                            "(host sync every invocation)",
                    evidence={"primitive": eqn.primitive.name,
                              "callback": repr(cb)[:120]}))
    return out


@register("trace/dtype_policy",
          "Compute-dtype discipline under the precision policy: no mixed-"
          "dtype matmuls, no bf16-accumulated reductions, no f64 leaks, no "
          "dtype drift on carried state.", tags=("trace",))
def dtype_policy(ctx: AnalysisContext) -> List[Finding]:
    out = []
    for name, art in artifacts(ctx).items():
        if art.jaxpr is None:
            continue
        low_reduces: dict = {}
        for eqn in iter_eqns(art.jaxpr):
            prim = eqn.primitive.name
            avals = [v.aval for v in eqn.invars
                     if hasattr(v, "aval") and hasattr(v.aval, "dtype")]
            dts = [a.dtype for a in avals
                   if jnp.issubdtype(a.dtype, jnp.floating)]
            if prim == "dot_general" and len(set(map(str, dts))) > 1:
                out.append(Finding(
                    rule="trace/dtype_policy", severity="fail", target=name,
                    message="mixed-dtype dot_general (silent upcast: one "
                            "operand missed the compute-dtype cast)",
                    evidence={"operand_dtypes": sorted(map(str, dts))}))
            if prim in REDUCE_PRIMS and dts and all(_is_low(d) for d in dts):
                odts = [str(v.aval.dtype) for v in eqn.outvars
                        if hasattr(v.aval, "dtype")]
                if all(_is_low(jnp.dtype(d)) for d in odts):
                    k = (prim, odts[0])
                    low_reduces[k] = low_reduces.get(k, 0) + 1
            if any(str(d) == "float64" for d in dts):
                out.append(Finding(
                    rule="trace/dtype_policy", severity="fail", target=name,
                    message=f"float64 operand reached {prim} (x64 leak)",
                    evidence={"primitive": prim}))
            if prim == "scan":
                out.extend(_scan_carry_accumulators(name, eqn))
        for (prim, dt), n in sorted(low_reduces.items()):
            out.append(Finding(
                rule="trace/dtype_policy", severity="warn", target=name,
                message=f"{n}x {prim} accumulating in {dt} (AD cotangent "
                        "sums are expected under bf16; audit any "
                        "hand-written lax reduce)",
                evidence={"primitive": prim, "dtype": dt, "count": n}))
        out.extend(_state_dtype_drift(name, art))
    return out


def _scan_carry_accumulators(target: str, eqn) -> List[Finding]:
    """bf16/f16 scan carries that feed DIRECTLY into an add in the body."""
    body = eqn.params["jaxpr"].jaxpr
    n_consts = eqn.params.get("num_consts", 0)
    n_carry = eqn.params.get("num_carry", 0)
    carry_vars = body.invars[n_consts:n_consts + n_carry]
    low = {id(v) for v in carry_vars if _is_low(_dtype(v.aval))}
    if not low:
        return []
    out = []
    for beqn in body.eqns:
        if beqn.primitive.name in ("add", "add_any") and \
                any(id(v) in low for v in beqn.invars):
            dt = str(beqn.outvars[0].aval.dtype)
            out.append(Finding(
                rule="trace/dtype_policy", severity="warn", target=target,
                message=f"scan carry in {dt} is summed in the body "
                        "(low-precision running accumulator?)",
                evidence={"carry_dtype": dt}))
    return out


def _state_dtype_drift(target: str, art) -> List[Finding]:
    """Carried-state args must come back with identical leaf dtypes."""
    out = []
    outs = art.out_shape
    if outs is None or not isinstance(outs, (tuple, list)):
        return out
    for arg_i, out_i in art.target.state_map:
        if out_i >= len(outs):
            continue
        a_dts = [str(x.dtype) for x in
                 jax.tree_util.tree_leaves(art.target.args[arg_i])]
        o_dts = [str(x.dtype) for x in jax.tree_util.tree_leaves(outs[out_i])]
        if a_dts != o_dts:
            drift = sorted({(a, o) for a, o in zip(a_dts, o_dts) if a != o})
            out.append(Finding(
                rule="trace/dtype_policy", severity="fail", target=target,
                message=f"carried state arg[{arg_i}] -> out[{out_i}] "
                        "changes dtype across the step",
                evidence={"drift": [f"{a}->{o}" for a, o in drift][:8]}))
    return out


@register("trace/donation",
          "Every buffer the call site requests donated is donated in the "
          "traced program (params/opt-state/caches reuse their memory).",
          tags=("trace",))
def donation(ctx: AnalysisContext) -> List[Finding]:
    from repro.launch.hlo_analysis import dtype_byte_breakdown
    out = []
    for name, art in artifacts(ctx).items():
        if art.jaxpr is None or not art.target.donate:
            continue
        counts = leaf_counts(art.target.args)
        expected = sum(counts[i] for i in art.target.donate)
        mask = donated_invars(art)
        if mask is None:
            out.append(Finding(
                rule="trace/donation", severity="fail", target=name,
                message="entry point requests donation but the trace "
                        "carries no donated_invars (donation dropped "
                        "by a wrapper?)",
                evidence={"requested_argnums": list(art.target.donate)}))
            continue
        actual = sum(mask)
        if actual < expected:
            # attribute the undonated leaves back to their argnums
            starts = [sum(counts[:i]) for i in range(len(counts))]
            undonated_bytes = {}
            for i in art.target.donate:
                seg = mask[starts[i]:starts[i] + counts[i]]
                if not all(seg):
                    bb = dtype_byte_breakdown(art.target.args[i])
                    for k, v in bb.items():
                        undonated_bytes[k] = undonated_bytes.get(k, 0) + v
            out.append(Finding(
                rule="trace/donation", severity="fail", target=name,
                message=f"only {actual}/{expected} requested leaves are "
                        "donated in the traced program",
                evidence={"expected": expected, "actual": actual,
                          "undonated_bytes_by_dtype": undonated_bytes}))
        else:
            out.append(Finding(
                rule="trace/donation", severity="info", target=name,
                message=f"all {expected} requested leaves donated",
                evidence={"donated_leaves": expected}))
    return out


@register("trace/recompile_hazard",
          "Entry points trace cleanly (no unhashable static args / shape-"
          "dependent Python branches) and are single jitted programs.",
          tags=("trace",))
def recompile_hazard(ctx: AnalysisContext) -> List[Finding]:
    from repro.analysis.trace import top_jit_eqn
    out = []
    for name, art in artifacts(ctx).items():
        if art.error is not None:
            out.append(Finding(
                rule="trace/recompile_hazard", severity="fail", target=name,
                message="entry point failed to trace (unhashable static "
                        "arg or data-dependent Python control flow?)",
                evidence={"error": art.error.splitlines()[-1]}))
            continue
        if top_jit_eqn(art.jaxpr) is None:
            out.append(Finding(
                rule="trace/recompile_hazard", severity="warn", target=name,
                message="entry point is not one top-level jitted program "
                        "(op-by-op dispatch / partial jit)",
                evidence={"n_top_eqns": len(art.jaxpr.jaxpr.eqns)}))
    return out
