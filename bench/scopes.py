"""Device time of the step program's named regions, for the per-layer
metrics that read them.

The program wraps each region of its step in a named scope and tells which
scopes each compiled instruction lies in (``repro.obs.scopes``): its table
``op_scopes()`` maps an instruction's name, the name a traced run's
``Reduced.ops`` gives a device operation, to the instruction's ``op_name``,
and ``scope_path`` reads the scopes and the pass from that.  Container
operations (``while``, ``conditional``, ``call``) are left out: their time
is their children's.  A program without the table (older than it), or with
no step registered, leaves every metric here silent.

XLA names a fusion after its root, so a fusion that crosses a region's edge
is charged to its root's region.
"""
from __future__ import annotations

import sys
import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

from bench.trace import CONTAINERS

Pred = Callable[[Tuple[str, ...], str], bool]


@dataclass
class Charged:
    """Each leaf operation of the window: (instruction, its scopes, outermost
    first, or () where none; its pass; seconds)."""
    rows: List[Tuple[str, Tuple[str, ...], str, float]]
    steps: int

    def seconds(self, pred: Pred) -> float:
        return sum(s for _, names, pass_, s in self.rows
                   if pred(names, pass_))

    @property
    def total_s(self) -> float:
        return sum(r[3] for r in self.rows)


_last: Optional[Tuple[object, Charged]] = None


def charge(m) -> Optional[Charged]:
    """The window's leaf operations of ``m.trace`` charged to the scopes of
    the most recently registered step program; None without a trace, a
    step in the window, or the program's table."""
    global _last
    if m.trace is None or m.steps <= 0:
        return None
    if _last is not None and _last[0] is m.trace:
        return _last[1]
    try:
        from repro.obs import scopes
    except ImportError:
        return None
    t0 = time.perf_counter()
    try:
        table = scopes.op_scopes()
    except LookupError:
        return None
    print(f"scopes: the step's table of {len(table)} instructions in "
          f"{time.perf_counter() - t0:.3f} s", file=sys.stderr)
    rows = []
    for instr, (_, secs) in m.trace.ops.items():
        if m.trace.kinds.get(instr) in CONTAINERS:
            continue
        op_name = table.get(instr)
        names, pass_ = scopes.scope_path(op_name) if op_name else ((), "")
        rows.append((instr, names, pass_, secs))
    _last = (m.trace, Charged(rows, m.steps))
    return _last[1]


def ms_per_step(m, pred: Pred) -> Optional[float]:
    """Device ms per window step of the operations ``pred`` selects."""
    c = charge(m)
    return None if c is None else 1e3 * c.seconds(pred) / c.steps


def share(m, pred: Pred) -> Optional[float]:
    """Percent of all leaf operation time that ``pred`` selects."""
    c = charge(m)
    if c is None or c.total_s <= 0:
        return None
    return 100.0 * c.seconds(pred) / c.total_s
