"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A traced run records the measured window with ``jax.profiler``.  Device
operations are the events of the ``XLA Ops`` line of each ``/device:TPU:n``
plane; the benchmark's own spans (``window``, ``input``, ``dispatch``,
``wait``, ``drain``) are ``TraceAnnotation`` events on the host plane.  The
reduction below works on plain ``(name, start_ns, end_ns)`` tuples, so it is
tested without a chip.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]       # (name, start_ns, end_ns)

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPANS = ("window", "input", "dispatch", "wait", "drain")
# operations that only contain others (their time is their children's)
CONTAINERS = ("while", "conditional", "call")

_HLO = re.compile(r"^%?([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")


def hlo_name(event_name: str) -> Tuple[str, str]:
    """(instruction name, op kind) of an ``XLA Ops`` event, whose name is the
    HLO text: ``%fusion.12 = bf16[..] fusion(...)`` -> ("fusion.12",
    "fusion").  Names that are not HLO text come back whole, kind ""."""
    m = _HLO.match(event_name)
    if not m:
        return event_name, ""
    return m.group(1), m.group(2)


def base_name(instr: str) -> str:
    """The instruction name without its numeric suffix."""
    return re.sub(r"\.\d+$", "", instr)


def union(intervals: Sequence[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged intervals of ``intervals`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Reduced:
    """One device's reduced trace over the measured window (times in s)."""
    window_s: float
    busy_s: float
    ops: Dict[str, Tuple[int, float]] = field(default_factory=dict)
    kinds: Dict[str, str] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, name: str) -> Tuple[int, float]:
        """(calls, seconds) of the operations whose instruction name holds
        ``name`` (a kernel's HLO name is its jitted wrapper's, prefixed as
        autodiff nests it: ``jvp_jit_sil_mse_fwd_tpu__.1``)."""
        calls, secs = 0, 0.0
        for instr, (c, s) in self.ops.items():
            if name in base_name(instr):
                calls += c
                secs += s
        return calls, secs

    def top_ops(self, n: int = 10) -> List[List]:
        leaf = [(instr, s) for instr, (c, s) in self.ops.items()
                if self.kinds.get(instr) not in CONTAINERS]
        leaf.sort(key=lambda x: -x[1])
        return [[f"{instr} ({self.kinds.get(instr, '')})", s]
                for instr, s in leaf[:n]]


def reduce_events(device_events: Sequence[Event], host_spans: Sequence[Event],
                  window: Tuple[float, float]) -> Reduced:
    """Busy union, per-operation time and idle gaps inside ``window``.

    Each idle gap is named by the innermost host span that covers its middle
    (what the host was doing while the device waited), or "host" where no
    span of the benchmark covers it."""
    lo, hi = window
    busy = union([(s, e) for _, s, e in device_events], lo, hi)
    ops: Dict[str, Tuple[int, float]] = {}
    kinds: Dict[str, str] = {}
    for name, s, e in device_events:
        if e <= lo or s >= hi:
            continue
        instr, kind = hlo_name(name)
        c, t = ops.get(instr, (0, 0.0))
        ops[instr] = (c + 1, t + (min(e, hi) - max(s, lo)) * 1e-9)
        kinds[instr] = kind
    gaps = []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            gaps.append((_label(host_spans, (s + e) / 2), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(e - s for s, e in busy) * 1e-9,
                   ops=ops, kinds=kinds, gaps=gaps)


def _label(spans: Sequence[Event], t: float) -> str:
    best: Optional[Event] = None
    for sp in spans:
        if sp[0] != "window" and sp[1] <= t <= sp[2]:
            if best is None or sp[2] - sp[1] < best[2] - best[1]:
                best = sp
    return best[0] if best else "host"


# --------------------------------------------------------------------------
# reading the profiler's xplane file
# --------------------------------------------------------------------------

def xplane_path(logdir: str) -> str:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise RuntimeError(f"no .xplane.pb under {logdir}")
    return max(found, key=os.path.getmtime)


def read_xplane(path: str, device_plane: str = DEVICE_PLANE,
                ops_line: str = OPS_LINE):
    """({device plane name: [Event]}, [host span Event]) from a trace.
    Device events are those of the lines whose name starts with
    ``ops_line``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(device_plane):
            devices[plane.name] = [
                (e.name, e.start_ns, e.end_ns) for line in plane.lines
                if line.name.startswith(ops_line) for e in line.events]
        if plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.end_ns) for line in plane.lines
                      for e in line.events if e.name in HOST_SPANS]
    return devices, spans


def reduce_trace(logdir: str, device_plane: str = DEVICE_PLANE,
                 ops_line: str = OPS_LINE) -> List[Reduced]:
    """One ``Reduced`` per device plane, over the host's ``window`` span."""
    devices, spans = read_xplane(xplane_path(logdir), device_plane, ops_line)
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if len(windows) != 1:
        raise RuntimeError(f"{len(windows)} 'window' spans in the trace")
    if not devices:
        raise RuntimeError(f"no {device_plane!r} plane in the trace")
    return [reduce_events(devices[name], spans, windows[0])
            for name in sorted(devices)]


# --------------------------------------------------------------------------
# what the readers and the result line take from the reduced traces
# --------------------------------------------------------------------------

def roofline(m, kernel: str) -> Optional[float]:
    """Percent of its roofline a kernel ran at over the window, on the
    cell's chips together: the least time its calls could take on the chip
    (per call the larger of its FLOPs over the peak and its bytes over the
    bandwidth, ``m.kernel_costs``) over the device time of its events,
    found by the kernel's name.  None without a trace, a call, or the
    family's cost of the kernel."""
    if not m.traces:
        return None
    least = secs = 0.0
    for t, costs in zip(m.traces, m.kernel_costs):
        calls, s = t.kernel(kernel)
        if calls == 0 or s <= 0:
            continue
        if kernel not in costs:
            return None
        flops, nbytes = costs[kernel]
        least += calls * max(flops / m.peak["bf16_flops_per_s"],
                             nbytes / m.peak["hbm_bytes_per_s"])
        secs += s
    return 100.0 * least / secs if secs > 0 else None


def breakdown(traces: Sequence[Reduced], n: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time and the longest idle gaps, ``n`` of each; over several chips
    each name starts with its chip (``chip2 fusion.12``)."""
    if len(traces) == 1:
        return {"device_ops": traces[0].top_ops(n),
                "idle_gaps": [[g, s] for g, s in traces[0].gaps[:n]]}
    ops = [[f"chip{c} {name}", s] for c, t in enumerate(traces)
           for name, s in t.top_ops(n)]
    gaps = [[f"chip{c} {g}", s] for c, t in enumerate(traces)
            for g, s in t.gaps[:n]]
    return {"device_ops": sorted(ops, key=lambda x: -x[1])[:n],
            "idle_gaps": sorted(gaps, key=lambda x: -x[1])[:n]}
