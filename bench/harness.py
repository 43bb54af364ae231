"""One run of one cell: set-up, the measured window, the metrics, and the
comparison that decides ``correct``.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` (the ``file`` of the configuration);
* ``bench/traffic/<traffic>.json``;
* ``bench/limits/<cell>.json``, the limits of the cell's comparison;
* ``bench/metrics/<metric>.py``, a reader ``read(m) -> float | None`` of
  the ``Measured`` record below.

So a new configuration, cell or metric is a new file and an entry in
``BENCHMARK.json``, with no edit to this module.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


# --------------------------------------------------------------------------
# the benchmark's files
# --------------------------------------------------------------------------

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it under ``workloads``, and those that list no cells."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def bench_file(root: str, *parts: str) -> str:
    path = os.path.join(root, "bench", *parts)
    if not os.path.exists(path):
        raise FileNotFoundError(f"missing benchmark file {path}")
    return path


def load_reader(root: str, metric: str):
    path = bench_file(root, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(root: str, device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    with open(bench_file(root, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json"
                       f" (known: {sorted(table)})")
    return table[device_kind]


# --------------------------------------------------------------------------
# what a run measured, for the metric readers
# --------------------------------------------------------------------------

@dataclass
class Measured:
    cfg: object                  # bench.model.ModelCfg
    traffic: object              # bench.traffic.Traffic
    stage: int
    peak: dict                   # the chip's row of bench/peaks.json
    steps: int                   # steps completed in the window
    window_s: float
    tokens_per_step: int
    flops_per_step: Dict[str, int]
    bytes_in_use_start: Optional[int]
    temp_bytes: int
    setup_s: float
    compile_s: float
    input_s: List[float]         # host seconds building and putting inputs
    trace: Optional[object] = None       # bench.trace.Reduced, device 0


class CompileClock:
    """Host seconds JAX spends lowering and compiling programs (jax.monitoring
    events; tracing is left out: its events nest and it is small)."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.events: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.events.append((event, duration))

    def seconds(self) -> float:
        return sum(d for _, d in self.events)

    def compiles(self) -> int:
        return sum(1 for e, _ in self.events if e == self.EVENTS[1])


def configure_jax_cache(root: str) -> str:
    """JAX's persistent compile cache at the fixed path ``<root>/.jax_cache``
    (``repro.runtime.enable_compile_cache``'s own default), every program
    cached, so that only a checkout's first run compiles."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, root: str = ROOT, require_chip: bool = True,
             peak: Optional[dict] = None, fault: Optional[str] = None,
             device_plane: str = "/device:TPU:", ops_line: str = "XLA Ops",
             cache: bool = True, log=sys.stderr) -> dict:
    """Run cell ``name`` once and return its result line as a dict.

    ``fault`` ("half_batch", "unchanged" or "control") breaks the timed path
    underneath, for the tests that the comparison fails it.  Tests without
    a chip also pass ``require_chip=False``, a ``peak`` row, the trace's
    host plane and XLA line for ``device_plane`` and ``ops_line``, and
    ``cache=False``."""
    from bench import check, reference
    from bench import model as bm
    from bench.traffic import load_traffic

    bench = load_benchmark(root)
    cell = workload(bench, name)
    cfg = bm.load_config(os.path.join(root, config_entry(
        bench, cell["config"])["file"]))
    traffic = load_traffic(bench_file(root, "traffic",
                                      f"{cell['traffic']}.json"),
                           cell["traffic"])
    limits, loss_steps = check.load_limits(
        bench_file(root, "limits", f"{name}.json"))
    kind = "per_layer" if trace else "end_to_end"
    wanted = metrics_for(bench, name, kind)
    readers = {m["name"]: load_reader(root, m["name"]) for m in wanted}

    import jax
    t_imports = time.perf_counter()
    if cache:
        configure_jax_cache(root)
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu" or len(devices) < cell["chips"]:
            raise NoChip(f"cell {name} needs {cell['chips']} TPU chip(s); "
                         f"JAX finds {len(devices)} {dev.platform} device(s)")
        peak = peaks_for(root, dev.device_kind)
    clock = CompileClock()
    marks = [("imports", t_imports), ("runtime", time.perf_counter())]

    from bench.stage import StageRun
    run = StageRun(cfg, traffic, seed, half_batch=fault == "half_batch")
    k = run.k
    marks.append(("state", time.perf_counter()))
    prog = run.first_steps()
    marks.append(("first steps", time.perf_counter()))
    temp = run.temp_bytes()
    marks.append(("memory analysis", time.perf_counter()))
    if fault == "unchanged":
        prog = dict(prog, change={n: 0.0 for n in prog["change"]})

    # ---- the measured window --------------------------------------------
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    compiles0 = clock.compiles()
    in_use = _bytes_in_use(dev)
    n_check = traffic.check_steps
    ann = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    n = 0
    with ann("window"):
        while True:
            with ann("input"):
                x = run.feed(n_check + n)
            with ann("dispatch"):
                loss = run.dispatch(x)
            n += 1
            if n >= 2:
                with ann("wait"):
                    run.losses[-2].block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with ann("drain"):
            jax.block_until_ready((run.params, run.opt_state, loss))
    t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = clock.compiles() - compiles0
    if compiles_in_window:
        raise RuntimeError(f"{compiles_in_window} programs compiled inside "
                           "the measured window")
    window_losses = [float(v) for v in jax.device_get(run.losses[n_check:])]
    failed = sum(1 for v in window_losses if not math.isfinite(v))
    peak_bytes = _peak_bytes(devices[:cell["chips"]])

    reduced = None
    if trace:
        from bench import trace as trace_lib
        try:
            reduced = trace_lib.reduce_trace(tdir, device_plane, ops_line)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    measured = Measured(
        cfg=cfg, traffic=traffic, stage=k, peak=peak, steps=n,
        window_s=t1 - t0, tokens_per_step=run.rows * traffic.seq,
        flops_per_step=bm.step_flops(cfg, k, run.rows, traffic.seq),
        bytes_in_use_start=in_use, temp_bytes=temp, setup_s=setup_s,
        compile_s=clock.seconds(), input_s=run.input_s[-n:],
        trace=reduced[0] if reduced else None)
    metrics = {}
    for m in wanted:
        v = readers[m["name"]](measured)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # ---- the comparison, with the chip to the reference -----------------
    batches = run.batches
    run.free()
    ref = reference.readings(cfg, k, batches, seed, n_check)
    if fault == "control":
        prog = reference.readings(cfg, k, batches, seed, n_check,
                                  precision="fp8")
    ok, report = check.judge(check.numbers(prog, ref, loss_steps), limits)
    correct = bool(ok and failed == 0 and n > 0)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": n, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced:
        device["busy_s"] = sum(r.busy_s for r in reduced) / len(reduced)
        device["window_s"] = reduced[0].window_s
        result["breakdown"] = {
            "device_ops": reduced[0].top_ops(10),
            "idle_gaps": [[g, s] for g, s in reduced[0].gaps[:10]]}
    last = t_start
    parts = []
    for what, t in marks:
        parts.append(f"{what} {t - last:.3f}")
        last = t
    print(f"set-up: {', '.join(parts)}, to the window {t0 - last:.3f} s",
          file=log)
    print(f"cell {name} seed {seed} stage {k}: {n} steps in "
          f"{t1 - t0:.3f} s, set-up {setup_s:.3f} s "
          f"({measured.compile_s:.3f} s compiling), first losses "
          f"{prog['losses']} reference {ref['losses']}; change compared on "
          f"{len(check.moved_leaves(ref))} of {len(ref['grad'])} leaves",
          file=log)
    result["check"] = {n_: {"value": r["value"], "limit": r["limit"]}
                       for n_, r in report.items()}
    result["_report"] = report
    return result


def _bytes_in_use(dev) -> Optional[int]:
    stats = dev.memory_stats()
    return None if not stats else int(stats["bytes_in_use"])


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start)
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    report = result.pop("_report")
    sys.stdout.flush()
    for n, r in report.items():
        lim = "none (reported only)" if r["limit"] is None else repr(r["limit"])
        print(f"check {n}: {r['value']!r} limit {lim} (worst at {r['at']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0
