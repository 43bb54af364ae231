"""One run of one cell: set-up, the measured window, the metrics, and the
comparison that decides ``correct``.

Everything that belongs to one model family, configuration, traffic mix or
metric lives in a file of its own, found by the name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` (the ``file`` of the configuration),
  whose ``"family"`` names its model family;
* ``bench/families/<family>.py``, the family: how its configuration reads,
  its state, the program adapter, its plain reference and its counts (the
  contract is in ``bench/families/dense.py``'s docstring);
* ``bench/traffic/<traffic>.json``;
* ``bench/limits/<cell>.json``, the limits of the cell's comparison;
* ``bench/metrics/<metric>.py``, a reader ``read(m) -> float | None`` of
  the ``Measured`` record below.

So a new family, configuration, cell or metric is new files and an entry
in ``BENCHMARK.json``, with no edit to this module.  A cell runs one stage
on one chip (the family's ``StageRun``), or, where its traffic says
``"stage": "all"``, every stage at once, stage k on chip k
(``bench/deployment.py``).
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


def _load_module(path: str, name: str):
    """The module at ``path``, executed anew and registered as ``name``
    (a dataclass looks its module up there)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: str, metric: str):
    return _load_module(bench_file(root, "metrics", f"{metric}.py"),
                        f"bench_metric_{metric}").read


def load_family(root: str, family: str):
    """The family module ``bench/families/<family>.py``, loaded by path."""
    path = os.path.join(root, "bench", "families", f"{family}.py")
    if not os.path.exists(path):
        known = sorted(f[:-3] for f in os.listdir(os.path.dirname(path))
                       if f.endswith(".py"))
        raise ValueError(f"no model family {family!r}: {path} does not exist "
                         f"(known: {known})")
    return _load_module(path, f"bench_family_{family}")


def load_config(root: str, entry: dict):
    """(family module, its config object) of a ``configs`` entry."""
    path = os.path.join(root, entry["file"])
    with open(path) as f:
        raw = json.load(f)
    if "family" not in raw:
        raise ValueError(f"{path} names no \"family\"")
    family = load_family(root, raw["family"])
    return family, family.parse(raw, path)


def peaks_for(root: str, device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    with open(bench_file(root, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json"
                       f" (known: {sorted(table)})")
    return table[device_kind]


@dataclass
class Cell:
    """A cell's files, read: everything a run needs but the chip."""
    name: str
    entry: dict                  # its BENCHMARK.json workload
    family: object               # its configuration's family module
    cfg: object                  # the family's config object
    traffic: object              # bench.traffic.Traffic
    limits: Dict[str, Optional[float]]
    loss_steps: Dict[str, Optional[list]]   # by prefix of the numbers

    def prefix(self, k: int) -> str:
        """The prefix of stage k's numbers: none for a one-stage cell,
        ``s<k>.`` where the cell runs every stage."""
        return f"s{k}." if self.traffic.deployment else ""

    def stages(self) -> List[int]:
        return self.traffic.stages(self.cfg.stages)


def load_cell(root: str, name: str) -> Cell:
    from bench import check
    from bench.traffic import load_traffic

    bench = load_benchmark(root)
    entry = workload(bench, name)
    family, cfg = load_config(root, config_entry(bench, entry["config"]))
    traffic = load_traffic(bench_file(root, "traffic",
                                      f"{entry['traffic']}.json"),
                           entry["traffic"])
    cell = Cell(name, entry, family, cfg, traffic, {}, {})
    cell.limits, cell.loss_steps = check.load_limits(
        bench_file(root, "limits", f"{name}.json"),
        [cell.prefix(k) for k in cell.stages()])
    return cell


def make_run(cell: Cell, seed: int, devices, *, half_batch: bool = False):
    """The system under test, set up for ``seed``: the family's one-stage
    run, or every stage through the program's executor."""
    if cell.traffic.deployment:
        from bench.deployment import DeploymentRun
        return DeploymentRun(cell.family, cell.cfg, cell.traffic, seed,
                             devices, half_batch=half_batch)
    return cell.family.StageRun(cell.cfg, cell.traffic, seed,
                                half_batch=half_batch)


def reference_readings(cell: Cell, stages, devices, batches, seed: int,
                       precision: str = "fp32") -> Dict[int, dict]:
    """{stage: the family's reference readings}; several stages run at
    once, each on its own chip."""
    n = cell.traffic.check_steps
    if len(stages) == 1:
        return {stages[0]: cell.family.readings(cell.cfg, stages[0], batches,
                                                seed, n, precision)}
    import jax
    from concurrent.futures import ThreadPoolExecutor

    def one(k, dev):
        with jax.default_device(dev):
            return cell.family.readings(cell.cfg, k, batches, seed, n,
                                        precision)
    with ThreadPoolExecutor(len(stages)) as pool:
        futures = {k: pool.submit(one, k, dev)
                   for k, dev in zip(stages, devices)}
        return {k: f.result() for k, f in futures.items()}


def compare(cell: Cell, progs: Dict[int, dict], refs: Dict[int, dict]):
    """{number: (value, where)} over the cell's stages, each stage's
    numbers under its prefix."""
    from bench import check
    out = {}
    for k, ref in refs.items():
        p = cell.prefix(k)
        for n, v in check.numbers(progs[k], ref, cell.loss_steps[p]).items():
            out[p + n] = v
    return out


# --------------------------------------------------------------------------
# what a run measured, for the metric readers
# --------------------------------------------------------------------------

@dataclass
class Measured:
    """Per-chip fields list the cell's chips in order; chip c runs stage
    ``stages[c]``."""
    cfg: object                  # the family's config object
    traffic: object              # bench.traffic.Traffic
    stages: List[int]
    chips: int
    peak: dict                   # the chip's row of bench/peaks.json
    steps: int                   # steps (ticks) completed in the window
    window_s: float
    tokens_per_step: int         # tokens the model trained in one step
    flops_per_step: Dict[str, int]   # summed over the stages
    kernel_costs: List[Dict[str, tuple]]   # per chip: kernel -> (FLOPs, bytes)
    bytes_in_use_start: List[Optional[int]]
    temp_bytes: List[int]        # per chip: its step's temporaries
    setup_s: float
    compile_s: float
    input_s: List[float]         # host seconds building and putting inputs
    dispatch_s: List[float]      # host seconds in the dispatch span
    traces: Optional[List[object]] = None   # bench.trace.Reduced per chip

    @property
    def trace(self):
        """The first chip's reduced trace (a one-chip cell's only one)."""
        return self.traces[0] if self.traces else None


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


def _sum_counts(counts: List[Dict[str, int]]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for c in counts:
        for key, v in c.items():
            out[key] = out.get(key, 0) + v
    return out


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
    underneath, for the tests that the comparison fails it; "unchanged"
    leaves a middle stage's state unchanged where the cell runs several.
    Tests without a chip also pass ``require_chip=False``, a ``peak`` row,
    the trace's host plane and XLA line for ``device_plane`` and
    ``ops_line``, and ``cache=False``."""
    from bench import check

    cell = load_cell(root, name)
    bench = load_benchmark(root)
    kind = "per_layer" if trace else "end_to_end"
    wanted = metrics_for(bench, name, kind)
    readers = {m["name"]: load_reader(root, m["name"]) for m in wanted}
    traffic = cell.traffic

    import jax
    t_imports = time.perf_counter()
    if cache:
        configure_jax_cache(root)
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu" or len(devices) < cell.entry["chips"]:
            raise NoChip(f"cell {name} needs {cell.entry['chips']} TPU "
                         f"chip(s); JAX finds {len(devices)} {dev.platform} "
                         "device(s)")
        peak = peaks_for(root, dev.device_kind)
    clock = CompileClock()
    marks = [("imports", t_imports), ("runtime", time.perf_counter())]

    run = make_run(cell, seed, devices, half_batch=fault == "half_batch")
    chips = run.devices
    marks.append(("state", time.perf_counter()))
    prog = run.first_steps()
    marks.append(("first steps", time.perf_counter()))
    temp = run.temp_bytes()
    marks.append(("memory analysis", time.perf_counter()))
    if fault == "unchanged":
        k = run.stages[len(run.stages) // 2]
        prog[k] = dict(prog[k], change={n: 0.0 for n in prog[k]["change"]})

    # ---- the measured window --------------------------------------------
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    compiles0 = clock.compiles()
    in_use = [_bytes_in_use(d) for d in chips]
    n_check = traffic.check_steps
    dispatch_s: List[float] = []
    ann = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    n = 0
    with ann("window"):
        while True:
            with ann("input"):
                x = run.feed(n_check + n)
            with ann("dispatch"):
                td = time.perf_counter()
                run.dispatch(x)
                dispatch_s.append(time.perf_counter() - td)
            n += 1
            if n >= 2:
                with ann("wait"):
                    run.wait()
            if time.perf_counter() - t0 >= seconds:
                break
        with ann("drain"):
            run.drain()
    t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = clock.compiles() - compiles0
    if compiles_in_window:
        raise RuntimeError(f"{compiles_in_window} programs compiled inside "
                           "the measured window")
    window_losses = run.window_losses(n_check)
    failed = sum(1 for v in window_losses if not math.isfinite(v))
    peak_bytes = _peak_bytes(chips)

    reduced = None
    if trace:
        from bench import trace as trace_lib
        try:
            reduced = trace_lib.reduce_trace(tdir, device_plane,
                                             ops_line)[:len(chips)]
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    fam, cfg, seq = cell.family, cell.cfg, traffic.seq
    measured = Measured(
        cfg=cfg, traffic=traffic, stages=run.stages, chips=len(chips),
        peak=peak, steps=n, window_s=t1 - t0,
        tokens_per_step=run.rows * seq,
        flops_per_step=_sum_counts([fam.step_flops(cfg, k, run.rows, seq)
                                    for k in run.stages]),
        kernel_costs=[fam.kernel_costs(cfg, k, run.rows, seq)
                      for k in run.stages],
        bytes_in_use_start=in_use, temp_bytes=temp, setup_s=setup_s,
        compile_s=clock.seconds(), input_s=run.input_s[-n:],
        dispatch_s=dispatch_s, traces=reduced)
    metrics = {}
    for m in wanted:
        v = readers[m["name"]](measured)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # ---- the comparison, with the chips to the reference ----------------
    batches = run.batches
    run.free()
    ref = reference_readings(cell, run.stages, chips, batches, seed)
    if fault == "control":
        prog = reference_readings(cell, run.stages, chips, batches, seed,
                                  precision="fp8")
    ok, report = check.judge(compare(cell, prog, ref), cell.limits)
    correct = bool(ok and failed == 0 and n > 0)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": n, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced:
        from bench import trace as trace_lib
        device["busy_s"] = sum(r.busy_s for r in reduced) / len(reduced)
        device["window_s"] = reduced[0].window_s
        result["breakdown"] = trace_lib.breakdown(reduced, 10)
    last = t_start
    parts = []
    for what, t in marks:
        parts.append(f"{what} {t - last:.3f}")
        last = t
    print(f"set-up: {', '.join(parts)}, to the window {t0 - last:.3f} s",
          file=log)
    for k in run.stages:
        print(f"cell {name} seed {seed} stage {k}: {n} steps in "
              f"{t1 - t0:.3f} s, set-up {setup_s:.3f} s "
              f"({measured.compile_s:.3f} s compiling), first losses "
              f"{prog[k]['losses']} reference {ref[k]['losses']}; change "
              f"compared on {len(check.moved_leaves(ref[k]))} of "
              f"{len(ref[k]['grad'])} leaves", file=log)
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
