"""repro.obs: metrics core (host + device-resident), structured events,
span timelines, the instrumentation threaded through trainer / executor /
supervisor / engine / checkpoint, and the loadgen + metrics CLIs.

The two contracts that matter most:

* **Zero hot-path cost** — instrumentation lives entirely outside the
  jitted steps (jaxprs byte-identical, trace lint fails clean) and device
  metrics drain only at the flush boundaries the system already has.
* **Replay safety** — draining twice, or replaying executor ticks after
  ``resume_stage``, never double-counts (the same high-water discipline
  PR 8 pinned for loss logging).
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.obs import (DEPTH_BUCKETS, LOSS_BUCKETS, TID_LOOP, TID_REQ0,
                       TID_STAGE0, Counter, DeviceCounter, DeviceHistogram,
                       EventLog, Gauge, Histogram, MetricsRegistry, Tracer,
                       default_log, default_registry, set_default_log,
                       set_default_registry)
from repro.obs.registry import SCHEMA
from repro.resilience import FakeClock

# ==========================================================================
# metrics core
# ==========================================================================


def test_counter_labels_total_and_monotonicity():
    c = Counter("reqs")
    c.inc()
    c.inc(2, reason="cache")
    c.inc(3, reason="queue")
    c.inc(1, reason="cache")
    assert c.value() == 1
    assert c.value(reason="cache") == 3
    assert c.total() == 7
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    rows = list(c.rows())
    assert {tuple(sorted(r["labels"].items())): r["value"] for r in rows} \
        == {(): 1, (("reason", "cache"),): 3, (("reason", "queue"),): 3}


def test_gauge_set_and_set_max():
    g = Gauge("peak")
    g.set(2.0)
    g.set_max(5.0)
    g.set_max(3.0)
    assert g.value() == 5.0
    g.set(1.0)
    assert g.value() == 1.0
    assert g.value(stage=0) is None


def test_histogram_percentiles_vs_numpy():
    """Bucket-interpolated percentiles stay within the covering bucket's
    width of exact numpy percentiles, and never exceed the tracked max."""
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=3.0, sigma=1.0, size=2000)  # heavy tail, ~ms
    h = Histogram("lat", (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                          500.0, 1000.0, 2500.0))
    for v in vals:
        h.observe(v)
    edges = (0.0,) + h.edges + (float("inf"),)
    for q in (50, 90, 99):
        est, exact = h.percentile(q), float(np.percentile(vals, q))
        i = np.searchsorted(h.edges, exact, side="left")
        width = edges[i + 1] - edges[i]
        if not np.isinf(width):
            assert abs(est - exact) <= width, (q, est, exact, width)
        assert est <= h.max
    assert h.summary()["count"] == 2000
    assert abs(h.mean - vals.mean()) < 1e-6 * vals.mean() + 1e-9


def test_histogram_underflow_bucket_bounded_by_extrema():
    """Every observation below edges[0] (sub-ms TTFTs under a 1 ms first
    edge): percentiles interpolate inside [min, max] via the tracked
    extrema instead of reporting the unrelated first edge."""
    rng = np.random.default_rng(2)
    vals = rng.uniform(0.05, 0.4, size=500)      # all under the 1.0 edge
    h = Histogram("ttft", (1.0, 2.5, 5.0))
    for v in vals:
        h.observe(v)
    assert h.counts[0] == 500                    # everything underflowed
    for q in (50, 90, 99):
        est, exact = h.percentile(q), float(np.percentile(vals, q))
        assert h.min <= est <= h.max             # bounded by the extrema
        assert abs(est - exact) <= (h.max - h.min)   # one-bucket error
    s = h.summary()
    assert s["min"] == h.min and s["max"] == h.max
    # single observation: every percentile IS that value
    one = Histogram("one", (1.0, 2.5))
    one.observe(0.125)
    assert one.percentile(50) == one.percentile(99) == 0.125
    h = Histogram("x", (1.0, 2.0))
    assert h.percentile(50) is None and h.mean is None
    assert h.summary()["count"] == 0
    with pytest.raises(ValueError, match="ascending"):
        Histogram("bad", (2.0, 1.0))
    with pytest.raises(ValueError, match="ascending"):
        Histogram("bad", ())


def test_device_counter_drain_idempotent():
    c = DeviceCounter("ticks")
    c.add(2)
    c.add(jnp.asarray(3, jnp.int32))     # device scalar, no sync until drain
    c.drain()
    assert c.total() == 5
    c.drain()                            # idempotent: nothing left to fold
    assert c.total() == 5
    c.add(1)
    c.drain()
    assert c.total() == 6


def test_device_histogram_matches_host_histogram():
    rng = np.random.default_rng(1)
    vals = rng.uniform(0.0, 10.0, size=256).astype(np.float32)
    host = Histogram("h", LOSS_BUCKETS)
    dev = DeviceHistogram("d", LOSS_BUCKETS)
    for v in vals:
        host.observe(float(v))
    dev.observe_device(vals[:100])       # batched device observation
    for v in vals[100:]:
        dev.observe_device(jnp.asarray(v))
    dev.drain()
    assert dev.counts == host.counts
    assert dev.total == host.total
    assert abs(dev.sum - host.sum) < 1e-2
    assert abs(dev.max - host.max) < 1e-6
    before = (list(dev.counts), dev.total, dev.sum)
    dev.drain()                          # drain twice never double-counts
    assert (list(dev.counts), dev.total, dev.sum) == before
    dev.observe_device(jnp.zeros((0,)))  # empty observation is a no-op
    dev.drain()
    assert dev.total == host.total


def test_registry_get_or_create_kind_check_and_export():
    reg = MetricsRegistry()
    c = reg.counter("a", help="x")
    assert reg.counter("a") is c
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("a")
    reg.device_histogram("h", DEPTH_BUCKETS).observe_device(
        jnp.asarray([1.0, 3.0]))
    c.inc(2)
    out = reg.export()                   # export drains by default
    assert out["schema"] == SCHEMA
    by_name = {r["name"]: r for r in out["metrics"]}
    assert by_name["a"]["value"] == 2
    assert by_name["h"]["count"] == 2 and by_name["h"]["p50"] is not None
    assert reg.names() == ["a", "h"]


# ==========================================================================
# structured events
# ==========================================================================


def test_event_log_ring_bound_and_monotone_seq():
    log = EventLog(capacity=4, clock=FakeClock(5.0).monotonic)
    for i in range(10):
        log.emit("admit", slot=i)
    assert len(log) == 4
    assert log.dropped == 6
    seqs = [e.seq for e in log.records()]
    assert seqs == [6, 7, 8, 9]          # evicted records keep their numbers
    row = log.rows()[0]
    assert row == {"schema_v": 1, "seq": 6, "t": 5.0, "kind": "admit",
                   "fields": {"slot": 6}}


def test_event_kind_vocabulary_enforced():
    log = EventLog()
    with pytest.raises(ValueError, match="unknown event kind"):
        log.emit("vibes", level=11)
    with pytest.raises(ValueError, match="capacity"):
        EventLog(capacity=0)


def test_event_records_filter_and_clear():
    log = EventLog()
    log.emit("admit", slot=0)
    log.emit("retire", slot=0)
    log.emit("admit", slot=1)
    assert [e.fields["slot"] for e in log.records("admit")] == [0, 1]
    log.clear()
    assert len(log) == 0 and log.dropped == 3


# ==========================================================================
# spans / chrome trace
# ==========================================================================


def test_span_nesting_and_ordering_under_fake_clock():
    clk = FakeClock()
    tr = Tracer(clock=clk.monotonic)
    with tr.span("outer", cat="phase"):
        clk.advance(1.0)
        with tr.span("inner", cat="stage", tid=TID_STAGE0, stage=0):
            clk.advance(2.0)
        clk.advance(0.5)
    tr.instant("marker", tid=TID_STAGE0)
    by_tid = tr.by_tid()
    (outer,) = by_tid[TID_LOOP]
    inner, marker = by_tid[TID_STAGE0]
    assert (outer.ts, outer.dur) == (0.0, 3.5)
    assert (inner.ts, inner.dur) == (1.0, 2.0)
    assert outer.ts <= inner.ts and inner.end <= outer.end   # nested
    assert marker.ts == 3.5 and marker.dur == 0.0
    assert inner.args == {"stage": 0}


def test_chrome_trace_export_shape():
    clk = FakeClock()
    tr = Tracer(clock=clk.monotonic, capacity=2)
    with tr.span("a"):
        clk.advance(0.001)
    tr.instant("b", tid=3)
    tr.instant("overflow")               # past capacity: counted, dropped
    doc = tr.chrome_trace()
    evs = doc["traceEvents"]
    assert len(evs) == 2 and doc["otherData"]["dropped_spans"] == 1
    a, b = evs
    assert a["ph"] == "X" and a["ts"] == 0.0 and a["dur"] == 1000.0  # us
    assert b["ph"] == "i" and b["s"] == "t" and b["tid"] == 3
    assert all("pid" in e and "name" in e for e in evs)


def test_write_chrome_trace_is_valid_json(tmp_path):
    tr = Tracer(clock=FakeClock().monotonic)
    tr.add_span("x", 0.0, 1.0)
    path = str(tmp_path / "trace.json")
    tr.write_chrome_trace(path)
    with open(path) as f:
        assert json.load(f)["traceEvents"][0]["name"] == "x"


def test_span_is_on_the_profilers_host_plane(tmp_path):
    """A span taken while jax.profiler traces shows by its name on the
    trace's host plane, on the profiler's clock."""
    import glob
    from jax.profiler import ProfileData
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("tick 7", cat="stage", tid=TID_STAGE0, stage=0):
            jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for e in line.events}
    assert "tick 7" in names
    assert [s.name for s in tr.spans] == ["tick 7"]


# ==========================================================================
# scheduler <-> event log mapping (exactly once), open-loop take
# ==========================================================================


def test_scheduler_take_now_and_next_arrival():
    from repro.serve.scheduler import Scheduler
    s = Scheduler(4, event_log=EventLog())
    s.submit(0, "a", 1.0)
    s.submit(1, "b", 5.0)
    s.submit(2, "c", 9.0)
    assert s.next_arrival() == 1.0
    got = s.take(3, now=6.0)             # head arrived, tail still future
    assert [i for i, _, _ in got] == [0, 1]
    assert s.next_arrival() == 9.0
    assert s.take(3, now=6.0) == []
    assert [i for i, _, _ in s.take(3)] == [2]   # legacy: no arrival gate
    assert s.next_arrival() is None


def _ticking(dt=0.1):
    clk = FakeClock()

    def tick():
        t = clk.monotonic()
        clk.advance(dt)
        return t
    return tick


def test_scheduler_audits_map_to_event_log_exactly_once(serve_world):
    """Every legacy audit tuple has exactly one structured record, in the
    same order, with slot/req fields — including the reject path."""
    from repro.serve import Engine
    from repro.verify.scenarios import serve_requests
    cfg, params = serve_world()
    log = EventLog(clock=FakeClock().monotonic)
    reqs = serve_requests(cfg, lens=(8, 8), news=(8, 8))
    eng = Engine(cfg, params, max_slots=1, decode_block=4,
                 max_queue_wait_ms=250, clock=_ticking(), event_log=log)
    eng.generate(reqs)
    tuples = eng.scheduler.events
    recs = [e for e in log.records()
            if e.kind in ("admit", "retire", "reject")]
    assert len(recs) == len(tuples)
    for (kind, ident), rec in zip(tuples, recs):
        assert rec.kind == kind
        if kind == "reject":
            assert rec.fields == {"req": ident}
        else:
            assert rec.fields["slot"] == ident and "req" in rec.fields
    assert ("reject", 1) in tuples       # the queue-timeout shed happened
    begin, end = log.records("generate_begin"), log.records("generate_end")
    assert len(begin) == 1 and len(end) == 1
    assert begin[0].fields == {"n": 2}


# ==========================================================================
# engine: stats read-through, TTFT, lifecycle spans, open loop
# ==========================================================================


def test_engine_stats_dict_byte_for_byte(serve_world):
    """The legacy ``stats`` dict — now a read-through view over the
    ``serve_rejected_total`` counter — is byte-identical to the old shape."""
    from repro.serve import Engine
    from repro.verify.scenarios import serve_requests
    cfg, params = serve_world()
    eng = Engine(cfg, params, max_slots=2, decode_block=4,
                 max_cache_tokens=16)
    assert json.dumps(eng.stats, sort_keys=False) == \
        '{"rejected_cache": 0, "rejected_queue": 0, "rejected_deadline": 0}'
    reqs = serve_requests(cfg, lens=(8, 8), news=(6, 60))
    eng.generate(reqs)
    assert json.dumps(eng.stats, sort_keys=False) == \
        '{"rejected_cache": 1, "rejected_queue": 0, "rejected_deadline": 0}'
    assert eng.metrics.get("serve_rejected_total").value(reason="cache") == 1


def test_engine_metrics_and_request_spans(serve_world):
    from repro.serve import Engine
    from repro.verify.scenarios import serve_requests
    cfg, params = serve_world()
    reqs = serve_requests(cfg, lens=(8, 12, 5, 10), news=(6, 9, 4, 7))
    eng = Engine(cfg, params, max_slots=2, decode_block=4)
    outs = eng.generate(reqs)
    m = eng.metrics
    n_tok = sum(c.n_generated for c in outs)
    assert m.get("serve_tokens_total").total() == n_tok
    assert m.get("serve_requests_total").value(reason="length") == 4
    ttft = m.get("serve_ttft_ms")
    assert ttft.total == 4 and ttft.percentile(99) is not None
    assert m.get("serve_peak_slots_busy").value() == 2
    assert m.get("serve_cache_tokens").value() == eng._pool.cache_len
    assert m.get("serve_slots_busy").total > 0
    by_tid = eng.tracer.by_tid()
    for i in range(4):
        names = [s.name for s in by_tid[TID_REQ0 + i]]
        assert names == [f"req {i} queued", f"req {i} active"]
        active = by_tid[TID_REQ0 + i][1]
        assert active.args["reason"] == "length"
        assert active.args["tokens"] == outs[i].n_generated
    loop_cats = {s.cat for s in by_tid[TID_LOOP]}
    assert loop_cats == {"serve"}        # admit + decode driving-loop spans


def test_engine_open_loop_arrivals_deterministic(serve_world):
    """Open-loop arrivals with an injected clock+sleep: same tokens as the
    closed-loop run, idle gaps slept (not spun), future requests never
    admitted early."""
    from repro.serve import Engine
    from repro.verify.scenarios import serve_requests
    cfg, params = serve_world()
    reqs = serve_requests(cfg, lens=(8, 8), news=(6, 6))
    closed = Engine(cfg, params, max_slots=1,
                    decode_block=4).generate(reqs)
    clk = FakeClock()
    eng = Engine(cfg, params, max_slots=1, decode_block=4,
                 clock=clk.monotonic, sleep=clk.sleep)
    outs = eng.generate(reqs, arrivals=[0.0, 50.0])
    assert [c.tokens for c in outs] == [c.tokens for c in closed]
    assert all(c.finish_reason == "length" for c in outs)
    assert clk.sleeps and max(clk.sleeps) > 0      # idle gap was slept
    # request 1's queued span starts at its (future) arrival stamp
    q1 = [s for s in eng.tracer.by_tid()[TID_REQ0 + 1]
          if s.name == "req 1 queued"][0]
    assert q1.ts == 50.0
    with pytest.raises(ValueError, match="align"):
        eng.generate(reqs, arrivals=[0.0])


# ==========================================================================
# trainer + executor: flush-boundary publication, replay safety, trace
# ==========================================================================


def test_trainer_parallel_sil_metrics_and_trace(tiny_mlp):
    """The acceptance trace: a 2-stage parallel SIL run yields per-stage
    tick spans on tids 1+k, sequential within a stage, enclosed by the
    phase span on tid 0 — and the loss histogram drains at finalize."""
    from repro.models import mlp as MLP
    from repro.train import MLPBackend, ParallelSilPhase, Trainer
    from repro.train.backends import balanced_bounds
    cfg, data, spec = tiny_mlp(n_stages=2, epochs=(3, 3), n_train=256,
                               batch_size=64)
    be = MLPBackend(cfg, data, spec, bounds=balanced_bounds(cfg, 2))
    params = MLP.init_params(cfg, jax.random.PRNGKey(0))
    tr = Trainer(be, spec)
    tr.run([ParallelSilPhase(plan=[0, 0])], params=params,
           key=jax.random.PRNGKey(3))
    # metrics: 3 epochs x 4 batches x 2 stages, drained at the join
    loss = tr.metrics.get("train_loss")
    n_batches = 256 // 64
    assert loss.total == 3 * n_batches * 2
    assert loss.percentile(50) is not None
    ticks = tr.metrics.get("executor_ticks_total")
    assert ticks.value(stage=0) == 3 and ticks.value(stage=1) == 3
    # trace: tick spans per stage, nested inside the phase span
    by_tid = tr.tracer.by_tid()
    (phase,) = by_tid[TID_LOOP]
    assert phase.name == "ParallelSilPhase"
    for k in range(2):
        spans = by_tid[TID_STAGE0 + k]
        assert [s.name for s in spans] == ["tick 0", "tick 1", "tick 2"]
        assert [s.args["stage"] for s in spans] == [k, k, k]
        for a, b in zip(spans, spans[1:]):      # sequential, no overlap
            assert a.end <= b.ts
        assert phase.ts <= spans[0].ts and spans[-1].end <= phase.end
    doc = tr.tracer.chrome_trace()
    assert {e["tid"] for e in doc["traceEvents"]} \
        == {TID_LOOP, TID_STAGE0, TID_STAGE0 + 1}


def test_executor_replay_does_not_double_count(tmp_path, tiny_mlp):
    """Replayed ticks after resume_stage re-run the math under the metrics
    high-water guard: loss/tick series identical to the unfaulted run."""
    from repro.dist import StageExecutor, placement as P
    from repro.models import mlp as MLP
    from repro.train.backends import MLPBackend, balanced_bounds, \
        make_optimizer_for
    cfg, data, spec = tiny_mlp(n_stages=2, epochs=(3, 3), n_train=256,
                               batch_size=64)
    be = MLPBackend(cfg, data, spec, bounds=balanced_bounds(cfg, 2))
    params = MLP.init_params(cfg, jax.random.PRNGKey(0))
    sils = be.make_sils(jax.random.PRNGKey(3), spec.kappa)
    hps = [spec.stage(k) for k in range(2)]
    opts = [make_optimizer_for(hp, spec) for hp in hps]
    reg = MetricsRegistry()
    ex = StageExecutor(be, P.round_robin(2), be.split(params), sils, opts,
                       hps, shuffle=True, ckpt_dir=str(tmp_path / "ck"),
                       ckpt_every=1, metrics=reg)
    ex.run(3)
    reg.drain()
    loss, ticks = reg.get("train_loss"), reg.get("executor_ticks_total")
    base = (loss.total, loss.sum, ticks.value(stage=1))
    assert base[0] == 3 * (256 // 64) * 2 and base[2] == 3
    ex.resume_stage(1, step=1)           # roll stage 1 back two ticks...
    ex.run(3, stages=[1])                # ...and replay them
    reg.drain()
    assert (loss.total, loss.sum, ticks.value(stage=1)) == base


def test_trainer_skipped_steps_counter_high_water(monkeypatch, tiny_mlp):
    """note_skipped publishes counter DELTAS against the high-water mark:
    re-reading the same cumulative device counter adds nothing."""
    from repro.train import MLPBackend, Trainer, trainer as trainer_mod
    from repro.train.trainer import TrainState
    cfg, data, spec = tiny_mlp(n_stages=2)
    tr = Trainer(MLPBackend(cfg, data, spec), spec)
    state = TrainState(stage_params=[])
    reads = iter([2, 2, 5])
    monkeypatch.setattr(trainer_mod, "read_skipped",
                        lambda _s: jnp.asarray(next(reads), jnp.int32))
    for _ in range(3):
        tr.note_skipped(state, object(), "p", 0)
    assert state.history.meta["skipped_steps"] == {"p[0]": 5}
    assert tr.metrics.get("train_skipped_steps_total").value(
        phase="p[0]") == 5
    assert state.skipped_steps == 5


# ==========================================================================
# supervisor: health transitions + fault record mapping
# ==========================================================================


def test_supervisor_structured_events_and_health(tmp_path, tiny_mlp):
    from repro.dist import StageExecutor, placement as P
    from repro.models import mlp as MLP
    from repro.resilience import (FaultSchedule, StageCrash,
                                  SupervisedExecutor, TransientError)
    from repro.train.backends import MLPBackend, balanced_bounds, \
        make_optimizer_for
    cfg, data, spec = tiny_mlp(n_stages=2, epochs=(3, 3), n_train=256,
                               batch_size=64)
    be = MLPBackend(cfg, data, spec, bounds=balanced_bounds(cfg, 2))
    params = MLP.init_params(cfg, jax.random.PRNGKey(0))
    sils = be.make_sils(jax.random.PRNGKey(3), spec.kappa)
    hps = [spec.stage(k) for k in range(2)]
    opts = [make_optimizer_for(hp, spec) for hp in hps]
    ex = StageExecutor(be, P.round_robin(2), be.split(params), sils, opts,
                       hps, shuffle=True, ckpt_dir=str(tmp_path / "ck"))
    clk = FakeClock()
    log = EventLog(clock=clk.monotonic)
    sched = FaultSchedule([StageCrash(stage=0, tick=1),
                           TransientError(stage=1, tick=1, failures=1)])
    sup = SupervisedExecutor(ex, schedule=sched, clock=clk.monotonic,
                             sleep=clk.sleep, event_log=log)
    sup.run()
    assert ex.ticks == [3, 3]
    # exactly-once mapping: every legacy fault tuple has one record
    fault_tuples = [e for e in sup.events if e[0] == "fault"]
    fault_recs = log.records("fault")
    assert len(fault_recs) == len(fault_tuples) == 2
    for (_, kind, k, i, *_), rec in zip(fault_tuples, fault_recs):
        assert rec.fields["fault"] == kind
        assert (rec.fields["stage"], rec.fields["tick"]) == (k, i)
    # the crash recovered from checkpoint -> one recover record
    assert [(e.fields["stage"], e.fields["tick"])
            for e in log.records("recover")] == [(0, 1)]
    # health transitions: crash drives 0 through recovering->ok, the
    # transient drives 1 through retrying->ok
    hs = [(e.fields["stage"], e.fields["old"], e.fields["new"])
          for e in log.records("health")]
    assert (0, "ok", "recovering") in hs and (0, "recovering", "ok") in hs
    assert (1, "ok", "retrying") in hs and (1, "retrying", "ok") in hs
    assert sup.metrics.get("supervisor_faults_total").value(kind="crash") == 1
    assert sup.metrics.get("supervisor_recoveries_total").total() == 1


# ==========================================================================
# checkpoint events (module-level -> process-wide default log/registry)
# ==========================================================================


@pytest.fixture
def fresh_defaults():
    log, reg = EventLog(clock=FakeClock().monotonic), MetricsRegistry()
    set_default_log(log)
    set_default_registry(reg)
    yield log, reg
    set_default_log(None)
    set_default_registry(None)


def test_checkpoint_save_restore_events(tmp_path, fresh_defaults):
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    log, reg = fresh_defaults
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    d = str(tmp_path)
    save_checkpoint(d, 1, tree)
    save_checkpoint(d, 2, tree)
    restore_checkpoint(d, tree)
    saves = log.records("checkpoint_save")
    assert [e.fields["step"] for e in saves] == [1, 2]
    assert saves[0].fields["leaves"] == 1
    (restore,) = log.records("checkpoint_restore")
    assert restore.fields["step"] == 2 and restore.fields["skipped"] == 0
    assert reg.counter("checkpoint_saves_total").total() == 2
    assert reg.counter("checkpoint_restores_total").total() == 1
    # corrupt the newest step: the fallback restore reports what it skipped
    os.remove(os.path.join(d, "ckpt_00000002.json"))
    restore_checkpoint(d, tree)
    assert log.records("checkpoint_restore")[-1].fields \
        == {"step": 1, "directory": d, "skipped": 1}


def test_default_log_and_registry_singletons():
    set_default_log(None)
    set_default_registry(None)
    try:
        assert default_log() is default_log()
        assert default_registry() is default_registry()
    finally:
        set_default_log(None)
        set_default_registry(None)


# ==========================================================================
# zero hot-path cost: jaxpr identity + trace lint fail-clean
# ==========================================================================


def _decode_jaxpr(eng):
    n_slots = eng.max_slots
    pool = eng._pool_for(16)
    args = (eng.params, pool.cache, jnp.zeros((n_slots,), jnp.int32),
            jnp.zeros((n_slots,), jnp.int32),
            jnp.zeros((n_slots, 2), jnp.uint32),
            jnp.zeros((n_slots,), jnp.float32),
            jnp.zeros((n_slots,), jnp.int32),
            jnp.ones((n_slots,), jnp.float32))
    return str(jax.make_jaxpr(eng._decode_chunk(2, "greedy"))(*args))


def test_decode_jaxpr_identical_under_instrumentation(serve_world):
    """The jitted decode chunk is byte-identical whether the engine carries
    default obs objects or injected ones that have already collected data —
    instrumentation never reaches inside jit."""
    from repro.serve import Engine
    from repro.verify.scenarios import serve_requests
    cfg, params = serve_world()
    plain = Engine(cfg, params, max_slots=2, decode_block=4)
    log = EventLog()
    inst = Engine(cfg, params, max_slots=2, decode_block=4,
                  metrics=MetricsRegistry(), tracer=Tracer(), event_log=log)
    inst.generate(serve_requests(cfg, lens=(8,), news=(4,)))  # collect data
    assert _decode_jaxpr(plain) == _decode_jaxpr(inst)


def test_trace_lint_fail_clean_on_instrumented_entrypoints():
    """The registered hot paths — built through the instrumented classes —
    carry zero host callbacks: the host_transfer rule reports no failures
    for the guarded MLP epoch, the parallel LM stage step, or the fused
    decode chunk."""
    from repro.analysis import AnalysisContext, entrypoints, get_rule, \
        run_rule
    from repro.analysis.rules_trace import host_transfer  # noqa: F401
    from repro.analysis.trace import trace
    names = {"train/mlp_guarded_epoch": "paper_mlp",
             "train/lm_parallel_stage_step": "qwen2-1.5b",
             "serve/decode_chunk": "qwen2-1.5b"}
    for arch in sorted(set(names.values())):
        ctx = AnalysisContext(arch=arch)
        targets = [t for t in entrypoints.build_targets(ctx)
                   if names.get(t.name) == arch]
        assert targets, f"entry points missing on {arch}"
        ctx.cache[entrypoints.cache_key(ctx)] = {t.name: trace(t)
                                                 for t in targets}
        res = run_rule(get_rule("trace/host_transfer"), ctx)
        assert res.error is None, res.error
        fails = [f for f in res.findings if f.severity == "fail"]
        assert fails == [], fails


# ==========================================================================
# loadgen + metrics CLI
# ==========================================================================


def test_loadgen_tiny_report_and_metrics_cli(tmp_path):
    from repro.launch.loadgen import run_loadgen, summarize
    from repro.launch.metrics import main as metrics_main, validate_report
    report = run_loadgen("tiny", seed=0, n=4, rate=50.0,
                         trace_path=str(tmp_path / "trace.json"))
    assert validate_report(report) == []
    slo = report["slo"]
    assert slo["ttft_ms"]["count"] == 3          # 4 requests, 1 oversized
    assert slo["ttft_ms"]["p50"] is not None
    assert slo["ttft_ms"]["p99"] is not None
    assert slo["tokens_per_s"] > 0
    assert slo["shed"]["rejected_cache"] == 1    # deterministic cache shed
    assert slo["shed"]["rate"] == pytest.approx(0.25)
    assert slo["completed"] == 3
    assert report["events"]["by_kind"]["admit"] == 3
    assert "tok/s" in summarize(report)
    with open(tmp_path / "trace.json") as f:
        assert json.load(f)["traceEvents"]
    path = str(tmp_path / "BENCH.json")
    with open(path, "w") as f:
        json.dump(report, f)
    assert metrics_main(["--check", path]) == 0
    assert metrics_main([path]) == 0             # summary mode
    assert metrics_main(["--dump", path]) == 0


def test_metrics_cli_check_fails_on_violations(tmp_path):
    from repro.launch.metrics import main as metrics_main, validate_report
    bad = {"schema": "nope", "metrics": [
        {"name": "h", "kind": "histogram", "count": 5,
         "p50": None, "p90": None, "p99": None},
        {"name": "c", "kind": "counter"},
    ]}
    errs = validate_report(bad)
    assert any("schema" in e for e in errs)
    assert any("empty percentile" in e for e in errs)
    assert any("lacks value" in e for e in errs)
    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        json.dump(bad, f)
    assert metrics_main(["--check", path]) == 1


# ==========================================================================
# named regions of the step program (repro.obs.scopes)
# ==========================================================================

_LAYER = "jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint"


@pytest.mark.parametrize("op_name,names,pass_", [
    ("jit(step)/jvp(layers)/while/body/closed_call/mlp/dot_general",
     ("layers", "mlp"), "forward"),
    (_LAYER + "/rematted_computation/attention/flash/pallas_call",
     ("layers", "attention", "flash"), "remat"),
    (_LAYER + "/mlp/transpose", ("layers", "mlp"), "backward"),
    # the custom VJP's body, and the recomputation inside it
    (_LAYER + "/attention/flash/jvp(jit(chunked_attention))/while/body/exp",
     ("layers", "attention", "flash"), "backward"),
    (_LAYER + "/attention/flash/flash/transpose(jvp(jit(chunked_attention)))"
     "/while/body/closed_call/checkpoint/rematted_computation/exp",
     ("layers", "attention", "flash"), "backward"),
    ("jit(step)/transpose(jvp(ce))/while/body/reduce_max", ("ce",),
     "backward"),
    ("jit(step)/optimizer/mul", ("optimizer",), "forward"),
    # a jitted function's name is no scope, even one spelled like a scope
    ("jit(step)/sil_mse/jit(sil_mse)/dot_general", ("sil_mse",), "forward"),
    ("jit(step)/jit(sil_mse)/dot_general", (), "forward"),
    ("jit(step)/transpose(jvp())/mul", (), "backward"),
    ("jit(step)/jvp(layers)/while/body/dynamic_update_slice", ("layers",),
     "forward"),
])
def test_scope_path(op_name, names, pass_):
    from repro.obs.scopes import scope_path
    assert scope_path(op_name) == (names, pass_)


_HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %exp.1 = f32[8]{0} exponential(%param_0), metadata={op_name="jit(step)/mlp/exp"}
  ROOT %convert.2 = f32[8]{0} convert(%exp.1)
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %copy.3 = f32[8]{0} copy(%arg)
  %named.4 = f32[8]{0} add(%copy.3, %copy.3), metadata={op_name="jit(step)/jvp(layers)/while/body/attention/add"}
  ROOT %tuple.5 = (s32[], f32[8]{0}) tuple(%arg, %named.4)
}

%cond (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.6 = pred[] compare(%arg.1, %arg.1), direction=LT
}

ENTRY %main.7 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  %copy.8 = f32[8]{0} copy(%x.1)
  %while.9 = (s32[], f32[8]{0}) while(%copy.8), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(layers)/while"}
  ROOT %fusion.10 = f32[8]{0} fusion(%while.9), kind=kLoop, calls=%fused_computation
}
"""


def test_hlo_op_names_fill_gaps_from_fused_root_and_caller():
    from repro.obs.scopes import hlo_op_names
    table = hlo_op_names(_HLO)
    assert table["named.4"] == \
        "jit(step)/jvp(layers)/while/body/attention/add"
    # unnamed in the loop body and condition: the while's op_name
    assert table["copy.3"] == table["lt.6"] == "jit(step)/jvp(layers)/while"
    # a fusion whose root the compiler made: its last named instruction
    assert table["fusion.10"] == "jit(step)/mlp/exp"
    # unnamed in the entry computation: left out
    assert "copy.8" not in table and "x.1" not in table


def _three_stage_lm():
    """A 3-stage tiny LM: stage 0, one interior stage and the last."""
    from repro.configs import get
    from repro.core import partition
    from repro.models import model as M
    from repro.train.backends import LMBackend, make_optimizer_for
    from repro.train.spec import StageSpec, TrainSpec
    cfg = get("qwen2-1.5b", smoke=True).replace(n_layers=3)
    plan = partition.make_plan(cfg, 3)
    spec = TrainSpec(n_stages=3, kappa=1.0, stages=tuple(
        StageSpec(steps=1, lr=1e-3, optimizer="adamw") for _ in range(3)))
    be = LMBackend(cfg, plan, None, spec)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    sils = be.make_sils(jax.random.PRNGKey(1), spec.kappa)
    opt = make_optimizer_for(spec.stage(0), spec)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                              cfg.vocab_size)
    return be, be.split(params), sils, opt, {"tokens": toks, "labels": toks}


def _run_stage(be, sps, sils, opt, batch, k):
    """Build stage k's step as a deployment does and take one step."""
    sp = jax.device_put(sps[k])
    st = opt.init(be.trainable(sp))
    if k == 0:
        step = be.build_stage_step(0, opt, sils[0])
        step(sp, st, batch, batch["labels"])
        return step, "lm_stage_step.0"
    target = sils[k] if k < be.n_stages - 1 else None
    step = be.build_parallel_stage_step(k, opt, sils[k - 1], target)
    step(sp, st, batch["labels"])
    return step, f"lm_parallel_stage_step.{k}"


@pytest.mark.parametrize("k,ran", [
    (0, {"embed", "layers", "attention", "flash", "mlp", "sil_mse",
         "optimizer"}),
    (1, {"sil_input", "layers", "attention", "flash", "mlp", "sil_mse",
         "optimizer"}),
    (2, {"sil_input", "layers", "attention", "flash", "mlp", "ce",
         "optimizer"}),
])
def test_op_scopes_of_a_stage_step(k, ran):
    """Every region the step runs is in its compiled program's table; the
    attention core shows its forward, its recomputation and its backward,
    and the optimizer runs after the backward."""
    from repro.obs import scopes
    world = _three_stage_lm()
    _, name = _run_stage(*world, k)
    table = scopes.op_scopes(name)
    assert table is scopes.op_scopes(name)       # compiled once
    seen = {}
    for op_name in table.values():
        names, pass_ = scopes.scope_path(op_name)
        for n in names:
            seen.setdefault(n, set()).add(pass_)
    assert set(seen) == ran
    assert seen["flash"] == {"forward", "remat", "backward"}
    assert seen["optimizer"] == {"forward"}


def test_register_records_once_and_op_scopes_follows_the_latest(monkeypatch):
    from repro.obs import scopes
    monkeypatch.setattr(scopes, "_PROGRAMS", {})
    with pytest.raises(LookupError):
        scopes.op_scopes()
    calls = []
    real = scopes.register
    monkeypatch.setattr(scopes, "register",
                        lambda *a: calls.append(a[0]) or real(*a))
    be, sps, sils, opt, batch = _three_stage_lm()
    step, name = _run_stage(be, sps, sils, opt, batch, 0)
    sp = jax.device_put(sps[0])
    step(sp, opt.init(be.trainable(sp)), batch, batch["labels"])
    assert calls == [name] and list(scopes._PROGRAMS) == [name]
    # shapes only: the registry holds no device array of the step
    args = scopes._PROGRAMS[name].args
    assert not any(isinstance(x, jax.Array)
                   for x in jax.tree_util.tree_leaves(args))
    first = scopes.op_scopes()
    assert first is scopes.op_scopes(name)
    # a call under a transformation is not the program that runs
    last = be.build_parallel_stage_step(2, opt, sils[1], None)
    sp2 = jax.device_put(sps[2])
    st2 = opt.init(be.trainable(sp2))
    jax.make_jaxpr(last)(sp2, st2, batch["labels"])
    assert list(scopes._PROGRAMS) == [name]
    last(sp2, st2, batch["labels"])
    assert list(scopes._PROGRAMS) == [name, "lm_parallel_stage_step.2"]
    latest = scopes.op_scopes()
    assert latest is scopes.op_scopes("lm_parallel_stage_step.2")
    assert any("ce" in scopes.scope_path(o)[0] for o in latest.values())
    assert not any("ce" in scopes.scope_path(o)[0] for o in first.values())
    with pytest.raises(LookupError):
        scopes.op_scopes("no_such_step")
