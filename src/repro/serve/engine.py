"""The serving engine: continuous batching over a contiguous or paged cache pool.

``Engine.generate(requests)`` runs prefill-on-admit + a fused multi-token
decode inner loop:

* Admission: queued requests are grouped by prompt length (mixed-length
  prompts never pad each other) and each group runs ONE jitted call that
  prefills, samples the first tokens, and scatters caches + per-slot decode
  state into the free slots.
* Decode: between scheduler events the engine runs ONE jitted ``lax.scan``
  of up to ``decode_block`` steps with sampling folded in — per-slot
  positions, PRNG keys, temperature/top-k/top-p all live on device, so
  nothing round-trips through the host per token.  The chunk length tracks
  the nearest guaranteed retirement (rounded to a power of two so the
  compile set stays ~log2(decode_block); overshoot is truncated at sync).
* Retirement: at each sync the host checks EOS / max-token per slot,
  retires finished requests, and admits queued ones into the freed slots.

Modes: pass ``plan=`` + per-stage params for PartitionPlan-aware serving
(paper partitions as deployable stages), and/or ``policy=`` (a
``launch.sharding.Policy``) to route params and the cache pool through the
production mesh plumbing.

Known limit: admission compiles one prefill program per distinct prompt
length (decode programs are bounded at ~log2(decode_block) per sampling
mode, and the cache pool is bucketed).  Bucketing prompts needs left-pad
masking in the prefill attention path — not built yet; until then, callers
with adversarially varied prompt lengths should quantize lengths upstream.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.models import model as M
from repro.obs.events import EventLog, default_log
from repro.obs.metrics import DEPTH_BUCKETS, TTFT_MS_BUCKETS
from repro.obs.registry import MetricsRegistry
from repro.obs.trace import TID_LOOP, TID_REQ0, Tracer
from repro.serve import sampling, staged
from repro.serve.api import Completion, Request, StreamEvent
from repro.serve.kv_cache import (GARBAGE_BLOCK, CachePool, PagedCachePool,
                                  place_blocks, place_rows)
from repro.serve.scheduler import Scheduler


class Engine:
    """Serves one model (or one PartitionPlan stage chain) from resident
    params.  Thread-compatible with one ``generate`` call at a time."""

    def __init__(self, cfg, params=None, *, key=None, max_slots: int = 4,
                 decode_block: int = 16, plan=None, stage_params=None,
                 policy=None, precision=None,
                 max_queue_wait_ms: Optional[float] = None,
                 max_cache_tokens: Optional[int] = None, clock=None,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 event_log: Optional[EventLog] = None, sleep=None,
                 paged: bool = False, block_size: int = 16):
        """precision: optional repro.precision preset name or PrecisionPolicy
        — re-dtypes the serving compute path (activations + the slot cache
        pool run in the policy's compute dtype; params keep their storage
        dtype; sampling always sees fp32 logits).

        Degradation knobs (repro.resilience; all default OFF, preserving
        exact legacy behavior):
        max_queue_wait_ms — a request still queued after this long is
        rejected instead of waiting forever behind a stalled batch.
        max_cache_tokens — admission control under cache pressure: a request
        whose prompt+generation span exceeds this never enters the queue
        (rejected up front), and the grow-only pool is capped at it.
        clock — injectable ``time.monotonic`` substitute (deterministic
        deadline tests; see ``resilience.FakeClock``).

        Observability (repro.obs; all optional):
        metrics — a ``MetricsRegistry``; defaults to a PRIVATE registry so
        the cumulative-per-engine semantics of the legacy ``stats`` dict
        are preserved (pass a shared one to aggregate, as loadgen does).
        tracer — span timelines (request lifecycles on tid 1000+i, the
        admit/decode driving loop on tid 0); defaults to a fresh ``Tracer``
        on this engine's clock.
        event_log — structured event stream shared with the scheduler;
        defaults to the process-wide ``obs.default_log()``.
        sleep — injectable ``time.sleep`` substitute, used only by the
        open-loop ``arrivals=`` path in ``generate``.

        paged — serve from a block-paged cache (``PagedCachePool``):
        attention K/V pages over ``block_size``-token physical blocks with
        per-request block tables, ``max_cache_tokens`` becomes an exact
        total-token budget shared by all in-flight requests, and common
        prompt prefixes are prefilled once (shared-prefix reuse).  OFF by
        default — the contiguous path is byte-identical to before.  The
        contiguous pool's decode kernel walks its cache in ``block_size``
        tiles too, so the two pools decode the same tokens."""
        if precision is not None:
            from repro.precision import get_policy
            cfg = get_policy(precision).apply_to_model(cfg)
        if (plan is None) != (stage_params is None):
            raise ValueError("pass plan= and stage_params= together")
        if params is not None and stage_params is not None:
            raise ValueError("pass either joined params= or staged "
                             "stage_params=, not both")
        if params is None and stage_params is None:
            # random weights only on explicit opt-in (benches/smoke tests) —
            # a serving engine must never silently invent its weights
            if key is None:
                raise ValueError("pass params= / stage_params=, or key= to "
                                 "explicitly serve random-init weights")
            params = M.init_params(cfg, key)
        self.cfg = cfg
        self.max_slots = max_slots
        self.decode_block = decode_block
        self.paged = paged
        self.block_size = block_size
        self.plan = plan
        self.policy = policy
        if plan is not None:
            if policy is not None:
                stage_params = [jax.device_put(sp, policy.params_shardings(sp))
                                for sp in stage_params]
            self.params = list(stage_params)
        else:
            if policy is not None:
                params = jax.device_put(params, policy.params_shardings(params))
            self.params = params
        self._prefill_jit: Dict[Any, Any] = {}
        self._decode_jit: Dict[Any, Any] = {}
        self._pool: Optional[CachePool] = None      # grow-only, one per engine
        # donate the cache/state buffers into the jitted steps (in-place
        # updates; halves peak cache memory) — CPU can't donate and would
        # just warn per call; repro.runtime owns the decision so trace-only
        # introspection (REPRO_ASSUME_DONATION=1) sees the real masks
        self._donate = runtime.donation_enabled()
        self.scheduler: Optional[Scheduler] = None  # last generate()'s
        self.max_queue_wait_ms = max_queue_wait_ms
        self.max_cache_tokens = max_cache_tokens
        self._clock = clock or time.monotonic
        self._sleep = sleep or time.sleep
        # observability: per-engine registry (cumulative across generate()
        # calls, like the legacy stats dict it now backs), spans, events
        self.tracer = tracer if tracer is not None else Tracer(
            clock=self._clock)
        self.event_log = event_log if event_log is not None else default_log()
        self.bind_metrics(metrics if metrics is not None
                          else MetricsRegistry())

    def bind_metrics(self, metrics: MetricsRegistry) -> None:
        """(Re-)home the engine's series in ``metrics``.  Called once from
        ``__init__``; loadgen calls it again with a fresh registry after its
        warmup pass, so compile-time TTFTs never pollute the measured
        distribution."""
        self.metrics = metrics
        self._rejected = metrics.counter(
            "serve_rejected_total",
            help="requests shed, by reason (cache/queue/deadline)")
        self._requests = metrics.counter(
            "serve_requests_total", help="completions, by finish reason")
        self._tokens = metrics.counter(
            "serve_tokens_total", help="generated tokens (incl. partial)")
        self._ttft = metrics.histogram(
            "serve_ttft_ms", TTFT_MS_BUCKETS,
            help="submit -> first sampled token, ms")
        self._queue_depth = metrics.histogram(
            "serve_queue_depth", DEPTH_BUCKETS,
            help="wait-queue depth sampled at each decode sync")
        self._slots_busy = metrics.histogram(
            "serve_slots_busy", DEPTH_BUCKETS,
            help="active slots sampled at each decode sync")
        self._peak_slots = metrics.gauge(
            "serve_peak_slots_busy", help="max concurrent active slots")
        self._cache_tokens = metrics.gauge(
            "serve_cache_tokens", help="cache-pool length, tokens per slot")
        if self.paged:
            # block-utilization series exist only on paged engines, so a
            # contiguous engine's metric/report surface is unchanged
            self._blocks_busy = metrics.histogram(
                "serve_blocks_busy", DEPTH_BUCKETS,
                help="allocated cache blocks sampled at each decode sync")
            self._peak_blocks = metrics.gauge(
                "serve_peak_blocks_busy",
                help="max concurrently allocated cache blocks")
            self._prefix_hits = metrics.counter(
                "serve_prefix_hits_total",
                help="prompt blocks reused via shared-prefix registry")

    @property
    def stats(self) -> Dict[str, int]:
        """Degraded-mode telemetry, cumulative across ``generate()`` calls.

        Legacy read-through view: the source of truth is now the
        ``serve_rejected_total`` counter; the dict shape (exactly these
        three keys) is pinned byte-for-byte in tests."""
        return {"rejected_cache": self._rejected.value(reason="cache"),
                "rejected_queue": self._rejected.value(reason="queue"),
                "rejected_deadline": self._rejected.value(reason="deadline")}

    # -- forward fns (plain vs staged) --------------------------------------

    def _decode_fn(self, params, cache, tok, pos, paged=None):
        kv_block = self.block_size
        if self.plan is not None:
            return staged.staged_decode_step(self.cfg, self.plan, params,
                                             cache, tok, pos, paged=paged,
                                             kv_block=kv_block)
        return M.decode_step(self.cfg, params, cache, tok, pos, paged=paged,
                             kv_block=kv_block)

    def _prefill_fn(self, params, batch, cache_len):
        if self.plan is not None:
            return staged.staged_prefill(self.cfg, self.plan, params, batch,
                                         cache_len)
        return M.prefill(self.cfg, params, batch, cache_len)

    def _admit_step(self, bshape, cache_len: int, mode: str):
        """ONE jitted call per admitted group: prefill + first-token sample +
        cache-pool scatter + per-slot state scatter (cached per group shape).

        Paged engines append a ``write_rows`` (R, nb) physical-block arg and
        scatter attention K/V via ``place_blocks`` (shared-prefix rows point
        at the garbage block); everything else is identical.
        """
        key = (("paged", bshape, cache_len, mode) if self.paged
               else (bshape, cache_len, mode))
        fn = self._prefill_jit.get(key)
        if fn is not None:
            return fn
        vs = self.cfg.vocab_size
        bs = self.block_size

        def admit(params, batch, pool_cache, tok, pos, keys, temps, tks,
                  tps, slots, seeds, g_temps, g_tks, g_tps, *rest):
            logits, group_cache, p1 = self._prefill_fn(params, batch,
                                                       cache_len)
            k0s, s0s = sampling.split_keys(
                jax.vmap(sampling.make_key)(seeds))
            # sampling always runs on fp32 logits regardless of the cache /
            # activation compute dtype (precision-policy contract)
            t0 = sampling.sample_tokens(logits[:, :vs].astype(jnp.float32),
                                        s0s, g_temps, g_tks, g_tps, mode=mode)
            if self.paged:
                pool_cache = place_blocks(pool_cache, group_cache, slots,
                                          rest[0], block_size=bs)
            else:
                pool_cache = place_rows(pool_cache, group_cache, slots)
            tok = tok.at[slots].set(t0)
            pos = pos.at[slots].set(p1)
            keys = keys.at[slots].set(k0s)
            temps = temps.at[slots].set(g_temps)
            tks = tks.at[slots].set(g_tks)
            tps = tps.at[slots].set(g_tps)
            return pool_cache, tok, pos, keys, temps, tks, tps, t0

        donate = tuple(range(2, 9)) if self._donate else ()
        fn = self._prefill_jit[key] = jax.jit(admit, donate_argnums=donate)
        return fn

    def _decode_chunk(self, n: int, mode: str, lc: Optional[int] = None):
        """Jitted scan of n fused decode+sample steps (cached per n, mode).

        Paged engines append the (n_slots, nb) block-table arg and key the
        cache on ``lc`` too (the logical cache length is baked into the
        traced program as the attention ring modulus / validity bound)."""
        key = ("paged", n, mode, lc) if self.paged else (n, mode)
        fn = self._decode_jit.get(key)
        if fn is not None:
            return fn
        vs = self.cfg.vocab_size
        paged_mode = self.paged

        def chunk(params, cache, tok, pos, keys, temps, tks, tps, *rest):
            def body(carry, _):
                cache, tok, pos, keys = carry
                paged = (rest[0], lc) if paged_mode else None
                logits, cache = self._decode_fn(params, cache, tok, pos,
                                                paged=paged)
                if mode != "greedy":
                    keys, sub = sampling.split_keys(keys)
                else:
                    sub = keys
                tok = sampling.sample_tokens(
                    logits[:, :vs].astype(jnp.float32), sub, temps, tks, tps,
                    mode=mode)
                return (cache, tok, pos + 1, keys), tok

            (cache, tok, pos, keys), toks = jax.lax.scan(
                body, (cache, tok, pos, keys), None, length=n)
            return cache, tok, pos, keys, toks

        donate = (1, 2, 3, 4) if self._donate else ()
        fn = self._decode_jit[key] = jax.jit(chunk, donate_argnums=donate)
        return fn

    # -- request plumbing ---------------------------------------------------

    def _request_batch(self, reqs: Sequence[Request]):
        """Batch for a group of SAME-LENGTH prompts (batched admission)."""
        cfg = self.cfg
        b = len(reqs)
        toks = np.stack([np.asarray(r.tokens, np.int32).reshape(-1)
                         for r in reqs])
        batch = {"tokens": jnp.asarray(toks)}
        if cfg.enc_dec:
            batch["frames"] = jnp.stack([
                jnp.zeros((cfg.enc_seq, cfg.d_model), jnp.float32)
                if r.frames is None else
                jnp.asarray(r.frames).reshape(cfg.enc_seq, cfg.d_model)
                for r in reqs])
        if cfg.frontend == "vision":
            batch["image_embeds"] = jnp.stack([
                jnp.zeros((cfg.vision_tokens, cfg.d_model), jnp.float32)
                if r.image_embeds is None else
                jnp.asarray(r.image_embeds).reshape(cfg.vision_tokens,
                                                    cfg.d_model)
                for r in reqs])
        assert batch["tokens"].shape[0] == b
        return batch

    def _cache_len_for(self, requests: Sequence[Request]) -> int:
        extra = self.cfg.vision_tokens if self.cfg.frontend == "vision" else 0
        return max(len(np.asarray(r.tokens).reshape(-1))
                   + r.gen.max_new_tokens for r in requests) + extra

    def _pool_for(self, need_len: int) -> CachePool:
        """The engine's single cache pool, grow-only and bucketed to 32
        tokens, so serving varied request lengths reuses one device cache
        instead of allocating per distinct length."""
        if self.max_cache_tokens is not None:
            # admission control already rejected anything that needs more —
            # the grow-only pool must never outgrow the configured budget
            need_len = min(need_len, self.max_cache_tokens)
        if self._pool is None or self._pool.cache_len < need_len:
            size = -(-need_len // 32) * 32
            if self.paged:
                self._pool = PagedCachePool(
                    self.cfg, self.max_slots, size,
                    block_size=self.block_size,
                    max_tokens=self.max_cache_tokens, policy=self.policy)
            else:
                self._pool = CachePool(self.cfg, self.max_slots, size,
                                       policy=self.policy)
        return self._pool

    def _chunk_len(self, remaining: int) -> int:
        """Fused steps until the next sync: the nearest guaranteed
        retirement, rounded up to a power of two (bounds the jit-compile set
        at ~log2(decode_block) scan lengths; overshoot tokens are truncated
        at the sync, so the round-up costs at most a few cheap steps)."""
        if remaining >= self.decode_block:
            return self.decode_block
        return min(1 << max(remaining - 1, 0).bit_length(), self.decode_block)

    # -- the loop -----------------------------------------------------------

    def generate(self, requests: Sequence[Request],
                 cache_len: Optional[int] = None,
                 arrivals: Optional[Sequence[float]] = None
                 ) -> List[Completion]:
        """Continuously-batched generation; completions in request order.

        cache_len is a minimum — the engine may serve from a larger pooled
        cache (validity masks make extra slots inert).

        arrivals — optional per-request submission offsets (seconds from
        call start): open-loop traffic.  Request i is only admissible once
        the clock passes ``start + arrivals[i]``; the engine sleeps (via
        the injectable ``sleep``) when all slots are idle and the next
        arrival is in the future.  ``None`` (default) is the legacy
        closed-loop path: everything arrives at once."""
        done: Dict[int, Completion] = {}
        for ev in self.stream(requests, cache_len=cache_len,
                              arrivals=arrivals):
            if ev.kind == "done":
                done[ev.req_idx] = ev.completion
        return [done[i] for i in range(len(requests))]

    def stream(self, requests: Sequence[Request],
               cache_len: Optional[int] = None,
               arrivals: Optional[Sequence[float]] = None
               ) -> Iterator[StreamEvent]:
        """Streaming form of ``generate``: yields a "delta" ``StreamEvent``
        per generated token (in emission order; different requests
        interleave) and one "done" event per request carrying its final
        ``Completion``.  TTFT can be measured on the first "delta" of a
        request instead of waiting for the whole batch.  ``generate`` is a
        thin wrapper that collects the "done" events."""
        if not requests:
            return
        if arrivals is not None and len(arrivals) != len(requests):
            raise ValueError("arrivals must align 1:1 with requests")
        n_slots = self.max_slots
        extra = self.cfg.vision_tokens if self.cfg.frontend == "vision" else 0

        def span(r) -> int:
            return np.asarray(r.tokens).reshape(-1).shape[0] \
                + r.gen.max_new_tokens + extra

        def completion(r, tokens, reason) -> Completion:
            self._requests.inc(1, reason=reason)
            return Completion(
                id=r.id,
                prompt_tokens=tuple(int(t) for t in
                                    np.asarray(r.tokens).reshape(-1)),
                tokens=tokens, finish_reason=reason)

        sched = self.scheduler = Scheduler(
            n_slots, max_queue_wait_ms=self.max_queue_wait_ms,
            event_log=self.event_log)
        paged = self.paged
        done: Dict[int, Completion] = {}
        evq: List[StreamEvent] = []          # events pending the next yield

        def flush() -> List[StreamEvent]:
            out = evq[:]
            evq.clear()
            return out

        def ev_done(req_idx: int, r, comp: Completion) -> None:
            done[req_idx] = comp
            evq.append(StreamEvent("done", req_idx, r.id, completion=comp))

        accepted: List[Request] = []
        now0 = self._clock()
        self.event_log.emit("generate_begin", n=len(requests))
        for i, r in enumerate(requests):
            if self.max_cache_tokens is not None \
                    and span(r) > self.max_cache_tokens:
                # cache-pressure admission control: this request could never
                # fit a slot of the capped pool — shed it up front, loudly
                ev_done(i, r, completion(r, (), "rejected"))
                self._rejected.inc(1, reason="cache")
                self.event_log.emit("reject", req=i)
            elif r.gen.max_new_tokens <= 0:    # prefill-only: nothing to emit
                ev_done(i, r, completion(r, (), "length"))
            else:
                t = now0 + (arrivals[i] if arrivals is not None else 0.0)
                sched.submit(i, r, t)
                accepted.append(r)
        yield from flush()
        if not accepted:
            self.event_log.emit("generate_end", n=len(requests))
            return
        # pools are reusable without zeroing: admission fully overwrites a
        # slot before it decodes, and free slots never reach a Completion
        pool = self._pool_for(max(cache_len or 0,
                                  self._cache_len_for(accepted)))
        cache_len = pool.cache_len
        if paged:
            # host-side block tables: one row per slot, garbage-padded; free
            # slots stay all-garbage so their (ignored) decode writes land
            # in the garbage block
            tables = np.zeros((n_slots, pool.blocks_per_slot), np.int32)
            lc = pool.attn_len

        tok = jnp.zeros((n_slots,), jnp.int32)
        pos = jnp.zeros((n_slots,), jnp.int32)
        keys = jnp.zeros((n_slots, 2), jnp.uint32)
        temps = jnp.zeros((n_slots,), jnp.float32)
        tks = jnp.zeros((n_slots,), jnp.int32)
        tps = jnp.ones((n_slots,), jnp.float32)

        mode = sampling.mode_for([r.gen for r in requests])
        # degradation is active only when some limit can actually fire —
        # otherwise shed() stays a no-op and the loop is the legacy loop
        shedding = self.max_queue_wait_ms is not None or any(
            r.deadline_ms is not None for r in accepted)
        open_loop = arrivals is not None
        admit_t: Dict[int, float] = {}       # req_idx -> admission walltime

        def finish(slot: int, reason: str) -> None:
            st = sched.retire(slot)
            st.finish_reason = reason
            ev_done(st.req_idx, st.request,
                    completion(st.request, tuple(st.emitted), reason))
            if paged and st.blocks is not None:
                pool.release(st.blocks)     # last owner frees the blocks
                tables[slot] = GARBAGE_BLOCK
                st.blocks = None
            self._tokens.inc(len(st.emitted))
            t_adm = admit_t.pop(st.req_idx, None)
            if t_adm is not None:
                self.tracer.add_span(
                    f"req {st.req_idx} active", t_adm,
                    self._clock() - t_adm, cat="request",
                    tid=TID_REQ0 + st.req_idx, reason=reason,
                    tokens=len(st.emitted))

        def shed() -> None:
            """Degraded mode: reject what can no longer be served in time —
            queued requests past their wait budget, active slots past their
            deadline (partial tokens kept) — instead of stalling everyone."""
            if not shedding:
                return
            now = self._clock()
            for req_idx, r in sched.expire_queued(now):
                ev_done(req_idx, r, completion(r, (), "rejected"))
                self._rejected.inc(1, reason="queue")
                self.tracer.instant(f"req {req_idx} shed", ts=now,
                                    cat="request", tid=TID_REQ0 + req_idx)
            for slot in sched.overdue_active(now):
                finish(slot, "rejected")
                self._rejected.inc(1, reason="deadline")

        def admit_group(items, allocs=None) -> None:
            """Admit same-prompt-length requests via ONE jitted batched
            prefill+sample+scatter call.  ``allocs`` (paged mode) carries
            each request's ``PagedAlloc``, already reserved by
            ``admit_ready``."""
            nonlocal tok, pos, keys, temps, tks, tps
            reqs = [r for _, r, _ in items]
            batch = self._request_batch(reqs)
            t_adm = self._clock()
            slots = [sched.admit(i, r, batch["tokens"].shape[1], arrival=t)
                     for i, r, t in items]
            if paged:
                wrows = []
                for slot, alloc in zip(slots, allocs):
                    sched.active[slot].blocks = alloc.ids
                    tables[slot] = pool.table_row(alloc)
                    wrows.append(pool.write_row(alloc))
                    if alloc.n_shared:
                        self._prefix_hits.inc(alloc.n_shared)
            for i, _, t in items:
                admit_t[i] = t_adm
                self.tracer.add_span(f"req {i} queued", t, t_adm - t,
                                     cat="request", tid=TID_REQ0 + i)
            step = self._admit_step(batch["tokens"].shape, cache_len, mode)
            with self.tracer.span("admit", cat="serve", tid=TID_LOOP,
                                  batch=len(reqs)):
                args = [
                    self.params, batch, pool.cache, tok, pos, keys, temps,
                    tks, tps, jnp.asarray(slots, jnp.int32),
                    jnp.asarray([r.gen.seed for r in reqs], jnp.uint32),
                    jnp.asarray([r.gen.temperature for r in reqs],
                                jnp.float32),
                    jnp.asarray([r.gen.top_k for r in reqs], jnp.int32),
                    jnp.asarray([r.gen.top_p for r in reqs], jnp.float32)]
                if paged:
                    args.append(jnp.asarray(wrows, jnp.int32))
                pool.cache, tok, pos, keys, temps, tks, tps, t0 = step(*args)
                t0h = np.asarray(t0)     # the sync: first tokens are real
            now = self._clock()
            for _, _, t in items:        # TTFT measured at the sync point
                self._ttft.observe((now - t) * 1000.0)
            for row, (slot, (i, r, _)) in enumerate(zip(slots, items)):
                g = r.gen
                tv = int(t0h[row])
                sched.active[slot].emitted.append(tv)
                evq.append(StreamEvent("delta", i, r.id, token=tv))
                if g.eos_id is not None and tv == g.eos_id:
                    finish(slot, "eos")
                elif g.max_new_tokens <= 1:
                    finish(slot, "length")

        def admit_ready() -> None:
            now = self._clock() if open_loop else None
            while sched.queued() and sched.free:
                take = sched.take(len(sched.free), now=now)
                if not take:         # head of queue hasn't arrived yet
                    break
                stalled = False
                if paged:
                    # block-granular admission control: reserve each
                    # request's blocks (shared-prefix lookup included)
                    # before it reaches a slot; when blocks run out the
                    # tail goes back to the queue head (FIFO preserved)
                    # and waits for the next retirement
                    admitted: List[Any] = []
                    allocs: List[Any] = []
                    for j, (i, r, t) in enumerate(take):
                        ptoks = np.asarray(r.tokens,
                                           np.int32).reshape(-1).tolist()
                        alloc = pool.allocate(ptoks, span(r))
                        if alloc is None:
                            if pool.allocator.n_used == 0:
                                # alone with every block free and still no
                                # fit — this request can NEVER be served
                                # under the block budget; shed it instead
                                # of deadlocking the queue
                                ev_done(i, r, completion(r, (), "rejected"))
                                self._rejected.inc(1, reason="cache")
                                self.event_log.emit("reject", req=i)
                                continue
                            sched.requeue_front(take[j:])
                            stalled = True
                            break
                        admitted.append((i, r, t))
                        allocs.append(alloc)
                    groups: Dict[int, list] = {}
                    for item, alloc in zip(admitted, allocs):
                        plen = np.asarray(item[1].tokens).reshape(-1).shape[0]
                        groups.setdefault(plen, []).append((item, alloc))
                    for pairs in groups.values():
                        admit_group([it for it, _ in pairs],
                                    [al for _, al in pairs])
                else:
                    groups = {}
                    for i, r, t in take:
                        plen = np.asarray(r.tokens).reshape(-1).shape[0]
                        groups.setdefault(plen, []).append((i, r, t))
                    for items in groups.values():
                        admit_group(items)
                if stalled:
                    break

        shed()
        admit_ready()
        yield from flush()
        while sched.active or sched.queued():
            if not sched.active:
                # open-loop idle: nothing in flight and the next arrival is
                # still in the future — sleep the gap (injectable) and retry
                na = sched.next_arrival()
                if na is None:
                    break
                gap = na - self._clock()
                if gap > 0:
                    self._sleep(gap)
                shed()
                admit_ready()
                yield from flush()
                continue
            self._queue_depth.observe(sched.queued())
            self._slots_busy.observe(len(sched.active))
            if paged:
                self._blocks_busy.observe(pool.allocator.n_used)
            n = self._chunk_len(sched.min_remaining())
            step = (self._decode_chunk(n, mode, lc) if paged
                    else self._decode_chunk(n, mode))
            with self.tracer.span(f"decode[{n}]", cat="serve", tid=TID_LOOP,
                                  active=len(sched.active)):
                args = [self.params, pool.cache, tok, pos, keys, temps, tks,
                        tps]
                if paged:
                    args.append(jnp.asarray(tables))
                pool.cache, tok, pos, keys, toks = step(*args)
                toks_h = np.asarray(toks)                  # (n, n_slots)
            for slot in list(sched.active):
                st = sched.active[slot]
                eos = st.request.gen.eos_id
                for t in toks_h[:, slot]:
                    tv = int(t)
                    st.emitted.append(tv)
                    evq.append(StreamEvent("delta", st.req_idx,
                                           st.request.id, token=tv))
                    if eos is not None and tv == eos:
                        finish(slot, "eos")
                        break
                    if st.remaining <= 0:
                        finish(slot, "length")
                        break
            shed()
            admit_ready()
            yield from flush()
        self._peak_slots.set_max(sched.max_concurrent)
        self._cache_tokens.set(pool.cache_len)
        if paged:
            self._peak_blocks.set_max(pool.allocator.peak_used)
        self.metrics.drain()         # flush boundary (idempotent, host-only)
        self.event_log.emit("generate_end", n=len(requests),
                            completed=len(done))
        yield from flush()
