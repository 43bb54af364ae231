"""Paged KV cache: block allocator, shared-prefix reuse, streaming, and the
serve-layer bugfix sweep.

The tentpole contract: ``Engine(..., paged=True)`` — block-grained K/V
allocation with per-request block tables, copy-on-write shared prefixes,
and a garbage block absorbing masked writes — is a pure *capacity*
optimization, never a tokens change.  Every test here compares against the
contiguous pool or the sequential greedy reference.

Also pinned: the paged Pallas decode kernel (scalar-prefetched block
table) against the gather reference, exact ``max_cache_tokens`` budget
enforcement, the streaming API's delta/done protocol, the oversized-
request safety valve, and the scheduler fixes (head-of-line blocking in
``take(now=)``, ``min_remaining`` on an empty active set).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve import (BlockAllocator, Engine, GenerationConfig,
                         PagedCachePool, Request, Scheduler)
from repro.serve.kv_cache import GARBAGE_BLOCK
from repro.verify.scenarios import greedy_reference, serve_requests


# -- paged == contiguous / sequential, token-identical ----------------------

@pytest.mark.parametrize("name,window", [
    ("qwen2-1.5b", 0),      # standard decoder
    ("qwen2-1.5b", 8),      # sliding-window ring over padded blocks
    ("xlstm-125m", 0),      # recurrent carries stay slot-resident
])
def test_paged_token_identical(serve_world, name, window):
    cfg, params = serve_world(name, window)
    reqs = serve_requests(cfg)
    outs = Engine(cfg, params, max_slots=2, decode_block=4, paged=True,
                  block_size=4).generate(reqs)
    for req, c in zip(reqs, outs):
        assert c.tokens == greedy_reference(cfg, params, req), c
        assert c.finish_reason == "length"


def test_paged_equals_contiguous_mixed_lengths(serve_world):
    cfg, params = serve_world()
    reqs = serve_requests(cfg, lens=(8, 5, 8, 5, 7, 8),
                          news=(1, 5, 3, 7, 2, 4))
    ctg = Engine(cfg, params, max_slots=3, decode_block=4).generate(reqs)
    pgd = Engine(cfg, params, max_slots=3, decode_block=4, paged=True,
                 block_size=4).generate(reqs)
    assert [c.tokens for c in pgd] == [c.tokens for c in ctg]
    assert all(c.finish_reason == "length" for c in pgd)


# -- shared-prefix reuse -----------------------------------------------------

def test_shared_prefix_reuse_identical_and_counted(serve_world):
    """Requests sharing a block-aligned prompt prefix reuse the first
    writer's physical blocks (prefix_hits > 0) and still decode the exact
    greedy-reference tokens — first-writer-wins is invisible."""
    cfg, params = serve_world()
    base = serve_requests(cfg, lens=(8, 8, 8), news=(6, 6, 6))
    t0 = np.asarray(base[0].tokens, np.int32)
    reqs = [base[0],
            Request(tokens=t0.copy(), gen=GenerationConfig(max_new_tokens=6),
                    id="twin"),
            Request(tokens=np.concatenate([t0[:4],
                                           np.asarray(base[2].tokens)[:4]]),
                    gen=GenerationConfig(max_new_tokens=6), id="halfshare")]
    eng = Engine(cfg, params, max_slots=3, decode_block=4, paged=True,
                 block_size=4)
    outs = eng.generate(reqs)
    for req, c in zip(reqs, outs):
        assert c.tokens == greedy_reference(cfg, params, req)
    pool = eng._pool
    # twin shares both 4-token prompt blocks, halfshare only the first
    assert pool.prefix_hits == 3
    assert pool.prefix_lookups == 3
    assert pool.allocator.n_used == 0        # everything released


def test_shared_prefix_disabled_for_windowed(serve_world):
    cfg, params = serve_world("qwen2-1.5b", 8)
    pool = PagedCachePool(cfg, 2, 32, block_size=4)
    assert not pool.share_prefixes
    a = pool.allocate(list(range(8)), 12)
    b = pool.allocate(list(range(8)), 12)
    assert a.n_shared == 0 and b.n_shared == 0
    assert pool.prefix_lookups == 0


# -- block allocator invariants ---------------------------------------------

def test_block_allocator_invariants():
    a = BlockAllocator(6, block_size=4)      # 5 usable + garbage block
    assert a.n_free == 5 and a.n_used == 0
    x = a.alloc(2)
    y = a.alloc(3)
    assert a.alloc(1) is None                # all-or-nothing exhaustion
    assert a.n_used == 5 and a.peak_used == 5
    a.incref(x)                              # second owner of x
    assert a.free(x) == []                   # first release frees nothing
    gen0 = [a.gen[i] for i in x]
    assert a.free(x) == x                    # last owner returns the blocks
    assert [a.gen[i] for i in x] == [g + 1 for g in gen0]   # gen bumped
    z = a.alloc(2)                           # recycled from the free pool
    assert set(z) <= set(x)
    a.free(y)
    a.free(z)
    a.check()
    assert a.n_used == 0
    with pytest.raises(AssertionError, match="double free"):
        a.free(z)
    with pytest.raises(ValueError, match="garbage"):
        BlockAllocator(1, block_size=4)


def test_paged_pool_budget_and_table_rows(serve_world):
    cfg, params = serve_world()
    pool = PagedCachePool(cfg, 4, 32, block_size=8, max_tokens=32)
    assert pool.allocator.n_blocks == 5      # 32 // 8 usable + garbage
    al = pool.allocate(list(range(16)), 20)  # 3 blocks
    assert al is not None and len(al.ids) == 3
    # an unrelated prompt needs 2 fresh blocks, only 1 left: budget hit
    assert pool.allocate(list(range(100, 108)), 12) is None
    row = pool.table_row(al)
    assert len(row) == pool.blocks_per_slot
    assert row[3:] == [GARBAGE_BLOCK]        # garbage-padded tail
    # the twin shares the 2 full prompt blocks -> needs only 1 fresh
    twin = pool.allocate(list(range(16)), 20)
    assert twin is not None and twin.n_shared == 2
    assert pool.write_row(twin)[:2] == [GARBAGE_BLOCK, GARBAGE_BLOCK]
    pool.release(al.ids)
    pool.release(twin.ids)
    assert pool.allocator.n_used == 0


# -- exact token budget => higher admission concurrency ---------------------

def test_block_budget_bounds_concurrency_not_tokens(serve_world):
    """Same ``max_cache_tokens``: the paged engine admits as many requests
    as fit the block budget (not one full row each), and the budget is
    exact — concurrency is capped right where blocks run out."""
    cfg, params = serve_world()
    reqs = serve_requests(cfg, lens=(8, 8, 8, 8), news=(4, 4, 4, 4))
    free = Engine(cfg, params, max_slots=4, decode_block=4, paged=True,
                  block_size=4).generate(reqs)
    eng = Engine(cfg, params, max_slots=4, decode_block=4, paged=True,
                 block_size=4, max_cache_tokens=24)
    outs = eng.generate(reqs)
    assert [c.tokens for c in outs] == [c.tokens for c in free]
    # 24-token budget / (12-token span -> 3 blocks) = 2 concurrent
    assert eng.scheduler.max_concurrent == 2
    assert eng._pool.allocator.peak_used == 6
    assert eng._pool.allocator.n_used == 0


def test_oversized_paged_request_rejected_not_deadlocked(serve_world):
    """A request that can NEVER fit the block budget is rejected with
    reason "cache" even though slots are free — the admission safety valve
    (alloc failed with zero blocks in use) instead of an infinite stall."""
    cfg, params = serve_world()
    reqs = serve_requests(cfg, lens=(8, 28), news=(4, 8))
    eng = Engine(cfg, params, max_slots=2, decode_block=4, paged=True,
                 block_size=4, max_cache_tokens=24)
    outs = eng.generate(reqs)
    assert outs[0].finish_reason == "length"
    assert outs[0].tokens == greedy_reference(cfg, params, reqs[0])
    assert outs[1].finish_reason == "rejected"
    assert eng.stats["rejected_cache"] == 1


# -- streaming ---------------------------------------------------------------

def test_stream_deltas_match_generate(serve_world):
    cfg, params = serve_world()
    reqs = serve_requests(cfg, lens=(8, 5, 10), news=(6, 4, 3))
    outs = Engine(cfg, params, max_slots=2, decode_block=4).generate(reqs)
    eng = Engine(cfg, params, max_slots=2, decode_block=4)
    deltas = {i: [] for i in range(len(reqs))}
    done = {}
    for ev in eng.stream(reqs):
        if ev.kind == "delta":
            assert ev.id == reqs[ev.req_idx].id
            deltas[ev.req_idx].append(ev.token)
        else:
            assert ev.kind == "done"
            assert ev.req_idx not in done    # exactly one done per request
            done[ev.req_idx] = ev.completion
    for i, c in enumerate(outs):
        assert tuple(deltas[i]) == c.tokens
        assert done[i].tokens == c.tokens
        assert done[i].finish_reason == c.finish_reason
    assert set(done) == set(range(len(reqs)))


def test_stream_rejected_request_yields_done_without_deltas(serve_world):
    cfg, params = serve_world()
    reqs = serve_requests(cfg, lens=(8, 28), news=(4, 4))
    eng = Engine(cfg, params, max_slots=2, decode_block=4,
                 max_cache_tokens=16)
    evs = list(eng.stream(reqs))
    by_req = {}
    for ev in evs:
        by_req.setdefault(ev.req_idx, []).append(ev.kind)
    assert by_req[1] == ["done"]             # rejected: no deltas, one done
    assert by_req[0][-1] == "done" and "delta" in by_req[0]


def test_paged_stream_equals_paged_generate(serve_world):
    cfg, params = serve_world()
    reqs = serve_requests(cfg, lens=(8, 8), news=(6, 6))
    outs = Engine(cfg, params, max_slots=2, decode_block=4, paged=True,
                  block_size=4).generate(reqs)
    eng = Engine(cfg, params, max_slots=2, decode_block=4, paged=True,
                 block_size=4)
    got = {ev.req_idx: ev.completion for ev in eng.stream(reqs)
           if ev.kind == "done"}
    assert [got[i].tokens for i in range(2)] == [c.tokens for c in outs]


# -- paged decode kernel (interpret mode) == gather reference ---------------

def test_paged_decode_kernel_matches_ref():
    from repro.kernels.flash_attention import kernel as K, ref as R
    rng = np.random.default_rng(0)
    b, h, kv, d, bs, nb, n_blocks, lc = 3, 4, 2, 8, 4, 3, 10, 12
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    k_pages = jnp.asarray(rng.normal(size=(n_blocks, bs, kv, d)),
                          jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(n_blocks, bs, kv, d)),
                          jnp.float32)
    # distinct physical blocks per slot, never the garbage block
    bt = jnp.asarray(rng.permutation(np.arange(1, 10)).reshape(b, nb),
                     jnp.int32)
    pos = jnp.asarray([3, 7, 11], jnp.int32)
    want = R.paged_decode_attention(q, k_pages, v_pages, bt, pos,
                                    logical_len=lc)
    got = K.paged_decode_attention_tpu(q, k_pages, v_pages, bt, pos,
                                       logical_len=lc, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # windowed: ring over the logical span
    want_w = R.paged_decode_attention(q, k_pages, v_pages, bt, pos,
                                      logical_len=8, window=8)
    got_w = K.paged_decode_attention_tpu(q, k_pages, v_pages, bt[:, :2],
                                         pos, logical_len=8, window=8,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bs", [4, 8])
def test_contiguous_decode_kernel_at_block_tile_matches_paged_bitwise(bs):
    """The contiguous decode kernel tiled at the paged block size folds the
    softmax over the same blocks in the same order as the paged kernel:
    the outputs agree bit for bit, so greedy tokens cannot depend on the
    pool."""
    from repro.kernels.flash_attention import kernel as K
    rng = np.random.default_rng(1)
    b, h, kv, d, nb = 3, 4, 2, 8, 5
    lc = nb * bs - 1
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, nb * bs, kv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, nb * bs, kv, d)), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(b * nb).reshape(b, nb), jnp.int32)
    pages = [jnp.zeros((b * nb + 1, bs, kv, d), jnp.float32).at[bt].set(
        c.reshape(b, nb, bs, kv, d)) for c in (kc, vc)]
    pos = jnp.asarray([2, bs + 1, lc - 1], jnp.int32)
    ctg = K.decode_attention_tpu(q, kc[:, :lc], vc[:, :lc], pos, bk=bs,
                                 interpret=True)
    pgd = K.paged_decode_attention_tpu(q, *pages, bt, pos, logical_len=lc,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(ctg), np.asarray(pgd))


@pytest.mark.parametrize("bs", [4, 8])
def test_paged_equals_contiguous_pallas_path(serve_world, monkeypatch, bs):
    """Through the Pallas decode kernels (interpret mode), the contiguous
    engine tiles its decode at its ``block_size`` and so decodes the same
    tokens as the paged engine at that block size."""
    from repro.kernels import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    dispatch.cache_clear()
    cfg, params = serve_world()
    reqs = serve_requests(cfg, lens=(8, 5, 7), news=(6, 3, 5))
    ctg = Engine(cfg, params, max_slots=3, decode_block=4,
                 block_size=bs).generate(reqs)
    pgd = Engine(cfg, params, max_slots=3, decode_block=4, paged=True,
                 block_size=bs).generate(reqs)
    dispatch.cache_clear()
    assert [c.tokens for c in pgd] == [c.tokens for c in ctg]


# -- scheduler bugfix sweep --------------------------------------------------

def test_take_head_of_line_blocking_fixed():
    """A future-stamped entry at the queue head must not starve an
    already-arrived entry behind it (the head-of-line bug): ``take(now=)``
    scans the WHOLE queue and returns arrivals in stamp order."""
    s = Scheduler(4)
    s.submit(0, "late", 10.0)            # future-stamped head
    s.submit(1, "early", 1.0)
    s.submit(2, "mid", 3.0)
    got = s.take(2, now=5.0)
    assert [i for i, _, _ in got] == [1, 2]      # stamp order, head skipped
    assert [i for i, _, _ in s.queue] == [0]     # future head still queued
    assert s.take(1, now=5.0) == []
    assert [i for i, _, _ in s.take(1, now=11.0)] == [0]


def test_requeue_front_preserves_order():
    s = Scheduler(4)
    for i in range(4):
        s.submit(i, f"r{i}", float(i))
    got = s.take(4, now=10.0)
    s.requeue_front(got[2:])             # tail goes back to the head
    assert [i for i, _, _ in s.queue] == [2, 3]
    assert [i for i, _, _ in s.take(4, now=10.0)] == [2, 3]


def test_min_remaining_empty_active_returns_zero():
    s = Scheduler(2)
    assert s.min_remaining() == 0        # was: ValueError (min of empty)
    s.admit(0, Request(tokens=[1, 2], gen=GenerationConfig(max_new_tokens=5),
                       deadline_ms=1.0), n_prompt=2)
    assert s.min_remaining() == 5
    s.retire(0)
    assert s.min_remaining() == 0


def test_all_slots_shed_mid_tick_engine_survives(serve_world):
    """Every active slot blows its deadline in the same tick: the engine
    sheds them all and must idle (min_remaining == 0 path) instead of
    crashing — subsequent arrivals still get served."""
    from repro.resilience import FakeClock
    cfg, params = serve_world()
    clk = FakeClock()

    def slow_clock():
        t = clk.monotonic()
        clk.advance(40.0)                # every tick jumps past deadlines
        return t

    reqs = [Request(tokens=np.asarray(r.tokens), gen=r.gen, id=r.id,
                    deadline_ms=1.0)
            for r in serve_requests(cfg, lens=(8, 8), news=(60, 60))]
    ok = serve_requests(cfg, lens=(5,), news=(3,))[0]
    eng = Engine(cfg, params, max_slots=2, decode_block=4,
                 clock=slow_clock, sleep=lambda _s: None)
    outs = eng.generate(list(reqs) + [ok],
                        arrivals=[0.0, 0.0, 500.0])
    assert [c.finish_reason for c in outs[:2]] == ["rejected", "rejected"]
    assert outs[2].finish_reason in ("length", "rejected")
    assert not eng.scheduler.active
