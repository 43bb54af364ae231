"""`repro.serve` engine: continuous batching must be a pure latency/throughput
optimization — never a tokens change.

Covers: (a) continuous-batched generation token-identical to one-request-at-
a-time generation at temperature 0 (standard decoder, sliding-window ring,
and a recurrent-state arch); (b) ring cache == full cache within the window;
(c) staggered admit/retire never leaks a slot, including the retire-on-admit
tick and zero-free-slot edges; (d) sampler sanity under a fixed key; plus
PartitionPlan-staged serving and Policy plumbing.

Setup comes from the shared ``repro.verify.scenarios`` builders via the
session-scoped ``serve_world`` fixture (params built once per arch/window).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import partition
from repro.serve import (Engine, GenerationConfig, Request, Scheduler,
                         sampling)
from repro.verify.scenarios import greedy_reference, serve_requests


# -- (a) continuous batching == sequential, greedy --------------------------

@pytest.mark.parametrize("name,window", [
    ("qwen2-1.5b", 0),      # standard decoder
    ("qwen2-1.5b", 8),      # sliding-window ring cache
    ("xlstm-125m", 0),      # recurrent-state caches
])
def test_continuous_batching_token_identical(serve_world, name, window):
    cfg, params = serve_world(name, window)
    reqs = serve_requests(cfg)
    outs = Engine(cfg, params, max_slots=2, decode_block=4).generate(reqs)
    for req, c in zip(reqs, outs):
        assert c.tokens == greedy_reference(cfg, params, req), c
        assert c.finish_reason == "length"
        assert c.n_generated == req.gen.max_new_tokens


def test_slots_one_equals_slots_many(serve_world):
    cfg, params = serve_world()
    reqs = serve_requests(cfg)
    a = Engine(cfg, params, max_slots=1, decode_block=4).generate(reqs)
    b = Engine(cfg, params, max_slots=4, decode_block=4).generate(reqs)
    assert [c.tokens for c in a] == [c.tokens for c in b]


# -- (b) ring cache == full cache within the window -------------------------

def test_ring_cache_matches_full_within_window(serve_world):
    base, params = serve_world()
    reqs = serve_requests(base, lens=(8, 6), news=(6, 8))
    # window covers prompt+generation entirely -> identical tokens
    full = Engine(base, params, max_slots=2, decode_block=4).generate(reqs)
    ring = Engine(base.replace(sliding_window=32), params, max_slots=2,
                  decode_block=4).generate(reqs)
    assert [c.tokens for c in full] == [c.tokens for c in ring]


# -- (c) staggered admit/retire never leaks a slot --------------------------

def test_scheduler_never_leaks_slots(serve_world):
    cfg, params = serve_world()
    # more requests than slots, wildly varied durations (incl. 1-token)
    reqs = serve_requests(cfg, lens=(8, 5, 8, 5, 7, 8), news=(1, 5, 3, 7, 2, 4))
    eng = Engine(cfg, params, max_slots=2, decode_block=4)
    outs = eng.generate(reqs)
    sched = eng.scheduler
    assert sorted(sched.free) == [0, 1] and not sched.active
    admits = [s for e, s in sched.events if e == "admit"]
    retires = [s for e, s in sched.events if e == "retire"]
    assert len(admits) == len(retires) == len(reqs)
    assert sched.max_concurrent <= 2
    for req, c in zip(reqs, outs):
        assert c.n_generated == req.gen.max_new_tokens
    # Scheduler rejects double-admission beyond capacity
    s = Scheduler(1)
    s.admit(0, reqs[0], 8)
    with pytest.raises(RuntimeError):
        s.admit(1, reqs[1], 5)


def test_retire_on_admit_tick_reuses_slot(serve_world):
    """A 1-token request retires DURING its admission tick; with one slot
    and a queue behind it, the freed slot must be re-admitted into in the
    same scheduling round, never leaked, never double-booked."""
    cfg, params = serve_world()
    reqs = serve_requests(cfg, lens=(8, 8, 6), news=(1, 1, 4))
    eng = Engine(cfg, params, max_slots=1, decode_block=4)
    outs = eng.generate(reqs)
    sched = eng.scheduler
    # all three served through the single slot, one at a time
    assert [s for e, s in sched.events] == [0] * 6
    assert [e for e, _ in sched.events] == ["admit", "retire"] * 3
    assert sched.max_concurrent == 1
    assert outs[0].n_generated == outs[1].n_generated == 1
    assert outs[2].n_generated == 4
    # the 1-token completions match the sequential reference's first token
    for i in (0, 1):
        assert outs[i].tokens == greedy_reference(cfg, params, reqs[i])[:1]


def test_eos_on_first_token_retires_at_admission(serve_world):
    """EOS hit on the token sampled inside the admission call itself (the
    earliest possible retire) frees the slot for the queued request."""
    cfg, params = serve_world()
    base = serve_requests(cfg, lens=(8, 6), news=(6, 5))
    first = greedy_reference(cfg, params, base[0])[0]
    reqs = [Request(tokens=base[0].tokens,
                    gen=GenerationConfig(max_new_tokens=6, eos_id=first)),
            base[1]]
    eng = Engine(cfg, params, max_slots=1, decode_block=4)
    outs = eng.generate(reqs)
    assert outs[0].finish_reason == "eos"
    assert outs[0].tokens == (first,)
    assert outs[1].n_generated == 5
    assert eng.scheduler.max_concurrent == 1


def test_zero_free_slot_admission_is_rejected():
    """admit() with no free slot is a programming error and fails loudly
    (the engine's admit_ready loop must gate on sched.free)."""
    s = Scheduler(2)
    r = Request(tokens=[1, 2, 3], gen=GenerationConfig(max_new_tokens=2))
    s.admit(0, r, 3)
    s.admit(1, r, 3)
    with pytest.raises(RuntimeError, match="no free slot"):
        s.admit(2, r, 3)
    # retire -> the slot is admissible again, audit trail intact
    s.retire(0)
    slot = s.admit(2, r, 3)
    assert slot == 0
    assert s.events == [("admit", 0), ("admit", 1), ("retire", 0),
                        ("admit", 0)]


def test_eos_retires_and_frees_slot(serve_world):
    cfg, params = serve_world()
    ref = greedy_reference(cfg, params, serve_requests(cfg)[0])
    eos = ref[2]
    reqs = serve_requests(cfg)
    reqs[0] = Request(tokens=reqs[0].tokens,
                      gen=GenerationConfig(max_new_tokens=6, eos_id=eos))
    outs = Engine(cfg, params, max_slots=2, decode_block=4).generate(reqs)
    assert outs[0].finish_reason == "eos"
    assert outs[0].tokens == ref[:3]          # eos included, then retired
    assert outs[1].n_generated == reqs[1].gen.max_new_tokens


# -- (d) samplers are distribution-sane under a fixed key -------------------

def test_samplers_sane_fixed_key():
    key = jax.random.PRNGKey(0)
    v, n = 64, 256
    logits = jnp.tile(jax.random.normal(key, (1, v)) * 3.0, (n, 1))
    keys = jax.vmap(lambda s: jax.random.PRNGKey(s))(jnp.arange(n))
    ones = jnp.ones((n,), jnp.float32)

    # temperature 0 -> argmax regardless of keys/filters
    out = sampling.sample_tokens(logits, keys, ones * 0.0,
                                 jnp.full((n,), 5, jnp.int32), ones * 0.5)
    assert set(np.asarray(out).tolist()) == {int(jnp.argmax(logits[0]))}

    # top_k=1 -> argmax even at high temperature
    out = sampling.sample_tokens(logits, keys, ones * 5.0,
                                 jnp.ones((n,), jnp.int32), ones)
    assert set(np.asarray(out).tolist()) == {int(jnp.argmax(logits[0]))}

    # top_k=5 -> support is exactly within the top-5 set, and >1 distinct
    top5 = set(np.asarray(jnp.argsort(logits[0])[::-1][:5]).tolist())
    out = sampling.sample_tokens(logits, keys, ones * 2.0,
                                 jnp.full((n,), 5, jnp.int32), ones)
    seen = set(np.asarray(out).tolist())
    assert seen <= top5 and len(seen) > 1

    # top_p -> smallest prefix covering p (peaked dist: tiny p == argmax)
    out = sampling.sample_tokens(logits, keys, ones, jnp.zeros((n,), jnp.int32),
                                 ones * 1e-4)
    assert set(np.asarray(out).tolist()) == {int(jnp.argmax(logits[0]))}

    # unfiltered sampling roughly follows softmax: the argmax token must be
    # the modal sample under a peaked distribution
    out = np.asarray(sampling.sample_tokens(logits, keys, ones,
                                            jnp.zeros((n,), jnp.int32), ones))
    vals, counts = np.unique(out, return_counts=True)
    assert vals[np.argmax(counts)] == int(jnp.argmax(logits[0]))

    # per-slot independence: same key row -> same token, different -> varies
    out1 = sampling.sample_tokens(logits, keys, ones * 2.0,
                                  jnp.zeros((n,), jnp.int32), ones)
    out2 = sampling.sample_tokens(logits, keys, ones * 2.0,
                                  jnp.zeros((n,), jnp.int32), ones)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# -- staged + policy serving ------------------------------------------------

def test_partitioned_engine_matches_joined(serve_world):
    cfg, params = serve_world()
    reqs = serve_requests(cfg, lens=(8, 5), news=(5, 4))
    joined = Engine(cfg, params, max_slots=2, decode_block=4).generate(reqs)
    plan = partition.make_plan(cfg, 2)
    sp = [partition.slice_stage_params(cfg, plan, params, k)
          for k in range(plan.n_stages)]
    stagedo = Engine(cfg, plan=plan, stage_params=sp, max_slots=2,
                     decode_block=4).generate(reqs)
    assert [c.tokens for c in joined] == [c.tokens for c in stagedo]


def test_policy_plumbing_single_device(serve_world):
    from repro.launch.sharding import Policy
    cfg, params = serve_world()
    reqs = serve_requests(cfg, lens=(8,), news=(4,))
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plain = Engine(cfg, params, max_slots=1, decode_block=4).generate(reqs)
    sharded = Engine(cfg, params, max_slots=1, decode_block=4,
                     policy=Policy(cfg, mesh)).generate(reqs)
    assert [c.tokens for c in plain] == [c.tokens for c in sharded]


def test_sampled_stream_independent_of_batching(serve_world):
    """A request's sampled tokens depend only on its own seed, not on what
    else is in the batch (continuous batching must not couple streams)."""
    cfg, params = serve_world()
    gen = GenerationConfig(max_new_tokens=6, temperature=0.8, top_k=16,
                           top_p=0.9, seed=13)
    rng = np.random.RandomState(1)
    r = Request(tokens=rng.randint(0, cfg.vocab_size, size=(8,)), gen=gen)
    other = serve_requests(cfg, lens=(5, 10), news=(7, 3))
    solo = Engine(cfg, params, max_slots=1, decode_block=4).generate([r])
    crowd = Engine(cfg, params, max_slots=3,
                   decode_block=4).generate([other[0], r, other[1]])
    assert solo[0].tokens == crowd[1].tokens
