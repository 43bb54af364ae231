"""PartitionPlan-aware serving: prefill/decode over per-stage param trees.

The paper's partitions are independently trainable AND independently
deployable — this module serves directly from the per-stage trees
(``partition.slice_stage_params``) without joining them.  Stage 0 owns the
embedding (+ encoder/frontend), the last stage owns the final norm and
unembedding (reading the frozen ``tied_unembed`` snapshot when embeddings
are tied).  The caches stay in the full stacked (G, B, ...) layout so the
same ``CachePool`` serves both modes; each stage touches only its group
slice.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import model as M


def stage_params_from_checkpoints(cfg, plan, ckpt_root, *, step=None,
                                  devices=None):
    """Per-stage param trees for staged serving, restored straight from a
    ``repro.dist.lifecycle`` per-stage checkpoint directory — the paper's
    partitions deploy WITHOUT ever being joined.

    The restore needs only tree *structure*, so the ``like`` trees are
    ``jax.eval_shape`` stand-ins (no weights materialize besides the
    checkpointed ones).  Feed the result to ``serve.Engine(cfg, plan=plan,
    stage_params=...)``; ``devices`` optionally pins stage k's tree to
    ``devices[k]`` on the way in."""
    from repro.core import partition
    from repro.dist import lifecycle

    def all_likes():
        params = M.init_params(cfg, jax.random.PRNGKey(0))  # repro: allow-const-key
        return [partition.slice_stage_params(cfg, plan, params, k)
                for k in range(plan.n_stages)]
    likes = jax.eval_shape(all_likes)   # ONE abstract trace for all stages
    sps = lifecycle.load_stage_params(ckpt_root, likes, step=step,
                                      devices=devices)
    if devices is None:
        sps = [jax.tree_util.tree_map(jnp.asarray, sp) for sp in sps]
    return sps


def _unembed_params(cfg, last_stage_params):
    """Param view for the last stage's unembedding (tied-snapshot aware)."""
    if "tied_unembed" in last_stage_params:
        return {"tok_embed": last_stage_params["tied_unembed"]}
    return last_stage_params


def _stage_cache(plan, k, cache):
    g0, g1 = plan.bounds[k]
    return jax.tree_util.tree_map(lambda a: a[g0:g1], cache)


def staged_prefill(cfg, plan, stage_params, batch, cache_len):
    """Prompt forward through the stage chain, building the decode cache.

    Same contract as ``model.prefill``: (last_token_logits, cache, next_pos);
    the returned cache is stacked over ALL groups (stage slices concatenated)
    so it drops into the shared CachePool.
    """
    x, enc_out, _ = M.embed_inputs(cfg, stage_params[0], batch)
    s = x.shape[1]
    rope_cs = M.rope_for(cfg, jnp.arange(s))
    caches = []
    for k in range(plan.n_stages):
        x, _, c = M.forward_groups(cfg, stage_params[k]["groups"], x,
                                   rope_cs=rope_cs, enc_out=enc_out,
                                   collect_cache=True, remat=False)
        caches.append(c)
    full = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *caches)
    cache = M.repack_prefill_cache(cfg, full, cache_len)
    last = stage_params[-1]
    xl = L.norm_apply(last["final_norm"], x[:, -1:])
    logits = M.unembed(cfg, _unembed_params(cfg, last), xl)[:, 0]
    return logits, cache, jnp.int32(s)


def staged_decode_step(cfg, plan, stage_params, cache, tok, pos, paged=None,
                       kv_block=None):
    """One decode step through the stage chain. Same contract as
    ``model.decode_step`` (pos: scalar or per-request vector; ``paged``
    routes attention K/V through one block table shared by every stage —
    the paged leaves keep the leading G axis, so stage slices still work)."""
    x, rope_cs = M.decode_embed(cfg, stage_params[0], tok, pos)
    new_parts = []
    for k in range(plan.n_stages):
        x, nc = M.decode_groups(cfg, stage_params[k]["groups"],
                                _stage_cache(plan, k, cache), x, rope_cs, pos,
                                paged=paged, kv_block=kv_block)
        new_parts.append(nc)
    new_cache = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *new_parts)
    last = stage_params[-1]
    x = L.norm_apply(last["final_norm"], x)
    logits = M.unembed(cfg, _unembed_params(cfg, last), x)[:, 0]
    return logits, new_cache
