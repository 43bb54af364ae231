"""Model partitioning for PNN (paper §2, Figures 2-4).

A ``PartitionPlan`` cuts a transformer's group stack into `n_stages`
contiguous stages.  Stage 0 owns the embedding (and encoder/frontend); the
last stage owns the final norm + unembedding.  Boundaries are residual-stream
activations (width d_model) — the fixed-width interface every assigned
architecture exposes (DESIGN.md §4.1).

The MLP variant (the paper's own experiment) cuts at layer granularity.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M


@dataclass(frozen=True)
class PartitionPlan:
    n_stages: int
    bounds: Tuple[Tuple[int, int], ...]  # group ranges [g0, g1) per stage

    @property
    def cuts(self) -> int:
        return self.n_stages - 1


def make_plan(cfg: ModelConfig, n_stages: int, strategy: str = "uniform",
              **search_kw) -> PartitionPlan:
    """Cut the group stack into ``n_stages`` contiguous stages.

    strategy="uniform" (default) is the balanced contiguous divmod split;
    strategy="auto" routes through the ``repro.plan`` cost-model searcher
    (``search_kw`` — batch/seq/optimizer/objective — feeds its cost table).
    """
    g = M.n_groups(cfg)
    if n_stages > g:
        raise ValueError(f"{n_stages} stages > {g} groups for {cfg.name}")
    if strategy == "auto":
        # lazy import: repro.plan imports PartitionPlan from this module
        from repro import plan as plan_lib
        return plan_lib.auto_plan(cfg, n_stages, **search_kw)
    if strategy != "uniform":
        raise ValueError(f"unknown partition strategy {strategy!r}; "
                         "expected 'uniform' or 'auto'")
    # balanced contiguous split
    base, rem = divmod(g, n_stages)
    bounds = []
    start = 0
    for k in range(n_stages):
        size = base + (1 if k < rem else 0)
        bounds.append((start, start + size))
        start += size
    return PartitionPlan(n_stages, tuple(bounds))


def stage_param_keys(cfg: ModelConfig, plan: PartitionPlan, k: int) -> List[str]:
    keys = ["groups"]
    if k == 0:
        keys.append("tok_embed")
        if cfg.enc_dec:
            keys += ["encoder", "enc_norm", "dec_pos"]
        if cfg.frontend == "vision":
            keys.append("img_proj")
    if k == plan.n_stages - 1:
        keys.append("final_norm")
        if not cfg.tie_embeddings:
            keys.append("unembed")
        elif "tok_embed" not in keys:
            # Tied unembedding on a stage that does NOT own the embedding:
            # a FROZEN copy.  Giving the last stage a trainable "tok_embed"
            # would let two stages train divergent copies of the same tensor,
            # with join_stage_params silently keeping whichever came last.
            keys.append("tied_unembed")
    return keys


def slice_stage_params(cfg: ModelConfig, plan: PartitionPlan, params,
                       k: int) -> Dict[str, Any]:
    """Extract exactly the parameters stage k trains (paper: each partition
    holds only its own params + optimizer state).  ``tied_unembed`` is a
    frozen snapshot of the embedding, not a trainable copy."""
    g0, g1 = plan.bounds[k]
    out: Dict[str, Any] = {}
    for key in stage_param_keys(cfg, plan, k):
        if key == "groups":
            out[key] = jax.tree_util.tree_map(lambda a: a[g0:g1],
                                              params["groups"])
        elif key == "tied_unembed":
            out[key] = params["tok_embed"]
        else:
            out[key] = params[key]
    return out


def refresh_tied_unembed(cfg: ModelConfig, plan: PartitionPlan,
                         stage_params: List[Dict[str, Any]]) -> None:
    """Sync the last stage's frozen tied-unembedding snapshot with stage 0's
    (possibly already trained) embedding.  Call before training the last
    stage in a sequential schedule so its CE phase sees the same table the
    deployed joined network will use."""
    if plan.n_stages > 1 and cfg.tie_embeddings:
        last = stage_params[plan.n_stages - 1]
        if "tied_unembed" in last:
            # a COPY, not an alias: the last stage's train step donates its
            # param buffers on accelerators, and donating an alias of stage
            # 0's trainable embedding would delete it out from under the
            # prefix forward and the final join
            last["tied_unembed"] = jnp.copy(stage_params[0]["tok_embed"])


def join_stage_params(cfg: ModelConfig, plan: PartitionPlan,
                      stage_params: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Rebuild the full param tree from per-stage trees (paper: "the
    partitions can be joined after this stage, to use the network").  Frozen
    ``tied_unembed`` snapshots are dropped: the joined network's tied
    unembedding is stage 0's trained embedding."""
    full: Dict[str, Any] = {}
    groups = [sp["groups"] for sp in stage_params]
    full["groups"] = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *groups)
    for k, sp in enumerate(stage_params):
        for key, val in sp.items():
            if key not in ("groups", "tied_unembed"):
                full[key] = val
    return full


def stage_unembed(cfg: ModelConfig, stage_params, x):
    """The last stage's unembedding of final-normed hidden states x."""
    if "tied_unembed" in stage_params:
        # frozen snapshot of the embedding: gradients must not flow into
        # it (stage 0 owns the trainable copy)
        up = dict(stage_params)
        up["tok_embed"] = jax.lax.stop_gradient(up.pop("tied_unembed"))
        return M.unembed(cfg, up, x)
    return M.unembed(cfg, stage_params, x)


def stage_forward(cfg: ModelConfig, plan: PartitionPlan, k: int, stage_params,
                  batch_or_x, *, remat=True, shard_x=None, logits=True):
    """Forward of stage k alone.

    Stage 0 consumes the raw batch (dict); later stages consume the boundary
    activation (B, S, d).  Returns (output, aux): output is the boundary
    activation for interior stages or logits for the last stage — or, with
    ``logits=False``, the last stage's final-normed hidden states (unembed
    them with ``stage_unembed``).
    """
    g0, g1 = plan.bounds[k]
    n = g1 - g0
    enc_out = None
    n_prefix = 0
    if k == 0:
        x, enc_out, n_prefix = M.embed_inputs(cfg, stage_params, batch_or_x)
    elif cfg.enc_dec:
        # boundary payload for enc-dec models carries encoder output too
        x, enc_out = batch_or_x
    else:
        x = batch_or_x
    s = x.shape[1]
    rope_cs = M.rope_for(cfg, jnp.arange(s))
    x, aux, _ = M.forward_groups(cfg, stage_params["groups"], x,
                                 rope_cs=rope_cs, enc_out=enc_out,
                                 g0=0, g1=n, remat=remat, shard_x=shard_x)
    aux["n_prefix"] = n_prefix
    if k == plan.n_stages - 1:
        x = M.norm_apply_final(cfg, stage_params, x)
        if not logits:
            return x, aux
        return stage_unembed(cfg, stage_params, x), aux
    if cfg.enc_dec:
        return (x, enc_out), aux
    return x, aux
