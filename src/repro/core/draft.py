"""Draft model + tree expansion.

The draft is a small dense transformer sharing the target's vocabulary (the
classic two-model speculative setup). An EAGLE-style feature-fusion hook is
available: when ``feature_fusion`` is on, the draft's input embedding at the
pending root is augmented with the target's last hidden state (projected),
which is how EAGLE-3 conditions the draft on target features.

Tree expansion runs level by level: level-(d+1) candidate tokens are the
top-k of the draft's logits at the depth-d nodes, taken from those parent
rows alone by k max-reductions (``top_k_indices``), not by sorting the
vocabulary. Each level re-verifies the partial tree through the draft's own
``verify_step`` (tree-masked), so the draft KV used for deeper levels is
exact. The final full-tree pass also
yields the draft-side K/V updates used to commit accepted tokens into the
draft cache, and the per-node draft distributions ``node_q`` consumed by
stochastic acceptance.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import ModelConfig
from repro.core.tree import TreeTopology, positions_for
from repro.models import layers, model


def draft_config(target_cfg: ModelConfig, num_layers: int = 2, d_model: int = 0,
                 name: str = "") -> ModelConfig:
    d = d_model or max(64, target_cfg.d_model // 4)
    heads = max(2, target_cfg.num_heads // 4)
    while d % heads:
        heads -= 1
    return dataclasses.replace(
        target_cfg,
        name=name or f"{target_cfg.name}-draft",
        num_layers=num_layers,
        d_model=d,
        num_heads=heads,
        num_kv_heads=heads,
        head_dim=0,
        d_ff=2 * d,
        attention="dense",
        block_pattern=("attn",),
        moe=None,
        recurrent=None,
        modality="text",
        frontend_dim=0,
    )


def sibling_ranks(topo: TreeTopology) -> np.ndarray:
    """rank[i] = index of node i among its siblings (drives top-k assignment)."""
    T = topo.num_nodes
    rank = np.zeros(T, np.int64)
    seen: dict = {}
    for i in range(1, T):
        p = int(topo.parents[i])
        rank[i] = seen.get(p, 0)
        seen[p] = rank[i] + 1
    return rank


def _total_order_key(x):
    """int32 keys ordered as ``lax.top_k`` orders floats (IEEE total order:
    -NaN < -inf < ... < -0 < +0 < ... < +inf < NaN). bf16 widens to f32
    exactly; no key equals int32's minimum except that of the one f32 NaN
    whose bits are all ones."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def top_k_indices(x, k: int):
    """``jax.lax.top_k(x, k)[1]`` by k max-reductions over the last axis.

    Higher value first; among equal values the lower index first. Pass j
    takes the first argmax of the keys with passes 0..j-1's indices masked
    below every key, so the picks stay distinct even in a row of -inf.
    TPU lowers ``top_k`` to a sort of the whole row; this reads the row k
    times.
    """
    key = _total_order_key(x)
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    floor = jnp.iinfo(jnp.int32).min
    picks = []
    for _ in range(k):
        i = jnp.argmax(key, axis=-1).astype(jnp.int32)
        picks.append(i)
        key = jnp.where(iota == i[..., None], floor, key)
    return jnp.stack(picks, axis=-1)


def expand_tree(verify_fn, draft_cfg: ModelConfig, draft_caches, topo: TreeTopology,
                pending_token, temperature: float = 0.0):
    """Fill the tree's token ids by expanding with the draft model.

    verify_fn(caches, tokens, positions, tmask, parents) -> (logits, updates)
    — typically a jitted closure over the draft params/config.
    pending_token: (B,) int32 — the tree root's token.
    Returns (tokens (B, T), node_q (B, T, V) draft distributions, updates)
    where ``updates`` are the draft verify-step cache updates of the final
    full-tree pass (for committing).
    """
    B = pending_token.shape[0]
    T = topo.num_nodes
    prefix = draft_caches["length"]
    positions = jnp.asarray(positions_for(topo, 0))[None] + prefix
    positions = jnp.broadcast_to(positions, (B, T)).astype(jnp.int32)
    tmask = jnp.broadcast_to(jnp.asarray(topo.mask)[None], (B, T, T))
    parents = jnp.asarray(topo.parents)

    tokens = jnp.zeros((B, T), jnp.int32).at[:, 0].set(pending_token)
    depths = topo.depths
    maxd = int(depths.max()) if T > 1 else 0
    rank = sibling_ranks(topo)
    node_q = None
    updates = None

    for d in range(maxd + 1):
        logits, updates = verify_fn(draft_caches, tokens, positions, tmask, parents)
        scaled = logits.astype(jnp.float32)
        if temperature > 0:
            scaled = scaled / temperature
        node_q = jax.nn.softmax(scaled, axis=-1)
        if d == maxd:
            break
        # assign depth-(d+1) tokens: child i gets the rank[i]-th top token of
        # its parent's draft logits, read from the depth-d parent rows only
        level = np.where(depths == d + 1)[0]
        kmax = int(rank[level].max()) + 1
        rows, row_of = np.unique(topo.parents[level], return_inverse=True)
        lo = int(rows[0])
        # bfs parents are contiguous: a slice the reductions read in place
        sel = (slice(lo, lo + len(rows)) if rows[-1] - lo + 1 == len(rows)
               else rows)
        with jax.named_scope("ssv.draft.topk"):
            topk_idx = top_k_indices(logits[:, sel], kmax)           # (B, R, kmax)
        obs.count("draft.topk.rows", len(rows))
        obs.count("draft.topk.passes", kmax)
        picked = topk_idx[:, jnp.asarray(row_of), jnp.asarray(rank[level])]
        tokens = tokens.at[:, jnp.asarray(level)].set(picked)
    return tokens, node_q, updates
