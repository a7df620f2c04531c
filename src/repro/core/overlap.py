"""Cross-query overlap machinery (paper §4).

Three pieces:
  * overlap statistics — the Fig. 2 / Fig. 4 profiling quantities;
  * merged schedule (exact variant) — per-group union + dedup of selected
    block indices with per-query ownership masks;
  * shared index (approximate variant) — the representative query's indices
    broadcast to its whole group.

All functions are shape-static and jit-safe: merged schedules are padded to
the group capacity C * n with a sentinel, exactly what the Pallas kernel's
scalar-prefetch path consumes.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

SENTINEL = jnp.int32(2 ** 30)


def pad_to_groups(T: int, C: int) -> int:
    return -(-T // C)


def _dedupe(idx, valid):
    """Sort and keep only first occurrences (set semantics for ratio math)."""
    key = jnp.where(valid, idx, SENTINEL)
    s = jnp.sort(key, axis=-1)
    first = jnp.concatenate([jnp.ones(s.shape[:-1] + (1,), bool),
                             s[..., 1:] != s[..., :-1]], axis=-1)
    v = first & (s < SENTINEL)
    return s, v


def overlap_ratio(idx_a, valid_a, idx_b, valid_b):
    """|I_a ∩ I_b| / |I_a ∪ I_b| (set semantics) for two index sets (..., n)."""
    ia, va = _dedupe(idx_a, valid_a)
    ib, vb = _dedupe(idx_b, valid_b)
    eq = (ia[..., :, None] == ib[..., None, :]) & \
        va[..., :, None] & vb[..., None, :]
    inter = eq.any(-1).sum(-1).astype(jnp.float32)
    na = va.sum(-1).astype(jnp.float32)
    nb = vb.sum(-1).astype(jnp.float32)
    union = na + nb - inter
    return jnp.where(union > 0, inter / union, 1.0)


def adjacent_overlap(sel_idx, sel_valid):
    """Mean selected-block overlap between adjacent verifier queries
    (Fig. 2). sel_idx: (B, T, Hkv, n). Returns (T-1,) per-adjacency means."""
    a, b = sel_idx[:, :-1], sel_idx[:, 1:]
    va, vb = sel_valid[:, :-1], sel_valid[:, 1:]
    r = overlap_ratio(a, va, b, vb)          # (B, T-1, Hkv)
    return r.mean(axis=(0, 2))


def pairwise_overlap_by_distance(sel_idx, sel_valid, positions, max_delta: int = 16):
    """Fig. 4: overlap ratio vs |token-position distance|. Returns
    (deltas (max_delta,), mean overlap (max_delta,))."""
    B, T, H, n = sel_idx.shape
    r = overlap_ratio(sel_idx[:, :, None], sel_valid[:, :, None],
                      sel_idx[:, None, :], sel_valid[:, None, :])   # (B,T,T,H)
    d = jnp.abs(positions[:, :, None] - positions[:, None, :])      # (B,T,T)
    out = []
    for delta in range(1, max_delta + 1):
        m = jnp.broadcast_to((d == delta)[..., None], r.shape)
        tot = jnp.where(m, r, 0.0).sum()
        cnt = m.sum()
        out.append(jnp.where(cnt > 0, tot / cnt, jnp.nan))
    return np.arange(1, max_delta + 1), jnp.stack(out)


@functools.lru_cache(maxsize=4096)
def group_queries(T: int, C: int):
    """Static grouping of a flattened draft batch into ceil(T/C) groups of up
    to C adjacent queries (the traversal order determines adjacency).

    Memoized by (T, C): the layout map is pure host-side numpy and was being
    rebuilt on every fused-verify call (`kernels/nsa_verify/ops.prepare_groups`
    invokes it once per layer per step). The cached array is marked
    read-only so call sites cannot mutate the shared copy. Call sites
    count their lookups, and this body its builds, into ``obs``."""
    obs.count("kernel.group_layout.builds")
    ngroups = pad_to_groups(T, C)
    pad = ngroups * C - T
    qidx = np.concatenate([np.arange(T), np.full(pad, T - 1)])      # clamp pad
    qmap = qidx.reshape(ngroups, C)
    qmap.setflags(write=False)
    return qmap, pad


def merged_schedule(sel_idx, sel_valid, C: int):
    """Exact merged-schedule (paper §4.2): per group, the sorted union of the
    member queries' selected blocks, deduplicated, plus ownership masks.

    sel_idx/sel_valid: (B, T, Hkv, n)  ->
      merged:    (B, G, Hkv, C*n) int32, sorted, padded with SENTINEL
      own:       (B, G, Hkv, C, C*n) bool — query c owns merged slot s
      m_valid:   (B, G, Hkv, C*n) bool
    Loading each merged slot once and masking rows by ``own`` is semantically
    identical to independent per-query execution.
    """
    B, T, H, n = sel_idx.shape
    obs.count("kernel.group_layout.lookups")
    qmap, pad = group_queries(T, C)
    G = qmap.shape[0]
    gi = jnp.asarray(qmap)                                           # (G, C)
    idx = sel_idx[:, gi]                                             # (B,G,C,H,n)
    val = sel_valid[:, gi]
    if pad:
        # padded replicas must not contribute ownership
        padmask = jnp.asarray(np.arange(G * C).reshape(G, C) < T)
        val = val & padmask[None, :, :, None, None]
    idx = jnp.where(val, idx, SENTINEL)
    flat = idx.transpose(0, 1, 3, 2, 4).reshape(B, G, H, C * n)      # (B,G,H,C*n)
    fval = val.transpose(0, 1, 3, 2, 4).reshape(B, G, H, C * n)
    merged = jnp.sort(flat, axis=-1)
    # dedup: first occurrence survives
    first = jnp.concatenate([
        jnp.ones(merged.shape[:-1] + (1,), bool),
        merged[..., 1:] != merged[..., :-1]], axis=-1)
    m_valid = first & (merged < SENTINEL)
    merged = jnp.where(m_valid, merged, SENTINEL)
    # compact valid entries to the front (stable: sort by (invalid, value))
    key = jnp.where(m_valid, merged, SENTINEL)
    order = jnp.argsort(key, axis=-1)
    merged = jnp.take_along_axis(merged, order, axis=-1)
    m_valid = jnp.take_along_axis(m_valid, order, axis=-1)
    # ownership: query c owns slot s iff merged[s] in its original set
    own = _ownership(merged, idx, val)
    return merged, own, m_valid


def _ownership(merged, idx, val):
    """merged: (B,G,H,M); idx/val: (B,G,C,H,n) -> own (B,G,H,C,M)."""
    cand = jnp.where(val, idx, -1).transpose(0, 1, 3, 2, 4)          # (B,G,H,C,n)
    eq = merged[:, :, :, None, :, None] == cand[:, :, :, :, None, :]  # (B,G,H,C,M,n)
    return eq.any(-1)                                                # (B,G,H,C,M)


def shared_index(sel_idx, sel_valid, positions, C: int):
    """Approximate shared-index variant (paper §4.3): every query in a group
    adopts the representative's selected blocks. Representative = the member
    with the longest prefix (max position), per the paper.

    Returns (idx, valid) with the same (B, T, Hkv, n) shape so downstream
    verification is oblivious to the grouping mode.
    """
    B, T, H, n = sel_idx.shape
    obs.count("kernel.group_layout.lookups")
    qmap, pad = group_queries(T, C)
    G = qmap.shape[0]
    gi = jnp.asarray(qmap)                                           # (G, C)
    gpos = positions[:, gi]                                          # (B, G, C)
    rep_c = jnp.argmax(gpos, axis=-1)                                # (B, G)
    rep_q = jnp.take_along_axis(jnp.broadcast_to(gi[None], (B, G, gi.shape[1])),
                                rep_c[..., None], axis=-1)[..., 0]   # (B, G)
    rep_idx = jnp.take_along_axis(sel_idx, rep_q[:, :, None, None].repeat(H, 2).repeat(n, 3), axis=1)
    rep_val = jnp.take_along_axis(sel_valid, rep_q[:, :, None, None].repeat(H, 2).repeat(n, 3), axis=1)
    # broadcast back to every member of the group
    out_idx = jnp.repeat(rep_idx, C, axis=1)[:, :T]
    out_val = jnp.repeat(rep_val, C, axis=1)[:, :T]
    # exact per-query causality is enforced downstream by position masks, but
    # a representative deeper than the member may select the block containing
    # positions the member cannot see — masked inside attention (tok <= pos).
    return out_idx, out_val
