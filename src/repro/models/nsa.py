"""Native Sparse Attention (NSA) in pure JAX — the target-model attention
backend that SSV verifies against.

NSA (Yuan et al., ACL 2025) fuses three branches with learned per-head gates:
  cmp — attention over compressed KV blocks (length l, stride d)
  slc — attention over Top-n *selected* raw KV blocks (size l'), routed by
        compressed-attention scores (GQA-group shared)
  win — dense sliding window over the last w tokens

This module provides:
  * parameter init (projections + compression pooling + gates)
  * compression-cache construction / incremental update
  * routing: cmp scores -> selection-block scores -> Top-n indices
  * three execution modes:
      - train/prefill: mask-based (exact semantics, chunked, O(S·S) compute
        upper bound but no gather blow-up; what the dry-run lowers)
      - decode: true sparse gather for a single query
      - verify: gamma tree-masked draft queries with *external* per-query
        selected indices (supplied by core/verify.py, which implements the
        paper's refresh/reuse + exact/approx grouping policies)

Compression uses learned softmax position-pooling plus a per-head linear
projection — a TPU-friendly stand-in for NSA's block MLP (same information
flow: intra-block position-aware learned aggregation). Noted in DESIGN.md.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, NSAConfig
from repro.core import kvstore
from repro.models import layers
from repro.models.attention import NEG_INF, attn_init, qkv


# ---------------------------------------------------------------- init
def nsa_init(key, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = attn_init(ks[0], cfg, dtype)
    nsa = cfg.nsa
    p["phi_k"] = jnp.zeros((nsa.cmp_block,), jnp.float32)     # softmax pooling logits
    p["phi_v"] = jnp.zeros((nsa.cmp_block,), jnp.float32)
    p["w_cmp_k"] = (jnp.eye(cfg.head_dim) +
                    0.02 * jax.random.normal(ks[1], (cfg.head_dim, cfg.head_dim))).astype(dtype)
    p["w_cmp_v"] = (jnp.eye(cfg.head_dim) +
                    0.02 * jax.random.normal(ks[2], (cfg.head_dim, cfg.head_dim))).astype(dtype)
    # per-head gates for (cmp, slc, win); bias init so win starts dominant
    p["w_gate"] = (jax.random.normal(ks[3], (cfg.d_model, 3 * cfg.num_heads)) * 0.01).astype(dtype)
    p["b_gate"] = jnp.zeros((3 * cfg.num_heads,), jnp.float32)
    return p


# ---------------------------------------------------------------- geometry
def num_cmp_blocks(P: int, nsa: NSAConfig) -> int:
    return 0 if P < nsa.cmp_block else (P - nsa.cmp_block) // nsa.cmp_stride + 1


def num_sel_blocks(P: int, nsa: NSAConfig) -> int:
    return max(0, -(-P // nsa.sel_block))


@functools.lru_cache(maxsize=64)
def overlap_matrix(ncb: int, nsb: int, l: int, d: int, lp: int) -> np.ndarray:
    """Fractional overlap M[i, j] of cmp block i (start i*d, len l) with sel
    block j (start j*lp, len lp): used to map cmp-attention probability mass
    onto selection blocks (NSA eq. 9 generalized to l' != d)."""
    i = np.arange(ncb)[:, None]
    j = np.arange(nsb)[None, :]
    lo = np.maximum(i * d, j * lp)
    hi = np.minimum(i * d + l, (j + 1) * lp)
    return (np.maximum(0, hi - lo) / float(l)).astype(np.float32)


def cmp_visible_mask(positions, ncb: int, nsa: NSAConfig):
    """cmp block i fully precedes query at pos p iff i*d + l - 1 <= p.
    positions: (..., T) -> mask (..., T, ncb)."""
    ends = jnp.arange(ncb) * nsa.cmp_stride + nsa.cmp_block - 1
    return ends[None, :] <= positions[..., None]


# ---------------------------------------------------------------- compression
def compress_kv(params, k, v, nsa: NSAConfig):
    """k, v: (B, S, Hkv, Dh) -> (B, NCB, Hkv, Dh) with NCB = num_cmp_blocks(S).

    Strided blocks are materialized as a gather of shape (NCB, l); softmax
    position pooling then projects each block to one compressed KV pair.
    """
    B, S, H, Dh = k.shape
    ncb = num_cmp_blocks(S, nsa)
    if ncb == 0:
        z = jnp.zeros((B, 0, H, Dh), k.dtype)
        return z, z
    starts = np.arange(ncb) * nsa.cmp_stride
    idx = starts[:, None] + np.arange(nsa.cmp_block)[None, :]        # (NCB, l)
    kb = jnp.take(k, jnp.asarray(idx), axis=1)                        # (B, NCB, l, H, Dh)
    vb = jnp.take(v, jnp.asarray(idx), axis=1)
    wk = jax.nn.softmax(params["phi_k"]).astype(jnp.float32)
    wv = jax.nn.softmax(params["phi_v"]).astype(jnp.float32)
    k_cmp = jnp.einsum("bnlhd,l->bnhd", kb.astype(jnp.float32), wk)
    v_cmp = jnp.einsum("bnlhd,l->bnhd", vb.astype(jnp.float32), wv)
    k_cmp = (k_cmp @ params["w_cmp_k"].astype(jnp.float32)).astype(k.dtype)
    v_cmp = (v_cmp @ params["w_cmp_v"].astype(jnp.float32)).astype(v.dtype)
    return k_cmp, v_cmp


def update_cmp_cache(params, cache, cmp_cache, old_len, new_len, nsa: NSAConfig):
    """Incrementally append compressed blocks that became complete when the
    committed prefix grew old_len -> new_len (static ints for the ref path)."""
    ncb_old, ncb_new = num_cmp_blocks(old_len, nsa), num_cmp_blocks(new_len, nsa)
    if ncb_new == ncb_old:
        return cmp_cache
    starts = np.arange(ncb_old, ncb_new) * nsa.cmp_stride
    idx = starts[:, None] + np.arange(nsa.cmp_block)[None, :]
    kb = jnp.take(cache["k"], jnp.asarray(idx), axis=1)
    vb = jnp.take(cache["v"], jnp.asarray(idx), axis=1)
    wk = jax.nn.softmax(params["phi_k"]).astype(jnp.float32)
    wv = jax.nn.softmax(params["phi_v"]).astype(jnp.float32)
    k_new = (jnp.einsum("bnlhd,l->bnhd", kb.astype(jnp.float32), wk)
             @ params["w_cmp_k"].astype(jnp.float32)).astype(cmp_cache["k_cmp"].dtype)
    v_new = (jnp.einsum("bnlhd,l->bnhd", vb.astype(jnp.float32), wv)
             @ params["w_cmp_v"].astype(jnp.float32)).astype(cmp_cache["v_cmp"].dtype)
    k_cmp = jax.lax.dynamic_update_slice_in_dim(cmp_cache["k_cmp"], k_new, ncb_old, axis=1)
    v_cmp = jax.lax.dynamic_update_slice_in_dim(cmp_cache["v_cmp"], v_new, ncb_old, axis=1)
    return {"k_cmp": k_cmp, "v_cmp": v_cmp}


def update_cmp_cache_dyn(params, cache, cmp_cache, old_len, new_len, max_new: int,
                         nsa: NSAConfig, overlay=None):
    """Traced-length incremental compression update for the jitted engine.

    old_len/new_len are traced int32; at most ``max_new`` blocks can complete
    per commit (static bound: ceil((gamma+1)/stride)+1). Candidate blocks are
    computed unconditionally and masked into the cache.

    ``cache`` is a raw ``{"k", "v"}`` dict (dense) or a ``kvstore.KVView``
    over either backend. ``overlay`` = (k_acc, v_acc) of shape
    (B, T_acc, Hkv, Dh) supplies the tokens committed at ``old_len`` this
    step *before* they land in the store — the paged batched commit reads
    the fresh region from the accept buffer instead of ordering a pool
    write ahead of the compression update.
    """
    kv = kvstore.as_view(cache)
    ncb_old = dyn_num_cmp_blocks(old_len, nsa)
    ncb_new = dyn_num_cmp_blocks(new_len, nsa)
    B = kv.batch
    S = kv.max_len
    starts = (ncb_old + jnp.arange(max_new)) * nsa.cmp_stride          # (max_new,)
    idx = jnp.clip(starts[:, None] + jnp.arange(nsa.cmp_block)[None, :], 0, S - 1)
    kb, vb = kv.gather_tokens(jnp.broadcast_to(idx[None], (B,) + idx.shape))
    if overlay is not None:
        k_acc, v_acc = overlay                                         # (B,T_acc,H,Dh)
        T_acc = k_acc.shape[1]
        rel = jnp.clip(idx[None] - old_len, 0, T_acc - 1)              # (B?,max_new,l)
        rel = jnp.broadcast_to(rel, (B,) + idx.shape).reshape(B, -1)
        fresh = (idx[None] >= old_len) & (idx[None] < old_len + T_acc)
        fresh = jnp.broadcast_to(fresh, (B,) + idx.shape)[..., None, None]
        ko = jnp.take_along_axis(k_acc, rel[..., None, None], axis=1
                                 ).reshape(kb.shape)
        vo = jnp.take_along_axis(v_acc, rel[..., None, None], axis=1
                                 ).reshape(vb.shape)
        # cast to the store dtype first: the dense path reads these tokens
        # back from the cache after the write (post-rounding), and backend
        # token-equality requires bit-matching compression inputs
        kb = jnp.where(fresh, ko.astype(kb.dtype), kb)
        vb = jnp.where(fresh, vo.astype(vb.dtype), vb)
    wk = jax.nn.softmax(params["phi_k"]).astype(jnp.float32)
    wv = jax.nn.softmax(params["phi_v"]).astype(jnp.float32)
    k_new = (jnp.einsum("bnlhd,l->bnhd", kb.astype(jnp.float32), wk)
             @ params["w_cmp_k"].astype(jnp.float32))
    v_new = (jnp.einsum("bnlhd,l->bnhd", vb.astype(jnp.float32), wv)
             @ params["w_cmp_v"].astype(jnp.float32))
    valid = (ncb_old + jnp.arange(max_new)) < ncb_new                  # (max_new,)
    NCB = cmp_cache["k_cmp"].shape[1]
    slot = jnp.clip(ncb_old + jnp.arange(max_new), 0, NCB - 1)
    oh = (jax.nn.one_hot(slot, NCB, dtype=jnp.float32) * valid[:, None])  # (max_new,NCB)
    k_cmp = cmp_cache["k_cmp"].astype(jnp.float32) * (1 - oh.sum(0))[None, :, None, None] \
        + jnp.einsum("bnhd,nc->bchd", k_new, oh)
    v_cmp = cmp_cache["v_cmp"].astype(jnp.float32) * (1 - oh.sum(0))[None, :, None, None] \
        + jnp.einsum("bnhd,nc->bchd", v_new, oh)
    return {"k_cmp": k_cmp.astype(cmp_cache["k_cmp"].dtype),
            "v_cmp": v_cmp.astype(cmp_cache["v_cmp"].dtype)}


def init_cmp_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.float32,
                   store=None):
    """Compressed-KV cache. Under the paged store the compressed blocks stay
    row-dense on purpose: they are ``cmp_stride``x smaller than raw KV (the
    dominant term paging targets) and the routing launch reads them densely
    every step — paging them would turn one contiguous read into a gather
    for <7% of the KV footprint. ``store`` is accepted so call sites thread
    one handle; only the raw-KV layout changes with the backend."""
    del store
    ncb = num_cmp_blocks(max_len, cfg.nsa)
    # pad the block axis to a shardable multiple (512 covers the multi-pod
    # sequence-sharded layout); padded blocks are invisible to every query
    # (cmp_visible_mask + ncb_valid) so the values never matter
    pad_to = 512 if max_len >= 8192 else 8
    ncb_p = max(-(-max(ncb, 1) // pad_to) * pad_to, pad_to) if ncb > 0 else \
        max(1, min(pad_to, 8))
    return {
        "k_cmp": jnp.zeros((batch, ncb_p, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v_cmp": jnp.zeros((batch, ncb_p, cfg.num_kv_heads, cfg.head_dim), dtype),
    }


# ---------------------------------------------------------------- routing
def routing(params, cfg: ModelConfig, q, k_cmp, v_cmp, positions, kv_len: int,
            ncb_valid=None):
    """The compression/routing launch (paper §5.1 'Routing Launch').

    q: (B, T, Hq, Dh); k_cmp/v_cmp: (B, NCB, Hkv, Dh); positions: (B, T).
    Returns (o_cmp (B,T,Hq,Dh), p_slc (B,T,Hkv,NSB), sel indices not included —
    Top-n is applied by the caller so exact/approx grouping policies can
    reinterpret the scores).
    """
    nsa = cfg.nsa
    B, T, Hq, Dh = q.shape
    Hkv, G = cfg.num_kv_heads, cfg.q_per_kv
    ncb = k_cmp.shape[1]
    qg = q.reshape(B, T, Hkv, G, Dh)
    scale = 1.0 / np.sqrt(Dh)
    logits = jnp.einsum("bthgd,bnhd->bthgn", qg.astype(jnp.float32),
                        k_cmp.astype(jnp.float32)) * scale
    vis = cmp_visible_mask(positions, ncb, nsa)                     # (B, T, NCB)
    if ncb_valid is not None:
        vis = vis & (jnp.arange(ncb)[None, None, :] < ncb_valid)
    logits = jnp.where(vis[:, :, None, None], logits, NEG_INF)
    p_cmp = jax.nn.softmax(logits, axis=-1)                          # (B,T,Hkv,G,NCB)
    p_cmp = jnp.where(vis[:, :, None, None], p_cmp, 0.0)             # all-masked rows -> 0
    o_cmp = jnp.einsum("bthgn,bnhd->bthgd", p_cmp, v_cmp.astype(jnp.float32))
    o_cmp = o_cmp.reshape(B, T, Hq, Dh)

    nsb = num_sel_blocks(kv_len, nsa)
    M = jnp.asarray(overlap_matrix(ncb, max(nsb, 1), nsa.cmp_block, nsa.cmp_stride,
                                   nsa.sel_block))
    # GQA-group share: sum scores over the G query heads of each KV group.
    p_grp = p_cmp.sum(axis=3)                                        # (B,T,Hkv,NCB)
    p_slc = jnp.einsum("bthn,ns->bths", p_grp, M)                    # (B,T,Hkv,NSB)
    return o_cmp, p_slc


def select_topn(p_slc, positions, kv_len: int, nsa: NSAConfig):
    """Top-n selection-block indices with mandatory initial + local blocks.

    p_slc: (B, T, Hkv, NSB); positions: (B, T).  Returns
    (indices (B,T,Hkv,n) int32 sorted ascending, valid (B,T,Hkv,n) bool).
    Invalid slots (block not yet causal / short prefix) carry index 0 and
    valid=False; downstream kernels mask them.
    """
    B, T, Hkv, NSB = p_slc.shape
    n = min(nsa.n_selected, NSB)
    starts = jnp.arange(NSB) * nsa.sel_block                         # block start pos
    causal = starts[None, None, :] <= positions[:, None][..., None] if positions.ndim == 1 \
        else starts[None, None, None, :] <= positions[..., None, None]
    # normalize shapes: causal (B, T, 1, NSB)
    causal = jnp.broadcast_to(causal.reshape(B, T, 1, NSB), (B, T, Hkv, NSB))
    # prefix-bounded: selection only routes over committed tokens
    causal &= (starts < kv_len)[None, None, None, :]

    scores = jnp.where(causal, p_slc, NEG_INF)
    # mandatory blocks: initial blocks + last n_local blocks at/preceding pos
    mand = jnp.zeros((B, T, Hkv, NSB), bool)
    if nsa.n_init_blocks > 0:
        mand = mand.at[..., : nsa.n_init_blocks].set(True)
    if nsa.n_local_blocks > 0:
        # last local blocks relative to each query position (within prefix)
        last_blk = jnp.minimum(positions[..., None], kv_len - 1) // nsa.sel_block  # (B,T,1)->? positions (B,T)
        last_blk = last_blk.reshape(B, T, 1, 1)
        off = jnp.arange(nsa.n_local_blocks).reshape(1, 1, 1, -1)
        loc = jnp.clip(last_blk - off, 0, NSB - 1)
        mand = mand | (jax.nn.one_hot(loc, NSB, dtype=jnp.int32).sum(axis=3) > 0)
    mand &= causal
    scores = jnp.where(mand, scores + 1e6, scores)

    top_vals, top_idx = jax.lax.top_k(scores, n)                      # (B,T,Hkv,n)
    valid = top_vals > NEG_INF / 2
    top_idx = jnp.where(valid, top_idx, 0)
    order = jnp.argsort(jnp.where(valid, top_idx, NSB + 1), axis=-1)
    top_idx = jnp.take_along_axis(top_idx, order, axis=-1)
    valid = jnp.take_along_axis(valid, order, axis=-1)
    return jax.lax.stop_gradient(top_idx), jax.lax.stop_gradient(valid)


# ---------------------------------------------------------------- gates
def gates(params, x, num_heads: int):
    g = jax.nn.sigmoid(x.astype(jnp.float32) @ params["w_gate"].astype(jnp.float32)
                       + params["b_gate"])
    B, T = x.shape[0], x.shape[1]
    return g.reshape(B, T, 3, num_heads)  # (B,T,3,Hq): order cmp, slc, win


# ---------------------------------------------------------------- train mode
def attend_train_nsa(params, cfg: ModelConfig, x, positions, chunk: int = 512):
    """Full-sequence NSA with exact semantics via masks (train / prefill).

    Returns (out (B,S,D), (k, v) full-sequence for cache building).
    Chunked over queries: per chunk the slc branch is a masked dense
    attention (selection mask at token granularity), cmp is an (S_c, NCB)
    attention, win an (S_c, S) banded attention.
    """
    nsa = cfg.nsa
    B, S, _ = x.shape
    Hq, Hkv, G, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim
    q, k, v = qkv(params, cfg, x, positions)
    k_cmp, v_cmp = compress_kv(params, k, v, nsa)
    ncb = k_cmp.shape[1]
    nsb = num_sel_blocks(S, nsa)
    g_all = gates(params, x, Hq)
    scale = 1.0 / np.sqrt(Dh)

    nchunk = max(1, S // chunk) if (chunk and S % chunk == 0) else 1
    Sc = S // nchunk

    def one_chunk(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * Sc, Sc, axis=1)
        qc, posc, gc = sl(q), sl(positions) if positions.ndim > 1 else jax.lax.dynamic_slice_in_dim(positions, i * Sc, Sc, 0), sl(g_all)
        posc2 = posc if posc.ndim == 2 else jnp.broadcast_to(posc[None], (B, Sc))
        # --- routing + cmp branch. Serve-consistent semantics: the query at
        # position p treats tokens < p as its committed prefix, so routing,
        # mandatory-local-block choice, and the slc token mask all use p-1 /
        # strict inequalities — exactly what nsa_verify_ref computes with
        # prefix_len == p (verified by tests/test_model_parity.py).
        o_cmp, p_slc = routing(params, cfg, qc, k_cmp, v_cmp, posc2 - 1, S)
        idx, idx_valid = select_topn(p_slc, posc2 - 1, S, nsa)        # (B,Sc,Hkv,n)
        # --- slc branch: token-granular mask from selected blocks
        blk_of_tok = jnp.arange(S) // nsa.sel_block                   # (S,)
        sel_mask = (idx[..., None] == blk_of_tok[None, None, None, None, :]) & \
            idx_valid[..., None]                                      # (B,Sc,Hkv,n,S)
        sel_mask = sel_mask.any(axis=3)                               # (B,Sc,Hkv,S)
        tok_strict = jnp.arange(S)[None, None, :] < posc2[..., None]   # slc: < p
        tok_causal = jnp.arange(S)[None, None, :] <= posc2[..., None]  # win: <= p
        sel_mask &= tok_strict[:, :, None, :]
        qg = qc.reshape(B, Sc, Hkv, G, Dh)
        logit_s = jnp.einsum("bthgd,bkhd->bhgtk", qg.astype(jnp.float32),
                             k.astype(jnp.float32)) * scale
        logit_s = jnp.where(sel_mask.transpose(0, 2, 1, 3)[:, :, None], logit_s, NEG_INF)
        p_s = jax.nn.softmax(logit_s, axis=-1)
        p_s = jnp.where(sel_mask.transpose(0, 2, 1, 3)[:, :, None], p_s, 0.0)
        o_slc = jnp.einsum("bhgtk,bkhd->bthgd", p_s, v.astype(jnp.float32)).reshape(B, Sc, Hq, Dh)
        # --- win branch
        win_mask = tok_causal & (jnp.arange(S)[None, None, :] > posc2[..., None] - nsa.window)
        logit_w = jnp.einsum("bthgd,bkhd->bhgtk", qg.astype(jnp.float32),
                             k.astype(jnp.float32)) * scale
        logit_w = jnp.where(win_mask[:, None, None], logit_w, NEG_INF)
        p_w = jax.nn.softmax(logit_w, axis=-1)
        o_win = jnp.einsum("bhgtk,bkhd->bthgd", p_w, v.astype(jnp.float32)).reshape(B, Sc, Hq, Dh)
        # --- gated combine
        out = (gc[:, :, 0, :, None] * o_cmp + gc[:, :, 1, :, None] * o_slc +
               gc[:, :, 2, :, None] * o_win)
        return out.astype(x.dtype)

    if nchunk > 1:
        _, outs = jax.lax.scan(lambda c, i: (c, one_chunk(i)), None, jnp.arange(nchunk))
        out = outs.swapaxes(0, 1).reshape(B, S, Hq, Dh)
    else:
        out = one_chunk(0)
    out = out.reshape(B, S, Hq * Dh) @ params["wo"]
    return out, (k, v)


def dyn_num_cmp_blocks(P, nsa: NSAConfig):
    """Traced version of num_cmp_blocks (P may be a traced int32)."""
    return jnp.where(P < nsa.cmp_block, 0, (P - nsa.cmp_block) // nsa.cmp_stride + 1)


# ---------------------------------------------------------------- verify (ref)
def gather_blocks(kv, idx, sel_block: int):
    """Gather selected blocks per (batch, query, kv-head) through the KV
    store: ``kv`` is a ``kvstore.KVView`` (dense or paged) or a raw
    ``{"k", "v"}`` dict. idx: (B, T, Hkv, n) block indices. Returns k_sel,
    v_sel: (B, T, Hkv, n, l', Dh).

    Out-of-range, negative, or (paged) unmapped block indices read an
    explicit zero page — never a silently clamped neighbor block. Callers
    additionally mask such positions out of the softmax (``nsa_verify_ref``
    adds ``tok_pos >= 0`` to the selection mask), so an adversarial index
    can neither read foreign KV nor shift attention mass.
    """
    return kvstore.as_view(kv).gather_blocks(idx, sel_block)


def nsa_verify_ref(params, cfg: ModelConfig, x, cache, cmp_cache, prefix_len,
                   positions, tree_mask, sel_idx=None, sel_valid=None,
                   return_kv: bool = True):
    """Reference NSA verification over gamma draft tokens (pure jnp oracle).

    x: (B, T, D) draft hidden states; positions (B, T) absolute; tree_mask
    (B, T, T).  ``sel_idx``/``sel_valid`` ((B,T,Hkv,n)) may be supplied by the
    SSV orchestrator (refresh/reuse + grouping policies); if None, fresh
    routing is computed (all-refresh, per-query exact behavior).

    cmp/slc branches attend the committed prefix only; the win branch covers
    the trailing window of the prefix plus tree-masked draft tokens —
    mirroring the paper's kernel semantics (sliding window stays exact).

    ``cache`` is the KV store handle: a ``kvstore.KVView`` (dense or paged —
    the slc gather and the win slice resolve through the page table when
    paged) or a raw ``{"k", "v"}`` dict (seed call sites).
    """
    nsa = cfg.nsa
    B, T, _ = x.shape
    Hq, Hkv, G, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim
    kv = kvstore.as_view(cache)
    q, k_new, v_new = qkv(params, cfg, x, positions)
    scale = 1.0 / np.sqrt(Dh)
    ncb_valid = dyn_num_cmp_blocks(prefix_len, nsa)
    g_all = gates(params, x, Hq)

    # ---- routing + cmp branch over committed prefix (max shapes + validity:
    # prefix_len may be a traced scalar in the jitted serve path)
    k_cmp, v_cmp = cmp_cache["k_cmp"], cmp_cache["v_cmp"]
    with jax.named_scope("nsa.cmp"):
        o_cmp, p_slc = routing(params, cfg, q, k_cmp, v_cmp, positions,
                               kv_len=kv.max_len, ncb_valid=ncb_valid)
    if sel_idx is None:
        with jax.named_scope("nsa.select"):
            sel_idx, sel_valid = select_topn(p_slc, positions, prefix_len, nsa)

    # ---- slc branch: gather + per-token causal/prefix mask
    qg = q.reshape(B, T, Hkv, G, Dh)
    with jax.named_scope("nsa.slc"):
        k_sel, v_sel = gather_blocks(kv, sel_idx, nsa.sel_block)
        n = sel_idx.shape[-1]
        tok_pos = sel_idx[..., None] * nsa.sel_block + jnp.arange(nsa.sel_block)  # (B,T,Hkv,n,l')
        logit_sel = jnp.einsum("bthgd,bthnld->bthgnl", qg.astype(jnp.float32),
                               k_sel.astype(jnp.float32)) * scale
        # tok_pos >= 0 guards adversarial negative block indices (which would
        # otherwise pass the prefix/causal checks against a zero-filled gather)
        m_sel = (tok_pos >= 0) & (tok_pos < prefix_len) & \
            (tok_pos <= positions[:, :, None, None, None]) & sel_valid[..., None]
        logit_sel = jnp.where(m_sel[:, :, :, None], logit_sel, NEG_INF)
        flat = logit_sel.reshape(B, T, Hkv, G, n * nsa.sel_block)
        p_sel = jax.nn.softmax(flat, axis=-1)
        p_sel = jnp.where(m_sel[:, :, :, None].reshape(B, T, Hkv, 1, -1), p_sel, 0.0)
        o_slc = jnp.einsum("bthgk,bthkd->bthgd", p_sel,
                           v_sel.reshape(B, T, Hkv, n * nsa.sel_block, Dh).astype(jnp.float32))
        o_slc = o_slc.reshape(B, T, Hq, Dh)

    # ---- win branch: trailing-window *slice* of the prefix (keeps decode
    # sub-quadratic at 500K context) + tree-masked draft tokens
    with jax.named_scope("nsa.win"):
        S_max = kv.max_len
        W = min(nsa.window, S_max)
        win_start = jnp.clip(jnp.asarray(prefix_len) - W, 0, max(S_max - W, 0))
        k_win, v_win = kv.window(win_start, W)
        kpos = jnp.broadcast_to((win_start + jnp.arange(W)).reshape(1, 1, W), (B, T, W))
        pmask = (kpos < jnp.asarray(prefix_len)) & \
            (kpos > positions[..., None] - nsa.window) & (kpos <= positions[..., None])
        logit_p = jnp.einsum("bthgd,bkhd->bthgk", qg.astype(jnp.float32),
                             k_win.astype(jnp.float32)) * scale
        logit_p = jnp.where(pmask[:, :, None, None], logit_p, NEG_INF)
        dist = positions[:, :, None] - positions[:, None, :]
        dmask = tree_mask & (dist < nsa.window) & (dist >= 0)
        logit_d = jnp.einsum("bthgd,bkhd->bthgk", qg.astype(jnp.float32),
                             k_new.astype(jnp.float32)) * scale
        logit_d = jnp.where(dmask[:, :, None, None], logit_d, NEG_INF)
        logit_w = jnp.concatenate([logit_p, logit_d], axis=-1)
        p_w = jax.nn.softmax(logit_w, axis=-1)
        o_win = jnp.einsum("bthgk,bkhd->bthgd", p_w[..., :W],
                           v_win.astype(jnp.float32)) + \
            jnp.einsum("bthgk,bkhd->bthgd", p_w[..., W:], v_new.astype(jnp.float32))
        o_win = o_win.reshape(B, T, Hq, Dh)

    out = (g_all[:, :, 0, :, None] * o_cmp + g_all[:, :, 1, :, None] * o_slc +
           g_all[:, :, 2, :, None] * o_win).astype(x.dtype)
    out = out.reshape(B, T, Hq * Dh) @ params["wo"]
    if return_kv:
        return out, (k_new, v_new), (sel_idx, sel_valid)
    return out


def nsa_decode_ref(params, cfg: ModelConfig, x, cache, cmp_cache, length: int):
    """Single-token autoregressive NSA decode (the paper's 49-tok/s baseline
    shape). Thin wrapper: verify with T=1 and a trivial tree mask, then
    commit k/v through the store handle (dense write or page-table scatter);
    the caller updates the compression cache via update_cmp_cache.

    ``cache`` may be a raw ``{"k", "v"}`` dict or a ``kvstore.KVView``; the
    updated store comes back in the same form."""
    B = x.shape[0]
    positions = jnp.full((B, 1), length, jnp.int32)
    tree_mask = jnp.ones((B, 1, 1), bool)
    out, (k_new, v_new), _ = nsa_verify_ref(params, cfg, x, cache, cmp_cache,
                                            length, positions, tree_mask)
    kv = kvstore.as_view(cache)
    k, v = kv.write(k_new, v_new, length)
    if isinstance(cache, kvstore.KVView):
        return out, kvstore.KVView(k, v, kv.pages)
    return out, {"k": k, "v": v}
