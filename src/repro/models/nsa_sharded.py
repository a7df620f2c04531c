"""Sequence-sharded NSA decode via shard_map — the §Perf optimization for
batch-1 long-context serving (long_500k cells).

Problem (measured in the baseline dry-run): with the KV cache sharded along
the sequence axis, XLA's SPMD partitioner cannot execute the selection-branch
gather or the sliding-window dynamic-slice locally — it falls back to
"involuntary full rematerialization" (replicating multi-GB cache slices), so
a single decoded token is COLLECTIVE-bound (≈1.5 s roofline for qwen3-8b at
524K context on the single-pod mesh).

Fix — the flash-decoding/split-KV pattern adapted to NSA's three branches.
Each shard owns a contiguous slice of the raw and compressed caches and
computes only over local data:

  1. local routing: q·K_cmp over local compressed blocks -> the cmp branch's
     local online-softmax state AND local partial selection-block scores;
  2. one psum of the (B, Hkv, NSB) partial score vector -> every shard
     derives the IDENTICAL exact global Top-n (mandatory blocks included);
  3. local gathers: the tokens of each selected block that live on this
     shard (token-granular ownership, so blocks may straddle shard
     boundaries), the local window segment, and (on shard 0 only) the new
     token itself -> local slc/win branch states;
  4. per-branch log-sum-exp merge across shards (psum of O(Hq·Dh) floats)
     and gated aggregation.

Wire bytes per layer-step: one (B,Hkv,NSB) psum + three O(B·Hq·Dh) merges —
microscopic next to the baseline's replicated cache slices. Cache commits
(raw K/V + freshly completed compressed blocks) happen shard-locally inside
the same shard_map. Exact semantics vs nsa.nsa_verify_ref (T=1) up to
reduction order — tests/test_distributed_nsa.py.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig, NSAConfig
from repro.models import layers
from repro.models.attention import NEG_INF, qkv
from repro.models.nsa import dyn_num_cmp_blocks, gates, select_topn


def _state(logits, mask, v):
    """Branch state: logits (B,Hkv,Gq,K), mask (B,1|Hkv,1,K)-broadcastable,
    v (B,K,Hkv,Dh) -> m,l (B,Hkv,Gq), acc (B,Hkv,Gq,Dh)."""
    lm = jnp.where(mask, logits, NEG_INF)
    m = lm.max(-1)
    p = jnp.exp(lm - m[..., None]) * mask
    l = p.sum(-1)
    acc = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return m, l, acc


def _merge(states, axis):
    """LSE-merge branch states across shards. states = (m, l, acc)."""
    m, l, acc = states
    m_max = jax.lax.pmax(m, axis)
    s = jnp.exp(m - m_max)
    l_g = jax.lax.psum(l * s, axis)
    acc_g = jax.lax.psum(acc * s[..., None], axis)
    return jnp.where(l_g[..., None] > 0,
                     acc_g / jnp.maximum(l_g, 1e-30)[..., None], 0.0)


def nsa_attend_decode_sharded(params, cfg: ModelConfig, mesh, x, cache,
                              cmp_cache, prefix_len, seq_axes: Tuple[str, ...]):
    """One-token NSA attention + cache commit over a sequence-sharded cache.

    x: (B, 1, D). cache k/v: (B, S, Hkv, Dh) sharded on dim 1 over seq_axes;
    cmp_cache likewise. Returns (out (B,1,D), new cache, new cmp_cache).
    """
    nsa = cfg.nsa
    B = x.shape[0]
    Hq, Hkv, Gq, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim
    n = nsa.n_selected
    S = cache["k"].shape[1]
    NCB = cmp_cache["k_cmp"].shape[1]
    NSB = -(-S // nsa.sel_block)
    nshards = int(np.prod([mesh.shape[a] for a in seq_axes]))
    S_loc, NCB_loc = S // nshards, NCB // nshards
    axis = seq_axes if len(seq_axes) > 1 else seq_axes[0]

    positions = jnp.broadcast_to(jnp.asarray(prefix_len)[None, None], (B, 1))
    q, k_new, v_new = qkv(params, cfg, x, positions.astype(jnp.int32))
    g_all = gates(params, x, Hq)                                   # (B,1,3,Hq)
    scale = 1.0 / np.sqrt(Dh)
    ncb_valid = dyn_num_cmp_blocks(prefix_len, nsa)

    # static overlap geometry: cmp block i -> fractional weight onto sel blocks
    from repro.models.nsa import overlap_matrix
    M_full = jnp.asarray(overlap_matrix(NCB, NSB, nsa.cmp_block, nsa.cmp_stride,
                                        nsa.sel_block))            # (NCB, NSB)

    def body(q, k_new, v_new, g_all, k_c, v_c, k_cm, v_cm, prefix_len, ncb_valid):
        # shard-local slices: k_c (B, S_loc, Hkv, Dh), k_cm (B, NCB_loc, Hkv, Dh)
        if isinstance(axis, tuple):
            idx = sum(jax.lax.axis_index(a) *
                      int(np.prod([mesh.shape[b] for b in axis[i + 1:]]))
                      for i, a in enumerate(axis))
        else:
            idx = jax.lax.axis_index(axis)
        off = idx * S_loc
        cmp_off = idx * NCB_loc
        pos = jnp.asarray(prefix_len)                              # scalar
        qg = (q.reshape(B, 1, Hkv, Gq, Dh)[:, 0] * scale).astype(jnp.float32)

        # ---- 1+2. local routing + cmp branch state
        cmp_ids = cmp_off + jnp.arange(NCB_loc)
        ends = cmp_ids * nsa.cmp_stride + nsa.cmp_block - 1
        cvis = (ends <= pos) & (cmp_ids < ncb_valid)               # (NCB_loc,)
        lc = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cm.astype(jnp.float32))
        cmask = jnp.broadcast_to(cvis[None, None, None], lc.shape)
        st_cmp = _state(lc, cmask, v_cm)

        # partial selection scores: exp(l - m_glob) mass mapped onto blocks
        m_glob = jax.lax.pmax(jnp.where(cmask, lc, NEG_INF).max((-1)), axis)
        pmass = jnp.exp(jnp.where(cmask, lc, NEG_INF) - m_glob[..., None]) * cmask
        # GQA-share: sum over the Gq query heads of each kv group
        pm = pmass.sum(2)                                          # (B,Hkv,NCB_loc)
        M_loc = jax.lax.dynamic_slice_in_dim(M_full, cmp_off, NCB_loc, axis=0)
        p_slc = jax.lax.psum(jnp.einsum("bhk,ks->bhs", pm, M_loc), axis)

        # ---- exact global Top-n (identical on every shard)
        sel_idx, sel_valid = select_topn(p_slc[:, None], positions, pos, nsa)
        sel_idx, sel_valid = sel_idx[:, 0], sel_valid[:, 0]        # (B,Hkv,n)

        # ---- 3a. slc branch: token-granular local ownership
        tok = sel_idx[..., None] * nsa.sel_block + jnp.arange(nsa.sel_block)
        tok = tok.reshape(B, Hkv, n * nsa.sel_block)               # (B,Hkv,K)
        ownm = (tok >= off) & (tok < off + S_loc) & (tok < pos) & \
            jnp.repeat(sel_valid, nsa.sel_block, axis=-1)
        loc = jnp.clip(tok - off, 0, S_loc - 1)
        bidx = jnp.arange(B)[:, None, None]
        hidx = jnp.arange(Hkv)[None, :, None]
        k_sel = k_c[bidx, loc, hidx]                               # (B,Hkv,K,Dh)
        v_sel = v_c[bidx, loc, hidx]
        ls = jnp.einsum("bhgd,bhkd->bhgk", qg, k_sel.astype(jnp.float32))
        m_s = ownm[:, :, None]                                      # (B,Hkv,1,K)
        lm = jnp.where(m_s, ls, NEG_INF)
        m1 = lm.max(-1)
        p1 = jnp.exp(lm - m1[..., None]) * m_s
        l1 = p1.sum(-1)
        a1 = jnp.einsum("bhgk,bhkd->bhgd", p1, v_sel.astype(jnp.float32))
        st_slc = (m1, l1, a1)

        # ---- 3b. win branch: local window segment (+ the new token, shard 0)
        W = min(nsa.window, S_loc)
        wstart_g = jnp.clip(pos - nsa.window + 1, 0, S - 1)  # (pos-w, pos) open
        lstart = jnp.clip(wstart_g - off, 0, max(S_loc - W, 0))
        k_w = jax.lax.dynamic_slice_in_dim(k_c, lstart, W, axis=1)
        v_w = jax.lax.dynamic_slice_in_dim(v_c, lstart, W, axis=1)
        wpos = off + lstart + jnp.arange(W)
        wmask = (wpos < pos) & (wpos >= wstart_g) & (wpos < off + S_loc)
        lw = jnp.einsum("bhgd,bkhd->bhgk", qg, k_w.astype(jnp.float32))
        st_win = _state(lw, jnp.broadcast_to(wmask[None, None, None], lw.shape), v_w)
        # new token: contributes once (shard 0)
        lnew = jnp.einsum("bhgd,bkhd->bhgk", qg, k_new.astype(jnp.float32))
        nmask = jnp.broadcast_to(jnp.reshape(idx == 0, (1, 1, 1, 1)), lnew.shape)
        mw, lw_, aw = st_win
        mn, ln_, an = _state(lnew, nmask, v_new)
        m2 = jnp.maximum(mw, mn)
        s_w, s_n = jnp.exp(mw - m2), jnp.exp(mn - m2)
        st_win = (m2, lw_ * s_w + ln_ * s_n,
                  aw * s_w[..., None] + an * s_n[..., None])

        # ---- 4. merge + gates
        o_cmp = _merge(st_cmp, axis)
        o_slc = _merge(st_slc, axis)
        o_win = _merge(st_win, axis)
        g = g_all[:, 0].reshape(B, 3, Hkv, Gq)
        o = (g[:, 0, :, :, None] * o_cmp + g[:, 1, :, :, None] * o_slc +
             g[:, 2, :, :, None] * o_win)
        o = o.reshape(B, 1, Hq * Dh)

        # ---- shard-local cache commit (raw KV at position `pos`)
        in_range = (pos >= off) & (pos < off + S_loc)
        wr = jnp.clip(pos - off, 0, S_loc - 1)
        k_upd = jax.lax.dynamic_update_slice_in_dim(
            k_c, jnp.where(in_range, k_new, jax.lax.dynamic_slice_in_dim(
                k_c, wr, 1, axis=1)).astype(k_c.dtype), wr, axis=1)
        v_upd = jax.lax.dynamic_update_slice_in_dim(
            v_c, jnp.where(in_range, v_new, jax.lax.dynamic_slice_in_dim(
                v_c, wr, 1, axis=1)).astype(v_c.dtype), wr, axis=1)
        return o, k_upd, v_upd

    specs_seq = P(None, seq_axes, None, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), specs_seq, specs_seq, specs_seq,
                  specs_seq, P(), P()),
        out_specs=(P(), specs_seq, specs_seq),
        check_vma=False)
    o, k_upd, v_upd = fn(q, k_new, v_new, g_all, cache["k"], cache["v"],
                         cmp_cache["k_cmp"], cmp_cache["v_cmp"],
                         jnp.asarray(prefix_len), ncb_valid)
    out = o.astype(x.dtype) @ params["wo"]
    return out, {"k": k_upd, "v": v_upd}, cmp_cache


def decode_step_sharded(params, cfg: ModelConfig, mesh, caches, tokens,
                        seq_axes: Tuple[str, ...]):
    """Full-model one-token decode with sequence-sharded NSA attention.
    Matches model.decode_step semantics for homogeneous attn/moe stacks with
    cfg.attention == 'nsa' (the long_500k serving configuration).

    NOTE: compressed-cache incremental updates append at block granularity
    (a new block completes every cmp_stride tokens); the update is shard-local
    by construction and folded into the serving engine's commit cadence —
    for the single-token dry-run step the cmp cache is read-only.
    """
    from repro.models import model as model_lib

    prefix_len = caches["length"]
    x = layers.embed(params["embed"], tokens)
    new_segs = []
    for (kinds, ngroups), stacked, seg_caches in zip(
            model_lib.segments(cfg), params["segments"], caches["segments"]):
        def body(h, xs, kinds=kinds):
            gp, gcache = xs
            new_cache = []
            for j, kind in enumerate(kinds):
                bp = gp[j]
                hn = layers.rmsnorm(bp["norm1"], h, cfg.norm_eps)
                mix, kv, cmp = nsa_attend_decode_sharded(
                    bp["mix"], cfg, mesh, hn, gcache[j]["kv"], gcache[j]["cmp"],
                    prefix_len, seq_axes)
                h = h + mix
                hn = layers.rmsnorm(bp["norm2"], h, cfg.norm_eps)
                y, _ = model_lib._apply_ffn(bp, cfg, kind, hn, serving=True)
                h = h + y
                new_cache.append({"kv": kv, "cmp": cmp})
            return h, tuple(new_cache)

        x, seg_new = jax.lax.scan(body, x, (stacked, seg_caches))
        new_segs.append(seg_new)
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = model_lib.logits_fn(params, cfg, x)
    return logits, {"segments": new_segs, "length": prefix_len + 1}
