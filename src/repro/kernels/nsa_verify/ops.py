"""jit-ready wrappers around the fused SSV verification kernel.

``nsa_verify_fused`` is the public entry: it takes model-level tensors plus
the SSV grouping strategy, builds the merged-schedule (exact) or shared-index
(approx) layouts + ownership masks, pads everything to kernel tiles, invokes
the Pallas kernel, and un-groups the output.

All layout preparation is pure jnp (fuses into the surrounding XLA graph) —
the TPU-native replacement for the paper's in-kernel warp sort/dedup (see
DESIGN.md §3).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import NSAConfig
from repro.core import kvstore, overlap
from repro.kernels.nsa_verify import kernel as K
from repro.kernels.platform import resolve_interpret


def _heads_major(x):
    """(B, S, Hkv, Dh) -> (B, Hkv, S, Dh): the kernel tiles tokens x Dh."""
    return x.transpose(0, 2, 1, 3)


def _pad_axis(x, axis: int, target: int):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# Mixed-bucket serving multiplies the distinct kernel shapes in flight: each
# execution group's (strategy, group-size) pair contributes its own (T, C, M,
# ...) tuple per layer mode, so the seed maxsize of 128 could thrash once a
# profile's worth of strategies serve concurrently. 1024 entries keep every
# realistic shape set resident. Lookups (at the call site) and builds count
# into ``obs``, from which the engines' kernel-cache metrics read them.
@functools.lru_cache(maxsize=1024)
def _cached_call(key):
    obs.count("kernel.verify_call.builds")
    return K.build_verify_call(**dict(key))


def verify_call_cache_info():
    """Hit/miss/size counters of the fused-kernel build cache (process-wide —
    every engine in the process shares one kernel cache)."""
    return _cached_call.cache_info()


def prepare_groups(q, gates, sel_idx, sel_valid, positions, C: int, mode: str,
                   n_sel: int):
    """Group queries and build merged/ownership layouts.

    q: (B,T,Hq,Dh) -> q_grp (B,G,Hkv,R,Dh); gates (B,T,3,Hq) ->
    (B,G,Hkv,R,3); merged (B,G,Hkv,M); mvalid; own (B,G,Hkv,C,M);
    pos_grp (B,G,C).
    """
    B, T, Hq, Dh = q.shape
    Hkv = sel_idx.shape[2]
    Gq = Hq // Hkv
    obs.count("kernel.group_layout.lookups")
    qmap, _ = overlap.group_queries(T, C)
    G = qmap.shape[0]
    gi = jnp.asarray(qmap)                                          # (G, C)

    qx = q.reshape(B, T, Hkv, Gq, Dh)[:, gi]                        # (B,G,C,Hkv,Gq,Dh)
    q_grp = qx.transpose(0, 1, 3, 2, 4, 5).reshape(B, G, Hkv, C * Gq, Dh)
    gx = gates.transpose(0, 1, 3, 2).reshape(B, T, Hkv, Gq, 3)[:, gi]
    gates_grp = gx.transpose(0, 1, 3, 2, 4, 5).reshape(B, G, Hkv, C * Gq, 3)
    pos_grp = positions[:, gi]                                      # (B, G, C)

    if mode == "approx":
        idx2, val2 = overlap.shared_index(sel_idx, sel_valid, positions, C)
        # per group, merged list = the representative's n blocks (every member
        # of the group carries identical values — take member 0's)
        merged = idx2[:, gi[:, 0]]                                  # (B,G,Hkv,n)
        merged = jnp.where(val2[:, gi[:, 0]], merged, -1)
        mvalid = val2[:, gi[:, 0]]
        own = jnp.ones((B, G, Hkv, C, merged.shape[-1]), jnp.int32)
        merged = merged.astype(jnp.int32)
        return q_grp, gates_grp, merged, mvalid.astype(jnp.int32), own, pos_grp, gi
    # exact merged schedule
    merged, own, mvalid = overlap.merged_schedule(sel_idx, sel_valid, C)
    merged = jnp.where(mvalid, merged, -1).astype(jnp.int32)
    return q_grp, gates_grp, merged, mvalid.astype(jnp.int32), \
        own.astype(jnp.int32), pos_grp, gi


def nsa_verify_fused(q, k_cache, v_cache, k_cmp, v_cmp, k_draft, v_draft,
                     sel_idx, sel_valid, positions, prefix_len, ncb_valid,
                     tree_mask, gates, nsa: NSAConfig, C: int = 2,
                     mode: str = "exact", include_cmp: bool = True,
                     o_cmp_in=None, combine: bool = True,
                     include_sel: bool = True, include_win: bool = True,
                     interpret: Optional[bool] = None, page_table=None):
    """Fused grouped-query NSA verification (see kernel.py docstring).

    q: (B,T,Hq,Dh) — ALREADY rope'd and scaled by 1/sqrt(Dh).
    Returns (B, T, Hq, Dh) f32.

    ``page_table`` (B, max_pages) int32 switches the KV inputs to the paged
    store: ``k_cache``/``v_cache`` are then the shared page pool
    (P, page_size, Hkv, Dh). Selected-block indices are resolved through the
    page table in the jnp prep layer (fusing into the surrounding XLA graph,
    like the merged-schedule build): logical block -> physical pool block
    for the slc gather index_map, and the win branch's trailing slice is
    gathered from the row's pages. Unmapped / out-of-range blocks are
    masked, not clamped. The kernel itself is oblivious to paging — it sees
    pre-resolved physical block indices (ref parity:
    tests/test_kernels_nsa_verify.py::test_fused_paged_matches_dense).

    ``interpret=None`` interprets the kernel on the CPU backend only.
    """
    B, T, Hq, Dh = q.shape
    lb = nsa.sel_block
    paged = page_table is not None
    if paged:
        ps = k_cache.shape[1]
        S = page_table.shape[1] * ps
        Hkv = k_cache.shape[2]
    else:
        S = k_cache.shape[1]
        Hkv = k_cache.shape[2]
    Gq = Hq // Hkv

    q_grp, gates_grp, merged, mvalid, own, pos_grp, gi = prepare_groups(
        q, gates, sel_idx, sel_valid, positions, C, mode, nsa.n_selected)
    G = q_grp.shape[1]
    M = merged.shape[-1]
    R = C * Gq

    if paged:
        # pages tile selection blocks (page_size % sel_block == 0), so the
        # BlockSpec index_map resolves a LOGICAL merged block to a physical
        # pool block via the scalar-prefetched page table; ``merged`` stays
        # logical (the kernel's prefix/causal masks are position-based).
        # Unmapped pages only get their validity bit cleared here.
        m = ps // lb
        P = k_cache.shape[0]
        NSB = P * m                                      # physical blocks
        nsb_logical = page_table.shape[1] * m
        lp = jnp.clip(jnp.where(merged >= 0, merged, 0) // m, 0,
                      page_table.shape[1] - 1)
        phys_pg = jnp.take_along_axis(
            page_table, lp.reshape(B, -1), axis=1).reshape(lp.shape)
        mvalid = jnp.where((merged >= 0) & (phys_pg >= 0), mvalid, 0)
        merged = jnp.where(mvalid > 0, merged, -1)
        # the pool stays SHARED (leading dim 1, never broadcast-materialized
        # to B copies — that would forfeit paging's memory win); the paged
        # blk index_map pins the pool's batch coordinate to 0 and the page
        # table supplies the per-row physical block
        k_blkd = k_cache.reshape(1, P * m, lb, Hkv, Dh)
        v_blkd = v_cache.reshape(1, P * m, lb, Hkv, Dh)
        page_rows = page_table.astype(jnp.int32).reshape(-1)
    else:
        # cache reshaped into selection blocks for the gather index_map
        Sp = -(-S // lb) * lb
        NSB = Sp // lb
        nsb_logical = NSB
        k_blkd = _pad_axis(k_cache, 1, Sp).reshape(B, NSB, lb, Hkv, Dh)
        v_blkd = _pad_axis(v_cache, 1, Sp).reshape(B, NSB, lb, Hkv, Dh)

    k_blkd = k_blkd.transpose(0, 3, 1, 2, 4)           # (B|1, Hkv, NSB, lb, Dh)
    v_blkd = v_blkd.transpose(0, 3, 1, 2, 4)

    # compressed cache padded to the cmp tile
    NCB = k_cmp.shape[1]
    TC = min(128, max(8, NCB))
    NCBp = -(-NCB // TC) * TC
    k_cmp_p = _heads_major(_pad_axis(k_cmp, 1, NCBp))
    v_cmp_p = _heads_major(_pad_axis(v_cmp, 1, NCBp))

    # window slice (paged: gathered from the row's pages by the store view)
    W = min(nsa.window, S)
    win_start = jnp.clip(jnp.asarray(prefix_len) - W, 0, max(S - W, 0))
    kv_view = kvstore.KVView(k_cache, v_cache, page_table)
    k_win, v_win = kv_view.window(win_start, W)
    TW = min(128, max(8, W))
    Wp = -(-W // TW) * TW
    k_win = _heads_major(_pad_axis(k_win, 1, Wp))
    v_win = _heads_major(_pad_axis(v_win, 1, Wp))

    # draft tile + combined draft mask (tree ∧ window ∧ causal ∧ valid)
    Tp = max(8, -(-T // 8) * 8)
    k_draft_p = _heads_major(_pad_axis(k_draft, 1, Tp))
    v_draft_p = _heads_major(_pad_axis(v_draft, 1, Tp))
    dist = positions[:, :, None] - positions[:, None, :]            # (B,T,T)
    dmask = tree_mask & (dist < nsa.window) & (dist >= 0)
    dmask_g = dmask[:, gi]                                          # (B,G,C,T)
    dmask_g = jnp.repeat(dmask_g, Gq, axis=2)                       # (B,G,R,T)
    dmask_g = _pad_axis(dmask_g.astype(jnp.int32), 3, Tp)

    s_scalar = jnp.stack([jnp.asarray(prefix_len, jnp.int32),
                          jnp.asarray(ncb_valid, jnp.int32),
                          win_start.astype(jnp.int32),
                          jnp.asarray(T, jnp.int32)])

    key = tuple(sorted(dict(
        B=B, G=G, Hkv=Hkv, C=C, Gq=Gq, Dh=Dh, NSB=NSB, NCBp=NCBp, M=M,
        Wp=Wp, Tp=Tp, sel_block=lb, cmp_block=nsa.cmp_block,
        cmp_stride=nsa.cmp_stride, window=nsa.window, TC=TC, TW=TW,
        include_cmp=include_cmp, include_sel=include_sel,
        include_win=include_win, combine=combine,
        has_cmp_in=o_cmp_in is not None,
        interpret=resolve_interpret(interpret),
        paged=paged, blocks_per_page=(ps // lb if paged else 1),
        max_pages=(page_table.shape[1] if paged else 0)).items()))
    obs.count("kernel.verify_call.lookups")
    call = _cached_call(key)

    merged_c = jnp.clip(merged, 0, nsb_logical - 1)
    # query row r = c*Gq + j of a group sits at its c-th query's position
    pos_rows = jnp.repeat(pos_grp.astype(jnp.int32), Gq, axis=2)[..., None]
    args = [merged_c.reshape(-1), mvalid.reshape(-1), own.reshape(-1),
            s_scalar]
    if paged:
        args.append(page_rows)
    args += [pos_rows, q_grp, k_cmp_p, v_cmp_p, k_blkd, v_blkd, k_win, v_win,
             k_draft_p, v_draft_p, gates_grp, dmask_g]
    if o_cmp_in is not None:
        oc = o_cmp_in.reshape(B, T, Hkv, Gq, Dh)[:, gi]
        oc = oc.transpose(0, 1, 3, 2, 4, 5).reshape(B, G, Hkv, R, Dh)
        args.append(oc)
    o_grp = call(*args)                                             # (B,G,Hkv,R,Dh)

    o = o_grp.reshape(B, G, Hkv, C, Gq, Dh).transpose(0, 1, 3, 2, 4, 5)
    o = o.reshape(B, G * C, Hkv * Gq, Dh)[:, :T]
    return o


def kernel_launch_count(nsa: NSAConfig, mode: str) -> int:
    """Structural launch-count metric used by the benchmarks: vanilla NSA =
    3 branch kernels + routing; refresh = routing + fused downstream; reuse =
    1 fully fused kernel."""
    return {"vanilla": 4, "refresh": 2, "reuse": 1}[mode]


def nsa_verify_kernel_layer(params, cfg, x, cache, cmp_cache, prefix_len,
                            positions, tree_mask, sel_idx=None, sel_valid=None,
                            C: int = 2, mode: str = "exact",
                            reuse: bool = False,
                            interpret: Optional[bool] = None,
                            page_table=None):
    """Full NSA verification of one layer through the Pallas kernels — the
    kernel-backed counterpart of ``models.nsa.nsa_verify_ref``.

    reuse=False (refresh layer): routing launch (compressed attention +
      selection scores, XLA) -> Top-n indices -> partially fused downstream
      kernel (slc + win + gated aggregation, include_cmp=False).
    reuse=True: indices are inherited (``sel_idx`` required) -> single fully
      fused kernel computing all three branches.

    ``cache`` is a raw ``{"k", "v"}`` dict, or the paged store's pool with
    ``page_table`` supplied (equivalently a ``kvstore.KVView``).

    Returns (out (B,T,D), (k_new, v_new), (sel_idx, sel_valid)).
    """
    import numpy as _np

    from repro.models import attention as attn_lib
    from repro.models import nsa as nsa_lib

    if isinstance(cache, kvstore.KVView):
        kv = cache
    else:
        kv = kvstore.KVView(cache["k"], cache["v"], page_table)
    nsa = cfg.nsa
    B, T, _ = x.shape
    Hq, Dh = cfg.num_heads, cfg.head_dim
    q, k_new, v_new = attn_lib.qkv(params, cfg, x, positions)
    q_s = q / _np.sqrt(Dh)
    g_all = nsa_lib.gates(params, x, Hq)                           # (B,T,3,Hq)
    ncb_valid = nsa_lib.dyn_num_cmp_blocks(prefix_len, nsa)

    if reuse:
        assert sel_idx is not None, "reuse layers inherit indices"
        out = nsa_verify_fused(
            q_s, kv.k, kv.v, cmp_cache["k_cmp"], cmp_cache["v_cmp"],
            k_new, v_new, sel_idx, sel_valid, positions, prefix_len, ncb_valid,
            tree_mask, g_all, nsa, C=C, mode=mode, include_cmp=True,
            interpret=interpret, page_table=kv.pages)
    else:
        o_cmp, p_slc = nsa_lib.routing(params, cfg, q, cmp_cache["k_cmp"],
                                       cmp_cache["v_cmp"], positions,
                                       kv_len=kv.max_len,
                                       ncb_valid=ncb_valid)
        sel_idx, sel_valid = nsa_lib.select_topn(p_slc, positions, prefix_len, nsa)
        out = nsa_verify_fused(
            q_s, kv.k, kv.v, cmp_cache["k_cmp"], cmp_cache["v_cmp"],
            k_new, v_new, sel_idx, sel_valid, positions, prefix_len, ncb_valid,
            tree_mask, g_all, nsa, C=C, mode=mode, include_cmp=False,
            o_cmp_in=o_cmp, interpret=interpret, page_table=kv.pages)
    out = out.astype(x.dtype).reshape(B, T, Hq * Dh) @ params["wo"]
    return out, (k_new, v_new), (sel_idx, sel_valid)


def nsa_verify_vanilla_layer(params, cfg, x, cache, cmp_cache, prefix_len,
                             positions, tree_mask,
                             interpret: Optional[bool] = None):
    """Vanilla-NSA baseline execution (paper Fig. 6(a)): per-branch kernels
    with intermediate branch-output materialization, no grouping (C=1), fresh
    index construction — the reference point the SSV speedups are measured
    against."""
    import numpy as _np

    from repro.models import attention as attn_lib
    from repro.models import nsa as nsa_lib

    nsa = cfg.nsa
    B, T, _ = x.shape
    Hq, Dh = cfg.num_heads, cfg.head_dim
    q, k_new, v_new = attn_lib.qkv(params, cfg, x, positions)
    q_s = q / _np.sqrt(Dh)
    g_all = nsa_lib.gates(params, x, Hq)
    ncb_valid = nsa_lib.dyn_num_cmp_blocks(prefix_len, nsa)
    o_cmp, p_slc = nsa_lib.routing(params, cfg, q, cmp_cache["k_cmp"],
                                   cmp_cache["v_cmp"], positions,
                                   kv_len=cache["k"].shape[1], ncb_valid=ncb_valid)
    sel_idx, sel_valid = nsa_lib.select_topn(p_slc, positions, prefix_len, nsa)
    common = dict(interpret=interpret, C=1, mode="exact", combine=False)
    o_slc = nsa_verify_fused(q_s, cache["k"], cache["v"], cmp_cache["k_cmp"],
                             cmp_cache["v_cmp"], k_new, v_new, sel_idx, sel_valid,
                             positions, prefix_len, ncb_valid, tree_mask, g_all,
                             nsa, include_cmp=False, include_win=False, **common)
    o_win = nsa_verify_fused(q_s, cache["k"], cache["v"], cmp_cache["k_cmp"],
                             cmp_cache["v_cmp"], k_new, v_new, sel_idx, sel_valid,
                             positions, prefix_len, ncb_valid, tree_mask, g_all,
                             nsa, include_cmp=False, include_sel=False, **common)
    # branch outputs materialize (HBM round-trip), gated combine in XLA
    out = g_all[:, :, 0][..., None] * o_cmp.astype(jnp.float32) + \
        g_all[:, :, 1][..., None] * o_slc + g_all[:, :, 2][..., None] * o_win
    out = out.astype(x.dtype).reshape(B, T, Hq * Dh) @ params["wo"]
    return out, (k_new, v_new), (sel_idx, sel_valid)
