"""Fused SSV verification kernel (Pallas TPU).

TPU-native redesign of the paper's grouped-query NSA verification kernels
(§4, §5). One grid cell = (batch b, query-group g, kv-head h) — the Pallas
analogue of a GPU thread block; the 4th grid dimension walks a *work list*:

    [cmp tiles | merged selected blocks | window tiles | draft tile]

Each work step loads exactly one KV tile into VMEM (the other inputs' block
indices are frozen, so the TPU pipeline skips their re-fetch), computes
masked logits for the group's R = C·Gq query rows, and accumulates into the
branch's private online-softmax state held in VMEM scratch — the TPU version
of the paper's "per-branch normalization state in registers". The final work
step applies the learned gates and performs the single HBM write-back
("Unified Write-back" / "In-Register Aggregation").

Variants (all built by ``build_verify_call``):
  * full fusion (reuse layers):     include_cmp=True, combine=True
  * partial fusion (refresh layers): include_cmp=False + o_cmp input
  * branch-wise vanilla baseline:   one include_* flag at a time,
    combine=False (materializes the branch output — Figure 6(a) behavior)
  * exact vs approximate grouping is purely a matter of the merged-index /
    ownership inputs (built in ops.py) — the kernel is oblivious.

Selected blocks are gathered from HBM via scalar-prefetched block indices in
the BlockSpec index_map (the paged-attention pattern) — each unique merged
block is fetched exactly once per group, which is the paper's dedup-and-share
semantics on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _qk(q, k):
    """(R, Dh) x (K, Dh) -> (R, K) f32 logits (contract the head dim)."""
    return jax.lax.dot_general(q, k.astype(jnp.float32),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _update(acc_ref, l_ref, m_ref, br: int, logits, mask, v_tile):
    """Online-softmax accumulation for one branch slot ``br``.
    logits: (R, K) f32; mask: (R, K) bool; v_tile: (K, Dh). The running max
    and sum are (R, 1) columns, so every value stays 2-D."""
    lm = jnp.where(mask, logits, NEG)
    m_old = m_ref[br]                                    # (R, 1)
    m_new = jnp.maximum(m_old, lm.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.where(mask, jnp.exp(lm - m_new), 0.0)
    l_ref[br] = l_ref[br] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[br] = acc_ref[br] * alpha + jnp.dot(
        p, v_tile.astype(jnp.float32), preferred_element_type=jnp.float32)
    m_ref[br] = m_new


def make_kernel(*, C: int, Gq: int, Dh: int, M: int, TC: int, NCB_T: int,
                TW: int, WT: int, Tp: int, sel_block: int, cmp_block: int,
                cmp_stride: int, window: int, include_cmp: bool,
                include_sel: bool, include_win: bool, combine: bool,
                has_cmp_in: bool, G: int, Hkv: int, paged: bool = False):
    R = C * Gq
    CMP_STEPS = NCB_T if include_cmp else 0
    SEL_STEPS = M if include_sel else 0
    WIN_STEPS = (WT + 1) if include_win else 0     # +1 = draft tile step
    TOTAL = max(CMP_STEPS + SEL_STEPS + WIN_STEPS, 1)

    def kernel(s_merged, s_mvalid, s_own, s_scalar, *tail):
        # paged store: the scalar-prefetched page table drives the BlockSpec
        # index_map (logical block -> physical pool block); the kernel body
        # itself stays position-based on LOGICAL indices, so the masks below
        # are backend-oblivious.
        if paged:
            _s_pages, *tail = tail
        (pos_ref, q_ref, kcmp_ref, vcmp_ref, kblk_ref, vblk_ref, kwin_ref,
         vwin_ref, kdr_ref, vdr_ref, gates_ref, dmask_ref, *rest) = tail
        if has_cmp_in:
            ocmp_ref, o_ref, acc_ref, l_ref, m_ref = rest
        else:
            o_ref, acc_ref, l_ref, m_ref = rest
        b, g, h, w = (pl.program_id(i) for i in range(4))

        @pl.when(w == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            l_ref[...] = jnp.zeros_like(l_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG)

        q = q_ref[0, 0, 0].astype(jnp.float32)                     # (R, Dh)
        pos_r = pos_ref[0, 0]                                       # (R, 1)
        prefix_len = s_scalar[0]
        ncb_valid = s_scalar[1]
        win_start = s_scalar[2]

        if include_cmp:
            @pl.when(w < CMP_STEPS)
            def _cmp():
                t = jnp.minimum(w, NCB_T - 1)
                ids = t * TC + jax.lax.broadcasted_iota(jnp.int32, (1, TC), 1)
                ends = ids * cmp_stride + cmp_block - 1
                mask = (ends <= pos_r) & (ids < ncb_valid)
                _update(acc_ref, l_ref, m_ref, 0, _qk(q, kcmp_ref[0, 0]), mask,
                        vcmp_ref[0, 0])

        if include_sel:
            @pl.when((w >= CMP_STEPS) & (w < CMP_STEPS + SEL_STEPS))
            def _sel():
                m = jnp.clip(w - CMP_STEPS, 0, M - 1)
                slot = ((b * G + g) * Hkv + h) * M + m
                blk = s_merged[slot]
                # an invalid merged slot admits no token: fold its validity
                # into the scalar prefix bound instead of a vector mask
                limit = jnp.where(s_mvalid[slot] > 0, prefix_len, 0)
                tok = blk * sel_block + jax.lax.broadcasted_iota(
                    jnp.int32, (1, sel_block), 1)
                # ownership of this slot per query row: rows
                # [c*Gq, (c+1)*Gq) belong to the group's c-th query
                row = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
                own_r = jnp.zeros((R, 1), jnp.int32)
                for c in range(C):
                    own_c = s_own[(((b * G + g) * Hkv + h) * C + c) * M + m]
                    own_r = jnp.where((row >= c * Gq) & (row < (c + 1) * Gq),
                                      own_c, own_r)
                mask = (tok < limit) & (tok <= pos_r) & (own_r > 0)
                _update(acc_ref, l_ref, m_ref, 1, _qk(q, kblk_ref[0, 0, 0]),
                        mask, vblk_ref[0, 0, 0])

        if include_win:
            @pl.when((w >= CMP_STEPS + SEL_STEPS) & (w < TOTAL - 1))
            def _win():
                t = jnp.clip(w - CMP_STEPS - SEL_STEPS, 0, max(WT - 1, 0))
                kpos = win_start + t * TW + jax.lax.broadcasted_iota(
                    jnp.int32, (1, TW), 1)
                mask = (kpos < prefix_len) & (kpos > pos_r - window) & \
                    (kpos <= pos_r)
                _update(acc_ref, l_ref, m_ref, 2, _qk(q, kwin_ref[0, 0]), mask,
                        vwin_ref[0, 0])

            @pl.when(w == TOTAL - 1)
            def _draft():
                mask = dmask_ref[0, 0] > 0                          # (R, Tp)
                _update(acc_ref, l_ref, m_ref, 2, _qk(q, kdr_ref[0, 0]), mask,
                        vdr_ref[0, 0])

        @pl.when(w == TOTAL - 1)
        def _finalize():
            gts = gates_ref[0, 0, 0].astype(jnp.float32)            # (R, 3)

            def safe(br):
                l = l_ref[br]                                       # (R, 1)
                return jnp.where(l > 0, acc_ref[br] / jnp.maximum(l, 1e-30),
                                 0.0)

            if combine:
                o_cmp = (ocmp_ref[0, 0, 0].astype(jnp.float32) if has_cmp_in
                         else safe(0))
                out = gts[:, 0:1] * o_cmp + gts[:, 1:2] * safe(1) + gts[:, 2:3] * safe(2)
            else:
                out = safe(0 if include_cmp else (1 if include_sel else 2))
            o_ref[0, 0, 0] = out.astype(o_ref.dtype)

    return kernel, TOTAL, CMP_STEPS, SEL_STEPS


def build_verify_call(*, B: int, G: int, Hkv: int, C: int, Gq: int, Dh: int,
                      NSB: int, NCBp: int, M: int, Wp: int, Tp: int,
                      sel_block: int, cmp_block: int, cmp_stride: int,
                      window: int, TC: int = 128, TW: int = 128,
                      include_cmp: bool = True, include_sel: bool = True,
                      include_win: bool = True, combine: bool = True,
                      has_cmp_in: bool = False, out_dtype=jnp.float32,
                      interpret: bool, paged: bool = False,
                      blocks_per_page: int = 1, max_pages: int = 0):
    """Returns fn(s_merged, s_mvalid, s_own, s_scalar[, s_pages], pos_rows,
    q_grp, k_cmp, v_cmp, k_blkd, v_blkd, k_win, v_win, k_draft, v_draft,
    gates_grp, dmask_grp[, o_cmp_grp]) -> o_grp (B, G, Hkv, R, Dh).

    Every K/V operand is HEAD-MAJOR, so a kernel block is a (tokens, Dh)
    tile whose last two dims satisfy the TPU (8, 128) tiling rule:
    k_cmp/v_cmp (B, Hkv, NCBp, Dh), k_blkd/v_blkd (B, Hkv, NSB, sel_block,
    Dh), k_win/v_win (B, Hkv, Wp, Dh), k_draft/v_draft (B, Hkv, Tp, Dh).
    The scalar-prefetch operands are flat int32 vectors (SMEM pads every
    trailing dim, so multi-dim tables would waste it): s_merged/s_mvalid
    index ((b*G + g)*Hkv + h)*M + m, s_own (((b*G + g)*Hkv + h)*C + c)*M + m.
    ``pos_rows`` (B, G, R, 1) int32 holds each query row's position.

    ``paged``: ``s_merged`` carries LOGICAL selection-block indices and the
    extra ``s_pages`` (B*max_pages,) scalar-prefetch input maps them to
    physical pool blocks inside the slc BlockSpec index_map — the
    paged-attention gather pattern; ``NSB`` is then the PHYSICAL block count
    of the (batch-broadcast) pool, whose leading dim is 1."""
    R = C * Gq
    TC = min(TC, NCBp)
    TW = min(TW, Wp)
    NCB_T = max(1, NCBp // TC)
    WT = max(1, Wp // TW)
    kernel, TOTAL, _, _ = make_kernel(
        C=C, Gq=Gq, Dh=Dh, M=M, TC=TC, NCB_T=NCB_T, TW=TW, WT=WT, Tp=Tp,
        sel_block=sel_block, cmp_block=cmp_block, cmp_stride=cmp_stride,
        window=window, include_cmp=include_cmp, include_sel=include_sel,
        include_win=include_win, combine=combine, has_cmp_in=has_cmp_in,
        G=G, Hkv=Hkv, paged=paged)

    grid = (B, G, Hkv, TOTAL)
    CMP_STEPS = NCB_T if include_cmp else 0
    SEL_STEPS = M if include_sel else 0

    def cmp_tile(b, g, h, w, *s):
        return (b, h, jnp.minimum(w, max(CMP_STEPS - 1, 0)) if include_cmp else 0, 0)

    def blk_tile(b, g, h, w, *s):
        s_merged = s[0]
        m = jnp.clip(w - CMP_STEPS, 0, M - 1)
        logical = s_merged[((b * G + g) * Hkv + h) * M + m]
        if paged:
            # logical -> physical: page-table lookup + sub-block offset.
            # Invalid / unmapped blocks were already devalidated (mvalid=0)
            # by the prep layer, so the clips only pick a safe fetch target.
            # The pool is shared across the batch (leading dim 1): batch
            # coordinate 0, row identity lives in the page table.
            blk = jnp.clip(logical, 0, max_pages * blocks_per_page - 1)
            s_pages = s[4]
            phys = s_pages[b * max_pages + blk // blocks_per_page]
            blk = jnp.clip(phys * blocks_per_page + blk % blocks_per_page,
                           0, NSB - 1)
            return (0, h, blk, 0, 0)
        return (b, h, jnp.clip(logical, 0, NSB - 1), 0, 0)

    def win_tile(b, g, h, w, *s):
        t = jnp.clip(w - CMP_STEPS - SEL_STEPS, 0, max(WT - 1, 0))
        return (b, h, t, 0)

    def per_group(b, g, h, w, *s):
        return (b, g, h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, R, 1), lambda b, g, h, w, *s: (b, g, 0, 0)),   # pos_rows
        pl.BlockSpec((1, 1, 1, R, Dh), per_group),                          # q
        pl.BlockSpec((1, 1, TC, Dh), cmp_tile),                             # k_cmp
        pl.BlockSpec((1, 1, TC, Dh), cmp_tile),                             # v_cmp
        pl.BlockSpec((1, 1, 1, sel_block, Dh), blk_tile),                   # k blocks
        pl.BlockSpec((1, 1, 1, sel_block, Dh), blk_tile),                   # v blocks
        pl.BlockSpec((1, 1, TW, Dh), win_tile),                             # k_win
        pl.BlockSpec((1, 1, TW, Dh), win_tile),                             # v_win
        pl.BlockSpec((1, 1, Tp, Dh), lambda b, g, h, w, *s: (b, h, 0, 0)),  # k_draft
        pl.BlockSpec((1, 1, Tp, Dh), lambda b, g, h, w, *s: (b, h, 0, 0)),  # v_draft
        pl.BlockSpec((1, 1, 1, R, 3), per_group),                           # gates
        pl.BlockSpec((1, 1, R, Tp), lambda b, g, h, w, *s: (b, g, 0, 0)),   # dmask
    ]
    if has_cmp_in:
        in_specs.append(pl.BlockSpec((1, 1, 1, R, Dh), per_group))          # o_cmp

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5 if paged else 4,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, 1, R, Dh), per_group),
            scratch_shapes=[
                pltpu.VMEM((3, R, Dh), jnp.float32),   # acc
                pltpu.VMEM((3, R, 1), jnp.float32),    # l
                pltpu.VMEM((3, R, 1), jnp.float32),    # m
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, G, Hkv, R, Dh), out_dtype),
        interpret=interpret,
    )
