"""Routing kernel (Pallas TPU): the refresh-layer "Routing Launch" of
paper §5.1 — fused compressed-branch attention + selection-score mapping.

Grid (B, Hkv, cmp-tiles): each step loads one (TC, Dh) compressed-KV tile
and the matching (TC, NSB) slice of the static overlap matrix into VMEM,
updates the per-row online-softmax state AND the selection-score accumulator
(kept in the same rescaled space as the attention accumulator, so one
normalization at the finalize step yields both the branch output and the
exact selection scores). This fuses what the vanilla implementation runs as
two passes (attention, then score mapping) with an intermediate
materialization of the (T, NCB) probability matrix.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def make_kernel(*, R: int, Gq: int, Dh: int, TC: int, NT: int, NSB: int,
                cmp_block: int, cmp_stride: int):
    T = R // Gq

    def kernel(s_scalar, pos_ref, q_ref, k_ref, v_ref, m_ref_in, o_ref, p_ref,
               acc_ref, l_ref, m_ref, s_ref):
        t = pl.program_id(2)

        @pl.when(t == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            l_ref[...] = jnp.zeros_like(l_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG)
            s_ref[...] = jnp.zeros_like(s_ref)

        q = q_ref[0, 0].astype(jnp.float32)                  # (R, Dh)
        pos_r = pos_ref[0]                                   # (R, 1)
        ncb_valid = s_scalar[0]
        ids = t * TC + jax.lax.broadcasted_iota(jnp.int32, (1, TC), 1)
        ends = ids * cmp_stride + cmp_block - 1
        vis = (ends <= pos_r) & (ids < ncb_valid)            # (R, TC)

        k = k_ref[0, 0].astype(jnp.float32)                  # (TC, Dh)
        logits = jnp.where(vis, jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), NEG)
        m_new = jnp.maximum(m_ref[...], logits.max(-1, keepdims=True))
        alpha = jnp.exp(m_ref[...] - m_new)
        p = jnp.where(vis, jnp.exp(logits - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        s_ref[...] = s_ref[...] * alpha + jnp.dot(
            p, m_ref_in[...].astype(jnp.float32),
            preferred_element_type=jnp.float32)              # (R, NSB)
        m_ref[...] = m_new

        @pl.when(t == NT - 1)
        def _fin():
            l = jnp.maximum(l_ref[...], 1e-30)
            nz = l_ref[...] > 0
            o_ref[0, 0] = jnp.where(nz, acc_ref[...] / l, 0.0).astype(o_ref.dtype)
            ps = jnp.where(nz, s_ref[...] / l, 0.0)
            # GQA share: sum the Gq query heads of each draft token — rows
            # [t*Gq, (t+1)*Gq) — as one (T, R) 0/1 matmul
            tq = jax.lax.broadcasted_iota(jnp.int32, (T, R), 0)
            rr = jax.lax.broadcasted_iota(jnp.int32, (T, R), 1)
            share = ((rr >= tq * Gq) & (rr < (tq + 1) * Gq)).astype(jnp.float32)
            p_ref[0, 0] = jnp.dot(share, ps, preferred_element_type=jnp.float32
                                  ).astype(p_ref.dtype)

    return kernel


def build_routing_call(*, B: int, Hkv: int, R: int, Gq: int, Dh: int,
                       NCBp: int, NSB: int, TC: int, cmp_block: int,
                       cmp_stride: int, interpret: bool):
    """Returns fn(s_scalar, pos_rows (B, R, 1), q (B, Hkv, R, Dh),
    k_cmp, v_cmp (B, Hkv, NCBp, Dh), overlap (NCBp, NSB)) ->
    (o (B, Hkv, R, Dh), p_slc (B, Hkv, T, NSB)). K/V are head-major so each
    block is a (TC, Dh) tile."""
    TC = min(TC, NCBp)
    NT = max(1, NCBp // TC)
    T = R // Gq
    kernel = make_kernel(R=R, Gq=Gq, Dh=Dh, TC=TC, NT=NT, NSB=NSB,
                         cmp_block=cmp_block, cmp_stride=cmp_stride)

    def tile(b, h, t, *s):
        return (b, h, jnp.minimum(t, NT - 1), 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, NT),
            in_specs=[
                pl.BlockSpec((1, R, 1), lambda b, h, t, *s: (b, 0, 0)),         # pos
                pl.BlockSpec((1, 1, R, Dh), lambda b, h, t, *s: (b, h, 0, 0)),   # q
                pl.BlockSpec((1, 1, TC, Dh), tile),                               # k_cmp
                pl.BlockSpec((1, 1, TC, Dh), tile),                               # v_cmp
                pl.BlockSpec((TC, NSB), lambda b, h, t, *s:
                             (jnp.minimum(t, NT - 1), 0)),                        # M tile
            ],
            out_specs=[
                pl.BlockSpec((1, 1, R, Dh), lambda b, h, t, *s: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, T, NSB), lambda b, h, t, *s: (b, h, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((R, Dh), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, NSB), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, R, Dh), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, T, NSB), jnp.float32)],
        interpret=interpret,
    )
