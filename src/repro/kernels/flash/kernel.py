"""Flash-attention tree-verification kernel (Pallas TPU).

Dense baseline for speculative verification: grid (B, Hkv, work) where work
walks KV-cache tiles then one draft tile; online softmax in VMEM scratch;
single write-back. Shares the accumulation structure of the fused NSA kernel
but with one branch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def make_kernel(*, R: int, Gq: int, Dh: int, TS: int, ST: int, Tp: int,
                window: int):
    TOTAL = ST + 1

    def kernel(s_scalar, pos_ref, q_ref, k_ref, v_ref, kd_ref, vd_ref,
               dmask_ref, o_ref, acc_ref, l_ref, m_ref):
        w = pl.program_id(2)

        @pl.when(w == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            l_ref[...] = jnp.zeros_like(l_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG)

        q = q_ref[0, 0].astype(jnp.float32)                 # (R, Dh)
        pos_r = pos_ref[0]                                  # (R, 1)
        prefix_len = s_scalar[0]

        def update(k, mask, v):
            logits = jax.lax.dot_general(
                q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            lm = jnp.where(mask, logits, NEG)
            m_new = jnp.maximum(m_ref[...], lm.max(-1, keepdims=True))
            alpha = jnp.exp(m_ref[...] - m_new)
            p = jnp.where(mask, jnp.exp(lm - m_new), 0.0)
            l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        @pl.when(w < ST)
        def _cache():
            t = jnp.minimum(w, ST - 1)
            kpos = t * TS + jax.lax.broadcasted_iota(jnp.int32, (1, TS), 1)
            mask = (kpos < prefix_len) & (kpos <= pos_r)
            if window > 0:
                mask &= kpos > pos_r - window
            update(k_ref[0, 0], mask, v_ref[0, 0])

        @pl.when(w == ST)
        def _draft():
            update(kd_ref[0, 0], dmask_ref[0] > 0, vd_ref[0, 0])   # (R, Tp)

        @pl.when(w == TOTAL - 1)
        def _fin():
            l = l_ref[...]
            o_ref[0, 0] = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30),
                                    0.0).astype(o_ref.dtype)

    return kernel, TOTAL


def build_flash_verify(*, B: int, Hkv: int, R: int, Gq: int, Dh: int, Sp: int,
                       Tp: int, TS: int = 128, window: int = 0,
                       out_dtype=jnp.float32, interpret: bool):
    """Returns fn(s_scalar, pos_rows (B, R, 1), q (B, Hkv, R, Dh),
    k, v (B, Hkv, Sp, Dh), k_draft, v_draft (B, Hkv, Tp, Dh),
    dmask (B, R, Tp)) -> (B, Hkv, R, Dh). K/V are head-major so each block
    is a (tokens, Dh) tile."""
    TS = min(TS, Sp)
    ST = max(1, Sp // TS)
    kernel, TOTAL = make_kernel(R=R, Gq=Gq, Dh=Dh, TS=TS, ST=ST, Tp=Tp,
                                window=window)
    grid = (B, Hkv, TOTAL)

    def cache_tile(b, h, w, *s):
        return (b, h, jnp.minimum(w, ST - 1), 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, R, 1), lambda b, h, w, *s: (b, 0, 0)),         # pos
                pl.BlockSpec((1, 1, R, Dh), lambda b, h, w, *s: (b, h, 0, 0)),  # q
                pl.BlockSpec((1, 1, TS, Dh), cache_tile),                        # k
                pl.BlockSpec((1, 1, TS, Dh), cache_tile),                        # v
                pl.BlockSpec((1, 1, Tp, Dh), lambda b, h, w, *s: (b, h, 0, 0)),  # k_draft
                pl.BlockSpec((1, 1, Tp, Dh), lambda b, h, w, *s: (b, h, 0, 0)),  # v_draft
                pl.BlockSpec((1, R, Tp), lambda b, h, w, *s: (b, 0, 0)),         # dmask
            ],
            out_specs=pl.BlockSpec((1, 1, R, Dh), lambda b, h, w, *s: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((R, Dh), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, Dh), out_dtype),
        interpret=interpret,
    )
