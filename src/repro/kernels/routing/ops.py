"""jit wrapper for the fused routing kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import NSAConfig
from repro.kernels.platform import resolve_interpret
from repro.kernels.routing import kernel as K
from repro.models.nsa import num_sel_blocks, overlap_matrix


def _pad_axis(x, axis, target):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, pad)
    return jnp.pad(x, w)


@functools.lru_cache(maxsize=64)
def _cached(key):
    return K.build_routing_call(**dict(key))


def routing_fused(q, k_cmp, v_cmp, positions, ncb_valid, nsa: NSAConfig,
                  kv_len: int, interpret: Optional[bool] = None):
    """q: (B,T,Hq,Dh) pre-scaled + rope'd; k_cmp/v_cmp (B,NCB,Hkv,Dh).
    Returns (o_cmp (B,T,Hq,Dh) f32, p_slc (B,T,Hkv,NSB) f32).
    ``interpret=None`` interprets the kernel on the CPU backend only."""
    B, T, Hq, Dh = q.shape
    NCB, Hkv = k_cmp.shape[1], k_cmp.shape[2]
    Gq = Hq // Hkv
    R = T * Gq
    NSB = num_sel_blocks(kv_len, nsa)
    TC = min(128, max(8, NCB))
    NCBp = -(-NCB // TC) * TC
    M = jnp.asarray(overlap_matrix(NCBp, NSB, nsa.cmp_block, nsa.cmp_stride,
                                   nsa.sel_block))
    q_l = q.reshape(B, T, Hkv, Gq, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, R, Dh)
    key = tuple(sorted(dict(B=B, Hkv=Hkv, R=R, Gq=Gq, Dh=Dh, NCBp=NCBp,
                            NSB=NSB, TC=TC, cmp_block=nsa.cmp_block,
                            cmp_stride=nsa.cmp_stride,
                            interpret=resolve_interpret(interpret)).items()))
    call = _cached(key)
    s_scalar = jnp.stack([jnp.asarray(ncb_valid, jnp.int32)])
    # kernel row r = t*Gq + j sits at draft token t's position
    pos_rows = jnp.repeat(positions.astype(jnp.int32), Gq, axis=1)[..., None]
    heads_major = lambda x: _pad_axis(x, 1, NCBp).transpose(0, 2, 1, 3)
    o, p_slc = call(s_scalar, pos_rows, q_l, heads_major(k_cmp),
                    heads_major(v_cmp), M)
    o = o.reshape(B, Hkv, T, Gq, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, T, Hq, Dh)
    return o, p_slc.transpose(0, 2, 1, 3)
