"""jit wrapper for the flash tree-verification kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash import kernel as K
from repro.kernels.platform import resolve_interpret


def _pad_axis(x, axis: int, target: int):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, pad)
    return jnp.pad(x, w)


@functools.lru_cache(maxsize=128)
def _cached(key):
    return K.build_flash_verify(**dict(key))


def flash_verify(q, k_cache, v_cache, k_draft, v_draft, positions, prefix_len,
                 tree_mask, window: int = 0, interpret: Optional[bool] = None):
    """q: (B,T,Hq,Dh) pre-scaled + rope'd. Returns (B,T,Hq,Dh) f32.
    ``interpret=None`` interprets the kernel on the CPU backend only."""
    B, T, Hq, Dh = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    Gq = Hq // Hkv
    R = T * Gq
    TS = min(128, max(8, S))
    Sp = -(-S // TS) * TS
    Tp = max(8, -(-T // 8) * 8)

    q_l = q.reshape(B, T, Hkv, Gq, Dh).transpose(0, 2, 1, 3, 4).reshape(B, Hkv, R, Dh)
    heads_major = lambda x, n: _pad_axis(x, 1, n).transpose(0, 2, 1, 3)
    k_p, v_p = heads_major(k_cache, Sp), heads_major(v_cache, Sp)
    kd, vd = heads_major(k_draft, Tp), heads_major(v_draft, Tp)
    dmask = tree_mask & (positions[:, :, None] >= positions[:, None, :])
    if window > 0:
        dmask &= (positions[:, :, None] - positions[:, None, :]) < window
    # row layout matches q_l: jnp.repeat along axis 1 maps draft row t to the
    # Gq consecutive rows [t*Gq, (t+1)*Gq)
    dm = _pad_axis(jnp.repeat(dmask, Gq, axis=1).astype(jnp.int32), 2, Tp)

    key = tuple(sorted(dict(B=B, Hkv=Hkv, R=R, Gq=Gq, Dh=Dh, Sp=Sp, Tp=Tp,
                            TS=TS, window=window,
                            interpret=resolve_interpret(interpret)).items()))
    call = _cached(key)
    s_scalar = jnp.stack([jnp.asarray(prefix_len, jnp.int32)])
    pos_rows = jnp.repeat(positions.astype(jnp.int32), Gq, axis=1)[..., None]
    o = call(s_scalar, pos_rows, q_l, k_p, v_p, kd, vd, dm)
    o = o.reshape(B, Hkv, T, Gq, Dh).transpose(0, 2, 1, 3, 4).reshape(B, T, Hq, Dh)
    return o
