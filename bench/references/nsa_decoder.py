"""Plain float32 reference of an NSA decoder (the configurations'
``"reference": "nsa_decoder"``).

Written from the model's equations, in straightforward ``jax.numpy`` at
float32 with every matmul at ``HIGHEST`` precision. It imports nothing of
the served program: it reads the benchmark's own weight tree by name.

One block, per layer: x += Attn(RMSNorm(x)); x += SwiGLU(RMSNorm(x)).
Attention is NSA with three branches mixed by per-head sigmoid gates
(order: compressed, selected, window), grouped-query heads, RoPE on q and k
(rotate-half, theta from the configuration) after an optional per-head
RMSNorm of q and k:

- compressed: block i covers tokens [i*d, i*d + l); its key (value) is the
  softmax(phi_k)-weighted mean of the block's keys (values), times w_cmp_k
  (w_cmp_v). A query sees block i when the block ends before its sparse
  bound.
- selected: the compressed attention probabilities, summed over the query
  heads of a KV group, are spread onto selection blocks of l' tokens by
  the fraction of each compressed block that falls in the selection block.
  The first ``n_init_blocks`` blocks and the ``n_local_blocks`` blocks
  ending at the bound are always kept; the rest of the n slots go to the
  highest scores. The query attends every token of its selected blocks
  that lies before its bound.
- window: the w tokens ending at the query itself.

The sparse bound of a query at position p is p for every prompt token and
every token committed as the root of a speculative step; a token accepted
deeper in a draft tree was verified against the prefix committed before
that step, so its bound is that step's committed length. ``bounds`` gives
it per position. An empty branch contributes zero.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def round_e4m3(x):
    """Round float32 to the nearest float8 e4m3fn value (3 mantissa bits,
    exponent bias 7, subnormal step 2**-9, saturating at 448), ties to
    even, in float32 arithmetic so that any backend computes it."""
    _, e = jnp.frexp(x)
    q = jnp.exp2(jnp.maximum(e - 4, -9).astype(jnp.float32))
    return jnp.clip(jnp.round(x / q) * q, -F8_MAX, F8_MAX)


def _fp8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return round_e4m3(x / s) * s


def _store(x, fp8: bool):
    """A tensor the served program keeps in its storage dtype (the
    residual stream, K and V): the control rounds it to float8 per row."""
    return _fp8(x, -1) if fp8 else x


def _linear(x, w, fp8: bool):
    """x (..., d_in) @ w (d_in, d_out) in float32; the control rounds both
    operands to float8 first (per row of x, per column of w)."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    s, c = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _softmax_masked(logits, mask):
    """Softmax over the last axis where ``mask``; rows with no key give 0."""
    logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(mask, jnp.exp(logits - m), 0.0)
    z = jnp.sum(e, axis=-1, keepdims=True)
    return e / jnp.where(z > 0, z, 1.0)


def _overlap(ncb, nsb, l, d, lp):
    i = np.arange(ncb)[:, None]
    j = np.arange(nsb)[None, :]
    lo = np.maximum(i * d, j * lp)
    hi = np.minimum(i * d + l, (j + 1) * lp)
    return (np.maximum(0, hi - lo) / float(l)).astype(np.float32)


def _attention(p, c, h, pos, bounds, fp8, block_q):
    """NSA over a whole sequence h (S, d) of one request."""
    nsa = c["nsa"]
    S = h.shape[0]
    hq, hkv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    G = hq // hkv
    l, d, lp = nsa["cmp_block"], nsa["cmp_stride"], nsa["sel_block"]
    n_sel, w = nsa["n_selected"], nsa["window"]
    eps = c["rms_norm_eps"]
    q = _linear(h, p["wq"], fp8).reshape(S, hq, dh)
    k = _linear(h, p["wk"], fp8).reshape(S, hkv, dh)
    v = _linear(h, p["wv"], fp8).reshape(S, hkv, dh)
    if c.get("qk_norm"):
        q = _rms(q, p["q_norm"]["scale"], eps)
        k = _rms(k, p["k_norm"]["scale"], eps)
    q = _rope(q, pos, c["rope_theta"])
    k = _store(_rope(k, pos, c["rope_theta"]), fp8)
    v = _store(v, fp8)
    gates = jax.nn.sigmoid(_linear(h, p["w_gate"], fp8)
                           + p["b_gate"].astype(jnp.float32)).reshape(S, 3, hq)

    ncb = 0 if S < l else (S - l) // d + 1
    nsb = -(-S // lp)
    idx = np.arange(ncb)[:, None] * d + np.arange(l)[None, :]
    wk = jax.nn.softmax(p["phi_k"].astype(jnp.float32))
    wv = jax.nn.softmax(p["phi_v"].astype(jnp.float32))
    k_cmp = jnp.matmul(jnp.einsum("nlhd,l->nhd", k[idx], wk, precision=HI),
                       p["w_cmp_k"].astype(jnp.float32), precision=HI)
    v_cmp = jnp.matmul(jnp.einsum("nlhd,l->nhd", v[idx], wv, precision=HI),
                       p["w_cmp_v"].astype(jnp.float32), precision=HI)
    cmp_end = jnp.asarray(np.arange(ncb) * d + l - 1)
    M = jnp.asarray(_overlap(ncb, nsb, l, d, lp))
    sel_start = jnp.arange(nsb) * lp
    tok = jnp.arange(S)
    n_eff = min(n_sel, nsb)
    scale = 1.0 / math.sqrt(dh)
    nblk = S // block_q
    W = w + block_q

    def one_block(b):
        s0 = b * block_q
        qb = jax.lax.dynamic_slice_in_dim(q, s0, block_q).reshape(block_q, hkv, G, dh)
        pb = jax.lax.dynamic_slice_in_dim(pos, s0, block_q)
        bb = jax.lax.dynamic_slice_in_dim(bounds, s0, block_q)
        # compressed branch
        vis = cmp_end[None, :] <= (bb[:, None] - 1)                     # (Q, NCB)
        lc = jnp.einsum("qhgd,nhd->qhgn", qb, k_cmp, precision=HI) * scale
        pc = _softmax_masked(lc, vis[:, None, None, :])
        o_cmp = jnp.einsum("qhgn,nhd->qhgd", pc, v_cmp, precision=HI)
        # selection
        score = jnp.matmul(pc.sum(axis=2), M, precision=HI)             # (Q, Hkv, NSB)
        causal = sel_start[None, :] < bb[:, None]                       # (Q, NSB)
        last = (bb - 1) // lp
        blk = jnp.arange(nsb)
        mand = blk[None, :] < nsa["n_init_blocks"]
        for o in range(nsa["n_local_blocks"]):
            mand = mand | (blk[None, :] == jnp.maximum(last - o, 0)[:, None])
        mand = mand & causal
        key = jnp.where(mand[:, None, :], jnp.inf, jnp.where(causal[:, None, :], score, -jnp.inf))
        top_v, top_i = jax.lax.top_k(key, n_eff)
        chosen = jnp.zeros((block_q, hkv, nsb), bool)
        chosen = jnp.any(jax.nn.one_hot(top_i, nsb, dtype=bool)
                         & (top_v > -jnp.inf)[..., None], axis=2) | chosen
        m_sel = chosen[:, :, tok // lp] & (tok[None, None, :] < bb[:, None, None])
        ls = jnp.einsum("qhgd,khd->qhgk", qb, k, precision=HI) * scale
        ps = _softmax_masked(ls, m_sel[:, :, None, :])
        o_slc = jnp.einsum("qhgk,khd->qhgd", ps, v, precision=HI)
        # window
        w0 = jnp.clip(s0 - w, 0, max(S - W, 0))
        kw = jax.lax.dynamic_slice_in_dim(k, w0, min(W, S))
        vw = jax.lax.dynamic_slice_in_dim(v, w0, min(W, S))
        tw = w0 + jnp.arange(min(W, S))
        m_win = (tw[None, :] <= pb[:, None]) & (tw[None, :] > pb[:, None] - w)
        lw = jnp.einsum("qhgd,khd->qhgk", qb, kw, precision=HI) * scale
        pw = _softmax_masked(lw, m_win[:, None, None, :])
        o_win = jnp.einsum("qhgk,khd->qhgd", pw, vw, precision=HI)
        gb = jax.lax.dynamic_slice_in_dim(gates, s0, block_q).reshape(block_q, 3, hkv, G)
        o = (gb[:, 0, :, :, None] * o_cmp + gb[:, 1, :, :, None] * o_slc
             + gb[:, 2, :, :, None] * o_win)
        return o.reshape(block_q, hq * dh)

    out = jax.lax.map(one_block, jnp.arange(nblk)).reshape(S, hq * dh)
    return _linear(out, p["wo"], fp8)


@functools.partial(jax.jit, static_argnames=("c_items", "fp8", "block_q"))
def _logits(params, tokens, bounds, at, c_items, fp8, block_q):
    c = _unfreeze(c_items)
    eps = c["rms_norm_eps"]
    S = tokens.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    x = _store(params["embed"]["table"].astype(jnp.float32)[tokens], fp8)
    (stacked,) = params["segments"][0]

    def layer(x, lp):
        h = _rms(x, lp["norm1"]["scale"], eps)
        x = _store(x + _attention(lp["mix"], c, h, pos, bounds, fp8, block_q), fp8)
        h = _rms(x, lp["norm2"]["scale"], eps)
        f = lp["ffn"]
        y = jax.nn.silu(_linear(h, f["w_gate"], fp8)) * _linear(h, f["w_up"], fp8)
        return _store(x + _linear(y, f["w_down"], fp8), fp8), None

    x, _ = jax.lax.scan(layer, x, stacked)
    h = _rms(x[at], params["final_norm"]["scale"], eps)
    if c.get("tie_word_embeddings"):
        return _linear(h, params["embed"]["table"].T, fp8)
    return _linear(h, params["lm_head"]["w"], fp8)


def _freeze(d):
    if isinstance(d, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in d.items()))
    if isinstance(d, list):
        return ("__list__",) + tuple(_freeze(v) for v in d)
    return d


def _unfreeze(t):
    if isinstance(t, tuple) and t and t[0] == "__list__":
        return [_unfreeze(v) for v in t[1:]]
    if isinstance(t, tuple):
        return {k: _unfreeze(v) for k, v in t}
    return t


MODEL_KEYS = ("num_hidden_layers", "hidden_size", "intermediate_size",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "vocab_size", "rope_theta", "rms_norm_eps", "qk_norm",
              "tie_word_embeddings", "nsa")


def logits(params, cfg: dict, tokens, bounds, at, *, fp8: bool = False,
           block_q: int = 256):
    """Logits (len(at), vocab) at positions ``at`` of the sequence
    ``tokens`` (S,), whose per-position sparse bounds are ``bounds`` (S,).
    S must be a multiple of ``block_q``; padding after the last position
    read changes nothing before it."""
    if len(params["segments"]) != 1 or len(params["segments"][0]) != 1:
        raise ValueError("nsa_decoder covers one repeated attention block")
    c_items = _freeze({k: cfg[k] for k in MODEL_KEYS if k in cfg})
    return _logits(params, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(bounds, jnp.int32), jnp.asarray(at, jnp.int32),
                   c_items, fp8, block_q)
