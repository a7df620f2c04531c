"""Composable decoder-only model covering all assigned architectures.

Layers are organized into *segments*: maximal runs of the repeating
``block_pattern`` that can be scanned with stacked parameters (compile time
O(1) in depth — essential for the 96-layer dry-run cells). A segment holds a
tuple of stacked block-param trees, one per position in the pattern group.

Four execution paths:
  * ``loss_fn`` / ``forward_train`` — full-sequence causal training forward
    (chunked attention + chunked vocab cross-entropy).
  * ``prefill``     — training-style forward that also builds KV / compressed /
    recurrent caches for serving.
  * ``decode_step`` — single-token autoregressive decode (the paper's NSA
    decode baseline when ``cfg.attention == "nsa"``).
  * ``verify_step`` — gamma tree-masked draft tokens; NSA layers implement the
    paper's refresh/reuse schedule (cross-layer index inheritance via the
    layer-scan carry + ``lax.cond``) and exact/approx grouped selection via
    externally transformed indices.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, SSVConfig
from repro.core import kvstore
from repro.models import attention, layers, moe as moe_lib, nsa as nsa_lib, recurrent

RECURRENT_KINDS = ("rglru", "mlstm", "slstm")


# ------------------------------------------------------------------ segments
def segments(cfg: ModelConfig) -> List[Tuple[Tuple[str, ...], int]]:
    """[(group kinds, n_groups)] — tiles block_pattern over num_layers."""
    pat = tuple(cfg.block_pattern)
    m = len(pat)
    full = cfg.num_layers // m
    segs: List[Tuple[Tuple[str, ...], int]] = []
    if full > 0:
        segs.append((pat, full))
    rem = cfg.num_layers - full * m
    if rem:
        segs.append((tuple(cfg.layer_kinds()[full * m:]), 1))
    return segs


def layer_index(cfg: ModelConfig, seg_idx: int, group_idx, pos_in_group: int):
    """Absolute layer index of (segment, group, position)."""
    segs = segments(cfg)
    base = sum(len(k) * n for k, n in segs[:seg_idx])
    return base + group_idx * len(segs[seg_idx][0]) + pos_in_group


# ------------------------------------------------------------------ blocks
def block_init(key, cfg: ModelConfig, kind: str, dtype):
    k1, k2 = jax.random.split(key)
    p: Dict[str, Any] = {
        "norm1": layers.rmsnorm_init(cfg.d_model, dtype),
        "norm2": layers.rmsnorm_init(cfg.d_model, dtype),
    }
    if kind in RECURRENT_KINDS:
        p["mix"] = recurrent.INITS[kind](k1, cfg, dtype)
        if cfg.d_ff:
            p["ffn"] = layers.ffn_init(k2, cfg.d_model, cfg.d_ff, cfg.activation, dtype)
        return p
    if cfg.attention == "nsa":
        p["mix"] = nsa_lib.nsa_init(k1, cfg, dtype)
    else:
        p["mix"] = attention.attn_init(k1, cfg, dtype)
    if kind == "moe":
        p["ffn"] = moe_lib.moe_init(k2, cfg, dtype)
    else:
        p["ffn"] = layers.ffn_init(k2, cfg.d_model, cfg.d_ff, cfg.activation, dtype)
    return p


def _apply_ffn(bp, cfg: ModelConfig, kind: str, x, serving: bool = False):
    """Returns (y, aux). Expert layers serve through the dropless dispatch
    (a token's output never depends on the other tokens in the call) and
    train through the capacity dispatch."""
    if kind == "moe":
        if serving:
            return moe_lib.moe_apply_dropless(bp["ffn"], cfg, x)
        return moe_lib.moe_apply(bp["ffn"], cfg, x)
    if "ffn" in bp:
        return layers.ffn(bp["ffn"], x, cfg.activation), jnp.float32(0.0)
    return jnp.zeros_like(x), jnp.float32(0.0)


def _attn_window(cfg: ModelConfig) -> int:
    return cfg.window if cfg.attention == "swa" else 0


def block_apply_train(bp, cfg: ModelConfig, kind: str, x, positions, chunk: int):
    h = layers.rmsnorm(bp["norm1"], x, cfg.norm_eps)
    if kind in RECURRENT_KINDS:
        mix = recurrent.TRAIN[kind](bp["mix"], cfg, h)
    elif cfg.attention == "nsa":
        mix, _ = nsa_lib.attend_train_nsa(bp["mix"], cfg, h, positions, chunk=chunk)
    elif cfg.attention_impl == "flash":
        mix, _ = attention.attend_train_flash(bp["mix"], cfg, h, positions,
                                              window=_attn_window(cfg))
    elif cfg.attention_impl == "online":
        mix, _ = attention.attend_train_online(bp["mix"], cfg, h, positions,
                                               window=_attn_window(cfg))
    else:
        mix, _ = attention.attend_train(
            bp["mix"], cfg, h, positions, window=_attn_window(cfg), chunk=chunk,
            remat_chunks=(cfg.attention_impl == "chunked_remat"))
    x = x + mix
    h = layers.rmsnorm(bp["norm2"], x, cfg.norm_eps)
    y, aux = _apply_ffn(bp, cfg, kind, h)
    return x + y, aux


# ------------------------------------------------------------------ init
def init(key, cfg: ModelConfig):
    dtype = layers.dtype_of(cfg.dtype)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {"embed": layers.embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.lm_head_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)
    params["final_norm"] = layers.rmsnorm_init(cfg.d_model, dtype)
    if cfg.modality != "text" and cfg.frontend_dim:
        params["frontend_proj"] = layers.linear_init(keys[2], cfg.frontend_dim, cfg.d_model, dtype)
    segs = []
    for si, (kinds, n) in enumerate(segments(cfg)):
        seg_key = jax.random.fold_in(keys[3], si)
        stacked = []
        for j, kind in enumerate(kinds):
            jkeys = jax.random.split(jax.random.fold_in(seg_key, j), n)
            stacked.append(jax.vmap(lambda k: block_init(k, cfg, kind, dtype))(jkeys))
        segs.append(tuple(stacked))
    params["segments"] = segs
    return params


# ------------------------------------------------------------------ embedding
def embed_inputs(params, cfg: ModelConfig, tokens, frontend=None):
    """Returns (x (B, S_total, d), positions (B, S_total), n_prefix)."""
    x = layers.embed(params["embed"], tokens)
    n_prefix = 0
    if frontend is not None and "frontend_proj" in params:
        fx = frontend.astype(x.dtype) @ params["frontend_proj"]["w"]
        x = jnp.concatenate([fx, x], axis=1)
        n_prefix = frontend.shape[1]
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return x, positions, n_prefix


# ------------------------------------------------------------------ train fwd
def forward_train(params, cfg: ModelConfig, tokens, frontend=None, remat: bool = True,
                  attn_chunk: int = 512, constrain=None):
    """``constrain`` (optional) re-asserts the residual-stream sharding on the
    scan carry between layers — at scale this pins the stored (rematerialized)
    activations to a sequence-parallel layout (see launch/sharding.py)."""
    x, positions, n_prefix = embed_inputs(params, cfg, tokens, frontend)
    if constrain is not None:
        x = constrain(x)
    aux_total = jnp.float32(0.0)
    for (kinds, n), stacked in zip(segments(cfg), params["segments"]):
        def body(carry, gp, kinds=kinds):
            h, aux = carry
            for j, kind in enumerate(kinds):
                h, a = block_apply_train(gp[j], cfg, kind, h, positions, attn_chunk)
                aux = aux + a
            if constrain is not None:
                h = constrain(h)
            return (h, aux), None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), stacked)
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux_total, n_prefix


def logits_fn(params, cfg: ModelConfig, hidden):
    if cfg.tie_embeddings:
        return layers.unembed(params["embed"], hidden)
    return layers.lm_head(params["lm_head"], hidden)


def loss_fn(params, cfg: ModelConfig, tokens, frontend=None, remat: bool = True,
            loss_chunk: int = 512, aux_weight: float = 0.01, attn_chunk: int = 512,
            constrain=None):
    """Next-token cross-entropy, chunked over the sequence so the (chunk, V)
    logits working set stays bounded for 256K vocabularies."""
    hidden, aux, n_prefix = forward_train(params, cfg, tokens, frontend, remat,
                                          attn_chunk, constrain)
    B, S_tok = tokens.shape
    # predict tokens[t+1] from hidden at prefix+t
    h_pred = hidden[:, n_prefix : n_prefix + S_tok - 1]
    labels = tokens[:, 1:]
    S = h_pred.shape[1]
    chunk = min(loss_chunk, S)
    while S % chunk:
        chunk -= 1
    nchunk = S // chunk
    hc = h_pred.reshape(B, nchunk, chunk, cfg.d_model).swapaxes(0, 1)
    lc = labels.reshape(B, nchunk, chunk).swapaxes(0, 1)

    def body(tot, xs):
        h, l = xs
        logits = logits_fn(params, cfg, h).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hc, lc))
    loss = total / (B * S)
    return loss + aux_weight * aux


# ------------------------------------------------------------------ caches
def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype,
                     store: Optional[kvstore.KVStoreConfig] = None):
    if kind in RECURRENT_KINDS:
        return {"state": recurrent.STATE_INITS[kind](cfg, batch)}
    c = {"kv": kvstore.init_kv(cfg, batch, max_len, dtype,
                               store or kvstore.DENSE)}
    if cfg.attention == "nsa":
        c["cmp"] = nsa_lib.init_cmp_cache(cfg, batch, max_len, dtype, store)
    return c


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                store: Optional[kvstore.KVStoreConfig] = None):
    """Serving caches. Dense (default): raw-KV leaves (B, max_len, Hkv, Dh).
    Paged store: raw-KV leaves are the shared page pool (P, page_size, Hkv,
    Dh) — the engine owns the (B, max_pages) page table and threads it in as
    ``caches["pages"]``; cmp / recurrent leaves stay row-batched."""
    dtype = layers.dtype_of(cfg.dtype)
    caches = []
    for (kinds, n) in segments(cfg):
        stacked = []
        for kind in kinds:
            one = init_block_cache(cfg, kind, batch, max_len, dtype, store)
            stacked.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy() if n > 1 else a[None], one))
        caches.append(tuple(stacked))
    return {"segments": caches, "length": jnp.int32(0)}


# ------------------------------------------------------------------ prefill
def prefill(params, cfg: ModelConfig, tokens, max_len: int, frontend=None,
            attn_chunk: int = 512, constrain=None):
    """Run the full prompt, build caches. Returns (hidden (B,S,d), caches)."""
    dtype = layers.dtype_of(cfg.dtype)
    x, positions, n_prefix = embed_inputs(params, cfg, tokens, frontend)
    if constrain is not None:
        x = constrain(x)
    B, S, _ = x.shape
    assert S <= max_len
    seg_caches = []
    for (kinds, n), stacked in zip(segments(cfg), params["segments"]):
        def body(h, gp, kinds=kinds):
            caches_out = []
            for j, kind in enumerate(kinds):
                bp = gp[j]
                hn = layers.rmsnorm(bp["norm1"], h, cfg.norm_eps)
                if kind in RECURRENT_KINDS:
                    state0 = recurrent.STATE_INITS[kind](cfg, B)
                    if kind == "rglru":
                        mix, state = _rglru_prefill(bp["mix"], cfg, hn)
                    else:
                        mix, state = _xlstm_prefill(kind, bp["mix"], cfg, hn)
                    caches_out.append({"state": state})
                elif cfg.attention == "nsa":
                    mix, (k, v) = nsa_lib.attend_train_nsa(bp["mix"], cfg, hn, positions,
                                                           chunk=attn_chunk)
                    cache = attention.init_cache(cfg, B, max_len, dtype)
                    cache = attention.write_cache(cache, k, v, 0)
                    cmp = nsa_lib.init_cmp_cache(cfg, B, max_len, dtype)
                    k_cmp, v_cmp = nsa_lib.compress_kv(bp["mix"], k, v, cfg.nsa)
                    ncb = k_cmp.shape[1]
                    if ncb:
                        cmp = {"k_cmp": jax.lax.dynamic_update_slice_in_dim(
                                   cmp["k_cmp"], k_cmp.astype(dtype), 0, axis=1),
                               "v_cmp": jax.lax.dynamic_update_slice_in_dim(
                                   cmp["v_cmp"], v_cmp.astype(dtype), 0, axis=1)}
                    caches_out.append({"kv": cache, "cmp": cmp})
                elif cfg.attention_impl == "flash":
                    mix, (k, v) = attention.attend_train_flash(
                        bp["mix"], cfg, hn, positions, window=_attn_window(cfg))
                    cache = attention.init_cache(cfg, B, max_len, dtype)
                    cache = attention.write_cache(cache, k, v, 0)
                    caches_out.append({"kv": cache})
                else:
                    mix, (k, v) = attention.attend_train(bp["mix"], cfg, hn, positions,
                                                         window=_attn_window(cfg),
                                                         chunk=attn_chunk)
                    cache = attention.init_cache(cfg, B, max_len, dtype)
                    cache = attention.write_cache(cache, k, v, 0)
                    caches_out.append({"kv": cache})
                h = h + mix
                hn = layers.rmsnorm(bp["norm2"], h, cfg.norm_eps)
                y, _ = _apply_ffn(bp, cfg, kind, hn, serving=True)
                h = h + y
            if constrain is not None:
                h = constrain(h)
            return h, tuple(caches_out)

        x, caches = jax.lax.scan(body, x, stacked)
        seg_caches.append(caches)
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, {"segments": seg_caches, "length": jnp.int32(S)}


def _rglru_prefill(p, cfg, x):
    out = recurrent.rglru_apply_train(p, cfg, x)
    # recover final state: rerun coefficient path for last position via scan-free math
    u0 = x @ p["w_in"]
    u, _ = recurrent._causal_conv(p["conv"], u0)
    a, b = recurrent._rglru_coeffs(p, u)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, hh = jax.lax.associative_scan(combine, (a, b), axis=1)
    cw = p["conv"].shape[0]
    pad = jnp.concatenate([jnp.zeros((x.shape[0], cw - 1, u0.shape[-1]), u0.dtype), u0], axis=1)
    return out, {"h": hh[:, -1], "conv": pad[:, -(cw - 1):] if cw > 1 else pad[:, :0]}


def _xlstm_prefill(kind, p, cfg, x):
    B, S, d = x.shape
    state = recurrent.STATE_INITS[kind](cfg, B)
    step = recurrent.STEPS[kind]

    def body(st, t):
        xt = jax.lax.dynamic_slice_in_dim(x, t, 1, axis=1)
        out, st2 = step(p, cfg, xt, st)
        return st2, out[:, 0]

    state, outs = jax.lax.scan(body, state, jnp.arange(S))
    return outs.swapaxes(0, 1), state


# ------------------------------------------------------------------ decode / verify
def _reuse_layer_flags(cfg: ModelConfig, ssv: Optional[SSVConfig]):
    """Per-layer bool: True if the layer REUSES inherited indices.
    Layer 0 is a mandatory refresh (paper §5.2)."""
    L = cfg.num_layers
    flags = np.zeros((L,), bool)
    if ssv is not None:
        for i in ssv.refresh_schedule:
            if 0 <= i < L:
                flags[i] = True
    flags[0] = False
    return flags


def _mix_verify(bp, cfg: ModelConfig, kind: str, h, cache, prefix_len, positions,
                tree_mask, parents, carry_idx, reuse_flag, ssv: Optional[SSVConfig],
                pages=None):
    """Sequence-mix a block in verify mode. Returns (mix_out, cache_updates,
    new_carry_idx). ``pages`` is the request-shared page table under the
    paged KV store (None = dense layout)."""
    B, T, _ = h.shape
    if kind in RECURRENT_KINDS:
        step = recurrent.STEPS[kind]
        outs, buf = recurrent.verify_states(step, bp["mix"], cfg, h, parents,
                                            cache["state"])
        return outs, {"state_buf": buf}, carry_idx
    kv = kvstore.as_view(cache["kv"], pages)
    if cfg.attention == "nsa":
        @jax.named_scope("nsa.select")
        def fresh(_):
            q, _, _ = attention.qkv(bp["mix"], cfg, h, positions)
            _, p_slc = nsa_lib.routing(bp["mix"], cfg, q, cache["cmp"]["k_cmp"],
                                       cache["cmp"]["v_cmp"], positions,
                                       kv_len=kv.max_len,
                                       ncb_valid=nsa_lib.dyn_num_cmp_blocks(prefix_len, cfg.nsa))
            idx, val = nsa_lib.select_topn(p_slc, positions, prefix_len, cfg.nsa)
            if ssv is not None and ssv.group_mode == "approx" and ssv.group_size > 1:
                from repro.core.overlap import shared_index
                idx, val = shared_index(idx, val, positions, ssv.group_size)
            return idx, val

        def inherit(c):
            return c

        carry_idx = jax.lax.cond(reuse_flag, inherit, fresh, carry_idx)
        sel_idx, sel_valid = carry_idx
        out, (k_new, v_new), _ = nsa_lib.nsa_verify_ref(
            bp["mix"], cfg, h, kv, cache["cmp"], prefix_len, positions,
            tree_mask, sel_idx=sel_idx, sel_valid=sel_valid)
        return out, {"k_new": k_new, "v_new": v_new}, carry_idx
    out, (k_new, v_new) = attention.attend_verify(bp["mix"], cfg, h, kv,
                                                  prefix_len, positions, tree_mask,
                                                  window=_attn_window(cfg))
    return out, {"k_new": k_new, "v_new": v_new}, carry_idx


def verify_step(params, cfg: ModelConfig, caches, draft_tokens, positions, tree_mask,
                parents, ssv: Optional[SSVConfig] = None):
    """Verify gamma draft tokens against the committed caches.

    draft_tokens: (B, T); positions: (B, T) absolute; tree_mask (B, T, T);
    parents (T,) int32 (-1 = root attaches to committed prefix).

    Returns (logits (B, T, V), updates) where updates carries per-layer draft
    K/V (attention) or per-node state buffers (recurrent) for committing.
    """
    prefix_len = caches["length"]
    x = layers.embed(params["embed"], draft_tokens)
    B, T, _ = x.shape
    # carry for refresh/reuse index inheritance
    if cfg.attention == "nsa":
        nsb_max = nsa_lib.num_sel_blocks(_max_len_of(caches), cfg.nsa)
        n_idx = min(cfg.nsa.n_selected, max(nsb_max, 1))
        carry_idx = (jnp.zeros((B, T, cfg.num_kv_heads, n_idx), jnp.int32),
                     jnp.zeros((B, T, cfg.num_kv_heads, n_idx), bool))
    else:
        carry_idx = (jnp.zeros((B, T, 1, 1), jnp.int32), jnp.zeros((B, T, 1, 1), bool))

    flags = _reuse_layer_flags(cfg, ssv)
    li = 0
    seg_updates = []
    for (kinds, ngroups), stacked, seg_caches in zip(segments(cfg), params["segments"],
                                                     caches["segments"]):
        m = len(kinds)
        seg_flags = flags[li : li + ngroups * m].reshape(ngroups, m)
        li += ngroups * m

        def body(carry, xs, kinds=kinds):
            h, cidx = carry
            gp, gcache, gflags = xs
            ups = []
            for j, kind in enumerate(kinds):
                hn = layers.rmsnorm(gp[j]["norm1"], h, cfg.norm_eps)
                mix, up, cidx = _mix_verify(gp[j], cfg, kind, hn, gcache[j], prefix_len,
                                            positions, tree_mask, parents, cidx,
                                            gflags[j], ssv, pages=caches.get("pages"))
                h = h + mix
                hn = layers.rmsnorm(gp[j]["norm2"], h, cfg.norm_eps)
                y, _ = _apply_ffn(gp[j], cfg, kind, hn, serving=True)
                h = h + y
                ups.append(up)
            return (h, cidx), tuple(ups)

        (x, carry_idx), updates = jax.lax.scan(
            body, (x, carry_idx), (stacked, seg_caches, jnp.asarray(seg_flags)))
        seg_updates.append(updates)
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_fn(params, cfg, x)
    return logits, seg_updates


def _max_len_of(caches):
    pages = caches.get("pages")
    for seg in caches["segments"]:
        for c in seg:
            if "kv" in c:
                if pages is not None:
                    # stacked pool: (n, P, page_size, Hkv, Dh); logical
                    # capacity = pages per row x page size
                    return pages.shape[1] * c["kv"]["k"].shape[2]
                return c["kv"]["k"].shape[2]  # stacked: (n, B, S, Hkv, Dh)
    return 0


def decode_step(params, cfg: ModelConfig, caches, tokens, ssv: Optional[SSVConfig] = None):
    """One autoregressive step: tokens (B, 1). Returns (logits, new caches)."""
    B = tokens.shape[0]
    T = 1
    positions = jnp.broadcast_to(caches["length"][None, None], (B, 1)).astype(jnp.int32)
    tree_mask = jnp.ones((B, 1, 1), bool)
    parents = jnp.full((1,), -1, jnp.int32)
    logits, seg_updates = verify_step(params, cfg, caches, tokens, positions,
                                      tree_mask, parents, ssv)
    new_caches = commit(params, cfg, caches, seg_updates,
                        accepted=jnp.zeros((B, 1), jnp.int32),
                        n_accepted=jnp.ones((B,), jnp.int32))
    return logits, new_caches


def commit(params, cfg: ModelConfig, caches, seg_updates, accepted, n_accepted):
    """Commit accepted draft tokens into the caches.

    accepted: (B, T_acc) node indices into the draft batch (a root-to-leaf
    path, padded with the last valid entry); n_accepted: (B,) how many are
    real. Appends accepted K/V (or selects the accepted recurrent state) and
    advances length. All shapes static; garbage beyond n_accepted is masked
    by `length` downstream. A row with n_accepted == 0 is a no-op commit
    (length frozen, recurrent state preserved) — batched serving uses this to
    freeze finished requests while the rest of the batch keeps stepping.

    Paged caches (``"pages"`` present) route through the prepare/apply pair
    below: accepted K/V scatter into the shared page pool through the page
    table instead of a dense slice write.
    """
    if "pages" in caches:
        prep, new_len = commit_paged_prepare(params, cfg, caches, seg_updates,
                                             accepted, n_accepted)
        segs = commit_apply_paged(caches["segments"], prep, caches["pages"],
                                  caches["length"], n_accepted)
        return {"segments": segs, "length": new_len, "pages": caches["pages"]}
    old_len = caches["length"]
    B, T_acc = accepted.shape
    # NOTE: batched serving commits per-row lengths; the engine uses B==1 per
    # sequence group, so a scalar length is sound here.
    new_len = old_len + n_accepted[0]
    max_new_cmp = (T_acc // cfg.nsa.cmp_stride) + 2
    new_segs = []
    for (kinds, ngroups), stacked, seg_caches, updates in zip(
            segments(cfg), params["segments"], caches["segments"], seg_updates):
        new_stack = []
        for j, kind in enumerate(kinds):
            cache_j = seg_caches[j]
            up_j = updates[j]
            if kind in RECURRENT_KINDS:
                new_stack.append({"state": _pick_recurrent(cache_j, up_j,
                                                           accepted, n_accepted)})
                continue
            # attention: gather accepted K/V along the draft axis and append
            k_acc, v_acc = _gather_accepted(up_j, accepted)
            kv = cache_j["kv"]
            k_cache = jax.vmap(lambda c, kn: jax.lax.dynamic_update_slice_in_dim(
                c, kn.astype(c.dtype), old_len, axis=1))(kv["k"], k_acc)
            v_cache = jax.vmap(lambda c, vn: jax.lax.dynamic_update_slice_in_dim(
                c, vn.astype(c.dtype), old_len, axis=1))(kv["v"], v_acc)
            new_c = {"kv": {"k": k_cache, "v": v_cache}}
            if "cmp" in cache_j:
                new_c["cmp"] = jax.vmap(
                    lambda p, kvc, cmpc: nsa_lib.update_cmp_cache_dyn(
                        p, kvc, cmpc, old_len, new_len, max_new_cmp, cfg.nsa),
                    in_axes=(0, 0, 0))(stacked[j]["mix"], new_c["kv"], cache_j["cmp"])
            new_stack.append(new_c)
        new_segs.append(tuple(new_stack))
    return {"segments": new_segs, "length": new_len}


def _gather_accepted(up_j, accepted):
    """Pick the accepted root-to-leaf path's K/V out of a layer's draft
    updates: (n, B, T, Hkv, Dh) -> (n, B, T_acc, Hkv, Dh)."""
    B, T_acc = accepted.shape
    k_new, v_new = up_j["k_new"], up_j["v_new"]
    gi = accepted[None, :, :, None, None]
    k_acc = jnp.take_along_axis(k_new, jnp.broadcast_to(
        gi, (k_new.shape[0], B, T_acc) + k_new.shape[3:]), axis=2)
    v_acc = jnp.take_along_axis(v_new, jnp.broadcast_to(
        gi, (v_new.shape[0], B, T_acc) + v_new.shape[3:]), axis=2)
    return k_acc, v_acc


def _pick_recurrent(cache_j, up_j, accepted, n_accepted):
    """Accepted-state selection for a recurrent layer (shared by the dense
    and paged commits): take the state after the deepest accepted node, keep
    the old state for rows with nothing accepted."""
    B = accepted.shape[0]
    buf = up_j["state_buf"]          # leaves: (n, T+1, B, ...)
    last = accepted[:, -1]           # (B,)

    def pick(b):
        idx = jnp.clip(last + 1, 0, b.shape[1] - 1)
        idxe = idx.reshape((1, 1, B) + (1,) * (b.ndim - 3))
        g = jnp.take_along_axis(
            b, jnp.broadcast_to(idxe, (b.shape[0], 1, B) + b.shape[3:]), axis=1)
        return g[:, 0]

    new_state = jax.tree.map(pick, buf)
    live = n_accepted > 0

    def keep(ns, o):
        m = live.reshape((1, B) + (1,) * (ns.ndim - 2))
        return jnp.where(m, ns.astype(o.dtype), o)

    return jax.tree.map(keep, new_state, cache_j["state"])


def commit_paged_prepare(params, cfg: ModelConfig, caches, seg_updates,
                         accepted, n_accepted):
    """Everything in a paged commit EXCEPT the page-pool writes.

    Per attention layer: the accepted K/V path (``{"acc": {"k", "v"}}``,
    (n, B, T_acc, Hkv, Dh)) plus the updated compression cache — computed
    against the *pre-write* pool with the accepted tokens overlaid, so it
    never depends on write ordering. Per recurrent layer: the selected
    state. Splitting prepare from apply lets the batched step run prepare
    inside its per-row vmap (pools are read-only there) and issue the shared
    -pool scatters once, at batch level, where rows cannot alias.
    Returns (prep segments, new_len)."""
    old_len = caches["length"]
    B, T_acc = accepted.shape
    new_len = old_len + n_accepted[0]
    max_new_cmp = (T_acc // cfg.nsa.cmp_stride) + 2
    pages = caches["pages"]
    prep = []
    for (kinds, ngroups), stacked, seg_caches, updates in zip(
            segments(cfg), params["segments"], caches["segments"], seg_updates):
        group = []
        for j, kind in enumerate(kinds):
            cache_j = seg_caches[j]
            up_j = updates[j]
            if kind in RECURRENT_KINDS:
                group.append({"state": _pick_recurrent(cache_j, up_j,
                                                       accepted, n_accepted)})
                continue
            k_acc, v_acc = _gather_accepted(up_j, accepted)
            entry = {"acc": {"k": k_acc, "v": v_acc}}
            if "cmp" in cache_j:
                def upd(p, pk, pv, cmpc, ka, va):
                    view = kvstore.KVView(pk, pv, pages)
                    return nsa_lib.update_cmp_cache_dyn(
                        p, view, cmpc, old_len, new_len, max_new_cmp, cfg.nsa,
                        overlay=(ka, va))
                entry["cmp"] = jax.vmap(upd)(
                    stacked[j]["mix"], cache_j["kv"]["k"], cache_j["kv"]["v"],
                    cache_j["cmp"], k_acc, v_acc)
            group.append(entry)
        prep.append(tuple(group))
    return prep, new_len


def commit_apply_paged(segs, prep, pages, old_len, n_accepted):
    """Apply a prepared paged commit to the cache segments: scatter each
    layer's accepted K/V into the shared page pool through the page table
    (rows with ``n_accepted == 0`` — finished slots whose pages may already
    belong to a new request — are dropped, not clamped) and swap in the
    prepared cmp / recurrent leaves.

    Works for the single-request caches (prep leaves (n, B, T_acc, ...),
    ``old_len`` scalar) and for the batched engine (prep leaves stacked to
    (n, R, T_acc, ...), ``old_len``/``n_accepted`` shaped (R,))."""
    mask = n_accepted > 0
    new_segs = []
    for seg_prep, seg_caches in zip(prep, segs):
        group = []
        for cp, cc in zip(seg_prep, seg_caches):
            if "state" in cp:
                group.append({"state": cp["state"]})
                continue
            kv = cc["kv"]

            def write_one(pk, pv, ka, va):
                view = kvstore.KVView(pk, pv, pages)
                return view.write(ka, va, old_len, row_mask=mask)

            k_pool, v_pool = jax.vmap(write_one)(kv["k"], kv["v"],
                                                 cp["acc"]["k"], cp["acc"]["v"])
            new_c = {"kv": {"k": k_pool, "v": v_pool}}
            if "cmp" in cp:
                new_c["cmp"] = cp["cmp"]
            group.append(new_c)
        new_segs.append(tuple(group))
    return new_segs
