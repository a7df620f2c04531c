"""Mixture-of-Experts FFN with top-k routing.

The router scores ``MoEConfig.router_width`` experts (softmax), takes the
``top_k`` over all of them and renormalises their weights. A layer holds
``num_experts`` of those experts, from ``first_expert`` on, as one chip of
an expert-parallel deployment does; a token's output is the weighted sum
of its chosen experts that are held here (every expert, unless the config
states a share). What absent experts would add is left out: on one chip
the layer runs without the exchange that would bring it.

Two dispatches:

* ``moe_apply`` (training): GShard-style grouped one-hot dispatch/combine
  einsums with a capacity factor. Assignments past an expert's capacity in
  a group are dropped, so a token's output depends on the other tokens of
  its group; it returns the load-balancing auxiliary loss.
* ``moe_apply_dropless`` (serving: prefill, and verify, which decode runs
  through): every token runs through every held expert, and each expert's
  output counts with the token's routing weight, 0 where the token did not
  choose it. Nothing is dropped, so a token's output depends on that token
  alone, and the layer needs no sort or gather. It computes
  ``router_width / top_k`` times the FLOPs of the held assignments (16
  times for a 128-wide top-8 router, whether the layer holds a share of
  the experts or all of them): at a tree verify's few hundred tokens the
  layer is bound by reading the held experts' weights, at a long prefill
  by those FLOPs. A sort by expert through ``jax.lax.ragged_dot`` sizes
  its buffer for the worst case, every token choosing only held experts,
  and at 8 held experts of 128 it did the same work and was slower on a
  TPU v5e. Mapped over rows (the fused step maps its verify pass over the
  batch), its einsums batch against the unmapped weights, so each
  expert's weights are read once per layer.

The trace-time counters ``moe.dispatch.capacity`` / ``moe.dispatch.dropless``
say which dispatch a program got. The dropless dispatch's named scopes,
``moe.ffn`` with the children ``moe.route``, ``moe.dispatch``,
``moe.experts`` and ``moe.combine``, put its device ops under them in a
profile.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import ModelConfig, MoEConfig
from repro.models import layers


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32):
    moe = cfg.moe
    d = cfg.d_model
    dff = moe.d_expert or cfg.d_ff
    gated = cfg.activation in layers.GATED
    ks = jax.random.split(key, 5)
    scale = 1.0 / np.sqrt(d)
    p = {
        "router": (jax.random.normal(ks[0], (d, moe.router_width)) * 0.02).astype(jnp.float32),
        "w_up": (jax.random.normal(ks[1], (moe.num_experts, d, dff)) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[2], (moe.num_experts, dff, d)) / np.sqrt(dff)).astype(dtype),
    }
    if gated:
        p["w_gate"] = (jax.random.normal(ks[3], (moe.num_experts, d, dff)) * scale).astype(dtype)
    if moe.num_shared_experts:
        p["shared"] = layers.ffn_init(ks[4], d, cfg.d_ff, cfg.activation, dtype)
    return p


def router_probs(params, x, moe: MoEConfig):
    """x: (N, d) -> (probs (N, router_width) f32, topk_idx (N, k), topk_w
    (N, k)); the router runs in float32."""
    logits = jnp.matmul(x.astype(jnp.float32), params["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, moe.top_k)
    topk_w = topk_w / jnp.clip(topk_w.sum(-1, keepdims=True), 1e-9)   # renormalize
    return probs, topk_idx, topk_w


def load_balance_loss(probs, topk_idx, num_experts: int):
    """Switch-style auxiliary loss: E * sum_e f_e * p_e."""
    N = probs.shape[0]
    counts = jnp.zeros((num_experts,), jnp.float32).at[topk_idx.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(1.0, N * topk_idx.shape[-1])
    p = probs.mean(axis=0)
    return num_experts * jnp.sum(f * p)


def _expert_ffn(params, xd, activation: str):
    """xd: (..., E, C, d) grouped tokens -> expert FFN output, batched over E."""
    if activation in layers.GATED:
        act = layers.GATED[activation]
        h = act(jnp.einsum("...ecd,edf->...ecf", xd, params["w_gate"])) * \
            jnp.einsum("...ecd,edf->...ecf", xd, params["w_up"])
    else:
        act = layers.ACTIVATIONS[activation]
        h = act(jnp.einsum("...ecd,edf->...ecf", xd, params["w_up"]))
    return jnp.einsum("...ecf,efd->...ecd", h, params["w_down"])


def moe_apply(params, cfg: ModelConfig, x, group_size: int = 0):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Grouped one-hot dispatch: tokens are reshaped to (n_groups, G, d); each
    group has capacity C = ceil(G * top_k * cf / E). Positions beyond capacity
    are dropped (their combine weight is 0; residual connection keeps the
    token's value). A layer that holds a share of a wider router's experts
    only serves (``moe_apply_dropless``).
    """
    moe = cfg.moe
    if moe.router_width != moe.num_experts:
        raise ValueError("capacity dispatch routes over all experts; a layer "
                         "that holds a share of them serves through "
                         "moe_apply_dropless")
    obs.count("moe.dispatch.capacity")
    B, S, d = x.shape
    N = B * S
    xf = x.reshape(N, d)
    G = min(group_size or moe.dispatch_group, N)
    while N % G:
        G //= 2
    ngroups = N // G
    E, K = moe.num_experts, moe.top_k
    C = int(np.ceil(G * K * moe.capacity_factor / E))
    C = max(C, K)

    probs, topk_idx, topk_w = router_probs(params, xf, moe)
    aux = load_balance_loss(probs, topk_idx, E)

    xg = xf.reshape(ngroups, G, d)
    idx_g = topk_idx.reshape(ngroups, G, K)
    w_g = topk_w.reshape(ngroups, G, K)

    # position of each (token, k) within its expert, per group
    onehot = jax.nn.one_hot(idx_g, E, dtype=jnp.int32)                 # (n, G, K, E)
    flat = onehot.reshape(ngroups, G * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat                              # (n, G*K, E)
    pos_in_e = (pos * flat).sum(-1).reshape(ngroups, G, K)             # (n, G, K)
    keep = pos_in_e < C
    w_g = jnp.where(keep, w_g, 0.0)

    # dispatch tensor (n, G, E, C) — bf16 one-hot keeps the einsum on the MXU
    disp = (jax.nn.one_hot(idx_g, E, dtype=x.dtype)[..., None] *
            jax.nn.one_hot(jnp.where(keep, pos_in_e, C), C + 1, dtype=x.dtype)[..., None, :]
            ).sum(axis=2)[..., :C]                                     # (n, G, E, C)
    xd = jnp.einsum("ngec,ngd->necd", disp, xg)                        # (n, E, C, d)
    yd = _expert_ffn(params, xd, cfg.activation)                       # (n, E, C, d)
    comb = (w_g[..., None, None].astype(jnp.float32) *
            jax.nn.one_hot(idx_g, E, dtype=jnp.float32)[..., None] *
            jax.nn.one_hot(jnp.where(keep, pos_in_e, C), C + 1,
                           dtype=jnp.float32)[..., None, :]).sum(axis=2)[..., :C]
    y = jnp.einsum("ngec,necd->ngd", comb.astype(x.dtype), yd)         # (n, G, d)
    y = y.reshape(B, S, d)
    if moe.num_shared_experts:
        y = y + layers.ffn(params["shared"], x, cfg.activation)
    return y, aux


def moe_apply_dropless(params, cfg: ModelConfig, x):
    """x: (B, S, d) -> (out (B, S, d), 0.0): the dropless dispatch of the
    serving paths (see the module docstring)."""
    obs.count("moe.dispatch.dropless")
    moe = cfg.moe
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    with jax.named_scope("moe.ffn"):
        with jax.named_scope("moe.route"):
            _, topk_idx, topk_w = router_probs(params, xf, moe)
        with jax.named_scope("moe.dispatch"):
            # (N, held): each held expert's routing weight, 0 where unchosen
            # (an expert held elsewhere has no column)
            chosen = jax.nn.one_hot(topk_idx - moe.first_expert,
                                    moe.num_experts, dtype=jnp.float32)
            weight = (chosen * topk_w[..., None]).sum(axis=1)
        with jax.named_scope("moe.experts"):
            up = jnp.einsum("nd,edf->nef", xf, params["w_up"])
            if cfg.activation in layers.GATED:
                gate = jnp.einsum("nd,edf->nef", xf, params["w_gate"])
                h = layers.GATED[cfg.activation](gate) * up
            else:
                h = layers.ACTIVATIONS[cfg.activation](up)
        with jax.named_scope("moe.combine"):
            # weighted, then projected down and summed over the held experts
            # in one contraction
            h = (h * weight[:, :, None]).astype(h.dtype)
            y = jnp.einsum("nef,efd->nd", h, params["w_down"])
            if moe.num_shared_experts:
                y = y + layers.ffn(params["shared"], xf, cfg.activation)
    return y.reshape(B, S, d), jnp.float32(0.0)
