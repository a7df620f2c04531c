"""Plain float32 reference of an NSA decoder whose FFNs are expert layers
(the configurations' ``"reference": "nsa_moe_decoder"``).

Written from the model's equations, in straightforward ``jax.numpy`` at
float32 with every matmul at ``HIGHEST`` precision. It imports nothing of
the served program: it reads the benchmark's own weight tree by name, and
takes NSA attention, the norms and the float8 control from
``nsa_decoder``.

One block, per layer: x += Attn(RMSNorm(x)); x += MoE(RMSNorm(x)), with
Attn as in ``nsa_decoder``. The expert layer (Qwen3-MoE, ``norm_topk_prob``
true): the router's softmax over its ``router_experts`` outputs, the
``top_k`` largest probabilities renormalised to sum to 1, and

    MoE(h) = sum over the chosen experts e held here of
             w_e * W_down_e (silu(W_gate_e h) * W_up_e h).

This chip holds the ``num_experts`` experts from ``first_expert`` on;
what the chosen experts held elsewhere would add is left out, as the
served layer leaves it out (it would come back over the expert-parallel
exchange). The held experts are computed densely: every token through
every held expert, weighted by 0 where the expert was not chosen.

The float8 control rounds the expert matmuls' operands, as every other
linear layer's; the router stays in float32, as the program runs it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.references.nsa_decoder import (HI, _attention, _fp8, _freeze,
                                          _linear, _rms, _store, _unfreeze)


def _per_expert(spec, x, w, fp8: bool):
    """``x`` through every held expert's ``w`` (E, d_in, d_out) by the
    einsum ``spec``; the control rounds x per row and w per output column."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, -2)
    return jnp.einsum(spec, x, w, precision=HI)


def _experts(f, c, h, fp8):
    m = c["moe"]
    logits = jnp.matmul(h, f["router"].astype(jnp.float32), precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, m["top_k"])
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # the routing weight of each held expert, 0 where it was not chosen
    held = m.get("first_expert", 0) + jnp.arange(m["num_experts"])
    weight = jnp.sum(jnp.where(top_i[:, :, None] == held, top_w[:, :, None],
                               0.0), axis=1)                      # (S, E)
    y = (jax.nn.silu(_per_expert("sd,edf->esf", h, f["w_gate"], fp8))
         * _per_expert("sd,edf->esf", h, f["w_up"], fp8))         # (E, S, f)
    out = _per_expert("esf,efd->esd", y, f["w_down"], fp8)        # (E, S, d)
    return jnp.einsum("se,esd->sd", weight, out, precision=HI)


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
        return _store(x + _experts(lp["ffn"], c, h, fp8), fp8), None

    x, _ = jax.lax.scan(layer, x, stacked)
    h = _rms(x[at], params["final_norm"]["scale"], eps)
    if c.get("tie_word_embeddings"):
        return _linear(h, params["embed"]["table"].T, fp8)
    return _linear(h, params["lm_head"]["w"], fp8)


MODEL_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "vocab_size", "rope_theta",
              "rms_norm_eps", "qk_norm", "tie_word_embeddings", "nsa", "moe")


def logits(params, cfg: dict, tokens, bounds, at, *, fp8: bool = False,
           block_q: int = 256):
    """Logits (len(at), vocab) at positions ``at`` of the sequence
    ``tokens`` (S,), whose per-position sparse bounds are ``bounds`` (S,).
    S must be a multiple of ``block_q``; padding after the last position
    read changes nothing before it."""
    if (len(params["segments"]) != 1 or len(params["segments"][0]) != 1
            or cfg.get("block_pattern") != ["moe"]):
        raise ValueError("nsa_moe_decoder covers one repeated expert block")
    if cfg["moe"].get("num_shared_experts") or cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("nsa_moe_decoder covers routed SwiGLU experts only")
    c_items = _freeze({k: cfg[k] for k in MODEL_KEYS if k in cfg})
    return _logits(params, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(bounds, jnp.int32), jnp.asarray(at, jnp.int32),
                   c_items, fp8, block_q)
