"""Required FLOPs, worked out from a configuration's shapes alone.

What a step or an admission must compute, not what the implementation
happens to run: padding rows, gathers, the draft's re-expansion of the
whole tree at every depth and the masked-dense prefill attention are not
counted, so a change of implementation leaves this yardstick unchanged.
A multiply-add counts as 2 FLOPs.

``cfg`` is the dict of a configuration file (``bench/configs/*.json``);
its ``draft`` group describes the draft model, which is dense.

Layers are counted by kind, ``block_pattern`` tiled over the layers as the
program tiles it (all ``"attn"`` where the file names none):

- ``"attn"``: attention projections, NSA gates and the gated FFN of width
  ``intermediate_size``;
- ``"moe"``: the same attention and gates; the router, ``d x
  router_experts``; the routed experts at this chip's share, ``top_k x
  num_experts / router_experts`` experts per token, each ``3 d d_expert``
  (``d_expert`` 0 is ``intermediate_size``); and, where
  ``num_shared_experts`` is set, one shared FFN of width
  ``intermediate_size``, counted once, as the program runs it.
  ``num_experts`` is the experts this chip holds and ``router_experts`` the
  router's width (``num_experts`` where absent).

Every FFN is gated (three matrices): the harness admits no other
activation (``harness.ACTIVATIONS``). Any other kind is an error.
"""
from __future__ import annotations


def _attn_params(c: dict, *, nsa: bool) -> int:
    """Weights of one layer's attention that every token multiplies:
    the projections and the NSA gates."""
    d, hq, hkv, dh = (c["hidden_size"], c["num_attention_heads"],
                      c["num_key_value_heads"], c["head_dim"])
    attn = d * hq * dh + 2 * d * hkv * dh + hq * dh * d
    gates = d * 3 * hq if nsa else 0
    return attn + gates


def _expert_params(c: dict):
    """Weights of one ``"moe"`` layer's FFN that a token multiplies."""
    m, d = c["moe"], c["hidden_size"]
    held = m["num_experts"]
    share = m["top_k"] * held / m.get("router_experts", held)
    per_expert = 3 * d * (m.get("d_expert") or c["intermediate_size"])
    shared = 3 * d * c["intermediate_size"] if m.get("num_shared_experts") else 0
    return d * m.get("router_experts", held) + share * per_expert + shared


def _matmul_params(c: dict, *, nsa: bool):
    """Weights that a token multiplies in all of the layers."""
    pattern = tuple(c.get("block_pattern", ("attn",)))
    L = c["num_hidden_layers"]
    kinds = (pattern * -(-L // len(pattern)))[:L]
    total = 0
    for kind in kinds:
        if kind == "attn":
            total += _attn_params(c, nsa=nsa) + 3 * c["hidden_size"] * c["intermediate_size"]
        elif kind == "moe":
            total += _attn_params(c, nsa=nsa) + _expert_params(c)
        else:
            raise ValueError(f"flops.py counts no layer of kind {kind!r}")
    return total


def nsa_keys(nsa: dict, base: int, pos: int) -> int:
    """Keys one query at ``pos`` attends under NSA when the sparse branches
    see the ``base`` tokens before it: visible compressed blocks, the
    selected tokens and the sliding window."""
    l, d = nsa["cmp_block"], nsa["cmp_stride"]
    cmp = 0 if base < l else (base - l) // d + 1
    slc = min(nsa["n_selected"] * nsa["sel_block"], base)
    win = min(nsa["window"], pos + 1)
    return cmp + slc + win


def _attn_flops(c: dict, keys: int) -> int:
    # scores and the weighted sum: 2 matmuls of (heads x head_dim) by keys
    return 4 * c["num_attention_heads"] * c["head_dim"] * keys


def _tree_nodes(strategy: dict) -> int:
    n, level = 1, 1
    for _ in range(strategy["tree_depth"]):
        level *= strategy["tree_width"]
        n += level
    if strategy.get("tree_budget"):
        n = min(n, strategy["tree_budget"] + 1)
    return n


def _tree_depths(strategy: dict) -> list:
    depths, level = [0], 1
    for dep in range(1, strategy["tree_depth"] + 1):
        level *= strategy["tree_width"]
        depths += [dep] * level
    return depths[:_tree_nodes(strategy)]


def step_flops(cfg: dict, contexts) -> float:
    """One fused speculative step over the active rows, whose committed
    lengths are ``contexts``: the target's forward over every tree node
    (NSA attention over the prefix's compressed blocks, selected tokens and
    window), its head on every node, and the draft's forward over every
    node once (dense attention over its whole context)."""
    dr = cfg["draft"]
    depths = _tree_depths(cfg["strategy"])
    T = len(depths)
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    per_node = 2 * (_matmul_params(cfg, nsa=True) + cfg["hidden_size"] * V)
    d_node = 2 * (_matmul_params(dr, nsa=False) + dr["hidden_size"] * V)
    total = 0
    for ctx in contexts:
        ctx = int(ctx)
        total += T * (per_node + d_node)
        for dep in depths:
            total += L * _attn_flops(cfg, nsa_keys(cfg["nsa"], ctx, ctx + dep))
            total += dr["num_hidden_layers"] * _attn_flops(dr, ctx + dep + 1)
    return float(total)

