"""The work of a configuration's expert layers in one fused step, from
shapes alone, so any implementation of the same work reads the same.

The step verifies ``slots x tree nodes`` tokens (every row of the batch,
as the fused step computes them). Per ``"moe"`` layer (``cfg["moe"]`` is
the program's ``MoEConfig`` by field; ``num_experts`` held here from a
router of ``router_experts``):

- FLOPs: the routed experts at this chip's share, ``tokens x top_k x
  num_experts / router_experts`` assignments of ``6 d d_expert`` each (three
  matrices, a multiply-add is 2 FLOPs), plus the router, ``2 d
  router_experts`` per token;
- bytes: each held expert's three matrices read once, in the configuration's
  dtype; the router's ``d x router_experts`` in float32, as the program and
  the reference keep it; and the tokens in and out, ``2 x tokens x d`` in
  the configuration's dtype.

``floor_s`` is the least time the chip could take for that work: the larger
of FLOPs over the bf16 peak and bytes over the HBM rate.
"""
from __future__ import annotations

from bench import flops

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def step_tokens(cfg: dict, mix: dict) -> int:
    return int(mix["slots"]) * flops._tree_nodes(cfg["strategy"])


def _moe_layers(cfg: dict) -> int:
    pattern = tuple(cfg.get("block_pattern", ("attn",)))
    L = cfg["num_hidden_layers"]
    return sum(1 for i in range(L) if pattern[i % len(pattern)] == "moe")


def _width(m: dict) -> int:
    return m.get("router_experts") or m["num_experts"]


def step_flops(cfg: dict, mix: dict) -> float:
    m, d = cfg["moe"], cfg["hidden_size"]
    n = step_tokens(cfg, mix)
    routed = n * m["top_k"] * m["num_experts"] / _width(m)
    per_layer = routed * 6 * d * m["d_expert"] + n * 2 * d * _width(m)
    return float(_moe_layers(cfg) * per_layer)


def step_bytes(cfg: dict, mix: dict) -> float:
    m, d = cfg["moe"], cfg["hidden_size"]
    b = DTYPE_BYTES[cfg["torch_dtype"]]
    experts = m["num_experts"] * 3 * d * m["d_expert"] * b
    router = d * _width(m) * 4
    tokens = 2 * step_tokens(cfg, mix) * d * b
    return float(_moe_layers(cfg) * (experts + router + tokens))


def floor_s(cfg: dict, mix: dict, peaks: dict) -> float:
    return max(step_flops(cfg, mix) / peaks["bf16_flops_per_s"],
               step_bytes(cfg, mix) / peaks["hbm_bytes_per_s"])
