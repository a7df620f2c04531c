"""bench/flops.py against hand counts for the tiny configuration, and
pinned for the benchmark's own files."""
import pytest

from bench import flops, harness
from bench.tests import tiny


def test_step_flops_hand_count():
    # target per layer: q,k,v,o 4096+2048+2048+4096, gates 64*12, FFN 3*64*128
    per_layer = 12288 + 768 + 24576
    node_t = 2 * (2 * per_layer + 64 * 512)          # 2 layers + head
    node_d = 2 * (1 * (4096 + 6144) + 32 * 512)      # 1 draft layer + head
    # 7 nodes at depths 0,1,1,2,2,2,2 on a 100-token prefix: NSA keys
    # 24 compressed + 64 selected + 32 window = 120 per node per layer
    attn_t = 7 * 2 * (4 * 4 * 16 * 120)
    # the draft attends its whole context densely: 101 + depth keys
    attn_d = 4 * 2 * 16 * (7 * 101 + 10)
    want = 7 * (node_t + node_d) + attn_t + attn_d
    assert want == 2407040
    assert flops.step_flops(tiny.CFG, [100]) == want
    assert flops.step_flops(tiny.CFG, [100, 100]) == 2 * want


def test_nsa_keys_saturate():
    nsa = tiny.CFG["nsa"]
    assert flops.nsa_keys(nsa, 0, 0) == 1
    # long prefix: all branches at their caps but the compressed blocks
    assert flops.nsa_keys(nsa, 1000, 1003) == (1000 - 8) // 4 + 1 + 64 + 32


@pytest.mark.parametrize("name, contexts, want", [
    ("ssv-nsa-1b", [12288, 16384, 14000, 18000], 262340624384.0),
    ("qwen3-8b-nsa", [2048, 4096, 3000, 5000], 613824495616.0),
    ("qwen3-8b-nsa", [0], 145441038336.0),
])
def test_step_flops_of_the_benchmark_files_pinned(name, contexts, want):
    """The counts window_mfu and step_mfu were read from before layers were
    counted by kind."""
    assert flops.step_flops(harness.load_config(name), contexts) == want


def test_moe_layer_hand_count():
    # layer 0 "attn" as in the dense hand count; layer 1 "moe" with 2 of 8
    # experts held, top-2: 2 * 2 / 8 = 0.5 experts per token at 3*64*32,
    # the router 64 x 8 and one shared FFN of intermediate_size 128.
    # router_experts is not a field of the program's MoEConfig yet, so
    # harness.model_config refuses this file until it is; the count is the
    # one a file that states it will get
    c = tiny.cfg(block_pattern=["attn", "moe"],
                 moe={"num_experts": 2, "router_experts": 8, "top_k": 2,
                      "d_expert": 32, "num_shared_experts": 1})
    attn = 12288 + 768
    dense = attn + 24576
    moe = attn + 64 * 8 + 0.5 * 3 * 64 * 32 + 3 * 64 * 128
    node_t = 2 * (dense + moe + 64 * 512)
    node_d = 2 * (1 * (4096 + 6144) + 32 * 512)
    attn_t = 7 * 2 * (4 * 4 * 16 * 120)
    attn_d = 4 * 2 * 16 * (7 * 101 + 10)
    want = 7 * (node_t + node_d) + attn_t + attn_d
    assert want == 2457216
    assert flops.step_flops(c, [100]) == want
    # the router's width defaults to the experts held, so both are at full
    # share; no shared FFN; a file the harness maps (no router_experts yet)
    c["moe"] = {"num_experts": 2, "top_k": 2, "d_expert": 32}
    harness.model_config(c)
    moe = attn + 64 * 2 + 2 * 3 * 64 * 32
    node_t = 2 * (dense + moe + 64 * 512)
    assert flops.step_flops(c, [100]) == 7 * (node_t + node_d) + attn_t + attn_d


def test_unknown_layer_kind_raises():
    with pytest.raises(ValueError, match="mamba"):
        flops.step_flops(tiny.cfg(block_pattern=["attn", "mamba"]), [100])
