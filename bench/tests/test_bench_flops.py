"""bench/flops.py against hand counts for the tiny configuration."""
from bench import flops
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
