"""``harness.model_config``: the program's ModelConfig from a configuration
file. The files in the benchmark map exactly as before layer kinds and
experts could be declared; a file with a ``moe`` group and a ``"moe"``
kind gets expert layers; inconsistent or unknown expert keys raise."""
import pytest

from bench import harness
from bench.tests import tiny
from repro.config import ModelConfig, MoEConfig, NSAConfig

NSA = NSAConfig(cmp_block=32, cmp_stride=16, sel_block=64, n_selected=16,
                window=512, n_init_blocks=1, n_local_blocks=2)
# what the harness built for each file before block_pattern and moe existed
BUILT_BEFORE = {
    "ssv-nsa-1b": ModelConfig(
        name="ssv-nsa-1b", num_layers=16, d_model=2048, num_heads=32,
        num_kv_heads=8, d_ff=6144, vocab_size=32768, head_dim=64,
        max_seq_len=65536, attention="nsa", qk_norm=False, rope_theta=10000.0,
        activation="swiglu", nsa=NSA, tie_embeddings=False, dtype="bfloat16",
        norm_eps=1e-06),
    "qwen3-8b-nsa": ModelConfig(
        name="qwen3-8b-nsa", num_layers=8, d_model=4096, num_heads=32,
        num_kv_heads=8, d_ff=12288, vocab_size=151936, head_dim=128,
        max_seq_len=40960, attention="nsa", qk_norm=True, rope_theta=1000000.0,
        activation="swiglu", nsa=NSA, tie_embeddings=False, dtype="bfloat16",
        norm_eps=1e-06),
}


@pytest.mark.parametrize("name", sorted(BUILT_BEFORE))
def test_benchmark_files_map_as_before(name):
    got = harness.model_config(harness.load_config(name))
    assert got == BUILT_BEFORE[name]
    assert got.block_pattern == ("attn",) and got.moe is None


def test_moe_file_maps_to_expert_layers():
    c = tiny.moe_cfg(capacity_factor=2.0)
    got = harness.model_config(c)
    assert got.block_pattern == ("moe",)
    assert got.moe == MoEConfig(**c["moe"])
    assert got.layer_kinds() == ("moe", "moe")
    mixed = harness.model_config(tiny.moe_cfg() | {"block_pattern": ["attn", "moe"]})
    assert mixed.layer_kinds() == ("attn", "moe")


@pytest.mark.parametrize("change, match", [
    ({"moe": None}, "go together"),                          # a moe kind, no group
    ({"block_pattern": ["attn"]}, "go together"),            # a group, no moe kind
    ({"moe": {"num_experts": 4, "top_k": 2, "expert_width": 64}}, "not fields"),
    ({"block_pattern": ["moe", "mamba"]}, "kinds must be"),
    ({"block_pattern": []}, "kinds must be"),
], ids=["kind-without-group", "group-without-kind", "unknown-group-key",
        "unknown-kind", "empty-pattern"])
def test_inconsistent_expert_keys_raise(change, match):
    c = tiny.moe_cfg()
    c.update(change)
    if c["moe"] is None:
        del c["moe"]
    with pytest.raises(ValueError, match=match):
        harness.model_config(c)


def test_source_expert_keys_without_expert_layers_raise():
    with pytest.raises(ValueError, match="dense FFN"):
        harness.model_config(tiny.cfg(num_experts=128, num_experts_per_tok=8))
    # beside a moe group they are a record of the source, as published
    harness.model_config(tiny.moe_cfg() | {"num_experts": 128})
