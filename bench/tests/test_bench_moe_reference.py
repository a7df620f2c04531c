"""An expert-layer cell whose layers hold a share of a wider router: the
file maps through ``harness.model_config``; served by the engine on the CPU
it agrees with the plain reference ``nsa_moe_decoder`` within the tiny
cell's limits, and the float8 control fails them; the expert layer's
readers read a synthetic trace; ``expert_work`` matches hand counts."""
import json
from pathlib import Path

import pytest

from bench import expert_work, harness
from bench import run as bench_run
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = 2 ** 31 + 1616
MIX = {"slots": 2}


def share_cfg():
    """The tiny cell's layers holding experts 4-7 of a 16-wide router, top-4."""
    c = tiny.moe_cfg(num_experts=4, router_experts=16, first_expert=4, top_k=4)
    c["reference"] = "nsa_moe_decoder"
    return c


def test_router_share_maps_to_the_program():
    got = harness.model_config(share_cfg()).moe
    assert (got.num_experts, got.router_experts, got.first_expert, got.top_k) == (4, 16, 4, 4)
    assert got.router_width == 16


def test_served_cell_agrees_with_the_reference_and_the_control_fails():
    cfg = share_cfg()
    out = bench_run.run_cell({"name": "q3-235b.decode4k"}, cfg, tiny.CLOSED,
                             BM["end_to_end"], [], SEED, 2.0, False, tiny.PEAKS,
                             control=True)
    assert out["correct"] is True
    assert out["checks"]["tokens_compared"]["value"] > 0
    for name, limit in cfg["check"].items():
        assert out["readings"][name] <= limit < out["control"][name]


def test_expert_layer_readers_on_a_synthetic_trace():
    cfg = harness.load_config("qwen3-moe-235b-a22b-nsa")
    mix = {"slots": 8}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = harness.RunRecord(cfg=cfg, mix=mix, peaks=peaks, window=(0.0, 1.0),
                            steps=[], requests=[], origin=0.0, compiles_in_window=0)
    device_ms = harness.load_reader("moe_device_ms")
    roofline = harness.load_reader("moe_roofline")
    # a program without the scope (the parent's) reads nothing
    assert device_ms(run) is None and roofline(run) is None
    run.trace = {"scope_s": {"ssv.verify": 0.5}, "steps": 10.0}
    assert device_ms(run) is None and roofline(run) is None
    # 60 ms of moe.ffn over 10 steps: 6 ms a step, against a 3.01 ms floor
    run.trace = {"scope_s": {"ssv.verify": 0.5, "moe.ffn": 0.06}, "steps": 10.0}
    assert device_ms(run) == pytest.approx(6.0)
    floor_ms = 2465202176 / 819e9 * 1e3
    assert roofline(run) == pytest.approx(100 * floor_ms / 6.0)


def test_expert_work_hand_count_tiny():
    c = tiny.moe_cfg(num_experts=4, router_experts=16, top_k=2)
    # 2 rows x 7 tree nodes = 14 tokens; 14 x 2 x 4/16 = 7 assignments here
    assert expert_work.step_tokens(c, MIX) == 14
    per_layer = 7 * 6 * 64 * 64 + 14 * 2 * 64 * 16
    assert expert_work.step_flops(c, MIX) == 2 * per_layer == 401408
    # float32: experts 4 x 3 x 64 x 64, router 64 x 16, tokens in and out
    per_layer = 4 * 3 * 64 * 64 * 4 + 64 * 16 * 4 + 2 * 14 * 64 * 4
    assert expert_work.step_bytes(c, MIX) == 2 * per_layer == 415744
    # no moe layer counts nothing
    mixed = c | {"block_pattern": ["attn", "moe"]}
    assert expert_work.step_bytes(mixed, MIX) == per_layer


def test_expert_work_hand_count_of_the_cell():
    c = harness.load_config("qwen3-moe-235b-a22b-nsa")
    mix = json.loads((ROOT / "bench" / "traffic" / "decode4k.json").read_text())
    # 8 rows x 31 nodes = 248 tokens; 248 x 8 x 8/128 = 124 assignments
    assert expert_work.step_tokens(c, mix) == 248
    experts = 8 * 3 * 4096 * 1536 * 2          # bf16, read once a layer
    router = 4096 * 128 * 4                    # float32
    tokens = 2 * 248 * 4096 * 2
    assert expert_work.step_bytes(c, mix) == 8 * (experts + router + tokens) == 2465202176
    flop = 124 * 6 * 4096 * 1536 + 248 * 2 * 4096 * 128
    assert expert_work.step_flops(c, mix) == 8 * flop == 39527120896
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # memory bound: 2.47 GB at 819 GB/s
    assert expert_work.floor_s(c, mix, peaks) == pytest.approx(2465202176 / 819e9)
