"""A tiny cell for driving the harness on the CPU in the tests."""
import copy

CFG = {
    "name": "tiny-nsa", "reference": "nsa_decoder",
    "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "max_position_embeddings": 4096, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "hidden_act": "silu", "tie_word_embeddings": False,
    "qk_norm": True, "torch_dtype": "float32", "attention": "nsa",
    "nsa": {"cmp_block": 8, "cmp_stride": 4, "sel_block": 16, "n_selected": 4,
            "window": 32, "n_init_blocks": 1, "n_local_blocks": 2},
    "draft": {"num_hidden_layers": 1, "hidden_size": 32, "num_attention_heads": 2,
              "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 64,
              "attention": "dense"},
    "strategy": {"tree_depth": 2, "tree_width": 2, "traversal": "bfs",
                 "tree_budget": 0, "group_size": 2, "group_mode": "exact",
                 "refresh_schedule": [], "precision_class": "Strict"},
    "check": {"logit_gap_max": 1e-3, "logit_gap_mean": 1e-4},
}
CLOSED = {"loop": "closed", "slots": 2, "max_context": 320,
          "prompt": {"buckets": [100, 200]}, "output": {"fixed": 40},
          "block": 2, "requests": 6, "check": {"requests": 2}}
OPEN = {"loop": "open", "slots": 2, "max_context": 320, "rate_per_s": 4.0,
        "lead_s": 0.5, "drain_s": 30.0,
        "prompt": {"lognormal": {"median": 90, "sigma": 0.5}, "clip": [40, 200],
                   "buckets": [64, 128, 256]},
        "output": {"lognormal": {"median": 6, "sigma": 0.5}, "clip": [2, 12]},
        "block": 8, "requests": 64, "check": {"requests": 4}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def cfg(**kw):
    c = copy.deepcopy(CFG)
    c.update(kw)
    return c


def moe_cfg(**moe):
    """The tiny configuration with every layer an expert layer: 4 experts
    held, top-2, experts half the dense FFN's width."""
    group = {"num_experts": 4, "top_k": 2, "d_expert": 64}
    group.update(moe)
    return cfg(name="tiny-nsa-moe", block_pattern=["moe"], moe=group)
