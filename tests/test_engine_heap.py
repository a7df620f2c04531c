"""The batched engine freezes the host heap after a step that followed a
compile, so a later step's device wait holds no full collection of the
objects tracing left; a step that compiled nothing freezes nothing."""
import gc

import jax
import numpy as np

from repro import obs
from repro.config import ModelConfig, ServeConfig, SSVConfig
from repro.core import draft as draft_lib
from repro.core import engine as engine_lib
from repro.models import model


def test_heap_frozen_after_a_compiling_step_only():
    cfg = ModelConfig(name="heap", num_layers=1, d_model=32, num_heads=2,
                      num_kv_heads=2, d_ff=64, vocab_size=64, max_seq_len=256,
                      dtype="float32")
    dcfg = draft_lib.draft_config(cfg, num_layers=1)
    eng = engine_lib.BatchedSSVEngine(
        model.init(jax.random.PRNGKey(0), cfg), cfg,
        model.init(jax.random.PRNGKey(1), dcfg), dcfg,
        ServeConfig(max_new_tokens=8, temperature=0.0, max_context=128,
                    ssv=SSVConfig(tree_depth=2, tree_width=2),
                    use_planner=False))
    eng.start([np.arange(12) % 64, np.arange(9) % 64])
    live = np.ones((2,), bool)
    obs.reset()
    gc.unfreeze()
    eng.step(live)                      # compiles the fused step
    assert obs.counters().get("compiles.ssv_batched_step", 0) >= 1
    assert gc.get_freeze_count() > 0
    gc.unfreeze()
    eng.step(live)                      # the same program: no compile
    assert gc.get_freeze_count() == 0
