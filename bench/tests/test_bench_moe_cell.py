"""A tiny expert-layer cell built through ``harness.model_config`` runs one
paged fused step of ``BatchedSSVEngine`` on the CPU, and ``harness.step_hlo``
reads that step's optimized HLO, with its named scopes, without compiling
(and gives none where reading it compiled).
(No reference check: the benchmark's reference has no expert layer yet.)"""
import time

import jax
import numpy as np

from bench import harness, trace, weights
from bench.tests import tiny
from repro.config import ServeConfig
from repro.core import engine as engine_lib
from repro.models import model

SEED = 2 ** 31 + 1515


def test_moe_cell_steps_and_names_its_phases():
    cfg = tiny.moe_cfg()
    tcfg = harness.model_config(cfg)
    dcfg = harness.draft_model_config(cfg, tcfg)
    assert dcfg.moe is None and dcfg.block_pattern == ("attn",)
    serve = ServeConfig(max_batch=2, max_new_tokens=8, temperature=0.0,
                        max_context=320, ssv=harness.strategy(cfg),
                        use_planner=False, kv_backend="paged",
                        kv_page_size=cfg["nsa"]["sel_block"])
    key = jax.random.PRNGKey(0)
    tp = weights.make_params(jax.eval_shape(lambda: model.init(key, tcfg)), SEED, salt=1)
    dp = weights.make_params(jax.eval_shape(lambda: model.init(key, dcfg)), SEED, salt=2)
    ffn = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(tp)
           if "router" in jax.tree_util.keystr(path)]
    assert ffn and all(leaf.shape[-1] == cfg["moe"]["num_experts"] for leaf in ffn)

    eng = engine_lib.BatchedSSVEngine(tp, tcfg, dp, dcfg, serve)
    eng.start_empty(2)
    rng = np.random.default_rng(SEED)
    for slot, n in enumerate((100, 200)):
        eng.admit(slot, rng.integers(0, cfg["vocab_size"], n).astype(np.int32),
                  max_new_tokens=8)
    before = eng.committed_len.copy()
    toks, n_acc = eng.step(np.ones(2, bool))
    assert toks.shape[0] == 2 and (n_acc >= 0).all()
    assert (eng.committed_len == before + n_acc + 1).all()
    assert ((toks >= 0) & (toks < cfg["vocab_size"])).all()

    clock = harness.CompileClock(jax.monitoring, time.perf_counter)
    text = harness.step_hlo(eng, clock)
    assert clock.compile_times == []
    module, names = trace.op_names(text)
    assert module == "jit_ssv_batched_step"
    found = {s for _, p in names.values() for s in trace.scopes_of(p)}
    assert {"ssv.draft", "ssv.draft.topk", "ssv.verify", "ssv.commit",
            "nsa.slc"} <= found

    # a clock that saw a backend compile while the text was read
    clock.compiles_between = lambda t0, t1: 1
    assert harness.step_hlo(eng, clock) == ""
