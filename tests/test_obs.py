"""The program's tracing module, ``repro.obs``: spans (nesting, parents,
the ring), counters, compile records keyed by the jitted function's name,
and what the serving engine records through it. The fused step carries the
``ssv.*`` / ``nsa.*`` named scopes in its lowered op metadata."""
import os
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.config import ModelConfig, NSAConfig, ServeConfig, SSVConfig
from repro.core import draft as draft_lib
from repro.core import engine as engine_lib
from repro.models import model

NSA = NSAConfig(cmp_block=8, cmp_stride=4, sel_block=16, n_selected=4, window=32)
SSV = SSVConfig(tree_depth=2, tree_width=2)
PROMPTS = [np.arange(18) % 64, (np.arange(23) * 3) % 64]
STEP_CHILDREN = {"ssv.step.prepare", "ssv.step.launch", "ssv.step.sync",
                 "ssv.step.update"}
SCOPES = ("ssv.admit_reset", "ssv.draft", "ssv.draft.topk", "ssv.verify",
          "ssv.accept", "ssv.commit", "nsa.slc", "nsa.win", "nsa.cmp",
          "nsa.select")


@pytest.fixture(autouse=True)
def fresh_records():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(scope="module")
def pair():
    # a config name of its own: its jit caches start empty in any process
    tcfg = ModelConfig(name="obs-target", num_layers=2, d_model=64,
                       num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
                       max_seq_len=512, dtype="float32", attention="nsa",
                       nsa=NSA)
    dcfg = draft_lib.draft_config(tcfg, num_layers=1)
    tp = model.init(jax.random.PRNGKey(0), tcfg)
    dp = model.init(jax.random.PRNGKey(1), dcfg)
    return tp, tcfg, dp, dcfg


def _engine(pair, backend, temperature=0.0):
    tp, tcfg, dp, dcfg = pair
    serve = ServeConfig(max_new_tokens=6, temperature=temperature,
                        max_context=256, ssv=SSV, use_planner=False,
                        kv_backend=backend)
    return engine_lib.BatchedSSVEngine(tp, tcfg, dp, dcfg, serve)


def test_spans_nest_with_parent_indices():
    with obs.span("a", rows=2):
        with obs.span("b"):
            pass
        with obs.span("c", slot=1):
            with obs.span("d"):
                pass
    with obs.span("e"):
        pass
    spans = obs.snapshot()["spans"]
    assert [s.name for s in spans] == list("abcde")
    assert [s.index for s in spans] == [0, 1, 2, 3, 4]
    assert [s.parent for s in spans] == [-1, 0, 0, 2, -1]
    assert spans[0].attrs == {"rows": 2} and spans[2].attrs == {"slot": 1}
    a, b, c, d, _ = spans
    assert a.start_ns <= b.start_ns <= b.end_ns <= c.start_ns
    assert c.start_ns <= d.start_ns <= d.end_ns <= c.end_ns <= a.end_ns


def test_spans_on_other_threads_have_their_own_parents():
    done = threading.Event()

    def other():
        with obs.span("t.outer"):
            with obs.span("t.inner"):
                done.wait(5)

    with obs.span("main"):
        th = threading.Thread(target=other)
        th.start()
        with obs.span("main.child"):
            pass
        done.set()
        th.join()
    by_name = {s.name: s for s in obs.snapshot()["spans"]}
    assert by_name["main.child"].parent == by_name["main"].index
    assert by_name["t.outer"].parent == -1
    assert by_name["t.inner"].parent == by_name["t.outer"].index


def test_threads_lose_no_count_and_share_no_index():
    workers = (os.cpu_count() or 4) + 2
    each = min(200, obs.RING // workers)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(each):
                with obs.span("w"):
                    obs.count("n")
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert obs.counters()["n"] == workers * each
    spans = obs.snapshot()["spans"]
    assert sorted(s.index for s in spans) == list(range(workers * each))


def test_ring_drops_the_oldest(monkeypatch):
    monkeypatch.setattr(obs, "RING", 8)
    obs.reset()
    for i in range(20):
        with obs.span("s", i=i):
            pass
    spans = obs.snapshot()["spans"]
    assert [s.index for s in spans] == list(range(12, 20))
    assert [s.attrs["i"] for s in spans] == list(range(12, 20))


def test_counters():
    obs.count("x")
    obs.count("x", 4)
    obs.count("y", 2)
    got = obs.counters()
    assert got == {"x": 5, "y": 2}
    got["x"] = 0                       # a copy
    assert obs.counters()["x"] == 5
    assert obs.snapshot()["counters"] == {"x": 5, "y": 2}
    obs.reset()
    assert obs.counters() == {}


def test_compile_records_keyed_by_name():
    def obs_probe_program(x):
        return jnp.sin(x) * 2

    t0 = obs.now_ns()
    jax.jit(obs_probe_program)(jnp.ones(5)).block_until_ready()
    t1 = obs.now_ns()
    recs = [c for c in obs.snapshot()["compiles"] if c.name == "obs_probe_program"]
    assert {c.phase for c in recs} == {"trace", "lower", "compile"}
    assert all(t0 <= c.end_ns <= t1 and c.seconds >= 0 for c in recs)
    assert obs.counters()["compiles.obs_probe_program"] == 1


def test_cache_load_is_taken_out_of_its_compile():
    jax.monitoring.record_event_duration_secs(obs.CACHE_LOAD_EVENT, 0.25)
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 1.0,
        fun_name="jit(loaded_program)")
    recs = {c.phase: c.seconds for c in obs.snapshot()["compiles"]
            if c.name == "loaded_program"}
    assert recs == {"cache_load": 0.25, "compile": 0.75}
    assert obs.counters()["compiles.loaded_program"] == 1


def test_kernel_cache_stats_read_the_counters():
    obs.count("kernel.verify_call.lookups", 5)
    obs.count("kernel.verify_call.builds", 2)
    obs.count("kernel.group_layout.lookups", 3)
    stats = engine_lib.kernel_cache_stats()
    assert (stats["verify_call_hits"], stats["verify_call_misses"]) == (3, 2)
    assert (stats["group_layout_hits"], stats["group_layout_misses"]) == (3, 0)


def test_serving_records_named_programs_and_spans(pair):
    eng = _engine(pair, "paged")
    res = eng.serve_continuous(PROMPTS, num_slots=2)
    snap = obs.snapshot()
    names = {c.name for c in snap["compiles"]}
    assert "f" not in names
    assert {"ssv_batched_step", "ssv_prefill"} <= names
    c = snap["counters"]
    assert c["ssv.steps"] == res.steps
    assert c["ssv.admissions"] == 2
    assert c["ssv.prefill_tokens"] == sum(len(p) - 1 for p in PROMPTS)
    assert c["ssv.tokens_committed"] >= res.total_tokens
    assert c["compiles.ssv_batched_step"] == 1

    spans = snap["spans"]
    kids = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    steps = [s for s in spans if s.name == "ssv.step"]
    assert len(steps) == res.steps
    for s in steps:
        assert {k.name for k in kids[s.index]} == STEP_CHILDREN
        assert s.attrs["rows"] in (1, 2)
    admits = [s for s in spans if s.name == "ssv.admit"]
    assert sorted(s.attrs["prompt_len"] for s in admits) == sorted(map(len, PROMPTS))
    for s in admits:
        got = sorted((k.name, k.attrs.get("model", "")) for k in kids[s.index])
        assert got == [("ssv.kv.alloc", ""), ("ssv.kv.scatter", ""),
                       ("ssv.prefill", "draft"), ("ssv.prefill", "target")]
    serve_admits = [s for s in spans if s.name == "ssv.serve.admit"]
    assert sorted(s.attrs["req_id"] for s in serve_admits) == [0, 1]
    assert {s.attrs["req_id"] for s in spans if s.name == "ssv.serve.harvest"} == {0, 1}
    # a prefill compile falls inside the span of the admission that paid it,
    # which names the prompt length it was compiled for
    prefills = [s for s in spans if s.name == "ssv.prefill"]
    for comp in (c for c in snap["compiles"]
                 if c.name == "ssv_prefill" and c.phase == "compile"):
        owner = [s for s in prefills if s.start_ns <= comp.end_ns <= s.end_ns]
        assert len(owner) == 1
        assert spans[owner[0].parent].name == "ssv.admit"


def test_step_group_spans(pair):
    eng = _engine(pair, "dense")
    eng.start_empty(2)
    eng.admit(0, PROMPTS[0])
    eng.admit(1, PROMPTS[1])
    obs.reset()
    eng.step_group([1], SSV)
    spans = obs.snapshot()["spans"]
    (group,) = [s for s in spans if s.name == "ssv.step_group"]
    assert group.attrs == {"rows": 1}
    assert {s.name for s in spans if s.parent == group.index} == (
        STEP_CHILDREN | {"ssv.group.gather", "ssv.group.scatter"})
    assert obs.counters()["ssv.rows_stepped"] == 1


@pytest.mark.parametrize("backend,temperature", [
    ("dense", 0.0), ("paged", 0.0), ("dense", 1.0), ("paged", 1.0)])
def test_fused_step_carries_named_scopes(pair, backend, temperature):
    eng = _engine(pair, backend, temperature)
    eng.start_empty(2)
    fn = engine_lib.jit_batched_step(eng.tcfg, eng.dcfg, SSV, temperature == 0.0,
                                     temperature, eng.store)
    text = fn.lower(*eng._group_step_specs(SSV, 2)).as_text(debug_info=True)
    assert "ssv_batched_step" in text
    for scope in SCOPES:
        # a scope opens a name-stack entry: ".../ssv.verify/..." or, under
        # the row vmap, ".../vmap(ssv.verify)/..."
        assert re.search(rf'[/("]{re.escape(scope)}[/)]', text), scope
