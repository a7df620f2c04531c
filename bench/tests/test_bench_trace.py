"""The reduction from a profiler trace to busy time, idle gaps, device time
per op and per named scope (bench/trace.py)."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev():
    ms = 1_000_000
    return {
        "device": [(DEV, "fusion.1", 10 * ms, 30 * ms, None),
                   (DEV, "fusion.2", 25 * ms, 40 * ms, None),     # overlaps the first
                   (DEV, "convolution.3", 60 * ms, 90 * ms, None),
                   (DEV, "fusion.1", 95 * ms, 130 * ms, None)],    # runs past the window
        "host": [(HOST, "bench.traced_window", 0, 100 * ms),
                 (HOST, "bench.step", 5 * ms, 45 * ms),
                 (HOST, "bench.admit", 45 * ms, 62 * ms),
                 (HOST, "bench.step", 62 * ms, 99 * ms)],
    }


def test_busy_gaps_and_ops():
    out = trace.reduce_events(_ev())
    assert out["window_s"] == pytest.approx(0.100)
    # union: [10,40] + [60,90] + [95,100] = 65 ms
    assert out["busy_s"] == pytest.approx(0.065)
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.admit", pytest.approx(0.020)]        # 40..60
    assert gaps[1] == ["bench.step", pytest.approx(0.010)]         # 0..10
    assert [g[0] for g in gaps[2:]] == ["bench.step"]              # 90..95
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.025)                 # 20 + 5 in window
    assert ops["convolution.3"] == pytest.approx(0.030)
    assert list(ops)[0] == "convolution.3"


def test_gap_outside_every_span_is_host_other():
    ev = _ev()
    ev["host"] = [h for h in ev["host"] if h[1] == "bench.traced_window"]
    labels = {g[0] for g in trace.reduce_events(ev)["breakdown"]["idle_gaps"]}
    assert labels == {"host other"}


def test_host_spans_of_a_recorded_trace():
    """``trace.events_of`` on a trace recorded by ``jax.profiler`` on the CPU:
    one traced window holding three ``bench.step`` spans."""
    ev = trace.events_of(str(DATA / "cpu_host_spans.xplane.pb"))
    names = [n for _, n, _, _ in ev["host"]]
    assert names.count("bench.traced_window") == 1 and names.count("bench.step") == 3
    assert ev["device"] == []                         # no TPU plane on the CPU
    (w0, w1), = [(a, b) for _, n, a, b in ev["host"] if n == "bench.traced_window"]
    assert all(w0 <= a < b <= w1 for _, n, a, b in ev["host"] if n == "bench.step")


MS = 1_000_000
# the optimized HLO's op_name of each instruction the synthetic trace runs
HLO = """
HloModule jit_ssv_batched_step, is_scheduled=true
%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc.2, metadata={op_name="jit(ssv_batched_step)/ssv.verify/while/body/nsa.slc/gather" stack_frame_id=3}
  ROOT %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kOutput, calls=%fc.3, metadata={op_name="jit(ssv_batched_step)/ssv.verify/while/body/nsa.select/dot_general"}
}
ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %while.1 = (s32[], f32[8]{0}) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(ssv_batched_step)/ssv.verify/while" stack_frame_id=2}
  %sort.4 = f32[8]{0} sort(%x), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(ssv_batched_step)/vmap()/ssv.draft/ssv.draft.topk/sort"}
  %fusion.5 = f32[8]{0} fusion(%x), kind=kOutput, calls=%fc.5, metadata={op_name="jit(ssv_batched_step)/ssv.draft/dot_general"}
  %copy.6 = f32[8]{0} copy(%sort.4)
  %fusion.7 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fc.7, metadata={op_name="jit(ssv_batched_step)/transpose(jvp(ssv.commit))/scatter"}
  ROOT %fusion.8 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fc.8, metadata={op_name="jit(ssv_batched_step)/ssv.commit/add"}
}
"""


# each op event is named as the trace names it: the instruction's
# name and result shape, then its operands
HEADS = {n: h for n, (h, _) in trace.op_names(HLO)[1].items()}


def _scoped_ev(fingerprints=("11", "11", "11")):
    op = lambda name, a, b, path=None: (DEV, f"%{HEADS[name]} op(%x)", a * MS, b * MS, path)
    return {
        "device": [op("while.1", 10, 50),                 # spans its body's ops
                   op("fusion.2", 12, 20), op("fusion.3", 20, 30),
                   op("fusion.5", 52, 56), op("sort.4", 55, 70),   # overlap
                   op("copy.6", 70, 80),                  # no op_name: no scope
                   # the event's own op_name goes before the HLO's
                   op("fusion.8", 80, 85, "jit(ssv_batched_step)/ssv.accept/argmax"),
                   op("fusion.7", 95, 110),               # runs past the window
                   # another program's op of the same instruction name
                   op("fusion.2", 86, 90)],
        "modules": [(DEV, "jit_ssv_batched_step", fingerprints[0], 9 * MS, 50 * MS),
                    (DEV, "jit_ssv_batched_step", fingerprints[1], 51 * MS, 85 * MS),
                    (DEV, "jit_prefill", "12", 86 * MS, 90 * MS),
                    (DEV, "jit_ssv_batched_step", fingerprints[2], 94 * MS, 111 * MS)],
        "host": [(HOST, "bench.traced_window", 0, 100 * MS),
                 (HOST, "bench.step", -10 * MS, 40 * MS),   # 4/5 of it inside
                 (HOST, "bench.step", 45 * MS, 99 * MS)],
    }


def test_op_and_scope_seconds():
    out = trace.reduce_events(_scoped_ev(), hlo=trace.op_names(HLO))
    assert out["busy_s"] == pytest.approx(0.082)
    scope = {k: pytest.approx(v) for k, v in {
        "ssv.verify": 0.040, "nsa.slc": 0.008, "nsa.select": 0.010,
        "ssv.draft": 0.018, "ssv.draft.topk": 0.015,
        "ssv.accept": 0.005, "ssv.commit": 0.005}.items()}
    assert out["scope_s"] == scope
    ops = out["op_s"]
    assert len(ops) == 8 and sum(ops.values()) == pytest.approx(0.101)
    assert ops["%while.1 = (s32[], f32[8]{0}) op(%x)"] == pytest.approx(0.040)
    assert ops["%fusion.7 = f32[8]{0} op(%x)"] == pytest.approx(0.005)
    assert out["hlo_match"] == 1.0 and out["hlo_programs"] == 1
    assert out["steps"] == pytest.approx(1.8)
    assert trace.scope_ms_per_step(out, "ssv.verify") == pytest.approx(40 / 1.8)
    assert trace.scope_ms_per_step(out, "ssv.admit_reset") is None
    assert trace.scope_ms_per_step(None, "ssv.verify") is None
    # the breakdown keeps its format: the top ops, the longest gaps
    assert [len(x) for x in out["breakdown"]["device_ops"]] == [2] * 8


def test_scope_seconds_average_over_chips():
    ev = _scoped_ev()
    ev["device"] += [("/device:TPU:1",) + e[1:] for e in ev["device"]
                     if e[1].startswith("%while.1")]
    ev["modules"] += [("/device:TPU:1",) + m[1:] for m in ev["modules"]]
    out = trace.reduce_events(ev, hlo=trace.op_names(HLO))
    assert out["scope_s"]["ssv.verify"] == pytest.approx(0.040)
    assert out["scope_s"]["nsa.slc"] == pytest.approx(0.004)


def test_without_op_names_no_scope():
    out = trace.reduce_events(_scoped_ev())
    assert out["scope_s"] == {"ssv.accept": pytest.approx(0.005)}
    assert out["hlo_match"] is None


def test_hlo_of_another_program_gives_no_scope():
    """The text of a program that did not run (its instructions' shapes
    differ from the ops the trace ran) names no op's scope."""
    other = trace.op_names(HLO.replace("f32[8]", "f32[16]"))
    out = trace.reduce_events(_scoped_ev(), hlo=other)
    assert out["hlo_match"] == 0.0
    assert out["scope_s"] == {"ssv.accept": pytest.approx(0.005)}


def test_two_programs_of_one_name_give_no_scope():
    """Where two programs of the module's name ran in the stretch, the text
    cannot be told to be either: no op takes its scope from it."""
    out = trace.reduce_events(_scoped_ev(("11", "13", "11")),
                              hlo=trace.op_names(HLO))
    assert out["hlo_programs"] == 2
    assert out["scope_s"] == {"ssv.accept": pytest.approx(0.005)}


@pytest.mark.parametrize("text, want", [
    # an op event on a TPU v5e, and lines of optimized HLO text
    ("%fusion.7 = bf16[1024,1024]{0,1:T(8,128)(2,1)S(1)} fusion(bf16[1024,1024]"
     "{1,0:T(8,128)(2,1)S(1)} %copy-done), kind=kOutput, calls=%fused_computation.5",
     "fusion.7 = bf16[1024,1024]{0,1:T(8,128)(2,1)S(1)}"),
    ("%while = (s32[]{:T(128)}, bf16[4,8]{1,0:T(8,128)(2,1)}) while((s32[]{:T(128)}, "
     "bf16[4,8]{1,0:T(8,128)(2,1)}) %tuple.11), condition=%c, body=%b",
     "while = (s32[]{:T(128)}, bf16[4,8]{1,0:T(8,128)(2,1)})"),
    ('  ROOT %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kOutput, metadata={op_name="a/b"}',
     "fusion.3 = f32[8]{0}"),
    ("HloModule jit_f, is_scheduled=true", ""),
])
def test_head_of_an_instruction(text, want):
    assert trace.head(text) == want


@pytest.mark.parametrize("path, want", [
    ("jit(ssv_batched_step)/ssv.verify/while/body/closed_call/nsa.slc/gather",
     ("ssv.verify", "nsa.slc")),
    ("jit(f)/transpose(jvp(ssv.draft))/vmap()/ssv.draft.topk/sort",
     ("ssv.draft", "ssv.draft.topk")),
    ("jit(ssv_batched_step)/while/body/dot_general", ()),
    ("", ()),
])
def test_scopes_of_a_path(path, want):
    assert trace.scopes_of(path) == want


def test_op_names_of_a_compiled_program():
    """``op_names`` on the optimized HLO text of a program compiled here:
    the scopes reach the instructions, inside a loop body and a vmap."""
    import jax
    import jax.numpy as jnp

    def body(_, c):
        with jax.named_scope("nsa.slc"):
            return jnp.tanh(c @ c)

    @jax.jit
    def f(x):
        with jax.named_scope("ssv.draft"):
            with jax.named_scope("ssv.draft.topk"):
                x = jnp.sort(x, axis=-1)
        with jax.named_scope("ssv.verify"):
            x = jax.vmap(lambda r: r * 2.0)(x)
            return jax.lax.fori_loop(0, 3, body, x)

    x = jnp.ones((16, 16))
    module, names = trace.op_names(f.lower(x).compile().as_text())
    assert module == "jit_f"
    found = {s for _, p in names.values() for s in trace.scopes_of(p)}
    assert {"ssv.draft", "ssv.draft.topk", "ssv.verify", "nsa.slc"} <= found
