"""The per-layer metrics that read the program's own records
(``bench/program_trace.py`` over ``repro.obs``): a tiny traced cell on the
CPU reports them as finite numbers, the clock offset derived from the
matched steps is the true one, and each reader returns None, not an error,
where the program keeps no such records."""
import json
import math
from pathlib import Path

import pytest

from bench import harness, program_trace
from bench import run as bench_run
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = 2 ** 31 + 7
NEW = ("step_host_ms", "slow_steps", "setup_compile_s")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of the tiny closed-loop cell, with the offset each
    call of ``program_trace.steps`` derived."""
    offsets = []
    steps = program_trace.steps

    def watched(run, snap=None):
        got = steps(run, snap)
        offsets.append(None if got is None else got[0])
        return got

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(program_trace, "steps", watched)
        out = bench_run.run_cell({"name": "q3-8b.decode4k"}, tiny.cfg(), tiny.CLOSED,
                                 BM["end_to_end"], BM["per_layer"], SEED, 2.0, True,
                                 tiny.PEAKS, trace_dir=tmp_path_factory.mktemp("t"))
    return out, offsets


def test_readers_report_finite_values(traced):
    out, _ = traced
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(m)
    assert all(math.isfinite(m[k]) for k in NEW)
    assert m["step_host_ms"] > 0 and m["setup_compile_s"] > 0
    assert m["slow_steps"] == int(m["slow_steps"]) >= 0


def test_clock_offset_matches(traced):
    # the run's clock is perf_counter() - T_START, the program's
    # perf_counter_ns(): the true offset is -T_START
    _, offsets = traced
    assert offsets and all(o is not None for o in offsets)
    for o in offsets:
        assert abs(o + bench_run.T_START) < 1e-3


def _run(n_steps, t=0.0):
    steps = [harness.StepRecord(t + i, t + i + 0.5, [100], [1]) for i in range(n_steps)]
    return harness.RunRecord(cfg={}, mix={}, peaks={}, window=(t, t + n_steps),
                             steps=steps, requests=[], origin=0.0, compiles_in_window=0)


@pytest.mark.parametrize("name", NEW)
def test_reader_without_records_returns_none(name, monkeypatch):
    read = harness.load_reader(name)
    monkeypatch.setattr(program_trace, "snapshot", lambda: None)
    assert read(_run(3)) is None
    # more steps than the program recorded: the pairs cannot be matched
    monkeypatch.setattr(program_trace, "snapshot",
                        lambda: {"spans": [], "counters": {}, "compiles": []})
    assert read(_run(3)) is None


def test_misaligned_steps_return_none():
    from repro import obs
    spans = [obs.Span("ssv.step", int(i * 1e9), int((i + 0.1) * 1e9), -1, {}, i)
             for i in range(3)]
    snap = {"spans": spans, "counters": {}, "compiles": []}
    assert program_trace.steps(_run(3), snap) is not None
    # the program's steps 10x apart cannot sit inside records 1 s apart
    far = [s._replace(start_ns=s.start_ns * 10, end_ns=s.start_ns * 10 + 10 ** 8)
           for s in spans]
    assert program_trace.steps(_run(3), dict(snap, spans=far)) is None
