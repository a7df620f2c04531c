"""The reduction from a profiler trace to busy time, idle gaps and top
device ops (bench/trace.py)."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev():
    ms = 1_000_000
    return {
        "device": [(DEV, "fusion.1", 10 * ms, 30 * ms),
                   (DEV, "fusion.2", 25 * ms, 40 * ms),     # overlaps the first
                   (DEV, "convolution.3", 60 * ms, 90 * ms),
                   (DEV, "fusion.1", 95 * ms, 130 * ms)],    # runs past the window
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
