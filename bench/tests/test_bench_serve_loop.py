"""The benchmark's serving loop end to end on the CPU: a tiny cell through
``run_cell``, called as a function (the look for a chip is in ``main``)."""
import json
from pathlib import Path

import pytest

from bench import run as bench_run
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = 2 ** 31 + 99


def drive(mix, cell, trace=False, **kw):
    return bench_run.run_cell({"name": cell}, tiny.cfg(), mix, BM["end_to_end"],
                              BM["per_layer"], SEED, 2.0, trace, tiny.PEAKS, **kw)


def test_closed_loop_cell():
    out = drive(tiny.CLOSED, "nsa1b.decode16k")
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    for name, limit in tiny.CFG["check"].items():
        assert out["checks"][name] == {"value": pytest.approx(out["checks"][name]["value"]),
                                       "limit": limit}
        assert out["checks"][name]["value"] <= limit
    assert out["checks"]["requests_compared"]["value"] == 2
    assert set(out["metrics"]) == {"output_tok_s", "tpot_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] == 6 and out["failed"] == 0


def test_open_loop_cell_traced(tmp_path):
    # the general loop's open-loop path (Poisson arrivals, admissions inside
    # the window, the drain), read with a serving cell's per-layer metrics
    out = drive(tiny.OPEN, "nsa1b.decode16k", trace=True, trace_dir=tmp_path / "t")
    assert out["correct"] is True
    m = out["metrics"]
    # the CPU trace has no TPU plane, so the device's idle share and the
    # phases' device time are left out rather than read as 0
    assert {"window_mfu", "step_mfu", "accept_len", "compiles_in_window",
            "step_host_ms", "slow_steps", "setup_compile_s"} == set(m)
    assert "device_idle_share" not in m and "busy_s" not in out["device"]
    assert m["compiles_in_window"]["value"] == 0
    assert 0 < m["step_mfu"]["value"] and 0 < m["window_mfu"]["value"]
    assert m["accept_len"]["value"] >= 1
    assert out["attempted"] >= 4 and out["failed"] == 0


def test_reference_bounds_follow_the_engine(monkeypatch):
    """The sparse bound check.py gives each served token, worked out from
    the prompt's length and the emissions alone, is the committed length
    the engine held before the step that produced the token."""
    from bench import check, harness
    seen = {}
    step = harness.ServeLoop.step

    def watched(self, active):
        held = [(self.sched.request_at(int(s)).req_id, int(self.eng.committed_len[s]))
                for s in active.nonzero()[0]]
        step(self, active)
        for rid, ctx in held:
            seen.setdefault(rid, []).extend([ctx] * self.by_id[rid].emissions[-1][1])

    monkeypatch.setattr(harness.ServeLoop, "step", watched)
    captured = []
    judge = check.judge

    def keep(reqs, *a, **kw):
        captured.extend(reqs)
        return judge(reqs, *a, **kw)

    monkeypatch.setattr(check, "judge", keep)
    drive(tiny.CLOSED, "nsa1b.decode16k")
    served = [r for r in captured if r.tokens]
    assert served
    for r in served:
        _, bounds, at, toks = check.sequence_of(r)
        assert bounds[at[:len(toks)]].tolist() == seen[r.req_id]
