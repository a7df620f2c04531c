"""BENCHMARK.json: every name resolves to its file, and the file keeps to
the benchmark's contract (keys, names, lengths, cross references)."""
import json
import re
from pathlib import Path

import pytest

from bench import harness, traffic

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


def test_top_level():
    assert set(BM) == TOP
    assert BM["paths"] == ["bench"] and BM["command"][1] == "bench/run.py"
    assert 1 <= BM["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_resolves(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
    cfg = harness.load_config(c["name"])
    assert c["file"] == f"bench/configs/{c['name']}.json" and cfg["name"] == c["name"]
    assert (ROOT / "bench" / "references" / f"{cfg['reference']}.py").exists()
    for k in c["reduced"]:
        assert not k.endswith(("_dim", "_rank", "_size")) and "head" not in k
    harness.model_config(cfg)
    assert set(cfg["check"]) <= {"logit_gap_max", "logit_gap_mean"} and cfg["check"]
    assert all(v > 0 for v in cfg["check"].values())


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    assert w["config"] in {c["name"] for c in BM["configs"]}
    mix = traffic.load_mix(w["traffic"])
    assert mix["loop"] in ("open", "closed")
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    reports = [m["name"] for m in BM["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
    assert "setup_s" in reports and len(reports) >= 2
    assert any(w["name"] in m.get("workloads", [w["name"]]) for m in BM["per_layer"])


@pytest.mark.parametrize("m", BM["end_to_end"] + BM["per_layer"], ids=lambda m: m["name"])
def test_metric_resolves(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert callable(harness.load_reader(m["name"]))
    cells = {w["name"] for w in BM["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    else:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        moved = next(e for e in BM["end_to_end"] if e["name"] == m["moves"])
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", cells)
        assert 1 <= len(m["layer"]) <= 200


def test_names_unique():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BM[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))
