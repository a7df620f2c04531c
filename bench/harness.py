"""The benchmark's harness: resolve a cell by name, build the system under
test from the cell's data files, drive it for a measured window, and
assemble the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in its own file, found by the name in ``BENCHMARK.json``:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/metrics/<metric>.py`` and ``bench/references/<reference>.py``.

The serving loop makes the calls ``BatchedSSVEngine.serve_continuous`` makes, in
the same order, on the host clock: ``Scheduler.admit(now)`` with ``now`` in
seconds since the schedule began, ``BatchedSSVEngine.admit`` for each
placed request, one fused ``step`` over the decoding rows, and the same
finish rule (token budget, or the context bound less one step's headroom).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import time
from pathlib import Path
from typing import List, Optional

import numpy as np

from bench import traffic

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# configuration-file keys -> the served program's ModelConfig fields
MODEL_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "qk_norm": "qk_norm",
    "torch_dtype": "dtype", "attention": "attention",
}
ACTIVATIONS = {"silu": "swiglu", "gelu": "geglu"}


# ---------------------------------------------------------------- lookup
def load_benchmark(root: Path = CHECKOUT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bm['workloads']]}")


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def load_reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


# ------------------------------------------------------- system under test
def model_config(c: dict, *, draft: bool = False):
    """The program's ModelConfig for a configuration file (or its draft)."""
    from repro.config import ModelConfig, NSAConfig
    kw = {MODEL_FIELDS[k]: v for k, v in c.items() if k in MODEL_FIELDS}
    kw["activation"] = ACTIVATIONS[c.get("hidden_act", "silu")]
    kw["nsa"] = NSAConfig(**c["nsa"])
    kw["name"] = c["name"] + ("-draft" if draft else "")
    return ModelConfig(**kw)


def draft_model_config(c: dict, tcfg):
    """The draft as the program builds it, held to the sizes the file
    states, so a change of the program's default draft cannot move a
    cell."""
    from repro.core import draft as draft_lib
    d = c["draft"]
    dcfg = draft_lib.draft_config(tcfg, num_layers=d["num_hidden_layers"],
                                  d_model=d["hidden_size"])
    got = {"num_attention_heads": dcfg.num_heads,
           "num_key_value_heads": dcfg.num_kv_heads, "head_dim": dcfg.head_dim,
           "intermediate_size": dcfg.d_ff, "attention": dcfg.attention}
    for k, v in got.items():
        if d[k] != v:
            raise ValueError(f"draft {k}: the program builds {v}, "
                             f"{c['name']}.json states {d[k]}")
    return dcfg


def strategy(c: dict):
    from repro.config import SSVConfig
    s = dict(c["strategy"])
    s["refresh_schedule"] = tuple(s["refresh_schedule"])
    return SSVConfig(**s)


# ------------------------------------------------------------ clocks
class CompileClock:
    """The host time of every backend compile, from jax.monitoring (as
    ``chip_smoke.py``'s clock listens for it)."""

    def __init__(self, monitoring, now):
        self.now = now
        self.compile_times: List[float] = []
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.compile_times.append(self.now())

    def compiles_between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.compile_times if t0 <= t <= t1)


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    contexts: List[int]        # committed length of each active row
    committed: List[int]       # n_accepted + 1 of each active row


# ------------------------------------------------------------ the serving loop
class ServeLoop:
    """Serves a request list through the engine on the host clock."""

    def __init__(self, eng, reqs, mix: dict, now):
        from repro.core import engine as engine_lib
        from repro.core import schedule
        import jax
        self.jax = jax
        self.eng = eng
        self.mix = mix
        self.now = now
        self.by_id = {r.req_id: r for r in reqs}
        self.reqs = reqs
        self.slots = int(mix["slots"])
        self.max_context = int(mix["max_context"])
        self.headroom = engine_lib.step_headroom(eng.serve, None)
        self.sched = schedule.Scheduler(
            self.slots,
            pages_for=lambda r: eng.pages_for(len(r.prompt), r.max_new_tokens),
            free_pages=lambda: eng.allocator.free_count)
        for r in reqs:
            self.sched.submit(schedule.Request(
                req_id=r.req_id, prompt=r.prompt,
                max_new_tokens=r.max_new_tokens, arrival=r.due))
        self.origin = 0.0
        self.steps: List[StepRecord] = []
        self.profile = None          # (start, stop) host times, traced run

    def span(self, name: str):
        """A host span on the profiler's clock (read by bench/trace.py)."""
        return self.jax.profiler.TraceAnnotation(name)

    def admit_arrived(self):
        now = self.now()
        for slot, r in self.sched.admit(now - self.origin):
            br = self.by_id[r.req_id]
            br.admit_start = self.now()
            with self.span("bench.admit"):
                self.eng.admit(slot, br.prompt, max_new_tokens=br.max_new_tokens)
            self.sched.mark_decoding(slot)

    def step(self, active: np.ndarray):
        eng = self.eng
        rows = np.nonzero(active)[0]
        ctx = eng.committed_len.copy()
        t0 = self.now()
        with self.span("bench.step"):
            toks, n_acc = eng.step(active)
        t1 = self.now()
        committed = []
        with self.span("bench.harvest"):
            for slot in rows:
                slot = int(slot)
                br = self.by_id[self.sched.request_at(slot).req_id]
                k = int(n_acc[slot]) + 1
                committed.append(k)
                take = min(k, br.max_new_tokens - len(br.tokens))
                br.tokens.extend(int(t) for t in toks[slot, :take])
                br.emissions.append((t1, take))
                if (len(br.tokens) >= br.max_new_tokens or
                        eng.committed_len[slot] + self.headroom >= self.max_context):
                    br.done = True
                    self.sched.finish(slot, now=t1 - self.origin)
                    eng._free_slot_pages(slot)        # as serve_continuous does
                    self.sched.release(slot)
        self.steps.append(StepRecord(t0, t1, [int(ctx[s]) for s in rows],
                                     committed))

    def serve(self, until: float, done=None, profile=None):
        """Admit and step until host time ``until`` or ``done()``. With
        ``profile`` = (start, stop, dir), the profiler traces that stretch."""
        while True:
            now = self.now()
            if profile is not None:
                self._profile(now, *profile)
            if now >= until or (done is not None and done()):
                break
            self.admit_arrived()
            active = self.sched.decoding_mask()
            if active.any():
                self.step(active)
                continue
            nxt = self.sched.next_arrival()
            if nxt is None:
                break
            wake = min(self.origin + nxt, until)
            with self.span("bench.wait"):
                while self.now() < wake:
                    time.sleep(min(0.001, max(wake - self.now(), 0.0)))
        if profile is not None:
            self._stop_profile()

    def _profile(self, now, start, stop, tdir):
        if self.profile is None and now >= start:
            self.jax.profiler.start_trace(str(tdir))
            self.profile = [self.now()]
            self._window_ann = self.jax.profiler.TraceAnnotation("bench.traced_window")
            self._window_ann.__enter__()
        elif now >= stop:
            self._stop_profile()

    def _stop_profile(self):
        if self.profile is not None and len(self.profile) == 1:
            self._window_ann.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            self.profile.append(self.now())


# ------------------------------------------------------------ the run
@dataclasses.dataclass
class RunRecord:
    """What per-layer metric readers read (``bench/metrics/*.py``)."""
    cfg: dict
    mix: dict
    peaks: dict
    window: tuple
    steps: List[StepRecord]
    requests: List
    origin: float
    compiles_in_window: int
    trace: Optional[dict] = None

    def window_steps(self):
        w0, w1 = self.window
        return [s for s in self.steps if s.t0 >= w0 and s.t1 <= w1]

    def due_in_window(self):
        w0, w1 = self.window
        return [r for r in self.requests
                if w0 <= self.origin + r.due < w1]


def percentile(values, q: float) -> float:
    """The q-th percentile (linear between order statistics); +inf where it
    falls on a failed request."""
    v = np.sort(np.asarray(values, np.float64))
    if len(v) == 0:
        return float("nan")
    pos = (len(v) - 1) * q / 100.0
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if not np.isfinite(v[hi]):
        return float("inf")
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))
