"""The program's own records (``repro.obs``) on the run's clock.

The serving loop makes exactly one ``eng.step`` per ``StepRecord``, in
order, and the program opens one ``ssv.step`` span inside each call, so the
last ``len(run.steps)`` ``ssv.step`` spans match ``run.steps`` one to one.
Each pair bounds the offset between the two clocks: a span lies inside its
record, so the offset is at least ``t0 - span start`` and at most
``t1 - span end``. The offset is the middle of the tightest bounds over all
pairs; bounds that cross mean the pairs do not line up.

Every function returns None where the program keeps no such records (a
program without ``repro.obs``, or one that names no ``ssv.step`` span) or
the pairs do not line up.
"""
from __future__ import annotations

from collections import defaultdict

STEP = "ssv.step"
SYNC = "ssv.step.sync"
SLACK_S = 1e-6         # rounding of the two clocks' seconds


def snapshot():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot()


def steps(run, snap=None):
    """(offset_s, [(record, span seconds, {child name: seconds})]) for
    every record of ``run.steps``; run time = program time + offset_s."""
    snap = snapshot() if snap is None else snap
    if not snap or not run.steps:
        return None
    spans = [s for s in snap["spans"] if s.name == STEP]
    if len(spans) < len(run.steps):
        return None
    spans = spans[-len(run.steps):]
    lo = max(r.t0 - s.start_ns / 1e9 for r, s in zip(run.steps, spans))
    hi = min(r.t1 - s.end_ns / 1e9 for r, s in zip(run.steps, spans))
    if lo > hi + SLACK_S:
        return None
    kids = defaultdict(lambda: defaultdict(float))
    ours = {s.index for s in spans}
    for c in snap["spans"]:
        if c.parent in ours:
            kids[c.parent][c.name] += (c.end_ns - c.start_ns) / 1e9
    return (lo + hi) / 2, [(r, (s.end_ns - s.start_ns) / 1e9, dict(kids[s.index]))
                           for r, s in zip(run.steps, spans)]


def window_steps(run):
    """[(span seconds, {child name: seconds})] of the fused steps inside
    the window (as ``RunRecord.window_steps`` picks them)."""
    got = steps(run)
    if got is None:
        return None
    w0, w1 = run.window
    return [(total, kids) for r, total, kids in got[1]
            if r.t0 >= w0 and r.t1 <= w1]


def compile_seconds_before(run, t: float):
    """Seconds of the program's compile records (tracing, lowering, backend
    compile, persistent-cache loads) that end on the run's clock between its
    start and ``t``."""
    snap = snapshot()
    got = steps(run, snap)
    if got is None:
        return None
    off = got[0]
    return sum(c.seconds for c in snap["compiles"]
               if 0.0 <= c.end_ns / 1e9 + off < t)
