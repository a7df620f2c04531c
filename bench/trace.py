"""Reduce a profiler trace to device busy time, idle gaps and top ops.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``; their
ops line holds one event per XLA op run on the chip. The serving loop's host
spans (``bench.*`` ``TraceAnnotation`` events) sit on host-thread lines of
``/host:CPU`` on the same clock, and ``bench.traced_window`` marks the
stretch that was traced.

- busy: the union of the op intervals within the traced window, averaged
  over the device planes;
- idle gaps: the stretches of that window with no op running, each named
  after the serving loop's span open at its midpoint (``host other`` if none);
- top ops: device seconds per op name, as the trace prints it.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINES = ("XLA Ops",)
WINDOW_SPAN = "bench.traced_window"
SPAN_PREFIX = "bench."


def events_of(path: str) -> Dict[str, List[Tuple[str, str, int, int]]]:
    """{"device": [(plane, name, start_ns, end_ns)], "host": [...]} from one
    xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"device": [], "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in OPS_LINES:
                    out["device"] += [(plane.name, e.name, e.start_ns,
                                       e.start_ns + e.duration_ns)
                                      for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [(plane.name, e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events
                                if e.name.startswith(SPAN_PREFIX)]
    return out


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def reduce_events(ev: dict, top: int = 10) -> dict:
    """busy_s, window_s and the breakdown from ``events_of``' output."""
    wins = [(a, b) for _, n, a, b in ev["host"] if n == WINDOW_SPAN]
    if wins:
        w0, w1 = wins[0]
    else:
        w0 = min(a for *_, a, _b in ev["device"])
        w1 = max(b for *_, b in ev["device"])
    planes = sorted({p for p, *_ in ev["device"]}) or ["none"]
    busy_ns = 0
    gaps = []
    op_ns = defaultdict(int)
    for plane in planes:
        ivs = [(max(a, w0), min(b, w1)) for p, _, a, b in ev["device"]
               if p == plane and b > w0 and a < w1]
        merged = _union(ivs)
        busy_ns += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    for p, n, a, b in ev["device"]:
        if b > w0 and a < w1:
            op_ns[n] += min(b, w1) - max(a, w0)
    spans = [(n, a, b) for _, n, a, b in ev["host"] if n != WINDOW_SPAN]

    def label(a, b):
        mid = (a + b) / 2
        inner = [(sb - sa, n) for n, sa, sb in spans if sa <= mid <= sb]
        return min(inner)[1] if inner else "host other"

    gaps.sort(key=lambda g: g[0] - g[1])
    k = len(planes)
    return {
        "busy_s": busy_ns / k / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "breakdown": {
            "device_ops": [[n, t / k / 1e9] for n, t in
                           sorted(op_ns.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": [[label(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
        },
    }


def reduce_dir(trace_dir: Path) -> dict:
    """Reduce the one trace under ``trace_dir`` (None if it holds no TPU
    op)."""
    paths = glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*" / "*.xplane.pb"))
    if not paths:
        return None
    ev = events_of(paths[0])
    if not ev["device"]:
        return None
    return reduce_events(ev)
