"""Reduce a profiler trace to device busy time, idle gaps, device time per
op and per named scope.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``; their
ops line holds one event per XLA op run on the chip, named by its HLO
instruction (``%fusion.7 = bf16[...] fusion(...)``). The serving loop's host
spans (``bench.*`` ``TraceAnnotation`` events) sit on host-thread lines of
``/host:CPU`` on the same clock, and ``bench.traced_window`` marks the
stretch that was traced.

- busy: the union of the op intervals within the traced window, averaged
  over the device planes;
- idle gaps: the stretches of that window with no op running, each named
  after the serving loop's span open at its midpoint (``host other`` if none);
- ops (``op_s``, the top ones in ``breakdown``): device seconds per op name,
  as the trace prints it;
- scopes (``scope_s``): device seconds per named scope, the union of the
  intervals of every op whose scope path holds it, so an outer scope holds
  its inner ones, and a ``while`` op the ops of its body. The path is the
  op's ``op_name``: the event's ``tf_op`` stat where the trace carries one,
  else the ``metadata={op_name=...}`` of its instruction in a program's
  optimized HLO text, for the ops that run inside that program's module
  (the ``XLA Modules`` line); a TPU v5e trace under jaxlib 0.9 carries no
  such stat. The HLO is taken only where it is the program that ran: one
  program (fingerprint) of that module name ran in the stretch, and the
  instructions of the text, by name and result shape, match the ops of
  ``HLO_MATCH`` of its device time; else no op takes a path from it
  (``hlo_match`` reports the share). A named scope is a path component with a dot in it
  (``ssv.verify``, ``nsa.slc``), unwrapped from transforms such as
  ``jvp(...)``;
- steps: the ``bench.step`` spans in the window, one cut by its edges
  counted by the share of it inside.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINES = ("XLA Ops",)
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced_window"
STEP_SPAN = "bench.step"
SPAN_PREFIX = "bench."
OP_NAME_STAT = "tf_op"
MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=")
OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
TRANSFORM = re.compile(r"^\w+\((.*)\)$")
SCOPE = re.compile(r"^[A-Za-z_][\w-]*(\.[\w-]+)+$")
# share of a module's device time whose ops the HLO text has to match
HLO_MATCH = 0.99


def head(text: str) -> str:
    """``name = shape`` of an HLO instruction, from an op event's name or a
    line of HLO text: its name and result shape, which both print alike."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    name, sep, rest = text.lstrip("%").partition(" = ")
    if not sep:
        return ""
    if not rest.startswith("("):
        return f"{name} = {rest.split(' ', 1)[0]}"
    depth = 0
    for i, ch in enumerate(rest):                 # a tuple shape
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            break
    return f"{name} = {rest[:i + 1]}"


def op_names(hlo_text: str) -> Tuple[str, Dict[str, Tuple[str, str]]]:
    """(module name, {instruction name: (``head``, op_name or "")}) of an
    HLO module's text, for every instruction."""
    module = MODULE.search(hlo_text)
    out = {}
    for line in hlo_text.splitlines():
        name = INSTRUCTION.match(line)
        if name:
            path = OP_NAME.search(line)
            out[name.group(1)] = (head(line), path.group(1) if path else "")
    return (module.group(1) if module else ""), out


def scopes_of(path: str) -> Tuple[str, ...]:
    """The named scopes on an op_name path, outermost first."""
    out = []
    for part in path.split("/"):
        while (m := TRANSFORM.match(part)):
            part = m.group(1)
        if SCOPE.match(part):
            out.append(part)
    return tuple(out)


def events_of(path: str) -> Dict[str, List[Tuple]]:
    """{"device": [(plane, name, start_ns, end_ns, op_name or None)],
    "modules": [(plane, module name, fingerprint, start_ns, end_ns)],
    "host": [(plane, name, start_ns, end_ns)]} from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"device": [], "modules": [], "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in OPS_LINES:
                    out["device"] += [(plane.name, e.name, e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       dict(e.stats).get(OP_NAME_STAT))
                                      for e in line.events]
                elif line.name == MODULES_LINE:
                    # "jit_f(<fingerprint>)"
                    out["modules"] += [(plane.name, *_module_of(e.name),
                                        e.start_ns, e.start_ns + e.duration_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [(plane.name, e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events
                                if e.name.startswith(SPAN_PREFIX)]
    return out


def _module_of(event_name: str) -> Tuple[str, str]:
    """("jit_f", "<fingerprint>") of a module event "jit_f(<fingerprint>)"."""
    name, _, fp = event_name.partition("(")
    return name, fp.rstrip(")")


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _runs_of(ev: dict, module: str, w0: int, w1: int):
    """(in_module, fingerprints): in_module(plane, start_ns) says whether an
    op starting then ran inside an execution of ``module``; fingerprints
    are those of its executions that overlap [w0, w1)."""
    runs = defaultdict(list)
    fps = set()
    for p, n, fp, a, b in ev.get("modules", ()):
        if n == module:
            runs[p].append((a, b))
            if b > w0 and a < w1:
                fps.add(fp)
    for r in runs.values():
        r.sort()

    def in_module(plane, t):
        r = runs.get(plane, ())
        i = bisect.bisect_right(r, (t, float("inf"))) - 1
        return i >= 0 and r[i][0] <= t < r[i][1]
    return in_module, fps


def reduce_events(ev: dict, top: int = 10,
                  hlo: Tuple[str, Dict[str, Tuple[str, str]]] = ("", {})) -> dict:
    """busy_s, window_s, op_s, scope_s, steps, hlo_match and the breakdown
    from ``events_of``' output; ``hlo`` (``op_names`` of a program's HLO)
    gives the op_name of an op of that program whose event carries none,
    where it is the program that ran (module docstring)."""
    wins = [(a, b) for _, n, a, b in ev["host"] if n == WINDOW_SPAN]
    if wins:
        w0, w1 = wins[0]
    else:
        w0 = min(e[2] for e in ev["device"])
        w1 = max(e[3] for e in ev["device"])
    inside = [(p, n, max(a, w0), min(b, w1), path)
              for p, n, a, b, path in ev["device"] if b > w0 and a < w1]
    planes = sorted({e[0] for e in ev["device"]}) or ["none"]
    busy_ns = 0
    gaps = []
    op_ns = defaultdict(int)
    scope_ivs = defaultdict(list)
    hlo_ivs = defaultdict(list)
    module_ns = matched_ns = 0
    module, instrs = hlo
    in_module, fps = _runs_of(ev, module, w0, w1)
    for p, n, a, b, path in inside:
        op_ns[n] += b - a
        if path is None and instrs and in_module(p, a):
            module_ns += b - a
            h = head(n)
            got = instrs.get(h.split(" = ", 1)[0])
            if got and got[0] == h:
                matched_ns += b - a
                for scope in scopes_of(got[1]):
                    hlo_ivs[scope].append((p, a, b))
            continue
        for scope in scopes_of(path or ""):
            scope_ivs[scope].append((p, a, b))
    hlo_match = matched_ns / module_ns if module_ns else None
    if hlo_match is not None and hlo_match >= HLO_MATCH and len(fps) == 1:
        for scope, ivs in hlo_ivs.items():
            scope_ivs[scope] += ivs
    for plane in planes:
        merged = _union([(a, b) for p, _, a, b, _ in inside if p == plane])
        busy_ns += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    scope_ns = {s: sum(b - a for plane in planes for a, b in
                       _union([(a, b) for p, a, b in ivs if p == plane]))
                for s, ivs in scope_ivs.items()}
    steps = sum((min(b, w1) - max(a, w0)) / (b - a)
                for _, n, a, b in ev["host"]
                if n == STEP_SPAN and b > w0 and a < w1 and b > a)
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
        "op_s": {n: t / k / 1e9 for n, t in op_ns.items()},
        "scope_s": {s: t / k / 1e9 for s, t in scope_ns.items()},
        "steps": steps,
        "hlo_match": hlo_match,
        "hlo_programs": len(fps),
        "breakdown": {
            "device_ops": [[n, t / k / 1e9] for n, t in
                           sorted(op_ns.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": [[label(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
        },
    }


def reduce_dir(trace_dir: Path, hlo_text: str = "") -> Optional[dict]:
    """Reduce the one trace under ``trace_dir`` (None if it holds no TPU
    op), with the scope paths of ``hlo_text``, the optimized HLO of the
    program the trace ran."""
    paths = glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*" / "*.xplane.pb"))
    if not paths:
        return None
    ev = events_of(paths[0])
    if not ev["device"]:
        return None
    return reduce_events(ev, hlo=op_names(hlo_text))


def scope_ms_per_step(trace: Optional[dict], scope: str) -> Optional[float]:
    """Device ms under ``scope`` per fused step of the traced stretch; None
    where the trace holds no such scope or no step."""
    if not trace or scope not in trace["scope_s"] or trace["steps"] <= 0:
        return None
    return 1e3 * trace["scope_s"][scope] / trace["steps"]
