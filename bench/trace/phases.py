"""From a profiler trace to the engine's time split by the program's own
spans.

`ServingEngine` wraps its phases in `repro.obs.trace.span`s, which are
profiler annotations named `engine.*` (`engine.tick`, `engine.admit`) and
`tick.*` (`tick.actuate`, `tick.serve`, `tick.canary`, `tick.host_read`,
`tick.retire`, `tick.qos_update`). They sit on the host line that holds
the benchmark's `bench.*` spans, nested inside them, on the device's
clock. Per span name, inside the window of `xplane.reduce_events`:

- count: the spans that overlap the window;
- host_s: their summed length, clipped to the window;
- idle_s: the first device's idle time that falls in the span and in no
  program span nested inside it, by exact overlap (a gap that crosses two
  phases is split between them);
- device_s: per program name, the device time inside the window of the
  program runs dispatched in the span (the innermost one over the
  dispatch's start). A run is matched to its dispatch by order: the n-th
  outermost `PjitFunction(<program>)` host event from the trace's start
  dispatched the n-th `jit_<program>` run on the first device (JAX
  nests each dispatch in a second event of the same name). The engine
  reads its tokens back every tick, so no run is in flight when a trace
  starts.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Tuple

from bench.trace import xplane

PROGRAM_PREFIXES = ("engine.", "tick.")
STEP_PROGRAMS = ("serve_step", "sharded_step")
_DISPATCH = re.compile(r"^PjitFunction\((.+)\)$")


def innermost(spans: List[Tuple[str, float, float]]
              ) -> List[Tuple[float, float, str]]:
    """The time that properly nested spans cover, cut into (start, end,
    name) pieces, each named after the innermost span over it, in order."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []       # open spans: name, end
    t = None
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, end = stack.pop()
            out.append((t, end, n))
            t = end
        if stack:
            out.append((t, s, stack[-1][0]))
            e = min(e, stack[-1][1])
        stack.append((name, e))
        t = s
    while stack:
        n, end = stack.pop()
        out.append((t, end, n))
        t = end
    return [p for p in out if p[1] > p[0]]


def outermost_dispatches(host) -> List[Tuple[str, float]]:
    """(program, start) of each `PjitFunction` host event that no other
    `PjitFunction` event holds, in order of start."""
    out, end = [], float("-inf")
    calls = [(m.group(1), s, e) for n, s, e in host
             for m in [_DISPATCH.match(n)] if m]
    for prog, s, e in sorted(calls, key=lambda c: (c[1], -c[2])):
        if s >= end:
            out.append((prog, s))
            end = e
    return out


def _overlap(pieces, gaps) -> Dict[str, float]:
    """Per name, the length of `gaps` that its pieces cover; both lists
    hold disjoint intervals in order."""
    out: Dict[str, float] = {}
    i = j = 0
    while i < len(pieces) and j < len(gaps):
        s, e, name = pieces[i]
        gs, ge = gaps[j]
        lo, hi = max(s, gs), min(e, ge)
        if hi > lo:
            out[name] = out.get(name, 0.0) + hi - lo
        if e < ge:
            i += 1
        else:
            j += 1
    return out


def reduce_phases(ev: Dict) -> Dict[str, Dict]:
    """{span name: {count, host_s, idle_s, device_s: {program: s}}} for
    the events `xplane.load_events` gives; times in seconds."""
    bench = [h for h in ev["host"] if h[0].startswith(xplane.SPAN_PREFIX)]
    lo = min(s for _, s, _ in bench)
    hi = max(e for _, _, e in bench)
    spans = [h for h in ev["host"] if h[0].startswith(PROGRAM_PREFIXES)]
    out: Dict[str, Dict] = {}
    for name, s, e in spans:
        s2, e2 = xplane._clip(s, e, lo, hi)
        if e2 > s2:
            p = out.setdefault(name, {"count": 0, "host_s": 0.0,
                                      "idle_s": 0.0, "device_s": {}})
            p["count"] += 1
            p["host_s"] += (e2 - s2) * 1e-9
    pieces = innermost(spans)
    dev = ev["devices"][sorted(ev["devices"])[0]]
    busy = [xplane._clip(s, e, lo, hi) for _, s, e in dev["ops"]]
    gaps = xplane.complement([b for b in busy if b[1] > b[0]], lo, hi)
    for name, t in _overlap(pieces, gaps).items():
        out[name]["idle_s"] += t * 1e-9
    runs: Dict[str, List[Tuple[float, float]]] = {}
    for name, s, e in sorted(dev["modules"], key=lambda m: m[1]):
        runs.setdefault(xplane.program_name(name), []).append((s, e))
    starts = [p[0] for p in pieces]
    seen: Dict[str, int] = {}
    for prog, t in outermost_dispatches(ev["host"]):
        n = seen[prog] = seen.get(prog, -1) + 1
        if n >= len(runs.get(prog, ())):
            continue
        s, e = xplane._clip(*runs[prog][n], lo, hi)
        i = bisect.bisect_right(starts, t) - 1
        if e > s and i >= 0 and t < pieces[i][1] and pieces[i][2] in out:
            d = out[pieces[i][2]]["device_s"]
            d[prog] = d.get(prog, 0.0) + (e - s) * 1e-9
    return out


def step_device_s(phase: Dict) -> float:
    """Device time of the decode-step programs dispatched in a phase."""
    return sum(phase["device_s"].get(p, 0.0) for p in STEP_PROGRAMS)
