"""From a profiler trace to device busy time, per-program device time and
idle gaps attributed to what the host was doing.

The benchmark wraps its own work in `jax.profiler.TraceAnnotation` spans
named `bench.*` (`bench.admit` around a wave's admission, `bench.tick`
around each engine tick); the traced window runs from the first such span's
start to the last one's end. On each device plane (`/device:...`) the line
`XLA Ops` holds one event per operation that ran, and `XLA Modules` one per
program run, named after the jitted function (`jit_serve_step(...)`).

- busy: the union of the operation intervals inside the window, per device;
- operations: per program and operation (`serve_step/fusion.12`), its
  device time inside the window less that of the operations nested in it
  (device 0);
- programs: per program name, the number of runs and their summed device
  time inside the window (device 0, or summed over devices by `per_device`);
- idle gaps: the complement of busy inside the window on the first device,
  each named after the innermost event of the benchmark's own host thread
  (the line that holds the `bench.*` spans) that covers its midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
TICK_SPAN = "bench.tick"


def find_trace(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def program_name(event_name: str) -> str:
    """`jit_serve_step(123)` -> `serve_step`."""
    name = re.sub(r"\(.*\)$", "", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def complement(intervals, lo, hi) -> List[Tuple[float, float]]:
    """Gaps in [lo, hi] not covered by any interval."""
    gaps, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi))
    return [(s, e) for s, e in gaps if e > s]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def op_name(event_name: str) -> str:
    """`%fusion.12 = bf16[4,64]{...} fusion(...)` -> `fusion.12`."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _program_of(modules):
    """A function from a time to the name of the program running then."""
    runs = sorted((s, e, program_name(n)) for n, s, e in modules)
    starts = [r[0] for r in runs]

    def owner(t):
        i = bisect.bisect_right(starts, t) - 1
        return runs[i][2] if i >= 0 and runs[i][1] >= t else "none"
    return owner


def self_times(ops: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Per operation name, its time less the time of the operations
    nested inside it (a `while` holds its body's operations on the same
    line), so that the times add up to the busy time."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [name, start, end, nested time]

    def close(item):
        name, s, e, child = item
        out[name] = out.get(name, 0.0) + (e - s) - child

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        # an op that ends inside the one open is nested in it; one that
        # starts before the open one's end but outlasts it only overlaps
        while stack and (stack[-1][2] <= s or stack[-1][2] < e):
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def load_events(path: str) -> Dict:
    """Flatten a trace into plain lists (start and end in ns):
    `devices`: {plane: {"ops": [(name, s, e)], "modules": [(name, s, e)]}},
    `host`: [(name, s, e)] of the host thread that holds the `bench.*`
    spans, events of nonzero length only."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
            if dev["ops"] or dev["modules"]:
                devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.end_ns) for e in line.events
                       if e.end_ns > e.start_ns]
                if any(n.startswith(SPAN_PREFIX) for n, _, _ in evs):
                    host.extend(evs)
    return {"devices": devices, "host": host}


def _attribute(gaps, host):
    """Name each gap after the shortest host event covering its midpoint
    (one sweep over both lists sorted by time)."""
    events = sorted(host, key=lambda h: h[1])
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][0])
    names, active, j = [None] * len(gaps), [], 0
    for i in order:
        s, e = gaps[i]
        mid = (s + e) / 2
        while j < len(events) and events[j][1] <= mid:
            active.append(events[j])
            j += 1
        active = [h for h in active if h[2] >= mid]
        names[i] = (min(active, key=lambda h: h[2] - h[1])[0]
                    if active else "none")
    return names


def reduce_events(ev: Dict, top: int = 10) -> Dict:
    """The numbers the metric readers use; all times in seconds."""
    spans = [h for h in ev["host"] if h[0].startswith(SPAN_PREFIX)]
    if not spans or not ev["devices"]:
        raise ValueError("trace holds no bench.* span or no device plane")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    window = (hi - lo) * 1e-9
    busy, programs, op_time = [], {}, {}
    names = sorted(ev["devices"])
    for i, plane in enumerate(names):
        dev = ev["devices"][plane]
        ivs = [_clip(s, e, lo, hi) for _, s, e in dev["ops"]]
        ivs = [(s, e) for s, e in ivs if e > s]
        busy.append(union_length(ivs) * 1e-9)
        if i == 0:
            first_busy = ivs
            owner = _program_of(dev["modules"])
            clipped = [(f"{owner(s)}/{op_name(n)}",) + _clip(s, e, lo, hi)
                       for n, s, e in dev["ops"]]
            op_time = {n: t * 1e-9 for n, t in self_times(
                [o for o in clipped if o[2] > o[1]]).items()}
            for name, s, e in dev["modules"]:
                s2, e2 = _clip(s, e, lo, hi)
                if e2 > s2:
                    p = programs.setdefault(program_name(name),
                                            {"runs": 0, "device_s": 0.0})
                    p["runs"] += 1
                    p["device_s"] += (e2 - s2) * 1e-9
    gaps = complement(first_busy, lo, hi)
    by_host = {}
    for who, (s, e) in zip(_attribute(gaps, ev["host"]), gaps):
        by_host[who] = by_host.get(who, 0.0) + (e - s) * 1e-9
    ticks = sum(1 for h in spans if h[0] == TICK_SPAN)
    return {
        "window_s": window,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "devices": len(names),
        "ticks": ticks,
        "programs": programs,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(by_host.items(), key=lambda kv: -kv[1])[:top],
        "idle_s": window - busy[0],
    }


def reduce_trace(log_dir: str, top: int = 10) -> Dict:
    return reduce_events(load_events(find_trace(log_dir)), top)
