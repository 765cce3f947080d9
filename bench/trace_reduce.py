"""Reduce a `jax.profiler` trace (`*.xplane.pb`) to device time.

Reads only JAX's own `ProfileData`.  On a TPU v5e the device planes are
`/device:TPU:<n>`; on each, "XLA Modules" holds one event per program
execution (`jit__step(<fingerprint>)`) and "XLA Ops" one event per
operation, named by its HLO text (`%fusion.12 = f32[...] fusion(...)`),
nested where a `while` body runs inside its loop.  From them:

  busy_s      union of the operation intervals, averaged over the chips
              that ran anything (time in which the device computed)
  window_s    from the first to the last device operation of the trace
  modules     {program: [device seconds, executions]}
  ops         {"<program>/<instruction>": [self seconds, count]}: time in
              an operation less the time of the operations nested in it
  breakdown   the ten operations with the most self time, and the idle
              time between operations grouped by the host event that
              covered most of each gap ("host idle" where none did)

Host planes (`/host:CPU`) supply the names of the host events.
"""
from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS, MODULES = "XLA Ops", "XLA Modules"
TOP = 10

Event = Tuple[float, float, str]           # (start ns, end ns, name)


def newest_trace(trace_dir) -> Path:
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line, short) -> List[Event]:
    return sorted(((e.start_ns, e.start_ns + e.duration_ns, short(e.name))
                   for e in line.events), key=lambda t: (t[0], -t[1]))


def _op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0]


def _module_name(name: str) -> str:
    return name.split("(", 1)[0]


def _self_times(ops: List[Event]) -> List[float]:
    """Each operation's duration less that of the operations nested in it
    (events sorted by start; a nested event lies inside its parent)."""
    self_ns = [e - s for s, e, _ in ops]
    stack: List[int] = []
    for i, (s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return self_ns


def _union(ops: List[Event]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e, _ in ops:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _attribute(gaps, host: List[Event]) -> Dict[str, float]:
    """Idle seconds per name of the host event overlapping each gap most."""
    starts = [h[0] for h in host]
    longest = max((h[1] - h[0] for h in host), default=0.0)
    out: Dict[str, float] = collections.defaultdict(float)
    for s, e in gaps:
        best, name = 0.0, "host idle"
        lo = bisect.bisect_left(starts, s - longest)
        for hs, he, hn in host[lo:bisect.bisect_right(starts, e)]:
            overlap = min(e, he) - max(s, hs)
            if overlap > best:
                best, name = overlap, hn
        out[name] += (e - s) * 1e-9
    return out


def reduce(trace_dir) -> Dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(newest_trace(trace_dir)))
    devices, host = [], []
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if DEVICE.match(plane.name) and OPS in lines:
            mods = (_events(lines[MODULES], _module_name)
                    if MODULES in lines else [])
            devices.append((_events(lines[OPS], _op_name), mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line, str))
    host.sort()
    ops: Dict[str, List[float]] = collections.defaultdict(lambda: [0.0, 0])
    modules: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0.0, 0])
    busy, spans, idle = [], [], collections.Counter()
    for op_events, mod_events in devices:
        if not op_events:
            continue
        mod_starts = [m[0] for m in mod_events]
        for s, e, name in mod_events:
            modules[name][0] += (e - s) * 1e-9
            modules[name][1] += 1
        for (s, e, name), own in zip(op_events, _self_times(op_events)):
            k = bisect.bisect_right(mod_starts, s) - 1
            mod = mod_events[k][2] if k >= 0 and s < mod_events[k][1] \
                else "?"
            ops[f"{mod}/{name}"][0] += own * 1e-9
            ops[f"{mod}/{name}"][1] += 1
        merged = _union(op_events)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        spans.append((merged[0][0], merged[-1][1]))
        idle.update(_attribute(
            [(a[1], b[0]) for a, b in zip(merged, merged[1:])], host))
    if not busy:
        raise ValueError(f"no device operation in the trace {trace_dir}")
    top = sorted(ops.items(), key=lambda kv: -kv[1][0])[:TOP]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (max(e for _, e in spans)
                     - min(s for s, _ in spans)) * 1e-9,
        "devices": len(busy),
        "modules": dict(modules),
        "ops": dict(ops),
        "breakdown": {
            "device_ops": [[n, v[0]] for n, v in top],
            "idle_gaps": [[n, v / len(busy)]
                          for n, v in idle.most_common(TOP)]},
    }
