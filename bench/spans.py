"""The program's host spans as a traced run recorded them.

`serve/tracing.py` records each span as a begin and an end event on the
thread that ran it (`run.events`, on in traced runs); spans nest per
thread, and the end event carries the span's `parent` and `cpu_ms` where
the program records them.  This module pairs the events into `Span`s and
places them in the measured window, for the metric readers that read
spans (`bench/metrics/host_gap_ms.backlog.py`, `harvest_ms.open.py`,
`job_init_ms.open.py`).

The events' `ts` is `time.monotonic()`, the clock `bench/run.py` times
the window with (`time.perf_counter()`; both are CLOCK_MONOTONIC on
Linux): the window opens `run.setup_s` after the harness module's
`T_START` and lasts `run.seconds`.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    t0: float                     # begin, monotonic seconds
    t1: float                     # end
    tid: int                      # thread
    trace_id: Optional[str]
    attrs: Dict[str, Any]         # the end event's attributes

    @property
    def ms(self) -> float:
        return 1e3 * (self.t1 - self.t0)


def spans(events) -> List[Span]:
    """Begin/end pairs, matched per thread innermost first, in end order;
    an end with no open begin of its name is dropped."""
    open_: Dict[int, List[Any]] = {}
    out: List[Span] = []
    for ev in events:
        if ev.kind == "begin":
            open_.setdefault(ev.tid, []).append(ev)
        elif ev.kind == "end":
            stack = open_.get(ev.tid, [])
            for i in range(len(stack) - 1, -1, -1):
                if stack[i].name == ev.name:
                    b = stack.pop(i)
                    out.append(Span(ev.name, b.ts, ev.ts, ev.tid,
                                    ev.trace_id, dict(ev.attrs)))
                    break
    return out


def window(run) -> Tuple[float, float]:
    """(open, close) of the measured window on the events' clock."""
    start = sys.modules[type(run).__module__].T_START
    t_open = start + run.setup_s
    return t_open, t_open + run.seconds


def in_window(run, found: List[Span]) -> List[Span]:
    """The spans that began inside the measured window."""
    t_open, t_close = window(run)
    return [s for s in found if t_open <= s.t0 < t_close]


def leaves(found: List[Span]) -> List[Span]:
    """Spans no other span names as its `parent`."""
    parents = {s.attrs.get("parent") for s in found}
    return [s for s in found if s.name not in parents]


def mean_ms(run, name: str) -> Optional[float]:
    """Mean milliseconds of the `name` spans of the window's jobs."""
    ids = {r.handle.request.trace_id for r in run.window_jobs
           if r.handle is not None and r.handle.request.trace_id}
    ms = [s.ms for s in spans(run.events)
          if s.name == name and s.trace_id in ids]
    note_longest_leaf(run)
    return sum(ms) / len(ms) if ms else None


def note_longest_leaf(run) -> None:
    """Note the window's longest leaf span: its name, milliseconds, the
    thread's CPU milliseconds in it and its offset into the window.  A
    long span with little CPU waited; one with as much CPU computed."""
    found = in_window(run, leaves(spans(run.events)))
    if not found:
        return
    worst = max(found, key=lambda s: s.t1 - s.t0)
    run.notes.update(
        longest_leaf_span=worst.name, longest_leaf_ms=worst.ms,
        longest_leaf_cpu_ms=worst.attrs.get("cpu_ms"),
        longest_leaf_at_s=worst.t0 - window(run)[0])
