"""Read the program's own spans: its stages in the profiler's trace and in
the registry.

The program times each stage of its serving pipeline and of the host run
planner with ``repro.obs.trace.stage``: a ``jax.profiler.TraceAnnotation``
on the profiler's host plane, on the device planes' clock, and a registry
histogram ``serving.stage_ms`` labelled ``stage=<name>``. A program that
has no such stage gives no event and no histogram, and every reader here
then returns None.
"""

from __future__ import annotations

import functools
import os
import pathlib
from typing import Optional

from bench import harness, xplane

STAGE_HIST = "serving.stage_ms"


def run_trace() -> pathlib.Path:
    """The trace of this run's ``--trace 1`` window."""
    return xplane.find(harness.TRACE_DIR)


@functools.lru_cache(maxsize=2)
def _host_events(path: str, mtime_ns: int) -> tuple:
    """Every event of the trace's host planes: ``(name, start_ns,
    end_ns)``. Cached by path and modification time, so several readers
    of one run parse its trace once."""
    del mtime_ns   # part of the cache key only
    return tuple((e.name, e.start_ns, e.end_ns)
                 for plane in xplane.load(path).planes
                 if plane.name.startswith(xplane.HOST_PREFIX)
                 for line in plane.lines for e in line.events)


def host_events(path) -> tuple:
    return _host_events(str(path), os.stat(path).st_mtime_ns)


def window(path, mark: str = harness.WINDOW_MARK) -> tuple:
    """``(start_ns, end_ns)`` of the first host event named ``mark``."""
    for name, s, e in host_events(path):
        if name == mark:
            return s, e
    raise ValueError(f"no host event {mark!r} in {path}")


def intervals(path, name: str, mark: str = harness.WINDOW_MARK
              ) -> Optional[list]:
    """The host events named ``name``, clipped to the window and merged
    where they overlap; None when the trace has no such event at all."""
    found = [(s, e) for n, s, e in host_events(path) if n == name]
    if not found:
        return None
    w0, w1 = window(path, mark)
    return xplane.union([(max(s, w0), min(e, w1)) for s, e in found
                         if min(e, w1) > max(s, w0)])


def overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share_inside(path, ops: list, name: str,
                      mark: str = harness.WINDOW_MARK) -> Optional[float]:
    """Share (%) of the window in which the host is inside a ``name``
    event and the device runs no op. ``ops`` are the device's
    ``(name, start_ns, end_ns)`` ops, already clipped to the window
    (``xplane.Reduced.ops``); for a cell of several chips an instant
    counts as idle only when no chip of the cell runs an op, so the share
    never exceeds the device's idle share."""
    spans = intervals(path, name, mark)
    if spans is None:
        return None
    w0, w1 = window(path, mark)
    if w1 <= w0:
        return None
    busy = xplane.union([(s, e) for _, s, e in ops])
    inside = sum(e - s for s, e in spans) - overlap_ns(spans, busy)
    return 100.0 * inside / (w1 - w0)


def stage_ms_per_call(counters: tuple, stage: str) -> Optional[float]:
    """Mean ms of one stage over the window: the change of the histogram
    ``serving.stage_ms{stage=...}``'s sum over the change of its count,
    between the window's two registry snapshots."""
    def read(snap):
        h = snap.get("hists", {}).get(STAGE_HIST, {}).get(f"stage={stage}")
        return (h["count"], h["sum"]) if h else (0, 0.0)

    (n0, ms0), (n1, ms1) = (read(s) for s in counters)
    return (ms1 - ms0) / (n1 - n0) if n1 > n0 else None
