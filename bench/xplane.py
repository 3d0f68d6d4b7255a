"""Reduce a JAX profiler trace (``.xplane.pb``) to the device's metrics.

Read with ``jax.profiler.ProfileData`` alone. What it matches:

* the window: the host event named by the harness's
  ``jax.profiler.TraceAnnotation`` (``bench.window``), on the host plane
  (``/host:CPU``); every device interval is clipped to it;
* the device: planes named ``/device:TPU:<n>`` (``/device:TPU:<n> ...``
  planes of other kinds are left out), one per chip;
* the device's operations: the events of the ``XLA Ops`` line of a device
  plane. Busy time is the union of their intervals; a kernel's time is the
  sum of the durations of the ops its ``matches`` accepts;
* what the host did in an idle gap: the host-plane events that overlap
  the gap; the gap takes the name of the shortest one that covers at least
  half of it, or else the one that overlaps it most.
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib
import re
from typing import Callable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"
TOP = 10


def find(trace_dir: pathlib.Path) -> pathlib.Path:
    """The newest ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def describe(path, events_per_line: int = 6) -> str:
    """Planes, lines, event counts and sample names: for a look by hand."""
    out = []
    for plane in load(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            span = ((min(e.start_ns for e in evs), max(e.end_ns for e in evs))
                    if evs else (0, 0))
            out.append(f"  line {line.name!r}: {len(evs)} events, "
                       f"{span[0]}..{span[1]} ns; top "
                       f"{names.most_common(events_per_line)}")
            for e in evs[:2]:
                out.append(f"    {e.name!r} {e.start_ns}+{e.duration_ns} "
                           f"stats {dict(list(e.stats)[:8]) if e.stats else {}}")
    return "\n".join(out)


def union(intervals: list) -> list:
    """Merged ``(start, end)`` intervals, in order."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                          # mean over the chips
    ops: list                              # [(name, start_ns, end_ns)]
    gaps: list             # [(label, seconds)]: the longest, first
    span: Optional[tuple] = None   # the traced span on the host's clock

    def kernel_seconds(self, matches: Callable[[str], bool]) -> float:
        """Device time of the ops ``matches`` accepts, over all chips."""
        return sum(e - s for name, s, e in self.ops if matches(name)) * 1e-9

    def breakdown(self) -> dict:
        per = collections.Counter()
        for name, s, e in self.ops:
            per[name] += (e - s) * 1e-9
        return {"device_ops": [[n, s] for n, s in per.most_common(TOP)],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def reduce(path, mark: str, device_ids: Optional[list] = None) -> Reduced:
    data = load(path)
    hosts, devices = [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name.startswith(HOST_PREFIX):
            hosts.append(plane)
    host_events = [(e.name, e.start_ns, e.end_ns) for p in hosts
                   for line in p.lines for e in line.events]
    marks = [(s, e) for n, s, e in host_events if n == mark]
    if not marks:
        raise ValueError(f"no host event {mark!r} in {path}")
    w0, w1 = marks[0]
    host_events = [h for h in host_events if h[0] != mark]
    if device_ids is not None:
        devices = {d: p for d, p in devices.items() if d in device_ids}
    if not devices:
        raise ValueError(f"no device plane in {path}")

    ops, busy, gaps = [], [], []
    for d in sorted(devices):
        mine = []
        for line in devices[d].lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                s, t = max(e.start_ns, w0), min(e.end_ns, w1)
                if t > s:
                    mine.append((e.name, s, t))
        ops.extend(mine)
        merged = union([(s, t) for _, s, t in mine])
        busy.append(sum(t - s for s, t in merged) * 1e-9)
        if d == min(devices):
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = sorted(((s, t) for s, t in zip(edges[::2], edges[1::2])
                           if t > s), key=lambda g: g[0] - g[1])[:TOP]
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=sum(busy) / len(busy),
                   ops=ops, gaps=[(_label(host_events, s, t), (t - s) * 1e-9)
                                  for s, t in gaps])


def _label(host_events: list, s: int, t: int) -> str:
    best, best_len, most, most_over = None, None, "idle", 0
    for name, hs, he in host_events:
        over = min(he, t) - max(hs, s)
        if over <= 0:
            continue
        if over * 2 >= t - s and (best_len is None or he - hs < best_len):
            best, best_len = name, he - hs
        if over > most_over:
            most, most_over = name, over
    return best if best is not None else most
