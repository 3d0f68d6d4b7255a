"""The trace reduction, on a small trace whose answers are known.

The trace is written as an XSpace text proto: a host plane carrying the
window's mark and two host events, and two TPU planes whose ``XLA Ops``
lines hold ops that overlap, that straddle the window's edges, and a
second line that must be left out.
"""

from __future__ import annotations

import pytest

from bench import xplane
from bench.kernels import probe_rows

MS = 1_000_000_000      # one millisecond in picoseconds


def event(meta: int, start_ms: float, dur_ms: float) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ms * MS)} "
            f"duration_ps: {int(dur_ms * MS)} }}")


def plane(pid: int, name: str, lines: dict, names: dict) -> str:
    body = []
    for lid, (lname, events) in enumerate(lines.items(), start=1):
        body.append(f"lines {{ id: {lid} name: \"{lname}\" timestamp_ns: 0 "
                    + " ".join(event(*e) for e in events) + " }")
    for mid, mname in names.items():
        body.append(f"event_metadata {{ key: {mid} value {{ id: {mid} "
                    f"name: \"{mname}\" }} }}")
    return f"planes {{ id: {pid} name: \"{name}\" " + " ".join(body) + " }"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from jax.profiler import ProfileData

    host = plane(1, "/host:CPU", {"python3": [
        (1, 10, 100),            # the window: 10 ms .. 110 ms
        (2, 30, 40),             # the host plans over the first gap
        (3, 0, 200)]},           # a long event over everything
        {1: "bench.window", 2: "plan_probe_runs", 3: "main"})
    ops = {1: "%probe_rows.4 = custom-call(...)", 2: "%fusion.1 = fusion(...)"}
    dev0 = plane(2, "/device:TPU:0", {
        "XLA Ops": [(1, 0, 20),      # clipped to 10..20 by the window
                    (2, 15, 10),     # overlaps: union 10..25
                    (1, 80, 50)],    # clipped to 80..110
        "XLA Modules": [(2, 0, 200)]}, ops)
    dev1 = plane(3, "/device:TPU:1", {"XLA Ops": [(2, 50, 10)]}, ops)
    proto = " ".join([host, dev0, dev1])
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(proto))
    return path


def test_window_busy_and_kernel_time(trace):
    r = xplane.reduce(trace, "bench.window", [0, 1])
    assert r.window_s == pytest.approx(0.100)
    # device 0 busy 15 ms + 30 ms, device 1 busy 10 ms: mean 27.5 ms
    assert r.busy_s == pytest.approx(0.0275)
    # probe_rows: 10 ms + 30 ms inside the window, on device 0
    assert r.kernel_seconds(probe_rows.matches) == pytest.approx(0.040)


def test_gaps_are_device_zero_and_named_by_the_host(trace):
    r = xplane.reduce(trace, "bench.window", [0, 1])
    # device 0 is idle 25..80 ms: the planner covers 30..70 of it
    assert r.gaps == [("plan_probe_runs", pytest.approx(0.055))]
    b = r.breakdown()
    assert b["idle_gaps"][0][0] == "plan_probe_runs"
    assert b["device_ops"][0] == ["%probe_rows.4 = custom-call(...)",
                                  pytest.approx(0.040)]


def test_one_chip_reads_its_own_plane(trace):
    r = xplane.reduce(trace, "bench.window", [1])
    assert r.busy_s == pytest.approx(0.010)
    assert r.kernel_seconds(probe_rows.matches) == 0


def test_union_merges_overlaps():
    assert xplane.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [[0, 3],
                                                               [5, 10]]
