"""The program-span readers, on a small trace whose answers are known.

The trace is written as an XSpace text proto: a host plane carrying the
window's mark and three ``plan`` events (one straddling each edge of the
window, one over a device op) beside an event of another name, and a TPU
plane whose ``XLA Ops`` line holds ops inside and across the window.
"""

from __future__ import annotations

import types

import pytest

from bench import harness, spans, xplane
from bench.tests.test_xplane import plane


def write(path, host_events: list, names: dict):
    from jax.profiler import ProfileData

    host = plane(1, "/host:CPU", {"python3": host_events}, names)
    dev = plane(2, "/device:TPU:0", {"XLA Ops": [
        (1, 5, 10),              # clipped to 10..15 by the window
        (1, 50, 10),             # inside the second plan event
        (1, 105, 3)]},           # inside the last plan event
        {1: "%probe_rows.4 = custom-call(...)"})
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        host + " " + dev))
    return path


@pytest.fixture
def trace(tmp_path):
    return write(tmp_path / "plugins" / "profile" / "run" / "t.xplane.pb", [
        (1, 10, 100),            # the window: 10 ms .. 110 ms
        (2, 0, 20),              # plan across the window's open: 10..20
        (2, 40, 30),             # plan 40..70, over the op at 50..60
        (3, 45, 5),              # another stage, never read as plan
        (2, 100, 30)],           # plan across the close: 100..110
        {1: harness.WINDOW_MARK, 2: "plan", 3: "plan.runs"})


def test_intervals_are_clipped_to_the_window(trace):
    ms = 1_000_000
    assert spans.window(trace) == (10 * ms, 110 * ms)
    assert spans.intervals(trace, "plan") == [[10 * ms, 20 * ms],
                                              [40 * ms, 70 * ms],
                                              [100 * ms, 110 * ms]]
    assert spans.intervals(trace, "serve.finalize") is None


def test_idle_share_inside_leaves_out_device_busy_time(trace):
    reduced = xplane.reduce(trace, harness.WINDOW_MARK, [0])
    # plan covers 50 ms of the window, 18 of them over device ops
    share = spans.idle_share_inside(trace, reduced.ops, "plan")
    assert share == pytest.approx(32.0)
    idle = 100.0 * (1 - reduced.busy_s / reduced.window_s)
    assert idle == pytest.approx(82.0)
    assert share <= idle


def test_a_program_without_the_stage_reads_none(tmp_path):
    path = write(tmp_path / "t.xplane.pb", [(1, 10, 100)],
                 {1: harness.WINDOW_MARK})
    reduced = xplane.reduce(path, harness.WINDOW_MARK, [0])
    assert spans.idle_share_inside(path, reduced.ops, "plan") is None


def test_overlap_of_merged_intervals():
    assert spans.overlap_ns([[0, 10], [20, 30]], [[5, 25], [28, 40]]) == 12
    assert spans.overlap_ns([], [[0, 5]]) == 0


def _snap(count, total):
    return {"hists": {"serving.stage_ms": {
        "stage=plan": {"count": count, "sum": total},
        "stage=plan.runs": {"count": 99, "sum": 1.0}}}}


def test_stage_ms_per_call_reads_the_window_delta():
    assert spans.stage_ms_per_call((_snap(2, 10.0), _snap(6, 50.0)),
                                   "plan") == pytest.approx(10.0)
    assert spans.stage_ms_per_call((_snap(2, 10.0), _snap(2, 10.0)),
                                   "plan") is None
    assert spans.stage_ms_per_call(({}, {}), "plan") is None


@pytest.mark.parametrize("cell", ["screen", "interactive"])
def test_metric_files_read_the_run(cell, trace, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", trace.parents[3])
    ctx = types.SimpleNamespace(
        trace=xplane.reduce(trace, harness.WINDOW_MARK, [0]),
        window=types.SimpleNamespace(counters=(_snap(1, 5.0),
                                               _snap(3, 25.0))))
    idle = harness.load_module("metrics", f"idle_host_plan_share.{cell}")
    plan = harness.load_module("metrics", f"plan_ms_per_batch.{cell}")
    assert idle.read(ctx) == pytest.approx(32.0)
    assert plan.read(ctx) == pytest.approx(10.0)
    ctx.trace = None
    assert idle.read(ctx) is None
