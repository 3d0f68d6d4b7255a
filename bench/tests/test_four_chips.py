"""The four-chip cell ``cobs-idl-4096-x4.screen`` on the CPU, and the
readers of its two metrics.

The rehearsals run in one subprocess with four virtual CPU devices:
``XLA_FLAGS`` must be set before JAX is imported, and the process that
runs the other benchmark tests keeps one device. The subprocess is this
file run as a script; it prints one JSON object, which the tests below
read. Run with ``PYTHONPATH=src:. python -m pytest bench/tests``.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "cobs-idl-4096-x4.screen"
SECONDS = 1.0


def _rehearsals() -> dict:
    from bench import rehearse
    from bench.tests import test_check

    sound = rehearse.rehearse(CELL, SECONDS)
    out = {"devices": sound["device"]["count"],
           "problems": rehearse.problems(CELL, sound)}
    for name, kw in (("control", {"control": True}),
                     ("altered", {"fault": test_check.wrap(
                         test_check.altered)})):
        result = rehearse.rehearse(CELL, SECONDS, **kw)
        out[name] = {"correct": result["correct"],
                     "mismatched": result["check"]["mismatched_answers"][
                         "value"]}
    return out


@pytest.fixture(scope="module")
def rehearsals():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, __file__], capture_output=True,
                          text=True, timeout=600, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(rehearsals):
    assert rehearsals["devices"] == 4
    assert rehearsals["problems"] == []


@pytest.mark.parametrize("run", ["control", "altered"])
def test_control_and_fault_are_not_correct(rehearsals, run):
    assert not rehearsals[run]["correct"]
    assert rehearsals[run]["mismatched"] > 0


# -- the metric readers, on a hand-built context ----------------------------

def _context(busy_s, counters=({}, {})):
    import numpy as np

    from bench import harness, synth

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.cell_of(spec, CELL)
    reads = [synth.Request(read=np.zeros(230, np.uint8), kind="random",
                           file=-1, done=t) for t in (0.5, 1.5, 2.5, 3.5)]
    reads[2].error = "lost"
    window = types.SimpleNamespace(requests=reads, counters=counters)
    trace = None if busy_s is None else types.SimpleNamespace(
        busy_s=busy_s, span=(1.0, 3.0))
    return harness.Context(cell=cell, window=window, setup_s=0.0,
                           peak={"hbm_bytes_per_s": 1e12}, trace=trace)


def _reader(name):
    from bench import harness
    return harness.load_module("metrics", name)


def test_sharded_step_roofline_reads_bytes_over_the_chips_busy_time():
    # one answered read in the span (1.0, 3.0]: the one at 2.5 failed
    # 200 kmers x eta 4 probes x 512-byte rows, read and written
    want_bytes = 2 * 200 * 4 * 512
    got = _reader("sharded_step_roofline.x4").read(_context(busy_s=1e-6))
    assert got == pytest.approx(100.0 * want_bytes / (4 * 1e12) / 1e-6)


@pytest.mark.parametrize("busy_s", [None, 0.0], ids=["untraced", "idle"])
def test_sharded_step_roofline_is_none_without_device_time(busy_s):
    assert _reader("sharded_step_roofline.x4").read(
        _context(busy_s)) is None


def _snap(count, total):
    return {"hists": {"serving.stage_ms": {
        "stage=shard.gather": {"count": count, "sum": total},
        "stage=plan": {"count": 50, "sum": 99.0}}}}


@pytest.mark.parametrize("counters, want", [
    ((_snap(4, 10.0), _snap(10, 22.0)), 2.0),
    ((_snap(4, 10.0), _snap(4, 10.0)), None),
    (({}, {}), None)], ids=["window_delta", "no_batch", "no_stage"])
def test_shard_dispatch_ms_per_batch(counters, want):
    got = _reader("shard_dispatch_ms_per_batch.x4").read(
        _context(None, counters))
    assert got == (pytest.approx(want) if want is not None else None)


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    print(json.dumps(_rehearsals()))
