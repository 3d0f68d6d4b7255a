#!/usr/bin/env python3
"""Rehearse every cell on the CPU, at the configurations' small sizes.

    python3 bench/rehearse.py [--seconds 2] [workload ...]

Runs each cell's whole path (set-up through the program's ingest, the
warm-up, the client loop, the scheduler, the reference check) with
``JAX_PLATFORMS=cpu``, each configuration's
``rehearsal`` sizes and each traffic's ``rehearsal`` settings, and checks
that the answers are correct and that the result line has the keys and
the metric names the cell must report. It prints no metric's value: a
CPU run times nothing that a chip does.
"""

import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

KEYS = ("correct", "attempted", "failed", "metrics", "device", "check")


def rehearse(name: str, seconds: float, seed: int = 2**31 + 12345,
             fault=None, control: bool = False) -> dict:
    """One rehearsal run of workload ``name``; returns its result line."""
    import argparse

    import jax

    from bench import harness
    # XLA:CPU's cached programs warn on every load; the rehearsal compiles
    jax.config.update("jax_enable_compilation_cache", False)
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=0, control=control, keep_trace=None)
    return harness.measure(args, time.monotonic(), allow_cpu=True,
                           rehearse=True, fault=fault)


def problems(name: str, result: dict) -> list:
    """What is wrong with a rehearsal's result line."""
    from bench import harness
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.cell_of(spec, name, rehearse=True)
    out = [f"missing key {k!r}" for k in KEYS if k not in result]
    if list(result)[-1] != "check":
        out.append("the check is not the last key")
    want = {m["name"] for m in cell.end_to_end}
    if set(result.get("metrics", {})) != want:
        out.append(f"metrics {sorted(result.get('metrics', {}))}, "
                   f"want {sorted(want)}")
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if key not in result.get("device", {}):
            out.append(f"device has no {key!r}")
    if not result.get("correct"):
        out.append(f"not correct: {result.get('check')}")
    if result.get("failed"):
        out.append(f"{result['failed']} requests failed")
    return out


def main() -> int:
    import argparse

    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("workloads", nargs="*")
    args = ap.parse_args()
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    names = args.workloads or [w["name"] for w in spec["workloads"]]
    bad = 0
    for name in names:
        result = rehearse(name, args.seconds)
        wrong = problems(name, result)
        bad += bool(wrong)
        print(f"rehearsal {name}: {'ok' if not wrong else wrong}; "
              f"{result['attempted']} requests, {result['failed']} failed, "
              f"{len(result['metrics'])} metrics reported", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
