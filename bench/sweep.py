#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate that the
system sustains without a growing backlog.

    python3 bench/sweep.py --workload cobs-idl.interactive --seed 5 \
        --seconds 8 --rates 100 200 300 400

Builds the cell once, then offers each rate in turn for ``--seconds`` and
prints, per rate, the answered rate, latency p50 and p95, and the median
latency of the window's first and last thirds: a backlog grows where the
last third waits far longer than the first. The cell's traffic file
holds the rate chosen from such a sweep (4/5 of the knee) as a number;
the benchmark's runs never search for one.
"""

import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

    import json

    import numpy as np

    from bench import harness, synth
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.cell_of(spec, args.workload)
    traffic = cell.traffic
    _, _, clock, genomes, svc = harness.prepare(cell, args.seed)

    from repro.obs import metrics as obs_metrics
    from repro.serving.scheduler import AsyncScheduler, SchedulerConfig
    loop = harness.load_module("loops", "open")
    for i, rate in enumerate(args.rates):
        window = harness.Window(clock=clock, registry=obs_metrics.DEFAULT)
        sched = AsyncScheduler(svc, SchedulerConfig(**traffic["scheduler"]),
                               on_batch=window.on_batch)
        loop.run(sched, synth.ReadStream(genomes, traffic, args.seed,
                                         stream=2 + i),
                 dict(traffic, rate_per_s=rate), args.seconds, window,
                 args.seed + i)
        sched.close()
        reqs = [r for r in window.requests if r.done is not None]
        lat = np.array([(r.done - r.scheduled) * 1e3 for r in reqs])
        third = max(len(lat) // 3, 1)
        last = max(r.done for r in reqs)
        print(json.dumps({
            "rate_per_s": rate, "answered_per_s":
                len(reqs) / (last - window.t_open),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "first_third_p50_ms": float(np.median(lat[:third])),
            "last_third_p50_ms": float(np.median(lat[-third:])),
            "in_window": harness.CompileClock.line(window.compiles)}),
            flush=True)
        time.sleep(1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
