"""Shared benchmark harness utilities.

Metrics reported per paper experiment:
  * FPR           — paper's quality metric (identical definitions).
  * page-miss     — LRU cache model at 4-KiB fetch granularity (the unit of
                    the paper's locality mechanism; see DESIGN.md §2).
  * line-miss     — same model at 64-B lines (the paper's Valgrind setting).
  * block DMAs    — TPU metric: HBM→VMEM tile fetches the Pallas probe
                    kernel would issue for the trace (1 resident tile/rep).
  * wall time     — wall-clock of the jitted JAX implementation on this CPU
                    (reported for completeness; the locality effect on real
                    hardware is captured by the miss/DMA columns, which is
                    what Valgrind measured in the paper too).
"""

from __future__ import annotations

import os
import platform
import time

import jax
import numpy as np

from repro.core import cache_model


def bench_metadata() -> dict:
    """Host/build provenance stamped into every ``BENCH_*.json``.

    Scaling numbers are meaningless without the hardware they ran on —
    a 2-shard fleet on a 1-core CI box CANNOT beat one shard, and the
    record has to say so. First slice of the cross-arch harness
    (ROADMAP: same benches, many boxes, keyed by this metadata).
    """
    return {
        "cpu_count": os.cpu_count(),
        "platform": platform.platform(),
        "machine": platform.machine(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "numpy": np.__version__,
        "jax_backend": jax.default_backend(),
        "jax_device_count": jax.device_count(),
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


def overlapping_stream(pool, n_requests: int, seed: int,
                       n_regions: int = 4, read_len: int = 120,
                       region_len: int | None = None):
    """Deep-coverage shotgun stream: every request is a random window
    into one of ``n_regions`` source regions, so consecutive requests
    re-probe mostly the same kmers — the regime intra-batch dedup and
    the serving membership cache are built for. Shared by the serving
    benches so their cache-on/cache-off numbers describe one workload.

    Regions default to single pool reads; pass ``region_len`` to build
    longer loci by concatenating pool reads, so full-length
    (``read_len``-sized) requests still overlap each other instead of
    all being the same read.
    """
    rng = np.random.default_rng(seed)
    if region_len is None:
        regions = [np.asarray(pool[i % len(pool)]) for i in range(n_regions)]
    else:
        regions = []
        for i in range(n_regions):
            parts, j, total = [], i, 0
            while total < region_len:
                part = np.asarray(pool[j % len(pool)])
                parts.append(part)
                total += part.shape[0]
                j += n_regions
            regions.append(np.concatenate(parts)[:region_len])
    out = []
    for _ in range(n_requests):
        g = regions[int(rng.integers(0, n_regions))]
        s = int(rng.integers(0, max(1, g.shape[0] - read_len + 1)))
        out.append(g[s:s + read_len])
    return out


def timeit(fn, *args, repeats: int = 7, warmup: int = 2) -> float:
    """Median wall seconds of fn(*args) after jit warmup.

    Two warmup calls (the first compiles, the second settles allocator and
    cache state) and median-of-7 by default: medians of too few repeats on
    a noisy shared CPU were the dominant error in early BENCH_query.json
    numbers. Raise ``repeats`` further for sub-ms kernels.
    """
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def timeit_ms(fn, *args, repeats: int = 7, warmup: int = 2) -> float:
    """Median wall milliseconds of fn(*args) after warmup."""
    return timeit(fn, *args, repeats=repeats, warmup=warmup) * 1e3


def locality_metrics(locs: np.ndarray, L: int,
                     l1_bytes: int = 2 << 20) -> dict[str, float]:
    trace = cache_model.probe_trace_from_locations(locs)
    page_miss, _ = cache_model.two_level_miss_rates(
        trace, l1_bytes=l1_bytes, line_bytes=4096)
    line_miss, _ = cache_model.two_level_miss_rates(
        trace, l1_bytes=l1_bytes, line_bytes=64)
    dmas = cache_model.count_block_dmas_partitioned(locs, L)
    return {
        "page_miss": page_miss,
        "line_miss": line_miss,
        "dma_switches": dmas["switches"],
        "dma_per_probe": dmas["switches"] / max(dmas["accesses"], 1),
    }


class Csv:
    def __init__(self, name: str, cols: list[str]):
        self.name = name
        self.cols = cols
        print(f"\n== {name} ==")
        print(",".join(cols))

    def row(self, *vals):
        out = []
        for v in vals:
            if isinstance(v, float):
                out.append(f"{v:.6g}")
            else:
                out.append(str(v))
        print(",".join(out))
