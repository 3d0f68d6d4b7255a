"""Serving-v2 benchmark: requests/sec and latency under ragged streams.

Drives :class:`repro.serving.service.GeneSearchService` over a bit-sliced
archive index with a mixed-length request stream (short amplicon reads up
to full-length reads, three pow2 kmer buckets) and reports:

* **throughput** — requests/sec of the steady-state stream (median wall of
  the whole stream via the hardened ``benchmarks.common.timeit`` harness);
* **latency** — per-request p50/p95 in ms (each request charged the wall
  of the batch that served it — what a caller actually waits);
* **batching** — bucket occupancy, padding waste, and the compile-once
  proof: each (bucket, backend) pair must show exactly ONE compiled
  executable after the whole ragged stream.

A ``kmer_cache`` section re-times a deep-coverage **overlapping** stream
with the versioned membership cache on vs off (parity asserted in-bench,
lifetime hit rate recorded honestly — cold misses included).

``--smoke`` (CI) runs a small config and asserts the service is
bit-identical to direct engine ``msmt`` for both the ``jnp`` and
``idl_probe`` backends — and with the membership cache on vs off (with
hit_rate > 0) — so serving can't silently drift from the engines.

    PYTHONPATH=src python -m benchmarks.serve_bench [--smoke]

Writes ``BENCH_serve.json`` (full mode) next to the repo root.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_metadata, overlapping_stream, timeit
from repro import obs
from repro.core import idl
from repro.data import genome
from repro.index import BitSlicedIndex, ingest, lsm
from repro.index import state as state_mod
from repro.obs import metrics as obs_metrics
from repro.serving import GeneSearchService, KmerCacheConfig, ServiceConfig
from repro.serving.live import LiveGeneSearchService


def _build_index(m: int, n_files: int, genome_len: int) -> BitSlicedIndex:
    cfg = idl.IDLConfig(k=31, t=16, L=1 << 12, eta=3, m=m)
    eng = BitSlicedIndex.build(cfg, "idl", n_files=n_files)
    archive = genome.synth_archive(n_files=n_files, genome_len=genome_len,
                                   seed=42)
    return ingest.build_archive(eng, archive, read_len=230, chunk_reads=64)


def _request_stream(archive_reads, n_requests: int, seed: int):
    """Ragged stream: read lengths drawn from amplicon-to-full mix."""
    rng = np.random.default_rng(seed)
    lens = rng.choice([70, 110, 150, 230], size=n_requests,
                      p=[0.3, 0.3, 0.2, 0.2])
    picks = rng.integers(0, len(archive_reads), size=n_requests)
    return [np.asarray(archive_reads[p][:n]) for p, n in zip(picks, lens)]


def run(m: int, n_files: int, n_requests: int, iters: int,
        backend: str) -> dict:
    eng = _build_index(m, n_files, genome_len=3_000)
    archive = genome.synth_archive(n_files=n_files, genome_len=3_000, seed=42)
    pool = [f.reads(230, 4)[i % 4] for i, f in enumerate(archive)]
    stream = _request_stream(pool, n_requests, seed=7)
    svc = GeneSearchService(eng, ServiceConfig(backend=backend, max_batch=16))

    def serve_stream():
        svc.search(stream)
        return svc.state.words[0]          # block target for the harness

    stream_s = timeit(serve_stream, repeats=iters, warmup=2)
    lat = np.asarray(svc.request_latencies_ms()[-n_requests:])
    buckets = sorted({s.bucket for s in svc.batch_stats})
    compiles = svc.compile_counts()
    assert all(c == 1 for c in compiles.values()), (
        f"a bucket recompiled: {compiles}")
    return {
        "config": {
            "engine": "bitsliced", "scheme": "idl", "m": m,
            "n_files": n_files, "n_requests": n_requests,
            "backend": backend, "max_batch": 16,
            "device": jax.default_backend(),
        },
        "throughput_rps": round(n_requests / stream_s, 1),
        "latency_ms": {
            "p50": round(float(np.percentile(lat, 50)), 3),
            "p95": round(float(np.percentile(lat, 95)), 3),
            "mean": round(float(lat.mean()), 3),
        },
        "batching": {
            "buckets": buckets,
            "compiles_per_bucket": {str(b): c for b, c in compiles.items()},
            "occupancy": round(svc.occupancy(), 3),
            "batches": len(svc.batch_stats),
        },
    }


def run_cache(m: int, n_files: int, n_requests: int, iters: int) -> dict:
    """Membership cache on vs off over a deep-coverage overlapping stream.

    Parity is asserted in-bench before anything is timed (cache on ==
    cache off, bit for bit), and the reported hit rate is the cache's
    lifetime counter — cold-start misses included, nothing reset
    between passes.
    """
    eng = _build_index(m, n_files, genome_len=3_000)
    archive = genome.synth_archive(n_files=n_files, genome_len=3_000, seed=42)
    pool = [f.reads(230, 4)[i % 4] for i, f in enumerate(archive)]
    stream = overlapping_stream(pool, n_requests, seed=11,
                                read_len=230, region_len=600)

    plain = GeneSearchService(eng, ServiceConfig(max_batch=32))
    cached = GeneSearchService(
        eng, ServiceConfig(max_batch=32,
                           kmer_cache=KmerCacheConfig(capacity=1 << 17)))
    for a, b in zip(plain.search(stream), cached.search(stream)):
        np.testing.assert_array_equal(np.asarray(a.matches),
                                      np.asarray(b.matches))

    def serve(svc):
        def f():
            svc.search(stream)
            return svc.state.words[0]
        return f

    off_s = timeit(serve(plain), repeats=iters, warmup=1)
    off_p50 = float(np.percentile(
        np.asarray(plain.request_latencies_ms()[-n_requests:]), 50))
    on_s = timeit(serve(cached), repeats=iters, warmup=1)
    on_p50 = float(np.percentile(
        np.asarray(cached.request_latencies_ms()[-n_requests:]), 50))
    st = cached.cache_stats()
    assert st["hits"] > 0, st
    assert all(c == 1 for c in cached.compile_counts().values())
    return {
        "config": {
            "engine": "bitsliced", "scheme": "idl", "m": m,
            "n_files": n_files, "n_requests": n_requests,
            "stream": ("overlapping read_len=230 windows into 4 "
                       "concatenated 600bp regions"),
            "max_batch": 32, "cache_capacity": 1 << 17,
            "device": jax.default_backend(),
        },
        "throughput_rps": {
            "cache_off": round(n_requests / off_s, 1),
            "cache_on": round(n_requests / on_s, 1),
        },
        "latency_p50_ms": {
            "cache_off": round(off_p50, 3),
            "cache_on": round(on_p50, 3),
        },
        "speedup": round(off_s / on_s, 2),
        "hit_rate": round(st["hit_rate"], 4),
        "cache": st,
        "note": ("parity asserted in-bench before timing (cache on == "
                 "cache off, bit for bit); hit_rate is the cache's "
                 "lifetime counter — cold-start misses included"),
    }


def run_obs_overhead(m: int, n_files: int, n_requests: int,
                     iters: int) -> dict:
    """Observability tax: the same ragged stream served with the obs
    plane on (default) vs off (``obs.set_enabled(False)`` — every bound
    handle becomes a branch-and-return no-op).

    Parity is asserted BEFORE anything is timed: obs-on answers are bit-
    identical to obs-off answers, so instrumentation provably cannot
    change results. The overhead target (< 5% throughput) is the
    documented contract for every serving tier; it is asserted here so a
    hot-path regression fails the bench, not a production stream.
    """
    eng = _build_index(m, n_files, genome_len=3_000)
    archive = genome.synth_archive(n_files=n_files, genome_len=3_000,
                                   seed=42)
    pool = [f.reads(230, 4)[i % 4] for i, f in enumerate(archive)]
    stream = _request_stream(pool, n_requests, seed=7)
    # idl_probe so the timed path includes the planner's locality
    # recording — the heaviest per-batch instrumentation we ship
    svc = GeneSearchService(eng, ServiceConfig(backend="idl_probe",
                                               max_batch=16))

    obs.set_enabled(True)
    res_on = svc.search(stream)
    obs.set_enabled(False)
    res_off = svc.search(stream)
    obs.set_enabled(True)
    for a, b in zip(res_on, res_off):
        np.testing.assert_array_equal(np.asarray(a.matches),
                                      np.asarray(b.matches))

    def pass_s(enabled: bool):
        """One timed pass: (wall_s, cpu_s). CPU time drives the overhead
        ratio (immune to preemption by other processes — on a contended
        box single-pass wall carries ~±10% scheduler jitter that no
        amount of pairing fully cancels); wall reports throughput."""
        obs.set_enabled(enabled)
        w0 = time.perf_counter()
        c0 = time.process_time()
        svc.search(stream)
        _ = svc.state.words[0]
        return time.perf_counter() - w0, time.process_time() - c0

    # paired interleaved protocol: this box's wall clock drifts 2-3x
    # between runs, so back-to-back on/off pairs (whose drift cancels)
    # are the only honest estimator at this timescale — never
    # on-block-then-off-block. ONE service instance serves both sides
    # (set_enabled flips live handles), so instance asymmetry (compile
    # caches, memory layout) can't read as overhead, pair order
    # alternates to cancel order bias, and GC is held off so collection
    # pauses don't land on one side. The estimator is a 20%-trimmed
    # mean of per-pair CPU-time ratios; on a breach one extra round is
    # pooled in before judging, so a single unlucky batch of pairs
    # can't fail a hot path whose true cost is microbenchmark-verified
    # at a few microseconds per request.
    import gc

    def measure_pairs(n_pairs: int) -> list:
        out = []
        gc.collect()
        gc.disable()
        try:
            for i in range(n_pairs):
                if i % 2 == 0:
                    on_i = pass_s(True)
                    off_i = pass_s(False)
                else:
                    off_i = pass_s(False)
                    on_i = pass_s(True)
                out.append((on_i, off_i))
        finally:
            gc.enable()
            obs.set_enabled(True)
        return out

    def trimmed_mean_ratio(pairs: list) -> float:
        ratios = sorted(on_i[1] / off_i[1] for on_i, off_i in pairs)
        k = len(ratios) // 5
        kept = ratios[k:len(ratios) - k] if len(ratios) > 2 * k else ratios
        return sum(kept) / len(kept)

    for _ in range(4):                           # warm
        pass_s(True)
        pass_s(False)
    pairs = measure_pairs(max(7 * iters, 21))
    overhead_pct = 100.0 * (trimmed_mean_ratio(pairs) - 1.0)
    if overhead_pct >= 5.0:                      # pool one re-measure round
        pairs += measure_pairs(max(7 * iters, 21))
        overhead_pct = 100.0 * (trimmed_mean_ratio(pairs) - 1.0)
    on_s = float(np.median([p[0][0] for p in pairs]))
    off_s = float(np.median([p[1][0] for p in pairs]))
    assert overhead_pct < 5.0, (
        f"obs overhead {overhead_pct:.2f}% breached the 5% contract "
        f"(trimmed-mean paired CPU ratio over {len(pairs)} pairs; "
        f"wall on={on_s:.4f}s off={off_s:.4f}s)")
    return {
        "config": {
            "engine": "bitsliced", "scheme": "idl", "m": m,
            "n_files": n_files, "n_requests": n_requests,
            "backend": "idl_probe", "max_batch": 16,
            "device": jax.default_backend(),
        },
        "throughput_rps": {
            "obs_on": round(n_requests / on_s, 1),
            "obs_off": round(n_requests / off_s, 1),
        },
        "overhead_pct": round(overhead_pct, 2),
        "target_pct": 5.0,
        "note": ("parity asserted in-bench before timing (obs-on answers "
                 "== obs-off, bit for bit); overhead is a trimmed mean of "
                 "back-to-back paired on/off CPU-time ratios on one "
                 "service (drift- and preemption-immune), negative = "
                 "noise floor; throughput_rps reports wall medians"),
    }


def run_live_locality(m: int, n_files: int, n_requests: int) -> dict:
    """Live IDL vs RH locality, measured at the serving source.

    Two identical live services (base + delta, ``idl_probe`` backend)
    serve the SAME interleaved stream of queries and writes — one over an
    IDL-hashed index, one over RH. The planner records the planned tile
    bytes of every executed batch into the obs registry
    (``locality.planned_tile_bytes``), so the ratio below is what the
    serving stack actually planned to fetch, not an offline model: IDL's
    co-located probes coalesce into a few long runs where RH scatters
    into near-one-probe runs.
    """
    archive = genome.synth_archive(n_files=n_files, genome_len=3_000,
                                   seed=42)
    pool = [f.reads(230, 4)[i % 4] for i, f in enumerate(archive)]
    stream = _request_stream(pool, n_requests, seed=7)
    per = {}
    for scheme in ("idl", "rh"):
        cfg = idl.IDLConfig(k=31, t=16, L=1 << 12, eta=3, m=m)
        eng = BitSlicedIndex.build(cfg, scheme, n_files=n_files)
        eng = ingest.build_archive(eng, archive, read_len=230,
                                   chunk_reads=64)
        svc = LiveGeneSearchService(
            lsm.LiveIndex(state_mod.from_engine(eng)),
            ServiceConfig(backend="idl_probe", max_batch=16))
        obs_metrics.reset()
        # a LIVE stream: writes land between query batches, so the
        # recorded locality includes post-write (base + delta) probes
        step = max(1, len(stream) // 4)
        for i, lo in enumerate(range(0, len(stream), step)):
            svc.search(stream[lo:lo + step])
            src = archive[i % n_files]
            svc.apply_insert(np.asarray(src.genome)[None, :230],
                             np.asarray([src.file_id], dtype=np.int32))
        snap = obs_metrics.DEFAULT.snapshot()
        where = {"scheme": scheme, "op": "query"}
        tb = obs_metrics.counter_total(
            snap, "locality.planned_tile_bytes", where=where)
        probes = obs_metrics.counter_total(
            snap, "locality.probes", where=where)
        per[scheme] = {
            "planned_tile_bytes": int(tb),
            "probes": int(probes),
            "probe_runs": int(obs_metrics.counter_total(
                snap, "locality.probe_runs", where=where)),
            "batches": int(obs_metrics.counter_total(
                snap, "locality.batches", where=where)),
            "bytes_per_probe": round(tb / max(probes, 1), 1),
        }
    ratio = (per["rh"]["planned_tile_bytes"]
             / per["idl"]["planned_tile_bytes"])
    assert ratio >= 2.0, (
        f"live IDL should touch >= 2x fewer planned tile bytes than RH, "
        f"got {ratio:.2f}x: {per}")
    return {
        "config": {
            "engine": "bitsliced", "m": m, "n_files": n_files,
            "n_requests": n_requests, "backend": "idl_probe",
            "max_batch": 16, "writes": 4,
            "device": jax.default_backend(),
        },
        "idl": per["idl"],
        "rh": per["rh"],
        "tile_bytes_ratio_rh_over_idl": round(ratio, 2),
        "note": ("same interleaved query+write stream over live (base + "
                 "delta) services; tile bytes read from the obs "
                 "registry's locality.planned_tile_bytes counters — the "
                 "bytes the executed probe plans fetch, per scheme"),
    }


def _assert_parity(m: int) -> None:
    """Service answers == direct engine msmt, jnp and idl_probe backends."""
    eng = _build_index(m, n_files=16, genome_len=1_200)
    archive = genome.synth_archive(n_files=16, genome_len=1_200, seed=42)
    stream = _request_stream([f.reads(230, 2)[0] for f in archive], 12,
                             seed=3)
    for backend in ("jnp", "idl_probe"):
        svc = GeneSearchService(eng, ServiceConfig(backend=backend,
                                                   max_batch=4))
        for q, res in zip(stream, svc.search(stream)):
            want = np.asarray(eng.msmt(jnp.asarray(q)[None]))[0]
            np.testing.assert_array_equal(np.asarray(res.matches), want)
        assert all(c == 1 for c in svc.compile_counts().values())
    print("parity: service == engine msmt (jnp, idl_probe); "
          "one compile per bucket")


def _assert_cache_parity(m: int) -> None:
    """Cache on == cache off on an overlapping stream, hits observed."""
    eng = _build_index(m, n_files=16, genome_len=1_200)
    archive = genome.synth_archive(n_files=16, genome_len=1_200, seed=42)
    pool = [f.reads(230, 2)[0] for f in archive]
    stream = overlapping_stream(pool, 24, seed=11)
    plain = GeneSearchService(eng, ServiceConfig(max_batch=4))
    cached = GeneSearchService(
        eng, ServiceConfig(max_batch=4,
                           kmer_cache=KmerCacheConfig(capacity=1 << 14)))
    for _ in range(2):                 # pass 2 answers from cached rows
        for a, b in zip(plain.search(stream), cached.search(stream)):
            np.testing.assert_array_equal(np.asarray(a.matches),
                                          np.asarray(b.matches))
    st = cached.cache_stats()
    assert st["hits"] > 0 and st["hit_rate"] > 0, st
    assert all(c == 1 for c in cached.compile_counts().values())
    print(f"cache parity: membership cache on == off (bit-identical); "
          f"hit_rate={st['hit_rate']:.2f} > 0; one compile per bucket")


def _assert_obs_smoke(m: int) -> None:
    """Obs-on answers == obs-off answers on a small stream, and the obs
    plane actually records: pipeline spans emitted, locality counters
    fed by the planner."""
    from repro.obs import trace as obs_trace
    eng = _build_index(m, n_files=16, genome_len=1_200)
    archive = genome.synth_archive(n_files=16, genome_len=1_200, seed=42)
    stream = _request_stream([f.reads(230, 2)[0] for f in archive], 12,
                             seed=3)
    svc_cfg = ServiceConfig(backend="idl_probe", max_batch=4)
    obs.reset()
    on = GeneSearchService(eng, svc_cfg).search(stream)
    snap = obs_metrics.DEFAULT.snapshot()
    assert obs_metrics.counter_total(
        snap, "serving.requests") >= len(stream), snap["counters"]
    assert obs_metrics.counter_total(
        snap, "locality.planned_tile_bytes", where={"op": "query"}) > 0
    names = {r["name"] for r in obs_trace.DEFAULT.records()}
    assert {"request", "queue_wait", "assemble", "execute",
            "finalize"} <= names, names
    obs.set_enabled(False)
    try:
        off = GeneSearchService(eng, svc_cfg).search(stream)
    finally:
        obs.set_enabled(True)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(np.asarray(a.matches),
                                      np.asarray(b.matches))
    print("obs smoke: obs-on == obs-off (bit-identical); pipeline spans "
          "+ locality counters recorded")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small config; assert parity; no JSON written")
    args = ap.parse_args()

    if args.smoke:
        _assert_parity(m=1 << 18)
        _assert_cache_parity(m=1 << 18)
        _assert_obs_smoke(m=1 << 18)
        res = run(m=1 << 18, n_files=16, n_requests=24, iters=2,
                  backend="jnp")
        print("smoke:", json.dumps(res["latency_ms"]))
        return

    _assert_parity(m=1 << 20)
    _assert_cache_parity(m=1 << 20)
    _assert_obs_smoke(m=1 << 20)
    res = {
        backend: run(m=1 << 21, n_files=64, n_requests=96, iters=3,
                     backend=backend)
        for backend in ("jnp", "idl_probe")
    }
    res["kmer_cache"] = run_cache(m=1 << 21, n_files=256, n_requests=192,
                                  iters=3)
    res["obs_overhead"] = run_obs_overhead(m=1 << 20, n_files=256,
                                           n_requests=96, iters=3)
    res["live_locality"] = run_live_locality(m=1 << 20, n_files=32,
                                             n_requests=64)
    res["host"] = bench_metadata()
    out_path = pathlib.Path(__file__).resolve().parent.parent / "BENCH_serve.json"
    out_path.write_text(json.dumps(res, indent=2) + "\n")
    print(json.dumps(res, indent=2))
    print(f"\nwrote {out_path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    main()
