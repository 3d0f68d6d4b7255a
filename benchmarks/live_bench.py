"""Live-index benchmark: ingest-while-serving under a mixed Poisson stream.

The new workload the LSM subsystem opens: a :class:`LiveReplicaRouter`
(2 replicas, write-ahead journal, base+delta per replica) serves a
**90/10 read/write** Poisson stream. Measured:

* **insert-to-searchable latency** — scheduled write arrival to the moment
  the write's ``InsertAck`` has resolved on EVERY replica (at which point
  the read is answerable fleet-wide: acks carry the ``(base_version,
  delta_seq)`` watermark the result stamps prove). Coordinated-omission-
  safe: latency is measured against the scheduled Poisson arrival, so a
  write path that falls behind is charged its queueing delay.
* **query p50 under the mixed stream** — same CO-safe accounting, while
  10% of arrivals are writes mutating every replica's delta.
* **before/after compaction** — the same query-only probe stream timed
  with the delta holding all absorbed writes, then again after
  ``router.compact()`` folds delta into base (delta empty, version+1).
  Compile counts are asserted ==1 per (bucket, backend) per replica
  across the compaction — the publish must not recompile anything.

Honest contention notes (recorded in the JSON): this box has one XLA:CPU
device, so (a) the two-probe merged query pays its extra probe on the
same device the writes scatter into — insert and query latencies contend
end-to-end; (b) the compaction merge is "off the hot path" logically
(queries keep answering from the frozen pair) but physically shares the
device, so mid-compaction latencies bulge; (c) insert-to-searchable
includes the scheduler flusher tick (``max_delay_ms``), which dominates
when the box is idle. Wall-clock on this host swings 2-3x run-to-run;
medians over the whole stream, not single shots.

A ``kmer_cache`` section re-times a deep-coverage overlapping stream —
served with a non-empty delta, so cached BASE rows merge with a fresh
delta probe every batch — with the membership cache on vs off (parity
asserted in-bench, lifetime hit rate recorded honestly).

``--smoke`` (CI) asserts, with no JSON written: the live fleet answers
bit-identically to a single-index oracle holding the union of all inserts
(including queries racing a mid-stream compaction), zero dropped futures,
and zero recompiles across the compaction swap — with the membership
cache off AND on (hit_rate > 0, compaction publishes invalidate).

    PYTHONPATH=src python -m benchmarks.live_bench [--smoke]

Writes ``BENCH_live.json`` (full mode) next to the repo root.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_metadata, overlapping_stream, timeit
from repro.core import idl
from repro.data import genome
from repro.index import BitSlicedIndex, ingest
from repro.serving import (
    GeneSearchService,
    KmerCacheConfig,
    LiveReplicaRouter,
    RouterConfig,
    SchedulerConfig,
    ServiceConfig,
)


# p50 insert-to-searchable from the checked-in BENCH_live.json recorded
# BEFORE LiveIndex.insert donated its delta into the scatter (every write
# paid a defensive whole-delta copy so a concurrent compaction plan could
# keep the old buffer; plan_compaction now takes its own copy instead —
# one copy per compaction, not one per write).
_P50_BEFORE_DONATION_MS = 132.986


def _build_base(m: int, n_files: int, genome_len: int) -> BitSlicedIndex:
    cfg = idl.IDLConfig(k=31, t=16, L=1 << 12, eta=3, m=m)
    eng = BitSlicedIndex.build(cfg, "idl", n_files=n_files)
    archive = genome.synth_archive(n_files=n_files, genome_len=genome_len,
                                   seed=42)
    return ingest.build_archive(eng, archive, read_len=230, chunk_reads=64)


def _mixed_stream(pool, fresh_reads, n_requests: int, write_frac: float,
                  rps: float, seed: int):
    """(kind, payload, gap) replay: ragged queries + single-read writes."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rps, size=n_requests)
    events = []
    w = 0
    for i in range(n_requests):
        if rng.random() < write_frac and w < len(fresh_reads):
            events.append(("write", fresh_reads[w], gaps[i]))
            w += 1
        else:
            n = int(rng.choice([70, 110, 150, 230],
                               p=[0.3, 0.3, 0.2, 0.2]))
            q = pool[int(rng.integers(0, len(pool)))]
            events.append(("query", np.asarray(q[:n]), gaps[i]))
    return events


def _replay_timed(router: LiveReplicaRouter, events) -> dict:
    """CO-safe paced replay with per-write searchable stamps."""
    q_lat, w_done, w_sched, futures = [], {}, [], []
    sched_t = time.perf_counter()
    for kind, payload, gap in events:
        sched_t += gap
        now = time.perf_counter()
        if now < sched_t:
            time.sleep(sched_t - now)
        if kind == "query":
            slot = len(q_lat)
            q_lat.append(np.nan)
            fut = router.submit(payload)
            fut.add_done_callback(
                lambda f, i=slot, s=sched_t: q_lat.__setitem__(
                    i, (time.perf_counter() - s) * 1e3))
            futures.append(fut)
        else:
            reads, fids = payload
            wid = len(w_sched)
            w_sched.append(sched_t)
            acks = router.insert(reads, fids)
            stamps = w_done.setdefault(wid, [])
            for a in acks:
                # list.append is atomic under the GIL; searchable = the
                # LAST replica's ack, resolved as max() after the drain
                a.add_done_callback(
                    lambda f, s=stamps: s.append(time.perf_counter()))
            futures.extend(acks)
    router.drain()
    for f in futures:
        f.result(timeout=120)          # zero dropped — raises otherwise
    w_ms = np.asarray([(max(w_done[i]) - s) * 1e3
                       for i, s in enumerate(w_sched)])
    return {"query_ms": np.asarray(q_lat), "write_ms": w_ms}


def _pcts(a: np.ndarray) -> dict:
    return {"p50": round(float(np.percentile(a, 50)), 3),
            "p99": round(float(np.percentile(a, 99)), 3)}


def _assert_compile_once(router) -> dict:
    counts = router.compile_counts()
    for per in counts.values():
        assert all(c == 1 for c in per.values()), (
            f"a bucket recompiled: {counts}")
    return {str(rid): {str(b): c for b, c in per.items()}
            for rid, per in counts.items()}


def run(m: int, n_files: int, n_requests: int, rps: float,
        n_replicas: int) -> dict:
    eng = _build_base(m, n_files, genome_len=3_000)
    archive = genome.synth_archive(n_files=n_files, genome_len=3_000,
                                   seed=42)
    pool = [f.reads(230, 4)[i % 4] for i, f in enumerate(archive)]
    # fresh material: reads the base has never seen, written into
    # existing file columns under traffic
    fresh = genome.synth_archive(n_files=8, genome_len=3_000, seed=77)
    rng = np.random.default_rng(3)
    fresh_reads = [
        (np.asarray(f.reads(230, 1)[0])[None],
         np.asarray([int(rng.integers(0, n_files))], dtype=np.int32))
        for f in fresh for _ in range(6)]

    with tempfile.TemporaryDirectory() as tmp:
        router = LiveReplicaRouter(
            eng, ServiceConfig(max_batch=16),
            RouterConfig(n_replicas=n_replicas,
                         scheduler=SchedulerConfig(max_delay_ms=2.0)),
            journal_path=str(pathlib.Path(tmp) / "wal.bin"))
        try:
            # warmup compiles (all query buckets + the write path)
            for q in (pool[0][:70], pool[0][:110], pool[0][:150],
                      pool[0][:230]):
                router.submit(np.asarray(q)).result(timeout=120)
            for a in router.insert(*fresh_reads[0]):
                a.result(timeout=120)

            events = _mixed_stream(pool, fresh_reads[1:], n_requests,
                                   write_frac=0.1, rps=rps, seed=7)
            mixed = _replay_timed(router, events)

            # before/after compaction: identical query-only probe stream
            probe = _mixed_stream(pool, [], n_requests // 2,
                                  write_frac=0.0, rps=rps, seed=8)
            pre = _replay_timed(router, probe)
            delta_before = router.delta_batches()
            t0 = time.perf_counter()
            version = router.compact()
            compact_s = time.perf_counter() - t0
            post = _replay_timed(router, probe)
            compiles = _assert_compile_once(router)
        finally:
            router.close()

    return {
        "config": {
            "engine": "bitsliced", "scheme": "idl", "m": m,
            "n_files": n_files, "n_requests": n_requests,
            "write_frac": 0.1, "offered_rps": rps,
            "n_replicas": n_replicas, "max_batch": 16,
            "max_delay_ms": 2.0, "device": jax.default_backend(),
        },
        "mixed_stream_90_10": {
            "query_ms": _pcts(mixed["query_ms"]),
            "insert_to_searchable_ms": _pcts(mixed["write_ms"]),
            "n_queries": int(len(mixed["query_ms"])),
            "n_writes": int(len(mixed["write_ms"])),
        },
        "delta_donation": {
            "insert_to_searchable_p50_ms_before": _P50_BEFORE_DONATION_MS,
            "insert_to_searchable_p50_ms_after": _pcts(
                mixed["write_ms"])["p50"],
            "note": ("before = the last record with the non-donated write "
                     "path (every insert copied the whole delta); after = "
                     "this run, with LiveIndex.insert donating the delta "
                     "into the scatter under the single-writer flusher"),
        },
        "compaction": {
            "delta_batches_folded": delta_before,
            "published_version": version,
            "compact_wall_s": round(compact_s, 3),
            "query_ms_before": _pcts(pre["query_ms"]),
            "query_ms_after": _pcts(post["query_ms"]),
            "compiles_per_bucket": compiles,
        },
        "notes": [
            "single XLA:CPU device: the delta probe and the write scatter "
            "share the serving device, so insert and query latencies "
            "contend end-to-end; on a multi-device host the delta is "
            "replica-local and the merge runs off-device",
            "insert-to-searchable = scheduled Poisson arrival -> last "
            "replica's InsertAck; includes the 2ms flusher tick, which "
            "dominates at low offered load",
            "compaction merge shares the device with serving on this box "
            "('off the hot path' is logical, not physical here) — the "
            "before/after query p50 gap, not mid-compaction latency, is "
            "the stable signal; wall-clock swings 2-3x run-to-run",
            "offered_rps sits below this box's saturation point (both "
            "replicas' scatters serialize on the one device); the write "
            "path now donates the delta into the scatter — the "
            "whole-delta defensive copy is gone (see delta_donation; "
            "plan_compaction snapshots its own copy, once per fold, not "
            "once per write); past saturation, CO-safe accounting "
            "correctly reports seconds of queueing delay rather than "
            "service latency",
        ],
    }


def run_cache(m: int, n_files: int, n_requests: int, iters: int) -> dict:
    """Membership cache on vs off on the LIVE router.

    The stream is deep-coverage overlapping windows served with a
    non-empty delta, so the cached path serves merged base|delta rows
    from the front cache keyed (version, delta_seq), with the
    version-keyed base-row cache behind it. Parity (cache on == cache
    off, bit for bit) is asserted before anything is timed; hit rate is
    the caches' lifetime counter — cold misses included.
    """
    eng = _build_base(m, n_files, genome_len=3_000)
    archive = genome.synth_archive(n_files=n_files, genome_len=3_000,
                                   seed=42)
    pool = [f.reads(230, 4)[i % 4] for i, f in enumerate(archive)]
    stream = overlapping_stream(pool, n_requests, seed=11,
                                read_len=460, region_len=600)
    fresh = genome.synth_archive(n_files=4, genome_len=3_000, seed=77)
    rng = np.random.default_rng(3)
    writes = [(np.asarray(f.reads(230, 1)[0])[None],
               np.asarray([int(rng.integers(0, n_files))], dtype=np.int32))
              for f in fresh]

    def drive(svc_cfg):
        with tempfile.TemporaryDirectory() as tmp:
            # One replica: on this 1-core box two worker threads
            # interleaving on the same CPU add ~2x wall-clock noise,
            # which is larger than the effect being measured. The cache
            # mechanics are identical at any fleet size (asserted by
            # tests/test_kmer_cache.py across a 2-replica router).
            router = LiveReplicaRouter(
                eng, svc_cfg,
                RouterConfig(n_replicas=1,
                             scheduler=SchedulerConfig(max_delay_ms=2.0)),
                journal_path=str(pathlib.Path(tmp) / "wal.bin"))
            try:
                for r, f in writes:        # delta live: two-probe path
                    for a in router.insert(r, f):
                        a.result(timeout=120)

                # Pause dispatch while submitting so batch formation is
                # identical for both configs: a fast (cached) execute
                # otherwise outruns the single submitting thread, and the
                # deadline flusher serves tiny batches whose fixed
                # per-batch cost swamps the probe savings being measured.
                scheds = [rep.scheduler for rep in router._replicas]

                def closed_loop():
                    for s in scheds:
                        s.pause()
                    futs = [router.submit(q) for q in stream]
                    for s in scheds:
                        s.resume()
                    router.drain()
                    for fu in futs:
                        fu.result(timeout=120)

                secs = timeit(closed_loop, repeats=iters, warmup=1)
                results = router.search(stream)
                _assert_compile_once(router)
                return secs, results, router.cache_stats()
            finally:
                router.close()

    off_s, ref, cs_off = drive(ServiceConfig(max_batch=32))
    assert cs_off is None
    on_s, got, cs_on = drive(ServiceConfig(
        max_batch=32, kmer_cache=KmerCacheConfig(capacity=1 << 17)))
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a.matches),
                                      np.asarray(b.matches))
    assert cs_on["hits"] > 0, cs_on
    return {
        "config": {
            "engine": "bitsliced", "scheme": "idl", "m": m,
            "n_files": n_files, "n_requests": n_requests,
            "n_replicas": 1, "n_delta_writes": len(writes),
            "stream": ("overlapping read_len=460 windows into 4 "
                       "concatenated 600bp regions"),
            "max_batch": 32, "cache_capacity": 1 << 17,
            "device": jax.default_backend(),
        },
        "throughput_rps": {
            "cache_off": round(n_requests / off_s, 1),
            "cache_on": round(n_requests / on_s, 1),
        },
        "speedup": round(off_s / on_s, 2),
        "hit_rate": round(cs_on["hit_rate"], 4),
        "cache": cs_on,
        "note": ("served with a non-empty delta: the front cache holds "
                 "merged base|delta rows keyed (version, delta_seq); a "
                 "write drops only those, and the version-keyed base-row "
                 "cache backfills without re-probing — parity vs "
                 "cache-off asserted in-bench before timing; hit_rate is "
                 "lifetime over both stores, cold misses included; one "
                 "replica and paused-submit batching so a 1-core host "
                 "measures serving capacity, not thread interleaving"),
    }


# ---------------------------------------------------------------------------
# Smoke: live fleet == union-index oracle, mid-compaction, zero recompiles.
# ---------------------------------------------------------------------------

def _smoke(m: int = 1 << 16) -> None:
    cfg = idl.IDLConfig(k=31, t=16, L=1 << 10, eta=2, m=m)
    rng = np.random.default_rng(5)
    base_reads = jnp.asarray(rng.integers(0, 4, size=(3, 150),
                                          dtype=np.uint8))
    write_reads = [rng.integers(0, 4, size=(1, 150), dtype=np.uint8)
                   for _ in range(6)]
    write_fids = [np.asarray([int(rng.integers(0, 24))], dtype=np.int32)
                  for _ in range(6)]
    eng = BitSlicedIndex.build(cfg, "idl", n_files=24).insert_batch(
        base_reads, np.arange(3))

    # oracle: ONE index holding the union of base + every write
    oracle = BitSlicedIndex.build(cfg, "idl", n_files=24).insert_batch(
        base_reads, np.arange(3))
    for r, f in zip(write_reads, write_fids):
        oracle = oracle.insert_batch(jnp.asarray(r), f)
    queries = [np.asarray(base_reads[i % 3][:n])
               for i, n in enumerate((70, 110, 150, 150, 70, 110))]
    queries += [w[0] for w in write_reads]
    want = GeneSearchService(oracle, ServiceConfig(max_batch=4)
                             ).search(queries)

    def drive(svc_cfg):
        """The interleaved write+query stream with a mid-stream fold;
        returns the router's merged cache stats (None = cache off)."""
        with tempfile.TemporaryDirectory() as tmp:
            router = LiveReplicaRouter(
                eng, svc_cfg,
                RouterConfig(n_replicas=2,
                             scheduler=SchedulerConfig(max_delay_ms=0.5)),
                journal_path=str(pathlib.Path(tmp) / "wal.bin"))
            try:
                futures = []
                # concurrent write+query load, compact mid-stream
                for i, (r, f) in enumerate(zip(write_reads, write_fids)):
                    futures += [router.submit(q) for q in queries[:3]]
                    futures += router.insert(r, f)
                    if i == 3:
                        assert router.compact() == 1   # mid-stream fold
                router.drain()
                for fut in futures:
                    fut.result(timeout=120)            # zero dropped
                got = router.search(queries)           # writes absorbed
                for g, w in zip(got, want):
                    np.testing.assert_array_equal(np.asarray(g.matches),
                                                  np.asarray(w.matches))
                assert router.compact() == 2           # fold the rest
                got = router.search(queries)
                for g, w in zip(got, want):
                    np.testing.assert_array_equal(np.asarray(g.matches),
                                                  np.asarray(w.matches))
                _assert_compile_once(router)
                assert router.delta_batches() == 0
                return router.cache_stats()
            finally:
                router.close()

    assert drive(ServiceConfig(max_batch=4)) is None
    cs = drive(ServiceConfig(max_batch=4,
                             kmer_cache=KmerCacheConfig(capacity=1 << 14)))
    assert cs["hits"] > 0 and cs["hit_rate"] > 0, cs
    assert cs["invalidations"] >= 2, cs   # both publishes flushed caches
    print("smoke: live fleet == union-index oracle (incl. mid-compaction), "
          "membership cache on AND off; zero dropped futures; one compile "
          "per bucket per replica across 2 compactions; cache hit_rate="
          f"{cs['hit_rate']:.2f} with {cs['invalidations']} compaction "
          "invalidations")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="parity vs oracle + zero-drop + compile-once; "
                         "no JSON")
    args = ap.parse_args()

    if args.smoke:
        _smoke()
        return

    res = run(m=1 << 22, n_files=64, n_requests=256, rps=25,
              n_replicas=2)
    res["kmer_cache"] = run_cache(m=1 << 21, n_files=256, n_requests=768,
                                  iters=7)
    res["host"] = bench_metadata()
    out_path = pathlib.Path(
        __file__).resolve().parent.parent / "BENCH_live.json"
    out_path.write_text(json.dumps(res, indent=2) + "\n")
    print(json.dumps(res, indent=2))
    print(f"\nwrote {out_path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    main()
