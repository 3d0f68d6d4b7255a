"""Serving-cluster benchmark: sync service vs async scheduler vs router.

Drives the same ragged Poisson request stream through three serving tiers
over one bit-sliced archive index, with *serving* semantics: a caller
wants each answer as its request completes, not at end-of-stream.

* **sync** — the PR-4 :class:`GeneSearchService` as a synchronous server.
  With no background flusher, a sync caller only gets timely results by
  flushing per request (otherwise results appear at end-of-stream — an
  unbounded latency, not serving). Configured at its per-request optimum
  (``max_batch=1``: the smallest compiled step per bucket).
* **async** — one :class:`AsyncScheduler` over the same index: futures +
  deadline flusher + double-buffered pipeline. Requests batch up to 16
  WITHOUT blocking the caller — the thing a synchronous API cannot do.
* **router** — :class:`ReplicaRouter` over 2 scheduler-fronted
  ``IndexState`` replicas (closed-loop rps recorded for every routing
  policy).

Metrics: closed-loop **throughput** (requests/sec to answer the whole
stream, median via ``benchmarks.common.timeit``) and open-loop **latency**
(p50/p99 of completion − *scheduled* Poisson arrival at a fixed offered
rate — coordinated-omission-safe, so a tier that falls behind the arrival
process shows its real queueing delay).

Context for reading the numbers: this CI box has 2 cores and one XLA:CPU
device, where concurrent replica steps contend (device execution is
in-order per device); the async tier therefore tops closed-loop
throughput and the router's replica scaling pays off only on multi-core /
multi-device hosts. The acceptance bar — router ≥ 1.5x the synchronous
single service on the same stream — holds with a wide margin because the
cluster tiers batch; the sync tier cannot.

``--smoke`` (CI) asserts the cluster cannot drift from the engines: the
router answers bit-identically to a direct single-service run across
2 engines × {idl, rh} schemes, with compile counts per (bucket, backend)
== 1 per replica, plus a live hot-swap with zero dropped futures.

    PYTHONPATH=src python -m benchmarks.cluster_bench [--smoke]

Writes ``BENCH_cluster.json`` (full mode) next to the repo root.
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

from benchmarks.common import bench_metadata, timeit
from repro.core import idl
from repro.data import genome
from repro.index import BitSlicedIndex, CobsIndex, ingest, store
from repro.serving import (
    AsyncScheduler,
    GeneSearchService,
    ReplicaRouter,
    RouterConfig,
    SchedulerConfig,
    ServiceConfig,
)


def _build_index(m: int, n_files: int, genome_len: int) -> BitSlicedIndex:
    cfg = idl.IDLConfig(k=31, t=16, L=1 << 12, eta=3, m=m)
    eng = BitSlicedIndex.build(cfg, "idl", n_files=n_files)
    archive = genome.synth_archive(n_files=n_files, genome_len=genome_len,
                                   seed=42)
    return ingest.build_archive(eng, archive, read_len=230, chunk_reads=64)


def _poisson_stream(archive_reads, n_requests: int, rps: float, seed: int):
    """Ragged lengths + exponential inter-arrival gaps (open-loop replay)."""
    rng = np.random.default_rng(seed)
    lens = rng.choice([70, 110, 150, 230], size=n_requests,
                      p=[0.3, 0.3, 0.2, 0.2])
    picks = rng.integers(0, len(archive_reads), size=n_requests)
    gaps = rng.exponential(1.0 / rps, size=n_requests)
    reads = [np.asarray(archive_reads[p][:n]) for p, n in zip(picks, lens)]
    return reads, gaps


class _Tier:
    """Uniform closed-loop / paced-replay facade over the serving tiers."""

    def __init__(self, kind: str, eng, backend: str, n_replicas: int = 2,
                 policy: str = "round_robin"):
        self.kind = kind
        if kind == "sync":
            # per-request flush is how a synchronous caller actually gets
            # answers under live traffic; max_batch=1 is its best config
            # (smallest compiled step — padding 15/16 rows would be worse)
            self.svc = GeneSearchService(
                eng, ServiceConfig(backend=backend, max_batch=1))
        elif kind == "async":
            self.sched = AsyncScheduler(
                GeneSearchService(
                    eng, ServiceConfig(backend=backend, max_batch=16)),
                SchedulerConfig(max_delay_ms=2.0))
        elif kind == "router":
            self.router = ReplicaRouter(
                eng, ServiceConfig(backend=backend, max_batch=16),
                RouterConfig(n_replicas=n_replicas, policy=policy,
                             scheduler=SchedulerConfig(max_delay_ms=2.0)))
        else:
            raise KeyError(kind)

    # -- closed loop: answer the whole stream as fast as possible ----------
    def serve_closed_loop(self, stream) -> None:
        if self.kind == "sync":
            svc = self.svc
            for q in stream:
                svc.result(svc.submit(q))      # auto-flush at max_batch=1
            return
        target = self.sched if self.kind == "async" else self.router
        futures = [target.submit(q) for q in stream]
        target.drain()
        for f in futures:
            f.result()

    # -- open loop: Poisson replay at the offered rate ----------------------
    def serve_paced(self, stream, gaps) -> np.ndarray:
        """Per-request latency (ms) = completion - SCHEDULED arrival.

        Scheduled (not actual) arrivals avoid coordinated omission: a tier
        that falls behind the Poisson process is charged its queueing
        delay instead of silently slowing the arrival clock.
        """
        lat = np.zeros(len(stream))
        t0 = time.perf_counter()
        sched_t = t0
        if self.kind == "sync":
            svc = self.svc
            for i, (q, gap) in enumerate(zip(stream, gaps)):
                sched_t += gap
                now = time.perf_counter()
                if now < sched_t:
                    time.sleep(sched_t - now)
                svc.result(svc.submit(q))      # executes inline
                lat[i] = (time.perf_counter() - sched_t) * 1e3
            return lat
        target = self.sched if self.kind == "async" else self.router
        futures = []
        for i, (q, gap) in enumerate(zip(stream, gaps)):
            sched_t += gap
            now = time.perf_counter()
            if now < sched_t:
                time.sleep(sched_t - now)
            fut = target.submit(q)
            fut.add_done_callback(
                lambda f, i=i, s=sched_t: lat.__setitem__(
                    i, (time.perf_counter() - s) * 1e3))
            futures.append(fut)
        target.drain()
        for f in futures:
            f.result()
        return lat

    def compile_counts(self):
        if self.kind == "sync":
            return {0: self.svc.compile_counts()}
        if self.kind == "async":
            return {0: self.sched.compile_counts()}
        return self.router.compile_counts()

    def close(self) -> None:
        if self.kind == "async":
            self.sched.close()
        elif self.kind == "router":
            self.router.close()


def _measure(tier: _Tier, stream, gaps, iters: int) -> dict:
    stream_s = timeit(lambda: tier.serve_closed_loop(stream),
                      repeats=iters, warmup=2)
    lat = tier.serve_paced(stream, gaps)
    counts = tier.compile_counts()
    for per_replica in counts.values():
        assert all(c == 1 for c in per_replica.values()), (
            f"{tier.kind}: a bucket recompiled: {counts}")
    return {
        "throughput_rps": round(len(stream) / stream_s, 1),
        "latency_ms": {
            "p50": round(float(np.percentile(lat, 50)), 3),
            "p99": round(float(np.percentile(lat, 99)), 3),
        },
        "compiles_per_bucket": {
            str(rid): {str(b): c for b, c in per.items()}
            for rid, per in counts.items()},
    }


def run(m: int, n_files: int, n_requests: int, iters: int, rps: float,
        n_replicas: int, backend: str) -> dict:
    eng = _build_index(m, n_files, genome_len=3_000)
    archive = genome.synth_archive(n_files=n_files, genome_len=3_000, seed=42)
    pool = [f.reads(230, 4)[i % 4] for i, f in enumerate(archive)]
    stream, gaps = _poisson_stream(pool, n_requests, rps, seed=7)

    out: dict = {
        "config": {
            "engine": "bitsliced", "scheme": "idl", "m": m,
            "n_files": n_files, "n_requests": n_requests,
            "backend": backend, "max_batch": 16, "offered_rps": rps,
            "n_replicas": n_replicas, "device": jax.default_backend(),
            "note": ("sync = per-request flush (serving semantics; "
                     "max_batch=1 is its best config — results at "
                     "end-of-stream is not serving); cluster tiers batch "
                     "up to 16 without blocking callers"),
        },
        "tiers": {},
    }
    for kind in ("sync", "async", "router"):
        tier = _Tier(kind, eng, backend, n_replicas)
        try:
            out["tiers"][kind] = _measure(tier, stream, gaps, iters)
        finally:
            tier.close()
    # closed-loop rps of the other routing policies (policy ablation)
    for policy in ("bucket_affinity", "least_outstanding"):
        tier = _Tier("router", eng, backend, n_replicas, policy=policy)
        try:
            stream_s = timeit(lambda: tier.serve_closed_loop(stream),
                              repeats=iters, warmup=2)
            out["tiers"].setdefault("router_policies_rps", {})[
                "round_robin"] = out["tiers"]["router"]["throughput_rps"]
            out["tiers"]["router_policies_rps"][policy] = round(
                n_requests / stream_s, 1)
        finally:
            tier.close()
    sync_rps = out["tiers"]["sync"]["throughput_rps"]
    out["speedup_vs_sync"] = {
        kind: round(out["tiers"][kind]["throughput_rps"] / sync_rps, 2)
        for kind in ("async", "router")
    }
    return out


# ---------------------------------------------------------------------------
# Smoke: router == direct single service, 2 engines x {idl, rh}, + hot swap.
# ---------------------------------------------------------------------------

def _build_smoke_engine(engine: str, scheme: str, m: int):
    cfg = idl.IDLConfig(k=31, t=16, L=1 << 10, eta=2, m=m)
    rng = np.random.default_rng(5)
    reads = jnp.asarray(rng.integers(0, 4, size=(3, 150), dtype=np.uint8))
    fids = np.arange(3)
    if engine == "cobs":
        eng = CobsIndex.build([120, 240, 170], cfg, scheme=scheme,
                              n_groups=2).insert_batch(reads, fids)
    else:
        eng = BitSlicedIndex.build(cfg, scheme, n_files=24).insert_batch(
            reads, fids)
    return eng, reads


def _assert_parity(m: int) -> None:
    rng = np.random.default_rng(9)
    for engine in ("bitsliced", "cobs"):
        for scheme in ("idl", "rh"):
            eng, reads = _build_smoke_engine(engine, scheme, m)
            lens = rng.choice([50, 90, 111, 150], size=14)
            stream = [np.asarray(reads[i % 3][:n])
                      for i, n in enumerate(lens)]
            svc_cfg = ServiceConfig(max_batch=4)
            ref = GeneSearchService(eng, svc_cfg).search(stream)
            with ReplicaRouter(eng, svc_cfg,
                               RouterConfig(n_replicas=2)) as router:
                got = router.search(stream)
                for r, want in zip(got, ref):
                    np.testing.assert_array_equal(np.asarray(r.matches),
                                                  np.asarray(want.matches))
                for per in router.compile_counts().values():
                    assert all(c == 1 for c in per.values())
    print("parity: router == direct service "
          "(bitsliced+cobs x idl+rh); one compile per bucket per replica")


def _assert_hot_swap(m: int, tmp: pathlib.Path) -> None:
    eng, reads = _build_smoke_engine("bitsliced", "idl", m)
    snap0 = store.save(eng, str(tmp / "v0"))
    rng = np.random.default_rng(11)
    new_read = np.asarray(rng.integers(0, 4, size=150, dtype=np.uint8))
    from repro.index import state as state_mod
    eng1 = state_mod.to_engine(store.load(snap0)).insert_batch(
        jnp.asarray(new_read)[None], np.asarray([7]))
    snap1 = store.save(eng1, str(tmp / "v1"))
    with ReplicaRouter.from_snapshot(snap0, ServiceConfig(max_batch=4),
                                     RouterConfig(n_replicas=2)) as router:
        futures = [router.submit(np.asarray(reads[i % 3]))
                   for i in range(24)]
        assert router.swap_snapshot(snap1) == 1
        futures += [router.submit(new_read) for _ in range(8)]
        router.drain()
        results = [f.result(timeout=60) for f in futures]   # zero dropped
        assert all(7 in r.file_ids for r in results[-8:])
        assert all(r.version == 1 for r in results[-8:])
    print("hot swap under load: zero dropped futures, "
          "post-swap results on the new version")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small config; assert parity + swap; no JSON")
    args = ap.parse_args()

    root = pathlib.Path(__file__).resolve().parent.parent

    if args.smoke:
        _assert_parity(m=1 << 16)
        with tempfile.TemporaryDirectory() as tmp:
            _assert_hot_swap(m=1 << 16, tmp=pathlib.Path(tmp))
        res = run(m=1 << 18, n_files=16, n_requests=48, iters=2, rps=2000,
                  n_replicas=2, backend="jnp")
        print("smoke:", json.dumps(res["speedup_vs_sync"]))
        return

    res = run(m=1 << 22, n_files=64, n_requests=256, iters=5, rps=2000,
              n_replicas=2, backend="jnp")
    res["host"] = bench_metadata()
    out_path = root / "BENCH_cluster.json"
    out_path.write_text(json.dumps(res, indent=2) + "\n")
    print(json.dumps(res, indent=2))
    print(f"\nwrote {out_path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    main()
