"""Process-fabric acceptance: gateway answers == union-index oracle
through real worker processes; a kill -9'd worker's in-flight requests
re-route and the fleet stays exact; a crash during a rolling swap leaves
the fleet on the old version; a gateway reboot replays the WAL with zero
acked writes lost.

Each fleet boot spawns real interpreters (each re-imports jax), so the
suite keeps fleets small (2 workers) and shares one serving fleet across
the non-destructive tests.
"""

import multiprocessing
import os
import signal
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import idl
from repro.index import store
from repro.obs import export as obs_export
from repro.index.engines import BitSlicedIndex
from repro.serving import (
    FabricConfig,
    FabricError,
    KmerCacheConfig,
    ProcessFabric,
    ScatterConfig,
    ScatterError,
    ScatterGatherRouter,
    ServiceConfig,
)

N_FILES = 40
BASE_FIDS = [0, 9, 39]
DELTA_FIDS = [5, 17]


def _cfg() -> idl.IDLConfig:
    return idl.IDLConfig(k=31, t=16, L=1 << 10, eta=2, m=1 << 16)


@pytest.fixture(scope="module")
def reads(rng):
    return rng.integers(0, 4, size=(6, 120), dtype=np.uint8)


@pytest.fixture(scope="module")
def queries(reads):
    lens = [120, 100, 77, 120, 61, 99]
    return [np.asarray(reads[i][:n]) for i, n in enumerate(lens)]


@pytest.fixture(scope="module")
def base_engine(reads):
    return BitSlicedIndex.build(_cfg(), "idl", n_files=N_FILES
                                ).insert_batch(jnp.asarray(reads[:3]),
                                               np.asarray(BASE_FIDS))


@pytest.fixture(scope="module")
def oracle(base_engine, reads):
    """The hypothetical single merged index: base + the write batch
    (donate=False: the base keeps serving the other fixtures)."""
    return base_engine.insert_batch(jnp.asarray(reads[3:5]),
                                    np.asarray(DELTA_FIDS), donate=False)


@pytest.fixture(scope="module")
def snap(base_engine, tmp_path_factory):
    return store.save(base_engine,
                      str(tmp_path_factory.mktemp("fab") / "snap"))


def _fab_cfg(**kw) -> FabricConfig:
    kw.setdefault("n_workers", 2)
    kw.setdefault("service", ServiceConfig(max_batch=4))
    return FabricConfig(**kw)


def _assert_matches(results, oracle, queries):
    for q, res in zip(queries, results):
        want = np.asarray(oracle.msmt(jnp.asarray(q)[None]))[0]
        np.testing.assert_array_equal(np.asarray(res.matches), want)


class TestFabricServing:
    """One shared 2-worker fleet: parity, stamps, admission."""

    @pytest.fixture(scope="class")
    def fab(self, snap, tmp_path_factory):
        fab = ProcessFabric(
            snap, _fab_cfg(),
            journal_path=str(tmp_path_factory.mktemp("wal") / "wal.idlj"))
        yield fab
        fab.close()

    def test_parity_and_read_your_writes(self, fab, oracle, base_engine,
                                         reads, queries):
        # pre-write: fleet == base index
        _assert_matches(fab.search(queries), base_engine, queries)
        ack = fab.insert(reads[3:5], DELTA_FIDS).result(timeout=120)
        assert ack.delta_seq == 1 and ack.n_reads == 2
        # post-write: fleet == union oracle, on EVERY worker (round-robin
        # over both via repeated search), stamps prove read-your-writes
        for _ in range(2):
            results = fab.search(queries)
            _assert_matches(results, oracle, queries)
            for res in results:
                assert res.delta_seq >= ack.delta_seq
                assert res.version == ack.base_version

    def test_gateway_rejects_malformed_reads(self, fab):
        with pytest.raises(ValueError, match="one 1-D read"):
            fab.submit(np.zeros((2, 120), dtype=np.uint8))
        with pytest.raises(ValueError, match="has no 31-mers"):
            fab.submit(np.zeros((7,), dtype=np.uint8))

    def test_stats_reach_every_worker(self, fab):
        stats = fab.stats()
        assert len(stats) == 2
        assert sum(s["requests_served"] for s in stats.values()) > 0
        assert {s["version"] for s in stats.values()} == {0}


class TestFaultPaths:
    """Destructive tests: each boots (and tears down) its own fleet."""

    def test_kill9_worker_midstream(self, snap, oracle, reads, queries,
                                    tmp_path):
        """kill -9 one worker with requests in flight: the gateway
        re-routes them to the survivor and every answer still equals the
        union oracle — zero dropped futures."""
        fab = ProcessFabric(
            snap, _fab_cfg(policy="round_robin"),
            journal_path=str(tmp_path / "wal.idlj"))
        try:
            fab.insert(reads[3:5], DELTA_FIDS).result(timeout=120)
            fab.search(queries)                    # warm both workers
            stream = [queries[i % len(queries)] for i in range(24)]
            futures = [fab.submit(q) for q in stream]
            victim = sorted(fab.worker_pids().items())[0][1]
            os.kill(victim, signal.SIGKILL)
            results = [f.result(timeout=120) for f in futures]
            _assert_matches(results, oracle, stream)
            deadline = time.monotonic() + 30
            while fab.n_workers > 1 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert fab.n_workers == 1
            # the fleet keeps serving — writes and reads — on the survivor
            fab.insert(reads[5:6], [23]).result(timeout=120)
            two = oracle.insert_batch(jnp.asarray(reads[5:6]),
                                      np.asarray([23]), donate=False)
            _assert_matches(fab.search(queries), two, queries)
        finally:
            fab.close()

    def test_worker_crash_during_rolling_swap(self, snap, base_engine,
                                              reads, queries, tmp_path):
        """A replacement that dies booting ABORTS the rollout: the fleet
        keeps serving the old snapshot at the old version — no mixed
        fleet, no dropped requests."""
        fab = ProcessFabric(snap, _fab_cfg())
        try:
            new_snap = store.save(base_engine, str(tmp_path / "snap2"))
            fab._test_flags["boot_fail_snapshot"] = new_snap
            with pytest.raises(FabricError, match="aborted"):
                fab.rolling_restart(new_snap)
            assert fab.version == 0
            assert fab.n_workers == 2
            stats = fab.stats()
            assert {s["version"] for s in stats.values()} == {0}
            results = fab.search(queries)
            _assert_matches(results, base_engine, queries)
            assert all(r.version == 0 for r in results)
        finally:
            fab.close()

    def test_rolling_restart_under_traffic(self, snap, base_engine,
                                           queries, tmp_path):
        """A healthy rolling swap: requests submitted before, during and
        after all resolve correctly; the fleet version advances only when
        every worker swapped."""
        fab = ProcessFabric(snap, _fab_cfg())
        try:
            fab.search(queries)                    # warm compile caches
            before = [fab.submit(q) for q in queries]
            version = fab.rolling_restart()        # same snapshot, v+1
            after = [fab.submit(q) for q in queries]
            assert version == 1 and fab.version == 1
            _assert_matches([f.result(timeout=120) for f in before],
                            base_engine, queries)
            results = [f.result(timeout=120) for f in after]
            _assert_matches(results, base_engine, queries)
            assert all(r.version == 1 for r in results)
            assert fab.n_workers == 2
        finally:
            fab.close()

    def test_gateway_reboot_replays_wal(self, snap, oracle, reads,
                                        queries, tmp_path):
        """Acked writes survive a gateway reboot: the new gateway's
        workers replay the WAL tail and answer == union oracle."""
        wal = str(tmp_path / "wal.idlj")
        fab = ProcessFabric(snap, _fab_cfg(n_workers=1), journal_path=wal)
        try:
            fab.insert(reads[3:5], DELTA_FIDS).result(timeout=120)
        finally:
            fab.close()
        reborn = ProcessFabric(snap, _fab_cfg(n_workers=1),
                               journal_path=wal)
        try:
            assert reborn.wal_seq == 1
            results = reborn.search(queries)
            _assert_matches(results, oracle, queries)
            assert all(r.delta_seq == 1 for r in results)
        finally:
            reborn.close()


class TestKmerCacheAcrossTheFleet:
    """Per-worker membership caches through the process boundary: the
    pickled ``ServiceConfig.kmer_cache`` fans out to every worker, the
    gateway aggregates their hit rates, a fanned write flips cached
    negatives fleet-wide, and the caches survive a zero-drop rolling
    restart (replacements boot cold, replay the WAL, and re-warm)."""

    def test_cache_survives_zero_drop_rolling_restart(
            self, snap, base_engine, oracle, reads, queries, tmp_path):
        fab = ProcessFabric(
            snap, _fab_cfg(service=ServiceConfig(
                max_batch=4, kmer_cache=KmerCacheConfig(capacity=1 << 14))),
            journal_path=str(tmp_path / "wal.idlj"))
        try:
            stream = [queries[i % len(queries)] for i in range(18)]
            # pass 1 warms every worker; pass 2 must reuse
            _assert_matches(fab.search(stream), base_engine, stream)
            _assert_matches(fab.search(stream), base_engine, stream)
            cs = fab.cache_stats()
            assert cs is not None and cs["hits"] > 0
            assert 0.0 < cs["hit_rate"] <= 1.0
            # a fanned write flips cached negatives on EVERY worker
            # (base rows stay cached; the delta is probed fresh)
            fab.insert(reads[3:5], DELTA_FIDS).result(timeout=120)
            _assert_matches(fab.search(stream), oracle, stream)
            # zero-drop rolling swap with caches on: requests in flight
            # before and after all resolve exactly; replacements boot
            # with cold caches and replay the WAL tail
            before = [fab.submit(q) for q in stream]
            version = fab.rolling_restart()
            after = [fab.submit(q) for q in stream]
            _assert_matches([f.result(timeout=120) for f in before],
                            oracle, stream)
            results = [f.result(timeout=120) for f in after]
            _assert_matches(results, oracle, stream)
            assert all(r.version == version for r in results)
            assert fab.n_workers == 2
            # the re-warmed fleet reuses again and still aggregates
            _assert_matches(fab.search(stream), oracle, stream)
            cs2 = fab.cache_stats()
            assert cs2 is not None and cs2["hits"] > 0
        finally:
            fab.close()

class TestObservabilityAcrossTheFleet:
    """End-to-end tracing through real process boundaries: the gateway's
    trace context rides the IPC frame, the worker opens child spans under
    it, and ``obs_snapshot()`` stitches one tree out of many pids. A
    kill -9 must error-close the dead worker's dispatch spans instead of
    leaking them, while the requests themselves still resolve."""

    def _stitched_traces(self, fab):
        """Traces in the fleet snapshot whose records span >1 process."""
        snap = fab.obs_snapshot()
        return {tid: recs
                for tid, recs in obs_export.traces_of(snap).items()
                if len({r["pid"] for r in recs}) > 1}

    def test_trace_stitches_across_processes(self, snap, base_engine,
                                             queries, tmp_path):
        obs.reset()
        fab = ProcessFabric(snap, _fab_cfg(),
                            journal_path=str(tmp_path / "wal.idlj"))
        try:
            _assert_matches(fab.search(queries), base_engine, queries)
            # root-closure callbacks and worker finalize can trail the
            # future resolution by a beat; poll the fleet snapshot
            deadline = time.monotonic() + 30
            stitched = self._stitched_traces(fab)
            while time.monotonic() < deadline and \
                    len(stitched) < len(queries):
                time.sleep(0.05)
                stitched = self._stitched_traces(fab)
            assert len(stitched) >= len(queries)
            gw_pid = os.getpid()
            for recs in stitched.values():
                by_name = {}
                for r in recs:
                    by_name.setdefault(r["name"], []).append(r)
                # gateway root, opened at submit, closed on the future
                (root,) = [r for r in by_name["request"]
                           if r["pid"] == gw_pid]
                assert root["parent"] is None
                assert root["status"] == "ok"
                assert root["attrs"]["tier"] == "gateway"
                # gateway-side dispatch span, child of the root
                (hop,) = by_name["worker_exec"]
                assert hop["pid"] == gw_pid
                assert hop["parent"] == root["span"]
                assert hop["status"] == "ok"
                # worker-side service chain, parented UNDER the dispatch
                # span minted in the gateway process
                (wreq,) = [r for r in by_name["request"]
                           if r["pid"] != gw_pid]
                assert wreq["parent"] == hop["span"]
                for stage in ("queue_wait", "assemble", "execute",
                              "finalize"):
                    (srec,) = by_name[stage]
                    assert srec["pid"] == wreq["pid"]
                    assert srec["parent"] == wreq["span"]
                # one trace id end to end — every record agrees
                assert len({r["trace"] for r in recs}) == 1
        finally:
            fab.close()

    def test_kill9_error_closes_orphaned_spans(self, snap, oracle, reads,
                                               queries, tmp_path):
        obs.reset()
        fab = ProcessFabric(snap, _fab_cfg(policy="round_robin"),
                            journal_path=str(tmp_path / "wal.idlj"))
        try:
            fab.insert(reads[3:5], DELTA_FIDS).result(timeout=120)
            fab.search(queries)                    # warm both workers
            stream = [queries[i % len(queries)] for i in range(24)]
            futures = [fab.submit(q) for q in stream]
            victim_id, victim_pid = sorted(fab.worker_pids().items())[0]
            os.kill(victim_pid, signal.SIGKILL)
            results = [f.result(timeout=120) for f in futures]
            _assert_matches(results, oracle, stream)

            def error_closed():
                return [r for r in obs_export.snapshot()["spans"]
                        if r["name"] == "worker_exec"
                        and r["status"] == "error"
                        and r.get("attrs", {}).get("error")
                        == f"worker {victim_id} died"]

            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and not error_closed():
                time.sleep(0.05)
            orphans = error_closed()
            assert orphans, "kill -9 left dispatch spans open"
            # the re-dispatched requests stayed on their original traces:
            # each orphaned span's trace also has an ok worker_exec hop
            ok_hops = {r["trace"] for r in obs_export.snapshot()["spans"]
                       if r["name"] == "worker_exec"
                       and r["status"] == "ok"}
            assert any(r["trace"] in ok_hops for r in orphans)
        finally:
            fab.close()


@pytest.mark.parametrize("tier", ["fabric", "scatter_procs"])
def test_process_tiers_refuse_a_chip_backend(tier, tmp_path, monkeypatch):
    """On a TPU host the tier's workers could never take the chip (it
    belongs to one process): construction raises before any spawn,
    instead of waiting out the boot timeout."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = set(multiprocessing.active_children())
    if tier == "fabric":
        with pytest.raises(FabricError, match="belongs to one process"):
            ProcessFabric(str(tmp_path), _fab_cfg())
    else:
        with pytest.raises(ScatterError, match="belongs to one process"):
            ScatterGatherRouter(str(tmp_path), ScatterConfig(procs=True))
    assert set(multiprocessing.active_children()) == before
