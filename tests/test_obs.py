"""Observability plane: registry semantics, tracer semantics, export
round-trips, and the span chains the serving tiers emit: the metrics
registry (pre-bound handles, log2 bucketing, snapshots), the tracer
(ids, parenting, idempotent closure, the bounded ring), the export module
(dump round-trip, the registry-backed cache view), and the query/insert
span trees emitted by the sync service, the async scheduler and the live
replica router.
"""

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import idl
from repro.index.engines import BitSlicedIndex
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving import (
    AsyncScheduler,
    GeneSearchService,
    LiveReplicaRouter,
    RouterConfig,
    SchedulerConfig,
    ServiceConfig,
)

N_FILES = 40


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
                                               np.asarray([0, 9, 39]))


def _wait(pred, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return pred()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestRegistry:

    def test_binding_dedupes_and_canonicalizes_labels(self):
        reg = obs_metrics.Registry()
        a = reg.counter("serving.requests", tier="service", replica=0)
        b = reg.counter("serving.requests", replica=0, tier="service")
        assert a is b                       # label order is canonicalized
        assert reg.counter("serving.requests", replica=1) is not a
        assert reg.gauge("x") is reg.gauge("x")
        assert reg.histogram("x") is reg.histogram("x")
        # same name, different instrument kind: independent tables
        assert reg.counter("x") is not reg.gauge("x")

    def test_parse_label_key_roundtrip(self):
        labels = {"tier": "service", "replica": "3", "scheme": "idl"}
        key = obs_metrics._label_key(labels)
        assert key == "replica=3,scheme=idl,tier=service"
        assert obs_metrics.parse_label_key(key) == labels
        assert obs_metrics.parse_label_key("") == {}

    def test_counter_and_gauge_values(self):
        reg = obs_metrics.Registry()
        c = reg.counter("c")
        c.inc()
        c.inc(2.5)
        g = reg.gauge("g")
        g.set(7)
        g.set(3)
        snap = reg.snapshot()
        assert snap["counters"]["c"][""] == 3.5
        assert snap["gauges"]["g"][""] == 3.0     # last write wins

    def test_histogram_log2_bucketing(self):
        reg = obs_metrics.Registry()
        h = reg.histogram("h")
        for v in (0.0, 0.5, 1.0, 2.0, 3.0, 4.0, 1000.0):
            h.observe(v)
        # bucket i counts int(v).bit_length() == i
        assert h.buckets[0] == 2                  # 0.0, 0.5
        assert h.buckets[1] == 1                  # 1.0
        assert h.buckets[2] == 2                  # 2.0, 3.0
        assert h.buckets[3] == 1                  # 4.0
        assert h.buckets[10] == 1                 # 1000 -> bit_length 10
        assert h.count == 7
        assert h.min == 0.0 and h.max == 1000.0
        assert h.sum == pytest.approx(1010.5)

    def test_histogram_clamps_to_top_bucket(self):
        reg = obs_metrics.Registry()
        h = reg.histogram("h")
        h.observe(float(2 ** 100))
        assert h.buckets[obs_metrics.N_BUCKETS - 1] == 1

    def test_observe_array_matches_scalar_path(self, rng):
        values = np.concatenate([
            rng.integers(0, 5000, size=200).astype(np.float64),
            np.array([0.0, 0.25, 1.0, 2.0**63]),
        ])
        reg = obs_metrics.Registry()
        scalar, bulk = reg.histogram("s"), reg.histogram("b")
        for v in values:
            scalar.observe(float(v))
        bulk.observe_array(values)
        assert bulk.buckets == scalar.buckets
        assert bulk.count == scalar.count
        assert bulk.sum == pytest.approx(scalar.sum)
        assert bulk.min == scalar.min and bulk.max == scalar.max
        bulk.observe_array(np.empty(0))           # no-op, no crash
        assert bulk.count == scalar.count

    def test_disabled_registry_is_a_noop(self):
        reg = obs_metrics.Registry()
        c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
        reg.enabled = False
        c.inc()
        g.set(5)
        h.observe(3)
        h.observe_array(np.arange(4))
        assert c.value == 0 and g.value == 0 and h.count == 0

    def test_reset_keeps_handles_valid(self):
        reg = obs_metrics.Registry()
        c, h = reg.counter("c"), reg.histogram("h")
        c.inc(5)
        h.observe(9)
        reg.reset()
        assert c.value == 0 and h.count == 0
        c.inc()                                   # same handle still live
        assert reg.snapshot()["counters"]["c"][""] == 1.0

    def test_snapshot_is_json_clean(self):
        reg = obs_metrics.Registry()
        reg.counter("c", tier="x").inc()
        reg.histogram("h").observe(2)
        doc = json.loads(json.dumps(reg.snapshot()))
        assert doc["hists"]["h"][""]["count"] == 1
        # empty histograms render finite min/max, not inf
        reg.histogram("empty")
        doc = reg.snapshot()["hists"]["empty"][""]
        assert doc["min"] == 0.0 and doc["max"] == 0.0


class TestMergeAndViews:

    def _snap(self, build):
        reg = obs_metrics.Registry()
        build(reg)
        return reg.snapshot()

    def test_counter_total_filters_on_labels(self):
        def build(reg):
            reg.counter("n", scheme="idl", op="query").inc(4)
            reg.counter("n", scheme="rh", op="query").inc(8)
            reg.counter("n", scheme="idl", op="insert").inc(1)
            reg.gauge("cap", cache="a").set(10)
            reg.gauge("cap", cache="b").set(30)

        snap = self._snap(build)
        assert obs_metrics.counter_total(snap, "n") == 13.0
        assert obs_metrics.counter_total(
            snap, "n", where={"scheme": "idl"}) == 5.0
        assert obs_metrics.counter_total(
            snap, "n", where={"scheme": "idl", "op": "query"}) == 4.0
        assert obs_metrics.counter_total(snap, "absent") == 0.0
        assert obs_metrics.gauge_total(snap, "cap") == 40.0

    def test_cache_stats_view(self):
        def build(reg):
            reg.counter("kmer_cache.hits", cache=0).inc(30)
            reg.counter("kmer_cache.hits", cache=1).inc(10)
            reg.counter("kmer_cache.misses", cache=0).inc(10)
            reg.counter("kmer_cache.evictions", cache=0).inc(2)
            reg.counter("kmer_cache.invalidations", cache=1).inc(1)
            reg.gauge("kmer_cache.entries", cache=0).set(5)
            reg.gauge("kmer_cache.capacity", cache=0).set(64)

        view = obs_export.cache_stats_view({"metrics": self._snap(build)})
        assert view["hits"] == 40 and view["misses"] == 10
        assert view["lookups"] == 50
        assert view["hit_rate"] == pytest.approx(0.8)
        assert view["entries"] == 5 and view["capacity"] == 64
        assert view["evictions"] == 2 and view["invalidations"] == 1
        empty = obs_export.cache_stats_view(
            {"metrics": obs_metrics.Registry().snapshot()})
        assert empty["lookups"] == 0 and empty["hit_rate"] == 0.0


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestTracer:

    def test_ids_are_pid_scoped_and_unique(self):
        trc = obs_trace.Tracer()
        ids = {trc.mint_trace() for _ in range(100)}
        assert len(ids) == 100
        span = trc.start("x")
        assert span.span_id.split(".")[0] == format(trc._pid, "x")
        span.end()

    def test_start_end_and_child_parenting(self):
        trc = obs_trace.Tracer()
        root = trc.start("request", tier="gateway")
        child = trc.start("worker_exec", trace=root.context(), worker=1)
        child.end()
        root.end(n=6)
        recs = {r["name"]: r for r in trc.records()}
        assert recs["request"]["parent"] is None
        assert recs["worker_exec"]["parent"] == recs["request"]["span"]
        assert recs["worker_exec"]["trace"] == recs["request"]["trace"]
        assert recs["request"]["attrs"] == {"tier": "gateway", "n": 6}
        assert recs["request"]["dur"] >= recs["worker_exec"]["dur"] >= 0

    def test_end_is_idempotent(self):
        trc = obs_trace.Tracer()
        span = trc.start("x")
        span.end()
        span.end(status="error")                  # late death-closure
        assert len(trc.records()) == 1
        assert trc.records()[0]["status"] == "ok"

    def test_context_manager_marks_errors(self):
        trc = obs_trace.Tracer()
        with pytest.raises(RuntimeError):
            with trc.start("boom"):
                raise RuntimeError("x")
        with trc.start("fine"):
            pass
        status = {r["name"]: r["status"] for r in trc.records()}
        assert status == {"boom": "error", "fine": "ok"}

    def test_close_open_spans(self):
        trc = obs_trace.Tracer()
        trc.start("a", worker=1)
        trc.start("b", worker=1)
        done = trc.start("c")
        done.end()
        assert trc.close_open_spans(error="worker 1 died") == 2
        assert trc.close_open_spans() == 0        # nothing left open
        errs = [r for r in trc.records() if r["status"] == "error"]
        assert len(errs) == 2
        assert all(r["attrs"]["error"] == "worker 1 died" for r in errs)

    def test_emit_fast_path_and_disabled(self):
        trc = obs_trace.Tracer()
        t0 = time.monotonic()
        tid = trc.mint_trace()
        root = trc.emit("request", tid, None, t0, t0 + 0.25)
        child = trc.emit("execute", tid, root, t0, t0 + 0.125,
                         attrs={"bucket": 128})
        assert root is not None and child is not None
        recs = trc.records()
        assert recs[1]["parent"] == root
        assert recs[0]["dur"] == pytest.approx(0.25)
        assert recs[1]["attrs"] == {"bucket": 128}
        trc.enabled = False
        assert trc.emit("x", tid, None, t0, t0) is None
        assert len(trc.records()) == 2

    def test_ring_is_bounded(self):
        trc = obs_trace.Tracer(capacity=4)
        t0 = time.monotonic()
        for i in range(10):
            trc.emit(f"s{i}", trc.mint_trace(), None, t0, t0)
        names = [r["name"] for r in trc.records()]
        assert names == ["s6", "s7", "s8", "s9"]  # oldest evicted

# ---------------------------------------------------------------------------
# export module
# ---------------------------------------------------------------------------

class TestExport:

    def _private(self):
        reg, trc = obs_metrics.Registry(), obs_trace.Tracer()
        reg.counter("c").inc(2)
        span = trc.start("request")
        trc.start("child", trace=span.context()).end()
        span.end()
        return obs_export.snapshot(registry=reg, tracer=trc)

    def test_dump_round_trip(self, tmp_path):
        snap = self._private()
        out = tmp_path / "obs" / "dump.json"
        paths = obs_export.dump(snap, str(out))
        assert paths == [str(out), str(out.with_suffix(".chrome.json"))]
        doc = json.loads(out.read_text())
        assert doc["metrics"]["counters"]["c"][""] == 2.0
        (spans,) = doc["traces"].values()
        assert len(spans) == 2
        chrome = json.loads(out.with_suffix(".chrome.json").read_text())
        assert len(chrome["traceEvents"]) == 2
        assert chrome["displayTimeUnit"] == "ms"


# ---------------------------------------------------------------------------
# stages: the pipeline's and the planner's intervals
# ---------------------------------------------------------------------------

# every stage the idl_probe query path times, and the stage holding each
# (``plan`` is the dispatch of the step that plans the runs on the device)
STAGES = {"serve.assemble": None, "serve.execute": None,
          "plan": "serve.execute",
          "serve.finalize": None, "serve.finalize.wait": "serve.finalize"}


def _stage_ms(name: str):
    """The ``serving.stage_ms`` histogram of one stage, or None."""
    snap = obs_metrics.DEFAULT.snapshot()
    return snap["hists"].get("serving.stage_ms", {}).get(f"stage={name}")


class TestStages:

    @pytest.fixture(autouse=True)
    def _clean_obs(self):
        obs.reset()
        yield
        obs.set_enabled(True)
        obs.reset()

    def test_stage_observes_its_duration_in_ms(self):
        with obs_trace.stage("unit.sleep"):
            time.sleep(0.02)
        with pytest.raises(ValueError):        # errors pass through, timed
            with obs_trace.stage("unit.sleep"):
                raise ValueError("boom")
        h = _stage_ms("unit.sleep")
        assert h["count"] == 2
        assert h["max"] >= 20.0 and h["min"] < 20.0

    def test_stage_is_a_noop_when_obs_is_disabled(self):
        obs.set_enabled(False)
        with obs_trace.stage("unit.off"):
            pass
        obs.set_enabled(True)
        h = _stage_ms("unit.off")
        assert h is None or h["count"] == 0

    def test_sync_service_times_every_stage_once_a_batch(self, base_engine,
                                                           queries):
        svc = GeneSearchService(
            base_engine, ServiceConfig(backend="idl_probe", max_batch=4))
        svc.search(queries)
        counts = {name: _stage_ms(name)["count"] for name in STAGES}
        batches = obs_metrics.counter_total(
            obs_metrics.DEFAULT.snapshot(), "serving.batches")
        assert batches >= 2
        assert set(counts.values()) == {batches}

    def test_stages_land_nested_on_the_profiler_host_plane(
            self, base_engine, reads, tmp_path):
        import jax
        from jax.profiler import ProfileData

        svc = GeneSearchService(
            base_engine, ServiceConfig(backend="idl_probe", max_batch=4))
        svc.search(list(reads[:4]))            # compile outside the trace
        sched = AsyncScheduler(svc, SchedulerConfig(max_delay_ms=1.0))
        try:
            jax.profiler.start_trace(str(tmp_path))
            try:
                futures = [sched.submit(r) for r in reads[:4]]
                sched.drain()
            finally:
                jax.profiler.stop_trace()
        finally:
            sched.close()
        assert all(f.result().file_ids is not None for f in futures)
        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        events: dict = {}
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in STAGES:
                        events.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
        assert set(events) == set(STAGES)
        for name, outer in STAGES.items():
            if outer is None:
                continue
            for s, t in events[name]:
                assert any(s0 <= s and t <= t0 for s0, t0 in events[outer]), \
                    f"{name} at {s}..{t} lies in no {outer}"


# ---------------------------------------------------------------------------
# span chains through the serving tiers (single process)
# ---------------------------------------------------------------------------

class TestServingSpanChains:

    @pytest.fixture(autouse=True)
    def _clean_obs(self):
        obs.reset()
        yield
        obs.set_enabled(True)
        obs.reset()

    def test_sync_service_emits_request_chain(self, base_engine, queries):
        svc = GeneSearchService(
            base_engine, ServiceConfig(backend="idl_probe", max_batch=4))
        svc.search(queries)
        snap = obs_export.snapshot()
        traces = obs_export.traces_of(snap)
        chains = 0
        for recs in traces.values():
            by_name = {r["name"]: r for r in recs}
            if "request" not in by_name:
                continue
            root = by_name["request"]
            assert root["parent"] is None         # minted at admission
            assert root["status"] == "ok"
            for stage in ("queue_wait", "assemble", "execute", "finalize"):
                assert by_name[stage]["parent"] == root["span"]
                assert by_name[stage]["trace"] == root["trace"]
            chains += 1
        assert chains >= len(queries)
        # the registry saw the same traffic, including locality counters
        m = snap["metrics"]
        assert obs_metrics.counter_total(
            m, "serving.requests") >= len(queries)
        assert obs_metrics.counter_total(
            m, "locality.planned_tile_bytes", where={"scheme": "idl"}) > 0

    def test_disabled_obs_serves_identically_and_records_nothing(
            self, base_engine, queries):
        svc = GeneSearchService(
            base_engine, ServiceConfig(backend="idl_probe", max_batch=4))
        want = [np.asarray(r.matches) for r in svc.search(queries)]
        obs.reset()
        obs.set_enabled(False)
        got = [np.asarray(r.matches) for r in svc.search(queries)]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)   # bit-identical
        snap = obs_export.snapshot()
        assert snap["spans"] == []
        assert obs_metrics.counter_total(
            snap["metrics"], "serving.requests") == 0.0

    def test_live_router_insert_span_tree(self, reads, queries):
        base = BitSlicedIndex.build(_cfg(), "idl", n_files=N_FILES
                                    ).insert_batch(jnp.asarray(reads[:3]),
                                                   np.asarray([0, 9, 39]))
        rt = LiveReplicaRouter(
            base, ServiceConfig(max_batch=4),
            RouterConfig(n_replicas=2, policy="round_robin"))
        with rt:
            for f in rt.insert(np.asarray(reads[3:5]),
                               np.asarray([5, 17])):
                f.result(timeout=60)
            rt.search(queries)

            def insert_tree():
                for recs in obs_export.traces_of(
                        obs_export.snapshot()).values():
                    names = {r["name"] for r in recs}
                    if "insert" in names and "replica_apply" in names:
                        return recs
                return None

            assert _wait(lambda: insert_tree() is not None)
            recs = insert_tree()
            by_name = {}
            for r in recs:
                by_name.setdefault(r["name"], []).append(r)
            (root,) = by_name["insert"]
            assert root["parent"] is None and root["status"] == "ok"
            assert root["attrs"]["tier"] == "router"
            assert root["attrs"]["n_reads"] == 2
            # ack closure stamps the fan-out width
            assert root["attrs"]["n_replicas"] == 2
            (journal,) = by_name["journal_append"]
            (fanout,) = by_name["fanout"]
            assert journal["parent"] == root["span"]
            assert fanout["parent"] == root["span"]
            # one apply per replica, all on the SAME trace as the root
            assert len(by_name["replica_apply"]) == 2
            for apply_rec in by_name["replica_apply"]:
                assert apply_rec["trace"] == root["trace"]
                assert apply_rec["parent"] == root["span"]
            # queries that followed the write carry their own traces
            q_traces = [recs for recs in obs_export.traces_of(
                obs_export.snapshot()).values()
                if any(r["name"] == "request" for r in recs)]
            assert len(q_traces) >= len(queries)
