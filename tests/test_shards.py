"""Sharded-archive acceptance: partition/join round-trips bit-exactly,
the parallel sharded build equals the serial archive builder, the
scatter-gather router's answers are bit-identical to one service over
the unsharded index — across every engine x scheme x theta — and shard
death keeps the exact semantics: row-probe answers name their
``missing_files``, bit-probe death fails loud (``ShardDeadError``), and
zero futures are ever dropped.
"""

import json
import os
import shutil

import numpy as np
import pytest

from repro.core import idl
from repro.index import engines, ingest, shards, state as state_mod, store
from repro.serving import service as service_mod
from repro.serving.scatter import (
    ScatterConfig,
    ScatterGatherRouter,
    ShardDeadError,
    ShardSearchService,
)

ENGINES = ("bitsliced", "cobs", "bloom", "rambo")
SCHEMES = ("idl", "rh")
THETAS = (1.0, 0.6)
N_FILES = 70     # >= 3 bit-sliced word columns, so 2-3 file shards exist


def _cfg() -> idl.IDLConfig:
    return idl.IDLConfig(k=31, t=16, L=1 << 10, eta=2, m=1 << 14)


@pytest.fixture(scope="module")
def files(rng):
    return [rng.integers(0, 4, size=(6, 120), dtype=np.uint8)
            for _ in range(N_FILES)]


@pytest.fixture(scope="module")
def reads(files):
    return np.stack([files[i][0] for i in range(6)])


@pytest.fixture(scope="module")
def queries(rng, files):
    qs = [rng.integers(0, 4, size=(int(n),), dtype=np.uint8)
          for n in rng.integers(40, 100, size=6)]
    qs[0] = files[3][0][:80].copy()     # true positives across the
    qs[1] = files[60][2][:60].copy()    # file-shard boundary
    return qs


def _fresh_index(engine: str, scheme: str, files):
    if engine == "bitsliced":
        return engines.BitSlicedIndex.build(_cfg(), scheme=scheme,
                                            n_files=N_FILES)
    if engine == "cobs":
        return engines.CobsIndex.build([f.size for f in files], _cfg(),
                                       scheme=scheme, n_groups=3)
    if engine == "rambo":
        return engines.RamboIndex.build(N_FILES, _cfg(), scheme=scheme)
    return engines.PackedBloomIndex.build(_cfg(), scheme=scheme)


def _items(engine: str, files):
    # the flat BF indexes ONE set: give it a single concatenated file
    if engine == "bloom":
        return [(0, np.concatenate([f.ravel() for f in files[:4]]))]
    return list(enumerate(files))


@pytest.fixture(scope="module")
def built(files, tmp_path_factory):
    """Memoized (engine, scheme) -> (spec, states, set_dir, full_state):
    one parallel sharded build + saved shard set per combo, shared by
    every test in the module."""
    cache = {}

    def get(engine: str, scheme: str, n_shards: int = 2):
        key = (engine, scheme, n_shards)
        if key not in cache:
            out = str(tmp_path_factory.mktemp(f"{engine}-{scheme}")
                      / "set")
            spec, states = ingest.build_sharded_archive(
                _fresh_index(engine, scheme, files),
                _items(engine, files), n_shards=n_shards, out_dir=out,
                read_len=120, chunk_reads=8)
            cache[key] = (spec, states, out,
                          shards.join_states(spec, states))
        return cache[key]

    return get


# ---------------------------------------------------------------------------
# The shard math: partition/join round-trip + exact merged queries.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("engine", ENGINES)
class TestShardMath:

    def test_partition_join_roundtrip(self, built, engine, scheme):
        spec, states, _, full = built(engine, scheme)
        spec2, parts = shards.partition_state(full, spec.n_shards)
        assert spec2 == spec
        for got, want in zip(parts, states):
            for gw, ww in zip(got.words, want.words):
                np.testing.assert_array_equal(np.asarray(gw),
                                              np.asarray(ww))
        joined = shards.join_states(spec, parts)
        for gw, ww in zip(joined.words, full.words):
            np.testing.assert_array_equal(np.asarray(gw), np.asarray(ww))

    def test_sharded_msmt_equals_oracle(self, built, reads, engine,
                                        scheme):
        spec, states, _, full = built(engine, scheme)
        oracle = state_mod.to_engine(full)
        for theta in THETAS:
            want = np.asarray(oracle.msmt(reads, theta=theta))
            got = np.asarray(shards.sharded_msmt(spec, states, reads,
                                                 theta=theta))
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"theta={theta}")

    def test_sharded_build_equals_serial_build(self, built, files,
                                               engine, scheme):
        spec, states, _, _ = built(engine, scheme)
        serial = ingest.build_archive(
            _fresh_index(engine, scheme, files), _items(engine, files),
            read_len=120, chunk_reads=8)
        _, serial_parts = shards.partition_state(serial, spec.n_shards)
        for got, want in zip(states, serial_parts):
            for gw, ww in zip(got.words, want.words):
                np.testing.assert_array_equal(np.asarray(gw),
                                              np.asarray(ww))


# ---------------------------------------------------------------------------
# The scatter-gather tier (in-process members): bit-identical answers.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("engine", ENGINES)
class TestScatterGatherParity:

    def test_router_equals_unsharded_service(self, built, queries,
                                             engine, scheme):
        _, _, set_dir, full = built(engine, scheme)
        for theta in THETAS:
            svc_cfg = service_mod.ServiceConfig(theta=theta, max_batch=4)
            oracle = service_mod.GeneSearchService(full, svc_cfg)
            want = oracle.search(queries)
            with ScatterGatherRouter(
                    set_dir, ScatterConfig(service=svc_cfg)) as router:
                got = router.search(queries)
            for w, g in zip(want, got):
                np.testing.assert_array_equal(
                    np.asarray(g.matches), np.asarray(w.matches),
                    err_msg=f"theta={theta}")
                assert g.file_ids == w.file_ids
                assert g.n_kmers == w.n_kmers and g.bucket == w.bucket
                assert g.missing_files == ()
                assert g.version == router.set_version


class TestScatterSurface:

    def test_stats_and_geometry_views(self, built):
        spec, _, set_dir, _ = built("bitsliced", "idl")
        with ScatterGatherRouter(set_dir) as router:
            assert router.n_shards == spec.n_shards
            assert router.spec == spec
            assert router.live_shards() == list(range(spec.n_shards))
            stats = router.stats()
            assert set(stats) == set(range(spec.n_shards))

    def test_router_rejects_malformed_reads(self, built):
        _, _, set_dir, _ = built("bitsliced", "idl")
        with ScatterGatherRouter(set_dir) as router:
            with pytest.raises(ValueError, match="one 1-D read"):
                router.submit(np.zeros((2, 120), dtype=np.uint8))
            with pytest.raises(ValueError, match="has no 31-mers"):
                router.submit(np.zeros((7,), dtype=np.uint8))

    def test_bit_probe_shard_service_refuses_kmer_cache(self, built):
        from repro.serving.kmer_cache import KmerCacheConfig
        spec, states, _, _ = built("rambo", "idl")
        cfg = service_mod.ServiceConfig(
            kmer_cache=KmerCacheConfig(capacity=1 << 10))
        with pytest.raises(ValueError, match="partial miss counts"):
            ShardSearchService(spec, 0, states[0], cfg)

    def test_inprocess_kill_row_probe_names_missing_files(self, built,
                                                          queries):
        spec, _, set_dir, _ = built("bitsliced", "idl")
        with ScatterGatherRouter(set_dir) as router:
            router.search(queries[:1])
            router.kill_shard(1)
            lost = shards.shard_files(spec, 1)
            res = router.search(queries)
            for r in res:
                assert r.missing_files == lost
                assert not np.asarray(r.matches)[list(lost)].any()
            assert router.live_shards() == [0]

    def test_inprocess_kill_bit_probe_fails_loud(self, built, queries):
        """A rambo shard taken down under a stream: what it already held
        resolves exactly, every later gather raises ShardDeadError — never
        a silently inflated answer, never a dropped future."""
        _, _, set_dir, full = built("rambo", "idl")
        svc_cfg = service_mod.ServiceConfig(theta=0.6, max_batch=4)
        want = service_mod.GeneSearchService(full, svc_cfg).search(queries)
        with ScatterGatherRouter(
                set_dir, ScatterConfig(service=svc_cfg)) as router:
            futures = [router.submit(q) for q in queries]
            router.kill_shard(0)
            futures += [router.submit(q) for q in queries]
            outcomes = {"ok": 0, "dead": 0}
            for i, f in enumerate(futures):
                try:
                    r = f.result(timeout=120)
                    np.testing.assert_array_equal(
                        np.asarray(r.matches),
                        np.asarray(want[i % len(want)].matches))
                    outcomes["ok"] += 1
                except ShardDeadError:
                    outcomes["dead"] += 1
            # zero dropped: the shard drained what it held before it
            # went down, and every later gather failed loud
            assert outcomes == {"ok": len(queries), "dead": len(queries)}
            assert router.live_shards() == [1]
            with pytest.raises(ShardDeadError, match="failing loud"):
                router.submit(queries[0]).result(timeout=120)


# ---------------------------------------------------------------------------
# Persistence: the CRC-checked shard-set manifest fails by name.
# ---------------------------------------------------------------------------

class TestShardSetPersistence:

    def test_load_round_trip(self, built, tmp_path):
        spec, states, _, _ = built("rambo", "rh")
        out = str(tmp_path / "set")
        shards.save_shard_set(spec, states, out, version=7)
        sm, loaded = shards.load_shard_set(out)
        assert sm.spec == spec and sm.set_version == 7
        for got, want in zip(loaded, states):
            for gw, ww in zip(got.words, want.words):
                np.testing.assert_array_equal(np.asarray(gw),
                                              np.asarray(ww))

    @pytest.fixture()
    def set_copy(self, built, tmp_path):
        _, _, set_dir, _ = built("bitsliced", "idl")
        dst = str(tmp_path / "set")
        shutil.copytree(set_dir, dst)
        return dst

    def test_missing_shard_dir_fails_by_name(self, set_copy):
        shutil.rmtree(os.path.join(set_copy, "shard_01"))
        with pytest.raises(shards.ShardSetError,
                           match="'shard_01' is missing"):
            shards.load_shard_set(set_copy)

    def test_rewritten_shard_manifest_fails_by_name(self, set_copy):
        manifest = os.path.join(set_copy, "shard_00", "manifest.json")
        with open(manifest) as f:
            doc = json.load(f)
        with open(manifest, "w") as f:
            json.dump(doc, f, indent=3)     # same content, foreign bytes
        with pytest.raises(shards.ShardSetError,
                           match="foreign or rewritten"):
            shards.load_shard(set_copy, 0)

    def test_corrupt_set_manifest_fails_closed(self, set_copy):
        path = os.path.join(set_copy, shards.SET_MANIFEST)
        with open(path) as f:
            doc = json.load(f)
        doc["body"]["n_shards"] = 3         # body edit without new CRC
        with open(path, "w") as f:
            json.dump(doc, f)
        with pytest.raises(shards.ShardSetError,
                           match="truncated or rewritten"):
            shards.read_set_meta(set_copy)

    def test_store_load_points_at_shard_set_loader(self, set_copy):
        with pytest.raises(store.SnapshotError,
                           match="SHARD-SET snapshot"):
            store.load(set_copy)

    def test_store_read_meta_answers_with_full_meta(self, built):
        spec, _, set_dir, _ = built("bitsliced", "idl")
        assert store.read_meta(set_dir) == spec.meta

    def test_plan_rejects_infeasible_shard_counts(self, built):
        spec, _, _, _ = built("bitsliced", "idl")
        with pytest.raises(shards.ShardSetError, match="want 1 <="):
            shards.plan_shards(spec.meta, 1000)
        with pytest.raises(shards.ShardSetError, match="want 1 <="):
            shards.plan_shards(spec.meta, 0)
