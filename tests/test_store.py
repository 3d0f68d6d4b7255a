"""Snapshot store: save→load→query bit-identical across 4 engines ×
{idl, rh, lsh} schemes, mmap/verify modes, and loud rejection of foreign,
corrupt, truncated or future-versioned snapshots."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import idl
from repro.index import (
    BitSlicedIndex,
    CobsIndex,
    PackedBloomIndex,
    RamboIndex,
    SnapshotError,
    store,
)
from repro.index import state as state_mod

ENGINES = ["bloom", "cobs", "rambo", "bitsliced"]
SCHEMES = ["idl", "rh", "lsh"]


def _cfg(m: int = 1 << 16) -> idl.IDLConfig:
    return idl.IDLConfig(k=31, t=16, L=1 << 10, eta=2, m=m)


@pytest.fixture(scope="module")
def reads(rng):
    return jnp.asarray(rng.integers(0, 4, size=(3, 120), dtype=np.uint8))


def _build(name: str, scheme: str, reads):
    if name == "bitsliced" and scheme == "lsh":
        pytest.skip("lsh has no 32-bit lane path (bit-sliced engines "
                    "run lane32)")
    fids = np.arange(reads.shape[0])
    if name == "bloom":
        return PackedBloomIndex.build(_cfg(), scheme).insert_batch(reads[:2])
    if name == "cobs":
        return CobsIndex.build(
            [100, 200, 150], _cfg(), scheme=scheme, n_groups=2
        ).insert_batch(reads, fids)
    if name == "rambo":
        return RamboIndex.build(
            5, _cfg(1 << 14), scheme=scheme, B=2, R=2
        ).insert_batch(reads, fids)
    if name == "bitsliced":
        return BitSlicedIndex.build(
            _cfg(), scheme, n_files=40
        ).insert_batch(reads, np.asarray([0, 9, 39]))
    raise KeyError(name)


class TestRoundTrip:
    @pytest.mark.parametrize("scheme", SCHEMES)
    @pytest.mark.parametrize("engine", ENGINES)
    def test_save_load_query_bit_identical(self, tmp_path, reads, engine,
                                           scheme):
        eng = _build(engine, scheme, reads)
        store.save(eng, str(tmp_path / "snap"))
        loaded = store.load(str(tmp_path / "snap"))
        assert loaded.meta == eng.state.meta
        view = state_mod.to_engine(loaded)
        np.testing.assert_array_equal(
            np.asarray(eng.query_batch(reads)),
            np.asarray(view.query_batch(reads)))
        for theta in (1.0, 0.6):
            np.testing.assert_array_equal(
                np.asarray(eng.msmt(reads, theta=theta)),
                np.asarray(view.msmt(reads, theta=theta)))

    def test_load_engine_and_no_mmap_and_no_verify(self, tmp_path, reads):
        eng = _build("bitsliced", "idl", reads)
        d = store.save(eng, str(tmp_path / "snap"))
        for kw in ({"mmap": False}, {"verify": False},
                   {"mmap": False, "verify": False}):
            view = store.load_engine(d, **kw)
            np.testing.assert_array_equal(
                np.asarray(view.words), np.asarray(eng.words))

    def test_save_accepts_state_and_is_rewritable(self, tmp_path, reads):
        st = _build("rambo", "idl", reads).state
        d = store.save(st, str(tmp_path / "snap"))
        store.save(st, d)                       # overwrite in place is fine
        loaded = store.load(d)
        np.testing.assert_array_equal(
            np.asarray(loaded.words[0]), np.asarray(st.words[0]))

    def test_save_rejects_consumed_state(self, tmp_path, reads):
        eng = _build("bloom", "idl", reads)
        _ = eng.insert_batch(reads[:1])
        from repro.index import StaleIndexError

        with pytest.raises(StaleIndexError):
            store.save(eng, str(tmp_path / "snap"))


class TestRejection:
    @pytest.fixture
    def snap(self, tmp_path, reads):
        eng = _build("bitsliced", "idl", reads)
        return store.save(eng, str(tmp_path / "snap"))

    def _manifest(self, snap):
        with open(os.path.join(snap, store.MANIFEST)) as f:
            return json.load(f)

    def _rewrite(self, snap, manifest):
        with open(os.path.join(snap, store.MANIFEST), "w") as f:
            json.dump(manifest, f)

    def test_missing_manifest(self, tmp_path):
        with pytest.raises(SnapshotError, match="not a snapshot"):
            store.load(str(tmp_path / "nowhere"))

    def test_shard_set_dir_points_at_the_right_loader(self, tmp_path,
                                                      reads):
        """A shard-set snapshot is NOT a single-index snapshot: loading
        one here names the real loader instead of claiming 'not a
        snapshot', and ``read_meta`` still answers — with the FULL
        unsharded meta, the geometry the set serves."""
        from repro.index import shards

        eng = _build("rambo", "idl", reads)
        spec, parts = shards.partition_state(eng, 2)
        d = str(tmp_path / "set")
        shards.save_shard_set(spec, parts, d)
        with pytest.raises(SnapshotError, match="SHARD-SET snapshot"):
            store.load(d)
        with pytest.raises(SnapshotError, match="load_shard_set"):
            store.load(d)
        assert store.read_meta(d) == eng.state.meta
        # an empty dir is still just "not a snapshot"
        with pytest.raises(SnapshotError, match="not a snapshot"):
            store.load(str(tmp_path / "nowhere"))

    def test_foreign_format_tag(self, snap):
        m = self._manifest(snap)
        m["format"] = "some-other-store"
        self._rewrite(snap, m)
        with pytest.raises(SnapshotError, match="not an index snapshot"):
            store.load(snap)

    def test_future_version_rejected(self, snap):
        m = self._manifest(snap)
        m["version"] = store.VERSION + 1
        self._rewrite(snap, m)
        with pytest.raises(SnapshotError, match="version"):
            store.load(snap)

    def test_corrupt_manifest_json(self, snap):
        with open(os.path.join(snap, store.MANIFEST), "w") as f:
            f.write("{not json")
        with pytest.raises(SnapshotError, match="corrupt"):
            store.load(snap)

    def test_missing_array_file(self, snap):
        os.remove(os.path.join(snap, "words_0.npy"))
        with pytest.raises(SnapshotError, match="missing"):
            store.load(snap)

    def test_bitrot_fails_checksum(self, snap):
        path = os.path.join(snap, "words_0.npy")
        raw = bytearray(open(path, "rb").read())
        raw[-1] ^= 0xFF                          # flip bits in the payload
        open(path, "wb").write(bytes(raw))
        with pytest.raises(SnapshotError, match="checksum"):
            store.load(snap)
        store.load(snap, verify=False)           # opt-out skips the scan

    def test_shape_mismatch_always_checked(self, snap):
        m = self._manifest(snap)
        m["arrays"][0]["shape"][0] += 1
        self._rewrite(snap, m)
        with pytest.raises(SnapshotError, match="manifest says"):
            store.load(snap, verify=False)

    def test_array_count_mismatch(self, snap):
        m = self._manifest(snap)
        m["arrays"] = []
        self._rewrite(snap, m)
        with pytest.raises(SnapshotError, match="inconsistent"):
            store.load(snap)

    def test_malformed_cfg_rejected(self, snap):
        m = self._manifest(snap)
        m["meta"]["cfgs"][0]["no_such_field"] = 1
        self._rewrite(snap, m)
        with pytest.raises(SnapshotError, match="IDLConfig"):
            store.load(snap)

    def test_wrong_typed_meta_rejected(self, snap):
        """TypeError-shaped corruption must still surface as SnapshotError."""
        m = self._manifest(snap)
        m["meta"]["cfgs"] = None
        self._rewrite(snap, m)
        with pytest.raises(SnapshotError, match="malformed"):
            store.load(snap)

    def test_array_path_escape_rejected(self, snap, tmp_path):
        """A crafted manifest must not read files outside the snapshot."""
        outside = tmp_path / "outside.npy"
        np.save(outside, np.zeros((4, 2), dtype=np.uint32))
        m = self._manifest(snap)
        for bad in (str(outside), "../outside.npy", "sub/words_0.npy"):
            m["arrays"][0]["file"] = bad
            self._rewrite(snap, m)
            with pytest.raises(SnapshotError, match="plain file name"):
                store.load(snap)


class TestVerifyModes:
    """The eager/lazy/off verification contract of ``store.load``."""

    @pytest.fixture
    def snap(self, tmp_path, reads):
        eng = _build("bitsliced", "idl", reads)
        return store.save(eng, str(tmp_path / "snap"))

    def _rot(self, snap):
        path = os.path.join(snap, "words_0.npy")
        raw = bytearray(open(path, "rb").read())
        raw[-1] ^= 0xFF
        open(path, "wb").write(bytes(raw))

    def test_bool_verify_maps_to_modes(self, snap):
        self._rot(snap)
        with pytest.raises(SnapshotError, match="checksum"):
            store.load(snap, verify=True)        # legacy True == "eager"
        store.load(snap, verify=False)           # legacy False == "off"
        store.load(snap, verify="off")

    def test_unknown_mode_rejected(self, snap):
        with pytest.raises(ValueError, match="verify must be one of"):
            store.load(snap, verify="sometimes")

    def test_lazy_load_of_clean_snapshot_verifies_in_background(self, snap):
        state = store.load(snap, verify="lazy")
        assert state.words[0].dtype == jnp.uint32
        assert store.check_verified(snap, wait=True) is True

    def test_lazy_load_of_corrupt_snapshot_fails_loudly(self, snap):
        """Lazy boot returns immediately, but the background pass still
        catches the rot: check_verified raises instead of letting a
        worker serve bit-rotted words forever."""
        self._rot(snap)
        store.load(snap, verify="lazy")          # boot succeeds (by design)
        with pytest.raises(SnapshotError, match="background checksum"):
            store.check_verified(snap, wait=True)
        # and the registry keeps raising on every later check
        with pytest.raises(SnapshotError, match="background checksum"):
            store.check_verified(snap, wait=False)

    def test_check_verified_without_lazy_load_is_trivially_true(self, snap):
        assert store.check_verified(snap) is True

    def test_truncated_array_fails_at_open_in_every_mode(self, snap):
        """Shape/dtype come from the .npy header vs the manifest; a short
        file can't even mmap to its declared shape — loud at open time
        with verification off entirely."""
        path = os.path.join(snap, "words_0.npy")
        raw = open(path, "rb").read()
        open(path, "wb").write(raw[: len(raw) // 2])
        for mode in ("eager", "lazy", "off"):
            with pytest.raises(SnapshotError):
                store.load(snap, verify=mode)

    def test_device_false_keeps_memmap_leaves(self, snap, reads):
        """O(manifest) open: leaves stay memory-mapped numpy arrays; the
        first computation converts and answers bit-identically."""
        lazy = store.load(snap, verify="off", device=False)
        assert isinstance(lazy.words[0], np.ndarray)
        assert not isinstance(lazy.words[0], jnp.ndarray)
        eager = store.load(snap)
        np.testing.assert_array_equal(
            np.asarray(state_mod.to_engine(lazy).msmt(reads)),
            np.asarray(state_mod.to_engine(eager).msmt(reads)))

    def test_read_meta_is_data_free(self, snap):
        meta = store.read_meta(snap)
        assert meta.engine == "bitsliced"
        assert state_mod.kmer_size(meta) == 31
        os.remove(os.path.join(snap, "words_0.npy"))   # no array bytes read
        assert store.read_meta(snap).engine == "bitsliced"
