"""Versioned on-disk snapshots of :class:`~repro.index.state.IndexState`.

A snapshot is one directory::

    snapshot/
      manifest.json      # format tag + version + StateMeta + array specs
      words_0.npy        # raw packed uint32 word matrices, one per leaf
      words_1.npy        # (COBS: one per size group)

``save(state_or_engine, dir)`` / ``load(dir)`` round-trip every engine
bit-exactly (``tests/test_store.py`` proves save→load→query parity for
all four engines × schemes). Design points:

* the word matrices are plain ``.npy`` files — ``load`` reads them with
  ``np.load(mmap_mode="r")`` so a multi-GB serving index pages in lazily
  and the host never holds a second copy while the device upload streams;
* the manifest carries a format tag and an integer version; any mismatch
  (foreign directory, future version) is rejected with a clear
  :class:`SnapshotError` instead of garbage answers;
* every array records shape, dtype and a CRC-32: truncated or bit-rotted
  words files fail loudly. ``verify`` picks WHEN the checksum pass runs:
  ``"eager"`` (default — every byte read before ``load`` returns),
  ``"lazy"`` (a background thread checksums the files while the caller
  already serves; :func:`check_verified` surfaces a failure), or
  ``"off"``. Manifest shape/dtype specs are always checked — they cost
  one ``.npy`` header read, not a data pass — so a truncated file still
  fails at open time even with verification off.

Cold-start cost is the reason the knobs exist: a server booting from an
on-disk snapshot wants the open to be O(manifest), not O(index bytes).
``mmap=True`` + ``verify="lazy"`` reads the data exactly once, while the
caller already serves; ``device=False`` additionally
keeps the word matrices as memory-mapped numpy leaves, deferring even
the page-in until first use (the first computation converts — and pays
the upload — once).

``load`` returns an :class:`IndexState`; ``load_engine`` rebuilds the
engine view in one call. Serving startup
(:meth:`repro.serving.service.GeneSearchService.from_snapshot`) is a thin
wrapper over ``load``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import zlib
from typing import Dict, Optional, Union

import jax.numpy as jnp
import numpy as np

from repro.core import idl as idl_mod
from repro.index import state as state_mod

FORMAT = "idl-index-snapshot"
VERSION = 1
MANIFEST = "manifest.json"


class SnapshotError(ValueError):
    """A snapshot directory is missing, foreign, corrupt, or from an
    incompatible format version."""


# ---------------------------------------------------------------------------
# Meta <-> JSON.
# ---------------------------------------------------------------------------

def _cfg_to_json(cfg: idl_mod.IDLConfig) -> dict:
    return dataclasses.asdict(cfg)


def _cfg_from_json(d: dict) -> idl_mod.IDLConfig:
    try:
        return idl_mod.IDLConfig(**d)
    except TypeError as e:
        raise SnapshotError(
            f"snapshot IDLConfig does not match this build's fields: {e}"
        ) from e


def meta_to_json(meta: state_mod.StateMeta) -> dict:
    return {
        "engine": meta.engine,
        "scheme": meta.scheme,
        "cfgs": [_cfg_to_json(c) for c in meta.cfgs],
        "n_files": meta.n_files,
        "k": meta.k,
        "group_file_ids": (
            None if meta.group_file_ids is None
            else [list(g) for g in meta.group_file_ids]),
        "n_buckets": meta.n_buckets,
        "n_rep": meta.n_rep,
    }


def meta_from_json(d: dict) -> state_mod.StateMeta:
    try:
        return state_mod.StateMeta(
            engine=d["engine"],
            scheme=d["scheme"],
            cfgs=tuple(_cfg_from_json(c) for c in d["cfgs"]),
            n_files=d.get("n_files"),
            k=d.get("k"),
            group_file_ids=(
                None if d.get("group_file_ids") is None
                else tuple(tuple(int(i) for i in g)
                           for g in d["group_file_ids"])),
            n_buckets=d.get("n_buckets"),
            n_rep=d.get("n_rep"),
        )
    except (KeyError, TypeError, ValueError) as e:
        raise SnapshotError(f"snapshot meta is malformed: {e!r}") from e


# ---------------------------------------------------------------------------
# Save / load.
# ---------------------------------------------------------------------------

def save(index: Union[state_mod.IndexState, object], directory: str) -> str:
    """Write a versioned snapshot of an ``IndexState`` (or engine view).

    Creates ``directory`` if needed and (over)writes ``manifest.json`` plus
    one ``words_<i>.npy`` per state leaf. Returns ``directory``.
    """
    state = state_mod.from_engine(index)
    state_mod.ensure_live(state, *state.words, what="IndexState")
    os.makedirs(directory, exist_ok=True)
    arrays = []
    for i, w in enumerate(state.words):
        arr = np.ascontiguousarray(np.asarray(w))
        fname = f"words_{i}.npy"
        np.save(os.path.join(directory, fname), arr)
        arrays.append({
            "file": fname,
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
            "crc32": zlib.crc32(arr.tobytes()),
        })
    manifest = {
        "format": FORMAT,
        "version": VERSION,
        "meta": meta_to_json(state.meta),
        "arrays": arrays,
    }
    tmp = os.path.join(directory, MANIFEST + ".tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1)
    os.replace(tmp, os.path.join(directory, MANIFEST))  # atomic publish
    return directory


def _read_manifest(directory: str) -> dict:
    path = os.path.join(directory, MANIFEST)
    if not os.path.exists(path):
        from repro.index import shards as shards_mod

        if shards_mod.is_shard_set(directory):
            raise SnapshotError(
                f"{directory!r} is a SHARD-SET snapshot, not a single-index "
                f"snapshot — load it with repro.index.shards.load_shard_set "
                f"(or read its meta with store.read_meta)")
        raise SnapshotError(f"no {MANIFEST} in {directory!r} — not a snapshot")
    try:
        with open(path) as f:
            manifest = json.load(f)
    except json.JSONDecodeError as e:
        raise SnapshotError(f"corrupt {MANIFEST} in {directory!r}: {e}") from e
    if manifest.get("format") != FORMAT:
        raise SnapshotError(
            f"{directory!r} is not an index snapshot "
            f"(format tag {manifest.get('format')!r}, want {FORMAT!r})")
    version = manifest.get("version")
    if version != VERSION:
        raise SnapshotError(
            f"snapshot format version {version!r} is not supported by this "
            f"build (reads version {VERSION}); rebuild the snapshot or "
            f"upgrade the reader")
    return manifest


VERIFY_MODES = ("eager", "lazy", "off")


class _LazyVerify:
    """Handle for one background checksum pass over a snapshot."""

    def __init__(self, directory: str, specs: list):
        self.directory = directory
        self.error: Optional[SnapshotError] = None
        self._thread = threading.Thread(
            target=self._run, args=(specs,), daemon=True,
            name=f"idl-snapshot-verify")
        self._thread.start()

    def _run(self, specs: list) -> None:
        try:
            for spec in specs:
                path = os.path.join(self.directory, spec["file"])
                arr = np.load(path, mmap_mode="r")
                crc = zlib.crc32(np.ascontiguousarray(arr).tobytes())
                if crc != spec["crc32"]:
                    raise SnapshotError(
                        f"array {spec['file']!r} failed its background "
                        f"checksum (crc32 {crc} != manifest "
                        f"{spec['crc32']}) — snapshot is corrupt")
        except SnapshotError as e:
            self.error = e
        except Exception as e:  # noqa: BLE001 - any read failure is corrupt
            self.error = SnapshotError(
                f"background verify of {self.directory!r} failed: {e!r}")

    def check(self, *, wait: bool = True) -> bool:
        if wait:
            self._thread.join()
        elif self._thread.is_alive():
            return False
        if self.error is not None:
            raise self.error
        return True


_lazy_verifies: Dict[str, _LazyVerify] = {}
_lazy_lock = threading.Lock()


def check_verified(directory: str, *, wait: bool = True) -> bool:
    """Outcome of a ``verify="lazy"`` load's background checksum pass.

    Returns True once the pass finished clean (or no lazy load of
    ``directory`` is registered — nothing to distrust); False while it is
    still running and ``wait=False``. Raises :class:`SnapshotError` if
    the snapshot failed its checksums — the loud failure a lazily booted
    worker must surface instead of serving bit-rotted words forever.
    """
    with _lazy_lock:
        handle = _lazy_verifies.get(os.path.abspath(directory))
    if handle is None:
        return True
    return handle.check(wait=wait)


def read_meta(directory: str) -> state_mod.StateMeta:
    """Read just the snapshot's :class:`StateMeta` — O(manifest), no array
    bytes touched: the kmer size and bucket geometry without holding the
    index itself. Shard-set
    snapshots (see :mod:`repro.index.shards`) answer with the FULL
    unsharded meta from their CRC-checked set manifest."""
    if not os.path.exists(os.path.join(directory, MANIFEST)):
        from repro.index import shards as shards_mod

        if shards_mod.is_shard_set(directory):
            return shards_mod.read_set_meta(directory).spec.meta
    return meta_from_json(_read_manifest(directory)["meta"])


def _normalize_verify(verify) -> str:
    if verify is True:
        return "eager"
    if verify is False:
        return "off"
    if verify not in VERIFY_MODES:
        raise ValueError(
            f"verify must be one of {VERIFY_MODES} (or a legacy bool), "
            f"got {verify!r}")
    return verify


def load(directory: str, *, mmap: bool = True,
         verify: Union[str, bool] = "eager",
         device: bool = True) -> state_mod.IndexState:
    """Load a snapshot back into an :class:`IndexState`.

    ``mmap=True`` opens the word files memory-mapped, so bytes page in as
    the device upload consumes them. ``verify`` schedules the CRC-32
    checksum pass: ``"eager"`` checks every array before returning (reads
    all bytes — the default, and what a cold trust boundary wants),
    ``"lazy"`` starts a background thread and returns immediately
    (:func:`check_verified` reports/raises its outcome), ``"off"`` skips
    it. Legacy ``True``/``False`` map
    to eager/off. Manifest shape/dtype specs are ALWAYS validated — one
    header read per file, so a truncated or reshaped array still fails
    loudly at open time in every mode. ``device=False`` keeps the word
    matrices as memory-mapped numpy leaves instead of uploading them:
    the open is O(manifest) and the first computation over the state
    pays the page-in + conversion (use for metadata tooling or when the
    caller controls materialization). Raises :class:`SnapshotError` on
    any mismatch.
    """
    verify = _normalize_verify(verify)
    manifest = _read_manifest(directory)
    meta = meta_from_json(manifest["meta"])
    specs = manifest.get("arrays", [])
    if len(specs) != len(meta.cfgs):
        raise SnapshotError(
            f"snapshot has {len(specs)} arrays but meta describes "
            f"{len(meta.cfgs)} — manifest is inconsistent")
    words = []
    for spec in specs:
        fname = spec["file"]
        if os.path.basename(fname) != fname or fname in ("", ".", ".."):
            # a crafted manifest must not read outside the snapshot dir
            raise SnapshotError(
                f"snapshot array file {fname!r} is not a plain file name")
        path = os.path.join(directory, fname)
        if not os.path.exists(path):
            raise SnapshotError(f"snapshot array file missing: {path!r}")
        try:
            arr = np.load(path, mmap_mode="r" if mmap else None)
        except ValueError as e:
            raise SnapshotError(f"corrupt array file {path!r}: {e}") from e
        if list(arr.shape) != list(spec["shape"]) or \
                str(arr.dtype) != spec["dtype"]:
            raise SnapshotError(
                f"array {spec['file']!r} is {arr.dtype}{arr.shape}, "
                f"manifest says {spec['dtype']}{tuple(spec['shape'])}")
        if verify == "eager":
            crc = zlib.crc32(np.ascontiguousarray(arr).tobytes())
            if crc != spec["crc32"]:
                raise SnapshotError(
                    f"array {spec['file']!r} failed its checksum "
                    f"(crc32 {crc} != manifest {spec['crc32']}) — "
                    f"snapshot is corrupt")
        words.append(jnp.asarray(arr) if device else arr)
    if verify == "lazy":
        with _lazy_lock:
            _lazy_verifies[os.path.abspath(directory)] = _LazyVerify(
                directory, list(specs))
    return state_mod.IndexState(words=tuple(words), meta=meta)


def load_engine(directory: str, **kw):
    """Load a snapshot and rebuild the engine view in one call."""
    return state_mod.to_engine(load(directory, **kw))
