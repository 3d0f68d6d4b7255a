"""The four index engines behind the :class:`GeneIndex` protocol.

=====================  =====================================================
Engine                 Storage (canonical packed-uint32 words)
=====================  =====================================================
PackedBloomIndex       flat partitioned BF: ``(m/32,)``
CobsIndex              size-grouped bit-sliced matrices: ``(m_g, ⌈F_g/32⌉)``
RamboIndex             stacked bucket BFs: ``(R·B, m_b/32)``
BitSlicedIndex         single bit-sliced matrix: ``(m, ⌈F/32⌉)`` (serving)
=====================  =====================================================

All engines resolve their hash family by name through
:mod:`repro.index.registry`. Engines are immutable dataclasses and thin
*views* over a :class:`repro.index.state.IndexState` pytree (``.state`` /
``.with_state()`` — protocol v2); ``insert_batch`` returns a new value and
donates the old buffer (linear use). The donated input is marked consumed:
using it again raises :class:`repro.index.state.StaleIndexError` with a
clear message instead of a backend-dependent deleted-buffer crash; pass
``donate=False`` to keep the input alive at the cost of one copy.

Both data paths route through shared planner/executor layers that treat
every engine's storage as a packed ``(n_rows, W)`` bit-matrix:

* queries through :mod:`repro.index.query` — backends ``"jnp"`` (pure-XLA
  gather), ``"idl_probe"`` (device run-length planner + the generalized
  Pallas ``probe_rows`` kernel), ``"sharded"`` (``shard_map`` over a 1-D
  device mesh);
* inserts through :mod:`repro.index.ingest` — backends ``"jnp"`` (one
  donated in-place scatter-OR), ``"idl_insert"`` (host run planner + the
  Pallas ``insert_runs`` kernel, one launch per batch), ``"sharded"``
  (device-local scatters, no collectives).

All backends of both paths are bit-identical
(``tests/test_index_parity.py``, ``tests/test_ingest.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing, idl as idl_mod
from repro.index import ingest, packed, query
from repro.index import state as state_mod


def _as_batch(reads: jax.Array) -> jax.Array:
    reads = jnp.asarray(reads)
    return reads[None, :] if reads.ndim == 1 else reads


class _StateView:
    """Protocol-v2 mixin: every engine is a thin view over an IndexState."""

    @property
    def state(self) -> state_mod.IndexState:
        """The pytree-native storage behind this view."""
        return state_mod.from_engine(self)

    def with_state(self, state: state_mod.IndexState):
        """Rebuild an engine view over ``state`` (same kind required)."""
        kind = state_mod.from_engine(self).meta.engine
        if state.meta.engine != kind:
            raise ValueError(
                f"with_state: state is for engine {state.meta.engine!r}, "
                f"this view is {kind!r}")
        return state_mod.to_engine(state)


def _as_file_ids(file_ids, batch: int) -> np.ndarray:
    if file_ids is None:
        raise ValueError("this engine requires file_ids for insert_batch")
    arr = np.atleast_1d(np.asarray(file_ids, dtype=np.int32))
    if arr.shape != (batch,):
        raise ValueError(f"file_ids shape {arr.shape} != batch ({batch},)")
    return arr


# ---------------------------------------------------------------------------
# Partitioned Bloom filter.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PackedBloomIndex(_StateView):
    """Single-set partitioned BF over any registered hash scheme."""

    cfg: idl_mod.IDLConfig
    scheme: str = "idl"
    words: Optional[jax.Array] = None     # (m/32,) uint32

    def __post_init__(self):
        if self.cfg.m % 32:
            raise ValueError(f"m={self.cfg.m} must be a multiple of 32")
        if self.words is None:
            object.__setattr__(
                self, "words", jnp.zeros((self.cfg.m // 32,), dtype=jnp.uint32)
            )

    @classmethod
    def build(cls, cfg: idl_mod.IDLConfig, scheme: str = "idl") -> "PackedBloomIndex":
        return cls(cfg=cfg, scheme=scheme)

    def insert_batch(self, reads, file_ids=None, **kw) -> "PackedBloomIndex":
        """Index a (B, read_len) batch; ``file_ids`` is ignored (single set).

        Keyword args pick the shared ingest executor (see
        :mod:`repro.index.ingest`): ``backend`` in {"jnp", "idl_insert",
        "sharded"}, plus ``mesh`` / ``interpret`` / ``use_ref`` /
        ``window_min`` passthroughs. All backends are bit-identical
        (``window_min`` sub-sampling excepted — it inserts fewer kmers).
        ``donate=True`` (default) donates this value's buffer and marks it
        consumed — keep only the returned index; ``donate=False`` keeps
        this value usable (one extra copy).
        """
        del file_ids
        state_mod.ensure_live(self, self.words, what="engine")
        reads = _as_batch(reads)
        plan = ingest.plan_insert(
            self.cfg, self.scheme, reads.shape, (self.cfg.m // 32, 1),
            kind="bits", window_min=kw.pop("window_min", None),
        )
        donate = kw.pop("donate", True)
        words = plan.execute(self.words, reads, donate=donate, **kw)
        if donate:
            state_mod.mark_consumed(self)
        return dataclasses.replace(self, words=words)

    def _plan(self, reads: jax.Array) -> query.QueryPlan:
        return query.plan_query(
            self.cfg, self.scheme, reads.shape,
            (self.cfg.m // 32, 1), bit_probe=True,
        )

    def query_batch(self, reads, *, backend: str = "jnp", **kw) -> jax.Array:
        """(B, n_kmers) bool per-kmer membership.

        ``backend`` picks the shared query executor (see
        :mod:`repro.index.query`): ``"jnp"``, ``"idl_probe"`` (device
        run-length planner + Pallas kernel; kw ``interpret`` forces or
        disables Pallas interpreter mode, defaulting to interpret on CPU;
        kw ``use_ref`` swaps in the kernel's fused jnp oracle) or
        ``"sharded"`` (``shard_map`` over kw ``mesh``, default the full
        1-D device mesh).
        """
        state_mod.ensure_live(self, self.words, what="engine")
        reads = _as_batch(reads)
        vals = self._plan(reads).execute(
            self.words, reads, backend=backend, **kw
        )
        return vals[..., 0] == 1

    def msmt(self, reads, theta: float = 1.0, **kw) -> jax.Array:
        """(B,) bool: kmer-coverage of the one indexed set >= theta."""
        return query.member_coverage(self.query_batch(reads, **kw), theta)

    @property
    def bits(self) -> jax.Array:
        """Compatibility view: (m,) uint8 bit-per-byte layout."""
        from repro.core import bloom as bloom_mod

        return bloom_mod.unpack_bits(self.words)

    @property
    def fill_fraction(self) -> jax.Array:
        return jnp.mean(self.bits.astype(jnp.float32))


# ---------------------------------------------------------------------------
# COBS — compact bit-sliced signature index (size-grouped).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CobsGroupState:
    """One size-group: files sharing a filter size ``cfg.m``."""

    cfg: idl_mod.IDLConfig
    file_ids: tuple[int, ...]
    words: Optional[jax.Array] = None     # (m_g, ceil(n_files/32)) uint32

    def __post_init__(self):
        if self.words is None:
            w = -(-len(self.file_ids) // 32)
            object.__setattr__(
                self, "words", jnp.zeros((self.cfg.m, w), dtype=jnp.uint32)
            )


@dataclasses.dataclass(frozen=True)
class CobsIndex(_StateView):
    """Size-grouped bit-sliced filters over N files (BIGSI/COBS layout)."""

    groups: tuple[CobsGroupState, ...]
    scheme: str
    n_files: int
    k: int

    def __post_init__(self):
        ks = {g.cfg.k for g in self.groups}
        if not self.groups:
            raise ValueError("CobsIndex needs at least one group")
        if ks != {self.k}:
            raise ValueError(f"groups disagree on k: {sorted(ks)} vs k={self.k}")

    @classmethod
    def build(
        cls,
        file_sizes: Sequence[int],
        base_cfg: idl_mod.IDLConfig,
        scheme: str = "idl",
        bits_per_kmer: float = 10.0,
        n_groups: int = 2,
    ) -> "CobsIndex":
        """Group files by kmer count; m_g sized from the group's largest file."""
        if len(file_sizes) == 0:
            raise ValueError("CobsIndex.build needs at least one file")
        order = np.argsort(file_sizes)
        chunks = np.array_split(order, n_groups)
        groups = []
        for chunk in chunks:
            if len(chunk) == 0:
                continue
            biggest = max(int(file_sizes[i]) for i in chunk)
            m_g = _round_up(int(bits_per_kmer * biggest), 1 << 12)
            m_g = max(m_g, base_cfg.eta * (base_cfg.L * 2))
            cfg = dataclasses.replace(base_cfg, m=m_g)
            groups.append(
                CobsGroupState(cfg=cfg, file_ids=tuple(int(i) for i in chunk))
            )
        return cls(groups=tuple(groups), scheme=scheme,
                   n_files=len(file_sizes), k=base_cfg.k)

    def _slot(self, file_id: int) -> tuple[int, int]:
        for gi, g in enumerate(self.groups):
            if file_id in g.file_ids:
                return gi, g.file_ids.index(file_id)
        raise KeyError(f"file {file_id} not in any group")

    def insert_batch(self, reads, file_ids=None, **kw) -> "CobsIndex":
        """Index reads into their files' group columns (one scatter/group).

        Keyword args pick the shared ingest executor (see
        :mod:`repro.index.ingest`); ``donate=False`` keeps this value
        usable after the insert.
        """
        state_mod.ensure_live(self, *(g.words for g in self.groups),
                              what="engine")
        reads = _as_batch(reads)
        fids = _as_file_ids(file_ids, reads.shape[0])
        window_min = kw.pop("window_min", None)
        donate = kw.pop("donate", True)
        slots = [self._slot(int(f)) for f in fids]
        groups = list(self.groups)
        for gi in sorted({gi for gi, _ in slots}):
            sel = np.array([i for i, (g, _) in enumerate(slots) if g == gi])
            cols = jnp.asarray(
                np.array([slots[i][1] for i in sel], dtype=np.int32))
            g = groups[gi]
            sub = jnp.take(reads, jnp.asarray(sel), axis=0)
            plan = ingest.plan_insert(
                g.cfg, self.scheme, sub.shape, g.words.shape,
                kind="cols", window_min=window_min,
            )
            words = plan.execute(g.words, sub, cols, donate=donate, **kw)
            groups[gi] = dataclasses.replace(g, words=words)
        if donate:
            state_mod.mark_consumed(self)
        return dataclasses.replace(self, groups=tuple(groups))

    def query_batch(self, reads, *, backend: str = "jnp", **kw) -> jax.Array:
        """(B, n_kmers, n_files) bool MSMT kmer slices (Definition 3)."""
        state_mod.ensure_live(self, *(g.words for g in self.groups),
                              what="engine")
        reads = _as_batch(reads)
        n_k = reads.shape[1] - self.k + 1
        out = jnp.zeros((reads.shape[0], n_k, self.n_files), dtype=bool)
        for g in self.groups:
            plan = query.plan_query(
                g.cfg, self.scheme, reads.shape, g.words.shape,
                bit_probe=False,
            )
            masks = plan.execute(g.words, reads, backend=backend, **kw)
            sl = packed.unpack_file_bits(masks, len(g.file_ids))
            out = out.at[:, :, jnp.asarray(g.file_ids)].set(sl)
        return out

    def msmt(self, reads, theta: float = 1.0, **kw) -> jax.Array:
        """(B, n_files) bool: per-file kmer-coverage >= theta."""
        return query.member_coverage(self.query_batch(reads, **kw), theta)

    @property
    def total_bits(self) -> int:
        return sum(int(g.cfg.m) * len(g.file_ids) for g in self.groups)


# ---------------------------------------------------------------------------
# RAMBO — repeated and merged bucketed Bloom filters.
# ---------------------------------------------------------------------------

def rambo_dimensions(
    n_files: int, B: Optional[int] = None, R: Optional[int] = None
) -> tuple[int, int]:
    """Default RAMBO shape: B = O(sqrt N) buckets, R = O(log N) repetitions."""
    if B is None:
        B = max(2, int(np.ceil(np.sqrt(n_files))))
    if R is None:
        R = max(2, int(np.ceil(np.log2(max(n_files, 2)))))
    return B, R


def rambo_assignment(n_files: int, n_buckets: int, n_rep: int) -> np.ndarray:
    """(R, N) int32 file->bucket map (same hash family as the query path)."""
    files = np.arange(n_files, dtype=np.uint64)
    return np.stack(
        [
            hashing.np_hash_to_range(files, 0xA3B0 + r, n_buckets).astype(np.int32)
            for r in range(n_rep)
        ],
        axis=0,
    )


@dataclasses.dataclass(frozen=True)
class RamboIndex(_StateView):
    """B buckets × R repetitions of merged BFs; sub-linear MSMT."""

    cfg: idl_mod.IDLConfig                 # cfg.m = bits per bucket BF
    scheme: str
    n_files: int
    n_buckets: int                         # B
    n_rep: int                             # R
    words: Optional[jax.Array] = None      # (R*B, m/32) uint32
    assignment: Optional[np.ndarray] = None

    def __post_init__(self):
        if self.cfg.m % 32:
            raise ValueError(f"bucket size m={self.cfg.m} must be a multiple of 32")
        if self.words is None:
            object.__setattr__(
                self, "words",
                jnp.zeros((self.n_rep * self.n_buckets, self.cfg.m // 32),
                          dtype=jnp.uint32),
            )
        if self.assignment is None:
            object.__setattr__(
                self, "assignment",
                rambo_assignment(self.n_files, self.n_buckets, self.n_rep),
            )

    @classmethod
    def build(
        cls, n_files: int, cfg: idl_mod.IDLConfig, scheme: str = "idl",
        B: Optional[int] = None, R: Optional[int] = None,
    ) -> "RamboIndex":
        B, R = rambo_dimensions(n_files, B, R)
        return cls(cfg=cfg, scheme=scheme, n_files=n_files,
                   n_buckets=B, n_rep=R)

    def _filter_rows(self, fids: np.ndarray) -> jax.Array:
        offs = np.arange(self.n_rep, dtype=np.int32) * self.n_buckets
        return jnp.asarray(self.assignment[:, fids].T + offs[None, :])  # (B, R)

    @property
    def _words_t(self) -> jax.Array:
        """(m/32, R·B) transposed view for the query layer, materialized
        once per index value (insert_batch returns a fresh instance, so the
        cache can never alias a donated buffer)."""
        cached = getattr(self, "_words_t_cache", None)
        if cached is None or cached[0] is not self.words:
            cached = (self.words, jnp.asarray(self.words.T))
            object.__setattr__(self, "_words_t_cache", cached)
        return cached[1]

    def insert_batch(self, reads, file_ids=None, **kw) -> "RamboIndex":
        """Index reads into their R bucket filters (shared ingest layer)."""
        state_mod.ensure_live(self, self.words, what="engine")
        reads = _as_batch(reads)
        fids = _as_file_ids(file_ids, reads.shape[0])
        plan = ingest.plan_insert(
            self.cfg, self.scheme, reads.shape, self.words.shape,
            kind="rows", window_min=kw.pop("window_min", None),
        )
        donate = kw.pop("donate", True)
        words = plan.execute(self.words, reads, self._filter_rows(fids),
                             donate=donate, **kw)
        if donate:
            state_mod.mark_consumed(self)
        return dataclasses.replace(self, words=words)

    def query_grid(self, reads, *, backend: str = "jnp", **kw) -> jax.Array:
        """(B, n_kmers, R, buckets) bool: bucket hits per kmer.

        The R·B stacked filters are probed as ONE transposed
        ``(m/32, R·B)`` bit-matrix: every location resolves all buckets'
        bits from a single gathered row of the shared query layer.
        """
        state_mod.ensure_live(self, self.words, what="engine")
        reads = _as_batch(reads)
        rb = self.n_rep * self.n_buckets
        plan = query.plan_query(
            self.cfg, self.scheme, reads.shape,
            (self.cfg.m // 32, rb), bit_probe=True,
        )
        vals = plan.execute(
            self._words_t, reads, backend=backend, **kw
        )                                                 # (B, n_k, RB) {0,1}
        return (vals == 1).reshape(
            vals.shape[0], vals.shape[1], self.n_rep, self.n_buckets
        )

    def query_batch(self, reads, *, backend: str = "jnp", **kw) -> jax.Array:
        """(B, n_kmers, n_files) bool: file present in all R of its buckets."""
        grid = self.query_grid(reads, backend=backend, **kw)  # (B, n_k, R, Bkt)
        idx = jnp.asarray(self.assignment)[None, None]    # (1, 1, R, N)
        per_rep = jnp.take_along_axis(grid, idx, axis=3)  # (B, n_k, R, N)
        return jnp.all(per_rep, axis=2)

    def msmt(self, reads, theta: float = 1.0, **kw) -> jax.Array:
        return query.member_coverage(self.query_batch(reads, **kw), theta)

    @property
    def total_bits(self) -> int:
        return int(self.words.shape[0]) * int(self.words.shape[1]) * 32


# ---------------------------------------------------------------------------
# Bit-sliced serving index (the paper's system; 32-bit lane path).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BitSlicedIndex(_StateView):
    """One bit-sliced (m, F/32) matrix queried on the TPU 32-bit lane path."""

    cfg: idl_mod.IDLConfig
    scheme: str
    n_files: int
    words: Optional[jax.Array] = None      # (m, ceil(n_files/32)) uint32

    def __post_init__(self):
        if self.words is None:
            w = -(-self.n_files // 32)
            object.__setattr__(
                self, "words", jnp.zeros((self.cfg.m, w), dtype=jnp.uint32)
            )

    @classmethod
    def build(
        cls, cfg: idl_mod.IDLConfig, scheme: str = "idl", n_files: int = 1024
    ) -> "BitSlicedIndex":
        return cls(cfg=cfg, scheme=scheme, n_files=n_files)

    def insert_batch(self, reads, file_ids=None, **kw) -> "BitSlicedIndex":
        """Index reads into their file columns (shared ingest layer)."""
        state_mod.ensure_live(self, self.words, what="engine")
        reads = _as_batch(reads)
        fids = _as_file_ids(file_ids, reads.shape[0])
        plan = ingest.plan_insert(
            self.cfg, self.scheme, reads.shape, self.words.shape,
            kind="cols", lane32=True, window_min=kw.pop("window_min", None),
        )
        donate = kw.pop("donate", True)
        words = plan.execute(self.words, reads, jnp.asarray(fids),
                             donate=donate, **kw)
        if donate:
            state_mod.mark_consumed(self)
        return dataclasses.replace(self, words=words)

    def query_batch(self, reads, *, backend: str = "jnp", **kw) -> jax.Array:
        """(B, n_kmers, F/32) uint32 per-kmer file masks (packed)."""
        state_mod.ensure_live(self, self.words, what="engine")
        reads = _as_batch(reads)
        plan = query.plan_query(
            self.cfg, self.scheme, reads.shape, self.words.shape,
            bit_probe=False, lane32=True,
        )
        return plan.execute(self.words, reads, backend=backend, **kw)

    def msmt(self, reads, theta: float = 1.0, **kw) -> jax.Array:
        """(B, n_files) bool — the serve-layout MSMT (one theta rule)."""
        per_kmer = self.query_batch(reads, **kw)          # (B, n_k, W)
        mask = query.file_match_mask(per_kmer, theta)     # (B, W)
        return packed.unpack_file_bits(mask, self.n_files)


def _round_up(x: int, align: int) -> int:
    return -(-x // align) * align
