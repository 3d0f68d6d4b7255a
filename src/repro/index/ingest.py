"""The shared ingest layer: one planned insert path for every engine.

Mirror of :mod:`repro.index.query` for the write side. The paper's claim is
that IDL speeds up *indexing and* query of COBS/RAMBO-style systems, and
RAMBO's pitch is whole-archive ingest measured in hours, not weeks — so the
build path gets the same treatment the query path got: every engine's
insert is a **scatter-OR of single bits into a packed ``(n_rows, W)``
uint32 bit-matrix**, described by ``(row, word_col, bit)`` targets —

======================  ==========================  ======================
Engine                  Target matrix               Target derivation
======================  ==========================  ======================
``PackedBloomIndex``    ``(m/32, 1)`` word column   ``(loc>>5, 0, loc&31)``
``RamboIndex``          ``(R·B, m/32)`` stack       ``(bucket_row, loc>>5, loc&31)``
``CobsIndex`` group     ``(m_g, ⌈F_g/32⌉)``         ``(loc, col>>5, col&31)``
``BitSlicedIndex``      ``(m, ⌈F/32⌉)``             ``(loc, col>>5, col&31)``
======================  ==========================  ======================

An :class:`InsertPlan` holds everything static — config, scheme, read
shape, matrix geometry, the run-coalescing tile height — and is built once
per ``(cfg, scheme, read_shape, matrix_shape)`` through an LRU cache
(:func:`plan_insert`). Executing a plan picks one of three backends:

* ``"jnp"``        — one jit-compiled, donated scatter-OR for the whole
  batch (the reference; the single implementation that replaced the
  three divergent scatter bodies in ``packed.py``);
* ``"idl_insert"`` — the host-side run-length planner + the generalized
  Pallas ``insert_runs`` kernel: the batch's targets are sorted,
  deduplicated and run-length-encoded by matrix row-block, each touched
  block costs ONE ``(rows_per_block, W)`` tile read + write (consecutive
  runs accumulate into the resident tile), and the whole batch executes as
  a single kernel launch with a donated destination;
* ``"sharded"``    — ``shard_map`` over a 1-D device mesh. Bit-scatter
  layouts (flat BF) split the words axis; row/column-scatter layouts
  (RAMBO word columns, COBS/bit-sliced file-words) split the W axis. Each
  shard drops the targets that are not its own — scatter-OR commutes, so
  there is no cross-shard traffic at all.

All backends are bit-identical; ``tests/test_ingest.py`` holds the parity
matrix. On top, :func:`build_archive` streams a whole archive of genome
files through the planner chunk-by-chunk (optional ``window_min``
minimizer sub-sampling), so an archive build is one Python loop of
jit-compiled donated inserts.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import hashing, idl as idl_mod, minhash
from repro.index import packed, query

BACKENDS = ("jnp", "idl_insert", "sharded")
KINDS = ("bits", "rows", "cols")
MESH_AXIS = query.MESH_AXIS


# ---------------------------------------------------------------------------
# Minimizer sub-sampling (optional archive-build densification knob).
# ---------------------------------------------------------------------------

def minimizer_mask(locs: jax.Array, w: int) -> jax.Array:
    """(B, n_kmers) bool: kmer is a window-``w`` minimizer of its read.

    The rank is a re-mix of the kmer's first-repetition location (so it is
    deterministic from the kmer, decorrelated from the probe address). A
    kmer is kept iff it attains the minimum rank of at least one length-w
    window containing it — the standard minimizer rule, computed with two
    Gil–Werman sliding minima (the second over inverted ranks = sliding
    max of the per-window minima). Reads shorter than w keep everything.
    """
    rank = hashing.mix32(locs[:, 0, :] ^ jnp.uint32(0x9E3779B9))
    n_k = rank.shape[1]
    if w <= 1 or n_k < w:
        return jnp.ones(rank.shape, dtype=bool)
    sw = jax.vmap(lambda r: minhash.sliding_window_min(r, w))(rank)
    inv = ~sw
    pad = jnp.full((inv.shape[0], w - 1), 0xFFFFFFFF, dtype=jnp.uint32)
    invp = jnp.concatenate([pad, inv, pad], axis=1)
    best = ~jax.vmap(lambda r: minhash.sliding_window_min(r, w))(invp)
    return best == rank


# ---------------------------------------------------------------------------
# The plan.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InsertPlan:
    """Static insert recipe for one (cfg, scheme, read_shape, matrix) tuple.

    ``kind`` names how a read's hash locations become (row, word, bit)
    targets: ``"bits"`` — locations are flat bit offsets of a packed word
    column (flat BF); ``"rows"`` — each read lands in aux filter rows and
    locations pick (word, bit) within the row (RAMBO); ``"cols"`` — each
    read owns an aux file column and locations pick the matrix row
    (bit-sliced COBS/serving layouts).
    """

    cfg: idl_mod.IDLConfig
    scheme: str
    read_shape: tuple[int, int]       # (B, read_len)
    matrix_shape: tuple[int, int]     # (n_rows, W)
    kind: str
    lane32: bool
    rows_per_block: int               # run-coalescing DMA tile height
    inserts_per_run: int
    window_min: Optional[int] = None  # minimizer sub-sampling window

    @property
    def batch(self) -> int:
        return self.read_shape[0]

    @property
    def n_kmers(self) -> int:
        return self.read_shape[1] - self.cfg.k + 1

    @property
    def n_rows(self) -> int:
        return self.matrix_shape[0]

    @property
    def row_words(self) -> int:
        return self.matrix_shape[1]

    @property
    def block_bits(self) -> int:
        """Bits per DMA tile in the flattened (rows*W*32) bit space."""
        return self.rows_per_block * self.row_words * 32

    @property
    def block_bytes(self) -> int:
        """HBM bytes one tile DMA moves — the quantity IDL minimizes."""
        return self.block_bits // 8

    # -- target stream (shared by every backend) ----------------------------
    def locations(self, reads: jax.Array) -> jax.Array:
        """(B, η, n_kmers) uint32 hash locations (the query layer's body)."""
        return query.batch_locations(
            reads, cfg=self.cfg, scheme=self.scheme, lane32=self.lane32
        )

    def targets(self, reads: jax.Array, aux: Optional[jax.Array] = None):
        """Flat (row, word_col, bit) int32/uint32 target streams.

        Targets masked off (minimizer sub-sampling) are routed to the
        out-of-range row ``n_rows`` and dropped by every backend's scatter.
        ``aux``: None (``"bits"``), (B, R) filter rows (``"rows"``), or
        (B,) file columns (``"cols"``).
        """
        locs = self.locations(reads)                    # (B, η, n_k)
        oob = jnp.int32(self.n_rows)
        keep = None
        if self.window_min is not None:
            keep = minimizer_mask(locs, self.window_min)
        if self.kind == "bits":
            row = (locs >> jnp.uint32(5)).astype(jnp.int32)
            wc = jnp.zeros_like(row)
            bit = locs & jnp.uint32(31)
            if keep is not None:
                row = jnp.where(keep[:, None, :], row, oob)
        elif self.kind == "cols":
            if aux is None:
                raise ValueError("kind='cols' plans need (B,) file columns")
            cols = aux.reshape(-1).astype(jnp.int32)    # (B,)
            row = locs.astype(jnp.int32)
            wc = jnp.broadcast_to((cols >> 5)[:, None, None], row.shape)
            bit = jnp.broadcast_to(
                (cols & 31).astype(jnp.uint32)[:, None, None], row.shape)
            if keep is not None:
                row = jnp.where(keep[:, None, :], row, oob)
        elif self.kind == "rows":
            if aux is None:
                raise ValueError("kind='rows' plans need (B, R) filter rows")
            frows = aux.astype(jnp.int32)               # (B, R)
            shape = frows.shape + locs.shape[1:]        # (B, R, η, n_k)
            row = jnp.broadcast_to(frows[:, :, None, None], shape)
            wc = jnp.broadcast_to(
                (locs >> jnp.uint32(5)).astype(jnp.int32)[:, None], shape)
            bit = jnp.broadcast_to((locs & jnp.uint32(31))[:, None], shape)
            if keep is not None:
                row = jnp.where(keep[:, None, None, :], row, oob)
        else:
            raise ValueError(f"unknown insert kind {self.kind!r}")
        return row.reshape(-1), wc.reshape(-1), bit.reshape(-1)

    def plan_runs(self, reads: jax.Array, aux: Optional[jax.Array] = None):
        """Host-side sorted/deduplicated run plan (ONE kernel launch)."""
        from repro.kernels.idl_insert import ops as ins_ops

        row, wc, bit = (np.asarray(t, dtype=np.int64)
                        for t in self.targets(reads, aux))
        flat = (row * self.row_words + wc) * 32 + bit
        flat[row >= self.n_rows] = -1                   # masked targets
        return ins_ops.plan_insert_runs(
            flat, block_bits=self.block_bits,
            inserts_per_run=self.inserts_per_run,
        )

    def run_dma_bytes(self, rplan) -> int:
        """Tile bytes the plan DMAs (read + write per touched block)."""
        return 0 if rplan is None else rplan.dma_bytes

    # -- execution ----------------------------------------------------------
    def execute(
        self,
        matrix: jax.Array,
        reads: jax.Array,
        aux: Optional[jax.Array] = None,
        *,
        backend: str = "jnp",
        interpret: Optional[bool] = None,
        use_ref: bool = False,
        mesh: Optional[Mesh] = None,
        donate: bool = True,
    ) -> jax.Array:
        """Scatter-OR the batch into ``matrix``; returns the updated matrix.

        ``matrix`` may be 1-D when ``W == 1`` (flat packed BF); the result
        always has the input's shape. The destination buffer is donated on
        the ``jnp`` and ``idl_insert`` backends — use linearly, or pass
        ``donate=False`` to scatter into a private copy and keep the input
        buffer alive (one extra device copy; same compiled executable).
        """
        if not donate:
            matrix = jnp.array(matrix, copy=True)
        if backend == "jnp":
            return _execute_jnp(matrix, reads, aux, plan=self)
        if backend == "idl_insert":
            return self._execute_idl_insert(matrix, reads, aux,
                                            interpret, use_ref)
        if backend == "sharded":
            if mesh is None:
                mesh = query.default_mesh()
            return _sharded_inserter(self, mesh)(matrix, reads, aux)
        raise ValueError(
            f"unknown ingest backend {backend!r} (want one of {BACKENDS})"
        )

    def _execute_idl_insert(self, matrix, reads, aux, interpret, use_ref):
        from repro.kernels.idl_insert import ops as ins_ops

        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        rplan = self.plan_runs(reads, aux)
        if rplan is not None:
            query.record_locality(
                scheme=self.scheme, op="insert",
                tile_bytes=self.run_dma_bytes(rplan),
                n_runs=rplan.n_runs, n_probes=int(rplan.n_locs),
                run_lengths=rplan.run_lengths)
        return ins_ops.insert_planned(
            matrix, rplan, interpret=interpret, use_ref=use_ref,
        )


# Bounded like query.PLAN_CACHE_SIZE (same argument: plans are frozen
# value objects, jitted executors key on plan hash/eq, so eviction never
# costs a recompile — asserted in tests/test_query_dedup.py).
PLAN_CACHE_SIZE = 512


@functools.lru_cache(maxsize=PLAN_CACHE_SIZE)
def plan_insert(
    cfg: idl_mod.IDLConfig,
    scheme: str,
    read_shape: tuple[int, int],
    matrix_shape: tuple[int, int],
    *,
    kind: str,
    lane32: bool = False,
    rows_per_block: Optional[int] = None,
    inserts_per_run: Optional[int] = None,
    window_min: Optional[int] = None,
) -> InsertPlan:
    """Build (or fetch) the cached plan for one insert geometry.

    ``rows_per_block`` defaults to the IDL locality window ``cfg.L``
    translated to matrix rows (``L/32`` packed words for ``"bits"``; for
    row/column targets, ``L`` rows clamped to a 2 MB bit image of the
    tile), as a power of two that divides ``n_rows``.
    ``inserts_per_run`` defaults to the TPU lane width (128); 32 on a CPU
    host, where narrow runs waste fewer pad lanes.
    """
    if kind not in KINDS:
        raise ValueError(f"unknown insert kind {kind!r} (want one of {KINDS})")
    n_rows, row_words = matrix_shape
    if inserts_per_run is None:
        inserts_per_run = 32 if jax.default_backend() == "cpu" else 128
    if rows_per_block is None:
        if kind == "bits":
            target = max(cfg.L // 32, 1)
        else:
            # 2^21 / (W*128) rows: a 2 MB f32 bit image of the tile, the
            # bound the probe kernel once had and the insert kernel keeps
            target = max(1, min(cfg.L, (1 << 21) // max(row_words * 128, 1)))
        rows_per_block = query._pow2_block(n_rows, target)
    if n_rows % rows_per_block:
        raise ValueError(
            f"rows_per_block={rows_per_block} must divide n_rows={n_rows}"
        )
    return InsertPlan(
        cfg=cfg, scheme=scheme,
        read_shape=tuple(read_shape), matrix_shape=tuple(matrix_shape),
        kind=kind, lane32=lane32,
        rows_per_block=rows_per_block, inserts_per_run=inserts_per_run,
        window_min=window_min,
    )


def plan_cache_info() -> "query.PlanCacheInfo":
    """Stats of the (bounded) insert-plan cache, incl. eviction count."""
    return query._with_evictions(plan_insert.cache_info())


def clear_plan_cache() -> None:
    plan_insert.cache_clear()


# ---------------------------------------------------------------------------
# Backend bodies.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("plan",))
def _execute_jnp(matrix, reads, aux, *, plan: InsertPlan):
    shape = matrix.shape
    row, wc, bit = plan.targets(reads, aux)
    if plan.kind == "bits":
        # W == 1: scatter into the flat word vector itself (masked rows
        # land out of range and are dropped)
        flat = (row.astype(jnp.uint32) << jnp.uint32(5)) | bit
        words = packed.scatter_or(jnp.reshape(matrix, (-1,)), flat)
        return words.reshape(shape)
    mat = jnp.reshape(matrix, plan.matrix_shape)
    return packed.scatter_or_matrix(mat, row, wc, bit).reshape(shape)


# Bounded; eviction HERE drops a compiled closure (cold re-entry
# recompiles) — 128 keeps realistic working sets hot (see query.py).
@functools.lru_cache(maxsize=128)
def _sharded_inserter(plan: InsertPlan, mesh: Mesh):
    """jit-compiled shard_map inserter for one (plan, mesh) pair.

    ``"bits"`` plans split the words (row) axis; ``"rows"``/``"cols"``
    plans split the W axis (RAMBO's m-words / the file-words of bit-sliced
    layouts — the serving sharding). Every shard recomputes the target
    stream, keeps only its own slice's targets, and scatters locally:
    scatter-OR commutes, so no collective is needed at all.
    """
    n_shards = int(np.prod(mesh.devices.shape))
    n_rows, w = plan.matrix_shape
    split_rows = plan.kind == "bits"
    per_shard = -(-(n_rows if split_rows else w) // n_shards)
    pad = per_shard * n_shards - (n_rows if split_rows else w)

    def body(mat, reads, aux):
        row, wc, bit = plan.targets(reads, aux)
        lo = jax.lax.axis_index(MESH_AXIS).astype(jnp.int32) * per_shard
        if split_rows:
            local = (row >= lo) & (row < lo + per_shard)
            row = jnp.where(local, row - lo, per_shard)     # oob -> dropped
        else:
            local = (wc >= lo) & (wc < lo + per_shard)
            wc = jnp.where(local, wc - lo, per_shard)
        return packed.scatter_or_matrix(mat, row, wc, bit)

    mat_spec = P(MESH_AXIS, None) if split_rows else P(None, MESH_AXIS)
    aux_spec = P() if plan.kind != "bits" else None

    def run(matrix, reads, aux):
        shape = matrix.shape
        mat = jnp.reshape(matrix, plan.matrix_shape)
        if pad:
            mat = jnp.pad(
                mat, ((0, pad), (0, 0)) if split_rows else ((0, 0), (0, pad)))
        if aux_spec is None:
            out = jax.shard_map(
                lambda m, r: body(m, r, None), mesh=mesh,
                in_specs=(mat_spec, P()), out_specs=mat_spec,
            )(mat, reads)
        else:
            out = jax.shard_map(
                body, mesh=mesh,
                in_specs=(mat_spec, P(), aux_spec), out_specs=mat_spec,
            )(mat, reads, aux)
        if pad:
            out = out[:n_rows] if split_rows else out[:, :w]
        return out.reshape(shape)

    return jax.jit(run, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Streaming archive builder.
# ---------------------------------------------------------------------------

def _engine_k(index) -> int:
    k = getattr(index, "k", None)
    if k is None:
        k = index.cfg.k
    return int(k)


def file_sequences(item, default_id: int):
    """Normalize an archive item to (file_id, [code arrays])."""
    from repro.data import genome as genome_mod

    if isinstance(item, genome_mod.GenomeFile):
        return item.file_id, [np.asarray(item.genome)]
    if isinstance(item, str):
        return default_id, [
            np.asarray(codes)
            for codes in genome_mod.read_fasta(item).values()
        ]
    fid, codes = item
    return int(fid), [np.asarray(codes)]


def build_archive(
    index,
    files: Iterable,
    *,
    read_len: int = 230,
    chunk_reads: int = 64,
    backend: str = "jnp",
    mesh: Optional[Mesh] = None,
    window_min: Optional[int] = None,
    pad_final: bool = True,
    **kw,
):
    """Stream a whole archive into any ``GeneIndex`` engine.

    ``files``: an iterable of ``data.genome.GenomeFile``, ``(file_id,
    codes)`` pairs, or FASTA paths (each path is one file; its records are
    kmerized separately, numbered by position). Every sequence is chopped
    into fixed-``read_len`` windows overlapping by ``k - 1`` bases — every
    kmer is covered, and the duplicate boundary kmers are free because
    scatter-OR is idempotent. Windows are batched ``chunk_reads`` at a
    time and fed to the engine's ``insert_batch`` with the chosen ingest
    backend, so the whole build is one Python loop of jit-compiled,
    donated inserts (with ``pad_final``, partial tail chunks are padded by
    repeating a read — idempotent again — so each window length compiles
    exactly once).

    ``window_min`` enables minimizer sub-sampling (insert only window-w
    minimizer kmers — a build-size/FPR trade, NOT bit-identical to a full
    build). Returns the updated engine (use linearly: buffers are donated).
    """
    from repro.data import genome as genome_mod

    k = _engine_k(index)
    pending: dict[int, tuple[list, list]] = {}

    def flush(length: int, force: bool):
        nonlocal index
        reads_l, fids_l = pending[length]
        while len(reads_l) >= chunk_reads or (force and reads_l):
            take = min(chunk_reads, len(reads_l))
            batch, fids = reads_l[:take], fids_l[:take]
            del reads_l[:take], fids_l[:take]
            if pad_final and take < chunk_reads:
                batch = batch + [batch[0]] * (chunk_reads - take)
                fids = fids + [fids[0]] * (chunk_reads - take)
            index = index.insert_batch(
                jnp.asarray(np.stack(batch)),
                np.asarray(fids, dtype=np.int32),
                backend=backend, mesh=mesh, window_min=window_min, **kw,
            )

    for pos, item in enumerate(files):
        fid, seqs = file_sequences(item, pos)
        for codes in seqs:
            windows = genome_mod.window_reads(codes, read_len, k)
            if windows.shape[0] == 0:
                continue
            length = windows.shape[1]
            reads_l, fids_l = pending.setdefault(length, ([], []))
            reads_l.extend(windows)
            fids_l.extend([fid] * windows.shape[0])
            flush(length, force=False)
    for length in sorted(pending):
        flush(length, force=True)
    return index


def build_sharded_archive(
    index,
    files: Iterable,
    *,
    n_shards: int,
    out_dir: Optional[str] = None,
    read_len: int = 230,
    chunk_reads: int = 64,
    backend: str = "jnp",
    window_min: Optional[int] = None,
    pad_final: bool = True,
    set_version: int = 0,
):
    """Partition an empty engine/state and stream the archive into every
    shard in parallel — one thread per shard over the same donated insert
    planner :func:`build_archive` uses (each shard compiles its plan
    once; under jax the scatters release the GIL, so shard builds overlap
    wherever the host has cores).

    Row-probe shards (bit-sliced / cobs) each ingest only their own file
    range — bit-sliced file ids are renumbered into the shard-local
    column space. Bit-probe shards (flat BF / rambo) each ingest EVERY
    read through a :class:`repro.index.shards.ShardBuilder`, which keeps
    only the targets in the shard's word range (scatter-OR commutes and
    is idempotent, so dropping foreign targets is exact). Joining the
    result is bit-identical to the unsharded ``build_archive`` — asserted
    in tests/test_shards.py.

    Returns ``(spec, [IndexState, ...])``; with ``out_dir`` also writes
    the shard-set snapshot (``shards.save_shard_set``) stamped
    ``set_version``.
    """
    from repro.index import shards as shards_mod
    from repro.index import state as state_mod

    spec, parts = shards_mod.partition_state(index, n_shards)
    items = []
    for pos, item in enumerate(files):
        fid, seqs = file_sequences(item, pos)
        items.extend((fid, codes) for codes in seqs)
    build_kw = dict(read_len=read_len, chunk_reads=chunk_reads,
                    window_min=window_min, pad_final=pad_final)
    results: list = [None] * n_shards
    errors: list = []

    def run(s: int) -> None:
        try:
            if spec.row_probe:
                owned = shards_mod.shard_files(spec, s)
                base = owned[0] if (
                    owned and spec.meta.engine == "bitsliced") else 0
                own = set(owned)
                mine = [(fid - base, codes)
                        for fid, codes in items if fid in own]
                built = build_archive(
                    state_mod.to_engine(parts[s]), mine,
                    backend=backend, **build_kw)
                results[s] = state_mod.from_engine(built)
            else:
                builder = shards_mod.ShardBuilder(spec, s, parts[s])
                built = build_archive(builder, items,
                                      backend=backend, **build_kw)
                results[s] = built.state
        except Exception as e:  # noqa: BLE001 - surfaced to the caller
            errors.append((s, e))

    threads = [threading.Thread(target=run, args=(s,),
                                name=f"idl-shard-build-{s}")
               for s in range(n_shards)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        s, e = min(errors)
        raise RuntimeError(f"shard {s} build failed: {e!r}") from e
    if out_dir is not None:
        shards_mod.save_shard_set(spec, results, out_dir,
                                  version=set_version)
    return spec, results
