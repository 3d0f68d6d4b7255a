"""The shared query-execution layer: one planned probe path for every engine.

The paper's whole argument is about the *query* side: IDL co-locates the
probes of successive kmers so membership tests hit one resident block
instead of scattering across the filter. Before this layer each engine
re-derived its own probe stream (``PackedBloomIndex`` reached the Pallas
planner, COBS / RAMBO / the bit-sliced serving index each had private
``jnp`` gather code). Now there is exactly one pipeline:

    kmer extraction -> hash-scheme codes -> row probes -> backend executor

unified by one observation: every engine's query is a **row gather over a
packed ``(n_rows, W)`` uint32 bit-matrix** followed by an AND over the η
hash repetitions —

======================  ==========================  =====================
Engine                  Probed matrix               Probe kind
======================  ==========================  =====================
``PackedBloomIndex``    ``(m/32, 1)`` word column   bit  (row = loc>>5)
``RamboIndex``          ``(m/32, R·B)`` transpose   bit  (row = loc>>5)
``CobsIndex`` group     ``(m_g, ⌈F_g/32⌉)``         row  (row = loc)
``BitSlicedIndex``      ``(m, ⌈F/32⌉)``             row  (row = loc)
======================  ==========================  =====================

A :class:`QueryPlan` holds everything static — config, scheme, read shape,
matrix geometry, the run-coalescing block height — and is built once per
``(cfg, scheme, read_shape, matrix_shape)`` through an LRU cache
(:func:`plan_query`). Executing a plan picks one of three backends:

* ``"jnp"``       — pure-XLA reference gather (always available);
* ``"idl_probe"`` — the run-length planner + the generalized Pallas
  ``probe_rows`` kernel: probes are run-length-encoded by matrix
  row-block, each run copies ONE ``(rows_per_block, W)`` tile, and the
  whole ``(B, η, n_kmers)`` batch executes as one kernel launch per 65536
  probes (one grid step per group of C probes). Hash,
  plan, kernel and the AND over η are one jitted program per plan, so
  nothing is pulled to the host between the reads and the kernel;
* ``"sharded"``   — ``shard_map`` over a 1-D device mesh. Bit probes split
  the words axis (each shard resolves its local probes and misses combine
  with a single ``lax.psum``); row probes split the file-words axis (the
  serving layout — gathers are device-local, outputs concatenate).

All backends are bit-identical; ``tests/test_index_parity.py`` holds the
parity matrix.

**Probe dedup** (``execute(..., dedup=True)``): membership of a kmer is a
pure function of ``(kmer, matrix)`` — the same kmer appearing twice in a
batch probes the same rows and ANDs to the same value, so factoring the
``(B, n_kmers)`` batch into its unique kmers, probing each once, and
inverse-permuting the per-kmer values back is an *exact* rewrite of the
naive path (scatter-OR/AND over duplicates is idempotent). The unique
kmers are probed in locality-sorted order — sorted by their repetition-0
hash location, so under IDL the dedup'd gather walks adjacent rows and is
also the DMA-minimal one. Unique counts are padded to the next power of
two, so the derived single-kmer plans (and their compiled executors) stay
bounded: one per ``(U_pad, k)`` shape, at most ``log2(B·n_kmers)`` of
them. ``tests/test_query_dedup.py`` holds the dedup == naive property
matrix across engines × schemes × backends.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import idl as idl_mod
from repro.index import packed
from repro.kernels import lanes
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

BACKENDS = ("jnp", "idl_probe", "sharded")
MESH_AXIS = "shards"


def record_locality(*, scheme: str, op: str, tile_bytes: int, n_runs: int,
                    n_probes: int, run_lengths) -> None:
    """Feed one executed probe/insert plan into the process registry —
    the paper's locality story as live counters: planned tile bytes (the
    quantity IDL minimizes), run/probe totals, and the per-run length
    histogram. Called once per executed batch on the planned backends
    (``idl_probe`` / ``idl_insert``), so an IDL stream and an RH stream
    over the same reads diverge visibly in
    ``locality.planned_tile_bytes``.

    The scalar counters are exact on EVERY batch (tile-byte ratios and
    run/probe totals are the paper's claim — they never sample); the
    run-length histogram, which is the only per-element cost here, is fed
    from every :data:`_HIST_SAMPLE`-th batch per (scheme, op) — a batch-
    granular sample that keeps the distribution honest (each sampled
    batch lands whole) at a quarter of the observe cost."""
    reg = obs_metrics.DEFAULT
    if not reg.enabled:
        return
    handles = _LOCALITY_HANDLES.get((scheme, op))
    if handles is None:
        # bind once per (scheme, op); the per-batch path below is then
        # pre-bound handle hits only
        labels = {"tier": "planner", "scheme": scheme, "op": op}
        handles = _LOCALITY_HANDLES[(scheme, op)] = (
            reg.counter("locality.planned_tile_bytes", **labels),
            reg.counter("locality.probe_runs", **labels),
            reg.counter("locality.probes", **labels),
            reg.counter("locality.batches", **labels),
            reg.histogram("locality.run_length", **labels),
        )
    c_bytes, c_runs, c_probes, c_batches, h_runs = handles
    c_bytes.inc(tile_bytes)
    c_runs.inc(n_runs)
    c_probes.inc(n_probes)
    c_batches.inc()
    if int(c_batches.value) % _HIST_SAMPLE == 1 or _HIST_SAMPLE == 1:
        h_runs.observe_array(run_lengths)


_LOCALITY_HANDLES: dict = {}


class _PendingLocality(NamedTuple):
    scheme: str
    block_bytes: int
    n_runs: jax.Array        # device scalar
    run_hist: jax.Array      # device (C + 1,) runs of each length 0..C


# Locality values of planned query batches, still on the device: the plan
# is made on the device, so its run count is known to the host only once
# the batch's program has run. Appended at dispatch, taken off by
# :func:`drain_locality`.
_PENDING: list = []
_PENDING_LOCK = threading.Lock()


def drain_locality() -> int:
    """Record the locality counters of every planned query batch whose
    program has finished, with one ``jax.device_get``; batches still
    running stay queued, so this never waits for the device. Returns the
    batches recorded.

    The serving layer calls it once a batch's answer is on the host (the
    answer comes from the same program as the batch's plan, so the batch
    is recorded before its answers are handed out); the planned backend
    calls it before each dispatch, which bounds the queue for callers
    that never drain."""
    with _PENDING_LOCK:
        ready = [e.n_runs.is_ready() and e.run_hist.is_ready()
                 for e in _PENDING]
        done = [e for e, r in zip(_PENDING, ready) if r]
        if not done:
            return 0
        _PENDING[:] = [e for e, r in zip(_PENDING, ready) if not r]
        got = jax.device_get([(e.n_runs, e.run_hist) for e in done])
        for e, (n_runs, hist) in zip(done, got):
            n_runs = int(n_runs)
            lengths = np.arange(hist.shape[0])
            record_locality(
                scheme=e.scheme, op="query",
                tile_bytes=n_runs * e.block_bytes, n_runs=n_runs,
                n_probes=int(lengths @ hist),
                run_lengths=np.repeat(lengths[1:], hist[1:]))
        return len(done)


# Feed the run-length histogram from every Nth batch (1 = every batch).
# The first batch after a reset always lands (count % N == 1), so short
# tests and cold streams still populate the histogram.
_HIST_SAMPLE = 4

_FULL = jnp.uint32(0xFFFFFFFF)


# ---------------------------------------------------------------------------
# Location stream (shared by every backend).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "scheme", "lane32"))
def batch_locations(
    reads: jax.Array, *, cfg: idl_mod.IDLConfig, scheme: str, lane32: bool
) -> jax.Array:
    """(B, η, n_kmers) uint32 locations — jitted view of the one rolling
    location body the insert path (:mod:`repro.index.packed`) also uses."""
    return packed.batch_locations(cfg, reads, scheme, lane32=lane32)


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def read_kmers(reads: np.ndarray, k: int) -> np.ndarray:
    """(B, read_len) uint8 reads -> (B·n_kmers, k) stride-1 kmer rows.

    A zero-copy sliding-window view reshaped (one small copy) — the host
    side of every dedup/cache path keys kmers by these byte rows.
    """
    arr = np.asarray(reads, dtype=np.uint8)
    if arr.ndim == 1:
        arr = arr[None]
    kms = np.lib.stride_tricks.sliding_window_view(arr, k, axis=1)
    return np.ascontiguousarray(kms.reshape(-1, k))


def factor_unique_kmers(
    reads, k: int
) -> tuple[np.ndarray, np.ndarray, tuple[int, int]]:
    """Factor a read batch into its distinct kmers.

    Returns ``(uniq, inverse, (b, n_kmers))``: ``uniq`` is ``(U, k)``
    uint8 (U = the distinct kmer count, lexicographic order) and
    ``inverse`` maps each of the ``b·n_kmers`` batch kmers to its row in
    ``uniq``. Membership of a kmer is a pure function of its bases, so
    probing ``uniq`` and gathering back through ``inverse`` is exact.
    Probe-side consumers pad ``uniq`` to a power of two themselves (so
    derived plans compile O(log) times, not per batch).
    """
    arr = np.asarray(reads, dtype=np.uint8)
    if arr.ndim == 1:
        arr = arr[None]
    b, read_len = arr.shape
    n_k = read_len - k + 1
    flat = read_kmers(arr, k)
    # unique rows via a void byte view: ONE memcmp sort, no per-column pass
    view = flat.view(np.dtype((np.void, k))).ravel()
    _, first, inverse = np.unique(view, return_index=True,
                                  return_inverse=True)
    return flat[first], inverse.reshape(-1), (b, n_k)


# ---------------------------------------------------------------------------
# The plan.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QueryPlan:
    """Static query recipe for one (cfg, scheme, read_shape, matrix) tuple.

    ``bit_probe=True``: locations are flat bit offsets — the probed row is
    ``loc >> 5`` and the answer is bit ``loc & 31`` of every word in that
    row. ``bit_probe=False``: locations are row indices and the answer is
    the whole W-word row (bit-sliced layouts).
    """

    cfg: idl_mod.IDLConfig
    scheme: str
    read_shape: tuple[int, int]       # (B, read_len)
    matrix_shape: tuple[int, int]     # (n_rows, W)
    bit_probe: bool
    lane32: bool
    rows_per_block: int               # run-coalescing DMA tile height
    probes_per_run: int

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
    def block_bytes(self) -> int:
        """HBM bytes one run's DMA moves — the quantity IDL minimizes."""
        return self.rows_per_block * self.row_words * 4

    # -- probe streams ------------------------------------------------------
    def locations(self, reads: jax.Array) -> jax.Array:
        """(B, η, n_kmers) uint32 hash locations."""
        return batch_locations(
            reads, cfg=self.cfg, scheme=self.scheme, lane32=self.lane32
        )

    def row_indices(self, locs: jax.Array) -> jax.Array:
        """Matrix row probed by each location."""
        return (locs >> jnp.uint32(5)) if self.bit_probe else locs

    def plan_runs(self, reads: jax.Array):
        """The batch's run plan, made on the host by the numpy planner: the
        oracle of the plan the ``idl_probe`` backend makes on the device
        (tests and offline locality counts; nothing served calls it).

        Returns ``(ProbePlan, locs)`` where locs is the (B, η, n_kmers)
        numpy location array the plan was built from.
        """
        from repro.kernels.idl_probe import ops as probe_ops

        locs = np.asarray(self.locations(reads))
        rows = (locs >> 5) if self.bit_probe else locs
        b, eta, n_k = locs.shape
        rplan = probe_ops.plan_probe_runs(
            rows.reshape(b * eta, n_k),
            block_bits=self.rows_per_block,
            probes_per_run=self.probes_per_run,
        )
        return rplan, locs

    def run_dma_bytes(self, rplan) -> int:
        """Total tile bytes the plan DMAs (n_runs × block_bytes)."""
        return rplan.n_runs * self.block_bytes

    # -- execution ----------------------------------------------------------
    def execute(
        self,
        matrix: jax.Array,
        reads: jax.Array,
        *,
        backend: str = "jnp",
        dedup: bool = False,
        interpret: Optional[bool] = None,
        use_ref: bool = False,
        mesh: Optional[Mesh] = None,
    ) -> jax.Array:
        """(B, n_kmers, W) uint32: AND over η of per-probe row values.

        ``bit_probe`` plans extract the probed bit first, so values are
        {0, 1} per word slot; row plans return full AND-ed word masks.
        ``matrix`` may be 1-D when ``W == 1`` (flat packed BF).

        ``dedup=True`` factors the batch into unique kmers, probes each
        once in locality-sorted order through the same backend, and
        inverse-permutes the per-kmer values back — bit-identical to the
        naive path (see module docstring) but with a probe stream sized
        by the batch's *distinct* kmers, the win for overlapping reads.
        """
        if backend == "kernel":   # pre-PR2 spelling of the planned backend
            backend = "idl_probe"
        if dedup:
            return self._execute_dedup(
                matrix, reads, backend=backend, interpret=interpret,
                use_ref=use_ref, mesh=mesh)
        if backend == "jnp":
            return _execute_jnp(matrix, reads, plan=self)
        if backend == "idl_probe":
            return self._execute_idl_probe(matrix, reads, interpret, use_ref)
        if backend == "sharded":
            return self._execute_sharded(matrix, reads, mesh)
        raise ValueError(
            f"unknown query backend {backend!r} (want one of {BACKENDS}; "
            f"'kernel' is accepted as an alias for 'idl_probe')"
        )

    def _execute_idl_probe(self, matrix, reads, interpret, use_ref):
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        drain_locality()          # earlier batches', never this one's
        # the plan is made on the device: the host's part is the dispatch
        with obs_trace.stage("plan"):
            vals, n_runs, run_hist = _execute_planned(
                matrix, reads, plan=self, interpret=interpret,
                use_ref=use_ref)
            if obs_metrics.DEFAULT.enabled:
                with _PENDING_LOCK:
                    _PENDING.append(_PendingLocality(
                        self.scheme, self.block_bytes, n_runs, run_hist))
        return vals

    def _execute_sharded(self, matrix, reads, mesh):
        if mesh is None:
            mesh = mesh_of(matrix)
        with obs_trace.stage("shard.gather"):
            return _sharded_executor(self, mesh)(matrix, reads)

    def _execute_dedup(self, matrix, reads, *, backend, interpret,
                       use_ref, mesh):
        """Unique-kmer probe path (host-factored, backend-shared).

        Each unique kmer is probed as a standalone length-k read through a
        derived ``(U_pad, k)`` plan — the rolling location of a kmer is a
        pure function of its own bases (per-kmer sliding-window MinHash),
        so the standalone probe is bit-identical to the in-read one.
        """
        k = self.cfg.k
        uniq, inverse, (b, n_k) = factor_unique_kmers(reads, k)
        u_pad = _next_pow2(len(uniq))
        if u_pad > len(uniq):   # pad rows repeat the last unique kmer, so
            uniq = np.concatenate(  # plans compile O(log) times, not per batch
                [uniq, np.broadcast_to(uniq[-1], (u_pad - len(uniq), k))])
        kplan = plan_query(
            self.cfg, self.scheme, (len(uniq), k), self.matrix_shape,
            bit_probe=self.bit_probe, lane32=self.lane32,
            rows_per_block=self.rows_per_block,
            probes_per_run=self.probes_per_run)
        ukmers = jnp.asarray(uniq)
        # locality sort: order unique kmers by their repetition-0 hash
        # location, so under IDL the dedup'd probe stream walks adjacent
        # matrix rows (the DMA-minimal order). One extra hash pass over
        # the unique set — cheap next to the gather it orders.
        locs0 = np.asarray(kplan.locations(ukmers))[:, 0, 0]
        order = np.argsort(locs0, kind="stable")
        rank = np.empty_like(order)
        rank[order] = np.arange(len(order))
        vals = kplan.execute(
            matrix, ukmers[jnp.asarray(order)], backend=backend,
            interpret=interpret, use_ref=use_ref, mesh=mesh)  # (U_pad, 1, W)
        per = jnp.take(vals[:, 0], jnp.asarray(rank[inverse]), axis=0)
        return per.reshape(b, n_k, vals.shape[-1])


def _pow2_block(n_rows: int, target: int) -> int:
    """Largest power of two <= target that divides n_rows (floor 1)."""
    blk = 1 << max(int(target).bit_length() - 1, 0)
    while blk > 1 and n_rows % blk:
        blk //= 2
    return max(blk, 1)


# Bounded: a long-lived server planning many geometries (every (bucket,
# unique-count) pair of the dedup path derives a plan) must not grow this
# without bound. Eviction is cheap by design — plans are frozen VALUE
# objects, and every jitted executor keys on the plan's hash/eq, so a
# rebuilt equal plan hits the same compiled executable (compile-once under
# eviction pressure is asserted in tests/test_query_dedup.py).
PLAN_CACHE_SIZE = 512


@functools.lru_cache(maxsize=PLAN_CACHE_SIZE)
def plan_query(
    cfg: idl_mod.IDLConfig,
    scheme: str,
    read_shape: tuple[int, int],
    matrix_shape: tuple[int, int],
    *,
    bit_probe: bool,
    lane32: bool = False,
    rows_per_block: Optional[int] = None,
    probes_per_run: Optional[int] = None,
) -> QueryPlan:
    """Build (or fetch) the cached plan for one query geometry.

    ``rows_per_block`` defaults to the smallest tile the probe kernel can
    copy: the kernel sees the matrix transposed, ``(W, n_rows)``, tiled
    ``(8, 128)`` in HBM, so a block is one lane tile of 128 rows (the
    largest power of two up to that which divides ``n_rows``), whatever
    the probe kind. ``probes_per_run`` defaults to the TPU lane width
    (128); on a CPU host 32 — narrower runs waste fewer pad lanes where
    there is no vector unit to fill.
    """
    n_rows = matrix_shape[0]
    if probes_per_run is None:
        probes_per_run = 32 if jax.default_backend() == "cpu" else 128
    if rows_per_block is None:
        rows_per_block = _pow2_block(n_rows, lanes.LANES)
    if n_rows % rows_per_block:
        raise ValueError(
            f"rows_per_block={rows_per_block} must divide n_rows={n_rows}"
        )
    return QueryPlan(
        cfg=cfg, scheme=scheme,
        read_shape=tuple(read_shape), matrix_shape=tuple(matrix_shape),
        bit_probe=bit_probe, lane32=lane32,
        rows_per_block=rows_per_block, probes_per_run=probes_per_run,
    )


class PlanCacheInfo(NamedTuple):
    """``lru_cache`` stats plus the eviction count a bounded cache needs.

    ``evictions`` is exact: every miss inserts one entry and ``currsize``
    counts the retained ones, so ``misses - currsize`` is how many were
    pushed out (both reset together on ``clear_plan_cache``).
    """

    hits: int
    misses: int
    maxsize: Optional[int]
    currsize: int
    evictions: int


def _with_evictions(info) -> PlanCacheInfo:
    return PlanCacheInfo(
        hits=info.hits, misses=info.misses, maxsize=info.maxsize,
        currsize=info.currsize, evictions=info.misses - info.currsize)


def plan_cache_info() -> PlanCacheInfo:
    """Stats of the (bounded) plan cache — hits prove plans are built
    once, ``evictions`` proves the bound is real under pressure."""
    return _with_evictions(plan_query.cache_info())


def clear_plan_cache() -> None:
    plan_query.cache_clear()


# ---------------------------------------------------------------------------
# Backend bodies.
# ---------------------------------------------------------------------------

def _finish_probe(rows: jax.Array, locs: jax.Array, *, bit_probe: bool):
    """(B, η, n_k, W) gathered rows -> (B, n_k, W) AND-over-η values."""
    if bit_probe:
        bit = (locs & jnp.uint32(31))[..., None]
        vals = (rows >> bit) & jnp.uint32(1)
    else:
        vals = rows
    return jax.lax.reduce(vals, _FULL, jax.lax.bitwise_and, dimensions=(1,))


@functools.partial(jax.jit, static_argnames=("plan", "interpret",
                                             "use_ref"))
def _execute_planned(matrix: jax.Array, reads: jax.Array, *,
                     plan: QueryPlan, interpret: bool, use_ref: bool):
    """The ``idl_probe`` step: hash, plan the runs on the device, run the
    kernel (or its oracle) and AND over η, in one program. Returns
    ``(values, n_runs, run_hist)``: the last two feed the locality
    counters."""
    from repro.kernels.idl_probe import ops as probe_ops

    locs = plan.locations(reads)
    rows = plan.row_indices(locs).astype(jnp.int32).reshape(-1)
    runs = probe_ops.device_plan_runs(
        rows, rows_per_block=plan.rows_per_block,
        probes_per_run=plan.probes_per_run)
    got = probe_ops._planned_gather(
        matrix, runs.rows, runs.starts,
        rows_per_block=plan.rows_per_block, n_probes=rows.shape[0],
        row_words=plan.row_words, interpret=interpret, use_ref=use_ref)
    got = got.reshape(locs.shape + (plan.row_words,))
    return (_finish_probe(got, locs, bit_probe=plan.bit_probe),
            runs.n_runs, runs.run_hist)


@functools.partial(jax.jit, static_argnames=("plan",))
def _execute_jnp(matrix: jax.Array, reads: jax.Array, *, plan: QueryPlan):
    matrix = jnp.reshape(matrix, plan.matrix_shape)
    locs = plan.locations(reads)
    rows = matrix[plan.row_indices(locs).astype(jnp.int32)]
    return _finish_probe(rows, locs, bit_probe=plan.bit_probe)


@functools.lru_cache(maxsize=None)
def default_mesh() -> Mesh:
    """1-D mesh over every visible device (the scale-out words/files axis)."""
    return Mesh(np.asarray(jax.devices()), (MESH_AXIS,))


def mesh_of(matrix) -> Mesh:
    """The mesh the ``sharded`` backend serves ``matrix`` on: the one that
    already holds it, when it is sharded by name over a 1-D
    ``MESH_AXIS`` mesh (a state split over some of the host's devices,
    as ``launch.serve.build_sharded_index`` places it), so that no
    dispatch moves it; otherwise :func:`default_mesh`."""
    sharding = getattr(matrix, "sharding", None)
    if isinstance(sharding, NamedSharding) and isinstance(
            sharding.mesh, Mesh) and sharding.mesh.axis_names == (MESH_AXIS,):
        return sharding.mesh
    return default_mesh()


# Bounded like the plan cache — but note the asymmetry: evicting an
# EXECUTOR drops its compiled closure, so a cold re-entry recompiles
# (jit caches key on the closure object, not the plan value). 128 keeps
# every realistic working set hot; the bound only guards runaway variety.
@functools.lru_cache(maxsize=128)
def _sharded_executor(plan: QueryPlan, mesh: Mesh):
    """jit-compiled shard_map executor for one (plan, mesh) pair."""
    n_shards = int(np.prod(mesh.devices.shape))
    n_rows, w = plan.matrix_shape

    if plan.bit_probe:
        # Split the words (row) axis: every probe is local to exactly one
        # shard. Each shard reduces its local probes to per-(kmer, slot)
        # miss counts over η; ONE psum combines shards; a hit is zero
        # misses anywhere.
        rows_per_shard = -(-n_rows // n_shards)

        def body(mat, reads):
            locs = plan.locations(reads)
            rows = plan.row_indices(locs).astype(jnp.int32)
            lo = jax.lax.axis_index(MESH_AXIS).astype(jnp.int32) * rows_per_shard
            local = (rows >= lo) & (rows < lo + rows_per_shard)
            got = mat[jnp.where(local, rows - lo, 0)]       # (B, η, n_k, W)
            bit = (got >> (locs & jnp.uint32(31))[..., None]) & jnp.uint32(1)
            miss = jnp.where(local[..., None], 1 - bit.astype(jnp.int32), 0)
            miss = jnp.sum(miss, axis=1)                    # (B, n_k, W)
            return jax.lax.psum(miss, MESH_AXIS)

        pad = rows_per_shard * n_shards - n_rows

        def run(matrix, reads):
            matrix = jnp.reshape(matrix, plan.matrix_shape)
            if pad:
                matrix = jnp.pad(matrix, ((0, pad), (0, 0)))
            miss = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(MESH_AXIS, None), P()), out_specs=P(),
            )(matrix, reads)
            return (miss == 0).astype(jnp.uint32)

        return jax.jit(run)

    # Row probe: split the file-words axis (the serving layout) — every
    # shard holds all rows for its file slice, gathers are device-local and
    # the only collective is the output concatenation.
    words_per_shard = -(-w // n_shards)

    def body(mat, reads):
        locs = plan.locations(reads)
        rows = mat[locs.astype(jnp.int32)]                  # (B, η, n_k, W/s)
        return jax.lax.reduce(rows, _FULL, jax.lax.bitwise_and, dimensions=(1,))

    pad = words_per_shard * n_shards - w

    def run(matrix, reads):
        matrix = jnp.reshape(matrix, plan.matrix_shape)
        if pad:
            matrix = jnp.pad(matrix, ((0, 0), (0, pad)))
        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, MESH_AXIS), P()),
            out_specs=P(None, None, MESH_AXIS),
        )(matrix, reads)
        return out[..., :w]

    return jax.jit(run)


# ---------------------------------------------------------------------------
# Shared coverage reductions (MSMT postludes).
# ---------------------------------------------------------------------------

def coverage_need(theta: float, n_kmers: int) -> int:
    """Integer hit threshold for kmer-coverage >= theta (exact at 1.0).

    The ONE definition of the theta rule — engines, ``serve_step`` and the
    v2 serving layer all resolve their thresholds here (host-side, exact
    float64; an in-graph f32 ``theta * n`` can flip boundary thetas).
    """
    return int(np.ceil(theta * n_kmers - 1e-9))


def _need_threshold(theta, n_kmers: int, need, lead_ndim: int):
    """Resolve ``need`` to something comparable against (B, ...) hit counts.

    ``need=None``: the scalar host-side :func:`coverage_need` of the full
    kmer axis. Otherwise a (B,) int array of per-row thresholds (the padded
    serving path: each row's threshold comes from its TRUE kmer count),
    reshaped to broadcast over ``lead_ndim`` trailing hit dimensions.
    """
    if need is None:
        return coverage_need(theta, n_kmers)
    need = jnp.asarray(need, dtype=jnp.int32)
    return need.reshape(need.shape + (1,) * lead_ndim)


def member_coverage(member: jax.Array, theta: float = 1.0, *,
                    valid: Optional[jax.Array] = None,
                    need=None) -> jax.Array:
    """(B, n_kmers[, ...]) bool kmer hits -> (B[, ...]) bool coverage >= θ.

    ``valid``: optional (B, n_kmers) bool marking REAL kmers — padding
    slots of a shape-bucketed batch are excluded from the hit count.
    ``need``: optional (B,) int32 per-row hit thresholds overriding theta
    (each padded row keeps the threshold of its true, unpadded length).
    """
    hits = member.astype(jnp.int32)
    if valid is not None:
        v = valid.astype(jnp.int32)
        hits = hits * v.reshape(v.shape + (1,) * (member.ndim - 2))
    hits = jnp.sum(hits, axis=1)
    return hits >= _need_threshold(theta, member.shape[1], need, hits.ndim - 1)


def file_match_mask(per_kmer: jax.Array, theta: float = 1.0, *,
                    valid: Optional[jax.Array] = None,
                    need=None) -> jax.Array:
    """(B, n_kmers, W) uint32 kmer file-masks -> (B, W) uint32 match mask.

    theta=1: pure AND over kmers. theta<1 (or per-row ``need``): per-file
    popcount against the exact integer threshold (a float mean of n ones
    != 1.0 in f32 for many n, which would flip boundary thetas).

    ``valid`` (B, n_kmers) bool marks real kmers of a shape-bucketed padded
    batch: pad kmers are neutralized (all-ones under AND, zero hits under
    popcount). ``need`` (B,) int32 gives per-row thresholds for rows whose
    true kmer counts differ (see :func:`coverage_need`).
    """
    if theta >= 1.0 and need is None:
        if valid is not None:
            per_kmer = jnp.where(valid[..., None], per_kmer, _FULL)
        return jax.lax.reduce(per_kmer, _FULL, jax.lax.bitwise_and,
                              dimensions=(1,))
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (per_kmer[..., None] >> shifts) & jnp.uint32(1)
    if valid is not None:
        bits = bits * valid[..., None, None].astype(jnp.uint32)
    hits = jnp.sum(bits.astype(jnp.int32), axis=1)          # (B, W, 32)
    thresh = _need_threshold(theta, per_kmer.shape[1], need, hits.ndim - 1)
    match = (hits >= thresh).astype(jnp.uint32)
    return jnp.sum(match << shifts, axis=-1, dtype=jnp.uint32)
