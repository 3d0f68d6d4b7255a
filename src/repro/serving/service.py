"""Gene-search serving v2: typed requests, shape-bucketed dynamic batching.

The v1 surface (:mod:`repro.serving.genesearch`) is a stateless function
over one raw matrix with one fixed read length. Real query streams are
ragged — reads of every length, arriving one at a time — and a compiled
serving path must not recompile per length. This layer closes that gap:

* **Typed boundary** — :class:`SearchRequest` in (one read of any length
  >= k), :class:`SearchResult` out (per-file verdicts + decoded ids +
  which bucket served it). The index itself is an immutable
  :class:`~repro.index.state.IndexState` pytree, so ANY engine (flat BF,
  COBS, RAMBO, bit-sliced) serves through the same front-end and hot
  snapshot swap is one attribute assignment.

* **Shape-bucketed dynamic batching** — a request with ``n`` kmers is
  padded to the next power-of-two kmer bucket (floor
  ``ServiceConfig.min_bucket_kmers``) and batched with its bucket peers
  into a fixed ``(max_batch, bucket + k - 1)`` shape, so each
  ``(bucket, backend)`` pair compiles **exactly once** no matter how many
  distinct read lengths arrive (asserted in ``tests/test_service.py``).
  Padding is proven not to change answers: pad kmers are masked out of
  the coverage reduction and each row keeps the integer threshold of its
  TRUE kmer count (``query.coverage_need`` — the single theta rule), so
  results are bit-identical to the engine's own unpadded ``msmt``.

* **Admission queue + stats** — ``submit`` enqueues; a bucket flushes
  when ``max_batch`` requests are waiting (or on ``flush()``). Every
  executed batch records occupancy, padding waste and wall time
  (:class:`BatchStats`) — the observability the autoscaling story needs.

* **Snapshot-backed startup** — :meth:`GeneSearchService.from_snapshot`
  boots straight from a :mod:`repro.index.store` directory.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import time
from typing import Deque, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.index import packed, query, store
from repro.index import state as state_mod
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving import kmer_cache as kmer_cache_mod

BACKENDS = ("jnp", "idl_probe", "sharded")

# distinguishes each service instance's counter series in the process
# registry (a router's replicas must not share one series, or the
# router-level sum would double-count)
_SERVICE_IDS = itertools.count()


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << max(int(n) - 1, 0).bit_length()


def bucket_for(n_kmers: int, min_bucket_kmers: int = 32) -> int:
    """The pow2 kmer bucket a request with ``n_kmers`` kmers lands in.

    Module-level so a layer that answers without a service of its own —
    the scatter router's merge — buckets with the exact geometry the
    shard services compile for.
    """
    return max(next_pow2(n_kmers), min_bucket_kmers)


# ---------------------------------------------------------------------------
# Typed request/response boundary.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SearchRequest:
    """One query read (uint8 base codes, any length >= k)."""

    read: np.ndarray
    request_id: Optional[int] = None   # assigned by the service if None


@dataclasses.dataclass(frozen=True)
class SearchResult:
    """Engine verdicts for one request.

    ``matches``: the engine's ``msmt`` row — a scalar bool for single-set
    engines (flat BF), a (n_files,) bool vector otherwise. ``file_ids``
    decodes it: indices of matching files ((0,) for a flat-BF hit).
    """

    request_id: int
    matches: np.ndarray
    file_ids: Tuple[int, ...]
    n_kmers: int
    bucket: int
    version: int = 0     # state version that served it (hot-swap audit trail)
    delta_seq: int = 0   # live-index write watermark that served it (0 =
    #                      static index / empty delta) — with `version` this
    #                      makes staleness observable per result
    missing_files: Tuple[int, ...] = ()  # file ids whose row-probe shard was
    #                      down when a scatter-gather answer was assembled —
    #                      those entries of `matches` are vacuously False
    #                      (see repro.serving.scatter); always () elsewhere


def normalize_request(request: Union[SearchRequest, np.ndarray], k: int
                      ) -> Tuple[SearchRequest, int]:
    """Shared admission validation: ``(request, n_kmers)`` or raise.

    The ONE place a read becomes a typed request — used by the service
    and the scatter router alike, so a malformed read is rejected with the
    same message at either boundary.
    """
    if not isinstance(request, SearchRequest):
        request = SearchRequest(read=np.asarray(request))
    read = np.asarray(request.read, dtype=np.uint8)
    if read.ndim != 1:
        # a flattened (B, L) batch would silently fuse reads across
        # their boundaries — one request is ONE read (batch via search)
        raise ValueError(
            f"submit takes one 1-D read, got shape {read.shape}; "
            f"submit each read separately (or use search())")
    n_kmers = read.shape[0] - k + 1
    if n_kmers < 1:
        raise ValueError(
            f"read of length {read.shape[0]} has no {k}-mers")
    return SearchRequest(read=read, request_id=request.request_id), n_kmers


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Serving knobs (static for the life of the service)."""

    theta: float = 1.0            # kmer-coverage threshold for a file match
    backend: str = "jnp"          # "jnp" | "idl_probe" | "sharded"
    max_batch: int = 8            # rows per bucket step (fixed batch shape)
    min_bucket_kmers: int = 32    # floor of the pow2 kmer buckets
    auto_flush: bool = True       # flush a bucket once max_batch are waiting
    stats_window: int = 4096      # batches of telemetry kept (bounded)
    # cross-batch membership cache (None = off): per-kmer probe results are
    # memoized under the served state's version — exact by construction
    # (see repro.serving.kmer_cache); the win for overlapping read streams
    kmer_cache: Optional[kmer_cache_mod.KmerCacheConfig] = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown serving backend {self.backend!r} "
                f"(want one of {BACKENDS})")
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")


@dataclasses.dataclass(frozen=True)
class BatchStats:
    """Accounting for one executed (bucket, batch) step."""

    bucket: int          # kmer bucket (padded kmer count)
    n_requests: int      # real requests served
    batch_rows: int      # fixed batch shape rows (= max_batch)
    pad_rows: int        # batch_rows - n_requests
    pad_kmers: int       # wasted kmer slots incl. pad rows
    wall_ms: float


def emit_request_spans(entries, *, bucket: int, t0: float, t_asm: float,
                       t_exec: float, t_done: float, replica: int = 0,
                       version: int = 0, status: str = "ok") -> None:
    """Emit the per-request span chain for one finalized batch.

    ``entries`` is ``[(trace_ctx, t_enq, request_id), ...]`` where
    ``trace_ctx`` is ``(trace_id, parent_span_id_or_None)`` minted at
    admission (by a router, when one dispatched the request). Each
    request gets a root ``request`` span with
    ``queue_wait → assemble → execute → finalize`` children; the batch
    stages share their (batch-level) boundaries, the queue wait is the
    request's own. The whole batch is ONE
    :meth:`~repro.obs.trace.Tracer.emit_request_chains` call (batch-
    invariant work hoisted out of the per-request loop), entirely off the
    submit hot path — the pipeline only stamps monotonic times it mostly
    takes anyway."""
    trc = obs_trace.DEFAULT
    if not trc.enabled:
        return
    stages = (("assemble", t0, t_asm), ("execute", t_asm, t_exec),
              ("finalize", t_exec, t_done))
    trc.emit_request_chains(
        [(ctx[0], ctx[1], t_enq, rid)
         for ctx, t_enq, rid in entries if ctx is not None],
        t0, stages, t_done, status=status,
        shared_attrs={"bucket": bucket, "replica": replica,
                      "version": version})


# ---------------------------------------------------------------------------
# The per-(engine-kind) MSMT postlude — ONE threshold path (query.py).
# ---------------------------------------------------------------------------

def _msmt_reduce(kind: str, n_files: Optional[int], theta: float,
                 per, valid, need):
    """Per-kmer engine output -> per-request verdicts, padding-aware.

    All threshold math routes through ``query.file_match_mask`` /
    ``query.member_coverage`` with per-row ``need`` thresholds — the same
    single ``coverage_need`` rule every engine and ``serve_step`` use.
    """
    if kind == "bitsliced":
        if theta >= 1.0:
            # at theta=1 a row matches iff ALL its valid kmers hit, which
            # is exactly the masked AND fast path — skip the 32x popcount
            # bit expansion (need[i] == n_valid[i] by construction, so the
            # answers are identical)
            mask = query.file_match_mask(per, theta, valid=valid)
        else:
            mask = query.file_match_mask(per, theta, valid=valid, need=need)
        return packed.unpack_file_bits(mask, n_files)
    return query.member_coverage(per, theta, valid=valid, need=need)


# ---------------------------------------------------------------------------
# The service.
# ---------------------------------------------------------------------------

class GeneSearchService:
    """Dynamic-batching front-end over any :class:`IndexState` / engine."""

    def __init__(self, index, config: Optional[ServiceConfig] = None,
                 *, version: int = 0):
        self.config = config or ServiceConfig()
        self._state = state_mod.from_engine(index)
        self._k = state_mod.kmer_size(self._state.meta)
        self._version = int(version)
        self._next_id = 0
        self._pending: Dict[int, List[Tuple[SearchRequest, int]]] = {}
        self._results: Dict[int, SearchResult] = {}
        self._inflight: set = set()
        self._runners: Dict[int, Tuple] = {}
        self.kmer_cache: Optional[kmer_cache_mod.KmerCache] = (
            kmer_cache_mod.KmerCache(self.config.kmer_cache.capacity)
            if self.config.kmer_cache is not None else None)
        if self.kmer_cache is not None and self._k > 32:
            raise ValueError(
                f"kmer_cache packs kmers into uint64 keys, so k <= 32 "
                f"(index has k={self._k})")
        # bounded: a long-running service must not leak telemetry. The
        # deque keeps the last-N per-batch records (request_latencies_ms
        # needs individual batches); the aggregate views below read the
        # registry counters fed by _record_batch — the single feed point
        # for the sync flush AND the async scheduler's completer.
        self.batch_stats: Deque[BatchStats] = collections.deque(
            maxlen=self.config.stats_window)
        meta = self._state.meta
        labels = {"tier": "service", "engine": meta.engine,
                  "scheme": meta.scheme, "backend": self.config.backend,
                  "service": next(_SERVICE_IDS)}
        reg = obs_metrics.DEFAULT
        self._obs_requests = reg.counter("serving.requests", **labels)
        self._obs_batches = reg.counter("serving.batches", **labels)
        self._obs_batch_rows = reg.counter("serving.batch_rows", **labels)
        self._obs_pad_rows = reg.counter("serving.pad_rows", **labels)
        self._obs_pad_kmers = reg.counter("serving.pad_kmers", **labels)
        self._obs_wall_ms = reg.histogram("serving.batch_wall_ms", **labels)
        # sync-path trace bookkeeping: request id -> (trace ctx, t_enq).
        # The async scheduler keeps its own (_Pending.trace) and never
        # routes through submit(), so the two never mix.
        self._admitted: Dict[int, Tuple[Tuple[str, Optional[str]], float]] \
            = {}

    # -- construction -------------------------------------------------------
    @classmethod
    def from_snapshot(cls, directory: str,
                      config: Optional[ServiceConfig] = None,
                      **load_kw) -> "GeneSearchService":
        """Boot a service straight from a ``repro.index.store`` snapshot."""
        return cls(store.load(directory, **load_kw), config)

    @property
    def state(self) -> state_mod.IndexState:
        return self._state

    @property
    def version(self) -> int:
        """Monotone id of the :class:`IndexState` currently served (stamped
        into every :class:`SearchResult` — the hot-swap audit trail)."""
        return self._version

    @property
    def n_files(self) -> int:
        return int(self._state.meta.n_files or 1)

    def swap_state(self, index, *, version: Optional[int] = None) -> int:
        """Hot snapshot swap: atomically replace the served state.

        Because every compiled step takes the state as a *pytree argument*
        (never a baked-in constant), swapping to a state with the same
        ``StateMeta`` reuses every compiled executable — zero recompiles
        under live traffic (asserted in ``tests/test_cluster.py``). A state
        with different meta (e.g. regrouped COBS) drops the runner cache so
        the next batch recompiles against the new geometry; a different
        kmer size is rejected outright (queued requests were bucketed under
        the old ``k``, their batches would be cut into the wrong kmers).

        NOT thread-safe on its own: callers running the async scheduler
        must pause it first (``AsyncScheduler.pause`` — what
        ``ReplicaRouter.swap_snapshot`` does).
        """
        new = state_mod.from_engine(index)
        if state_mod.kmer_size(new.meta) != self._k:
            raise ValueError(
                f"cannot hot-swap to a state with kmer size "
                f"{state_mod.kmer_size(new.meta)} (service buckets were "
                f"built for k={self._k}); boot a fresh service instead")
        if new.meta != self._state.meta:
            self._runners.clear()
        self._state = new
        self._version = self._version + 1 if version is None else int(version)
        return self._version

    # -- admission ----------------------------------------------------------
    def bucket_for(self, n_kmers: int) -> int:
        return bucket_for(n_kmers, self.config.min_bucket_kmers)

    def _normalize(self, request: Union[SearchRequest, np.ndarray]
                   ) -> Tuple[SearchRequest, int]:
        """Shared admission validation: ``(request, n_kmers)`` or raise."""
        return normalize_request(request, self._k)

    def submit(self, request: Union[SearchRequest, np.ndarray]) -> int:
        """Enqueue one read; returns its request id.

        The request joins its kmer bucket's queue; with ``auto_flush`` the
        bucket executes as soon as ``max_batch`` requests are waiting.
        """
        request, n_kmers = self._normalize(request)
        rid = request.request_id
        if rid is None:
            rid = self._next_id
        elif rid in self._inflight:
            raise ValueError(
                f"request id {rid} is already in flight (pending or "
                f"unclaimed result)")
        self._next_id = max(self._next_id, rid) + 1
        self._inflight.add(rid)
        if obs_trace.DEFAULT.enabled:
            # trace id minted at admission; the span chain is emitted in
            # one pass when the batch finalizes (_flush_bucket)
            self._admitted[rid] = ((obs_trace.DEFAULT.mint_trace(), None),
                                   time.monotonic())
        req = SearchRequest(read=request.read, request_id=rid)
        bucket = self.bucket_for(n_kmers)
        self._pending.setdefault(bucket, []).append((req, n_kmers))
        if self.config.auto_flush and \
                len(self._pending[bucket]) >= self.config.max_batch:
            self._flush_bucket(bucket)
        return rid

    def flush(self) -> None:
        """Execute every queued bucket (partial batches padded)."""
        for bucket in sorted(self._pending):
            while self._pending.get(bucket):
                self._flush_bucket(bucket)
        self._pending = {b: q for b, q in self._pending.items() if q}

    def result(self, request_id: int) -> SearchResult:
        """Pop a finished request's result (KeyError if not served yet)."""
        out = self._results.pop(request_id)
        self._inflight.discard(request_id)
        return out

    def search(self, reads: Sequence[np.ndarray]) -> List[SearchResult]:
        """Synchronous convenience: submit all, flush, return in order."""
        ids = [self.submit(r) for r in reads]
        self.flush()
        return [self.result(i) for i in ids]

    # -- execution ----------------------------------------------------------
    def _runner(self, bucket: int):
        """The compiled step for one (bucket, backend) pair.

        ``"jnp"`` jits the whole step end-to-end (the state is a pytree
        argument, so the index matrices are real inputs, not baked-in
        constants). The ``idl_probe`` and ``sharded`` backends dispatch
        the probe through their own jitted executors (one per plan) and
        jit the coverage postlude here.
        """
        r = self._runners.get(bucket)
        if r is not None:
            return r
        meta = self._state.meta
        reduce = functools.partial(
            _msmt_reduce, meta.engine, meta.n_files, self.config.theta)
        backend = self.config.backend
        if self.kmer_cache is not None:
            # cached path (any backend): the per-kmer probe runs host-side
            # through the membership cache, and only the coverage postlude
            # is jitted — one compile per bucket, same as planned backends
            post = jax.jit(reduce)

            def step(state, reads, valid, need):
                per = self._cached_per_kmer(
                    state, reads, generation=self._version)
                return post(per, valid, need)

            self._runners[bucket] = (step, post)
        elif backend == "jnp":
            @jax.jit
            def step(state, reads, valid, need):
                per = state_mod.to_engine(state).query_batch(
                    reads, backend="jnp")
                return reduce(per, valid, need)

            self._runners[bucket] = (step, step)
        else:
            post = jax.jit(reduce)
            kw = self._probe_kw()

            def step(state, reads, valid, need):
                per = state_mod.to_engine(state).query_batch(
                    reads, backend=backend, **kw)
                return post(per, valid, need)

            self._runners[bucket] = (step, post)
        return self._runners[bucket]

    def _probe_kw(self) -> Dict[str, bool]:
        """Backend kwargs for ``query_batch``: no Mosaic target on CPU, so
        ``idl_probe`` executes the planned backend with the kernel's fused
        jnp oracle instead of the (python-stepped) Pallas interpreter —
        same plan, bit-identical results."""
        if self.config.backend == "idl_probe" and \
                jax.default_backend() == "cpu":
            return {"use_ref": True}
        return {}

    def _probe_unique(self, state, kmers: np.ndarray) -> np.ndarray:
        """Probe ``(M, k)`` distinct kmers -> ``(M, ...)`` engine rows.

        Each kmer is a standalone length-k read through the dedup'd probe
        path (``query.execute(..., dedup=True)``): already-unique input
        means the dedup layer contributes only its pow2 padding (bounded
        compile shapes) and locality sort (DMA-minimal gather order).
        Small miss-sets are padded up to one floor size first — a warm
        cache yields a trickle of tiny, varied miss counts, and without
        the floor each distinct pow2 size would trigger its own XLA
        compile (seconds) to probe a handful of kmers (microseconds).
        """
        m = kmers.shape[0]
        floor = 128
        if m < floor:
            kmers = np.concatenate(
                [kmers, np.repeat(kmers[:1], floor - m, axis=0)])
        out = state_mod.to_engine(state).query_batch(
            jnp.asarray(kmers), backend=self.config.backend,
            dedup=True, **self._probe_kw())
        return np.asarray(out)[:m, 0]

    def _rows_via_cache(self, cache, state, arr, flat, generation
                        ) -> np.ndarray:
        """Per-kmer rows for ``flat`` packed codes, memoized in ``cache``.

        Warm path is pure vectorized numpy (one searchsorted + one row
        gather per tier — see ``kmer_cache``); only MISS codes are
        deduplicated and probed through the dedup'd compiled path, then
        inserted for the next batch. Returns a fresh ``(n, ...)`` row
        matrix the caller may mutate.
        """
        cache.begin(generation)
        vals, hit = cache.lookup(flat)
        if vals is None or not hit.all():
            miss = np.flatnonzero(~hit)
            uniq, first, inverse = np.unique(
                flat[miss], return_index=True, return_inverse=True)
            wins = np.lib.stride_tricks.sliding_window_view(
                arr, self._k, axis=1).reshape(-1, self._k)
            probed = self._probe_unique(state, wins[miss[first]])
            if vals is None:
                vals = np.zeros((flat.size,) + probed.shape[1:],
                                probed.dtype)
            vals[miss] = probed[inverse]
            cache.insert(uniq, probed)
        return vals

    def _rows_for_unique(self, cache, state, codes, wins, generation
                         ) -> np.ndarray:
        """Like ``_rows_via_cache`` for SORTED-UNIQUE codes with their
        aligned ``(M, k)`` windows — the live service's base-backfill
        entry point, where the (deduplicated) merged-cache misses are
        already known. Returns a fresh row matrix."""
        cache.begin(generation)
        vals, hit = cache.lookup(codes)
        if vals is None or not hit.all():
            miss = np.flatnonzero(~hit)
            probed = self._probe_unique(state, wins[miss])
            if vals is None:
                vals = np.zeros((codes.size,) + probed.shape[1:],
                                probed.dtype)
            vals[miss] = probed
            cache.insert(codes[miss], probed)
        return vals

    def _cached_per_kmer(self, state, reads, *, generation: int):
        """The cache-mediated probe: reads -> per-kmer membership rows.

        Packs the batch's kmers into uint64 codes and serves per-kmer
        rows from :class:`~repro.serving.kmer_cache.KmerCache`, probing
        only misses. Exact: membership is a pure function of ``(kmer,
        state)``. The live service overrides the runner with its merged
        base|delta variant (see ``LiveGeneSearchService._runner``).
        """
        arr = np.asarray(reads)
        codes = kmer_cache_mod.pack_codes(arr, self._k)
        flat = codes.ravel()
        vals = self._rows_via_cache(self.kmer_cache, state, arr, flat,
                                    int(generation))
        # host array straight out: the jitted postlude converts on entry,
        # which is cheaper than an explicit jnp.asarray round-trip here
        return vals.reshape(codes.shape + vals.shape[1:])

    # The flush pipeline, split into its three stages so the async
    # scheduler (repro.serving.scheduler) can overlap them across batches:
    # _assemble (host: padding + thresholds) -> _execute (device dispatch)
    # -> _finalize (host: materialize + decode). The synchronous path below
    # runs them back to back — both paths are the SAME code, so scheduler
    # answers are bit-identical to flush() by construction.

    def _assemble(self, take, bucket: int):
        """Pad ``take`` = [(request, n_kmers), ...] into the bucket's fixed
        batch shape (host-side; no device work)."""
        with obs_trace.stage("serve.assemble"):
            rows, read_len = self.config.max_batch, bucket + self._k - 1
            batch = np.zeros((rows, read_len), dtype=np.uint8)
            valid = np.zeros((rows, bucket), dtype=bool)
            need = np.zeros((rows,), dtype=np.int32)
            for i, (req, n_k) in enumerate(take):
                batch[i, :req.read.shape[0]] = req.read
                valid[i, :n_k] = True
                need[i] = query.coverage_need(self.config.theta, n_k)
            for i in range(len(take), rows):   # pad rows replay row 0;
                batch[i], valid[i], need[i] = batch[0], valid[0], need[0]
            return batch, valid, need

    def _execute(self, bucket: int, batch, valid, need):
        """Dispatch the bucket's compiled step; returns the on-device out."""
        with obs_trace.stage("serve.execute"):
            step, _ = self._runner(bucket)     # pad results are discarded
            if self.kmer_cache is not None:
                # host arrays straight through: the cached step packs and
                # looks up on the host anyway (a jnp round-trip of the
                # batch would be copied right back), and jit converts
                # valid/need
                return step(self._state, batch, valid, need)
            return step(self._state, jnp.asarray(batch), jnp.asarray(valid),
                        jnp.asarray(need))

    def _finalize(self, take, bucket: int, out) -> List[SearchResult]:
        """Materialize the device output and decode per-request verdicts."""
        with obs_trace.stage("serve.finalize"):
            with obs_trace.stage("serve.finalize.wait"):
                out = np.asarray(out)          # blocks until device done
            # the planned backend's locality counters of this batch, which
            # the device produced with the answer
            query.drain_locality()
            single_set = self._state.meta.engine == "bloom"
            results = []
            for i, (req, n_k) in enumerate(take):
                row = out[i]
                if single_set:
                    fids = (0,) if bool(row) else ()
                else:
                    fids = tuple(int(f) for f in np.nonzero(row)[0])
                results.append(SearchResult(
                    request_id=req.request_id, matches=row, file_ids=fids,
                    n_kmers=n_k, bucket=bucket, version=self._version))
            return results

    def _flush_bucket(self, bucket: int) -> None:
        queue = self._pending.get(bucket, [])
        take, self._pending[bucket] = \
            queue[:self.config.max_batch], queue[self.config.max_batch:]
        if not take:
            return
        t0 = time.monotonic()
        batch, valid, need = self._assemble(take, bucket)
        t_asm = time.monotonic()
        out = self._execute(bucket, batch, valid, need)
        t_exec = time.monotonic()
        for res in self._finalize(take, bucket, out):
            self._results[res.request_id] = res
        t_done = time.monotonic()
        self._record_batch(BatchStats(
            bucket=bucket, n_requests=len(take),
            batch_rows=self.config.max_batch,
            pad_rows=self.config.max_batch - len(take),
            pad_kmers=self.config.max_batch * bucket
            - sum(n_k for _, n_k in take),
            wall_ms=(t_done - t0) * 1e3))
        entries = []
        for req, _ in take:
            ctx, t_enq = self._admitted.pop(req.request_id, (None, t0))
            entries.append((ctx, t_enq, req.request_id))
        emit_request_spans(entries, bucket=bucket, t0=t0, t_asm=t_asm,
                           t_exec=t_exec, t_done=t_done,
                           version=self._version)

    # -- observability ------------------------------------------------------
    def _record_batch(self, bs: BatchStats) -> None:
        """The single batch-telemetry feed: window the record (the deque
        keeps per-batch detail for ``request_latencies_ms``) and mirror
        the aggregates into the process registry — both the sync flush
        and the async scheduler's completer land here, so the aggregate
        views below hold for either path."""
        self.batch_stats.append(bs)
        self._obs_requests.inc(bs.n_requests)
        self._obs_batches.inc()
        self._obs_batch_rows.inc(bs.batch_rows)
        self._obs_pad_rows.inc(bs.pad_rows)
        self._obs_pad_kmers.inc(bs.pad_kmers)
        self._obs_wall_ms.observe(bs.wall_ms)

    def compile_counts(self) -> Dict[int, int]:
        """Compiled-executable count per bucket (the compile-once proof).

        For the ``jnp`` backend this counts the end-to-end jitted step; for
        the others, the jitted coverage postlude (the probe is the
        backend's own executor, compiled once per plan).
        """
        return {b: counter._cache_size()
                for b, (_, counter) in sorted(self._runners.items())}

    def cache_stats(self) -> Optional[Dict[str, float]]:
        """``KmerCache.stats()`` of this service (None when cache is off)."""
        return (self.kmer_cache.stats()
                if self.kmer_cache is not None else None)

    def requests_served(self) -> int:
        """Lifetime requests served — a view over the registry counter
        (the deque is a bounded window; the counter never forgets)."""
        return int(self._obs_requests.value)

    def occupancy(self) -> float:
        """Fraction of batch rows that carried real requests (lifetime,
        registry-backed)."""
        rows = self._obs_batch_rows.value
        return self._obs_requests.value / rows if rows else 0.0

    def request_latencies_ms(self) -> List[float]:
        """Per-request latency: each request is charged its batch's wall."""
        out: List[float] = []
        for s in self.batch_stats:
            out.extend([s.wall_ms] * s.n_requests)
        return out
