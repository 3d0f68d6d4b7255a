"""Scatter-gather query tier over a sharded archive.

The second horizontal axis from the roadmap (replicas × shards): where
:mod:`repro.serving.router` puts K copies of ONE index behind a routing
policy, the :class:`ScatterGatherRouter` puts the N PIECES of one index
(:mod:`repro.index.shards`) behind a fan-out.
Every normalized request goes to ALL shards; partial answers are merged
EXACTLY — integer coverage thresholds make the merge lossless, so the
gathered result is bit-identical to one service holding the unsharded
index (asserted across engines × schemes × thetas in
tests/test_shards.py and in-bench by benchmarks/shards_bench.py).

Each shard member is an :class:`AsyncScheduler` over a
:class:`ShardSearchService`, mmap-booted from the shard-set snapshot:
N flusher threads in this process, sharing the GIL but overlapping
device dispatch. The router reads the geometry and the set version from
the CRC-checked set manifest.

**Shard death** is where the two partition axes genuinely differ, and
the router refuses to blur them:

* row-probe shards (bit-sliced / cobs) own a file range. A dead shard
  means those files are unanswerable; every gathered result names them
  in ``SearchResult.missing_files`` and reports their entries of
  ``matches`` as vacuously False. Partial truth, honestly labeled.
* bit-probe shards (flat BF / rambo) own a word range — every kmer's
  probes land across ALL shards. Dropping one shard's miss counts can
  only turn misses into hits: silent false-positive inflation. The
  router fails LOUD instead: affected futures get
  :class:`ShardDeadError`. Zero futures are ever dropped either way —
  every submit resolves with a result or an exception.

Results are stamped with the shard set's ``set_version`` (the audit
trail the replica tier keeps via service versions).
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.index import query, shards as shards_mod
from repro.index import state as state_mod
from repro.obs import export as obs_export
from repro.obs import trace as obs_trace
from repro.serving import service as service_mod
from repro.serving.scheduler import AsyncScheduler, SchedulerConfig

__all__ = [
    "ScatterConfig",
    "ScatterError",
    "ShardDeadError",
    "ShardSearchService",
    "ScatterGatherRouter",
]


class ScatterError(RuntimeError):
    """A shard-set-level operation failed (boot, no live shards)."""


class ShardDeadError(ScatterError):
    """A bit-probe shard died: its word range is unanswerable, and
    answering without it would silently inflate the false-positive rate
    (a missing MISS count can only turn misses into hits)."""


@dataclasses.dataclass(frozen=True)
class ScatterConfig:
    """Scatter-tier knobs (static for the life of the router)."""

    service: service_mod.ServiceConfig = dataclasses.field(
        default_factory=service_mod.ServiceConfig)
    scheduler: SchedulerConfig = dataclasses.field(
        default_factory=SchedulerConfig)
    verify: str = "eager"        # shard snapshot verify mode (store.load)


# ---------------------------------------------------------------------------
# The per-shard service.
# ---------------------------------------------------------------------------

class ShardSearchService(service_mod.GeneSearchService):
    """One shard's serving surface — a :class:`GeneSearchService` whose
    answers are the shard's EXACT mergeable partial.

    Row-probe shards are complete engines over their file range, so the
    base class already does the right thing: local verdicts (padding,
    theta and bucketing included) that the router concatenates / ORs.
    Bit-probe shards override the compiled step with
    ``shards.partial_prober`` — per-(kmer, slot) local MISS counts,
    trimmed to the request's true kmer count — and leave the one
    coverage threshold to the router's merge. Bit-probe partials are NOT
    client-facing verdicts; only the router should consume them.
    """

    def __init__(self, spec: shards_mod.ShardSpec, shard_id: int,
                 shard: state_mod.IndexState,
                 config: Optional[service_mod.ServiceConfig] = None,
                 *, version: int = 0):
        self._spec = spec
        self._shard_id = shard_id
        if not spec.row_probe and config is not None \
                and config.kmer_cache is not None:
            raise ValueError(
                "bit-probe shard services emit partial miss counts, not "
                "membership rows — the kmer cache caches the wrong thing "
                "here; cache at the gather tier instead")
        super().__init__(shard, config, version=version)

    @property
    def shard_id(self) -> int:
        return self._shard_id

    def _runner(self, bucket: int):
        if self._spec.row_probe:
            return super()._runner(bucket)
        r = self._runners.get(bucket)
        if r is None:
            lo, hi = self._spec.shard_units(self._shard_id)
            fn = shards_mod.partial_prober(
                self._spec.meta.cfgs[0], self._spec.meta.scheme, lo, hi,
                self._spec.meta.engine == "rambo")

            def step(state, reads, valid, need):
                del valid, need   # the router thresholds after the merge
                return fn(state.words[0], reads)

            r = self._runners[bucket] = (step, fn)
        return r

    def _finalize(self, take, bucket: int, out
                  ) -> List[service_mod.SearchResult]:
        if self._spec.row_probe:
            return super()._finalize(take, bucket, out)
        out = np.asarray(out)     # (max_batch, bucket, W') local misses
        return [service_mod.SearchResult(
            request_id=req.request_id,
            # trim pad kmers NOW: a pad slot has zero misses and would
            # alias a hit once partials are summed across shards
            matches=np.ascontiguousarray(out[i, :n_k]),
            file_ids=(), n_kmers=n_k, bucket=bucket,
            version=self._version)
            for i, (req, n_k) in enumerate(take)]


# ---------------------------------------------------------------------------
# The gather.
# ---------------------------------------------------------------------------

class _Gather:
    """One request's fan-out: a slot per shard, merged when the last
    slot is accounted for (answer, hard error, or death)."""

    def __init__(self, router: "ScatterGatherRouter", request_id: int,
                 n_kmers: int):
        self.future: Future = Future()
        self.request_id = request_id
        self.n_kmers = n_kmers
        self.parts: Dict[int, service_mod.SearchResult] = {}
        self.lost: set = set()
        self._router = router
        self._lock = threading.Lock()
        self._sealed = False

    def _account(self) -> bool:
        """True exactly once, when every shard has landed."""
        if self._sealed:
            return False
        if len(self.parts) + len(self.lost) < self._router.n_shards:
            return False
        self._sealed = True
        return True

    def shard_done(self, shard_id: int,
                   result: service_mod.SearchResult) -> None:
        with self._lock:
            self.parts[shard_id] = result
            finish = self._account()
        if finish:
            self._finish()

    def shard_lost(self, shard_id: int) -> None:
        with self._lock:
            self.lost.add(shard_id)
            finish = self._account()
        if finish:
            self._finish()

    def shard_failed(self, shard_id: int, exc: BaseException) -> None:
        """A shard answered with a hard error (bad request reaches every
        shard identically, so one error speaks for the gather)."""
        with self._lock:
            if self._sealed:
                return
            self._sealed = True
        if not self.future.done():
            self.future.set_exception(exc)

    def _finish(self) -> None:
        try:
            self.future.set_result(self._router._merge(self))
        except Exception as e:  # noqa: BLE001 - incl. ShardDeadError
            self.future.set_exception(e)


@dataclasses.dataclass
class _Shard:
    id: int
    sched: AsyncScheduler
    alive: bool = True


# ---------------------------------------------------------------------------
# The router.
# ---------------------------------------------------------------------------

class ScatterGatherRouter:
    """Fan one request over every shard of a shard-set snapshot; gather
    and merge the partials exactly. ``submit`` returns a
    ``Future[SearchResult]`` stamped with the shard set's version."""

    def __init__(self, shard_set_dir: str,
                 config: Optional[ScatterConfig] = None):
        self.config = config or ScatterConfig()
        self._dir = shard_set_dir
        # O(manifest): geometry + version from the CRC-checked set
        # manifest, before any shard is loaded
        sm = shards_mod.read_set_meta(shard_set_dir)
        self._spec = sm.spec
        self._set_version = sm.set_version
        self._k = state_mod.kmer_size(sm.spec.meta)
        self._lock = threading.Lock()
        self._next_rid = itertools.count()
        self._shards: List[_Shard] = []
        self._closed = False
        try:
            self._boot()
        except Exception:
            self.close()
            raise

    # -- boot ----------------------------------------------------------------
    def _boot(self) -> None:
        _, states = shards_mod.load_shard_set(
            self._dir, mmap=True, verify=self.config.verify)
        for s, st in enumerate(states):
            svc = ShardSearchService(self._spec, s, st,
                                     self.config.service,
                                     version=self._set_version)
            self._shards.append(_Shard(
                id=s, sched=AsyncScheduler(
                    svc, self.config.scheduler, replica_id=s)))

    # -- views ---------------------------------------------------------------
    @property
    def spec(self) -> shards_mod.ShardSpec:
        return self._spec

    @property
    def n_shards(self) -> int:
        return self._spec.n_shards

    @property
    def set_version(self) -> int:
        return self._set_version

    def live_shards(self) -> List[int]:
        with self._lock:
            return [sh.id for sh in self._shards if sh.alive]

    def stats(self) -> Dict[int, dict]:
        """Per-shard serving stats of the live shards."""
        with self._lock:
            members = [(sh.id, sh.sched) for sh in self._shards
                       if sh.alive]
        return {sid: {
            "shard_id": sid,
            "version": self._set_version,
            "compile_counts": sched.compile_counts(),
        } for sid, sched in members}

    def obs_snapshot(self) -> dict:
        """This process's obs snapshot: every shard member feeds the one
        process registry and tracer."""
        return obs_export.snapshot()

    # -- admission -----------------------------------------------------------
    def submit(self, request) -> Future:
        """Fan one read to every live shard; Future[SearchResult].

        Admission mints the trace id: the router's root span covers the
        whole scatter-gather (closed when the gathered future resolves),
        and each shard's pipeline spans open under it.
        """
        req, n_kmers = service_mod.normalize_request(request, self._k)
        rid = req.request_id
        if rid is None:
            rid = next(self._next_rid)
        req = service_mod.SearchRequest(read=req.read, request_id=rid)
        g = _Gather(self, rid, n_kmers)
        trc = obs_trace.DEFAULT
        ctx = None
        if trc.enabled:
            root = trc.start("request", tier="scatter", rid=rid)
            ctx = root.context()
            g.future.add_done_callback(lambda f: root.end(
                status="error" if (f.cancelled() or f.exception())
                else "ok"))
        with self._lock:
            if self._closed:
                raise ScatterError("scatter router is closed")
            members = list(self._shards)
        if not any(sh.alive for sh in members):
            raise ScatterError("scatter router has no live shards")
        for sh in members:
            if not sh.alive:
                g.shard_lost(sh.id)
            else:
                self._dispatch(sh, g, req, trace=ctx)
        return g.future

    def search(self, reads) -> List[service_mod.SearchResult]:
        """Synchronous convenience: submit all, results in order."""
        return [f.result() for f in [self.submit(r) for r in reads]]

    def _dispatch(self, sh: _Shard, g: _Gather,
                  req: service_mod.SearchRequest, *, trace=None) -> None:
        def _cb(f: Future) -> None:
            err = f.exception()
            if err is not None:
                g.shard_failed(sh.id, err)
            else:
                g.shard_done(sh.id, f.result())
        try:
            sh.sched.submit(req, trace=trace).add_done_callback(_cb)
        except Exception as e:  # noqa: BLE001 - closed scheduler = dead
            g.shard_lost(sh.id) if isinstance(e, RuntimeError) \
                else g.shard_failed(sh.id, e)

    # -- gather --------------------------------------------------------------
    def _merge(self, g: _Gather) -> service_mod.SearchResult:
        """Combine per-shard partials into the oracle's exact answer."""
        spec, meta = self._spec, self._spec.meta
        bucket = service_mod.bucket_for(
            g.n_kmers, self.config.service.min_bucket_kmers)
        missing: Tuple[int, ...] = ()
        if spec.row_probe:
            matches = np.zeros(int(meta.n_files), dtype=bool)
            if meta.engine == "bitsliced":
                for s, res in g.parts.items():
                    owned = shards_mod.shard_files(spec, s)
                    row = np.asarray(res.matches, dtype=bool)
                    matches[owned[0]:owned[0] + len(owned)] = \
                        row[:len(owned)]
            else:                     # cobs: OR over disjoint file sets
                for res in g.parts.values():
                    matches |= np.asarray(res.matches, dtype=bool)
            missing = tuple(sorted(
                f for s in g.lost for f in shards_mod.shard_files(spec, s)))
            fids = tuple(int(f) for f in np.nonzero(matches)[0])
        else:
            if g.lost:
                dead = sorted(g.lost)
                ranges = [spec.shard_units(s) for s in dead]
                raise ShardDeadError(
                    f"bit-probe shard(s) {dead} (word ranges {ranges}) "
                    f"died; their probes are unanswerable — failing loud "
                    f"instead of silently inflating the FPR")
            total = None              # (n_k, W') summed miss counts
            for s in range(spec.n_shards):
                part = np.asarray(g.parts[s].matches, dtype=np.int64)
                total = part if total is None else total + part
            member = total == 0       # a hit is zero misses ANYWHERE
            need = query.coverage_need(
                self.config.service.theta, g.n_kmers)
            if meta.engine == "bloom":
                hit = int(member[:, 0].sum()) >= need
                matches = np.bool_(hit)
                fids = (0,) if hit else ()
            else:                     # rambo: bucket grid -> per-file AND
                grid = member.reshape(g.n_kmers, meta.n_rep,
                                      meta.n_buckets)
                asn = shards_mod.rambo_file_assignment(meta)   # (R, N)
                per_rep = grid[:, np.arange(meta.n_rep)[:, None], asn]
                matches = per_rep.all(axis=1).sum(axis=0) >= need
                fids = tuple(int(f) for f in np.nonzero(matches)[0])
        return service_mod.SearchResult(
            request_id=g.request_id, matches=matches, file_ids=fids,
            n_kmers=g.n_kmers, bucket=bucket, version=self._set_version,
            missing_files=missing)

    # -- fault injection / lifecycle -----------------------------------------
    def kill_shard(self, shard_id: int) -> None:
        """Take one shard down (test/ops hook): it stops receiving
        traffic, and its scheduler drains what it already holds, so no
        future is dropped. Later gathers count it lost."""
        with self._lock:
            sh = self._shards[shard_id]
            if not sh.alive:
                return
            sh.alive = False
        sh.sched.close()              # drains: zero dropped futures

    def drain(self) -> None:
        """Block until every in-flight request has its reply."""
        with self._lock:
            members = [sh.sched for sh in self._shards if sh.alive]
        for sched in members:
            sched.drain()

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            members = [sh for sh in self._shards if sh.alive]
        for sh in members:
            sh.sched.close()

    def __enter__(self) -> "ScatterGatherRouter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
