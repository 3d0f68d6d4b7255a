"""Serving layer: dynamic-batching service + serving cluster + v1 compat.

:mod:`repro.serving.service` is the synchronous serving surface — typed
request/response, shape-bucketed dynamic batching over any ``IndexState``,
snapshot-backed startup. On top of it, the serving cluster:
:mod:`repro.serving.scheduler` (futures, deadline flusher, double-buffered
batch pipeline), :mod:`repro.serving.router` (K ``device_put`` replicas,
pluggable routing, hot snapshot swap under traffic) and
:mod:`repro.serving.autoscale` (admission policy + replica autoscaler
driven by the recorded batch telemetry). :mod:`repro.serving.live` adds
the write path: ``LiveGeneSearchService`` / ``LiveReplicaRouter`` serve a
:class:`repro.index.lsm.LiveIndex` (base + delta) with background
compaction. :mod:`repro.serving.scatter` fans each request over the
shards of a sharded archive and merges the partials exactly. Every fleet
(replicas, shards, the live router) runs in the process that owns the
devices. :mod:`repro.serving.genesearch` keeps the serve-geometry
config + plan helpers; its removed v1 bodies are call-time ImportError
stubs.
"""

from repro.serving import autoscale, genesearch, kmer_cache, live, router, \
    scatter, scheduler, service
from repro.serving.autoscale import (
    AdmissionPolicy,
    AutoscaleConfig,
    ReplicaAutoscaler,
)
from repro.serving.kmer_cache import KmerCache, KmerCacheConfig, \
    merge_cache_stats, pack_codes
from repro.serving.live import Compactor, LiveGeneSearchService, \
    LiveReplicaRouter
from repro.serving.router import ReplicaRouter, RouterConfig, RoutingPolicy
from repro.serving.scatter import ScatterConfig, ScatterError, \
    ScatterGatherRouter, ShardDeadError, ShardSearchService
from repro.serving.scheduler import AsyncScheduler, ClusterStats, InsertAck, \
    SchedulerConfig
from repro.serving.service import (
    BatchStats,
    GeneSearchService,
    SearchRequest,
    SearchResult,
    ServiceConfig,
)

__all__ = [
    "AdmissionPolicy",
    "AsyncScheduler",
    "AutoscaleConfig",
    "BatchStats",
    "ClusterStats",
    "Compactor",
    "GeneSearchService",
    "InsertAck",
    "KmerCache",
    "KmerCacheConfig",
    "LiveGeneSearchService",
    "LiveReplicaRouter",
    "ReplicaAutoscaler",
    "ReplicaRouter",
    "RouterConfig",
    "RoutingPolicy",
    "ScatterConfig",
    "ScatterError",
    "ScatterGatherRouter",
    "SchedulerConfig",
    "SearchRequest",
    "SearchResult",
    "ServiceConfig",
    "ShardDeadError",
    "ShardSearchService",
    "autoscale",
    "genesearch",
    "kmer_cache",
    "live",
    "merge_cache_stats",
    "pack_codes",
    "router",
    "scatter",
    "scheduler",
    "service",
]
