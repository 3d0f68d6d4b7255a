"""Obs export: one snapshot shape, one dump format.

``snapshot()`` bundles the process's metrics snapshot and finished trace
records into a single plain dict. Every serving tier (service, replica
router, live router, scatter router) runs in the one process and feeds
its registry and tracer, so this snapshot is the whole fleet's.

``dump()`` writes a snapshot to disk as two artifacts next to each
other: ``PATH`` (metrics + traces, JSON) and ``PATH`` with a ``.chrome``
suffix inserted (Chrome ``trace_event`` file for chrome://tracing) — the
``launch/serve.py --obs-dump`` and CI-artifact format.

``cache_stats_view()`` derives the classic membership-cache stats dict
(hits / misses / lookups / hit_rate / entries / capacity / evictions /
invalidations) from a snapshot's ``kmer_cache.*`` series, summed over
every cache in the process.
"""

from __future__ import annotations

import json
import pathlib
from typing import List, Optional

from repro.obs import metrics as metrics_mod
from repro.obs import trace as trace_mod


def snapshot(registry: Optional[metrics_mod.Registry] = None,
             tracer: Optional[trace_mod.Tracer] = None) -> dict:
    """This process's full obs state: ``{"metrics": <registry snapshot>,
    "spans": [finished records...]}``. Plain data — safe to json.dump to
    disk."""
    reg = registry if registry is not None else metrics_mod.DEFAULT
    trc = tracer if tracer is not None else trace_mod.DEFAULT
    return {"metrics": reg.snapshot(), "spans": trc.records()}


def traces_of(snap: dict) -> dict:
    """Group a snapshot's span records per trace id."""
    traces: dict = {}
    for rec in snap.get("spans", ()):
        traces.setdefault(rec["trace"], []).append(rec)
    for recs in traces.values():
        recs.sort(key=lambda r: r["t0"])
    return traces


def chrome_events(snap: dict) -> dict:
    """Chrome ``trace_event`` JSON for a snapshot's spans."""
    events = [{"name": rec["name"], "ph": "X", "cat": rec["status"],
               "ts": rec["t0"] * 1e6, "dur": rec["dur"] * 1e6,
               "pid": rec["pid"], "tid": rec["trace"],
               "args": {"span": rec["span"], "parent": rec["parent"],
                        **rec.get("attrs", {})}}
              for rec in snap.get("spans", ())]
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def dump(snap: dict, path: str) -> List[str]:
    """Write a snapshot to ``path`` (metrics + traces, JSON) and
    a sibling ``<stem>.chrome.json`` Chrome trace. Returns the written
    paths."""
    p = pathlib.Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    doc = {"metrics": snap.get("metrics", {}),
           "traces": traces_of(snap)}
    p.write_text(json.dumps(doc, indent=2, default=float) + "\n")
    chrome = p.with_suffix(".chrome.json")
    chrome.write_text(json.dumps(chrome_events(snap), default=float) + "\n")
    return [str(p), str(chrome)]


def cache_stats_view(snap: dict) -> dict:
    """Membership-cache stats dict from a snapshot's ``kmer_cache.*``
    series — counters sum across every cache instance in the snapshot, so
    one helper serves the single-service, router and scatter tiers alike
    (shape-compatible with the ``KmerCache.stats()`` /
    ``merge_cache_stats()`` dicts)."""
    m = snap.get("metrics", snap)   # accept a bare metrics snapshot too
    hits = metrics_mod.counter_total(m, "kmer_cache.hits")
    misses = metrics_mod.counter_total(m, "kmer_cache.misses")
    lookups = hits + misses
    return {
        "hits": int(hits),
        "misses": int(misses),
        "lookups": int(lookups),
        "hit_rate": (hits / lookups) if lookups else 0.0,
        "entries": int(metrics_mod.gauge_total(m, "kmer_cache.entries")),
        "capacity": int(metrics_mod.gauge_total(m, "kmer_cache.capacity")),
        "evictions": int(metrics_mod.counter_total(
            m, "kmer_cache.evictions")),
        "invalidations": int(metrics_mod.counter_total(
            m, "kmer_cache.invalidations")),
    }
