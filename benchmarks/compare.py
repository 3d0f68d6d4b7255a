"""Compare bench records: per-metric deltas between ``BENCH_*.json`` runs.

Every bench writes a ``BENCH_*.json`` stamped with
:func:`benchmarks.common.bench_metadata` (the ``host`` section). This tool
loads two or more such records — a baseline and one or more candidates —
joins them on that host key, and prints a per-metric delta table over
every shared numeric leaf (dotted paths, ``config``/``host``/``note``
subtrees excluded).

Scaling numbers are meaningless across different hardware, so records
whose host keys disagree are still compared but loudly flagged: the join
column says which fields differ (a 2-shard fleet on a 1-core CI box
cannot beat one shard, and the table has to say so).

``--fail-on-regression PCT`` makes the exit code a CI gate: metrics whose
names classify as higher-is-better (throughput, speedup, hit_rate, ...)
or lower-is-better (latency, miss rates, overhead, tile bytes, ...) fail
the run when the candidate is worse than the baseline by more than PCT
percent. Unclassified metrics are reported but never gate.

    PYTHONPATH=src python -m benchmarks.compare BASE.json NEW.json \
        [--fail-on-regression 10]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import Dict, List, Tuple

# Substrings that classify a metric path's direction. Checked in order:
# the first list that matches wins, so "latency_ms.p50" is lower-is-better
# even though it contains no higher-is-better marker.
HIGHER_IS_BETTER = ("throughput", "speedup", "hit_rate", "recall",
                    "ratio_rh_over_idl", "_rps", "rps_")
LOWER_IS_BETTER = ("latency", "_ms", "overhead", "miss", "_bytes",
                   "bytes_per", "wall_s", "dma", "_s_")

# host fields that define "same box" for the join; the rest of
# bench_metadata (timestamps, library patch versions) may drift freely
HOST_KEY_FIELDS = ("platform", "machine", "cpu_count", "jax_backend",
                   "jax_device_count")


def host_key(doc: dict) -> Tuple:
    """The identity :func:`benchmarks.common.bench_metadata` gives a run's
    hardware — the join key across records."""
    host = doc.get("host", {})
    return tuple(host.get(f) for f in HOST_KEY_FIELDS)


def numeric_leaves(doc: dict, prefix: str = "") -> Dict[str, float]:
    """Flatten a bench record to ``{dotted.path: value}`` over its numeric
    scalars; provenance subtrees (``config``/``host``/``note``) are not
    metrics and are skipped."""
    out: Dict[str, float] = {}
    for k, v in doc.items():
        if k in ("config", "host", "note"):
            continue
        path = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[path] = float(v)
        elif isinstance(v, dict):
            out.update(numeric_leaves(v, path))
    return out


def direction(path: str) -> int:
    """+1 higher-is-better, -1 lower-is-better, 0 unclassified."""
    low = path.lower()
    for marker in HIGHER_IS_BETTER:
        if marker in low:
            return 1
    for marker in LOWER_IS_BETTER:
        if marker in low:
            return -1
    return 0


def compare(base: dict, cand: dict) -> List[dict]:
    """Per-metric rows for every numeric leaf the two records share."""
    b, c = numeric_leaves(base), numeric_leaves(cand)
    rows = []
    for path in sorted(set(b) & set(c)):
        old, new = b[path], c[path]
        delta_pct = (100.0 * (new - old) / abs(old)) if old else (
            0.0 if new == old else float("inf"))
        d = direction(path)
        regressed_pct = (-delta_pct if d > 0 else
                         delta_pct if d < 0 else 0.0)
        rows.append({"metric": path, "base": old, "cand": new,
                     "delta_pct": delta_pct, "direction": d,
                     "regressed_pct": regressed_pct})
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("records", nargs="+",
                    help="two or more BENCH_*.json files; the first is "
                         "the baseline")
    ap.add_argument("--fail-on-regression", type=float, default=None,
                    metavar="PCT",
                    help="exit 1 if any direction-classified metric is "
                         "worse than the baseline by more than PCT%%")
    args = ap.parse_args()
    if len(args.records) < 2:
        ap.error("need a baseline and at least one candidate record")

    docs = []
    for p in args.records:
        path = pathlib.Path(p)
        docs.append((path.name, json.loads(path.read_text())))
    base_name, base = docs[0]
    base_key = host_key(base)

    failed = False
    for cand_name, cand in docs[1:]:
        cand_key = host_key(cand)
        print(f"\n== {base_name} (base) vs {cand_name} ==")
        if cand_key != base_key:
            diff = [f for f, a, b in zip(HOST_KEY_FIELDS, base_key,
                                         cand_key) if a != b]
            print(f"!! host mismatch on {diff} — cross-hardware deltas "
                  f"describe the boxes, not the code")
        else:
            print(f"host: {dict(zip(HOST_KEY_FIELDS, base_key))}")
        rows = compare(base, cand)
        if not rows:
            print("no shared numeric metrics")
            continue
        width = max(len(r["metric"]) for r in rows)
        print(f"{'metric':<{width}}  {'base':>12}  {'cand':>12}  "
              f"{'delta':>8}")
        for r in rows:
            flag = ""
            if args.fail_on_regression is not None and \
                    r["regressed_pct"] > args.fail_on_regression:
                flag = "  << REGRESSION"
                failed = True
            arrow = {1: "+", -1: "-", 0: " "}[r["direction"]]
            print(f"{r['metric']:<{width}}  {r['base']:>12.4g}  "
                  f"{r['cand']:>12.4g}  {r['delta_pct']:>+7.1f}%"
                  f" {arrow}{flag}")
        print("(direction: '+' higher is better, '-' lower is better, "
              "' ' informational)")

    if failed:
        print(f"\nFAIL: regression(s) beyond "
              f"{args.fail_on_regression}% vs {base_name}")
        sys.exit(1)


if __name__ == "__main__":
    main()
