"""Query-path benchmark: the shared planner/executor vs the per-kmer gather.

Mirrors ``insert_batch_bench.py`` for the query side of the acceptance
criteria: 64 reads × 200 kmers against a partitioned IDL-BF at m=2^26,
measured per backend of :mod:`repro.index.query`:

* ``per_kmer_gather_loop`` — the seed semantics every engine used before
  the unified layer: one jit'd per-read packed gather per read (the path
  the CI smoke run guards against regressing to);
* ``jnp``       — the batched pure-XLA reference gather (one jit call);
* ``idl_probe`` — the planned backend: device run-length planner + the
  generalized run-coalesced executor (the Pallas ``probe_rows`` kernel on
  accelerators; its fused jnp oracle on CPU, where Mosaic is unavailable —
  same plan, bit-identical results);
* ``sharded``   — ``shard_map`` over the default 1-D device mesh.

Also reports the planner's locality metrics — run count, mean run length
and DMA bytes (n_runs × block_bytes, the TPU HBM-traffic / CPU cache-miss
proxy the paper minimizes) — for IDL vs the RH baseline.

    PYTHONPATH=src python -m benchmarks.query_batch_bench [--smoke]

Writes ``BENCH_query.json`` (full mode) next to the repo root.
"""

from __future__ import annotations

import argparse
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_metadata, timeit_ms
from repro.core import bloom, idl
from repro.index import PackedBloomIndex, query, registry


def _time(fn, *, iters: int, result=None) -> float:
    """Median wall ms per call via the hardened warmup+median harness."""
    if result is not None:
        np.testing.assert_array_equal(np.asarray(fn()), result)
    return timeit_ms(fn, repeats=iters, warmup=2)


def run(m: int, n_reads: int, iters: int) -> dict:
    cfg = idl.IDLConfig(k=31, t=16, L=1 << 15, eta=4, m=m)
    rng = np.random.default_rng(0)
    reads = jnp.asarray(rng.integers(0, 4, size=(n_reads, 230), dtype=np.uint8))
    eng = PackedBloomIndex.build(cfg, "idl").insert_batch(reads[: n_reads // 8])

    want = np.asarray(eng.query_batch(reads, backend="jnp"))

    # seed semantics: one jit'd (η, n_k) packed gather per read
    per_read = jax.jit(
        lambda w, r: bloom.query_packed(
            w, registry.locations(cfg, r, "idl").astype(jnp.uint32)))

    def gather_loop():
        return jnp.stack([per_read(eng.words, r) for r in reads])

    timings = {
        "per_kmer_gather_loop": _time(gather_loop, iters=iters, result=want),
        "jnp": _time(lambda: eng.query_batch(reads, backend="jnp"),
                     iters=iters),
        "idl_probe": _time(
            lambda: eng.query_batch(reads, backend="idl_probe",
                                    **_cpu_executor_kw()),
            iters=iters, result=want),
        "sharded": _time(lambda: eng.query_batch(reads, backend="sharded"),
                         iters=iters, result=want),
    }

    plan = eng._plan(reads)
    locality = {}
    for scheme in ("idl", "rh"):
        sp = PackedBloomIndex.build(cfg, scheme)._plan(reads)
        rplan, _ = sp.plan_runs(reads)
        locality[scheme] = {
            "n_runs": int(rplan.n_runs),
            "n_probes": int(rplan.n_probes),
            "mean_run_len": round(rplan.n_probes / rplan.n_runs, 2),
            "planner_dma_bytes": int(sp.run_dma_bytes(rplan)),
        }

    out = {
        "config": {
            "m": m, "L": cfg.L, "eta": cfg.eta, "n_reads": n_reads,
            "read_len": 230, "n_kmers": 200, "scheme": "idl",
            "device": jax.default_backend(), "block_bytes": plan.block_bytes,
        },
        "ms_per_batch": {k: round(v, 3) for k, v in timings.items()},
        "ms_per_read": {k: round(v / n_reads, 4) for k, v in timings.items()},
        "planner_locality": locality,
        "speedups": {
            "planned_vs_per_kmer_gather": round(
                timings["per_kmer_gather_loop"] / timings["idl_probe"], 2),
            "batched_jnp_vs_per_kmer_gather": round(
                timings["per_kmer_gather_loop"] / timings["jnp"], 2),
            "planned_vs_batched_jnp": round(
                timings["jnp"] / timings["idl_probe"], 2),
            "idl_vs_rh_run_reduction": round(
                locality["rh"]["n_runs"] / locality["idl"]["n_runs"], 2),
        },
    }
    return out


def _cpu_executor_kw() -> dict:
    # no Mosaic target on CPU: execute the SAME plan with the kernel's
    # fused jnp oracle instead of the (python-stepped) Pallas interpreter
    return {"use_ref": True} if jax.default_backend() == "cpu" else {}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small config; assert parity; no JSON written")
    args = ap.parse_args()

    if args.smoke:
        res = run(m=1 << 20, n_reads=8, iters=2)
        print("smoke:", json.dumps(res["ms_per_batch"]))
        print("runs idl/rh:",
              res["planner_locality"]["idl"]["n_runs"],
              res["planner_locality"]["rh"]["n_runs"])
        return

    res = run(m=1 << 26, n_reads=64, iters=25)
    res["host"] = bench_metadata()
    out_path = pathlib.Path(__file__).resolve().parent.parent / "BENCH_query.json"
    out_path.write_text(json.dumps(res, indent=2) + "\n")
    print(json.dumps(res, indent=2))
    print(f"\nwrote {out_path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    main()
