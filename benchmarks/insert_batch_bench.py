"""Batched donated insert vs the seed's per-read loop.

Measures the original acceptance-criterion path: 64 reads inserted into a
partitioned IDL-BF as ONE jit-compiled, donated scatter-OR (the
``jnp`` backend of `repro.index.ingest`) against the seed semantics of one
`bf.at[locs].set(1)` full-array copy per read. See ``ingest_bench.py`` for
the full per-backend ingest matrix.

    PYTHONPATH=src python -m benchmarks.insert_batch_bench
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bloom, idl
from repro.index import PackedBloomIndex, ingest, registry


def main() -> None:
    cfg = idl.IDLConfig(k=31, t=16, L=1 << 15, eta=4, m=1 << 26)
    rng = np.random.default_rng(0)
    reads = jnp.asarray(rng.integers(0, 4, size=(64, 230), dtype=np.uint8))

    # --- new path: one jit call for the whole batch, donated buffer -------
    eng = PackedBloomIndex.build(cfg, "idl")
    eng.insert_batch(reads).words.block_until_ready()      # compile
    ingest._execute_jnp.clear_cache()
    t0 = time.perf_counter()
    out = PackedBloomIndex.build(cfg, "idl").insert_batch(reads)
    out.words.block_until_ready()
    t_batch_cold = time.perf_counter() - t0
    assert ingest._execute_jnp._cache_size() == 1          # ONE jit call
    t0 = time.perf_counter()
    out2 = PackedBloomIndex.build(cfg, "idl").insert_batch(reads)
    out2.words.block_until_ready()
    t_batch = time.perf_counter() - t0

    # --- seed path: per-read python loop, full-array copy per read --------
    insert_one = jax.jit(
        lambda bits, codes: bloom.insert_locations(
            bits, registry.locations(cfg, codes, "idl")))
    bits = bloom.empty_filter(cfg.m)
    bits = insert_one(bits, reads[0]).block_until_ready()  # compile
    bits = bloom.empty_filter(cfg.m)
    t0 = time.perf_counter()
    for r in reads:
        bits = insert_one(bits, r)
    bits.block_until_ready()
    t_loop = time.perf_counter() - t0

    np.testing.assert_array_equal(
        np.asarray(out.bits), np.asarray(bits))            # bit-identical
    print(f"m=2^26 bits, 64 reads x 200 kmers x eta={cfg.eta}:")
    print(f"  batched donated insert (1 jit call): {t_batch * 1e3:8.1f} ms "
          f"(cold {t_batch_cold * 1e3:.1f} ms)")
    print(f"  per-read loop (64 jit calls):        {t_loop * 1e3:8.1f} ms")
    print(f"  speedup: {t_loop / t_batch:.1f}x")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    main()
