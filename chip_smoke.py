"""Quickest proof that the gene-search system runs on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips, the sharded path only

One chip: builds the paper's bit-sliced IDL index (``idl-genesearch``:
1024 files, m = 2^26 rows, k = 31, eta = 4, L = 2^17; an 8 GiB state)
on the device through the ``idl_insert`` ingest backend, then serves
batches of 256 reads of 230 bp through ``GeneSearchService``, once with
the ``idl_probe`` backend (the Pallas probe kernel) and once with the
``jnp`` backend. Half of each batch is drawn from indexed files, half is
random. It checks that recall on the indexed reads is 1.0 (a Bloom filter
has no false negatives), that the two backends answer identically, and
that the served ``idl_probe`` step is the compiled kernel
(``tpu_custom_call``), not its jnp oracle.

Four chips: 4096 files over a 4-device files-axis mesh, 8 GiB per chip.
``repro.launch.serve.build_sharded_index`` builds each 1024-file shard on
its own device and joins them; the ``sharded`` query backend
serves the whole archive, and its answers must equal those of the plain
reference, the ``jnp`` backend on each shard alone, concatenated over
files. No device may hold more than its shard.

Only the genome length per file is cut (``--genome-len``); the data is
synthetic, made from ``--seed``. The script exits non-zero when JAX finds
no TPU or when any check fails; its last line on stdout is
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

BATCH = 256              # reads per served batch
N_BATCHES = 3


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def read_batches(archive, n_batches: int, read_len: int, rng):
    """``(reads, true file id or -1)`` per batch: half drawn from indexed
    files at random offsets, half uniformly random bases."""
    out = []
    for _ in range(n_batches):
        fids = rng.integers(0, len(archive), size=BATCH // 2)
        reads = []
        for f in fids:
            g = np.asarray(archive[int(f)].genome)
            s = int(rng.integers(0, g.shape[0] - read_len + 1))
            reads.append(g[s:s + read_len])
        reads.extend(rng.integers(0, 4, size=(BATCH - len(reads), read_len),
                                  dtype=np.uint8))
        truth = np.concatenate([fids, np.full(BATCH - len(fids), -1)])
        out.append((reads, truth))
    return out


def recall(results, truth) -> float:
    pos = [int(t) in r.file_ids for r, t in zip(results, truth) if t >= 0]
    return float(np.mean(pos))


def gib(n: int) -> str:
    return f"{n} B ({n / 2**30:.3f} GiB)"


class CompileClock:
    """Seconds the backend compiler ran, from JAX's own monitoring event
    (a compile served from the persistent cache does not count)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def one_chip(args, jax, cfg, clock) -> None:
    from repro.data import genome
    from repro.index import query
    from repro.launch.serve import build_index
    from repro.serving import GeneSearchService, ServiceConfig

    dev = jax.devices()[0]
    archive = genome.synth_archive(cfg.n_files, args.genome_len,
                                   seed=args.seed)
    t0 = time.perf_counter()
    c0 = clock.seconds
    eng = build_index(cfg, archive, chunk_reads=256)
    jax.block_until_ready(eng.words)
    build_s = time.perf_counter() - t0
    state_bytes = eng.state.nbytes
    print(f"state: {eng.words.shape} uint32 on {eng.words.devices()}, "
          f"{gib(state_bytes)}")
    print(f"build: {build_s:.3f} s with idl_insert "
          f"(backend compile {clock.seconds - c0:.3f} s)")
    check(state_bytes == cfg.m * cfg.file_words * 4,
          f"state holds {state_bytes} bytes, not the config's m x F/8")

    batches = read_batches(archive, N_BATCHES, cfg.read_len,
                           np.random.default_rng(args.seed + 1))
    answers = {}
    for backend in ("idl_probe", "jnp"):
        svc = GeneSearchService(eng, ServiceConfig(
            theta=cfg.theta, backend=backend, max_batch=BATCH))
        c0 = clock.seconds
        walls, results = [], []
        for reads, _ in batches:
            t0 = time.perf_counter()
            results.append(svc.search(reads))
            walls.append(time.perf_counter() - t0)
        rec = [recall(res, truth) for res, (_, truth) in zip(results,
                                                                batches)]
        false_hits = sum(len(r.file_ids) for res, (_, truth)
                         in zip(results, batches)
                         for r, t in zip(res, truth) if t < 0)
        answers[backend] = np.stack([r.matches for res in results
                                     for r in res])
        print(f"serve[{backend}]: {len(batches)} batches of {BATCH} reads; "
              f"batch wall s {[round(w, 6) for w in walls]} (first includes "
              f"compile); backend compile {clock.seconds - c0:.3f} s; "
              f"compile_counts {svc.compile_counts()}; recall on indexed "
              f"reads {rec}; files matched by random reads {false_hits}")
        check(all(r == 1.0 for r in rec),
              f"{backend}: recall {rec} on indexed reads, want 1.0")
    same = np.array_equal(answers["idl_probe"], answers["jnp"])
    print(f"idl_probe == jnp answers: {same} "
          f"({answers['jnp'].shape[0]} reads x {cfg.n_files} files)")
    check(same, "idl_probe and jnp answers differ")

    # The served idl_probe step is one jitted program: hash, run planner
    # and kernel. Run the last batch as the service padded it (reads of
    # one length fill the bucket's leading columns, zeros after): if that
    # call compiles nothing new, the service ran exactly this executable,
    # with interpret and the oracle off, and its text names the Mosaic
    # kernel.
    reads, _ = batches[-1]
    bucket = svc.bucket_for(cfg.read_len - cfg.k + 1)
    padded = np.zeros((BATCH, bucket + cfg.k - 1), np.uint8)
    padded[:, :cfg.read_len] = np.stack(reads)
    padded = jax.numpy.asarray(padded)
    plan = query.plan_query(cfg.idl_config(), cfg.scheme, padded.shape,
                            eng.words.shape, bit_probe=False, lane32=True)
    step = query._execute_planned
    n0 = step._cache_size()
    _, n_runs, _ = step(eng.words, padded, plan=plan, interpret=False,
                        use_ref=False)
    reused = step._cache_size() == n0
    text = step.lower(eng.words, padded, plan=plan, interpret=False,
                      use_ref=False).compile().as_text()
    custom = "tpu_custom_call" in text
    n_probes = BATCH * cfg.eta * plan.n_kmers
    print(f"idl_probe step: served executable reused {reused}, "
          f"tpu_custom_call in its compiled text {custom}; "
          f"{int(n_runs)} runs over {n_probes} probes")
    check(reused and custom, "the served idl_probe step is not the kernel")

    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"device: {dev.device_kind}; peak_bytes_in_use "
          f"{gib(peak) if peak is not None else 'not reported'}; "
          f"bytes_in_use {stats.get('bytes_in_use')}; "
          f"bytes_limit {stats.get('bytes_limit')}")
    print(f"compile: {clock.seconds:.3f} s backend compile in all")


def four_chips(args, jax, cfg, clock) -> None:
    import dataclasses

    from repro.data import genome
    from repro.index import BitSlicedIndex
    from repro.launch.serve import build_sharded_index
    from repro.serving import GeneSearchService, ServiceConfig

    devs = jax.devices()[:4]
    n_files = cfg.n_files * len(devs)     # each chip holds one config
    archive = genome.synth_archive(n_files, args.genome_len, seed=args.seed)
    t0 = time.perf_counter()
    whole = build_sharded_index(dataclasses.replace(cfg, n_files=n_files),
                                archive, devs, chunk_reads=256)
    jax.block_until_ready(whole.words)
    build_s = time.perf_counter() - t0
    # the shards in file order, each the index of its own files
    shards = [BitSlicedIndex(cfg=whole.cfg, scheme=whole.scheme,
                             n_files=cfg.n_files, words=s.data)
              for s in sorted(whole.words.addressable_shards,
                              key=lambda s: s.index[1].start)]
    shard_bytes = shards[0].state.nbytes
    for d, s in enumerate(shards):
        check(s.words.devices() == {devs[d]},
              f"shard {d} is on {s.words.devices()}, not {devs[d]}")
    print(f"build: {len(devs)} shards of {cfg.n_files} files, each "
          f"{gib(shard_bytes)} on its own device, {build_s:.3f} s with "
          f"idl_insert")
    print(f"state: {whole.words.shape} uint32 sharded "
          f"{whole.words.sharding.spec} over {len(devs)} devices, "
          f"{gib(whole.state.nbytes)}")

    batches = read_batches(archive, N_BATCHES, cfg.read_len,
                           np.random.default_rng(args.seed + 1))
    c0 = clock.seconds
    svc = GeneSearchService(whole, ServiceConfig(
        theta=cfg.theta, backend="sharded", max_batch=BATCH))
    results = [svc.search(reads) for reads, _ in batches]
    got = np.stack([r.matches for res in results for r in res])
    rec = [recall(res, truth) for res, (_, truth) in zip(results, batches)]
    print(f"serve[sharded]: {len(batches)} batches of {BATCH} reads; "
          f"backend compile {clock.seconds - c0:.3f} s; compile_counts "
          f"{svc.compile_counts()}; recall on indexed reads {rec}")
    check(all(r == 1.0 for r in rec),
          f"sharded: recall {rec} on indexed reads, want 1.0")

    per_shard = []
    for d, dev in enumerate(devs):
        with jax.default_device(dev):
            ref = GeneSearchService(shards[d], ServiceConfig(
                theta=cfg.theta, backend="jnp", max_batch=BATCH))
            per_shard.append(np.stack([r.matches for reads, _ in batches
                                       for r in ref.search(reads)]))
    want = np.concatenate(per_shard, axis=1)
    same = np.array_equal(got, want)
    print(f"sharded == per-shard jnp reference: {same} "
          f"({got.shape[0]} reads x {got.shape[1]} files)")
    check(same, "sharded answers differ from the per-shard reference")

    for d, dev in enumerate(devs):
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"device {d}: {dev.device_kind}; peak_bytes_in_use "
              f"{gib(peak) if peak is not None else 'not reported'}")
        if peak is not None:
            check(peak < 2 * shard_bytes,
                  f"device {d} peaked at {peak} bytes: more than its "
                  f"{shard_bytes}-byte shard")
    print(f"compile: {clock.seconds:.3f} s backend compile in all")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--genome-len", type=int, default=20_000,
                    help="bases per synthetic genome file")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"found no TPU: JAX's devices are {platform} devices")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX found "
             f"{len(devices)}")

    import repro.configs as configs
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    clock = CompileClock()
    cfg = configs.get("idl-genesearch").make_config()
    n_files = cfg.n_files * args.chips
    print(f"config {cfg.name}: {n_files} files, m={cfg.m}, k={cfg.k}, "
          f"t={cfg.t}, eta={cfg.eta}, L={cfg.L}, read_len={cfg.read_len}, "
          f"scheme={cfg.scheme}; reduced: genome length {args.genome_len} "
          f"bp per file (seeded synthetic archive, seed {args.seed})",
          flush=True)
    if args.chips == 1:
        one_chip(args, jax, cfg, clock)
    else:
        four_chips(args, jax, cfg, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
