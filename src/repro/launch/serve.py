"""Serving launcher: ``python -m repro.launch.serve --arch idl-genesearch``.

Builds a gene-search index over a synthetic archive and serves batched MSMT
queries through the v2 engine + service path — the runnable counterpart of
the serve cell the dry-run lowers. By default the arch's full config (for
``idl-genesearch`` the paper's 1024-file, m = 2^26 bit-sliced index, an
8 GiB state on one chip); ``--smoke`` takes its small config. Only the
genome length per file is cut, and it is printed as a reduction. The
index is built through the ``idl_insert`` ingest backend and served
through the ``idl_probe`` query backend. ``--shards N`` partitions the
index into N shard states, saves the shard-set snapshot, and serves
through a :class:`ScatterGatherRouter` whose shards are schedulers in
this process, then runs the same recall check against the merged answers.
"""

from __future__ import annotations

import argparse
import dataclasses
import tempfile
import time

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.configs as configs
from repro.data import genome
from repro.index import BitSlicedIndex, ingest, query
from repro.launch.compile_cache import use_compile_cache
from repro.serving import GeneSearchService, ServiceConfig


def build_index(cfg, archive, *, chunk_reads: int = 64) -> BitSlicedIndex:
    """The bit-sliced index over ``archive``, built through the
    ``idl_insert`` ingest backend in ``chunk_reads``-read batches of
    ``cfg.read_len``-bp windows. On a CPU host the plans execute with the
    kernel's jnp oracle (no Mosaic target there), as the service does for
    queries."""
    eng = BitSlicedIndex.build(cfg.idl_config(), cfg.scheme, cfg.n_files)
    return ingest.build_archive(eng, archive, read_len=cfg.read_len,
                                chunk_reads=chunk_reads, backend="idl_insert",
                                use_ref=jax.default_backend() == "cpu")


def build_sharded_index(cfg, archive, devices, *,
                        chunk_reads: int = 64) -> BitSlicedIndex:
    """The bit-sliced index over ``archive``, split by files over
    ``devices``: one index of ``cfg.n_files`` files whose ``words`` are
    sharded on the file-words axis over a 1-D mesh of exactly those
    devices, in their order.

    With ``F = cfg.n_files / len(devices)`` (a multiple of 32, so every
    shard holds whole words), shard ``d`` holds files ``d*F .. (d+1)*F - 1``
    and all ``m`` rows of their columns. It is built on ``devices[d]`` by
    :func:`build_index`, its files renumbered from 0, and the shards are
    joined without a copy. ``archive`` items are whatever
    :func:`repro.index.ingest.build_archive` takes; file ids in answers are
    the global ones. The ``sharded`` query backend serves the result on the
    mesh that holds it."""
    n = len(devices)
    per = cfg.n_files // n
    if per * n != cfg.n_files or per % 32:
        raise ValueError(
            f"{cfg.n_files} files do not split into {n} shards of whole "
            f"32-file words")
    parts: list = [[] for _ in range(n)]
    for pos, item in enumerate(archive):
        fid, seqs = ingest.file_sequences(item, pos)
        if not 0 <= fid < cfg.n_files:
            raise ValueError(f"file id {fid} is not in [0, {cfg.n_files})")
        parts[fid // per].extend((fid % per, s) for s in seqs)
    shard_cfg = dataclasses.replace(cfg, n_files=per)
    shards = []
    for dev, files in zip(devices, parts):
        with jax.default_device(dev):
            shards.append(build_index(shard_cfg, files,
                                      chunk_reads=chunk_reads).words)
    mesh = Mesh(np.asarray(devices), (query.MESH_AXIS,))
    words = jax.make_array_from_single_device_arrays(
        (cfg.m, cfg.n_files // 32),
        NamedSharding(mesh, P(None, query.MESH_AXIS)), shards)
    return BitSlicedIndex(cfg=cfg.idl_config(), scheme=cfg.scheme,
                          n_files=cfg.n_files, words=words)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="idl-genesearch")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the arch's small smoke config instead of "
                         "its full config")
    ap.add_argument("--files", type=int, default=None,
                    help="files in the archive (default: the config's)")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--requests", type=int, default=5)
    ap.add_argument("--shards", type=int, default=0, metavar="N",
                    help="partition the index into N shards and serve "
                         "through a scatter-gather router")
    ap.add_argument("--obs-dump", default=None, metavar="PATH",
                    help="after serving, write the observability "
                         "snapshot (metrics + traces, JSON) to PATH and "
                         "a Chrome trace_event file next to it")
    args = ap.parse_args()

    spec = configs.get(args.arch)
    if spec.family != "genesearch":
        raise SystemExit("serve launcher currently drives idl-genesearch; "
                         "LM decode is exercised via the dry-run cells")
    print(f"compile cache: {use_compile_cache()}")
    cfg = spec.make_smoke_config() if args.smoke else spec.make_config()
    n_files = cfg.n_files if args.files is None else args.files
    n_files = max(32, -(-n_files // 32) * 32)  # bit-sliced: 32/word
    if args.shards:
        # file shards split on 32-file word columns: one column per shard
        # is the floor
        n_files = max(n_files, 32 * args.shards)
    cfg = dataclasses.replace(cfg, n_files=n_files)
    genome_len = 2_000 if args.smoke else 20_000
    print(f"config {cfg.name}: {n_files} files, m={cfg.m}, k={cfg.k}, "
          f"eta={cfg.eta}, L={cfg.L}, read_len={cfg.read_len}; reduced: "
          f"genome length {genome_len} bp per file (seeded synthetic)")

    archive = genome.synth_archive(n_files=n_files, genome_len=genome_len,
                                   seed=11)
    t0 = time.perf_counter()
    eng = build_index(cfg, archive)
    jax.block_until_ready(eng.words)
    print(f"index: {n_files} files, "
          f"{eng.state.nbytes / 1e6:.1f} MB bit-sliced IndexState, built "
          f"with idl_insert in {time.perf_counter() - t0:.1f} s")

    svc_cfg = ServiceConfig(theta=cfg.theta, backend="idl_probe",
                            max_batch=args.batch)
    if args.shards:
        from repro.index import shards as shards_mod
        from repro.serving import ScatterConfig, ScatterGatherRouter
        tmp = tempfile.TemporaryDirectory(prefix="serve_shards_")
        spec, parts = shards_mod.partition_state(eng, args.shards)
        shards_mod.save_shard_set(spec, parts, f"{tmp.name}/set")
        router = ScatterGatherRouter(f"{tmp.name}/set",
                                     ScatterConfig(service=svc_cfg))
        print(f"shards: {spec.n_shards} shards over the {spec.axis!r} "
              f"axis, served by in-process schedulers (set version "
              f"{router.set_version})")
        search = router.search
    else:
        svc = GeneSearchService(eng, svc_cfg)
        search = svc.search
    rng = np.random.default_rng(0)
    lat = []
    correct = total = 0
    for r in range(args.requests):
        fids = rng.integers(0, n_files, size=args.batch)
        reads = [np.asarray(archive[int(f)].reads(cfg.read_len, 1)[0])
                 for f in fids]
        t0 = time.perf_counter()
        results = search(reads)
        lat.append(time.perf_counter() - t0)
        for fid, res in zip(fids, results):
            correct += int(int(fid) in res.file_ids)
            total += 1
    print(f"recall {correct}/{total}; "
          f"p50 latency {1e3 * float(np.median(lat)):.1f} ms "
          f"(batch={args.batch})")
    if args.obs_dump:
        from repro.obs import export as obs_export
        snap = obs_export.snapshot()   # one process = one registry
        paths = obs_export.dump(snap, args.obs_dump)
        print(f"obs: {len(snap.get('spans', ()))} spans, "
              f"{len(snap['metrics'].get('counters', {}))} counter "
              f"series -> {paths[0]} (+ {paths[1]})")
    if args.shards:
        router.close()
        tmp.cleanup()


if __name__ == "__main__":
    main()
