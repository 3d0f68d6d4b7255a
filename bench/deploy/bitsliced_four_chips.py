"""A bit-sliced COBS-style index split by files over the cell's chips.

Chip ``d`` holds every row of the columns of files ``d*F .. (d+1)*F - 1``
(``F = n_files / chips``). The program places the shards itself
(``repro.launch.serve.build_sharded_index``: each shard built on its own
chip through the ``idl_insert`` ingest path, then joined into one index
sharded over a mesh of exactly these chips), and one
``GeneSearchService`` serves it with the ``sharded`` query backend.
"""

from __future__ import annotations

from bench import harness


def build(cfg: dict, genomes, devices, service: dict):
    import jax
    from repro.launch.serve import build_sharded_index
    from repro.serving import GeneSearchService, ServiceConfig

    one_chip = harness.load_module("deploy", "bitsliced_one_chip")
    eng = build_sharded_index(
        one_chip.gene_search_config(cfg, cfg["n_files"]),
        list(enumerate(genomes)), list(devices),
        chunk_reads=cfg["index_chunk_reads"])
    jax.block_until_ready(eng.words)
    return GeneSearchService(eng, ServiceConfig(
        theta=cfg["theta"], backend=cfg["backend"], **service))
