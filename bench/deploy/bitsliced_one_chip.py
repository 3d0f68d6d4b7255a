"""A bit-sliced COBS-style index of the whole archive on one chip.

The index is built on the device through the program's own ingest path
(``repro.launch.serve.build_index``: windows of ``index_read_len`` bases
through the ``idl_insert`` backend), and served by one
``GeneSearchService`` with the configuration's query backend.
"""

from __future__ import annotations


def gene_search_config(cfg: dict, n_files: int):
    from repro.serving.genesearch import GeneSearchConfig
    return GeneSearchConfig(
        name=cfg["name"], n_files=n_files, m=cfg["m"], k=cfg["k"],
        t=cfg["t"], L=cfg["L"], eta=cfg["eta"],
        read_len=cfg["index_read_len"], scheme=cfg["scheme"],
        theta=cfg["theta"])


def build(cfg: dict, genomes, devices, service: dict):
    import jax
    from repro.launch.serve import build_index
    from repro.serving import GeneSearchService, ServiceConfig

    with jax.default_device(devices[0]):
        eng = build_index(gene_search_config(cfg, cfg["n_files"]),
                          list(enumerate(genomes)),
                          chunk_reads=cfg["index_chunk_reads"])
        jax.block_until_ready(eng.words)
    return GeneSearchService(eng, ServiceConfig(
        theta=cfg["theta"], backend=cfg["backend"], **service))
