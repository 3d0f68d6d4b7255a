"""The comparison that decides ``correct``, on the CPU at small sizes.

A sound run of each cell is correct; the control (the reference with the
configuration's threshold guarantee broken, in the program's place) is
not; nor is a run whose served answers carry any fault a cell can have.
Run with ``PYTHONPATH=src:. python -m pytest bench/tests``.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import Future

import pytest

from bench import rehearse, synth
from bench.reference import bitsliced_bloom

SECONDS = 1.0


class Faulty:
    """The scheduler with every answer passed through ``alter(i, result)``
    as it is produced, ``i`` counting the requests."""

    def __init__(self, sched, alter):
        self._sched, self._alter, self._n = sched, alter, 0

    def submit(self, read):
        i, self._n = self._n, self._n + 1
        out: Future = Future()

        def done(fut):
            try:
                out.set_result(self._alter(i, fut.result()))
            except Exception as e:  # noqa: BLE001 - forwarded to the caller
                out.set_exception(e)

        self._sched.submit(read).add_done_callback(done)
        return out

    def __getattr__(self, name):
        return getattr(self._sched, name)


def altered(i, res):
    """One answer in 50 names one more file, as a flipped bit would."""
    if i % 50:
        return res
    extra = (max(res.file_ids, default=-1) + 1,)
    return dataclasses.replace(res, file_ids=res.file_ids + extra)


def half_left_out(i, res):
    """Every other row of the batch never computed: its answer is empty."""
    return res if i % 2 == 0 else dataclasses.replace(res, file_ids=())


def wrap(alter):
    return lambda sched, cfg: Faulty(sched, alter)


@pytest.mark.parametrize("workload", ["cobs-idl.screen", "cobs-rh.screen",
                                      "cobs-idl.interactive"])
def test_sound_run_is_correct(workload):
    result = rehearse.rehearse(workload, SECONDS)
    assert rehearse.problems(workload, result) == []


@pytest.mark.parametrize("workload", ["cobs-idl.screen",
                                      "cobs-idl.interactive"])
def test_control_is_not_correct(workload):
    result = rehearse.rehearse(workload, SECONDS, control=True)
    assert not result["correct"]
    assert result["check"]["mismatched_answers"]["value"] > 0


@pytest.mark.parametrize("alter", [altered, half_left_out],
                         ids=["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("workload", ["cobs-idl.screen",
                                      "cobs-idl.interactive"])
def test_fault_is_not_correct(workload, alter):
    result = rehearse.rehearse(workload, SECONDS, fault=wrap(alter))
    assert not result["correct"]


@pytest.mark.parametrize("scheme", ["idl", "rh"])
def test_reference_agrees_with_the_served_index(scheme):
    """The reference and the program's served answers agree at a small
    size, read for read, over every kind of read of the mix."""
    from repro.launch.serve import build_index
    from repro.serving import GeneSearchService, ServiceConfig
    from repro.serving.genesearch import GeneSearchConfig

    cfg = dict(n_files=64, m=1 << 18, k=31, t=12, L=1 << 10, eta=2,
               scheme=scheme)
    genomes = synth.archive(64, 1200, seed=2**33 + 7)
    eng = build_index(GeneSearchConfig(
        name="t", n_files=64, m=cfg["m"], k=31, t=12, L=1 << 10, eta=2,
        read_len=230, scheme=scheme), list(enumerate(genomes)),
        chunk_reads=64)
    svc = GeneSearchService(eng, ServiceConfig(backend="idl_probe",
                                               max_batch=16))
    reqs = synth.ReadStream(genomes, {
        "read_lengths": {"uniform": [100, 300]},
        "mix": {"indexed": 2, "substituted": 1, "random": 1}}, 5).take(200)
    got = [r.file_ids for r in svc.search([q.read for q in reqs])]
    archive = bitsliced_bloom.Archive(cfg, genomes)
    want = archive.answers([q.read for q in reqs], 1.0)
    assert got == want
    assert sum(len(w) > 0 for w in want) >= 90
    for q, w in zip(reqs, want):
        if q.kind == "indexed":
            assert q.file in w
