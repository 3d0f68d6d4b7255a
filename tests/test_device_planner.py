"""The ``idl_probe`` backend plans its runs on the device.

``device_plan_runs`` must make the numpy planner's plan, run for run, on
every kind of stream the engines send it; and the served step (hash ->
plan -> kernel -> AND over eta) must run without the host waiting on the
device or sending it a plan, while the locality counters still read the
numpy plan's values once drained.
"""

import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import idl
from repro.index import query
from repro.index.engines import BitSlicedIndex
from repro.kernels.idl_probe import kernel, ops as probe_ops
from repro.obs import metrics as obs_metrics

CFG = idl.IDLConfig(k=31, t=16, L=1 << 10, eta=2, m=1 << 16)
RPB = 32                                  # rows per block of the streams


def _hash_rows(scheme: str, bit_probe: bool, n_reads: int = 3,
               read_len: int = 120) -> np.ndarray:
    """The (B·eta·n_kmers,) row stream a query of random reads probes."""
    reads = np.random.default_rng(11).integers(
        0, 4, size=(n_reads, read_len), dtype=np.uint8)
    n_rows = CFG.m // 32 if bit_probe else CFG.m
    plan = query.plan_query(CFG, scheme, reads.shape, (n_rows, 1),
                            bit_probe=bit_probe, rows_per_block=RPB)
    locs = np.asarray(plan.locations(jnp.asarray(reads)))
    return ((locs >> 5) if bit_probe else locs).reshape(-1)


STREAMS = {
    "idl_rows": lambda: _hash_rows("idl", bit_probe=False),
    "rh_rows": lambda: _hash_rows("rh", bit_probe=False),
    "idl_bits": lambda: _hash_rows("idl", bit_probe=True),
    "rh_bits": lambda: _hash_rows("rh", bit_probe=True),
    # 3 * 2 * 79 = 474 probes: a multiple of neither 32 nor 128
    "ragged": lambda: _hash_rows("idl", bit_probe=False, read_len=109),
    "one_block": lambda: np.arange(600) % RPB + 7 * RPB,
    "every_probe_a_run": lambda: (np.arange(600) % 2) * RPB + 3,
}


@pytest.mark.parametrize("probes_per_run", [32, 128])
@pytest.mark.parametrize("stream", sorted(STREAMS))
def test_device_plan_equals_numpy_plan(stream, probes_per_run):
    rows = np.asarray(STREAMS[stream](), dtype=np.int64)
    want = probe_ops.plan_probe_runs(rows, block_bits=RPB,
                                     probes_per_run=probes_per_run)
    got = jax.device_get(probe_ops.device_plan_runs(
        jnp.asarray(rows, dtype=jnp.int32), rows_per_block=RPB,
        probes_per_run=probes_per_run))
    n, size = want.n_runs, rows.size
    assert int(got.n_runs) == n
    # (G, C): the stream in groups of C; the lanes past its end are 0 and
    # start nothing
    g = -(-size // probes_per_run)
    assert got.rows.shape == got.starts.shape == (g, probes_per_run)
    np.testing.assert_array_equal(got.rows.reshape(-1)[:size], rows)
    np.testing.assert_array_equal(got.starts.reshape(-1)[:size],
                                  want.starts)
    assert not got.rows.reshape(-1)[size:].any()
    assert not got.starts.reshape(-1)[size:].any()
    hist = np.bincount(want.run_lengths, minlength=probes_per_run + 1)
    np.testing.assert_array_equal(got.run_hist[1:], hist[1:])
    assert got.run_hist[0] == 0
    if stream == "one_block":
        assert n == -(-size // probes_per_run)        # one run a group
    if stream == "every_probe_a_run":
        assert n == size


@pytest.mark.parametrize("stream", ["idl_bits", "one_block", "rh_rows"])
def test_kernel_takes_no_step_past_the_run_count(stream):
    """The interpreted kernel, fed the device plan, gathers every probe's
    row; the pad lanes of the last group are poisoned (each starts a run
    of another block) and change nothing, because the kernel stops at
    the stream's end: it copies no tile for them and reads none into
    them. The same kernel told that the pad lanes are probes reads the
    poison."""
    rows = np.asarray(STREAMS[stream](), dtype=np.int64)
    n_rows = -(-(int(rows.max()) + 1) // RPB) * RPB
    w, c = 3, 32
    matrix = jnp.asarray(np.random.default_rng(7).integers(
        0, 2 ** 32, size=(n_rows, w), dtype=np.uint32))
    runs = probe_ops.device_plan_runs(
        jnp.asarray(rows, dtype=jnp.int32), rows_per_block=RPB,
        probes_per_run=c)
    pad = (jnp.arange(runs.rows.size) >= rows.size).reshape(runs.rows.shape)
    poison = int((rows[-1] // RPB + 1) % (n_rows // RPB) * RPB + 1)
    poisoned = (jnp.where(pad, poison, runs.rows),
                jnp.where(pad, 1, runs.starts))
    assert int(jnp.sum(pad)) > 0

    def gather(n_probes):
        return np.asarray(kernel.probe_rows(
            matrix.T, *poisoned, rows_per_block=RPB, n_probes=n_probes,
            interpret=True)).T

    want = np.asarray(matrix)[rows]
    got = gather(rows.size)
    np.testing.assert_array_equal(got[:rows.size], want)
    assert not got[rows.size:].any()
    assert (gather(runs.rows.size)[rows.size:]
            == np.asarray(matrix)[poison]).all()


@jax.jit
def _held(reads, a):
    """``reads`` unchanged, once a few seconds of matmuls on ``a`` are
    done: the result stays unready on the device while the host goes on."""
    for _ in range(8):
        a = jnp.tanh(a @ a)
    return reads + (jnp.sum(a) * 0).astype(reads.dtype)


def _counts(snap) -> dict:
    where = {"scheme": "idl", "op": "query"}
    out = {name: obs_metrics.counter_total(snap, f"locality.{name}", where)
           for name in ("planned_tile_bytes", "probe_runs", "probes",
                        "batches")}
    (hist,) = [h for lk, h in snap["hists"]["locality.run_length"].items()
               if {"scheme=idl", "op=query"} <= set(lk.split(","))]
    out["run_length"] = (hist["count"], hist["sum"])
    return out


def test_served_step_makes_no_host_round_trip():
    """Planned execution dispatches hash, plan and kernel with no
    implicit upload and without waiting for the device: while the reads
    themselves are still being computed, ``execute`` returns. The
    locality counters then read the numpy plan's values."""
    reads = jnp.asarray(np.random.default_rng(5).integers(
        0, 4, size=(4, 120), dtype=np.uint8))
    eng = BitSlicedIndex.build(CFG, "idl", n_files=40).insert_batch(
        reads[:3], np.asarray([0, 9, 39]))
    plan = query.plan_query(CFG, "idl", reads.shape, eng.words.shape,
                            bit_probe=False, lane32=True)
    a = jnp.full((1500, 1500), 1.0 / 1500, jnp.float32)
    want = jax.block_until_ready(plan.execute(
        eng.words, _held(reads, a), backend="idl_probe", use_ref=True))
    query.drain_locality()
    obs.reset()

    held = _held(reads, a)
    with jax.transfer_guard("disallow"):
        out = plan.execute(eng.words, held, backend="idl_probe",
                           use_ref=True)
    assert not held.is_ready(), "execute waited for the device"
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(plan.execute(eng.words, reads, backend="jnp")))

    assert query.drain_locality() == 1
    rplan, _ = plan.plan_runs(reads)
    assert _counts(obs_metrics.DEFAULT.snapshot()) == {
        "planned_tile_bytes": plan.run_dma_bytes(rplan),
        "probe_runs": rplan.n_runs,
        "probes": rplan.n_probes,
        "batches": 1,
        "run_length": (rplan.n_runs, rplan.n_probes),
    }
    obs.reset()


def test_locality_queue_loses_no_batch_under_threads():
    """Dispatching threads queue, draining threads take off: every batch is
    recorded exactly once."""
    reads = jnp.asarray(np.random.default_rng(6).integers(
        0, 4, size=(2, 64), dtype=np.uint8))
    eng = BitSlicedIndex.build(CFG, "idl", n_files=40).insert_batch(
        reads, np.asarray([1, 2]))
    plan = query.plan_query(CFG, "idl", reads.shape, eng.words.shape,
                            bit_probe=False, lane32=True)
    jax.block_until_ready(plan.execute(eng.words, reads,
                                       backend="idl_probe", use_ref=True))
    query.drain_locality()
    obs.reset()
    n_threads, n_batches = 12, 6

    def worker():
        for _ in range(n_batches):
            jax.block_until_ready(plan.execute(
                eng.words, reads, backend="idl_probe", use_ref=True))
            query.drain_locality()

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(prev)
    assert not any(t.is_alive() for t in threads)
    query.drain_locality()
    rplan, _ = plan.plan_runs(reads)
    counts = _counts(obs_metrics.DEFAULT.snapshot())
    total = n_threads * n_batches
    assert counts["batches"] == total
    assert counts["probes"] == total * rplan.n_probes
    assert counts["probe_runs"] == total * rplan.n_runs
    obs.reset()


@pytest.mark.parametrize("scheme", ["idl", "rh"])
def test_tile_bytes_per_probe_counts_the_kernels_copies(scheme):
    """The locality counters of a served batch count the tiles the kernel
    copies: ``planned_tile_bytes / probes`` is the numpy plan's
    ``n_runs × 128 × W × 4 / probes`` at the served 128-row block."""
    reads = jnp.asarray(np.random.default_rng(8).integers(
        0, 4, size=(3, 140), dtype=np.uint8))
    eng = BitSlicedIndex.build(CFG, scheme, n_files=96).insert_batch(
        reads[:2], np.asarray([4, 70]))
    plan = query.plan_query(CFG, scheme, reads.shape, eng.words.shape,
                            bit_probe=False, lane32=True)
    assert plan.rows_per_block == 128
    w = eng.words.shape[1]
    query.drain_locality()
    obs.reset()
    got = plan.execute(eng.words, reads, backend="idl_probe")
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(plan.execute(eng.words, reads, backend="jnp")))
    assert query.drain_locality() == 1
    locs = np.asarray(plan.locations(reads)).reshape(-1)
    want = probe_ops.plan_probe_runs(locs, block_bits=128,
                                     probes_per_run=plan.probes_per_run)
    snap = obs_metrics.DEFAULT.snapshot()
    counts = {name: obs_metrics.counter_total(
        snap, f"locality.{name}", {"scheme": scheme, "op": "query"})
        for name in ("planned_tile_bytes", "probes")}
    assert counts["probes"] == want.n_probes == locs.size
    assert counts["planned_tile_bytes"] == want.n_runs * 128 * w * 4
    assert (counts["planned_tile_bytes"] / counts["probes"]
            == want.n_runs * 128 * w * 4 / want.n_probes)
    obs.reset()
