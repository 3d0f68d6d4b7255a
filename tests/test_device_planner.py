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
from repro.kernels.idl_probe import ops as probe_ops
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
    n = want.n_runs
    assert int(got.n_runs) == n
    np.testing.assert_array_equal(got.block_ids[:n], want.block_ids[:n])
    np.testing.assert_array_equal(got.group_ids[:n], want.group_ids[:n])
    np.testing.assert_array_equal(got.offsets[:n], want.offsets[:n])
    # R = the probe count rounded up to the kernel's 8-run blocks; pad
    # runs repeat the last run's block and group and probe nothing
    r = got.block_ids.shape[0]
    assert r == -(-rows.size // 8) * 8 and got.offsets.shape == (
        r, probes_per_run)
    assert (got.block_ids[n:] == want.block_ids[n - 1]).all()
    assert (got.group_ids[n:] == want.group_ids[n - 1]).all()
    assert (got.offsets[n:] == -1).all()
    hist = np.bincount(want.run_lengths, minlength=probes_per_run + 1)
    np.testing.assert_array_equal(got.run_hist[1:], hist[1:])
    assert got.run_hist[0] == r - n
    if stream == "one_block":
        assert n == -(-rows.size // probes_per_run)   # one run a group
    if stream == "every_probe_a_run":
        assert n == rows.size


@pytest.mark.parametrize("stream", ["idl_bits", "one_block", "rh_rows"])
def test_kernel_takes_no_step_past_the_run_count(stream):
    """The interpreted kernel, fed the device plan, gathers every probe's
    row; the pad runs are poisoned (offset 0 on every lane, which would
    overwrite the last group's lanes) and change nothing, because the
    grid stops at ``n_runs``. The oracle, which runs every run, sees the
    poison."""
    rows = np.asarray(STREAMS[stream](), dtype=np.int64)
    n_rows = -(-(int(rows.max()) + 1) // RPB) * RPB
    w, c = 3, 32
    matrix = jnp.asarray(np.random.default_rng(7).integers(
        0, 2 ** 32, size=(n_rows, w), dtype=np.uint32))
    runs = probe_ops.device_plan_runs(
        jnp.asarray(rows, dtype=jnp.int32), rows_per_block=RPB,
        probes_per_run=c)
    pad = jnp.arange(runs.offsets.shape[0])[:, None] >= runs.n_runs
    poisoned = jnp.where(pad, 0, runs.offsets)
    assert int(jnp.sum(pad)) > 0

    def gather(**kw):
        return np.asarray(probe_ops._planned_gather(
            matrix, runs.block_ids, runs.group_ids, poisoned, runs.n_runs,
            rows_per_block=RPB, probes_per_run=c, n_probes=rows.size,
            row_words=w, **kw))

    want = np.asarray(matrix)[rows]
    np.testing.assert_array_equal(gather(interpret=True, use_ref=False),
                                  want)
    assert not np.array_equal(gather(interpret=False, use_ref=True), want)


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
