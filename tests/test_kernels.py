"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py
oracles, all in interpret=True mode (kernel body executes on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bloom, idl, minhash
from repro.kernels.idl_insert import ops as ins_ops
from repro.kernels.idl_probe import kernel as kernel_mod
from repro.kernels.idl_probe import ops as probe_ops, ref as probe_ref
from repro.kernels.window_min import kernel as wm_kernel


class TestWindowMinKernel:
    @pytest.mark.parametrize("n,w,tile", [
        (1000, 16, 256), (4096, 16, 512), (5000, 7, 1024),
        (300, 2, 128), (2048, 16, 2048), (1025, 12, 256),
    ])
    def test_shapes_sweep(self, rng, n, w, tile):
        a = jnp.asarray(rng.integers(0, 2**32, size=n, dtype=np.uint32))
        got = wm_kernel.window_min(a, w=w, tile=tile, interpret=True)
        want = minhash.sliding_window_min(a, w)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("dtype", [np.uint32, np.int32, np.float32])
    def test_dtypes(self, rng, dtype):
        if np.issubdtype(dtype, np.floating):
            a = jnp.asarray(rng.normal(size=777).astype(dtype))
        else:
            a = jnp.asarray(rng.integers(0, 1 << 30, size=777).astype(dtype))
        got = wm_kernel.window_min(a, w=9, tile=128, interpret=True)
        want = minhash.sliding_window_min(a, 9)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def _row_stream(rng, kind: str, n_rows: int, rpb: int) -> np.ndarray:
    """Row probes of one kind, none a multiple of 128 or 32 long."""
    if kind == "mixed":
        rows = rng.integers(0, n_rows, size=(3, 97))
        rows[1].sort()  # one stream with long block runs, two scattered
        return rows
    idx = np.arange(300)
    if kind == "one_long_run":
        return (5 * rpb + idx % rpb) % n_rows
    assert kind == "every_probe_a_run"
    return (idx % 2 * rpb + idx % rpb) % n_rows


def _group_form(rows: np.ndarray, starts: np.ndarray, c: int):
    """``(G, C)`` rows and run flags of a flat stream, as the kernel takes
    them: lanes past the stream are 0."""
    pad = -rows.size % c
    return (jnp.asarray(np.pad(rows, (0, pad)).reshape(-1, c), jnp.int32),
            jnp.asarray(np.pad(starts, (0, pad)).reshape(-1, c), jnp.int32))


def _build_bf(rng, cfg, n=1500):
    codes = jnp.asarray(rng.integers(0, 4, size=n, dtype=np.uint8))
    bf = bloom.BloomFilter(cfg=cfg, scheme="idl").insert_sequence(codes)
    return codes, bf, bloom.pack_bits(bf.bits)


class TestProbeKernel:
    @pytest.mark.parametrize("L,eta,m,C", [
        (1 << 12, 4, 1 << 20, 128),
        (1 << 10, 2, 1 << 18, 64),
        (1 << 14, 8, 1 << 22, 256),
    ])
    def test_sweep_vs_ref(self, rng, L, eta, m, C):
        cfg = idl.IDLConfig(k=31, t=16, L=L, eta=eta, m=m)
        codes, bf, words = _build_bf(rng, cfg)
        locs = np.asarray(idl.idl_locations_rolling(cfg, codes))
        plan = probe_ops.plan_probe_runs(locs, block_bits=L, probes_per_run=C)
        got = probe_ops.probe_membership(words, plan, interpret=True)
        want = probe_ops.probe_membership(words, plan, interpret=False,
                                          use_ref=True)
        direct = bloom.query_packed(words, jnp.asarray(locs.astype(np.uint32)))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(direct))
        assert bool(jnp.all(got))  # inserted -> all present

    def test_negative_queries(self, rng):
        cfg = idl.IDLConfig(k=31, t=16, L=1 << 12, eta=4, m=1 << 20)
        codes, bf, words = _build_bf(rng, cfg)
        neg = jnp.asarray(rng.integers(0, 4, size=800, dtype=np.uint8))
        locs = np.asarray(idl.idl_locations_rolling(cfg, neg))
        plan = probe_ops.plan_probe_runs(locs, block_bits=cfg.L)
        got = probe_ops.probe_membership(words, plan, interpret=True)
        direct = bloom.query_packed(words, jnp.asarray(locs.astype(np.uint32)))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(direct))

    @pytest.mark.parametrize("n_rows,w,rpb,C,stream", [
        (256, 3, 16, 32, "mixed"),      # odd word count (COBS group slice)
        (1 << 12, 1, 64, 128, "mixed"), # flat packed BF as a (m/32, 1) matrix
        (512, 8, 8, 64, "mixed"),       # RAMBO-transpose-like wide rows
        # the served geometry: one 128-row lane tile a block
        (1 << 12, 1, 128, 128, "mixed"),
        (1 << 12, 3, 128, 128, "mixed"),
        (1 << 14, 32, 128, 128, "mixed"),
        (1 << 12, 32, 128, 128, "one_long_run"),
        (1 << 12, 32, 128, 128, "every_probe_a_run"),
        (1 << 12, 32, 128, 32, "every_probe_a_run"),
    ])
    def test_probe_rows_sweep_vs_ref(self, rng, n_rows, w, rpb, C, stream):
        """The generalized (rows, W) row-gather kernel: Pallas == ref ==
        direct numpy indexing, in probe order, for arbitrary matrices.
        Every stream is ragged (its last group part pad); ``mixed`` has one
        part with long block runs and two scattered, ``one_long_run`` fills
        whole groups from one block, and ``every_probe_a_run`` makes more
        tile copies than the kernel's ring has slots."""
        matrix = jnp.asarray(
            rng.integers(0, 2 ** 32, size=(n_rows, w), dtype=np.uint32))
        rows = _row_stream(rng, stream, n_rows, rpb)
        plan = probe_ops.plan_probe_runs(rows, block_bits=rpb,
                                         probes_per_run=C)
        group_rows, starts = _group_form(rows.reshape(-1), plan.starts, C)

        def gather(**kw):
            return probe_ops._planned_gather(
                matrix, group_rows, starts, rows_per_block=rpb,
                n_probes=plan.n_probes, row_words=w, **kw)

        got = gather(interpret=True, use_ref=False)
        got_ref = gather(interpret=False, use_ref=True)
        want = np.asarray(matrix)[rows.reshape(-1)]
        np.testing.assert_array_equal(np.asarray(got), want)
        np.testing.assert_array_equal(np.asarray(got_ref), want)

    @pytest.mark.parametrize("copies_in_flight", [1, 3, 15])
    def test_probe_rows_follows_the_run_flags(self, rng, copies_in_flight):
        """Each probe reads its run's tile, the block of the run's first
        probe, however few copies the ring holds: with extra starts in
        runs and starts left out at block changes, the kernel still equals
        the oracle, and past ``n_probes`` it reads nothing (0)."""
        n_rows, w, rpb, c = 1 << 11, 3, 128, 32
        matrix = jnp.asarray(
            rng.integers(0, 2 ** 32, size=(n_rows, w), dtype=np.uint32))
        rows = _row_stream(rng, "mixed", n_rows, rpb).reshape(-1)
        flags = rng.random(rows.size) < 0.3
        flags[::c] = True
        group_rows, starts = _group_form(rows, flags, c)
        got = kernel_mod.probe_rows(
            matrix.T, group_rows, starts, rows_per_block=rpb,
            n_probes=rows.size, copies_in_flight=copies_in_flight,
            interpret=True)
        want = probe_ref.probe_rows_ref(matrix, group_rows, starts,
                                        rows_per_block=rpb,
                                        n_probes=rows.size)
        np.testing.assert_array_equal(np.asarray(got).T, np.asarray(want))
        assert not np.asarray(want)[rows.size:].any()
        assert not np.array_equal(np.asarray(want)[:rows.size],
                                  np.asarray(matrix)[rows])

    def test_dma_savings_idl_vs_rh(self, rng):
        """The kernel's DMA count IS the paper's cache-miss metric on TPU:
        IDL's plan must need far fewer block DMAs than RH's."""
        cfg = idl.IDLConfig(k=31, t=16, L=1 << 15, eta=4, m=1 << 26)
        codes = jnp.asarray(rng.integers(0, 4, size=5000, dtype=np.uint8))
        locs_idl = np.asarray(idl.idl_locations_rolling(cfg, codes))
        locs_rh = np.asarray(idl.rh_locations_rolling(cfg, codes))
        p_idl = probe_ops.plan_probe_runs(locs_idl, cfg.L)
        p_rh = probe_ops.plan_probe_runs(locs_rh, cfg.L)
        assert p_rh.n_runs > 4 * p_idl.n_runs


class TestInsertKernel:
    @pytest.mark.parametrize("L,eta,m,C", [
        (1 << 12, 4, 1 << 20, 128),
        (1 << 10, 2, 1 << 18, 32),
    ])
    def test_sweep_vs_direct(self, rng, L, eta, m, C):
        cfg = idl.IDLConfig(k=31, t=16, L=L, eta=eta, m=m)
        codes = jnp.asarray(rng.integers(0, 4, size=1200, dtype=np.uint8))
        locs = np.asarray(idl.idl_locations_rolling(cfg, codes))
        plan = ins_ops.plan_insert_rounds(locs, block_bits=L, inserts_per_round=C)
        w0 = jnp.zeros((m // 32,), dtype=jnp.uint32)
        got = ins_ops.insert_with_plan(w0, plan, interpret=True)
        ref = ins_ops.insert_with_plan(w0, plan, interpret=False,
                                       use_ref=True)
        direct = bloom.pack_bits(
            bloom.BloomFilter(cfg=cfg, scheme="idl").insert_sequence(codes).bits)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(direct))

    def test_rounds_have_unique_blocks(self, rng):
        cfg = idl.IDLConfig(k=31, t=16, L=1 << 10, eta=4, m=1 << 18)
        codes = jnp.asarray(rng.integers(0, 4, size=3000, dtype=np.uint8))
        locs = np.asarray(idl.idl_locations_rolling(cfg, codes))
        plan = ins_ops.plan_insert_rounds(locs, cfg.L, 64)
        for bids, _ in plan.rounds:
            assert len(np.unique(bids)) == len(bids)


def _set_bits_oracle(matrix: np.ndarray, flat_bits: np.ndarray) -> np.ndarray:
    out = matrix.copy().reshape(-1)
    for pos in flat_bits.reshape(-1):
        if pos >= 0:
            out[pos // 32] |= np.uint32(1) << np.uint32(pos % 32)
    return out.reshape(matrix.shape)


class TestInsertRunsKernel:
    """The generalized single-launch (rows, W) scatter-OR kernel: Pallas ==
    ref == direct numpy bit-setting, for arbitrary matrices (the ingest
    layer's idl_insert backend)."""

    @pytest.mark.parametrize("n_rows,w,rpb,C", [
        (256, 3, 16, 32),      # odd word count (COBS group slice)
        (1 << 12, 1, 64, 128), # flat packed BF as a (m/32, 1) matrix
        (512, 8, 8, 64),       # RAMBO-stack-like wide rows
        (96, 5, 1, 16),        # single-row tiles
    ])
    def test_sweep_vs_ref_and_oracle(self, rng, n_rows, w, rpb, C):
        matrix = jnp.asarray(
            rng.integers(0, 2 ** 32, size=(n_rows, w), dtype=np.uint32))
        flat = rng.integers(0, n_rows * w * 32, size=500, dtype=np.int64)
        flat[:40] = flat[40:80]  # force duplicates through the dedup path
        plan = ins_ops.plan_insert_runs(flat, block_bits=rpb * w * 32,
                                        inserts_per_run=C)
        want = _set_bits_oracle(np.asarray(matrix), flat)
        # the destination is donated — hand each executor its own copy
        got = ins_ops.insert_planned(jnp.array(matrix), plan, interpret=True)
        got_ref = ins_ops.insert_planned(jnp.array(matrix), plan,
                                         interpret=False, use_ref=True)
        np.testing.assert_array_equal(np.asarray(got), want)
        np.testing.assert_array_equal(np.asarray(got_ref), want)

    def test_plan_invariants(self, rng):
        flat = rng.integers(0, 1 << 16, size=900, dtype=np.int64)
        plan = ins_ops.plan_insert_runs(flat, block_bits=1 << 10,
                                        inserts_per_run=32)
        # runs are nondecreasing (consecutive tile revisits) and count the
        # sorted unique touched blocks; the run axis is pow2-padded with
        # runs of the last block, so the executor's compile cache stays
        # small
        assert (np.diff(plan.block_ids) >= 0).all()
        np.testing.assert_array_equal(
            np.unique(plan.block_ids[:plan.n_runs]),
            np.unique(flat // (1 << 10)))
        assert plan.n_tiles == len(np.unique(flat // (1 << 10)))
        r_pad = plan.block_ids.shape[0]
        assert r_pad & (r_pad - 1) == 0 and r_pad % 8 == 0
        assert (plan.block_ids[plan.n_runs:] == plan.block_ids[
            plan.n_runs - 1]).all()
        # offsets are deduplicated: global (block, offset) pairs unique
        valid = plan.offsets >= 0
        pairs = (plan.block_ids[:, None].astype(np.int64) * (1 << 10)
                 + plan.offsets)[valid]
        assert len(np.unique(pairs)) == len(pairs) == plan.n_locs
        # pow2-padded run count; pad runs are all-pad no-ops
        assert plan.block_ids.shape[0] >= plan.n_runs
        assert (plan.offsets[plan.n_runs:] == -1).all()

    def test_empty_and_masked_plans(self):
        m = jnp.asarray(np.arange(8, dtype=np.uint32).reshape(4, 2))
        assert ins_ops.plan_insert_runs(
            np.asarray([-1, -1]), block_bits=64) is None
        out = ins_ops.insert_planned(m, None, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(m))
