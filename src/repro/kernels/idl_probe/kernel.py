"""Pallas TPU kernels: block-resident Bloom-filter probes.

The TPU translation of the paper's cache-locality argument (DESIGN.md §2):

* The BF lives packed (uint32 words) in HBM — far too big for VMEM.
* The run planner (ops.device_plan_runs, on the device, in the kernel's
  program) run-length-encodes the probe stream by block. IDL makes runs
  long when the block covers the locality window; RH makes every probe
  its own run.
* Grid = one step per group of C probes (the output's lane block). The
  kernel copies ONE tile from HBM per run itself, several runs ahead
  (across groups) into a ring of VMEM slots, so HBM traffic = n_runs ×
  block_bytes — the quantity IDL minimizes — and the copies overlap the
  selection. The probes' rows and run flags come in through scalar
  prefetch (SMEM).
* Within a resident tile a probe's row is one lane: a dynamic lane
  rotate moves it to the probe's output lane and a select keeps it — no
  scalar loads of vector data, no unsupported vector gathers, no matmul.

:func:`probe_rows` is the served kernel (every engine's query through
``repro.index.query``); it compiles for the chip (see
``repro.kernels.lanes`` and tests/test_tpu_compile.py). :func:`probe_runs`
is the original flat-BF kernel (one grid step a run, one-hot matmuls),
run only interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import lanes


def _probe_kernel(
    block_ids_ref,   # scalar-prefetch: (R,) int32 — BF block id per run
    offsets_ref,     # (1, C) int32 — bit offsets within the block (-1 = pad)
    bf_ref,          # (block_words,) uint32 — the resident BF tile (VMEM)
    out_ref,         # (1, C) int32 — probed bit per lane (pad lanes = 1)
):
    del block_ids_ref  # consumed by the index_map only
    offsets = offsets_ref[0, :]                      # (C,)
    valid = offsets >= 0
    off = jnp.where(valid, offsets, 0)
    word_idx = (off >> 5).astype(jnp.int32)          # word within block
    bit_idx = (off & 31).astype(jnp.int32)

    words = bf_ref[:]                                # (W,) uint32
    w = words.shape[0]
    # unpack words -> (W, 32) bit image {0,1} (vector shifts, no gather)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (w, 32), 1)
    bits2d = ((words[:, None] >> shifts) & jnp.uint32(1)).astype(jnp.float32)

    c = offsets.shape[0]
    # gather via two one-hot matmuls (MXU-native)
    row_onehot = (
        word_idx[:, None] == jax.lax.broadcasted_iota(jnp.int32, (c, w), 1)
    ).astype(jnp.float32)                            # (C, W)
    picked_rows = jnp.dot(
        row_onehot, bits2d, preferred_element_type=jnp.float32
    )                                                # (C, 32)
    col_onehot = (
        bit_idx[:, None] == jax.lax.broadcasted_iota(jnp.int32, (c, 32), 1)
    ).astype(jnp.float32)
    bit = jnp.sum(picked_rows * col_onehot, axis=1)  # (C,)
    out_ref[0, :] = jnp.where(valid, bit.astype(jnp.int32), 1)


# The copy of a run's tile starts this many probes before the run is
# served, so up to this many copies are in flight (on one v5e, 63 and 127
# serve a screen batch alike; 7 is ~25% slower).
COPIES_IN_FLIGHT = 63
# Probes a step of the wait-and-copy loop and of the selection loop
# handle, unrolled: the chip then schedules several probes' scalar and
# vector work together (powers of two up to C).
FETCH_UNROLL = 8
PICK_UNROLL = 16


def _loop(n, body, carry, unroll):
    """``fori_loop(0, n, body, carry)`` unrolled ``unroll`` times (Mosaic
    lowers only a loop unrolled once or fully)."""
    unroll = min(unroll, n)
    if n % unroll:
        raise ValueError(f"an unroll of {unroll} does not divide {n}")

    def step(k, carry):
        for j in range(unroll):
            carry = body(k * unroll + j, carry)
        return carry

    return jax.lax.fori_loop(0, n // unroll, step, carry)


def _probe_rows_kernel(
    rows_ref,        # scalar-prefetch (P,) int32: the row each probe reads
    starts_ref,      # scalar-prefetch (P,) int32: 1 where a probe starts a run
    mat_hbm,         # (W, n_rows) uint32, left in HBM
    prev_hbm,        # the output as earlier calls left it (aliased, unread)
    out_ref,         # (W, C) uint32: the group's rows, probe order
    slots,           # (S, W, rows_per_block) uint32 VMEM ring of tiles
    sems,            # (S,) DMA semaphores, one a slot
    runs,            # (2,) int32 SMEM: runs begun, copies started (this call)
    *,
    n_valid: int,    # probes of this call that are real; the rest are pad
    depth: int,      # probes ahead that a copy starts
    fetch_unroll: int,
    pick_unroll: int,
):
    del prev_hbm
    g = pl.program_id(0)
    w, c = out_ref.shape
    rpb = slots.shape[2]
    ring = slots.shape[0] - 1    # S >= C + depth: a group's runs and the
                                 # copies in flight never share a slot

    def copy(row, slot):
        lo = pl.multiple_of(row - (row & (rpb - 1)), rpb)
        return pltpu.make_async_copy(mat_hbm.at[:, pl.ds(lo, rpb)],
                                     slots.at[slot], sems.at[slot])

    def starts_run(i):
        ii = jnp.minimum(i, n_valid - 1)
        return ((i < n_valid) & (starts_ref[ii] != 0)).astype(jnp.int32)

    def start_copy(j, started):
        """Start the tile copy of probe ``j``'s run if ``j`` starts one."""
        go = starts_run(j)

        @pl.when(go != 0)
        def _():
            copy(rows_ref[j], started & ring).start()

        return started + go

    @pl.when(g == 0)
    def _():
        runs[0] = 0
        runs[1] = jax.lax.fori_loop(0, min(depth, n_valid), start_copy, 0)

    # 1. wait for the tiles of this group's runs, and start the copies of
    # the runs `depth` probes ahead (into the next groups)
    def fetch(p, carry):
        begun, started = carry
        i = g * c + p
        new = starts_run(i)
        begun = begun + new

        @pl.when(new != 0)
        def _():
            copy(0, (begun - 1) & ring).wait()

        return begun, start_copy(i + depth, started)

    first = runs[0]
    begun, started = _loop(c, fetch, (first, runs[1]), fetch_unroll)
    runs[0] = begun
    runs[1] = started

    # 2. put lane `row % rpb` of each probe's run tile into its output lane
    lane = jax.lax.broadcasted_iota(jnp.int32, (w, c), 1)
    span = max(rpb, c)

    def pick(p, carry):
        run, out = carry
        i = g * c + p
        run = run + starts_run(i)
        tile = jax.lax.bitcast_convert_type(slots[(run - 1) & ring],
                                            jnp.int32)
        if span > rpb:
            tile = jnp.concatenate(
                [tile, jnp.zeros((w, span - rpb), jnp.int32)], axis=1)
        row = rows_ref[jnp.minimum(i, n_valid - 1)]
        got = pltpu.roll(tile, (p - (row & (rpb - 1))) & (span - 1), 1)
        return run, jnp.where((lane == p) & (i < n_valid), got[:, :c], out)

    _, out = _loop(c, pick, (first, jnp.zeros((w, c), jnp.int32)),
                   pick_unroll)
    out_ref[...] = jax.lax.bitcast_convert_type(out, jnp.uint32)


def _pow2(n: int) -> bool:
    return n > 0 and not n & (n - 1)


@functools.partial(
    jax.jit,
    static_argnames=("rows_per_block", "n_probes", "copies_in_flight",
                     "interpret"),
)
def probe_rows(
    matrix_t: jax.Array,     # (W, n_rows) uint32 packed bit-matrix, transposed
    rows: jax.Array,         # (G, C) int32 row each probe reads
    starts: jax.Array,       # (G, C) int32 1 where a probe starts a run
    *,
    rows_per_block: int,
    n_probes: int,
    copies_in_flight: int = COPIES_IN_FLIGHT,
    interpret: bool,
) -> jax.Array:
    """Run-coalesced row gather over an arbitrary packed bit-matrix.

    Probe ``p`` of the stream is lane ``p % C`` of group ``p // C``; its
    row's block is ``row // rows_per_block`` and its offset in the block
    ``row % rows_per_block``. A run is a stretch of probes of one block
    within one group, and ``starts`` flags each run's first probe (lane 0
    of every group among them). One grid step serves a group. It waits
    for the ``(W, rows_per_block)`` tiles of the group's runs, which the
    kernel copies from HBM itself, one a run, each started
    ``copies_in_flight`` probes ahead of its run (across groups) into a
    ring of VMEM slots; then it puts each probe's lane of its run's tile
    into its output lane.
    Returns ``(W, G * C)`` uint32: the W-word row of every probe, in
    probe order, transposed like the matrix; the pad lanes past
    ``n_probes`` are never read and come out 0.
    """
    g, c = rows.shape
    if starts.shape != (g, c):
        raise ValueError(f"starts shape {starts.shape} != {(g, c)}")
    if not g * c - c < n_probes <= g * c:
        raise ValueError(f"{n_probes} probes do not fill {g} groups of {c}")
    w, n_rows = matrix_t.shape
    if not (_pow2(rows_per_block) and _pow2(c)):
        raise ValueError(f"rows_per_block={rows_per_block} and C={c} must "
                         f"be powers of two")
    if copies_in_flight < 1:
        raise ValueError("a copy must start before its run is served")
    if n_rows % rows_per_block:
        raise ValueError(
            f"n_rows={n_rows} must be a multiple of rows_per_block="
            f"{rows_per_block}"
        )

    # Mosaic copies whole sublane tiles of the matrix: 1, 2, 4 or a
    # multiple of 8 rows of u32. Other widths are padded up to one (a copy
    # of the matrix a call).
    wide = w if w in (1, 2, 4) else -(-w // 8) * 8
    if wide != w:
        matrix_t = jnp.pad(matrix_t, ((0, wide - w), (0, 0)))
    rows = rows.reshape(-1)
    starts = starts.reshape(-1)
    out = jnp.zeros((wide, g * c), jnp.uint32)
    n_slots = 1 << (c + copies_in_flight - 1).bit_length()
    for part in lanes.chunks(g * c):
        g0 = part.start // c
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=((part.stop - part.start) // c,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((wide, c),
                                   lambda i, *_, g0=g0: (0, i + g0)),
            scratch_shapes=[
                pltpu.VMEM((n_slots, wide, rows_per_block),
                           jnp.uint32),
                pltpu.SemaphoreType.DMA((n_slots,)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        )
        # the interpreter runs the loop op by op: unrolling it there only
        # grows what it traces
        body = functools.partial(
            _probe_rows_kernel, depth=copies_in_flight,
            fetch_unroll=1 if interpret else FETCH_UNROLL,
            pick_unroll=1 if interpret else PICK_UNROLL,
            n_valid=min(n_probes, part.stop) - part.start)
        with lanes.x32():
            out = pl.pallas_call(
                body,
                grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct(out.shape, jnp.uint32),
                input_output_aliases={3: 0},
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",)),
                interpret=interpret,
                name="probe_rows",
            )(rows[part], starts[part], matrix_t, out)
    return out[:w]


@functools.partial(
    jax.jit, static_argnames=("block_words", "probes_per_run", "interpret")
)
def probe_runs(
    bf_words: jax.Array,     # (n_words,) uint32 packed BF
    block_ids: jax.Array,    # (R,) int32
    offsets: jax.Array,      # (R, C) int32, -1 padded
    *,
    block_words: int,
    probes_per_run: int,
    interpret: bool,
) -> jax.Array:
    """Returns (R, C) int32 probed bits (pad lanes read as 1)."""
    r = block_ids.shape[0]
    c = probes_per_run
    if offsets.shape != (r, c):
        raise ValueError(f"offsets shape {offsets.shape} != {(r, c)}")
    if bf_words.shape[0] % block_words:
        raise ValueError("bf length must be a multiple of block_words")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((1, c), lambda i, bid: (i, 0)),
            pl.BlockSpec((block_words,), lambda i, bid: (bid[i],)),
        ],
        out_specs=pl.BlockSpec((1, c), lambda i, bid: (i, 0)),
    )
    return pl.pallas_call(
        _probe_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.int32),
        interpret=interpret,
    )(block_ids, offsets, bf_words)
