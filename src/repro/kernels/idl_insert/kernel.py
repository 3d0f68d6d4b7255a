"""Pallas TPU kernel: block-resident scatter-OR insert.

Indexing-side twin of idl_probe, in two generations:

* :func:`insert_round` — the original flat-BF kernel, run only
  interpreted. The host planner groups insert locations by BF block such
  that **each block appears at most once per call** (rounds, see
  ops.plan_insert_rounds) — no read-after-write hazards, but one launch
  per round.
* :func:`insert_runs` — the served kernel behind ``repro.index.ingest``:
  inserts into an arbitrary packed ``(rows, W)`` bit-matrix, planned as
  **sorted, deduplicated runs** (ops.plan_insert_runs). Runs of the same
  tile are consecutive, so the output tile is *revisited*: the first run
  of a tile initializes it from the resident input tile, the following
  runs OR into it while it stays in VMEM, and Pallas flushes it exactly
  once when the next tile begins. The output aliases the matrix, so the
  update is in place: one tile read + one tile write per *touched block*
  for the whole batch, however many runs land in it, and no other HBM.

Either way each grid step ORs in the bit-image of up to C insertions,
built MXU-natively from one-hot matmuls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import lanes


def _insert_kernel(
    block_ids_ref,   # scalar-prefetch (R,) int32
    offsets_ref,     # (1, C) int32, -1 padded
    bf_ref,          # (block_words,) uint32 resident tile
    out_ref,         # (1, block_words) uint32 updated tile
):
    del block_ids_ref
    words = bf_ref[:]
    out_ref[0, :] = words | _bit_image(offsets_ref[0, :], words.shape[0])


@functools.partial(
    jax.jit, static_argnames=("block_words", "inserts_per_round", "interpret")
)
def insert_round(
    bf_words: jax.Array,     # (n_words,) uint32
    block_ids: jax.Array,    # (R,) int32 — unique per call (planner guarantee)
    offsets: jax.Array,      # (R, C) int32, -1 padded
    *,
    block_words: int,
    inserts_per_round: int,
    interpret: bool,
) -> jax.Array:
    """Returns (R, block_words) updated tiles for the given blocks."""
    r = block_ids.shape[0]
    c = inserts_per_round
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((1, c), lambda i, bid: (i, 0)),
            pl.BlockSpec((block_words,), lambda i, bid: (bid[i],)),
        ],
        out_specs=pl.BlockSpec((1, block_words), lambda i, bid: (i, 0)),
    )
    return pl.pallas_call(
        _insert_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, block_words), jnp.uint32),
        interpret=interpret,
    )(block_ids, offsets, bf_words)


def _bit_image(offsets: jax.Array, n_words: int) -> jax.Array:
    """(C,) -1-padded bit offsets -> (n_words,) uint32 OR-image (MXU path)."""
    valid = offsets >= 0
    off = jnp.where(valid, offsets, 0)
    word_idx = (off >> 5).astype(jnp.int32)
    bit_idx = (off & 31).astype(jnp.int32)
    c = offsets.shape[0]
    row_onehot = (
        (word_idx[:, None]
         == jax.lax.broadcasted_iota(jnp.int32, (c, n_words), 1))
        & valid[:, None]
    ).astype(jnp.float32)                            # (C, NW)
    col_onehot = (
        bit_idx[:, None] == jax.lax.broadcasted_iota(jnp.int32, (c, 32), 1)
    ).astype(jnp.float32)                            # (C, 32)
    counts = jnp.dot(
        row_onehot.T, col_onehot, preferred_element_type=jnp.float32
    )                                                # (NW, 32)
    add_bits = (counts > 0.5).astype(jnp.uint32)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (n_words, 32), 1)
    return jnp.sum(add_bits << shifts, axis=1).astype(jnp.uint32)


def _insert_runs_kernel(
    block_ids_ref,   # scalar-prefetch (R,) int32 — matrix row-block per run
    rows_ref,        # (8, C) int32 row within the tile of 8 runs, -1 padded
    cols_ref,        # (8, C) int32 bit column within the row (word*32 + bit)
    mat_ref,         # (W, rows_per_block) uint32 resident input tile
    out_ref,         # (W, rows_per_block) uint32 the same tile, updated
):
    i = pl.program_id(0)
    w, rpb = mat_ref.shape
    row = lanes.run_lanes(rows_ref, i)                          # (1, C)
    col = lanes.run_lanes(cols_ref, i)                          # (1, C)
    c = row.shape[1]
    # bit image of the run: lane c sets bit col&31 of word col>>5 in row
    # row[c]; deduplicated targets are disjoint bits, so sums are ORs
    word = jax.lax.broadcasted_iota(jnp.int32, (w, c), 0)
    val = jnp.where(word == (col >> 5), 1 << (col & 31), 0)    # (W, C)
    hit = (jax.lax.broadcasted_iota(jnp.int32, (rpb, c), 0) == row
           ).astype(jnp.bfloat16)                               # (RPB, C)
    img = jax.lax.bitcast_convert_type(
        lanes.planes_dot(val, hit, rhs_contract=1), jnp.uint32)  # (W, RPB)

    # Runs are sorted by tile, so revisits are consecutive: initialize the
    # output tile on its first run, OR into the resident copy afterwards.
    prev_run = jnp.maximum(i - 1, 0)
    first = (i == 0) | (block_ids_ref[i] != block_ids_ref[prev_run])

    @pl.when(first)
    def _():
        out_ref[...] = mat_ref[...] | img

    @pl.when(jnp.logical_not(first))
    def _():
        out_ref[...] = out_ref[...] | img


@functools.partial(
    jax.jit,
    static_argnames=("rows_per_block", "inserts_per_run", "interpret"),
    donate_argnums=(0,),
)
def insert_runs(
    matrix_t: jax.Array,     # (W, n_rows) uint32 packed bit-matrix, transposed
    block_ids: jax.Array,    # (R,) int32 row-block id per run (nondecreasing)
    rows: jax.Array,         # (R, C) int32 row within the tile, -1 padded
    cols: jax.Array,         # (R, C) int32 bit column within the row
    *,
    rows_per_block: int,
    inserts_per_run: int,
    interpret: bool,
) -> jax.Array:
    """Run-coalesced scatter-OR into an arbitrary packed bit-matrix, in place.

    One grid step per run; one ``(W, rows_per_block)`` tile read and one
    write per *touched block* (consecutive runs of a block accumulate in
    the resident output tile). The output aliases the (donated) matrix, so
    untouched tiles are never moved and no second copy of the matrix
    exists. ``R`` must be a multiple of 8.
    """
    r = block_ids.shape[0]
    c = inserts_per_run
    if rows.shape != (r, c) or cols.shape != (r, c):
        raise ValueError(f"rows/cols shapes {rows.shape}/{cols.shape} "
                         f"!= {(r, c)}")
    if r % lanes.RUNS_PER_BLOCK:
        raise ValueError(f"run count {r} must be a multiple of "
                         f"{lanes.RUNS_PER_BLOCK}")
    w, n_rows = matrix_t.shape
    if n_rows % rows_per_block:
        raise ValueError(
            f"n_rows={n_rows} must be a multiple of rows_per_block="
            f"{rows_per_block}"
        )

    lane_spec = pl.BlockSpec((lanes.RUNS_PER_BLOCK, c),
                             lambda i, bid: (i // lanes.RUNS_PER_BLOCK, 0))
    tile_spec = pl.BlockSpec((w, rows_per_block), lambda i, bid: (0, bid[i]))
    for part in lanes.chunks(r):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(part.stop - part.start,),
            in_specs=[lane_spec, lane_spec, tile_spec],
            out_specs=tile_spec,
        )
        with lanes.x32():
            matrix_t = pl.pallas_call(
                _insert_runs_kernel,
                grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct(matrix_t.shape, jnp.uint32),
                input_output_aliases={3: 0},
                interpret=interpret,
            )(block_ids[part], rows[part], cols[part], matrix_t)
    return matrix_t
