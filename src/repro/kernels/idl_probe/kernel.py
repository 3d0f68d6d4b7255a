"""Pallas TPU kernels: block-resident Bloom-filter probes.

The TPU translation of the paper's cache-locality argument (DESIGN.md §2):

* The BF lives packed (uint32 words) in HBM — far too big for VMEM.
* The run planner (ops.device_plan_runs, on the device, in the kernel's
  program) run-length-encodes the probe stream by block. IDL makes runs
  long when the block covers the locality window; RH makes every probe
  its own run.
* Grid = one step per run. A scalar-prefetch array holds each run's block
  id; the BlockSpec index_map consumes it, so Pallas DMAs exactly ONE tile
  from HBM per run and double-buffers the next tile while the current one
  is probed. HBM traffic = n_runs × block_bytes — the quantity IDL
  minimizes. The plan's arrays are sized for the worst case (a run a
  probe); the grid is as long as the plan's run count, a device value,
  so the pad runs past it cost no step.
* Within a resident tile the gather is done MXU-natively with one-hot
  matmuls — no scalar loads, no unsupported vector gathers.

:func:`probe_rows` is the served kernel (every engine's query through
``repro.index.query``); it compiles for the chip (see
``repro.kernels.lanes`` and tests/test_tpu_compile.py). :func:`probe_runs`
is the original flat-BF kernel, run only interpreted.
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


def _probe_rows_kernel(
    block_ids_ref,   # scalar-prefetch: (R,) int32 — matrix row-block per run
    group_ids_ref,   # scalar-prefetch: (R,) int32 — output group per run
    offsets_ref,     # (8, C) int32 — row offsets of 8 runs (-1 = other run)
    mat_ref,         # (W, rows_per_block) uint32 — the resident tile (VMEM)
    acc_ref,         # (W, C) uint32 — the group as earlier calls left it
    out_ref,         # (W, C) uint32 — the run's output group, probe order
):
    del block_ids_ref  # consumed by the index_map only
    i = pl.program_id(0)
    off = lanes.run_lanes(offsets_ref, i)                       # (1, C)
    rpb = mat_ref.shape[1]
    # row gather as one one-hot matmul per byte plane (lanes of other runs
    # select nothing and keep the group's earlier value)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (rpb, off.shape[1]), 0)
              == off).astype(jnp.bfloat16)                      # (RPB, C)
    tile = jax.lax.bitcast_convert_type(mat_ref[...], jnp.int32)
    picked = lanes.planes_dot(tile, onehot, rhs_contract=0)     # (W, C)
    mine = off >= 0

    # Runs are in probe order, so the runs of a group are consecutive: the
    # first one starts the output block from the group's earlier value (a
    # group may straddle two calls), the others fill in their lanes while
    # it stays resident.
    prev_run = jnp.maximum(i - 1, 0)
    first = (i == 0) | (group_ids_ref[i] != group_ids_ref[prev_run])

    def fill_from(prev_ref):
        prev = jax.lax.bitcast_convert_type(prev_ref[...], jnp.int32)
        out_ref[...] = jax.lax.bitcast_convert_type(
            jnp.where(mine, picked, prev), jnp.uint32)

    pl.when(first)(lambda: fill_from(acc_ref))
    pl.when(jnp.logical_not(first))(lambda: fill_from(out_ref))


@functools.partial(
    jax.jit,
    static_argnames=("rows_per_block", "probes_per_run", "n_groups",
                     "interpret"),
)
def probe_rows(
    matrix_t: jax.Array,     # (W, n_rows) uint32 packed bit-matrix, transposed
    block_ids: jax.Array,    # (R,) int32 row-block id per run
    group_ids: jax.Array,    # (R,) int32 output group per run (nondecreasing)
    offsets: jax.Array,      # (R, C) int32 row offset in block, -1 elsewhere
    n_runs: jax.Array,       # () int32 runs of the plan; the rest are pad
    *,
    rows_per_block: int,
    probes_per_run: int,
    n_groups: int,
    interpret: bool,
) -> jax.Array:
    """Run-coalesced row gather over an arbitrary packed bit-matrix.

    One grid step per run; one ``(W, rows_per_block)`` tile DMA'd per step
    (the next tile double-buffers while the current one is probed). Probe
    ``p`` of the stream is lane ``p % C`` of group ``p // C``, and a run
    holds the probes of one block within one group. Returns
    ``(W, n_groups * C)`` uint32: the W-word row of every probe, in probe
    order, transposed like the matrix. ``R`` must be a multiple of 8.
    The grid stops after the first ``n_runs`` runs (a grid sized on the
    device), so the pad runs that fill ``R`` take no step.
    """
    r = block_ids.shape[0]
    c = probes_per_run
    if offsets.shape != (r, c):
        raise ValueError(f"offsets shape {offsets.shape} != {(r, c)}")
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
                             lambda i, bid, gid: (i // lanes.RUNS_PER_BLOCK, 0))
    tile_spec = pl.BlockSpec((w, rows_per_block),
                             lambda i, bid, gid: (0, bid[i]))
    group_spec = pl.BlockSpec((w, c), lambda i, bid, gid: (0, gid[i]))
    out = jnp.zeros((w, n_groups * c), jnp.uint32)
    n_runs = jnp.asarray(n_runs, jnp.int32)
    for part in lanes.chunks(r):
        steps = jnp.clip(n_runs - part.start, 0, part.stop - part.start)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(steps,),
            in_specs=[lane_spec, tile_spec, group_spec],
            out_specs=group_spec,
        )
        with lanes.x32():
            out = pl.pallas_call(
                _probe_rows_kernel,
                grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct(out.shape, jnp.uint32),
                input_output_aliases={4: 0},
                interpret=interpret,
                name="probe_rows",
            )(block_ids[part], group_ids[part], offsets[part], matrix_t, out)
    return out


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
