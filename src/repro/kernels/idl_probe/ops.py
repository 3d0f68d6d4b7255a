"""jit wrapper and run planners for the IDL probe kernel.

The planner run-length-encodes the probe stream by row block and emits the
fixed-shape arrays the kernel takes. The served planner,
:func:`device_plan_runs`, runs on the device inside the same jitted program
as the hash and the kernel, so a batch makes no host round trip between its
reads and the kernel's launch. :func:`plan_probe_runs` is its numpy twin,
which nothing served calls: the oracle the tests hold the device planner
to, and the planner of the original flat-BF kernel (:func:`probe_membership`,
run only interpreted, by the tests).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import lanes
from repro.kernels.idl_probe import kernel, ref


@dataclasses.dataclass
class ProbePlan:
    block_ids: np.ndarray    # (R_pad,) int32 block per run
    group_ids: np.ndarray    # (R_pad,) int32 output group per run
    offsets: np.ndarray      # (R_pad, C) int32 offset in block of the
                             # probe at lane p % C of the run's group, -1
                             # on lanes that belong to other runs
    run_lengths: np.ndarray  # (n_runs,) int32 probes per run (precomputed
                             # at plan time so telemetry never re-reduces
                             # the (R, C) offset matrix)
    probe_index: np.ndarray  # (R_pad, C) int32 position in flattened (η·n)
                             # stream, -1 where offsets is
    starts: np.ndarray       # (η·n,) bool: the probe starts a run
    n_runs: int              # true run count (before padding)
    n_probes: int
    eta: int
    n_keys: int
    block_bits: int
    probes_per_run: int
    # NOTE: per-run DMA bytes depend on the probed matrix's row width,
    # which the plan does not know — see QueryPlan.run_dma_bytes.


def plan_probe_runs(
    locs: np.ndarray, block_bits: int, probes_per_run: int = 128
) -> ProbePlan:
    """Run-length-encode (P, n) probe streams into block-resident runs, on
    the host (the numpy oracle of :func:`device_plan_runs`).

    ``locs`` may be bit locations (``block_bits`` = bits per block, the
    original flat-BF use) or matrix row indices (``block_bits`` = rows per
    block — the generalized ``probe_rows`` path); the arithmetic is
    identical. The flattened stream is cut into groups of C consecutive
    probes (probe ``p`` is lane ``p % C`` of group ``p // C``), and a run
    is a stretch of probes of one block within one group, so the kernel
    writes each group once, in probe order.

    The run count is padded to a power of two, and at least 8 (pad runs
    repeat the last run's block and group and probe nothing), so the
    executor's compile cache stays small although the true run count is
    data-dependent.
    """
    locs = np.asarray(locs, dtype=np.int64)
    if locs.ndim == 1:
        locs = locs[None, :]
    p, n = locs.shape
    c = probes_per_run

    # Vectorized over ALL streams at once (no per-stream Python loop): the
    # whole (P, n) probe stream is planned in a handful of cumsum passes,
    # which is what lets a (B·η, n_kmers) batch plan in ~ms on the host.
    flat = locs.reshape(-1)
    blocks = flat // block_bits
    idx = np.arange(p * n, dtype=np.int64)
    start = np.empty(p * n, dtype=bool)
    start[0] = True
    np.not_equal(blocks[1:], blocks[:-1], out=start[1:])
    start[:: c] = True                       # a run never crosses a group
    # run keys are nondecreasing along the stream, so a cumsum numbers them
    run = np.cumsum(start) - 1
    n_runs = int(run[-1]) + 1
    r_pad = max(1 << (n_runs - 1).bit_length(), lanes.RUNS_PER_BLOCK)
    lane = idx % c

    offs = np.full((r_pad, c), -1, dtype=np.int32)
    pidx = np.full((r_pad, c), -1, dtype=np.int32)
    offs[run, lane] = (flat % block_bits).astype(np.int32)
    pidx[run, lane] = idx.astype(np.int32)
    bids = np.full(r_pad, blocks[-1], dtype=np.int32)
    bids[run] = blocks.astype(np.int32)
    gids = np.full(r_pad, (p * n - 1) // c, dtype=np.int32)
    gids[run] = (idx // c).astype(np.int32)

    return ProbePlan(
        block_ids=bids,
        group_ids=gids,
        offsets=offs,
        run_lengths=np.bincount(run, minlength=n_runs).astype(np.int32),
        probe_index=pidx,
        starts=start,
        n_runs=n_runs,
        n_probes=p * n,
        eta=p,
        n_keys=n,
        block_bits=block_bits,
        probes_per_run=c,
    )


def probe_membership(
    bf_words: jax.Array, plan: ProbePlan, *, interpret: bool,
    use_ref: bool = False,
) -> jax.Array:
    """Execute the plan; return (n_keys,) bool membership (AND over η)."""
    block_words = plan.block_bits // 32
    args = (
        bf_words,
        jnp.asarray(plan.block_ids),
        jnp.asarray(plan.offsets),
    )
    if use_ref:
        bits = ref.probe_runs_ref(
            *args, block_words=block_words, probes_per_run=plan.probes_per_run
        )
    else:
        bits = kernel.probe_runs(
            *args,
            block_words=block_words,
            probes_per_run=plan.probes_per_run,
            interpret=interpret,
        )
    return scatter_and_reduce(bits, plan)


@functools.partial(jax.jit, static_argnames=(
    "rows_per_block", "n_probes", "row_words", "interpret", "use_ref",
))
def _planned_gather(matrix, rows, starts, *, rows_per_block, n_probes,
                    row_words, interpret, use_ref):
    """Execute a group plan (``rows`` and ``starts``, ``(G, C)``); return
    ``(n_probes, W)`` uint32 rows in probe order. ``use_ref`` swaps the
    Pallas kernel for its pure-jnp oracle (same plan, same result). The
    kernel takes and returns the transposed view (see
    ``repro.kernels.lanes``)."""
    matrix = jnp.reshape(matrix, (-1, row_words))
    if use_ref:
        got = ref.probe_rows_ref(matrix, rows, starts,
                                 rows_per_block=rows_per_block,
                                 n_probes=n_probes)
    else:
        got = kernel.probe_rows(matrix.T, rows, starts,
                                rows_per_block=rows_per_block,
                                n_probes=n_probes, interpret=interpret).T
    return got[:n_probes]


class DevicePlan(NamedTuple):
    """The run plan of one probe stream, as device arrays."""

    rows: jax.Array        # (G, C) int32 row of each probe (0 past the end)
    starts: jax.Array      # (G, C) int32 1 where a probe starts a run
    n_runs: jax.Array      # () int32 run count (tile copies of the kernel)
    run_hist: jax.Array    # (C + 1,) int32 runs of each length 0..C


def _shift_left(x: jax.Array, k: int, fill: int) -> jax.Array:
    """``x[:, j + k]``, and ``fill`` past the last column."""
    return jnp.concatenate(
        [x[:, k:], jnp.full((x.shape[0], k), fill, x.dtype)], axis=1)


@functools.partial(jax.jit, static_argnames=("rows_per_block",
                                             "probes_per_run"))
def device_plan_runs(rows: jax.Array, *, rows_per_block: int,
                     probes_per_run: int) -> DevicePlan:
    """Run-length-encode the ``(N,)`` probe stream ``rows`` on the device.

    The runs :func:`plan_probe_runs` makes, in the kernel's group form:
    probe ``p`` is lane ``p % C`` of group ``p // C``, and a run is a
    stretch of probes of one block within one group, flagged at its first
    probe. The lanes past the stream's end start nothing. No sort, no
    scatter and no gather: a run's length is the distance to the next
    start or to the stream's end, found by a cumulative min along its
    group (shifts, not ``lax.cummin``, whose reduce-window takes the TPU
    compiler nearly a minute at a screen batch's size).
    """
    n = rows.shape[0]
    c = probes_per_run
    g = -(-n // c)
    tail = g * c - n
    rows = rows.astype(jnp.int32)
    blocks = rows // rows_per_block
    idx = jnp.arange(n, dtype=jnp.int32)
    start = (idx % c == 0) | (blocks != jnp.roll(blocks, 1))
    start2 = jnp.pad(start, (0, tail)).reshape(g, c)
    real = (jnp.arange(g * c, dtype=jnp.int32) < n).reshape(g, c)
    lane = jnp.arange(c, dtype=jnp.int32)
    # the first start or end at or after each lane: a cumulative min from
    # the right, in log2(C) shifts
    bound = jnp.where(start2 | ~real, lane, c)
    shift = 1
    while shift < c:
        bound = jnp.minimum(bound, _shift_left(bound, shift, c))
        shift *= 2
    length = jnp.where(start2, _shift_left(bound, 1, c) - lane, 0)
    length = length.reshape(-1, 1)
    run_hist = jnp.sum(length == jnp.arange(1, c + 1, dtype=jnp.int32),
                       axis=0, dtype=jnp.int32)
    return DevicePlan(
        rows=jnp.pad(rows, (0, tail)).reshape(g, c),
        starts=start2.astype(jnp.int32),
        n_runs=jnp.sum(start, dtype=jnp.int32),
        run_hist=jnp.concatenate([jnp.zeros((1,), jnp.int32), run_hist]))


def scatter_and_reduce(bits: jax.Array, plan: ProbePlan) -> jax.Array:
    """(R, C) run bits -> (n_keys,) membership via the plan's probe_index."""
    flat = jnp.ones((plan.n_probes,), dtype=jnp.int32)
    idx = jnp.asarray(plan.probe_index.reshape(-1))
    vals = bits.reshape(-1)
    # pad lanes are routed out of range and dropped by the scatter
    idx = jnp.where(idx >= 0, idx, plan.n_probes)
    flat = flat.at[idx].set(vals, mode="drop")
    per_rep = flat.reshape(plan.eta, plan.n_keys)
    return jnp.all(per_rep == 1, axis=0)
