"""Host-side planners + wrappers for block-resident scatter-OR insertion.

Two generations of planner (mirroring kernel.py):

* :func:`plan_insert_rounds` (legacy) groups the (η, n) location grid by BF
  block and emits ROUNDS: within one round every block id is unique, so the
  kernel can process the whole round with zero write conflicts — but each
  round is its own launch.
* :func:`plan_insert_runs` — the vectorized planner behind
  ``repro.index.ingest``: the whole batch's (already flattened) bit
  positions are **sorted and deduplicated once** (np.unique), run-length
  encoded by matrix row-block in a handful of cumsum passes (the same
  technique as idl_probe.plan_probe_runs), and emitted as ONE kernel
  launch. Sorting makes runs of a block consecutive, so the kernel
  accumulates into a resident output tile (revisiting) and each touched
  block costs exactly one tile read + one tile write, however many runs
  land in it; the kernel writes the tiles back into the donated matrix.
  IDL needs few blocks (locality!) → few tiles; RH touches ~every block
  once → many singleton tiles. The run/tile structure is
  itself a locality measurement.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import lanes
from repro.kernels.idl_insert import kernel, ref


@dataclasses.dataclass
class InsertPlan:
    rounds: list[tuple[np.ndarray, np.ndarray]]  # [(block_ids (R,), offsets (R, C))]
    block_bits: int
    inserts_per_round: int
    n_locs: int

    @property
    def n_tiles(self) -> int:
        return sum(int(b.shape[0]) for b, _ in self.rounds)

    @property
    def dma_bytes(self) -> int:
        # read + write one tile per scheduled block
        return 2 * self.n_tiles * (self.block_bits // 8)


def plan_insert_rounds(
    locs: np.ndarray, block_bits: int, inserts_per_round: int = 128
) -> InsertPlan:
    flat = np.asarray(locs, dtype=np.int64).reshape(-1)
    c = inserts_per_round
    blocks = flat // block_bits
    offsets = (flat % block_bits).astype(np.int32)
    order = np.argsort(blocks, kind="stable")
    blocks_s = blocks[order]
    offsets_s = offsets[order]
    # segment boundaries per block
    uniq, starts = np.unique(blocks_s, return_index=True)
    ends = np.append(starts[1:], len(blocks_s))
    counts = ends - starts
    max_rounds = int(np.ceil(counts.max() / c)) if len(counts) else 0
    rounds = []
    for r in range(max_rounds):
        sel = counts > r * c
        bids = uniq[sel].astype(np.int32)
        offs = np.full((len(bids), c), -1, dtype=np.int32)
        for i, (s, e) in enumerate(zip(starts[sel], ends[sel])):
            lo = s + r * c
            hi = min(e, lo + c)
            offs[i, : hi - lo] = offsets_s[lo:hi]
        rounds.append((bids, offs))
    return InsertPlan(
        rounds=rounds, block_bits=block_bits,
        inserts_per_round=c, n_locs=len(flat),
    )


@dataclasses.dataclass
class InsertRunPlan:
    """One-launch, sorted-run plan over a flattened (rows*W*32)-bit space."""

    block_ids: np.ndarray    # (R_pad,) int32 row-block per run, nondecreasing
    offsets: np.ndarray      # (R_pad, C) int32 tile bit offsets, -1 padded
    run_lengths: np.ndarray  # (n_runs,) int32 inserts per true run
                             # (precomputed at plan time so telemetry never
                             # re-reduces the (R_pad, C) offset matrix)
    n_locs: int              # deduplicated insert count
    n_runs: int              # true run count (before pow2 padding)
    n_tiles: int             # touched-block count
    block_bits: int          # bits per tile (rows_per_block * W * 32)
    inserts_per_run: int

    @property
    def dma_bytes(self) -> int:
        # one tile read + one tile write per touched block, for the batch
        return 2 * self.n_tiles * (self.block_bits // 8)


def plan_insert_runs(
    flat_bits: np.ndarray, block_bits: int, inserts_per_run: int = 128
) -> InsertRunPlan | None:
    """Sort + dedup flat bit positions, run-length encode by block.

    ``flat_bits``: any-shape int array of global bit positions within the
    flattened matrix (``(row * W + word) * 32 + bit``); int64 on the host,
    so arbitrarily large matrices are fine. Negative positions are dropped
    (masked inserts). Returns None when nothing survives.

    The run count is padded to a power of two, and at least 8, so the
    executor's compile cache stays small: pad runs are all-pad lanes of
    the last block — bit-exact no-ops that move no tile.
    """
    flat = np.asarray(flat_bits, dtype=np.int64).reshape(-1)
    flat = np.unique(flat[flat >= 0])        # sorted + deduplicated
    n = int(flat.shape[0])
    if n == 0:
        return None
    c = inserts_per_run
    blocks = flat // block_bits
    idx = np.arange(n, dtype=np.int64)
    start = np.empty(n, dtype=bool)
    start[0] = True
    np.not_equal(blocks[1:], blocks[:-1], out=start[1:])
    pos_in_block = idx - np.maximum.accumulate(np.where(start, idx, 0))
    # new run at a block start or every C inserts (split long runs); block
    # keys are nondecreasing so a cumsum numbers runs directly
    run = np.cumsum(start | (pos_in_block % c == 0)) - 1
    n_runs = int(run[-1]) + 1
    r_pad = max(1 << (n_runs - 1).bit_length(), lanes.RUNS_PER_BLOCK)
    pos = pos_in_block % c

    offs = np.full((r_pad, c), -1, dtype=np.int32)
    offs[run, pos] = (flat % block_bits).astype(np.int32)
    bids = np.full(r_pad, blocks[-1], dtype=np.int32)
    bids[run] = blocks.astype(np.int32)

    return InsertRunPlan(
        block_ids=bids, offsets=offs,
        run_lengths=np.bincount(run, minlength=n_runs).astype(np.int32),
        n_locs=n, n_runs=n_runs, n_tiles=int(start.sum()),
        block_bits=block_bits, inserts_per_run=c,
    )


def insert_planned(
    matrix: jax.Array, plan: InsertRunPlan | None, *,
    interpret: bool, use_ref: bool = False,
) -> jax.Array:
    """Execute a run plan against a packed (n_rows, W) matrix — ONE launch.

    The matrix buffer is donated and the kernel updates it in place.
    ``use_ref`` swaps the Pallas kernel for its jnp oracle (same plan,
    bit-identical — the executor on hosts without Mosaic).
    """
    if plan is None:
        return matrix
    w = int(matrix.shape[-1]) if matrix.ndim > 1 else 1
    if plan.block_bits % (w * 32):
        raise ValueError(
            f"block_bits={plan.block_bits} not a row multiple of W={w}")
    return _planned_insert(
        matrix, jnp.asarray(plan.block_ids), jnp.asarray(plan.offsets),
        rows_per_block=plan.block_bits // (w * 32),
        inserts_per_run=plan.inserts_per_run,
        row_words=w,
        interpret=interpret,
        use_ref=use_ref,
    )


@functools.partial(
    jax.jit,
    donate_argnums=(0,),
    static_argnames=("rows_per_block", "inserts_per_run", "row_words",
                     "interpret", "use_ref"),
)
def _planned_insert(matrix, bids, offs, *, rows_per_block, inserts_per_run,
                    row_words, interpret, use_ref):
    """One fused call: run the kernel (or ref) over all runs. The kernel
    takes the transposed view (see ``repro.kernels.lanes``) and splits
    each tile bit offset into its row and its bit column."""
    shape = matrix.shape
    matrix = jnp.reshape(matrix, (-1, row_words))
    if use_ref:
        out = ref.insert_runs_ref(matrix, bids, offs,
                                  rows_per_block=rows_per_block)
    else:
        row_bits = row_words * 32
        mine = offs >= 0
        out = kernel.insert_runs(
            matrix.T, bids,
            jnp.where(mine, offs // row_bits, -1),
            jnp.where(mine, offs % row_bits, 0),
            rows_per_block=rows_per_block,
            inserts_per_run=inserts_per_run,
            interpret=interpret,
        ).T
    return out.reshape(shape)


def insert_with_plan(
    bf_words: jax.Array, plan: InsertPlan, *, interpret: bool,
    use_ref: bool = False,
) -> jax.Array:
    block_words = plan.block_bits // 32
    for bids_np, offs_np in plan.rounds:
        bids = jnp.asarray(bids_np)
        offs = jnp.asarray(offs_np)
        if use_ref:
            tiles = ref.insert_round_ref(
                bf_words, bids, offs,
                block_words=block_words,
                inserts_per_round=plan.inserts_per_round,
            )
        else:
            tiles = kernel.insert_round(
                bf_words, bids, offs,
                block_words=block_words,
                inserts_per_round=plan.inserts_per_round,
                interpret=interpret,
            )
        bf_words = ref.apply_insert_to_words(bf_words, bids, tiles, block_words)
    return bf_words
