"""In-kernel helpers shared by the run-coalesced probe and insert kernels.

The insert kernel walks one grid step per run and reads that run's ``C``
lane values from an ``(R, C)`` int32 array. Mosaic only tiles blocks whose
last two dimensions are multiples of (8, 128) or the whole array, so a
run's row is fetched as part of an ``(8, C)`` block (one DMA per eight
runs) and picked out here. Its bit images are one-hot matmuls on the MXU;
words are split into four byte planes, so every operand and every partial
sum is an integer below 256, which bf16 and f32 hold exactly. All
arithmetic is int32: Mosaic refuses unsigned-to-float casts and unsigned
reductions, so uint32 words are bitcast at the kernel boundary.

Both kernels see the packed ``(n_rows, W)`` matrix transposed, as
``(W, n_rows)``. For ``W`` that is not a multiple of 128 that is how XLA
lays the matrix out on a TPU (rows on lanes, no lane padding), so the
transpose is free and a tile ``(W, rows_per_block)`` is lane-dense; the
row-major layout would pad ``W`` to 128 lanes, a 4x larger matrix at
``W = 32``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

RUNS_PER_BLOCK = 8    # sublane tile: one (8, C) lane block serves 8 runs
LANES = 128           # lane tile: the narrowest (W, rows) tile a DMA copies
# Scalar-prefetch operands live in SMEM, 1 MiB on a v5e core: one kernel
# call takes at most this many runs or probes (two int32 scalars each),
# and longer plans are cut into calls that pass their output on.
MAX_RUNS_PER_CALL = 1 << 16


def chunks(n_runs: int):
    """Static ``slice`` per kernel call covering ``n_runs`` runs."""
    step = MAX_RUNS_PER_CALL
    return [slice(lo, min(lo + step, n_runs)) for lo in range(0, n_runs, step)]


def x32():
    """Context for tracing a kernel: ``repro`` turns ``jax_enable_x64`` on
    for its host-side 64-bit kmer paths, and under it Python integers in a
    kernel body become int64 constants whose casts Mosaic cannot lower.
    Every kernel operand is 32-bit, so tracing the body without x64
    changes no value."""
    return jax.enable_x64(False)


def run_lanes(ref, i) -> jax.Array:
    """Row ``i % 8`` of the resident ``(8, C)`` lane block, as ``(1, C)``."""
    block = ref[...]
    pick = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0) == (
        i % RUNS_PER_BLOCK)
    return jnp.sum(jnp.where(pick, block, 0), axis=0, keepdims=True)


def planes_dot(words: jax.Array, onehot: jax.Array, *, rhs_contract: int
               ) -> jax.Array:
    """Exact ``words @ onehot`` for int32 ``words`` holding 32-bit patterns.

    ``onehot`` is a bf16 {0, 1} matrix, contracted over its dimension
    ``rhs_contract``, such that each output element sums words whose set
    bits are disjoint: the sum of each byte plane is then its OR and stays
    below 256. Returns int32.
    """
    dims = (((1,), (rhs_contract,)), ((), ()))
    out = None
    for p in range(4):
        plane = ((words >> (8 * p)) & 0xFF).astype(jnp.float32)
        got = jax.lax.dot_general(plane.astype(jnp.bfloat16), onehot, dims,
                                  preferred_element_type=jnp.float32)
        part = got.astype(jnp.int32) << (8 * p)
        out = part if out is None else out | part
    return out
