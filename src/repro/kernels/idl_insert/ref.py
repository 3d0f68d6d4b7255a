"""Pure-jnp oracle for the insert kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def insert_round_ref(
    bf_words: jax.Array,
    block_ids: jax.Array,
    offsets: jax.Array,
    *,
    block_words: int,
    inserts_per_round: int,
) -> jax.Array:
    """(R, block_words) updated tiles: tile OR bit-image of valid offsets."""
    del inserts_per_round
    r, c = offsets.shape
    valid = offsets >= 0
    off = jnp.where(valid, offsets, 0)
    word_idx = off >> 5                           # (R, C)
    bit_idx = (off & 31).astype(jnp.uint32)
    tiles = bf_words.reshape(-1, block_words)[block_ids]  # (R, W)
    # build OR image per run with a one-hot reduce (jnp, exact)
    onehot_words = (
        word_idx[:, :, None]
        == jnp.arange(block_words, dtype=jnp.int32)[None, None, :]
    ) & valid[:, :, None]                          # (R, C, W)
    contrib = jnp.where(
        onehot_words,
        (np.uint32(1) << bit_idx)[:, :, None].astype(jnp.uint32),
        np.uint32(0),
    )
    img = jax.lax.reduce(
        contrib, np.uint32(0), jax.lax.bitwise_or, dimensions=(1,)
    )                                              # (R, W)
    return tiles | img


def apply_insert_to_words(
    bf_words: jax.Array, block_ids: jax.Array, tiles: jax.Array,
    block_words: int,
) -> jax.Array:
    """Scatter updated tiles back (block ids unique per call)."""
    blocks = bf_words.reshape(-1, block_words)
    return blocks.at[block_ids].set(tiles).reshape(-1)


def insert_runs_ref(
    matrix: jax.Array,
    block_ids: jax.Array,
    offsets: jax.Array,
    *,
    rows_per_block: int,
) -> jax.Array:
    """The updated ``(n_rows, W)`` matrix — oracle for insert_runs.

    Relies on the planner's invariant that deduplicated runs never repeat a
    (tile, offset) pair, so scatter-adding each target's bit where it is
    not yet set equals the OR the kernel computes. The scatter updates the
    (donated) matrix in place, so this doubles as the CPU executor for
    production-size plans.
    """
    n_rows, w = matrix.shape
    valid = offsets >= 0
    word = jnp.where(valid, offsets, 0) >> 5           # word within tile
    row = jnp.where(valid, block_ids[:, None] * rows_per_block + word // w,
                    n_rows)                            # pad lanes dropped
    col = word % w
    bit = jnp.uint32(1) << (offsets & 31).astype(jnp.uint32)
    old = matrix.at[row, col].get(mode="fill", fill_value=0)
    return matrix.at[row, col].add(
        jnp.where((old & bit) != 0, jnp.uint32(0), bit), mode="drop")


def insert_locations_packed_ref(bf_words: jax.Array, locs: jax.Array) -> jax.Array:
    """Direct packed insert oracle via the unpacked representation."""
    from repro.core import bloom

    bits = bloom.unpack_bits(bf_words)
    bits = bits.at[locs.reshape(-1)].set(np.uint8(1))
    return bloom.pack_bits(bits)
