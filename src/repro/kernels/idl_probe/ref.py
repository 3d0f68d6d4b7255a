"""Pure-jnp oracle for the probe kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def probe_runs_ref(
    bf_words: jax.Array,
    block_ids: jax.Array,
    offsets: jax.Array,
    *,
    block_words: int,
    probes_per_run: int,
) -> jax.Array:
    """(R, C) int32 bits; pad lanes (offset < 0) read as 1."""
    del probes_per_run
    valid = offsets >= 0
    off = jnp.where(valid, offsets, 0)
    global_word = block_ids[:, None] * block_words + (off >> 5)
    bit_idx = (off & 31).astype(jnp.uint32)
    words = bf_words[global_word]
    bit = ((words >> bit_idx) & np.uint32(1)).astype(jnp.int32)
    return jnp.where(valid, bit, 1)


def probe_rows_ref(
    matrix: jax.Array,
    block_ids: jax.Array,
    group_ids: jax.Array,
    offsets: jax.Array,
    *,
    rows_per_block: int,
    n_groups: int,
) -> jax.Array:
    """(n_groups·C, W) uint32 rows in probe order (untransposed, unlike
    the kernel); the padding lanes of the last group read row 0."""
    c = offsets.shape[1]
    n_out = n_groups * c
    lane = group_ids[:, None] * c + jnp.arange(c, dtype=jnp.int32)
    rows = block_ids[:, None] * rows_per_block + offsets
    at = jnp.zeros((n_out,), dtype=jnp.int32).at[
        jnp.where(offsets >= 0, lane, n_out)].set(rows, mode="drop")
    return matrix[at]


def query_membership_ref(bf_words: jax.Array, locs: jax.Array) -> jax.Array:
    """Direct packed query on (η, n) locations (matches core.bloom.query_packed)."""
    word_idx = (locs >> np.uint32(5)).astype(jnp.int32)
    bit = locs & np.uint32(31)
    got = (bf_words[word_idx] >> bit) & np.uint32(1)
    return jnp.all(got == np.uint32(1), axis=0)
