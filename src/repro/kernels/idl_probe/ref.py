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
    rows: jax.Array,
    starts: jax.Array,
    *,
    rows_per_block: int,
    n_probes: int,
) -> jax.Array:
    """(G·C, W) uint32 rows in probe order (untransposed, unlike the
    kernel). Each probe reads its offset in the tile of its run, the
    block of the run's first probe (the last start at or before it in its
    group); the pad lanes past ``n_probes`` read 0."""
    g, c = rows.shape
    lane = jnp.arange(c, dtype=jnp.int32)
    first = jax.lax.cummax(jnp.where(starts != 0, lane, 0), axis=1)
    block = jnp.take_along_axis(rows // rows_per_block, first, axis=1)
    at = (block * rows_per_block + rows % rows_per_block).reshape(-1)
    real = jnp.arange(g * c) < n_probes
    return jnp.where(real[:, None], matrix[jnp.where(real, at, 0)], 0)


def query_membership_ref(bf_words: jax.Array, locs: jax.Array) -> jax.Array:
    """Direct packed query on (η, n) locations (matches core.bloom.query_packed)."""
    word_idx = (locs >> np.uint32(5)).astype(jnp.int32)
    bit = locs & np.uint32(31)
    got = (bf_words[word_idx] >> bit) & np.uint32(1)
    return jnp.all(got == np.uint32(1), axis=0)
