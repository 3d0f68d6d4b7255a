"""Packed-uint32 word storage + the ONE scatter-OR primitive.

Canonical storage for every engine behind the :class:`~repro.index.protocol.
GeneIndex` protocol: Bloom-filter bits live packed 32-per-``uint32`` word
(the layout the Pallas kernels and the serving index already use), not as
one byte per bit.

Since the ingest refactor all mutation flows through
:mod:`repro.index.ingest` (the shared ``InsertPlan`` layer), and the three
storage-specific scatter bodies this module used to carry (flat words,
bit-sliced, RAMBO rows) collapsed into one: :func:`scatter_or_matrix`, a
scatter-OR of single bits at ``(row, word_col, bit)`` targets of any
packed ``(n_rows, W)`` matrix, in 32 passes, one per bit position ``j``:
the targets of bit ``j`` gather their words and write back
``word | 1 << j``. All targets of one word in one pass write the same
value, so duplicate targets need no dedup, and no sort: on a TPU a sort
of a batch's ~2^18 targets takes minutes to compile, three keys longer.
Out-of-range targets (masked ones are routed to ``row == n_rows``) are
dropped. The destination is donated by every caller, so the passes
update it in place: no second matrix-sized buffer, which an 8 GiB state
on a 16 GB chip has no room for.

The old per-layout helpers remain: ``scatter_or_bitsliced`` and
``scatter_or_rows`` as thin views of the one body, ``scatter_or`` as its
W == 1 specialization over flat bit locations (the flat-BF path). The legacy
jit entry points (``insert_batch_words`` / ``insert_batch_bitsliced`` /
``insert_batch_rows``) finished their deprecation window and are now
call-time ``ImportError`` stubs pointing at ``ingest.InsertPlan``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bloom as bloom_mod
from repro.core import idl as idl_mod
from repro.index import registry


def batch_locations(
    cfg: idl_mod.IDLConfig, reads: jax.Array, scheme: str, *, lane32: bool = False
) -> jax.Array:
    """(B, η, n_kmers) uint32 locations for a batch of equal-length reads."""
    fn = registry.locations32 if lane32 else registry.locations
    return jax.vmap(lambda codes: fn(cfg, codes, scheme))(reads)


# ---------------------------------------------------------------------------
# The one scatter-OR body (pure jnp, jit/vmap safe).
# ---------------------------------------------------------------------------

def _set_bits(matrix: jax.Array, index: tuple, bits: jax.Array) -> jax.Array:
    """OR bit ``bits[i]`` into ``matrix[index][i]``, one pass per bit
    position (see the module docstring); ``index[0]`` out of range drops
    a target."""
    oob = matrix.shape[0]

    def one_bit(j, m):
        idx = (jnp.where(bits == j, index[0], oob),) + index[1:]
        old = m.at[idx].get(mode="fill", fill_value=0)
        return m.at[idx].set(old | (jnp.uint32(1) << j), mode="drop")

    return jax.lax.fori_loop(jnp.uint32(0), jnp.uint32(32), one_bit, matrix)


def scatter_or_matrix(
    matrix: jax.Array,
    rows: jax.Array,
    word_cols: jax.Array,
    bits: jax.Array,
) -> jax.Array:
    """OR bit ``bits[i]`` of word ``(rows[i], word_cols[i])`` into ``matrix``.

    Duplicate-safe; out-of-range targets (including deliberately masked
    ones routed to ``row == n_rows``) are dropped. This is the single
    scatter body behind every engine's insert path.
    """
    return _set_bits(
        matrix,
        (rows.reshape(-1).astype(jnp.int32),
         word_cols.reshape(-1).astype(jnp.int32)),
        bits.reshape(-1).astype(jnp.uint32))


def scatter_or(words: jax.Array, locs: jax.Array) -> jax.Array:
    """OR the bits at flat bit-locations ``locs`` into packed ``words``.

    The W == 1 specialization of :func:`scatter_or_matrix`. Out-of-range
    locations are dropped.
    """
    flat = locs.reshape(-1).astype(jnp.uint32)
    return _set_bits(words, ((flat >> jnp.uint32(5)).astype(jnp.int32),),
                     flat & jnp.uint32(31))


def scatter_or_bitsliced(
    matrix: jax.Array, rows: jax.Array, file_ids: jax.Array
) -> jax.Array:
    """Set file bits at (row, file) pairs in a bit-sliced (m, F/32) matrix."""
    fids = file_ids.reshape(-1).astype(jnp.int32)
    return scatter_or_matrix(matrix, rows, fids >> 5, fids & 31)


def scatter_or_rows(
    filters: jax.Array, filter_rows: jax.Array, locs: jax.Array
) -> jax.Array:
    """Set bit ``locs[i]`` of packed filter row ``filter_rows[i]`` (RAMBO)."""
    flat = locs.reshape(-1).astype(jnp.int32)
    return scatter_or_matrix(filters, filter_rows, flat >> 5, flat & 31)


# ---------------------------------------------------------------------------
# Legacy batched entry points — removed; call-time ImportError stubs only.
# ---------------------------------------------------------------------------

def _removed(name: str, kind: str) -> "ImportError":
    return ImportError(
        f"packed.{name} was removed after its deprecation window; migrate: "
        f"ingest.plan_insert(cfg, scheme, reads.shape, dest.shape, "
        f"kind={kind!r}).execute(...) or the engine's insert_batch (see "
        "docs/API.md, 'Migration from the v1 serving surface')."
    )


def insert_batch_words(words, reads, *, cfg=None, scheme=None):
    """Removed legacy entry point — raises ImportError with the migration."""
    raise _removed("insert_batch_words", "bits")


def insert_batch_bitsliced(matrix, reads, cols, *, cfg=None, scheme=None,
                           lane32=False):
    """Removed legacy entry point — raises ImportError with the migration."""
    raise _removed("insert_batch_bitsliced", "cols")


def insert_batch_rows(filters, reads, filter_rows, *, cfg=None, scheme=None):
    """Removed legacy entry point — raises ImportError with the migration."""
    raise _removed("insert_batch_rows", "rows")


# ---------------------------------------------------------------------------
# Layout conversions (row-major stacks of packed filters).
# ---------------------------------------------------------------------------

def pack_rows(bits_u8: jax.Array) -> jax.Array:
    """(..., m) uint8 {0,1} -> (..., m/32) uint32 (rowwise pack_bits)."""
    m = bits_u8.shape[-1]
    if m % 32:
        raise ValueError(f"row length m={m} must be a multiple of 32")
    flat = bloom_mod.pack_bits(bits_u8.reshape(-1))
    return flat.reshape(bits_u8.shape[:-1] + (m // 32,))


def unpack_rows(words: jax.Array, m: int) -> jax.Array:
    """(..., m/32) uint32 -> (..., m) uint8 (rowwise unpack_bits)."""
    flat = bloom_mod.unpack_bits(words.reshape(-1))
    return flat.reshape(words.shape[:-1] + (m,))


def unpack_file_bits(masks: jax.Array, n_files: int) -> jax.Array:
    """(..., F/32) uint32 file masks -> (..., n_files) bool."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (masks[..., None] >> shifts) & jnp.uint32(1)
    return (bits.reshape(masks.shape[:-1] + (-1,))[..., :n_files]) == 1


def __getattr__(name: str):
    # coverage_need's single definition lives with the rest of the
    # query-side math (repro.index.query); re-exported here lazily so the
    # storage module keeps its historical surface without a duplicate body
    # or an import cycle.
    if name == "coverage_need":
        from repro.index import query

        return query.coverage_need
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
