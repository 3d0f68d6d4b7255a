"""The ``GeneIndex`` protocol — one index API for every engine.

Every gene-sequence index in this repo (partitioned Bloom filter, COBS,
RAMBO, the bit-sliced serving index) speaks the same four-method protocol:

* ``build(cfg, ...)``                  — classmethod constructor;
* ``insert_batch(reads, file_ids)``    — index a ``(B, read_len)`` batch of
  base-code reads. Every engine routes through the shared ingest layer
  (:mod:`repro.index.ingest`): ``backend="jnp"`` is one jit-compiled,
  donated, in-place scatter-OR (no per-read Python loop),
  ``backend="idl_insert"`` the host run-length planner + generalized
  Pallas ``insert_runs`` kernel (one launch per batch),
  ``backend="sharded"`` a collective-free ``shard_map`` over a 1-D device
  mesh. All three are bit-identical. ``file_ids`` is ignored by
  single-set engines;
* ``query_batch(reads, backend=...)``  — per-kmer membership for a batch.
  Every engine routes through the shared planner/executor layer
  (:mod:`repro.index.query`): ``backend="jnp"`` is the pure-XLA reference,
  ``backend="idl_probe"`` the device run-length planner + generalized Pallas
  ``probe_rows`` kernel, ``backend="sharded"`` a ``shard_map`` over a 1-D
  device mesh splitting the words/files axis. All three are bit-identical;
* ``msmt(reads, theta)``               — Multiple-Set Membership Testing
  (paper Definition 3): per-file kmer-coverage >= theta. ``theta=1.0``
  reproduces exact Membership Testing (Definition 2).

Engines are immutable: ``insert_batch`` returns a new index value whose
storage buffer was donated from the old one (linear-use style — keep only
the returned index). Hash families are resolved by name through
:mod:`repro.index.registry`; an engine never hard-codes a scheme.

**Protocol v2** makes the storage itself first-class: every engine is a
thin view over a :class:`repro.index.state.IndexState` — a registered
pytree whose leaves are the packed ``(n_rows, W)`` uint32 word matrices
and whose aux data is the static geometry. ``.state`` extracts it,
``.with_state(state)`` rebuilds a view, and the pure functions
``state.insert / state.query / state.msmt`` mirror the methods without an
object in sight — so a whole index can be jitted over, sharded,
snapshotted (:mod:`repro.index.store`) and served
(:mod:`repro.serving.service`) as a plain JAX value. Donation discipline
is enforced by the state layer: a consumed (donated-away) value raises
``state.StaleIndexError`` instead of crashing on a deleted buffer.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Optional, Protocol, runtime_checkable

import jax

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.index.state import IndexState


@runtime_checkable
class GeneIndex(Protocol):
    """Structural protocol shared by all index engines (v2)."""

    scheme: str

    @property
    def state(self) -> "IndexState":
        """The pytree-native storage behind this view."""
        ...

    def with_state(self, state: "IndexState") -> "GeneIndex":
        """Rebuild an engine view of the same kind over ``state``."""
        ...

    def insert_batch(
        self, reads: jax.Array, file_ids: Optional[jax.Array] = None
    ) -> "GeneIndex":
        """Index a batch of reads; returns the updated index."""
        ...

    def query_batch(self, reads: jax.Array, *, backend: str = "jnp") -> jax.Array:
        """Per-kmer membership for a batch of reads."""
        ...

    def msmt(self, reads: jax.Array, theta: float = 1.0) -> jax.Array:
        """Per-file match verdicts at kmer-coverage threshold ``theta``."""
        ...
