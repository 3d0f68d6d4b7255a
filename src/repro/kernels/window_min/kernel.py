"""Pallas TPU kernel: sliding-window minimum (rolling MinHash core).

TPU-native replacement for the paper's segment tree (DESIGN.md §2): for the
small windows used by gene search (w = k − t + 1 ≤ 16) the cheapest
branch-free form is w shifted vector-mins per tile — pure VPU work, fully
pipelined with the tile DMAs. Tiles need a (w−1)-element halo; Pallas blocks
don't overlap, so the input is passed twice with index_maps i and i+1 and
the kernel stitches the halo from the start of the next tile.

Lanes are uint32 (TPU target — see hashing.hash_pair32); the pure-jnp
reference path (core.minhash.sliding_window_min) keeps exact uint64 paper
semantics on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _window_min_kernel(a_ref, nxt_ref, out_ref, *, w: int):
    tile = a_ref[:]                 # (T,)
    halo = nxt_ref[: w - 1] if w > 1 else nxt_ref[:0]
    ext = jnp.concatenate([tile, halo])   # (T + w - 1,)
    t = tile.shape[0]
    acc = jax.lax.dynamic_slice(ext, (0,), (t,))
    for s in range(1, w):           # static unroll, w <= 16: w-1 vector mins
        acc = jnp.minimum(acc, jax.lax.dynamic_slice(ext, (s,), (t,)))
    out_ref[:] = acc


@functools.partial(jax.jit, static_argnames=("w", "tile", "interpret"))
def window_min(
    a: jax.Array, *, w: int, tile: int = 1024, interpret: bool
) -> jax.Array:
    """out[i] = min(a[i : i + w]) for all n − w + 1 windows.

    a: (n,) uint32 (or any orderable 32-bit dtype).
    """
    n = a.shape[0]
    if n < w:
        raise ValueError(f"length {n} < window {w}")
    if w > tile:
        raise ValueError(f"window {w} must fit in a tile ({tile})")
    out_len = n - w + 1
    fill = jnp.iinfo(a.dtype).max if jnp.issubdtype(a.dtype, jnp.integer) else jnp.inf
    # pad to a whole number of tiles PLUS one extra tile so the "next tile"
    # operand of the last step is in-bounds.
    n_tiles = -(-n // tile)
    padded = (n_tiles + 1) * tile
    ap = jnp.concatenate([a, jnp.full((padded - n,), fill, dtype=a.dtype)])

    out = pl.pallas_call(
        functools.partial(_window_min_kernel, w=w),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((tile,), lambda i: (i + 1,)),
        ],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile,), a.dtype),
        interpret=interpret,
    )(ap, ap)
    return out[:out_len]
