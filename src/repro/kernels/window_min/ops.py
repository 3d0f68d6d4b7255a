"""jit wrapper for the window_min kernel with CPU fallback."""

from __future__ import annotations

import jax

from repro.kernels.window_min import kernel, ref


def window_min(a: jax.Array, w: int, *, interpret: bool,
               use_kernel: bool = True, tile: int = 1024) -> jax.Array:
    if not use_kernel or a.shape[0] < w + 1:
        return ref.window_min_ref(a, w=w)
    return kernel.window_min(a, w=w, tile=tile, interpret=interpret)
