"""Pallas TPU kernels for the perf-critical hot spots.

Each kernel package: kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd wrapper + host-side planning), ref.py (pure-jnp oracle).
Every kernel entry point takes ``interpret`` explicitly; it is decided
where the backend is known (``repro.index.query`` / ``repro.index.ingest``:
interpreted on CPU only). Validated in interpret mode on CPU, and compiled
for a described v5e chip in tests/test_tpu_compile.py (32-bit lanes only,
MXU-friendly gathers, scalar-prefetch DMA scheduling; ``lanes.py`` holds
what Mosaic needs of them).
"""
