"""The main path's Pallas kernels compile for a TPU v5e at full size.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology. The shapes are the ``idl-genesearch`` config's: the
``(2^26, 32)`` uint32 bit-sliced matrix, 128 rows per probe block (what
``plan_query`` derives for it: one lane tile), 512 rows per insert block
(``plan_insert``'s) and 128 probes per group (the TPU default),
with ``repro`` imported, so ``jax_enable_x64`` is on as in the server.
The topology is described inside a fixture: only the process that runs
these tests loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro  # noqa: F401 - x64 on, as every process that serves
import repro.configs as configs
from repro.index import ingest, query
from repro.kernels import lanes
from repro.kernels.idl_insert import kernel as insert_kernel
from repro.kernels.idl_probe import kernel as probe_kernel

CFG = configs.get("idl-genesearch").make_config()
C = 128                                   # probes / inserts per run
RUNS = 4 * lanes.MAX_RUNS_PER_CALL        # a 256-read batch, several calls
GROUPS = RUNS // C                        # its probes in groups of C


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shapes(one_chip):
    """Kernel operands at full size, on the described chip."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return {
        "matrix_t": spec((CFG.file_words, CFG.m), jnp.uint32),
        "ids": spec((RUNS,), jnp.int32),
        "lanes": spec((RUNS, C), jnp.int32),
        "groups": spec((GROUPS, C), jnp.int32),
        "count": spec((), jnp.int32),
    }


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _rows_per_block() -> int:
    plan = query.plan_query(
        CFG.idl_config(), CFG.scheme, (256, CFG.read_len),
        (CFG.m, CFG.file_words), bit_probe=False, lane32=True,
        probes_per_run=C)
    return plan.rows_per_block


def _insert_rows_per_block() -> int:
    """The build's tile height: ``BitSlicedIndex.insert_batch``'s plan."""
    plan = ingest.plan_insert(
        CFG.idl_config(), CFG.scheme, (256, CFG.read_len),
        (CFG.m, CFG.file_words), kind="cols", lane32=True,
        inserts_per_run=C)
    return plan.rows_per_block


def test_full_config_block_geometry():
    assert _rows_per_block() == 128
    assert _insert_rows_per_block() == 512
    assert CFG.m * CFG.file_words * 4 == 8 * 2**30


def test_probe_rows_compiles_for_v5e(shapes, no_compile_cache):
    """The kernel of a 256-read batch: tile copies started by the kernel
    into a ring of VMEM slots, with DMA semaphores, over four calls."""
    compiled = jax.jit(
        lambda m, r, s: probe_kernel.probe_rows(
            m, r, s, rows_per_block=_rows_per_block(), n_probes=RUNS,
            interpret=False)
    ).lower(shapes["matrix_t"], shapes["groups"], shapes["groups"]).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 4
    mem = compiled.memory_analysis()
    # the (W, n_rows) view is lane-dense: the matrix goes in as 8 GiB,
    # with no relayout copy of it next to the output
    assert mem.argument_size_in_bytes < 8 * 2**30 + 2**28
    assert mem.temp_size_in_bytes < 2**30
    assert mem.output_size_in_bytes == CFG.file_words * RUNS * 4


@pytest.mark.parametrize("w", [1, 3, 12, 32])
def test_probe_rows_compiles_for_every_row_width(one_chip, no_compile_cache,
                                                 w):
    """Mosaic copies only whole sublane tiles (1, 2, 4 or a multiple of 8
    rows of u32); the kernel takes any row width, COBS groups' odd ones
    among them."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    groups = 4
    compiled = jax.jit(
        lambda m, r, s: probe_kernel.probe_rows(
            m.T, r, s, rows_per_block=128, n_probes=groups * C,
            interpret=False)
    ).lower(spec((1 << 16, w), jnp.uint32), spec((groups, C), jnp.int32),
            spec((groups, C), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_probe_rows_custom_call_is_named(one_chip, no_compile_cache):
    """The kernel's op in a device trace carries the name the benchmark
    matches (``bench/kernels/probe_rows.py``), whatever the Python
    functions around it are called."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    groups = 2 * lanes.RUNS_PER_BLOCK
    compiled = jax.jit(
        lambda m, r, s: probe_kernel.probe_rows(
            m, r, s, rows_per_block=128, n_probes=groups * C - 5,
            interpret=False)
    ).lower(spec((CFG.file_words, 1 << 16), jnp.uint32),
            spec((groups, C), jnp.int32),
            spec((groups, C), jnp.int32)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert calls
    assert all(re.search(r"%probe_rows(\.\d+)? = ", c) for c in calls)


def test_insert_runs_compiles_for_v5e(shapes, no_compile_cache):
    compiled = jax.jit(
        lambda m, b, r, c: insert_kernel.insert_runs(
            m, b, r, c, rows_per_block=_insert_rows_per_block(),
            inserts_per_run=C, interpret=False),
        donate_argnums=0,
    ).lower(shapes["matrix_t"], shapes["ids"], shapes["lanes"],
            shapes["lanes"]).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # updated in place: the output is the donated matrix itself
    assert mem.alias_size_in_bytes == 8 * 2**30
    assert mem.temp_size_in_bytes < 2**30


def test_served_probe_step_compiles_for_v5e(one_chip, no_compile_cache):
    """The whole ``idl_probe`` step of a screen batch (256 reads of 286
    bases: hash, device run planner, kernel, AND over eta) compiles as one
    program, with the 8 GiB matrix in place and the plan's temporaries
    under 1 GiB: the plan is the stream's rows and run flags in (G, C)
    groups."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    n_reads, read_len = 256, 256 + CFG.k - 1
    plan = query.plan_query(
        CFG.idl_config(), CFG.scheme, (n_reads, read_len),
        (CFG.m, CFG.file_words), bit_probe=False, lane32=True,
        probes_per_run=C)
    compiled = query._execute_planned.lower(
        spec((CFG.m, CFG.file_words), jnp.uint32),
        spec((n_reads, read_len), jnp.uint8),
        plan=plan, interpret=False, use_ref=False).compile()
    text = compiled.as_text()
    n_probes = n_reads * CFG.eta * 256
    assert n_probes == RUNS == GROUPS * C   # a 256-read batch's probes
    assert "tpu_custom_call" in text
    assert f"s32[{GROUPS},{C}]" in text     # the plan, on the device
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 8 * 2**30 + 2**28
    assert mem.temp_size_in_bytes < 2**30
    assert mem.output_size_in_bytes < n_reads * 256 * CFG.file_words * 4 + 2**12
