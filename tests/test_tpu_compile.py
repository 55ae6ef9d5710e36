"""Ahead-of-time compiles of the main path for a described TPU v5e.

The TPU compiler is installed here, and it compiles for a chip that is
described, not attached.  These tests run the chip's compiler on the
windowed kernel, the whole windowed converge and the sharded windowed
step at config-4 width (1M peers / 50M edges), and the kernel and the
converge where the score table is streamed in chunks (4,194,304 and
10,000,000 peers), so what it refuses — VMEM over-use, unaligned
tiles, a program over HBM, a lost collective — fails here at no chip
time.  A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and the test workers all
import this file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from protocol_tpu.analysis.comm.hlo_walk import parse_module
from protocol_tpu.ops.gather_window import TABLE_CHUNK, converge_windowed, gather_windowed
from protocol_tpu.parallel.mesh import SHARD_AXIS
from protocol_tpu.parallel.sharded import BLOCK_ROWS, _get_windowed_runner

#: Config 4's plan shapes: 1M peers pad to 977 windows of 1024; 50M
#: edges fill 48,828 vreg-rows plus one partial row per window, rounded
#: up to BLOCK_ROWS; about 14.7M (row, dst) runs, padded to SEG_QUANTUM.
N_PEERS = 1_000_000
TABLE_ENTRIES = 1_000_448
N_ROWS = 49_856
N_SEGMENTS = 14_680_064
N_SHARDS = 4
HBM_BYTES = 16 << 30  # one v5e chip

#: Plan shapes past one table chunk, as ``(peers, table entries, rows,
#: padded runs)``: the plan ``build_window_plan`` makes of
#: ``graph500-s22``'s seed-7 Graph500 scale-22 graph (4,194,304 ids,
#: 65,244,818 attestations, 56,268,897 runs), and BASELINE config 5's
#: 10M peers at config 4's 50M edges, where the whole-table kernel was
#: refused.
STREAMED = {
    "graph500-s22": (4_194_304, 4_194_304, 76_352, 63_302_656),
    "config-5": (10_000_000, 10_000_384, 59_968, 40_000_000),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_gather_windowed_compiles_to_a_kernel(one_chip):
    compiled = gather_windowed.lower(
        _spec((N_ROWS,), jnp.int32, one_chip),
        _spec((TABLE_ENTRIES,), jnp.float32, one_chip),
        _spec((N_ROWS * 8, 128), jnp.int32, one_chip),
        _spec((N_ROWS * 8, 128), jnp.float32, one_chip),
        n_rows=N_ROWS,
        table_chunk=TABLE_CHUNK,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _converge_on_one_chip(one_chip, n_peers, table_entries, n_rows, n_segments):
    """``converge_windowed`` compiled for one chip: the executable and
    its device bytes (arguments, outputs and temporaries)."""
    i32, f32 = jnp.int32, jnp.float32
    compiled = converge_windowed.lower(
        _spec((n_rows,), i32, one_chip),
        _spec((n_rows * 8, 128), i32, one_chip),
        _spec((n_rows * 8, 128), f32, one_chip),
        _spec((n_rows * 8, 128), jnp.bool_, one_chip),
        _spec((n_segments,), i32, one_chip),
        _spec((n_peers + 1,), i32, one_chip),
        _spec((n_peers,), f32, one_chip),
        _spec((n_peers,), f32, one_chip),
        _spec((n_peers,), f32, one_chip),
        n_rows=n_rows,
        table_entries=table_entries,
        table_chunk=TABLE_CHUNK,
        alpha=_spec((), f32, one_chip),
        tol=1e-6,
        max_iter=50,
        interpret=False,
    ).compile()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        - mem.alias_size_in_bytes
        + mem.temp_size_in_bytes
    )
    return compiled, total


def test_converge_windowed_fits_one_chip(one_chip):
    compiled, total = _converge_on_one_chip(
        one_chip, N_PEERS, TABLE_ENTRIES, N_ROWS, N_SEGMENTS
    )
    assert "tpu_custom_call" in compiled.as_text()
    assert total < HBM_BYTES, total


@pytest.mark.parametrize("shape", sorted(STREAMED))
def test_streamed_gather_compiles_to_a_kernel(one_chip, shape):
    _, table_entries, n_rows, _ = STREAMED[shape]
    compiled = gather_windowed.lower(
        _spec((n_rows,), jnp.int32, one_chip),
        _spec((table_entries,), jnp.float32, one_chip),
        _spec((n_rows * 8, 128), jnp.int32, one_chip),
        _spec((n_rows * 8, 128), jnp.float32, one_chip),
        n_rows=n_rows,
        table_chunk=TABLE_CHUNK,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", sorted(STREAMED))
def test_converge_windowed_streams_the_table_on_one_chip(one_chip, shape):
    compiled, total = _converge_on_one_chip(one_chip, *STREAMED[shape])
    assert "tpu_custom_call" in compiled.as_text()
    assert total < HBM_BYTES, total


def test_sharded_windowed_step_has_one_all_reduce(topo):
    mesh = Mesh(np.array(topo.devices[:N_SHARDS]), (SHARD_AXIS,))
    edge = NamedSharding(mesh, P(SHARD_AXIS))
    edge2d = NamedSharding(mesh, P(SHARD_AXIS, None))
    repl = NamedSharding(mesh, P())
    rows = N_ROWS // N_SHARDS
    rows -= rows % BLOCK_ROWS
    segs = N_SEGMENTS // N_SHARDS
    i32, f32 = jnp.int32, jnp.float32
    run = _get_windowed_runner(mesh, N_PEERS, rows, TABLE_ENTRIES, False, TABLE_CHUNK)
    compiled = run.lower(
        _spec((N_SHARDS * rows,), i32, edge),
        _spec((N_SHARDS * rows * 8, 128), i32, edge2d),
        _spec((N_SHARDS * rows * 8, 128), f32, edge2d),
        _spec((N_SHARDS * rows * 8, 128), jnp.bool_, edge2d),
        _spec((N_SHARDS * segs,), i32, edge),
        _spec((N_SHARDS, N_PEERS + 1), i32, edge2d),
        _spec((N_PEERS,), f32, repl),
        _spec((N_PEERS,), f32, repl),
        _spec((N_PEERS,), f32, repl),
        _spec((), f32, repl),
        max_iter=50,
        tol=1e-6,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert [op.kind for op in parse_module(text).collectives] == ["all-reduce"]
