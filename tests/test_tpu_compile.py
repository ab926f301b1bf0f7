"""The main-path kernels compile for a TPU v5e at deployment widths.

Each test lowers a kernel with Mosaic (interpret mode steered off) for
one chip of a described ``v5e:2x2`` topology and compiles it with the
TPU compiler -- which refuses illegal block tiles, scalar reads from
VMEM, unsigned reductions, DMA slices off the tiling and VMEM or SMEM
overflow, none of which the CPU interpreter checks.  Nothing runs.
Widths are ``chip_smoke.py``'s: a 16,777,216-record table of eight
16-bit columns in 8 record shards at 4 chunks (the predicate kernel's
row slabs: ``[8, 1024 * 512, 128]``), and a 1000-tree depth-6 forest
over 8 features of 8 bits scoring 4096 instances, its leaves summed
512 instances at a time.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.encoding import make_plan
from repro.kernels import clutch_merge, fused_query, ops, temporal_encode

SHARDS, TABLE_ROWS, TABLE_WORDS, CHUNKS = 8, 1024, 65536, 4
#: the stacked table LUT as ``fused_query.row_slabs`` lays it out
TABLE_SLABS = (SHARDS, TABLE_ROWS * TABLE_WORDS // 128, 128)
FOREST_ROWS, FOREST_WORDS, FEATURES, BATCH = 264, 256, 8, 4096
TREES, SCORE_BLOCK = 1000, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch, one_chip):
    """Lower the kernels for the chip, with no persistent cache (a
    chipless compile cannot be read back) and no traces left behind
    for the CPU tests that share this process."""
    for mod in (clutch_merge, fused_query, temporal_encode):
        monkeypatch.setattr(mod, "use_interpret", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    try:
        yield lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=one_chip)
    finally:
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _table_idx(num_ranges, spec):
    return spec((num_ranges * 4 * CHUNKS,), jnp.int32)


@pytest.mark.parametrize("num_ranges,disjunction",
                         [(1, False), (2, False), (2, True)])
def test_fused_predicate_banked_compiles(mosaic, num_ranges, disjunction):
    lut = mosaic(TABLE_SLABS, jnp.uint32)
    text = _compile_text(
        lambda lut, idx: fused_query.fused_predicate_banked(
            lut, idx, CHUNKS, num_ranges, disjunction, words=TABLE_WORDS),
        lut, _table_idx(num_ranges, mosaic))
    assert "tpu_custom_call" in text
    assert "%clutch_predicate" in text      # the kernel's name in a trace


def test_fused_compound_banked_compiles(mosaic):
    # (Q1 AND Q2) OR Q3: terms of 1, 2 and 2 ranges, K = 80 lanes
    lut = mosaic(TABLE_SLABS, jnp.uint32)
    text = _compile_text(
        lambda lut, idx: fused_query.fused_compound_banked(
            lut, idx, CHUNKS, (1, 2, 2), (False, False, True),
            (False, True), words=TABLE_WORDS),
        lut, _table_idx(5, mosaic))
    assert "tpu_custom_call" in text
    assert "%clutch_predicate" in text      # the kernel's name in a trace


def test_gbdt_leafbits_banked_compiles(mosaic):
    text = _compile_text(
        lambda lut, masks, idx: fused_query.gbdt_leafbits_banked(
            lut, masks, idx, 1, FEATURES),
        mosaic((FOREST_ROWS, FOREST_WORDS), jnp.uint32),
        mosaic((FEATURES, FOREST_WORDS), jnp.uint32),
        mosaic((BATCH, FEATURES * 2), jnp.int32))
    assert "tpu_custom_call" in text
    assert "%clutch_leafbits" in text      # the kernel's name in a trace


def test_gbdt_leaf_sum_compiles(mosaic):
    # one scoring block: 512 instances' addresses into 1000 trees of 64
    # leaves, each tree's leaves padded to one 128-lane row
    text = _compile_text(
        fused_query.gbdt_leaf_sum,
        mosaic((TREES, SCORE_BLOCK), jnp.int32),
        mosaic((TREES, 128), jnp.float32))
    assert "tpu_custom_call" in text
    assert "%clutch_leafsum" in text      # the kernel's name in a trace


def test_encode_lut_compiles(mosaic):
    # one record shard's column: 2,097,152 values -> [64, 65536] planes
    vals = mosaic((TABLE_WORDS * 32,), jnp.uint32)
    text = _compile_text(
        lambda v: ops.encode_lut(v, make_plan(16, CHUNKS), complement=True),
        vals)
    assert "tpu_custom_call" in text


def test_clutch_merge_banked_and_range_count_compile(mosaic):
    lut = mosaic((SHARDS, TABLE_ROWS, TABLE_WORDS), jnp.uint32)
    idx = mosaic((SHARDS, CHUNKS), jnp.int32)
    assert "tpu_custom_call" in _compile_text(
        clutch_merge.clutch_merge_banked, lut, idx, idx)
    plane = mosaic((TABLE_ROWS, TABLE_WORDS), jnp.uint32)
    assert "tpu_custom_call" in _compile_text(
        lambda a, b, i: fused_query.fused_range_count(a, b, i, CHUNKS),
        plane, plane, mosaic((4 * CHUNKS,), jnp.int32))
