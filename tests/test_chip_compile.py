"""The main path's device programs, compiled for a DESCRIBED TPU v5e.

No chip is attached here: the TPU compiler that ships with jax compiles
for a topology that is described (`v5e:2x2`, one device of it). Nothing
executes — a compile that passes is not a chip run — but what the chip's
compiler refuses (an op it has no lowering for, a program that does not
fit) is refused here too, at no chip time.

Shapes are the counter deployment's (chip_smoke.py): 16-byte keys →
``fast_flags`` = (uniform_klen, seq32, key_words=4), two value words,
uint64-add. N is the largest power of two that keeps each compile
≲ 30 s in this sandbox; the smoke's real shapes take minutes and are
recorded in PERF.md "Chip status" from a scratch script, not from here.

Everything that touches the topology lives in the module-scoped fixtures
below — never at import, never in conftest.py, never autouse: only one
process at a time may load the TPU library, and every xdist worker
imports this file. Keep these tests in THIS file (one worker owns it)
and compile in the test's own process.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from rocksplicator_tpu.ops.compaction_kernel import (MergeKind,
                                                     merge_resolve_kernel)
from rocksplicator_tpu.storage.bloom import num_words_for

U32 = jnp.uint32
# what fast_flags() gives for the counter data: one key length (16 B =
# 4 BE words), every seq below 2^32
FAST = dict(uniform_klen=True, seq32=True, key_words=4)
# counter_names_64x15k's (PR 35): keys ``counter-<n>`` of 9 to 15 bytes in
# one shard, so ``fast_flags`` gives ``uniform_klen=False`` (the key-length
# lane: a sort key after the four key words, a boundary compare, an output
# lane)
NAMES = dict(uniform_klen=False, seq32=True, key_words=4)
BITS_PER_KEY = 10  # examples/counter_service/options.py


@pytest.fixture(scope="module")
def one_chip():
    """SingleDeviceSharding on device 0 of a described v5e:2x2, with the
    persistent compile cache off for the module (a described-topology
    executable is written to it but cannot be read back without a chip:
    the next run would warn and recompile)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    assert topo.devices[0].platform == "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(one_chip):
    def make(dims, dtype=U32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _kernel_lanes(shape, n, lead=()):
    """merge_resolve_kernel's eight inputs at capacity ``n``."""
    return (shape(lead + (n, 6)), shape(lead + (n,)), shape(lead + (n,)),
            shape(lead + (n,)), shape(lead + (n,)), shape(lead + (n, 2)),
            shape(lead + (n,)), shape(lead + (n,), jnp.bool_))


def test_merge_resolve_lax_compiles(shape):
    """The per-DB program (TpuCompactionBackend.merge_runs_to_files →
    chunked.run_kernel_arrays): one shard, uint64-add."""
    compiled = merge_resolve_kernel.lower(
        *_kernel_lanes(shape, 8192), merge_kind=MergeKind.UINT64_ADD,
        drop_tombstones=True, **FAST).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("flags", [FAST, NAMES],
                         ids=["one_key_length", "key_length_lane"])
def test_service_pipeline_group8_compiles(shape, monkeypatch, flags):
    """The batched post-load program (compact_dbs_batched): the service's
    own vmapped merge-resolve + bloom pipeline at its group size 8, for
    keys of one length and with the key-length lane (keys of differing
    length: ``NAMES`` below)."""
    from rocksplicator_tpu.tpu.compaction_service import TpuCompactionService

    # the platform rule reads the env; conftest set it, keep it explicit
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    n = 2048
    fn = TpuCompactionService()._pipeline(
        MergeKind.UINT64_ADD, True, num_words_for(n, BITS_PER_KEY), **flags)
    compiled = fn.lower(*_kernel_lanes(shape, n, lead=(8,))).compile()
    assert compiled.memory_analysis() is not None


def test_service_index_pipeline_group8_compiles(shape, monkeypatch):
    """The record deployment's program (rec1k_32x20k: 1 KB values, no
    operator): sorts and resolve vmapped over 8 shards with one row-index
    lane, then each shard's 256-word rows gathered from a buffer of its
    own. The chip's compiler keeps the move a row gather (no per-word
    lowering) and adds no scratch memory of the values' size."""
    from rocksplicator_tpu.tpu.compaction_service import (
        PIPELINE_PROGRAM, PIPELINE_PROGRAM_INDEX, TpuCompactionService)

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    n, words = 512, 256
    fn = TpuCompactionService()._pipeline(
        MergeKind.NONE, True, num_words_for(n, BITS_PER_KEY), **FAST,
        val_words=words)
    lanes = list(_kernel_lanes(shape, n, lead=(8,)))
    lanes[5] = tuple(shape((n, words)) for _ in range(8))
    lowered = fn.lower(*lanes)
    assert PIPELINE_PROGRAM in PIPELINE_PROGRAM_INDEX
    assert "@jit_" + PIPELINE_PROGRAM_INDEX in lowered.as_text()[:200]
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count(f"u32[{n},{words}]") and " gather(" in text
    values = 8 * n * words * 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < values // 4
    assert mem.output_size_in_bytes < values + values // 4


# sha256 of the cells' programs as lowered (the text the persistent
# compile cache is keyed on) at the cells' own shapes, group 8, capacity
# 32,768: PR 31's readings, which PR 32 (values cross the seam on the pool
# threads) had to leave as they were, to the byte. jax 0.9.0; after an
# upgrade of jax, or a PR that MEANS to change a program, read them anew.
# The third (PR 35): counter_names_64x15k's, with the key-length lane
# (``NAMES`` above), values riding.
CELL_PROGRAMS = {
    "counter_64x20k": (
        MergeKind.UINT64_ADD, 2, FAST,
        "86313af6e2a4f28e227ffa9171c4e8429c83d307fcfec4f7a552d2667a2d3827"),
    "rec1k_32x20k": (
        MergeKind.NONE, 256, FAST,
        "b83ab464fc243d531ca85f7da0acfd0585f815ccfed7c0cff93dc1639083672b"),
    "counter_names_64x15k": (
        MergeKind.UINT64_ADD, 2, NAMES,
        "93019a9146520f0b6b49f5f795dd92e609f1bdf421ea76b3199b8adc96908651"),
}


@pytest.mark.parametrize("cell", sorted(CELL_PROGRAMS))
def test_cell_program_text_is_the_accepted_one(cell, monkeypatch):
    """Lowered here for the CPU (no topology, nothing compiled): a
    change of one byte is a cold compile of 60-90 s inside the first
    ingest RPC of every deployed process, and a new persistent-cache
    key on the chip."""
    import hashlib

    from rocksplicator_tpu.ops.compaction_kernel import value_path
    from rocksplicator_tpu.tpu.compaction_service import TpuCompactionService

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    kind, words, flags, accepted = CELL_PROGRAMS[cell]
    n, group = 32768, 8

    def lane(*dims, dtype=U32):
        return jax.ShapeDtypeStruct(dims, dtype)

    values = lane(group, n, words)
    if value_path(kind, words) == "index":
        values = tuple(lane(n, words) for _ in range(group))
    fn = TpuCompactionService()._pipeline(
        kind, True, num_words_for(n, BITS_PER_KEY), **flags, val_words=words)
    text = fn.lower(
        lane(group, n, 6), lane(group, n), lane(group, n), lane(group, n),
        lane(group, n), values, lane(group, n),
        lane(group, n, dtype=jnp.bool_)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == accepted


def test_bloom_build_compiles(shape):
    """The per-output-file bloom (one 16,384-key shard = one file)."""
    from rocksplicator_tpu.ops.bloom_tpu import bloom_build_tpu

    n = 16384
    compiled = bloom_build_tpu.lower(
        shape((n, 6)), shape((n,)), shape((n,), jnp.bool_),
        num_words=num_words_for(n, BITS_PER_KEY)).compile()
    assert compiled.memory_analysis() is not None


def test_planar_encode_and_checksums_compile(shape):
    from rocksplicator_tpu.ops.block_encode import (encode_planar_words_tpu,
                                                    planar_checksums_tpu)

    n, block_entries = 16384, 1024
    enc = encode_planar_words_tpu.lower(
        shape((n, 6)), shape((n,)), shape((n,)), shape((n,)),
        shape((n, 2)), klen=16, vlen=8, seq32=True,
        block_entries=block_entries).compile()
    (words,) = jax.tree_util.tree_leaves(enc.out_info)
    assert words.shape[0] == n // block_entries
    chk = planar_checksums_tpu.lower(shape(words.shape)).compile()
    assert chk.memory_analysis() is not None


def test_graft_entry_forward_compiles(shape):
    """The driver-facing single-chip step: CompactionModel.forward with
    the planar sink stages, at entry()'s own shapes."""
    import __graft_entry__ as graft

    forward, example_args = graft.entry()
    compiled = jax.jit(forward).lower(
        *(shape(a.shape, a.dtype) for a in example_args)).compile()
    assert compiled.memory_analysis() is not None
