"""CompactionModel — the framework's flagship jittable computation.

This framework's "model" is not a neural net: the forward step is the
fused merge-resolve + bloom-build pipeline over a fixed-capacity batch of
KV entries (one shard's compaction job). It is pure, static-shaped, and
jit/vmap/shard_map-composable — the unit the driver compile-checks and the
bench times.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import numpy as np

from ..ops.bloom_tpu import bloom_build_tpu
from ..ops.compaction_kernel import MergeKind, merge_resolve_kernel
from ..ops.kv_format import KEY_WORDS
from ..storage.bloom import num_words_for

_PUT, _DELETE, _MERGE = 1, 2, 3


@dataclass
class CompactionModel:
    """Configuration of the flagship pipeline."""

    capacity: int = 1 << 16        # entries per shard batch
    val_words: int = 2             # 8-byte counter values
    bits_per_key: int = 10
    merge_kind: MergeKind = MergeKind.UINT64_ADD
    drop_tombstones: bool = True
    # caller-verified fast-path promises (see ops/compaction_kernel):
    # synthetic/counter workloads have one key width and 32-bit seqs;
    # key_words bounds the u32 lanes that actually carry key bytes
    uniform_klen: bool = False
    seq32: bool = False
    key_words: int = KEY_WORDS
    # (row_klen, row_vlen) enables ON-DEVICE block encoding: forward also
    # emits the SST entry-row byte matrix (ops/block_encode.py), making
    # the flagship pipeline merge→bloom→bytes with no host byte-work
    emit_rows: bool = False
    row_klen: int = 16
    row_vlen: int = 8
    # PLANAR alternative (the production sink format): emit block plane
    # words + word-domain checksums instead of interleaved rows — on this
    # hardware the row matrix is the most expensive layout op in the
    # pipeline while planar is concatenation (PERF.md)
    emit_planar: bool = False
    planar_block_entries: int = 1024

    @property
    def num_bloom_words(self) -> int:
        return num_words_for(self.capacity, self.bits_per_key)

    def forward(
        self,
        key_words_be, key_len,
        seq_hi, seq_lo, vtype, val_words, val_len, valid,
    ) -> Dict:
        """One shard's compaction: merged entries + bloom + count.
        (LE key lanes are byteswap-derived on device — not an input.)"""
        import jax
        import jax.numpy as jnp

        out = merge_resolve_kernel(
            key_words_be, key_len, seq_hi, seq_lo,
            vtype, val_words, val_len, valid,
            merge_kind=self.merge_kind,
            drop_tombstones=self.drop_tombstones,
            uniform_klen=self.uniform_klen, seq32=self.seq32,
            key_words=self.key_words,
        )
        out_valid = jax.lax.iota(jnp.int32, key_len.shape[0]) < out["count"]
        out["bloom"] = bloom_build_tpu(
            out["key_words_le"], out["key_len"], out_valid,
            num_words=self.num_bloom_words,
        )
        if self.emit_rows:
            from ..ops.block_encode import encode_rows_tpu

            out["rows"] = encode_rows_tpu(
                out["key_words_be"], out["seq_hi"], out["seq_lo"],
                out["vtype"], out["val_words"],
                klen=self.row_klen, vlen=self.row_vlen,
            )
        if self.emit_planar:
            from ..ops.block_encode import (encode_planar_words_tpu,
                                            planar_checksums_tpu)

            words = encode_planar_words_tpu(
                out["key_words_be"], out["seq_hi"], out["seq_lo"],
                out["vtype"], out["val_words"],
                klen=self.row_klen, vlen=self.row_vlen, seq32=self.seq32,
                block_entries=self.planar_block_entries,
            )
            out["planar_words"] = words
            out["planar_chk"] = planar_checksums_tpu(words)
        return out

    def example_args(self, seed: int = 0) -> Tuple:
        """Numpy example inputs matching forward()'s signature."""
        b = synth_counter_batch(self.capacity, seed=seed,
                                val_words=self.val_words)
        return (
            b["key_words_be"], b["key_len"],
            b["seq_hi"], b["seq_lo"], b["vtype"], b["val_words"],
            b["val_len"], b["valid"],
        )


def synth_counter_batch_jax(
    n: int,
    key_space: int | None = None,
    seed: int = 0,
    merge_frac: float = 0.6,
    delete_frac: float = 0.05,
    val_words: int = 2,
    key_bytes: int = 16,
    start_seq: int = 1,
):
    """Device-side synth_counter_batch: same shapes/distribution, built
    with the JAX PRNG so benchmark inputs can be GENERATED ON THE DEVICE
    instead of shipped over host↔device (a 32-shard batch is 222 MB of
    lanes). Exact bits differ from the numpy
    generator (threefry vs PCG64) — callers compare throughput across
    distribution-matched, not bit-identical, data."""
    import jax
    import jax.numpy as jnp

    key_space = key_space or max(1, n // 8)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    key_ids = jax.random.randint(
        k1, (n,), 0, key_space, dtype=jnp.uint32)
    # numpy layout: first 8 key bytes are the big-endian u64 id, so BE
    # word0 is the (zero) high half and word1 the id; remaining lanes 0
    zeros = jnp.zeros((n,), jnp.uint32)
    kw_be = jnp.stack(
        [zeros, key_ids, zeros, zeros, zeros, zeros], axis=1)
    from ..ops.compaction_kernel import bswap32

    kw_le = bswap32(kw_be)
    r = jax.random.uniform(k2, (n,))
    vtype = jnp.where(
        r < merge_frac, jnp.uint32(_MERGE),
        jnp.where(r < merge_frac + delete_frac, jnp.uint32(_DELETE),
                  jnp.uint32(_PUT)),
    )
    vals = jax.random.randint(k3, (n,), 0, 1000, dtype=jnp.uint32)
    vals = jnp.where(vtype == _DELETE, jnp.uint32(0), vals)
    vw = jnp.zeros((n, val_words), jnp.uint32).at[:, 0].set(vals)
    seqs = start_seq + jnp.arange(n, dtype=jnp.uint32)
    return {
        "key_words_be": kw_be,
        "key_words_le": kw_le,
        "key_len": jnp.full((n,), jnp.uint32(key_bytes)),
        "seq_hi": jnp.zeros((n,), jnp.uint32),
        "seq_lo": seqs,
        "vtype": vtype,
        "val_words": vw,
        "val_len": jnp.where(vtype == _DELETE, jnp.uint32(0),
                             jnp.uint32(8)),
        "valid": jnp.ones((n,), bool),
    }


def synth_counter_batch(
    n: int,
    key_space: int | None = None,
    seed: int = 0,
    merge_frac: float = 0.6,
    delete_frac: float = 0.05,
    val_words: int = 2,
    key_bytes: int = 16,
    start_seq: int = 1,
) -> Dict[str, np.ndarray]:
    """Vectorized synthetic counter-workload batch (the bench generator).

    Keys: ``key_bytes``-long, first 8 bytes = big-endian key id drawn from
    ``key_space`` distinct ids (power-law-ish duplicates exercise the merge
    fold), remaining bytes zero. Ops: MERGE bumps, PUTs, a few DELETEs.
    """
    rng = np.random.default_rng(seed)
    key_space = key_space or max(1, n // 8)
    key_ids = rng.integers(0, key_space, size=n, dtype=np.uint64)
    key_buf = np.zeros((n, 24), dtype=np.uint8)
    key_buf[:, :8] = key_ids.astype(">u8").view(np.uint8).reshape(n, 8)
    r = rng.random(n)
    vtype = np.where(
        r < merge_frac, _MERGE, np.where(r < merge_frac + delete_frac, _DELETE, _PUT)
    ).astype(np.uint32)
    vals = rng.integers(0, 1000, size=n, dtype=np.uint64)
    vals = np.where(vtype == _DELETE, 0, vals)
    val_buf = np.zeros((n, val_words * 4), dtype=np.uint8)
    val_buf[:, :8] = vals.astype("<u8").view(np.uint8).reshape(n, 8)
    seqs = np.arange(start_seq, start_seq + n, dtype=np.uint64)
    return {
        "key_words_be": key_buf.view(">u4").astype(np.uint32).reshape(n, 6),
        "key_words_le": key_buf.view("<u4").reshape(n, 6).copy(),
        "key_len": np.full(n, key_bytes, dtype=np.uint32),
        "seq_hi": (seqs >> np.uint64(32)).astype(np.uint32),
        "seq_lo": (seqs & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        "vtype": vtype,
        "val_words": val_buf.view("<u4").reshape(n, val_words).copy(),
        "val_len": np.where(vtype == _DELETE, 0, 8).astype(np.uint32),
        "valid": np.ones(n, dtype=bool),
    }
