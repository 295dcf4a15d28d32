"""Compaction backends: TPU kernel and vectorized-numpy CPU baseline.

``TpuCompactionBackend`` implements the storage engine's CompactionBackend
seam with the ops/compaction_kernel pipeline; anything the fixed-shape
representation can't express (keys over 48 B, values over
``device_value_bytes_max``, custom merge operators) falls back to the CPU
heap-merge, mirroring the north star's "fall back to CPU on kernel
inapplicability". Keys up to 24 B take six key lanes, longer ones up to
48 B twelve. Values: 8 bytes under the uint64-add operator (they ride
both sorts and the fold rewrites them); with no operator, up to
``RIDE_MAX_VAL_WORDS`` words ride and wider ones, up to
``DEVICE_VALUE_BYTES_MAX`` bytes, take the kernel's index path (a row
index rides, the values are moved once), of one width or of differing
widths in one shard (each row's ``val_len`` rides). The array sink
(``merge_runs_to_files``) declines a wider shard before the kernel; the
tuple path (``merge_runs``) packs 8-byte values only and hands anything
else to the CPU.

``NumpyCompactionBackend`` is the honest vectorized CPU baseline the bench
compares against (np.lexsort + reduceat segment folds — the best a CPU
does without hand-written SIMD).
"""

from __future__ import annotations

import logging
import os
from typing import Iterable, Iterator, List, Optional, Tuple

import numpy as np

from ..storage.compaction import (CompactionBackend, CpuCompactionBackend,
                                  Entry, record_host_fallback)
from ..storage.merge import MergeOperator, UInt64AddOperator
from ..storage.native_compaction import lanes_decline_reason
from ..ops.compaction_kernel import MergeKind, merge_resolve_kernel
from ..ops.kv_format import (KEY_WORDS, KVBatch, UnsupportedBatch,
                             fast_flags, pack_entries, unpack_entries)
from ..utils.stats import Stats, tagged

log = logging.getLogger(__name__)

_PUT, _DELETE, _MERGE = 1, 2, 3

# Boundary between the single-shot kernel and the hierarchical chunked
# merge (tpu/chunked.py): batches up to this size launch once; larger ones
# fold per-run chunks then summaries at this fixed launch shape.
MAX_TPU_ENTRIES = 1 << 22


# Widest value, in bytes, that the device path takes without a merge
# operator (the index path of ops/compaction_kernel.py: the width costs
# no sort operand, only bytes moved once). The widest that has run on the
# chip: a group of 8 shards of 8,192 rows of 4 KB, 4.14 ms a launch
# (tools/value_path_bench.py, PR 29); a launch holds its values twice
# (group x capacity x width, in and out), 0.54 GB at the 1 KB
# deployment's (8, 32768).
DEVICE_VALUE_BYTES_MAX = 4096


def device_value_bytes_max(merge_operator: Optional[MergeOperator]) -> int:
    """The widest value, in bytes, of a shard the device path compacts
    for a DB with this merge operator; 0 where it takes none. The
    uint64-add fold is defined on 8-byte values; a custom operator runs
    Python."""
    if merge_operator is None:
        return DEVICE_VALUE_BYTES_MAX
    return 8 if isinstance(merge_operator, UInt64AddOperator) else 0


def device_decline_reason(lanes: Optional[dict],
                          merge_operator: Optional[MergeOperator],
                          ) -> Optional[str]:
    """None when the device path compacts a DB with this operator whose
    runs read as these concatenated ``lanes``, else why it declines them
    to the host path, before any program is built. THE rule of both
    device doors (``merge_runs_to_files``, ``compact_dbs_batched``):

    - ``custom_operator``: the operator runs Python;
    - ``value_width``: a value wider than ``device_value_bytes_max``
      (each door counts it under ``tpu.host_fallbacks reason=value_width``);
    - what no array path expresses (``lanes_decline_reason``):
      ``merge_without_operator``, ``key_width`` (a key over 48 bytes;
      keys of differing length up to that are taken),
      ``value_width_mixed`` (values of differing widths under the
      operator; with none they are taken), ``uint64add_width``.

    ``lanes=None`` asks about the operator alone (a plan costs a flush)."""
    limit = device_value_bytes_max(merge_operator)
    if limit == 0:
        return "custom_operator"
    if lanes is None:
        return None
    if len(lanes["val_len"]) and int(lanes["val_len"].max()) > limit:
        return "value_width"
    return lanes_decline_reason(lanes, merge_operator)


def _device_bloom_builder(bits_per_key: int, trace: Optional[dict] = None):
    """``write_resolved_lanes``' bloom builder for the device doors: one
    output file's bloom built on the device, sized from the file's own
    count and the DB's ``bits_per_key`` (a launch's own bloom is sized by
    the group's padded capacity and the service's default bits: reusing
    it would write a max-shard-sized bloom into every small shard of a
    mixed batch). A ``tpu.bloom`` span (``rows``) each, under ``trace``
    on a pool thread."""
    import jax.numpy as jnp

    from ..observability.span import start_span
    from ..ops.bloom_tpu import bloom_build_tpu
    from ..storage.bloom import num_words_for

    def build(sub: dict, n: int) -> np.ndarray:
        with start_span("tpu.bloom", remote=trace, rows=n):
            # the hash reads a key's first 24 bytes and its length
            # (storage/bloom.py): six words, whatever the lanes' width
            return np.asarray(bloom_build_tpu(
                jnp.asarray(sub["key_words_le"][:, :KEY_WORDS]),
                jnp.asarray(sub["key_len"]),
                jnp.asarray(np.ones(n, dtype=bool)),
                num_words=num_words_for(n, bits_per_key),
            ))

    return build


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def require_accelerator() -> str:
    """The platform jax resolved — which must be ``tpu``. A host with no
    chip would otherwise run "the TPU backend" as XLA-CPU under the
    TPU's name. The one exception is an EXPLICIT ``JAX_PLATFORMS=cpu``
    (the test suite's setting): whoever set that asked for the CPU."""
    import jax

    platform = jax.default_backend()
    if platform != "tpu" and (
            os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu"):
        raise RuntimeError(
            f"TPU compaction was asked for but jax resolved platform "
            f"{platform!r}; refusing to run the device path on it (set "
            f"JAX_PLATFORMS=cpu to run it on the CPU on purpose)")
    return platform


class TpuCompactionBackend(CompactionBackend):
    name = "tpu"
    runs_on_device = True
    supports_subcompactions = True
    supports_memory_budget = True

    def __init__(self, fallback: Optional[CompactionBackend] = None):
        # inapplicable batches (custom operators, >24B keys on the tuple
        # path's packer, >48B on the array paths, operand
        # chains without an operator) take the VECTORIZED cpu path: the
        # lexsort+reduceat numpy pipeline, itself falling back to the
        # streaming heap-merge for what lanes can't express
        self._fallback = fallback or NumpyCompactionBackend()
        import jax

        self._jax = jax
        self.platform = require_accelerator()
        Stats.get().incr(tagged("tpu.backend_platform",
                                platform=self.platform))

    def merge_runs(
        self,
        runs: List[Iterable[Entry]],
        merge_op: Optional[MergeOperator],
        drop_tombstones: bool,
    ) -> Iterator[Entry]:
        if merge_op is not None and not isinstance(merge_op, UInt64AddOperator):
            # custom operators run arbitrary Python — CPU path
            return self._fallback.merge_runs(runs, merge_op, drop_tombstones)
        run_lists: List[List[Entry]] = [list(run) for run in runs]
        total = sum(len(r) for r in run_lists)
        if total == 0:
            return iter(())
        if merge_op is not None and any(
            vtype != _DELETE and len(value) != 8
            for run in run_lists for _k, _s, vtype, value in run
        ):
            # uint64-add fold semantics require 8-byte values (a lone
            # non-8-byte PUT must stay verbatim; the fold would rewrite
            # it to the parsed-as-zero operand sum) — stream path
            return self._fallback.merge_runs(
                run_lists, merge_op, drop_tombstones)

        def cpu():
            entries = [e for run in run_lists for e in run]
            return self._fallback.merge_runs(
                [sorted(entries, key=lambda e: (e[0], -e[1]))],
                merge_op, drop_tombstones,
            )

        if total > MAX_TPU_ENTRIES:
            # hierarchical chunked merge: per-run folding then summary
            # merging, each launch at one fixed shape (tpu/chunked.py)
            result = self._chunked(run_lists, merge_op, drop_tombstones)
            if result is None:
                return cpu()
            return iter(result)
        entries = [e for run in run_lists for e in run]
        try:
            batch = pack_entries(entries, capacity=_next_pow2(total))
        except UnsupportedBatch as e:
            log.debug("TPU compaction fallback: %s", e)
            return cpu()
        if merge_op is None and bool((batch.vtype == _MERGE).any()):
            # MERGE records without an operator: the reference preserves the
            # unresolved operand chain — only the CPU path can express that.
            # (Checked on the packed vtype lane — a numpy any(), not a
            # Python walk of up to 4M tuples.)
            return cpu()
        result = self._run_batch(batch, merge_op, drop_tombstones)
        if result is None:  # kernel flagged limb-overflow risk
            return cpu()
        return iter(result)

    def _chunked(self, runs, merge_op, drop_tombstones) -> Optional[List[Entry]]:
        from .chunked import chunked_merge
        from ..ops.compaction_kernel import MergeKind as MK

        kind = (
            MK.UINT64_ADD if isinstance(merge_op, UInt64AddOperator)
            else MK.NONE
        )
        try:
            run_batches = [pack_entries(run) for run in runs]
        except UnsupportedBatch as e:
            log.debug("TPU chunked fallback: %s", e)
            return None
        if kind is MK.NONE and any(
            bool((b.vtype[: b.num_valid()] == _MERGE).any())
            for b in run_batches
        ):
            return None
        result = chunked_merge(
            run_batches, kind, drop_tombstones,
            chunk_entries=MAX_TPU_ENTRIES // 4,
            launch_entries=MAX_TPU_ENTRIES,
        )
        if result is None:
            return None
        arrays, count = result
        return unpack_entries(
            arrays["key_words_be"], arrays["key_len"], arrays["seq_hi"],
            arrays["seq_lo"], arrays["vtype"], arrays["val_words"],
            arrays["val_len"], count,
        )

    def merge_runs_to_files(
        self,
        runs: List,
        merge_op: Optional[MergeOperator],
        drop_tombstones: bool,
        path_factory,
        block_bytes: int,
        compression: int,
        bits_per_key: int,
        target_file_bytes: int,
        max_subcompactions: int = 1,
        io_budget=None,
        mem_tracker=None,
        memory_budget_bytes: int = 0,
    ) -> Optional[List[Tuple[str, dict]]]:
        """Merge + write output SSTs with the vectorized array sink and
        kernel-built blooms, splitting at ``target_file_bytes``. Inputs may
        be SSTReader objects — sink-written uniform files decode straight
        to lanes (no per-entry Python on the SOURCE side either) — or
        entry iterables. Returns [(path, props)] — empty list for an
        all-tombstoned result — or None → tuple path.

        Inputs whose projected lane image exceeds the compaction memory
        budget stream through the chunked bounded-memory merge with the
        DEVICE chunk resolver — double-buffered chunks: decode chunk
        N+1 on host while chunk N's lanes transfer back from device
        (the resolve itself still syncs at submit; see TpuChunkResolver)
        (storage/stream_merge.py + compaction_service.TpuChunkResolver).

        ``max_subcompactions > 1``: an in-RAM job splits into disjoint
        key-range slices resolved as places of the fixed group launch
        (tpu/compaction_service.resolve_slices_batched) — k smaller
        sorts, eight a launch, instead of one pow2(total) sort.
        ``io_budget`` paces the output file writes. What the door takes
        is ``device_decline_reason``'s to say; the runs are read and the
        files written by the host array path's own reader and writer
        (storage/native_compaction.py)."""
        from ..storage.native_compaction import (read_runs_as_lanes,
                                                 write_resolved_lanes)
        from ..storage.stream_merge import maybe_stream_merge
        from .chunked import run_kernel_arrays
        from .compaction_service import TpuChunkResolver

        if device_decline_reason(None, merge_op) is not None:
            return None
        streamed = maybe_stream_merge(
            runs, merge_op, drop_tombstones, path_factory, block_bytes,
            compression, bits_per_key, target_file_bytes,
            io_budget=io_budget, mem_tracker=mem_tracker,
            memory_budget_bytes=memory_budget_bytes,
            resolver=TpuChunkResolver(),
        )
        if streamed is not None:
            return streamed
        # (larger than one launch: the chunked/CPU paths return entries,
        # not files)
        read = read_runs_as_lanes(runs, None, max_entries=MAX_TPU_ENTRIES)
        if read is None:
            return None
        parts, lanes, total, _vw = read
        reason = device_decline_reason(lanes, merge_op)
        if reason is not None:
            if reason == "value_width":
                record_host_fallback(
                    reason, f"{int(lanes['val_len'].max())}-byte values")
            return None
        kind = (
            MergeKind.UINT64_ADD if isinstance(merge_op, UInt64AddOperator)
            else MergeKind.NONE
        )
        arrays = count = None
        if max_subcompactions > 1:
            sliced = self._subcompact_arrays(
                parts, lanes, total, kind, drop_tombstones,
                max_subcompactions)
            if sliced is not None:
                arrays, count = sliced
        if arrays is None:
            all_valid = np.ones(total, dtype=bool)
            uniform_klen, seq32, key_words = fast_flags(
                lanes["key_len"], lanes["seq_hi"], all_valid)
            arrays, count = run_kernel_arrays(
                lanes, total, kind, drop_tombstones,
                pad_to=_next_pow2(total),
                uniform_klen=uniform_klen, seq32=seq32,
                key_words=key_words,
            )
        if arrays is None:
            return None
        if count == 0:
            return []  # fully compacted away — nothing to write
        # PLANAR output: the kernel's struct-of-array lanes ARE the block
        # planes (storage/planar.py) — no byte interleaving on either
        # side, ~29% smaller uncompressed than the row format
        return write_resolved_lanes(
            arrays, count, path_factory, block_bytes, compression,
            bits_per_key, target_file_bytes, io_budget=io_budget,
            build_bloom=_device_bloom_builder(bits_per_key))

    @staticmethod
    def _subcompact_arrays(parts, lanes, total, kind, drop_tombstones,
                           max_subcompactions):
        """Key-range subcompactions on the device: boundary keys from
        the planner every key-range cut shares (``plan_subcompactions``),
        the runs cut at them by the placement the served door uses too
        (``slice_lanes``), and ALL slices resolved as places of the
        fixed group launch. Returns (arrays, count) concatenated in
        boundary order — identical logical output to the single-shot
        kernel — or None to take the unsliced path."""
        from ..storage.native_compaction import (plan_subcompactions,
                                                 shard_klen, slice_lanes)
        from .compaction_service import resolve_slices_batched

        klen = shard_klen(lanes)
        bounds = plan_subcompactions(parts, total, max_subcompactions, klen)
        if not bounds:
            return None
        slices = slice_lanes(parts, bounds, klen)
        if not slices:
            return None
        per_slice = resolve_slices_batched(slices, kind, drop_tombstones)
        live = [(a, c) for a, c in per_slice if c]
        if not live:
            return {}, 0
        fields = list(live[0][0].keys())
        arrays = {
            f: np.concatenate([np.asarray(a[f]) for a, _c in live])
            for f in fields
        }
        return arrays, int(sum(c for _a, c in live))

    def _run_batch(
        self, batch: KVBatch, merge_op: Optional[MergeOperator],
        drop_tombstones: bool,
    ) -> Optional[List[Entry]]:
        """None means the kernel flagged a condition (limb-overflow risk)
        requiring the CPU path."""
        jnp = self._jax.numpy
        kind = (
            MergeKind.UINT64_ADD if isinstance(merge_op, UInt64AddOperator)
            else MergeKind.NONE
        )
        uniform_klen, seq32, key_words = fast_flags(
            batch.key_len, batch.seq_hi, batch.valid)
        out = merge_resolve_kernel(
            jnp.asarray(batch.key_words_be),
            jnp.asarray(batch.key_len), jnp.asarray(batch.seq_hi),
            jnp.asarray(batch.seq_lo), jnp.asarray(batch.vtype),
            jnp.asarray(batch.val_words), jnp.asarray(batch.val_len),
            jnp.asarray(batch.valid),
            merge_kind=kind, drop_tombstones=drop_tombstones,
            uniform_klen=uniform_klen, seq32=seq32, key_words=key_words,
        )
        if bool(out["needs_cpu_fallback"]):
            return None
        return unpack_entries(
            np.asarray(out["key_words_be"]), np.asarray(out["key_len"]),
            np.asarray(out["seq_hi"]), np.asarray(out["seq_lo"]),
            np.asarray(out["vtype"]), np.asarray(out["val_words"]),
            np.asarray(out["val_len"]), int(out["count"]),
        )


class NumpyCompactionBackend(CompactionBackend):
    """Vectorized CPU implementation of the same algorithm (lexsort +
    reduceat). uint64add / no-operator semantics only; custom operators
    fall back like the TPU backend."""

    name = "numpy"

    def __init__(self, fallback: Optional[CompactionBackend] = None):
        self._fallback = fallback or CpuCompactionBackend()

    def merge_runs(self, runs, merge_op, drop_tombstones):
        if merge_op is not None and not isinstance(merge_op, UInt64AddOperator):
            return self._fallback.merge_runs(runs, merge_op, drop_tombstones)
        entries = [e for run in runs for e in run]
        if not entries:
            return iter(())

        def cpu():
            return self._fallback.merge_runs(
                [sorted(entries, key=lambda e: (e[0], -e[1]))],
                merge_op, drop_tombstones,
            )

        if merge_op is not None and any(
            vtype != _DELETE and len(value) != 8
            for _k, _s, vtype, value in entries
        ):
            # uint64-add fold semantics require 8-byte values (see
            # TpuCompactionBackend.merge_runs) — stream path
            return cpu()
        try:
            batch = pack_entries(entries)
        except UnsupportedBatch:
            return cpu()
        if merge_op is None and bool((batch.vtype == _MERGE).any()):
            return cpu()
        arrays, count = cpu_merge_resolve(
            batch, uint64_add=merge_op is not None,
            drop_tombstones=drop_tombstones,
        )
        return iter(unpack_entries(*arrays, count))


def cpu_merge_resolve(
    batch: KVBatch, uint64_add: bool, drop_tombstones: bool
) -> Tuple[tuple, int]:
    """Best-available CPU merge-resolve: the native C implementation
    (storage/native cpu_merge_resolve — packed-record sort + linear
    segment resolve) when the library is loaded, else the numpy path.
    Both are element-exact with the TPU kernel; parity is pinned in
    tests/test_native.py. The native record packs six key words: keys of
    twelve (over 24 bytes) take the numpy path."""
    from ..storage.native.binding import get_native

    lib = get_native()
    if (lib is None or not getattr(lib, "has_merge_resolve", False)
            or batch.key_words_be.shape[1] > KEY_WORDS):
        return numpy_merge_resolve(batch, uint64_add, drop_tombstones)
    valid_n = batch.num_valid()
    seq = (
        batch.seq_hi[:valid_n].astype(np.uint64) << np.uint64(32)
    ) | batch.seq_lo[:valid_n].astype(np.uint64)
    out_kw, out_klen, out_seq, out_vtype, out_vw, out_vlen, count = (
        lib.merge_resolve(
            batch.key_words_be[:valid_n], batch.key_len[:valid_n], seq,
            batch.vtype[:valid_n], batch.val_words[:valid_n],
            batch.val_len[:valid_n], uint64_add, drop_tombstones,
        )
    )
    out = (
        out_kw[:count], out_klen[:count],
        (out_seq[:count] >> np.uint64(32)).astype(np.uint32),
        (out_seq[:count] & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        out_vtype[:count].astype(batch.vtype.dtype), out_vw[:count],
        out_vlen[:count],
    )
    return out, count


def numpy_merge_resolve(
    batch: KVBatch, uint64_add: bool, drop_tombstones: bool
) -> Tuple[tuple, int]:
    """The kernel's algorithm in numpy (the CPU baseline)."""
    valid_n = batch.num_valid()
    kw = batch.key_words_be[:valid_n]
    klen = batch.key_len[:valid_n]
    seq = (batch.seq_hi[:valid_n].astype(np.uint64) << np.uint64(32)) | batch.seq_lo[
        :valid_n
    ].astype(np.uint64)
    vtype = batch.vtype[:valid_n]
    vw = batch.val_words[:valid_n]
    vlen = batch.val_len[:valid_n]

    # lexsort: last key has highest priority → (key words asc.., len, seq desc)
    order = np.lexsort(
        (~seq, klen) + tuple(kw[:, w] for w in range(kw.shape[1] - 1, -1, -1))
    )
    kw, klen, seq, vtype, vw, vlen = (
        kw[order], klen[order], seq[order], vtype[order], vw[order], vlen[order]
    )
    n = valid_n
    if n == 0:
        return (batch.key_words_be[:0], batch.key_len[:0], batch.seq_hi[:0],
                batch.seq_lo[:0], batch.vtype[:0], batch.val_words[:0],
                batch.val_len[:0]), 0

    new_key = np.ones(n, dtype=bool)
    if n > 1:
        same = np.all(kw[1:] == kw[:-1], axis=1) & (klen[1:] == klen[:-1])
        new_key[1:] = ~same
    bounds = np.flatnonzero(new_key)
    seg_ids = np.cumsum(new_key) - 1
    pos = np.arange(n)

    is_put = vtype == _PUT
    is_del = vtype == _DELETE
    is_merge = vtype == _MERGE
    is_base = is_put | is_del

    first_base_pos = np.minimum.reduceat(np.where(is_base, pos, n), bounds)
    fb = first_base_pos[seg_ids]
    operand_mask = is_merge & (pos < fb)
    has_op = np.maximum.reduceat(operand_mask.astype(np.int8), bounds).astype(bool)
    base_exists = first_base_pos < n
    base_is_put = np.zeros(len(bounds), dtype=bool)
    base_is_put[base_exists] = is_put[first_base_pos[base_exists]]
    base_is_del = np.zeros(len(bounds), dtype=bool)
    base_is_del[base_exists] = is_del[first_base_pos[base_exists]]

    sums = None
    if uint64_add:
        if vw.shape[1] > 1:
            vals = vw[:, 0].astype(np.int64) | (vw[:, 1].astype(np.int64) << 32)
        else:
            vals = vw[:, 0].astype(np.int64)
        # parity with UInt64AddOperator._parse: non-8-byte values parse as 0
        contrib = (operand_mask | (is_base & (pos == fb) & is_put)) & (vlen == 8)
        # the fold itself (wraparound semantics) is the shared
        # storage/merge implementation — single source of truth with the
        # scalar operator
        from ..storage.merge import uint64add_segment_sums

        sums = uint64add_segment_sums(vals, contrib, bounds)

    # representative = first row of each segment
    rep_idx = bounds
    out_kw = kw[rep_idx]
    out_klen = klen[rep_idx]
    out_seq = seq[rep_idx]
    out_vtype = vtype[rep_idx].copy()
    out_vw = vw[rep_idx].copy()
    out_vlen = vlen[rep_idx].copy()

    if uint64_add:
        pure_operands = has_op & ~base_is_put & ~base_is_del
        resolved_put = base_is_put | (has_op & base_is_del)
        fold_mask = resolved_put | pure_operands
        out_vw[fold_mask, 0] = (sums[fold_mask] & 0xFFFFFFFF).astype(np.uint32)
        if out_vw.shape[1] > 1:
            out_vw[fold_mask, 1] = (
                (sums[fold_mask] >> 32) & 0xFFFFFFFF
            ).astype(np.uint32)
        out_vlen[fold_mask] = 8
        out_vtype[resolved_put] = _PUT
        out_vtype[pure_operands] = _PUT if drop_tombstones else _MERGE
        dropped = base_is_del & ~has_op
    else:
        dropped = out_vtype == _DELETE

    keep = ~dropped if drop_tombstones else np.ones(len(bounds), dtype=bool)
    out = (
        out_kw[keep], out_klen[keep],
        (out_seq[keep] >> np.uint64(32)).astype(np.uint32),
        (out_seq[keep] & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        out_vtype[keep], out_vw[keep], out_vlen[keep],
    )
    return out, int(keep.sum())
