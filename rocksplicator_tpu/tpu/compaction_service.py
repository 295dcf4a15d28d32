"""TpuCompactionService: shard-batched compaction jobs on the device.

North star (BASELINE.json): "a TpuCompactionService is registered by
ApplicationDBManager so that L0→Ln compaction jobs and load_sst ingests
ship their key-value blocks to a TPU sidecar, where kernels run k-way
merge-sort, bloom construction, and block encoding as batched ops over
shards."

Two integration levels:
- ``install_on_options(options)`` — per-DB: plugs a TpuCompactionBackend
  into the engine's CompactionBackend seam (compact_range / L0→L1 jobs).
- ``compact_shard_batch(batches)`` — job-level: many shards' runs compact
  in ONE vmapped kernel launch (the 1000-shard load_sst path), each shard
  padded to a common capacity; returns per-shard merged entries + bloom
  words + counts.

The served path is ``compact_dbs_batched`` (the post-load compaction of
many DBs): fixed ``(group_size, capacity)`` launches through
``compact_shard_stream``, a shard of more rows than ``PLACE_ROWS_MAX``
cut by key range into several places of the same launch.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
from itertools import zip_longest
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..observability.context import current_span, wire_context
from ..observability.span import start_span
from ..storage.bloom import num_words_for
from ..storage.engine import DBOptions
from ..ops.bloom_tpu import bloom_build_tpu
from ..ops.compaction_kernel import (MergeKind, gather_value_rows,
                                     gather_value_stream,
                                     merge_resolve_kernel,
                                     merge_resolve_rows, value_path)
from ..ops.kv_format import KEY_WORDS, KVBatch, fast_flags, unpack_entries
from ..storage.compaction import record_host_fallback
from ..storage.native_compaction import (KeyGroupOverSlice,
                                         plan_subcompactions,
                                         read_runs_as_lanes, shard_klen,
                                         slice_lanes, write_resolved_lanes)
from ..storage.planar import PLANAR_MAX_KLEN, key_shape, value_shape
from ..testing import failpoints as fp
from ..utils.stats import Stats
from .backend import (DEVICE_VALUE_BYTES_MAX, TpuCompactionBackend,
                      _device_bloom_builder, _next_pow2,
                      device_decline_reason, require_accelerator)
from .format import (StreamOverrun, ValueStream, stream_may_carry,
                     stream_offsets, with_value_rows)
# what the device path takes is asked HERE by the record deployment's
# driver (chipbench/drivers/refresh_rec.py)
from .backend import device_value_bytes_max  # noqa: F401

log = logging.getLogger(__name__)

# jit(vmap(...)) of the merge-resolve + bloom pipeline: XLA module
# "jit_one_shard" (tests/test_tracing.py pins both programs' names)
PIPELINE_PROGRAM = "one_shard"
# the index path's pipeline (values moved once, inside the same module):
# its name holds the other's, so one reader finds either
PIPELINE_PROGRAM_INDEX = "one_shard_index"
_GROUP_LANES = (
    "key_words_be", "key_len", "seq_hi",
    "seq_lo", "vtype", "val_words", "val_len", "valid",
)
# The words a row of a place's capacity bucket that its value stream
# holds (``ValueStream``: 524,288 words, 2 MB, at 32,768 rows); a place
# whose values need more keeps its rows (``compact.value_move.overrun``)
STREAM_WORDS_PER_ROW = 16


class TpuCompactionService:
    _instance: Optional["TpuCompactionService"] = None
    _instance_lock = threading.Lock()

    def __init__(self, bits_per_key: int = 10):
        require_accelerator()
        self._bits_per_key = bits_per_key
        self._vmapped_cache: Dict[tuple, object] = {}
        self._zero_rows: Dict[tuple, object] = {}  # by shape

    @classmethod
    def instance(cls) -> "TpuCompactionService":
        if cls._instance is None:
            with cls._instance_lock:
                if cls._instance is None:
                    cls._instance = cls()
        return cls._instance

    # ------------------------------------------------------------------
    # per-DB integration (engine CompactionBackend seam)
    # ------------------------------------------------------------------

    @staticmethod
    def install_on_options(options: DBOptions) -> DBOptions:
        """Route this DB's compactions through the TPU backend."""
        options.compaction_backend = TpuCompactionBackend()
        return options

    # ------------------------------------------------------------------
    # job-level batched API (the load_sst / compaction-storm path)
    # ------------------------------------------------------------------

    def _pipeline(self, merge_kind: MergeKind, drop_tombstones: bool,
                  num_words: int, uniform_klen: bool = False,
                  seq32: bool = False, key_words: int = KEY_WORDS,
                  val_words: int = 0, stream: bool = False):
        """The jitted pipeline of one launch group. ``val_words`` (the
        group's value width in u32 words) chooses the value path as the
        kernel does (``value_path``). Riding: every lane stacked
        ``(group, N, ...)``. Index: ``val_words`` goes in and comes out
        as a TUPLE of per-shard ``(N, W)`` buffers, so that only real
        shards' values cross the host-device seam; with ``stream`` a
        tuple of per-shard word streams (``ValueStream``) in, and out as
        ``val_stream`` (``gather_value_stream``)."""
        index = value_path(merge_kind, val_words) == "index"
        key = (merge_kind, drop_tombstones, num_words, uniform_klen, seq32,
               key_words, index, index and stream)
        fn = self._vmapped_cache.get(key)
        if fn is None:
            flags = dict(drop_tombstones=drop_tombstones,
                         uniform_klen=uniform_klen, seq32=seq32,
                         key_words=key_words)

            def with_bloom(out, n):
                out_valid = jax.lax.iota(jax.numpy.int32, n) < out["count"]
                out["bloom"] = bloom_build_tpu(
                    out["key_words_le"], out["key_len"], out_valid,
                    num_words=num_words,
                )
                return out

            def one_shard(kwbe, klen, shi, slo, vt, vw, vl, valid):
                out = merge_resolve_kernel(
                    kwbe, klen, shi, slo, vt, vw, vl, valid,
                    merge_kind=merge_kind, **flags)
                return with_bloom(out, klen.shape[0])

            def one_shard_rows(kwbe, klen, shi, slo, vt, vl, valid):
                out = merge_resolve_rows(
                    kwbe, klen, shi, slo, vt, vl, valid, **flags)
                return with_bloom(out, klen.shape[0])

            def one_shard_index(kwbe, klen, shi, slo, vt, vws, vl, valid):
                out = jax.vmap(one_shard_rows)(
                    kwbe, klen, shi, slo, vt, vl, valid)
                rows = out.pop("val_row")
                out["val_words"] = tuple(
                    gather_value_rows(vw, rows[s], out["count"][s])
                    for s, vw in enumerate(vws))
                return out

            def one_shard_stream(kwbe, klen, shi, slo, vt, streams, vl,
                                 valid):
                out = jax.vmap(one_shard_rows)(
                    kwbe, klen, shi, slo, vt, vl, valid)
                moved = jax.vmap(gather_value_stream)(
                    jax.numpy.stack(streams), vl, out.pop("val_row"),
                    out["val_len"], out["count"])
                out["val_stream"] = tuple(moved[s]
                                          for s in range(len(streams)))
                return out

            # the XLA module's name (jit_<name>), by which a device
            # trace's reader finds the pipeline: fixed here, not left to
            # whatever the closure happens to be called
            one_shard.__name__ = PIPELINE_PROGRAM
            one_shard_index.__name__ = PIPELINE_PROGRAM_INDEX
            one_shard_stream.__name__ = PIPELINE_PROGRAM_INDEX
            fn = jax.jit(
                (one_shard_stream if stream else one_shard_index) if index
                else jax.vmap(one_shard))
            self._vmapped_cache[key] = fn
        return fn

    def compact_shard_batch(
        self,
        batches: Sequence[KVBatch],
        merge_kind: MergeKind = MergeKind.UINT64_ADD,
        drop_tombstones: bool = True,
        return_arrays: bool = False,
    ) -> List[dict]:
        """Compact many shards in one launch. Returns, per shard:
        {"entries": [(key, seq, vtype, value)], "bloom_words": np.ndarray,
        "count": int} — or, with ``return_arrays``, {"arrays": lane dict,
        "bloom_words", "count"} with NO per-entry tuple unpacking (the
        array-native sink path: callers feed the lanes straight to
        write_sst_from_arrays)."""
        if not batches:
            return []
        capacity = _next_pow2(max(b.capacity for b in batches))
        # The job-level trace answers "where does a shard-batch's wall
        # clock go": host stack + H2D (tpu.h2d), the launch
        # (tpu.dispatch), the host blocked on the device and D2H
        # (tpu.readback), host unpack (tpu.unpack) — one group of the
        # streamed path, through the same code.
        with start_span("tpu.compact_batch", always=True,
                        shards=len(batches), capacity=capacity):
            return self._compact_shard_stream(
                batches, merge_kind, drop_tombstones, len(batches),
                capacity, return_arrays)

    def compact_shard_stream(
        self,
        batches: Sequence[KVBatch],
        merge_kind: MergeKind = MergeKind.UINT64_ADD,
        drop_tombstones: bool = True,
        group_size: int = 8,
        return_arrays: bool = False,
        dbs: Optional[int] = None,
    ) -> List[dict]:
        """Pipelined variant of compact_shard_batch for big shard counts:
        shards run in fixed-size groups with double-buffered transfers —
        group i+1's H2D upload is issued while group i's kernel runs, and
        group i's D2H readback happens under group i+1's compute
        (device_put and jit dispatch are async; only np.asarray blocks).
        One compiled shape serves every group (the last one is padded
        with empty shards). Addresses the round-1 finding that H2D
        staging cost ~3.7x the kernel (SURVEY §7 front-load item 2).
        A batch is one PLACE of a launch: a whole shard, or one key
        range of a shard that was cut; the span's ``shards`` counts the
        places, ``dbs`` the whole shards they came from (the caller's
        to say; every place its own shard otherwise)."""
        if not batches:
            return []
        capacity = _next_pow2(max(b.capacity for b in batches))
        with start_span("tpu.compact_stream", always=True,
                        shards=len(batches), group_size=group_size,
                        capacity=capacity,
                        dbs=len(batches) if dbs is None else dbs):
            return self._compact_shard_stream(
                batches, merge_kind, drop_tombstones, group_size,
                capacity, return_arrays)

    def _compact_shard_stream(self, batches, merge_kind, drop_tombstones,
                              group_size, capacity, return_arrays=False):
        num_words = num_words_for(capacity, self._bits_per_key)
        flags = [fast_flags(b.key_len, b.seq_hi, b.valid) for b in batches]
        uniform_klen = all(u for u, _, _ in flags)
        seq32 = all(s for _, s, _ in flags)
        key_words = max(k for _, _, k in flags)
        # the group's value width: its widest place's (a narrower place
        # is zero-tailed to it); its key lanes' (6 or 12 words) are one;
        # its places' values as rows or as word streams, never both
        val_words = max(_val_cols(b) for b in batches)
        stream = getattr(batches[0], "val_stream", None) is not None
        key_cols = batches[0].key_words_be.shape[1]
        path = value_path(merge_kind, val_words)
        index = path == "index"
        vmixed = any(_values_mixed(b) for b in batches)
        # the shape of one place's values on the device
        per_place = ((STREAM_WORDS_PER_ROW * capacity,) if stream
                     else (capacity, val_words))
        span = current_span()  # tpu.compact_stream / tpu.compact_batch
        if span is not None:
            # key_widths: ``mixed`` where some place's keys differ in
            # length (the launch then carries the key-length lane:
            # ``uniform_klen=False``), key_bytes_max the longest key;
            # key_lanes the key matrix's width (``key_words``: the words
            # in use); value_widths ``mixed`` where some place's live
            # values differ in width; value_bytes the places' real value
            # bytes; value_move ``stream`` (each place's values one word
            # stream) or ``rows``; value_bytes_padded the bytes the
            # launch moves for values: a stream's capacity a place, or
            # the places' rows at the launch's width
            span.annotate(
                val_words=val_words, value_path=path,
                key_widths="uniform" if uniform_klen else "mixed",
                key_bytes_max=max(
                    (int(b.key_len.max()) for b in batches if b.capacity),
                    default=0),
                key_lanes=key_cols, key_words=key_words,
                value_widths="mixed" if vmixed else "uniform",
                value_bytes=sum(int(b.val_len.sum()) for b in batches),
                value_move="stream" if stream else "rows",
                value_bytes_padded=4 * (
                    per_place[0] * len(batches) if stream
                    else val_words * sum(b.capacity for b in batches)))
        Stats.get().incr("compact.value_path." + path, len(batches))
        fn = self._pipeline(merge_kind, drop_tombstones, num_words,
                            uniform_klen, seq32, key_words, val_words,
                            stream)

        def stage(lo: int) -> Dict[str, object]:
            """Stack one group on host and issue its async H2D. On the
            index path a shard's values go up as a buffer of their own,
            never stacked: the one its batch brought (``val_words_dev``:
            the caller's thread put it up already) where that is of this
            group's capacity bucket, else the host values (rows or word
            stream) padded and put here; an empty place takes the one
            zero buffer the device already holds."""
            group = list(batches[lo:lo + group_size])
            pad_shards = group_size - len(group)
            stacked = {}
            with start_span("tpu.h2d", shards=len(group)) as sp:
                for name in _GROUP_LANES:
                    if index and name == "val_words":
                        ups, prestaged, restaged = [], 0, 0
                        for b in group:
                            dev = getattr(b, "val_words_dev", None)
                            if dev is not None and dev.shape == per_place:
                                prestaged += 1
                            else:  # none came, or one of another bucket
                                restaged += dev is not None
                                dev = jax.device_put(_pad_to(
                                    b.val_stream, per_place[0]) if stream
                                    else _pad_to(_pad_cols(
                                        b.val_words, val_words), capacity))
                            ups.append(dev)
                        Stats.get().incr("seam.values.prestaged", prestaged)
                        Stats.get().incr("seam.values.restaged", restaged)
                        sp.annotate(prestaged=prestaged)
                        stacked[name] = tuple(ups) + (
                            self._zeros(per_place),) * pad_shards
                        continue
                    arr = np.stack([_pad_to(
                        _pad_cols(b.val_words, val_words)
                        if name == "val_words" else getattr(b, name),
                        capacity) for b in group])
                    if pad_shards:
                        arr = np.pad(
                            arr,
                            [(0, pad_shards)] + [(0, 0)] * (arr.ndim - 1))
                    stacked[name] = jax.device_put(arr)
            return stacked

        groups = list(range(0, len(batches), group_size))
        results: List[dict] = []
        pending: List[Tuple[int, dict]] = []  # (group_lo, device outputs)
        dev = stage(groups[0])
        for gi, lo in enumerate(groups):
            with start_span("tpu.dispatch"):  # the async launch alone
                out = fn(*(dev[name] for name in _GROUP_LANES))
            if gi + 1 < len(groups):
                dev = stage(groups[gi + 1])  # H2D overlaps the kernel
            pending.append((lo, out))
            # drain the PREVIOUS group while this one computes: its
            # np.asarray blocks only on already-finished work
            if len(pending) > 1:
                results.extend(self._drain(
                    *pending.pop(0), batches, merge_kind, drop_tombstones,
                    num_words, return_arrays))
        while pending:
            results.extend(self._drain(
                *pending.pop(0), batches, merge_kind, drop_tombstones,
                num_words, return_arrays))
        return results

    def _zeros(self, key: tuple):
        """The device's one all-zero buffer of this shape (``(capacity,
        val_words)`` rows, or ``(words,)`` of a stream): what an empty
        place of an index-path group reads."""
        if key not in self._zero_rows:
            self._zero_rows[key] = jax.device_put(
                np.zeros(key, dtype=np.uint32))
        return self._zero_rows[key]

    def _drain(self, lo: int, out, batches, merge_kind, drop_tombstones,
               num_words, return_arrays=False) -> List[dict]:
        """Readback + unpack one group's device outputs. The index
        path's values come back per shard (``out["val_words"]``, or
        ``out["val_stream"]``, is a tuple), the real shards' only, each as
        the padded block or stream: here,
        or, for a shard whose batch brought its values up as a device
        buffer, wherever the caller reads the device buffer its result
        carries (the copy is started here, behind the small lanes)."""
        group = batches[lo:lo + out["count"].shape[0]]
        with start_span("tpu.readback"):  # blocked on the device, and D2H
            host = {k: np.asarray(v) for k, v in out.items()
                    if not isinstance(v, tuple)}
            for key, per_shard in out.items():
                if not isinstance(per_shard, tuple):
                    continue
                host[key] = vals = []
                for b, a in zip(group, per_shard):
                    if (return_arrays and
                            getattr(b, "val_words_dev", None) is not None):
                        a.copy_to_host_async()
                    else:
                        a = np.asarray(a)
                    vals.append(a)
        results = []
        with start_span("tpu.unpack", shards=len(group)):
            for s in range(len(group)):
                if bool(host["needs_cpu_fallback"][s]):
                    results.append(self._cpu_recompute(
                        group[s], merge_kind, drop_tombstones, num_words,
                        return_arrays=return_arrays))
                    continue
                results.append(_shard_result(
                    host, s, int(host["count"][s]), return_arrays))
        return results

    def _cpu_recompute(self, batch: KVBatch, merge_kind: MergeKind,
                       drop_tombstones: bool, num_words: int,
                       return_arrays: bool = False) -> dict:
        """Host recompute for shards the kernel flagged (e.g. one key with
        ≥2^16 operands — beyond the limb-sum range). ``num_words`` is the
        job-wide bloom size so fallback blooms stay interchangeable with
        the TPU-built ones."""
        from ..storage.bloom import BloomFilter
        from ..storage.native.binding import get_native
        from .backend import cpu_merge_resolve

        record_host_fallback(
            "kernel_overflow", f"{batch.capacity}-entry shard")
        arrays, count = cpu_merge_resolve(
            batch, uint64_add=merge_kind is MergeKind.UINT64_ADD,
            drop_tombstones=drop_tombstones,
        )
        bf = BloomFilter(num_words)
        lib = get_native()
        if lib is not None and count:
            # bulk path into the job-pinned words array (build_from_arrays
            # would size its own filter)
            kb = (np.ascontiguousarray(arrays[0][:count].astype(">u4"))
                  .view(np.uint8).reshape(count, -1))
            lens = np.asarray(arrays[1][:count], dtype=np.uint64)
            lens = np.minimum(lens, np.uint64(kb.shape[1]))
            mask = (np.arange(kb.shape[1], dtype=np.uint64)[None, :]
                    < lens[:, None])
            offsets = np.zeros(count + 1, dtype=np.uint64)
            np.cumsum(lens, out=offsets[1:])
            lib.bloom_add_concat(bf.words, kb[mask], offsets, count)
        else:
            for key, _seq, _vt, _val in unpack_entries(*arrays, count):
                bf.add(key)
        if return_arrays:
            kw_be, klen, seq_hi, seq_lo, vtype, vw, vlen = (
                a[:count] for a in arrays)
            lanes = {
                "key_words_be": kw_be,
                # LE word values are the same key bytes read little-endian
                # — a per-element byteswap of the BE values
                "key_words_le": kw_be.byteswap(),
                "key_len": klen, "seq_hi": seq_hi, "seq_lo": seq_lo,
                "vtype": vtype, "val_words": vw, "val_len": vlen,
            }
            return {"arrays": lanes, "bloom_words": bf.words, "count": count}
        return {"entries": unpack_entries(*arrays, count),
                "bloom_words": bf.words, "count": count}


def _pad_to(arr: np.ndarray, capacity: int) -> np.ndarray:
    if arr.shape[0] == capacity:
        return arr
    pad = [(0, capacity - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad)


def _pad_cols(arr: np.ndarray, cols: int) -> np.ndarray:
    """``arr`` (rows, words) zero-tailed to ``cols`` words a row."""
    if arr.shape[1] == cols:
        return arr
    return np.pad(arr, [(0, 0), (0, cols - arr.shape[1])])


def _values_mixed(batch) -> bool:
    """Whether a place's live values differ in width (read on the
    launching thread: one width, the common case, costs two reductions
    and no mask)."""
    vl = batch.val_len
    if not len(vl) or int(vl.min()) == int(vl.max()):
        return False
    live = vl[batch.vtype != 2]
    return bool(len(live)) and int(live.min()) != int(live.max())


def value_bucket(words: int) -> int:
    """The value width, in words, an index-path launch is built for: the
    power of two at or above the shard's widest value (1,024 B: 256), so
    that shards whose widest values differ share one program."""
    return _next_pow2(max(1, words))


# lane names carried through the arrays-native result path (matches
# tpu/chunked.FIELDS; redeclared to avoid importing chunked at call time)
_LANES = (
    "key_words_be", "key_words_le", "key_len", "seq_hi", "seq_lo",
    "vtype", "val_words", "val_len",
)


def _shard_result(host: Dict[str, np.ndarray], s: int, count: int,
                  return_arrays: bool) -> dict:
    """One shard's result from stacked device outputs: lane views (no
    per-entry work) or unpacked tuples. A shard whose values came back
    as a word stream (``compact_dbs_batched``'s, lane views) has
    ``val_stream`` (whole) in ``val_words``' place."""
    lanes = _LANES
    if "val_stream" in host:
        lanes = tuple(f for f in _LANES if f != "val_words") + (
            "val_stream",)
    if return_arrays:
        def rows(f):
            a = host[f][s]
            # a value block left on the device stays whole: the thread
            # that reads it back slices it (``_write_arrays``)
            return (a[:count] if isinstance(a, np.ndarray)
                    and f != "val_stream" else a)

        return {
            "arrays": {f: rows(f) for f in lanes},
            "bloom_words": host["bloom"][s],
            "count": count,
        }
    return {
        "entries": unpack_entries(
            host["key_words_be"][s], host["key_len"][s],
            host["seq_hi"][s], host["seq_lo"][s],
            host["vtype"][s], host["val_words"][s],
            host["val_len"][s], count,
        ),
        "bloom_words": host["bloom"][s],
        "count": count,
    }


# ---------------------------------------------------------------------------
# key-range subcompactions as one device batch (round 16)
# ---------------------------------------------------------------------------


def _place_batches(slices: List[Dict[str, np.ndarray]],
                   devs: Sequence = ()) -> List["_LaneBatch"]:
    """One compaction's key-range slices as places of a launch, each a
    subcompaction of its own (the ``compact.subcompact`` failpoint, the
    ``compaction.subcompactions`` counter): both device doors'."""
    batches = []
    for lanes, dev in zip_longest(slices, devs):
        fp.hit("compact.subcompact")
        Stats.get().incr("compaction.subcompactions")
        batches.append(_LaneBatch(lanes, dev))
    return batches


def resolve_slices_batched(
    slice_lanes: List[Dict[str, np.ndarray]],
    merge_kind: "MergeKind",
    drop_tombstones: bool,
) -> List[Tuple[dict, int]]:
    """ONE compaction's key-range slices resolved as places of the fixed
    group launch (``compact_shard_stream``: eight places a launch, the
    last group padded with empty ones, so that no program exists per
    slice COUNT) — the TPU face of subcompactions: each slice is a
    "shard" of the job, padded to the common pow2 capacity exactly like
    the cross-db batched path, so k smaller sorts ride the launches
    instead of one pow2(total) sort. Returns per-slice
    ``(lane_arrays, count)`` in input order (empty slices come back as
    ``({}, 0)``); slice boundaries are keys, so MERGE operand groups
    are never split across slices by construction."""
    out: List[Tuple[dict, int]] = [({}, 0)] * len(slice_lanes)
    index = [i for i, lanes in enumerate(slice_lanes)
             if lanes["key_len"].shape[0]]
    batches = _place_batches([slice_lanes[i] for i in index])
    if batches:
        svc = TpuCompactionService.instance()
        results = svc.compact_shard_stream(
            batches, merge_kind=merge_kind,
            drop_tombstones=drop_tombstones, return_arrays=True, dbs=1)
        for i, res in zip(index, results):
            out[i] = (res["arrays"], int(res["count"]))
    return out


# ---------------------------------------------------------------------------
# cross-DB batched full compaction (the post-load_sst path)
# ---------------------------------------------------------------------------

# The most rows the served door reads of one shard (the lanes of a whole
# shard are held on the host while it is cut and launched: ~48 B a row
# plus its values); a larger shard compacts per-db.
MAX_BATCHED_DB_ENTRIES = 1 << 20

# The rows of one PLACE of the served door's launch: the largest capacity
# bucket the door launches. A shard of more rows is cut by key range into
# several places of the same fixed ``(group_size, PLACE_ROWS_MAX)`` launch
# and never asks for a larger program: ``(8, 32768)`` is the largest whose
# two ``lax.sort``s the chip's compiler builds inside a run (90 s cold
# with values riding, 59 s on the index path; PERF.md section 6, PRs 22
# and 29), and ``(8, 131072)`` is minutes of compile inside an ingest RPC.
PLACE_ROWS_MAX = 1 << 15


def device_mixed_key_bytes_max(merge_operator) -> int:
    """The longest key, in bytes, of a shard of DIFFERING key lengths
    that the served door (``compact_dbs_batched``) compacts on the
    device for a DB with this merge operator: the lanes' key width, 48
    bytes (twelve key words a row for a shard with a key over 24 bytes;
    ``lanes_decline_reason`` declines a longer key with ``key_width``);
    0 where the door takes no shard with this operator. Asked by a
    deployment's driver before it builds anything
    (chipbench/drivers/refresh_names.py, refresh_mixgraph.py)."""
    if device_decline_reason(None, merge_operator) is not None:
        return 0
    return PLANAR_MAX_KLEN


def device_mixed_value_bytes_max(merge_operator) -> int:
    """The widest value, in bytes, of a shard whose values DIFFER in
    width that the served door (``compact_dbs_batched``) compacts on the
    device for a DB with this merge operator: ``DEVICE_VALUE_BYTES_MAX``
    with no operator (the index path moves each row whole and its
    ``val_len`` rides the sorts), 0 where the door takes no such shard
    (the uint64-add fold is defined on 8-byte values alone). Asked by a
    deployment's driver before it builds anything
    (chipbench/drivers/refresh_mixgraph.py)."""
    if device_decline_reason(None, merge_operator) is not None:
        return 0
    return DEVICE_VALUE_BYTES_MAX if merge_operator is None else 0


def device_shard_rows_max(merge_operator) -> int:
    """The most rows of ONE shard that the served door
    (``compact_dbs_batched``) compacts on the device for a DB with this
    merge operator without building a program that a smaller shard has
    not built: up to ``PLACE_ROWS_MAX`` rows as one place, more as
    several places of the same launch; 0 where the door takes no shard
    with this operator. Asked by a deployment's driver before it builds
    anything (chipbench/drivers/refresh_ranges.py)."""
    if device_decline_reason(None, merge_operator) is not None:
        return 0
    return MAX_BATCHED_DB_ENTRIES


class _LaneBatch:
    """Duck-typed KVBatch over pre-read lane arrays — the arrays-native
    input to compact_shard_batch/stream (no per-entry pack loop): the
    lanes a launch takes (``_GROUP_LANES``), every row valid.
    ``val_words_dev``: the values on the device already, zero-padded to
    ``_next_pow2(rows)`` rows (a place of a cut shard: to the place
    capacity), where the thread that decoded the shard put them up
    itself (an index-path shard of the served door); such a shard's
    resolved values come back as a device buffer too. ``val_stream``:
    the place's values as one word stream (``ValueStream``'s buffer;
    ``val_words`` is then None and ``val_words_dev`` the stream on the
    device), ``val_cols`` the value bucket of its widest value, as its
    rows would have had."""

    __slots__ = _GROUP_LANES + ("val_words_dev", "val_stream", "val_cols")

    def __init__(self, lanes: Dict[str, np.ndarray], val_words_dev=None):
        for f in _GROUP_LANES[:-1]:
            setattr(self, f, lanes.get(f))
        self.valid = np.ones(lanes["key_len"].shape[0], dtype=bool)
        self.val_words_dev = val_words_dev
        self.val_stream = lanes.get("val_stream")
        self.val_cols = (self.val_words.shape[1] if self.val_stream is None
                         else value_bucket((int(self.val_len.max()) + 3) // 4))

    @property
    def capacity(self) -> int:
        return self.key_len.shape[0]

    def num_valid(self) -> int:
        return self.capacity


def _val_cols(batch) -> int:
    """A place's value width in words (a KVBatch's: its value rows')."""
    return getattr(batch, "val_cols", 0) or batch.val_words.shape[1]


def _write_arrays(db, res: dict, tctx: Optional[dict],
                  place_bloom: bool = False) -> dict:
    """Write one place's resolved lanes as PLANAR SSTs (the array sink,
    per-file blooms built on the device). Returns how to install them,
    as ``install_full_compaction``'s keywords: ``files``, or the
    entry-tuple sink's ``entries`` when the planar layout can't express
    the result. ``tctx``: the dispatch's trace context (this runs on a
    pool thread). ``place_bloom`` (a place of a shard that was cut):
    the file takes the bloom its launch built over exactly the place's
    keys (``_launch_bloom``) and no bloom program of its own."""
    arrays, count = res["arrays"], int(res["count"])
    if count == 0:
        return {"entries": []}
    stream = "val_stream" in arrays
    key = "val_stream" if stream else "val_words"
    vals = arrays[key]
    if not isinstance(vals, np.ndarray):
        # the launch left this shard's padded block (or word stream) on
        # the device (``_drain``): down on THIS thread, eight at once
        with start_span("tpu.readback.values", remote=tctx,
                        bytes=vals.nbytes):
            vals = np.asarray(vals)
        arrays[key] = vals if stream else vals[:count]
    if stream:  # each row's words from the rows before it
        arrays["val_off"] = stream_offsets(arrays["val_len"][:count])
    opts = db.options
    build_bloom = (
        _launch_bloom(res["bloom_words"], count, opts.bits_per_key)
        if place_bloom else _device_bloom_builder(opts.bits_per_key, tctx))
    outputs = write_resolved_lanes(
        arrays, count, db.allocate_sst_path, opts.block_bytes,
        opts.compression, opts.bits_per_key, opts.target_file_bytes,
        build_bloom=build_bloom, trace=tctx)
    if outputs is not None:
        return {"files": [os.path.basename(path) for path, _ in outputs]}
    # tuple fallback (non-uniform keys/values)
    return {"entries": unpack_entries(
        arrays["key_words_be"], arrays["key_len"], arrays["seq_hi"],
        arrays["seq_lo"], arrays["vtype"],
        with_value_rows(arrays, count)["val_words"],
        arrays["val_len"], count,
    )}


def _launch_bloom(words: np.ndarray, count: int, bits_per_key: int):
    """``write_resolved_lanes``' bloom builder for a place of a cut
    shard: the launch's own filter (built over exactly the place's
    ``count`` output keys, sized for a full place) where the place is
    ONE file and the filter gives the DB's ``bits_per_key`` or more a
    key; else None, which leaves the file to the host's bulk bloom. No
    program either way: the per-file device builder is a program per
    exact row count, and the places' counts differ by shard and seed."""
    def build(sub: dict, n: int) -> Optional[np.ndarray]:
        if n == count and 32 * len(words) >= n * bits_per_key:
            return words
        return None

    return build


def _write_places(db, results: List[dict], tctx: Optional[dict]) -> dict:
    """``_write_arrays`` over every place of one shard, in key order: an
    uncut shard's one place as it always was; a cut shard's places as
    key-disjoint file sets that install TOGETHER (``files`` in key
    order). A place that fails removes the files of the places before it
    and raises: all of a shard's places are installed or none."""
    if len(results) == 1:
        return _write_arrays(db, results[0], tctx)
    files: List[str] = []
    try:
        for res in results:
            how = _write_arrays(db, res, tctx, place_bloom=True)
            if how.get("entries"):
                raise RuntimeError("the planar sink declined a place")
            files.extend(how.get("files", ()))
    except BaseException:
        for name in files:
            try:
                os.remove(os.path.join(db.path, name))
            except OSError:
                pass
        raise
    return {"files": files}


def compact_dbs_batched(dbs, group_size: int = 8, pool=None):
    """Fully compact many DBs' key spaces with batched device launches —
    the cross-shard post-load compaction: N shards' merge-resolve runs as
    vmapped groups over one padded shape instead of N per-db pipelines,
    arrays end to end (runs decode to lanes, the resolved lanes write
    through the PLANAR sink — no per-entry Python on either side). The
    per-db host stages (plan + lane read, then SST write + install) fan
    out over ``pool`` (any Executor) when given; only the device launch
    is centralized. An index-path shard's value block crosses the
    host-device seam on those per-db threads too (``tpu.h2d.values``
    after its decode, ``tpu.readback.values`` before its write): the
    launching thread carries the small lanes alone.

    A launch is ALWAYS the fixed ``(group_size, capacity)`` shape with
    ``capacity`` at most ``PLACE_ROWS_MAX``. A shard of more rows into
    its compaction is cut at KEYS (``plan_subcompactions``: from the
    runs' own rows, the fewest key ranges of at most ``PLACE_ROWS_MAX``
    rows each, near-equal; a key's whole entry stack lies in one) into
    several PLACES, packed with other shards' places into full launches
    (8 shards of 3 places are 3 launches); each place writes a
    key-disjoint file set, its filter the launch's own, and ONE
    ``install_full_compaction`` installs all of a shard's places or,
    where one fails, none (the shard goes to the per-db path). How many
    places follows from the rows alone: no option. A shard one key
    group of which is over a place is declined before any program
    (``tpu.host_fallbacks reason=key_group_over_place``). Per cut shard
    a ``tpu.range_cut`` span (``rows``, ``places``, ``capacity``) on its
    pool thread, ``Stats`` ``compact.range_cut.shards`` / ``.places``;
    ``tpu.compact_stream`` carries ``shards`` = launched places and
    ``dbs`` = whole shards. ``device_shard_rows_max`` says what the
    door takes.

    Per DB: plan (engine plan_full_compaction: flush + snapshot under the
    compaction mutex), read its runs as lanes, launch the group, install
    each shard's output files (engine install_full_compaction). Values:
    with the uint64-add operator, 8 bytes, riding both sorts; with no
    operator, up to ``RIDE_MAX_VAL_WORDS`` words ride, wider ones up to
    ``device_value_bytes_max(None)`` bytes take the index path (one
    row-index lane rides, the values are moved once inside the same
    module; ``value_path`` on the ``tpu.compact_stream`` span says
    which); values of differing widths in one shard (no operator: each
    row's ``val_len`` rides; ``device_mixed_value_bytes_max``) launch at
    the power-of-two bucket of the group's widest (``value_bucket``), and
    ``value_widths`` / ``value_bytes`` / ``value_bytes_padded`` on
    ``tpu.compact_stream`` say what that costs; an uncut shard whose runs
    say its values differ in width (a run's own do, or two runs' widest
    differ) moves them as ONE word
    stream instead (``ValueStream``: decoded into it, put up, gathered by
    the merge order inside the same module, read back and written from
    it, ``STREAM_WORDS_PER_ROW`` words a row of its capacity bucket;
    ``value_move`` = ``stream`` on ``tpu.compact_stream``; such shards
    launch as groups of their own; ``Stats``
    ``compact.value_move.stream`` / ``.rows`` count index-path shards by
    move, ``.overrun`` the places whose values overran the stream and
    kept their rows). Keys: 1 to 48 bytes, of
    one length or mixed in any proportion
    (``device_mixed_key_bytes_max``; over 24 bytes the key lanes are
    twelve words, ``key_lanes`` 12, and such shards launch as groups of
    their own: ``Stats`` ``compact.key_lanes.wide``): a launch any place of
    which has keys of differing length carries the key-length lane
    (``uniform_klen=False``: a sort key after the key words, a boundary
    compare, an output lane; one more program a capacity bucket), and
    ``key_widths`` on ``tpu.compact_stream`` / ``tpu.lanes.decode`` /
    ``tpu.planar.write`` says ``uniform`` or ``mixed``, ``Stats``
    ``compact.key_widths.uniform`` / ``.mixed`` count the shards. DBs
    the device path can't express (``device_decline_reason`` says which
    and why: custom merge operators, values over
    ``device_value_bytes_max``, MERGE records with no operator, values
    of more than one width under the operator; besides >48B keys and
    shards of more than
    ``MAX_BATCHED_DB_ENTRIES`` rows, which the lane read declines) are
    declined untouched, before any program is built; a decline for
    width counts under ``tpu.host_fallbacks reason=value_width``.

    Returns ``(handled, remaining)``: db names compacted here, and the
    (name, db) pairs the caller must compact per-db (compact_range).
    """
    dbs = list(dbs)
    handled: List[str] = []
    remaining: List[tuple] = []
    groups: Dict[tuple, List[tuple]] = {}  # (kind, drop, width) -> items
    # every un-consumed plan holds its DB's compaction mutex; the finally
    # below releases any leaked by an unexpected raise so the caller's
    # per-db compact_range fallback can never deadlock
    pending: Dict[int, tuple] = {}
    pending_lock = threading.Lock()

    def _track(db, plan):
        with pending_lock:
            pending[id(plan)] = (db, plan)

    def _untrack(plan):
        with pending_lock:
            pending.pop(id(plan), None)

    def _abort(db, plan):
        _untrack(plan)
        db.abort_full_compaction(plan)

    def _pmap(fn, items):
        # the pool's threads start with an empty context: each per-shard
        # span below reattaches the caller's (``remote=``), so the
        # phases stay in the dispatch's trace
        tctx = wire_context()
        if pool is None or len(items) <= 1:
            return [fn(it, tctx) for it in items]
        return list(pool.map(lambda it: fn(it, tctx), items))

    def _stage(item, tctx):
        """(name, db) → ("handled"|"remaining"|("grouped", key, payload)).

        MUST NOT raise: staging runs through pool.map, and an exception
        there returns control to the caller while sibling _stage tasks
        are still acquiring compaction mutexes — a raced finally-sweep
        could then miss a just-tracked plan and leak its mutex forever.
        Any failure (corrupt SST read, OSError, ...) declines the db to
        the per-db compact_range fallback instead."""
        name, db = item
        merge_op = db.options.merge_operator
        # the operator alone, before the plan: a plan costs a flush
        if device_decline_reason(None, merge_op) is not None:
            return ("remaining", name, db, None)
        try:
            # mostly the wait for the memtable flush, which the engine's
            # flusher thread runs (a storage.flush trace of its own)
            with start_span("admin.compact.plan", remote=tctx, db=name):
                plan = db.plan_full_compaction()
        except BaseException:
            log.exception("plan failed for %s; declining to per-db", name)
            return ("remaining", name, db, None)
        if plan is None:
            return ("handled", name, db, None)  # nothing to compact
        _track(db, plan)
        kind = (
            MergeKind.UINT64_ADD if merge_op is not None else MergeKind.NONE
        )

        def value_rows(total, vw):
            # an index-path shard's values are decoded straight into the
            # padded buffer that goes up: its own capacity bucket and
            # value bucket (a shard that will be cut gets a buffer a
            # place instead)
            if value_path(kind, vw) != "index":
                return total
            rows = _next_pow2(total) if total <= PLACE_ROWS_MAX else total
            return rows, value_bucket(vw)

        def value_stream(runs):
            # an uncut index-path place whose runs say its live values
            # differ in width (a run's own do, or two runs' widest
            # differ): its values are decoded into ONE word stream of
            # ``STREAM_WORDS_PER_ROW`` words a row of its capacity bucket
            # (values of one width keep their rows, which already are
            # the values)
            if kind is not MergeKind.NONE:
                return None
            said = [stream_may_carry(r) if hasattr(r, "iterate") else None
                    for r in runs]
            if None in said or not (any(m for _w, m in said) or len(
                    {w for w, _m in said if w}) > 1):
                return None
            rows = sum(int(r.num_entries) for r in runs)
            vw = max(2, (max(w for w, _m in said) + 3) // 4)
            if rows > PLACE_ROWS_MAX or value_path(kind, vw) != "index":
                return None
            return ValueStream(STREAM_WORDS_PER_ROW * _next_pow2(rows))

        try:
            with start_span("tpu.lanes.decode", remote=tctx) as lsp:
                # None: nothing to compact, a run the lanes can't
                # express, or more rows than the door reads of one shard
                read_runs = functools.partial(
                    read_runs_as_lanes, plan["runs"], None,
                    max_entries=MAX_BATCHED_DB_ENTRIES,
                    value_rows=value_rows)
                try:
                    read = read_runs(value_stream=value_stream)
                except StreamOverrun:  # the values need more: rows
                    Stats.get().incr("compact.value_move.overrun")
                    read = read_runs()
                if read is not None:
                    shape = key_shape(read[1]["key_len"])
                    vshape = value_shape(read[1]["vtype"],
                                         read[1]["val_len"])
                    lsp.annotate(rows=read[2], **shape, **vshape,
                                 value_move="stream" if "val_stream"
                                 in read[1] else "rows")
        except BaseException:
            log.exception(
                "lane read failed for %s; declining to per-db", name)
            _abort(db, plan)
            return ("remaining", name, db, None)
        if read is None:
            _abort(db, plan)
            return ("remaining", name, db, None)
        parts, lanes, total, vw = read
        reason = device_decline_reason(lanes, merge_op)
        if reason is not None:
            if reason == "value_width":
                # wider than the device path takes (for uint64-add: than
                # the fold is defined on): the host path, and no program
                record_host_fallback(
                    reason,
                    f"{name}: {int(lanes['val_len'].max())}-byte values")
            _abort(db, plan)
            return ("remaining", name, db, None)
        # shards through the door by key shape: one key length, or
        # lengths that differ (``tpu.compact_stream`` says which launch);
        # by the key lanes' width (twelve words: a key over 24 bytes);
        # by value shape: one width, or widths that differ
        key_cols = lanes["key_words_be"].shape[1]
        stats = Stats.get()
        stats.incr("compact.key_widths." + shape["key_widths"])
        if key_cols > KEY_WORDS:
            stats.incr("compact.key_lanes.wide")
        stats.incr("compact.value_widths." + vshape["value_widths"])
        # index-path shards launch together at the bucket of the group's
        # widest value; a shard whose own bucket is narrower is padded
        # and put up again by the leader (``seam.values.restaged``)
        index = value_path(kind, vw) == "index"
        # index-path shards by how their values move: one word stream a
        # place, or rows (``compact.value_move.overrun``: the stream
        # could not hold them)
        stream = "val_stream" in lanes
        if index:
            stats.incr("compact.value_move." + ("stream" if stream
                                                else "rows"))
        cut = total > PLACE_ROWS_MAX  # else the shard is its one place
        try:
            places = (_range_cut(name, parts, lanes, total, index, tctx)
                      if cut else [lanes])
            devs = []
            for place in places if index else ():
                # up from THIS thread, eight shards at once, and not one
                # after another on the leader's (its ``tpu.h2d``)
                padded = (place["val_stream"] if stream
                          else place["val_words"].base)
                with start_span("tpu.h2d.values", remote=tctx,
                                bytes=padded.nbytes):
                    devs.append(
                        jax.block_until_ready(jax.device_put(padded)))
            batches = (_place_batches(places, devs) if cut
                       else [_LaneBatch(lanes, *devs)])
        except BaseException:
            log.exception(
                "range cut or value upload failed for %s; declining to "
                "per-db", name)
            _abort(db, plan)
            return ("remaining", name, db, None)
        key = (kind, plan["drop_tombstones"], index, key_cols, stream)
        return ("grouped", name, db, (key, plan, batches))

    def _range_cut(name, parts, lanes, total, index, tctx):
        """A shard of more rows than a place holds, as the lanes of its
        places in key order (the index path's values in a zero-tailed
        buffer a place, at the place's capacity). Raises where no cut
        at keys fits (counted: one key group is over a place) or the
        runs are not sorted."""
        klen = shard_klen(lanes)  # 0: the keys differ in length
        with start_span("tpu.range_cut", remote=tctx, rows=total,
                        capacity=PLACE_ROWS_MAX) as sp:
            try:
                bounds = plan_subcompactions(
                    parts, total, 1, klen, max_slice_rows=PLACE_ROWS_MAX)
            except KeyGroupOverSlice as e:
                record_host_fallback("key_group_over_place", f"{name}: {e}")
                raise
            if not bounds:  # the planner cuts sorted runs only
                raise RuntimeError(f"{name}: {total} rows in unsorted runs")
            places = slice_lanes(
                parts, bounds, klen,
                (lambda rows, vw: (PLACE_ROWS_MAX, value_bucket(vw)))
                if index else None)
            sp.annotate(places=len(places))
        Stats.get().incr("compact.range_cut.shards")
        Stats.get().incr("compact.range_cut.places", len(places))
        return places

    def _install(args, tctx):
        name, db, plan, results = args  # a result a place, in key order
        try:
            how = _write_places(db, results, tctx)
        except BaseException:
            # nothing is installed yet: hand the mutex back, so that the
            # per-db retry via compact_range can take it
            log.exception(
                "batched compaction output failed for %s; "
                "will re-compact per-db", name)
            _abort(db, plan)
            return ("remaining", name, db)
        _untrack(plan)  # install consumes the plan either way
        try:
            with start_span("admin.compact.install_db", remote=tctx,
                            db=name):
                db.install_full_compaction(plan, **how)
            return ("handled", name, db)
        except BaseException:
            # the mutex was released in install's finally; a per-db
            # retry via compact_range is safe
            log.exception(
                "batched compaction install failed for %s; "
                "will re-compact per-db", name)
            return ("remaining", name, db)

    try:
        with start_span("admin.compact_stage", shards=len(dbs)):
            staged = _pmap(_stage, dbs)
        for verdict, name, db, payload in staged:
            if verdict == "handled":
                handled.append(name)
            elif verdict == "remaining":
                remaining.append((name, db))
            else:
                key, plan, places = payload
                groups.setdefault(key, []).append((name, db, plan, places))

        svc = TpuCompactionService.instance()
        for (kind, drop, _index, _kc, _stream), items in groups.items():
            # every shard's places side by side: 8 shards of 3 places
            # fill 3 launches
            batches = [b for _n, _d, _p, places in items for b in places]
            try:
                # ALWAYS the fixed (group_size, capacity) launch shape,
                # short groups padded with empty shards: how many shards
                # a dispatch carries is the BatchCompactor's to decide
                # (its leader waits for the admitted ingests, up to a
                # full group; a lone caller or a straggler still makes a
                # short one), and a program per distinct count costs a
                # compile of minutes on the chip (PERF.md section 6, PR
                # 22). H2D of group i+1 overlaps group i's kernel.
                results = svc.compact_shard_stream(
                    batches, merge_kind=kind, drop_tombstones=drop,
                    group_size=group_size, return_arrays=True,
                    dbs=len(items))
            except Exception:
                record_host_fallback(
                    "batched_launch",
                    f"{len(items)} shards; re-compacting per-db",
                    exc_info=True)
                for name, db, plan, _b in items:
                    _abort(db, plan)
                    remaining.append((name, db))
                continue
            results = iter(results)
            installs = [(name, db, plan, [next(results) for _ in places])
                        for name, db, plan, places in items]
            with start_span("admin.compact_install", shards=len(installs)):
                installed = _pmap(_install, installs)
            for verdict, name, db in installed:
                if verdict == "handled":
                    handled.append(name)
                else:
                    remaining.append((name, db))
        return handled, remaining
    finally:
        with pending_lock:
            leaked = list(pending.values())
            pending.clear()
        for db, plan in leaked:
            try:
                db.abort_full_compaction(plan)
            except Exception:
                pass


# ---------------------------------------------------------------------------
# streaming bounded-memory compaction: the device chunk resolver
# ---------------------------------------------------------------------------


class TpuChunkResolver:
    """The TPU face of the streaming chunked merge
    (storage/stream_merge.py): each merge chunk launches the
    merge-resolve kernel with ``to_host=False`` so the output lanes stay
    DEVICE-resident at submit; ``collect`` materializes them to host one
    chunk later. The pipeline decodes chunk N+1's windows between
    submit(N) and collect(N), so host decode (70% of a large compaction,
    GIL-bound) overlaps chunk N's DEVICE→HOST transfer — the
    double-buffered chunk shape LUDA (arxiv 2004.03054) uses and the
    silicon bench needs. Honest scope: submit() still synchronizes on
    the kernel itself (``run_kernel_arrays`` reads the
    ``needs_cpu_fallback`` flag and count as Python scalars, forcing
    the launch), so today only the transfer overlaps the next decode;
    overlapping the resolve too needs an async fallback flag — silicon
    follow-on work. Chunks pad to the next pow2 of the window total,
    so steady-state launches reuse one compiled shape."""

    # chunk lanes carry LE key words too (device bloom hashing)
    from .chunked import FIELDS as fields
    pipelined = True  # one chunk stays in flight behind the decode

    def submit(self, parts, lanes, total: int, vw: int, merge_op,
               drop_tombstones: bool):
        from ..storage.merge import UInt64AddOperator
        from ..storage.stream_merge import _StreamDecline
        from .chunked import run_kernel_arrays

        kind = (
            MergeKind.UINT64_ADD
            if isinstance(merge_op, UInt64AddOperator) else MergeKind.NONE
        )
        uniform_klen, seq32, key_words = fast_flags(
            lanes["key_len"], lanes["seq_hi"],
            np.ones(total, dtype=bool))
        arrays, count = run_kernel_arrays(
            lanes, total, kind, drop_tombstones,
            pad_to=_next_pow2(total),
            uniform_klen=uniform_klen, seq32=seq32, key_words=key_words,
            to_host=False,
        )
        if arrays is None:
            # kernel flagged limb-overflow risk: the whole stream
            # declines and the caller's CPU/tuple fallback handles it
            raise _StreamDecline("device kernel flagged cpu fallback")
        return arrays, count

    def collect(self, handle) -> Tuple[dict, int]:
        arrays, count = handle
        return {f: np.asarray(a) for f, a in arrays.items()}, count
