"""NativeCompactionBackend — array-path compaction on the CPU.

The engine's default backend. Two faces:

- ``merge_runs`` (inherited from CpuCompactionBackend): the streaming
  heap-merge. For per-entry tuple IO this IS the fastest CPU path — the
  array backends lose the resolve win back to Python pack/unpack loops
  (measured: tuple-interface numpy path 4× slower than heapq).
- ``merge_runs_to_files``: the DIRECT sink. When every input run reads
  as lanes (sink-written planar/uniform TSSTs decode straight to
  arrays) and the values are of one width (keys of 1 to 24 bytes, of
  one length or mixed: ``lanes_decline_reason``), the merge runs as
  ``cpu_merge_resolve`` (storage/native C when loaded, numpy
  otherwise), blooms build in bulk with no per-key Python, and outputs
  write as PLANAR files via the vectorized array writer — no per-entry
  Python anywhere in the pipeline. Returns None for anything the lane
  representation can't express; the engine then takes the tuple path.

This mirrors TpuCompactionBackend.merge_runs_to_files (tpu/backend.py)
with the device kernel swapped for the native CPU resolve — the same
capability the reference gets from RocksDB's C++ compaction
(db/compaction_job.cc), built array-first so the TPU and CPU sinks stay
structurally interchangeable behind the CompactionBackend seam.
"""

from __future__ import annotations

import logging
import os
from typing import Callable, List, Optional, Tuple

import numpy as np

from .compaction import CpuCompactionBackend
from .merge import MergeOperator, UInt64AddOperator
from .planar import PLANAR_MAX_KLEN, key_shape

log = logging.getLogger(__name__)

_PUT, _DELETE, _MERGE = 1, 2, 3

# bound the in-memory lane concatenation (~48 B/entry of lanes)
MAX_DIRECT_ENTRIES = 1 << 22

# Key-range subcompactions engage only when every slice would carry at
# least this many entries — below it the thread fan-out costs more than
# the parallel resolve buys (tests lower it to force slicing on small
# fixtures).
MIN_SLICE_ENTRIES = 1 << 15


class NativeCompactionBackend(CpuCompactionBackend):
    name = "native"

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
        """[(path, props)], [] for an all-tombstoned result, or None →
        the engine's tuple path. (Shared with CpuCompactionBackend —
        see direct_merge_runs_to_files below.)"""
        return direct_merge_runs_to_files(
            runs, merge_op, drop_tombstones, path_factory, block_bytes,
            compression, bits_per_key, target_file_bytes,
            max_subcompactions=max_subcompactions, io_budget=io_budget,
            mem_tracker=mem_tracker,
            memory_budget_bytes=memory_budget_bytes,
        )

    # -- internals ---------------------------------------------------------

    @staticmethod
    def _arrays_from_entries(entries, pack_entries) -> Optional[dict]:
        if not entries:
            return None
        b = pack_entries(entries)
        n = b.num_valid()
        return {
            "key_words_be": b.key_words_be[:n], "key_len": b.key_len[:n],
            "seq_hi": b.seq_hi[:n], "seq_lo": b.seq_lo[:n],
            "vtype": b.vtype[:n], "val_words": b.val_words[:n],
            "val_len": b.val_len[:n],
        }

    @staticmethod
    def _sort_cols(part: dict):
        """The merge comparator's lexicographic columns, built by THE
        canonical helper (ops/compaction_kernel.composite_key_lanes —
        every consumer of the composite order shares it). The native
        MrRec packs these lanes pairwise into u64s, which preserves
        lexicographic order, so a run sorted by these columns is sorted
        for the k-way merge."""
        from ..ops.compaction_kernel import composite_key_lanes

        kw = np.asarray(part["key_words_be"], dtype=np.uint32)
        lanes = composite_key_lanes(
            np.zeros(kw.shape[0], dtype=np.uint32),  # all rows valid
            (kw[:, w] for w in range(kw.shape[1])),
            np.asarray(part["key_len"], dtype=np.uint32),
            np.asarray(part["seq_hi"], dtype=np.uint32),
            np.asarray(part["seq_lo"], dtype=np.uint32),
            uniform_klen=False, seq32=False,
        )
        return [np.asarray(lane) for lane in lanes]

    @classmethod
    def _run_is_sorted(cls, part: dict) -> bool:
        cols = cls._sort_cols(part)
        n = len(cols[0])
        if n <= 1:
            return True
        gt = np.zeros(n - 1, dtype=bool)
        eq = np.ones(n - 1, dtype=bool)
        for col in cols:
            x, y = col[:-1], col[1:]
            gt |= eq & (y > x)
            eq &= y == x
        return bool((gt | eq).all())

    @classmethod
    def _resolve(cls, parts: List[dict], lanes: dict, total: int, vw: int,
                 merge_op, drop_tombstones: bool):
        from ..ops.kv_format import KVBatch
        from ..storage.native.binding import get_native
        from ..tpu.backend import cpu_merge_resolve

        lib = get_native()
        if (lib is not None
                and getattr(lib, "has_merge_resolve_runs", False)
                and lanes["key_words_be"].shape[1] == 6
                and all(cls._run_is_sorted(p) for p in parts)):
            # pre-sorted runs (the normal compaction case): O(n log k)
            # k-way merge instead of the O(n log n) full re-sort
            offsets = np.zeros(len(parts) + 1, dtype=np.uint64)
            np.cumsum([p["key_len"].shape[0] for p in parts],
                      out=offsets[1:])
            seq = (lanes["seq_hi"].astype(np.uint64) << np.uint64(32)) \
                | lanes["seq_lo"].astype(np.uint64)
            out = lib.merge_resolve_runs(
                lanes["key_words_be"], lanes["key_len"], seq,
                lanes["vtype"], lanes["val_words"], lanes["val_len"],
                offsets, merge_op is not None, drop_tombstones,
            )
            count = out[6]
            arrays = {
                "key_words_be": out[0][:count], "key_len": out[1][:count],
                "seq_hi": (out[2][:count] >> np.uint64(32)).astype(
                    np.uint32),
                "seq_lo": (out[2][:count]
                           & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                "vtype": out[3][:count].astype(lanes["vtype"].dtype),
                "val_words": out[4][:count], "val_len": out[5][:count],
            }
            return arrays, count

        batch = KVBatch(
            key_words_be=lanes["key_words_be"],
            # LE lanes are for bloom hashing only — the CPU resolve and
            # the bulk bloom below derive bytes from the BE lanes
            key_words_le=lanes["key_words_be"],
            key_len=lanes["key_len"],
            seq_hi=lanes["seq_hi"], seq_lo=lanes["seq_lo"],
            vtype=lanes["vtype"], val_words=lanes["val_words"],
            val_len=lanes["val_len"],
            valid=np.ones(total, dtype=bool),
            val_bytes=vw * 4,
        )
        out, count = cpu_merge_resolve(
            batch, uint64_add=merge_op is not None,
            drop_tombstones=drop_tombstones,
        )
        arrays = {
            "key_words_be": out[0], "key_len": out[1],
            "seq_hi": out[2], "seq_lo": out[3], "vtype": out[4],
            "val_words": out[5], "val_len": out[6],
        }
        return arrays, count

    @staticmethod
    def _bulk_bloom(sub: dict, n: int, klen0: int, bits_per_key: int):
        """One file's host bloom from its rows, each key at its own
        length (``klen0``: the rows' widest)."""
        from .bloom import BloomFilter

        kb = (
            np.ascontiguousarray(sub["key_words_be"][:n].astype(">u4"))
            .view(np.uint8).reshape(n, -1)[:, :klen0]
        )
        lens = np.minimum(
            np.asarray(sub["key_len"][:n], dtype=np.uint64),
            np.uint64(kb.shape[1]))
        return BloomFilter.build_from_arrays(kb, lens, bits_per_key)


def read_runs_as_lanes(
    runs: List, merge_op: Optional[MergeOperator],
    max_entries: int = MAX_DIRECT_ENTRIES,
    value_rows: Optional[Callable[[int, int], int]] = None,
) -> Optional[Tuple[List[dict], dict, int, int]]:
    """Decode input runs (SSTReaders or entry iterables) straight into
    concatenated lane arrays. Returns (parts, lanes, total, vw) or None
    when the lane representation can't express the inputs (per-run
    checks bail early, before materializing the rest). Shared by the
    direct compaction sink and both device doors (tpu/backend.py,
    tpu/compaction_service.py), which pass no ``merge_op``: the uint64-add
    bail below is then theirs to make, with the rest of their rule.

    ``value_rows(total, vw)`` (the served device door's alone) gives the
    rows of a zero-tailed buffer that the runs' values are concatenated
    INTO: ``lanes["val_words"]`` is then its first ``total`` rows and
    ``lanes["val_words"].base`` the buffer, so that a caller that ships
    the values padded to a launch's capacity copies them once.

    Deliberately single-threaded: the per-block Python between the
    GIL-releasing zlib/numpy stretches convoys badly under a thread
    fan-out (measured 2.6x SLOWER with 4 decode threads) — the decode
    phase parallelizes by CHUNK in the planned streaming merge, not by
    thread here."""
    from ..ops.kv_format import UnsupportedBatch, pack_entries
    from ..tpu.format import read_sst_arrays

    def decode_one(run) -> Optional[dict]:
        if hasattr(run, "iterate"):  # an SSTReader
            arr = read_sst_arrays(run)
            if arr is None:
                arr = NativeCompactionBackend._arrays_from_entries(
                    list(run.iterate()), pack_entries)
        else:
            arr = NativeCompactionBackend._arrays_from_entries(
                list(run), pack_entries)
        return arr

    parts: List[dict] = []
    total = 0
    try:
        for arr in (decode_one(run) for run in runs):
            if arr is not None:
                if merge_op is not None:
                    # uint64-add fold semantics require 8-byte values
                    # (see the precondition comment in
                    # direct_merge_runs_to_files); checked PER RUN so a
                    # disqualifying workload bails after one run, not a
                    # full assembly
                    nd = arr["val_len"][arr["vtype"] != _DELETE]
                    if len(nd) and not (nd == 8).all():
                        return None
                parts.append(arr)
                total += arr["key_len"].shape[0]
                if total > max_entries:
                    # bail BEFORE materializing the rest — the cap
                    # exists to bound host memory, not to be checked
                    # after the allocation it should have prevented
                    return None
    except UnsupportedBatch:
        return None
    if total == 0:
        return None
    vw = max(p["val_words"].shape[1] for p in parts)
    for p in parts:
        w = p["val_words"].shape[1]
        if w < vw:
            p["val_words"] = np.pad(p["val_words"], [(0, 0), (0, vw - w)])
    return parts, concat_lanes(parts, total, value_rows), total, vw


_LANE_FIELDS = ("key_words_be", "key_len", "seq_hi", "seq_lo", "vtype",
                "val_words", "val_len")


def concat_lanes(parts: List[dict], total: int,
                 value_rows: Optional[Callable[[int, int], int]] = None,
                 ) -> dict:
    """The parts' rows (``total`` of them, one value width) as one dict
    of concatenated lanes; ``value_rows`` as ``read_runs_as_lanes`` says."""
    into = {}
    if value_rows is not None:
        vw = parts[0]["val_words"].shape[1]
        into["val_words"] = np.zeros(
            (value_rows(total, vw), vw), dtype=np.uint32)[:total]
    return {f: np.concatenate([p[f] for p in parts], out=into.get(f))
            for f in _LANE_FIELDS}


def lanes_decline_reason(lanes: dict,
                         merge_op: Optional[MergeOperator]) -> Optional[str]:
    """None when the array merge-resolve and the PLANAR sink can express
    these lanes, else why not — THE eligibility rule of every array
    compaction path (the device doors add their width limit on top:
    tpu/backend.py ``device_decline_reason``):

    - ``merge_without_operator``: MERGE records and no operator (only
      the tuple path keeps an unresolved operand chain);
    - ``key_width``: a key the lanes cannot hold (empty, or over
      ``PLANAR_MAX_KLEN`` = 24 bytes). Keys of DIFFERING length, 1 to 24
      bytes mixed in any proportion, are taken: the lanes carry each
      row's ``key_len``, the order everywhere is the zero-padded
      big-endian key words and then the length (the bytewise order),
      and a PLANAR block whose rows differ carries a key-length plane
      (storage/planar.py);
    - ``value_width_mixed``: the PLANAR sink needs one non-delete value
      width (kept tombstones are fine: the layout derives val_len from
      vtype);
    - ``uint64add_width``: the uint64-add RESOLUTION assumes 8-byte
      values: the fold rewrites every PUT segment to the operand sum,
      and a non-8-byte PUT parses as 0 (stream semantics only invoke
      the operator when operands exist, so a lone non-8-byte PUT must
      stay verbatim, which the array fold cannot express)."""
    if merge_op is None and bool((lanes["vtype"] == _MERGE).any()):
        return "merge_without_operator"
    kl = lanes["key_len"]
    if len(kl) and not 0 < int(kl.min()) <= int(kl.max()) <= PLANAR_MAX_KLEN:
        return "key_width"
    non_del_vlens = lanes["val_len"][lanes["vtype"] != _DELETE]
    if len(non_del_vlens) and not (
            non_del_vlens == non_del_vlens[0]).all():
        return "value_width_mixed"
    if (merge_op is not None and len(non_del_vlens)
            and not (non_del_vlens == 8).all()):
        return "uint64add_width"
    return None


def write_resolved_lanes(
    arrays: dict, count: int, path_factory, block_bytes: int,
    compression: int, bits_per_key: int, target_file_bytes: int,
    io_budget=None, build_bloom=None, trace: Optional[dict] = None,
) -> Optional[List[Tuple[str, dict]]]:
    """Write resolved lanes as PLANAR SSTs split at target_file_bytes,
    a bloom each — THE array file sink, the device doors' too. None when
    the planar layout can't express the rows; a mid-loop failure cleans
    up every file already written (nothing would ever GC the orphans).
    ``build_bloom(sub, n)`` gives one output file's bloom words from its
    own ``n`` rows (default, and where it gives None: the host bulk
    bloom; the device doors build theirs on the device). ``io_budget``
    (compaction callers only) throttles after each output file so
    compaction IO yields to foreground fsyncs. Each file's write is a
    ``tpu.planar.write`` span (``rows``; ``key_widths`` ``uniform`` /
    ``mixed`` and ``key_bytes_max`` of the file's own rows), under
    ``trace`` where the caller's thread carries no trace context of its
    own (a pool thread). Rows whose keys differ in length size their
    files and blocks by their widest key."""
    from ..observability.span import start_span
    from ..tpu.format import planar_stride, planar_widths, \
        write_sst_from_arrays

    widths = planar_widths(arrays, count)
    if widths is None:
        return None
    klen0, vlen0, _mixed = widths
    stride = planar_stride(*widths)
    entries_per_file = max(1024, target_file_bytes // max(1, stride))
    block_entries = max(64, block_bytes // max(1, stride))

    def host_bloom(sub, n):
        return NativeCompactionBackend._bulk_bloom(
            sub, n, klen0, bits_per_key).words

    if build_bloom is None:
        build_bloom = host_bloom
    outputs: List[Tuple[str, dict]] = []

    def cleanup():
        for p, _ in outputs:
            try:
                os.remove(p)
            except OSError:
                pass

    try:
        for start in range(0, count, entries_per_file):
            end = min(start + entries_per_file, count)
            sub = {f: arrays[f][start:end] for f in arrays}
            bloom_words = build_bloom(sub, end - start)
            if bloom_words is None:  # the caller's builder has none
                bloom_words = host_bloom(sub, end - start)
            path = path_factory()
            with start_span("tpu.planar.write", remote=trace,
                            rows=end - start, **key_shape(sub["key_len"])):
                props = write_sst_from_arrays(
                    sub, end - start, path,
                    bloom_words=bloom_words,
                    block_entries=block_entries,
                    compression=compression,
                    bits_per_key=bits_per_key,
                    planar=True,
                )
            if props is None:  # should not happen after width checks
                cleanup()
                return None
            outputs.append((path, props))
            if io_budget is not None:
                try:
                    size = os.path.getsize(path)
                except OSError:
                    size = (end - start) * stride
                io_budget.throttle(size)
    except BaseException:
        # a mid-loop failure (disk full on file 2 of 3) must not
        # leak file 1: the engine falls back to the tuple path and
        # nothing would ever reference or GC the orphan
        cleanup()
        raise
    return outputs


# ---------------------------------------------------------------------------
# key-range subcompactions (rocksdb max_subcompactions analog)
# ---------------------------------------------------------------------------
#
# One large compaction splits into disjoint KEY-RANGE slices executed in
# parallel across cores. Boundaries are chosen from the input runs' own
# key distribution (evenly spaced rows of each decoded SST — the lane
# image of the files' fence/block-index keys) and are plain KEYS, so a
# key's whole entry group — MERGE operand chains, duplicate seqs,
# tombstone stacks — lands in exactly one slice by construction and the
# per-slice resolve is byte-equivalent to the unsliced single pass
# (pinned by the slice-boundary matrix test). Slice outputs concatenate
# in boundary order and install atomically as ONE generation.


def shard_klen(lanes: dict) -> int:
    """The ``klen`` every key-range cut below takes: the rows' one key
    length, or 0 where their keys differ in length (each row then has
    its own: ``key_len``)."""
    kl = lanes["key_len"]
    return int(kl[0]) if len(kl) and bool((kl == kl[0]).all()) else 0


def _part_key(part: dict, i: int, klen: int) -> bytes:
    """Key bytes of row ``i`` (``klen`` as ``shard_klen`` gives it)."""
    return part["key_words_be"][i].astype(">u4").tobytes()[
        :klen or int(part["key_len"][i])]


def _first_row_ge(part: dict, key: bytes, klen: int) -> int:
    """First row index with key >= ``key`` in a (key asc)-sorted run."""
    lo, hi = 0, part["key_len"].shape[0]
    while lo < hi:
        mid = (lo + hi) // 2
        if _part_key(part, mid, klen) < key:
            lo = mid + 1
        else:
            hi = mid
    return lo


def choose_slice_boundaries(parts: List[dict], nslices: int,
                            klen: int) -> List[bytes]:
    """Up to ``nslices - 1`` boundary KEYS approximating equal-weight
    quantiles of the merged key distribution: each run contributes
    evenly spaced sample rows proportional to its size (the decoded
    form of its SST fence array), the pooled samples sort, and the
    quantile points dedupe. May return fewer boundaries than asked
    (skewed or tiny key sets)."""
    total = sum(p["key_len"].shape[0] for p in parts)
    if total == 0 or nslices <= 1:
        return []
    per_total = max(nslices * 8, 64)
    samples: List[bytes] = []
    for part in parts:
        n = part["key_len"].shape[0]
        if n == 0:
            continue
        take = max(1, min(n, (per_total * n + total - 1) // total))
        idx = np.linspace(0, n - 1, take).astype(int)
        samples.extend(_part_key(part, int(i), klen) for i in idx)
    samples.sort()
    bounds: List[bytes] = []
    lo_key = samples[0]
    for s in range(1, nslices):
        b = samples[(s * len(samples)) // nslices]
        if b > lo_key and (not bounds or b > bounds[-1]):
            bounds.append(b)
    return bounds


class KeyGroupOverSlice(Exception):
    """One key's entry stack alone holds more rows than a slice may:
    no cut at KEYS gives slices of at most that many rows."""


def _bounded_boundaries(parts: List[dict], total: int, nslices: int,
                        klen: int, max_rows: int) -> List[bytes]:
    """Boundary keys of the FEWEST slices (and at least ``nslices``,
    while the keys last) that hold at most ``max_rows`` rows each, as
    near to equal as whole key groups allow; [] where a run's keys do
    not ascend (the cut bisects each run by key). Exact, from every row:
    the runs' keys merged (one stable sort of presorted runs), a cut
    only where a key group starts. The keys' order is checked here, on
    the byte strings the merge sorts, in two numpy calls a run, and the
    seqs' not at all (the callers sort a slice's rows on the device):
    the k-way merge's own check (``_run_is_sorted``) is fifty calls a
    run over strided lanes, each handing the GIL round, which with
    eight shards cut at once costs more than the cut."""
    runs = [_order_strings(p, klen) for p in parts]
    if not all(bool((r[1:] >= r[:-1]).all()) for r in runs):
        return []
    keys = np.sort(np.concatenate(runs), kind="stable")
    # pos[g]: the merged row at which key group g starts; pos[-1]: total
    pos = np.append(
        np.flatnonzero(np.concatenate(([True], keys[1:] != keys[:-1]))),
        total)
    groups = len(pos) - 1
    # low[j]: the first group from which j slices reach the end (greedy
    # from the right); its length says how few slices cover every row
    low = [groups]
    while low[-1] > 0:
        g = int(np.searchsorted(pos, pos[low[-1]] - max_rows, side="left"))
        if g == low[-1]:
            raise KeyGroupOverSlice(
                f"a key group of {int(np.diff(pos).max())} rows, "
                f"{max_rows} rows a slice")
        low.append(g)
    n = min(max(nslices, len(low) - 1), groups)
    cuts: List[int] = []
    prev = 0
    for i in range(1, n):
        left = n - i  # slices after this cut
        lo = max(prev + 1, low[left] if left < len(low) else 0)
        hi = min(int(np.searchsorted(pos, pos[prev] + max_rows,
                                     side="right")) - 1, groups - left)
        want = int(np.searchsorted(pos, (i * total) // n, side="right")) - 1
        prev = min(max(want, lo), hi)
        cuts.append(prev)
    # a string of differing lengths ends in its key's length
    return [raw[:klen or raw[-1]] for raw in (
        keys[pos[g]:pos[g] + 1].tobytes() for g in cuts)]


def _order_strings(part: dict, klen: int) -> np.ndarray:
    """A run's keys as fixed-size byte strings whose order (numpy's: the
    zero-padded bytes) is the keys' bytewise order. One key length
    (``klen``): the key words it fills. Differing lengths (``klen`` 0):
    all 24 zero-padded key bytes and then the length as one byte more,
    which breaks the tie between a key and the same key with NUL bytes
    behind it."""
    if klen:
        words = (klen + 3) // 4
        return np.ascontiguousarray(
            part["key_words_be"][:, :words].astype(">u4")
        ).view(f"S{4 * words}").ravel()
    n = part["key_len"].shape[0]
    out = np.empty((n, 4 * 6 + 1), dtype=np.uint8)
    out[:, :24] = np.ascontiguousarray(
        part["key_words_be"].astype(">u4")).view(np.uint8).reshape(n, 24)
    out[:, 24] = part["key_len"]
    return out.view("S25").ravel()


def plan_subcompactions(parts: List[dict], total: int,
                        max_subcompactions: int, klen: int,
                        max_slice_rows: Optional[int] = None) -> List[bytes]:
    """Boundary keys for this compaction, or [] to run unsliced: THE
    planner of every key-range cut (the CPU sink's subcompactions and
    both device doors). ``klen`` is ``shard_klen``'s: the rows' one key
    length, or 0 where they differ; a boundary is a KEY of whatever
    length its row has, and the order is the bytewise one either way.
    Two rules, either or both:

    - parallelism (``max_subcompactions`` > 1): that many slices where
      every slice would clear MIN_SLICE_ENTRIES, at sampled quantiles of
      the runs' keys (``choose_slice_boundaries``), no bound on a
      slice's rows;
    - a bound (``max_slice_rows``; the served device door's place
      capacity): where ``total`` is over it, the fewest slices of at
      most that many rows each, near-equal, cut exactly
      (``_bounded_boundaries``). Raises ``KeyGroupOverSlice`` where one
      key's stack is over the bound.

    Slices only where every run is sorted: the bisect cut is only
    meaningful on sorted runs (unsorted inputs take the full-lexsort
    resolve unsliced). The parallelism rule wants (key, seq) order, as
    the k-way merge its slices feed does; the bound wants the keys'
    order alone (its callers sort on the device)."""
    nslices = min(int(max_subcompactions), total // max(1, MIN_SLICE_ENTRIES))
    bounded = max_slice_rows is not None and total > max_slice_rows
    if bounded:
        return _bounded_boundaries(parts, total, nslices, klen,
                                   max_slice_rows)
    if nslices <= 1:
        return []
    if not all(NativeCompactionBackend._run_is_sorted(p) for p in parts):
        return []
    return choose_slice_boundaries(parts, nslices, klen)


def slice_parts(parts: List[dict], bounds: List[bytes], si: int,
                klen: int, cuts: List[List[int]]) -> List[dict]:
    """Slice ``si``'s row ranges of every part (``cuts[p]`` = the
    per-part boundary row indices from _first_row_ge)."""
    out: List[dict] = []
    for p, c in zip(parts, cuts):
        lo = c[si - 1] if si > 0 else 0
        hi = c[si] if si < len(bounds) else p["key_len"].shape[0]
        if hi > lo:
            out.append({f: p[f][lo:hi] for f in _LANE_FIELDS})
    return out


def slice_lanes(parts: List[dict], bounds: List[bytes], klen: int,
                value_rows: Optional[Callable[[int, int], int]] = None,
                ) -> List[dict]:
    """THE placement of both device doors: the runs cut at ``bounds``,
    each non-empty slice as one dict of concatenated lanes, in key
    order (``value_rows`` as ``read_runs_as_lanes`` says, per slice)."""
    cuts = [[_first_row_ge(p, b, klen) for b in bounds] for p in parts]
    out: List[dict] = []
    for si in range(len(bounds) + 1):
        sub = slice_parts(parts, bounds, si, klen, cuts)
        if sub:
            rows = sum(p["key_len"].shape[0] for p in sub)
            out.append(concat_lanes(sub, rows, value_rows))
    return out


def _subcompact_to_files(
    parts: List[dict], bounds: List[bytes], klen: int, vw: int,
    merge_op: Optional[MergeOperator], drop_tombstones: bool,
    path_factory, block_bytes: int, compression: int, bits_per_key: int,
    target_file_bytes: int, io_budget,
) -> List[Tuple[str, dict]]:
    """Resolve + write every key-range slice in parallel; outputs
    concatenate in boundary order (still globally key-sorted and
    non-overlapping). Any slice failure sweeps every file already
    written by every slice and re-raises — the caller falls back to the
    unsliced/tuple path, and nothing would ever GC the orphans."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from ..observability.span import start_span
    from ..testing import failpoints as fp
    from ..utils.stats import Stats

    cuts = [[_first_row_ge(p, b, klen) for b in bounds] for p in parts]
    nsl = len(bounds) + 1
    results: List[Optional[List[Tuple[str, dict]]]] = [None] * nsl
    written_lock = threading.Lock()
    written_paths: List[str] = []

    def tracking_factory() -> str:
        path = path_factory()
        with written_lock:
            written_paths.append(path)
        return path

    def run_slice(si: int) -> None:
        fp.hit("compact.subcompact")
        Stats.get().incr("compaction.subcompactions")
        sub_parts = slice_parts(parts, bounds, si, klen, cuts)
        if not sub_parts:
            results[si] = []
            return
        fields = sub_parts[0].keys()
        sub_lanes = {f: np.concatenate([p[f] for p in sub_parts])
                     for f in fields}
        sub_total = sub_lanes["key_len"].shape[0]
        arrays, count = NativeCompactionBackend._resolve(
            sub_parts, sub_lanes, sub_total, vw, merge_op,
            drop_tombstones)
        if count == 0:
            results[si] = []
            return
        outs = write_resolved_lanes(
            arrays, count, tracking_factory, block_bytes, compression,
            bits_per_key, target_file_bytes, io_budget=io_budget)
        if outs is None:  # cannot happen after the global width checks
            raise RuntimeError(f"slice {si}: planar sink declined")
        results[si] = outs

    with start_span("compact.subcompactions", slices=nsl):
        with ThreadPoolExecutor(
            max_workers=min(nsl, os.cpu_count() or 2),
            thread_name_prefix="subcompact",
        ) as pool:
            futs = [pool.submit(run_slice, si) for si in range(nsl)]
            errs = []
            for f in futs:
                try:
                    f.result()
                except BaseException as e:
                    errs.append(e)
        if errs:
            with written_lock:
                for p in written_paths:
                    try:
                        os.remove(p)
                    except OSError:
                        pass
            raise errs[0]
    return [o for outs in results for o in (outs or [])]


def direct_merge_runs_to_files(
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
    """The CPU array compaction pipeline: runs → lanes → merge-resolve
    (native C when loaded, numpy lexsort+reduceat otherwise) → PLANAR
    files. [(path, props)], [] for an all-tombstoned result, or None →
    the engine's tuple path. Shared by CpuCompactionBackend and
    NativeCompactionBackend so every CPU-configured engine compacts
    array-to-array when the inputs allow it.

    Inputs whose projected lane image exceeds the compaction memory
    budget (or the MAX_DIRECT_ENTRIES cap) stream through the chunked
    bounded-memory merge instead of materializing here — byte-identical
    output, working set fixed by RSTPU_COMPACT_MEM_BUDGET
    (storage/stream_merge.py). Smaller compactions keep the in-RAM
    path: it already fits the ceiling, and key-range subcompactions
    (``max_subcompactions > 1``) can then resolve+write disjoint slices
    in parallel across cores. ``io_budget`` paces the output writes so
    compaction IO yields to foreground fsyncs; ``mem_tracker`` records
    the materialized-bytes high-water for the
    ``compaction.peak_bytes_materialized`` gauge on both paths."""
    from ..observability.span import start_span
    from .stream_merge import maybe_stream_merge

    if merge_op is not None and not isinstance(merge_op, UInt64AddOperator):
        return None
    streamed = maybe_stream_merge(
        runs, merge_op, drop_tombstones, path_factory, block_bytes,
        compression, bits_per_key, target_file_bytes,
        io_budget=io_budget, mem_tracker=mem_tracker,
        memory_budget_bytes=memory_budget_bytes,
    )
    if streamed is not None:
        return streamed
    read = read_runs_as_lanes(runs, merge_op)
    if read is None:
        return None
    parts, lanes, total, vw = read
    if lanes_decline_reason(lanes, merge_op) is not None:
        return None
    # in-RAM accounting for the peak gauge: per-run parts plus their
    # concatenation are live together right now
    inram_bytes = 2 * int(sum(a.nbytes for a in lanes.values()))
    if mem_tracker is not None:
        mem_tracker.add(inram_bytes)
    try:
        if max_subcompactions > 1:
            klen = shard_klen(lanes)
            bounds = plan_subcompactions(
                parts, total, max_subcompactions, klen)
            if bounds:
                return _subcompact_to_files(
                    parts, bounds, klen, vw, merge_op, drop_tombstones,
                    path_factory, block_bytes, compression, bits_per_key,
                    target_file_bytes, io_budget)
        with start_span("compact.resolve", entries=total):
            arrays, count = NativeCompactionBackend._resolve(
                parts, lanes, total, vw, merge_op, drop_tombstones)
        if count == 0:
            return []  # fully compacted away — nothing to write
        return write_resolved_lanes(
            arrays, count, path_factory, block_bytes, compression,
            bits_per_key, target_file_bytes, io_budget=io_budget,
        )
    finally:
        if mem_tracker is not None:
            mem_tracker.sub(inram_bytes)
