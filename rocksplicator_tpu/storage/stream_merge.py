"""Streaming bounded-memory compaction: the chunked k-way lane merge.

The round-9 array compaction pipeline (native_compaction.py) decodes
EVERY input run into RAM before resolving — the last O(dataset)
allocation in the engine (fine at 200k entries, an OOM at production
level sizes). This module replaces that merge with a streaming pipeline
whose working set is a fixed budget regardless of level size, the shape
Co-KV (arxiv 1807.04151) and LUDA (arxiv 2004.03054) use for
host/device compaction offload:

- each input run is read through a fixed-size lane *window*
  (tpu/format.SstBlockLaneSource — block-granular decode-on-demand,
  probing but never filling the decoded-block LRU);
- the merge advances in *chunks*: the cut key is the minimum loaded
  frontier over runs that still have undecoded blocks, so every key
  strictly below the cut is fully loaded in every run and one
  merge-resolve call sees each key's whole entry stack — per-key
  resolution is byte-identical to the unsliced pass by construction;
- when a single key's entry group spans a window boundary (a giant
  MERGE-operand chain, a dup-key run, a tombstone stack crossing
  blocks), its loaded rows are CARRIED raw across the chunk boundary
  and resolved together with the rest of the group once the cut passes
  the key — the straddle-state the slice-boundary matrix pins;
- resolved chunks stream into a per-file buffer that reproduces the
  unsliced sink's file splits exactly (same lazy width derivation, same
  entries-per-file arithmetic), so outputs are byte-identical
  file-for-file, emitted as input windows drain — and still installed
  by the engine as ONE atomic generation;
- a pluggable ChunkResolver runs the resolve: the CPU resolver is the
  shared native/numpy merge-resolve; the TPU resolver
  (tpu/compaction_service.TpuChunkResolver) launches the device kernel
  and materializes one chunk BEHIND the decode — decode of chunk
  N+1 overlaps chunk N's device→host transfer (the double-buffered
  chunk shape the silicon bench needs; the resolve itself still syncs
  at submit — see TpuChunkResolver's honest-scope note).

The ceiling is load-bearing: :class:`CompactionMemoryBudget`
(``RSTPU_COMPACT_MEM_BUDGET`` / DBOptions.compaction_memory_budget_bytes)
sizes the windows, window sizes HALVE while the process is over budget
(degrade, never abort), and the per-compaction high-water feeds the
``compaction.peak_bytes_materialized`` gauge the acceptance test
asserts against. Failpoint seams ``compact.stream.chunk`` /
``compact.stream.refill`` make the crash-at-any-chunk story testable:
no output is ever installed unless the whole pipeline finishes, so a
kill at any seam leaves reopen exactly pre-compaction.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import List, Optional, Tuple

import numpy as np

from ..observability.span import start_span
from ..testing import failpoints as fp
from ..utils.stats import Stats

_PUT, _DELETE, _MERGE = 1, 2, 3

# window/chunk lanes carry both key byte orders (the TPU resolver wants
# LE for bloom hashing); the CPU resolver concatenates only CPU_FIELDS
from ..ops.kv_format import LANE_FIELDS as FIELDS  # noqa: E402

CPU_FIELDS = tuple(f for f in FIELDS if f != "key_words_le")

# --- knobs (README "Tuning") ---------------------------------------------
# per-refill window target in entries; the chunk the resolver sees is
# roughly nruns windows
ENV_CHUNK_ENTRIES = "RSTPU_COMPACT_CHUNK_ENTRIES"
DEFAULT_CHUNK_ENTRIES = 1 << 16
# process-wide hard ceiling on live compaction lane bytes
ENV_MEM_BUDGET = "RSTPU_COMPACT_MEM_BUDGET"
DEFAULT_MEM_BUDGET = 256 << 20
# "auto" streams when the projected in-RAM working set exceeds the
# budget (or the direct path's entry cap); "1"/"always" streams every
# streamable full compaction; "0"/"never" disables streaming
ENV_STREAM_MODE = "RSTPU_COMPACT_STREAM"
# window degradation floor (block granularity still applies above it)
MIN_WINDOW_ENTRIES = 256

# test/chaos overrides (same pattern as native_compaction's
# MIN_SLICE_ENTRIES: chaos lowers the scale so streaming and its seams
# are reachable on tiny chaos memtables)
STREAM_MODE_OVERRIDE: Optional[str] = None
CHUNK_ENTRIES_OVERRIDE: Optional[int] = None


def stream_mode() -> str:
    if STREAM_MODE_OVERRIDE is not None:
        return STREAM_MODE_OVERRIDE
    raw = os.environ.get(ENV_STREAM_MODE, "auto").lower()
    if raw in ("0", "never", "false"):
        return "never"
    if raw in ("1", "always", "true"):
        return "always"
    return "auto"


def default_chunk_entries() -> int:
    if CHUNK_ENTRIES_OVERRIDE is not None:
        return int(CHUNK_ENTRIES_OVERRIDE)
    try:
        return max(MIN_WINDOW_ENTRIES,
                   int(os.environ.get(ENV_CHUNK_ENTRIES,
                                      DEFAULT_CHUNK_ENTRIES)))
    except ValueError:
        return DEFAULT_CHUNK_ENTRIES


class _StreamDecline(Exception):
    """The inputs turned out inexpressible mid-stream (width drift, a
    MERGE record without an operator, kernel fallback flag): clean up
    every written output and let the caller take the non-streaming
    path."""


class CompactionMemoryBudget:
    """Process-wide ceiling on live compaction lane bytes. One instance
    serves every DB in the process (concurrent compactions share RAM
    the way they share the disk); per-compaction accounting hangs off
    :meth:`tracker`."""

    _instance: Optional["CompactionMemoryBudget"] = None
    _instance_lock = threading.Lock()

    def __init__(self, budget_bytes: int):
        self.budget_bytes = max(1, int(budget_bytes))
        self._lock = threading.Lock()
        self._live = 0

    @classmethod
    def get(cls) -> "CompactionMemoryBudget":
        if cls._instance is None:
            with cls._instance_lock:
                if cls._instance is None:
                    try:
                        cap = int(os.environ.get(
                            ENV_MEM_BUDGET, DEFAULT_MEM_BUDGET))
                    except ValueError:
                        cap = DEFAULT_MEM_BUDGET
                    cls._instance = cls(cap)
        return cls._instance

    @classmethod
    def reset_for_test(cls, budget_bytes: Optional[int] = None) -> None:
        with cls._instance_lock:
            cls._instance = (
                cls(budget_bytes) if budget_bytes is not None else None)

    def _add(self, nbytes: int) -> None:
        with self._lock:
            self._live += nbytes

    def _sub(self, nbytes: int) -> None:
        with self._lock:
            self._live -= nbytes

    def live_bytes(self) -> int:
        with self._lock:
            return self._live

    def tracker(self) -> "MemTracker":
        return MemTracker(self)


class MemTracker:
    """Per-compaction view onto the process budget: live bytes, the
    high-water mark the ``compaction.peak_bytes_materialized`` gauge
    reports, and release back to the process counter on close()."""

    def __init__(self, budget: CompactionMemoryBudget):
        self._budget = budget
        self._lock = threading.Lock()
        self.live = 0
        self.peak = 0

    def add(self, nbytes: int) -> None:
        if nbytes <= 0:
            return
        with self._lock:
            self.live += nbytes
            if self.live > self.peak:
                self.peak = self.live
        self._budget._add(nbytes)

    def sub(self, nbytes: int) -> None:
        if nbytes <= 0:
            return
        with self._lock:
            self.live -= nbytes
        self._budget._sub(nbytes)

    def process_live(self) -> int:
        return self._budget.live_bytes()

    @property
    def budget_bytes(self) -> int:
        return self._budget.budget_bytes

    def close(self) -> None:
        """Release any residual accounting (windows alive at pipeline
        exit) back to the process counter; peak is preserved."""
        with self._lock:
            residual, self.live = self.live, 0
        if residual:
            self._budget._sub(residual)


def _lanes_nbytes(lanes: dict) -> int:
    return int(sum(np.asarray(a).nbytes for a in lanes.values()))


def _row_key(win: dict, i: int, klen: int) -> bytes:
    return win["key_words_be"][i].astype(">u4").tobytes()[:klen]


def _first_ge(win: dict, lo: int, hi: int, key: bytes, klen: int) -> int:
    while lo < hi:
        mid = (lo + hi) // 2
        if _row_key(win, mid, klen) < key:
            lo = mid + 1
        else:
            hi = mid
    return lo


def _first_gt(win: dict, lo: int, hi: int, key: bytes, klen: int) -> int:
    while lo < hi:
        mid = (lo + hi) // 2
        if _row_key(win, mid, klen) <= key:
            lo = mid + 1
        else:
            hi = mid
    return lo


class _RunCursor:
    """One input run's decode window: a block-granular slice of its lane
    image, refilled as the merge frontier drains it."""

    def __init__(self, source, vw: int, klen: int, tracker: MemTracker):
        self._src = source
        self._vw = vw
        self._klen = klen
        self._tracker = tracker
        self._next_block = 0
        self._win: Optional[dict] = None
        self._pos = 0
        self._n = 0
        self.win_bytes = 0

    @property
    def file_done(self) -> bool:
        return self._next_block >= self._src.num_blocks

    @property
    def empty(self) -> bool:
        return self._pos >= self._n

    @property
    def exhausted(self) -> bool:
        return self.empty and self.file_done

    def refill(self, target_entries: int) -> int:
        """Replace the drained window with >= target_entries fresh rows
        (block granular; only ever called on an EMPTY cursor — a
        stalled cut's unconsumed rows move out via take_eq, not by
        extending the window). Returns the RETIRED byte count of the
        replaced window — the pipeline defers releasing it until the
        in-flight chunk holding views of it has been collected."""
        fp.hit("compact.stream.refill")
        Stats.get().incr("compaction.stream_refills")
        parts: List[dict] = []
        rows = 0
        while self._next_block < self._src.num_blocks \
                and rows < target_entries:
            lanes = self._src.decode_blocks(
                self._next_block, self._next_block + 1)
            self._next_block += 1
            w = lanes["val_words"].shape[1]
            if w < self._vw:
                lanes["val_words"] = np.pad(
                    lanes["val_words"], [(0, 0), (0, self._vw - w)])
            rows += lanes["key_len"].shape[0]
            parts.append(lanes)
        retired = self.win_bytes
        if len(parts) == 1:
            self._win = parts[0]
        else:
            self._win = {f: np.concatenate([p[f] for p in parts])
                         for f in FIELDS}
        self._pos = 0
        self._n = self._win["key_len"].shape[0]
        self.win_bytes = _lanes_nbytes(self._win)
        self._tracker.add(self.win_bytes)
        return retired

    def frontier_key(self) -> bytes:
        """Last loaded key: every undecoded row of this run is >= it."""
        return _row_key(self._win, self._n - 1, self._klen)

    def take_lt(self, cut: Optional[bytes]) -> Optional[dict]:
        """Consume rows with key < cut (all remaining rows when cut is
        None); returns a lane-slice view or None."""
        if self.empty:
            return None
        hi = self._n if cut is None else _first_ge(
            self._win, self._pos, self._n, cut, self._klen)
        if hi <= self._pos:
            return None
        sl = {f: self._win[f][self._pos:hi] for f in FIELDS}
        self._pos = hi
        return sl

    def take_eq(self, cut: bytes) -> Optional[dict]:
        """Consume rows with key == cut as a COPY (carry rows must not
        pin a window the next refill retires)."""
        if self.empty:
            return None
        lo = _first_ge(self._win, self._pos, self._n, cut, self._klen)
        hi = _first_gt(self._win, lo, self._n, cut, self._klen)
        if hi <= lo:
            return None
        sl = {f: self._win[f][lo:hi].copy() for f in FIELDS}
        self._pos = hi
        return sl

    def release(self) -> None:
        self._tracker.sub(self.win_bytes)
        self.win_bytes = 0
        self._win = None


class CpuChunkResolver:
    """The shared native/numpy merge-resolve, run synchronously — one
    chunk in flight at a time (``pipelined = False``: the pipeline
    collects each chunk immediately, so consumed windows release before
    the next refill instead of staying pinned a whole extra chunk the
    way the device double buffer requires)."""

    fields = CPU_FIELDS
    pipelined = False

    def submit(self, parts: List[dict], lanes: dict, total: int, vw: int,
               merge_op, drop_tombstones: bool):
        from .native_compaction import NativeCompactionBackend

        return NativeCompactionBackend._resolve(
            parts, lanes, total, vw, merge_op, drop_tombstones)

    def collect(self, handle) -> Tuple[dict, int]:
        return handle


class _FileBufferSink:
    """Streaming output sink byte-identical to write_resolved_lanes:
    resolved chunks buffer per OUTPUT FILE (bounded by
    target_file_bytes, not dataset size) and each file writes through
    the same planar writer + bulk bloom with the same width derivation
    — klen from the first resolved row, vlen from the first non-delete
    resolved row — so file splits and bytes match the unsliced pass
    exactly."""

    def __init__(self, path_factory, block_bytes: int, compression: int,
                 bits_per_key: int, target_file_bytes: int,
                 tracker: MemTracker, io_budget=None,
                 plan_klen: int = 0, plan_vlen: int = 0):
        self._pf = path_factory
        self._block_bytes = block_bytes
        self._compression = compression
        self._bits_per_key = bits_per_key
        self._target_file_bytes = target_file_bytes
        self._tracker = tracker
        self._io_budget = io_budget
        self._plan_klen = plan_klen
        self._plan_vlen = plan_vlen
        self._buf: List[dict] = []
        self._buf_rows = 0
        self._buf_bytes = 0
        self._klen: Optional[int] = None
        self._vlen: Optional[int] = None
        self._epf = 0  # entries per file, once widths are known
        self._block_entries = 0
        self.outputs: List[Tuple[str, dict]] = []

    def _derive_widths(self, arrays: dict, count: int) -> None:
        from ..tpu.format import planar_stride

        if self._klen is None and count:
            self._klen = int(arrays["key_len"][0])
        if self._vlen is None:
            non_del = np.flatnonzero(arrays["vtype"][:count] != _DELETE)
            if len(non_del):
                self._vlen = int(arrays["val_len"][int(non_del[0])])
        if self._klen is not None and self._vlen is not None \
                and not self._epf:
            stride = planar_stride(self._klen, self._vlen)
            self._epf = max(
                1024, self._target_file_bytes // max(1, stride))
            self._block_entries = max(
                64, self._block_bytes // max(1, stride))

    def append(self, arrays: dict, count: int) -> None:
        if count == 0:
            return
        # trimmed rows COPY out of the resolver's chunk-sized output:
        # a [:count] view would pin the full base allocation (pow2-
        # padded on the TPU resolver) while the tracker counted only
        # the view — under heavy dedup the untracked bases would dwarf
        # the ceiling. count == base rows keeps the whole-array view.
        sub = {}
        for f in CPU_FIELDS:
            a = np.asarray(arrays[f])
            sub[f] = a if a.shape[0] == count else a[:count].copy()
        self._buf.append(sub)
        self._buf_rows += count
        nb = _lanes_nbytes(sub)
        self._buf_bytes += nb
        self._tracker.add(nb)
        self._derive_widths(sub, count)
        # vlen stays unknown while the resolved stream is all-tombstone
        # (drop_tombstones=False): buffer until a value appears — the
        # unsliced pass derives vlen from the SAME first non-delete row,
        # and splitting earlier would diverge from its file boundaries.
        # That wait must not defeat the ceiling: once a full file's
        # worth (by the PLANNED value width, which every later
        # non-delete row is width-checked to match) is buffered, seed
        # vlen from the plan. Any stream with a value ANYWHERE is still
        # byte-identical — the unsliced pass would retroactively use
        # the same vlen for this prefix; only a 100%-tombstone output
        # larger than one file now splits by the planned width instead
        # of the degenerate vlen=0 (same entries, bounded memory — the
        # honest trade, noted in PARITY).
        if not self._epf and self._vlen is None:
            from ..tpu.format import planar_stride

            stride = planar_stride(self._plan_klen, self._plan_vlen)
            plan_epf = max(1024,
                           self._target_file_bytes // max(1, stride))
            if self._buf_rows >= plan_epf:
                self._vlen = self._plan_vlen
                self._derive_widths(sub, count)
        while self._epf and self._buf_rows >= self._epf:
            self._flush_file(self._epf)

    def _pop_rows(self, n: int) -> dict:
        taken: List[dict] = []
        need = n
        while need > 0:
            head = self._buf[0]
            hn = head["key_len"].shape[0]
            if hn <= need:
                taken.append(self._buf.pop(0))
                need -= hn
            else:
                taken.append({f: head[f][:need] for f in CPU_FIELDS})
                self._buf[0] = {f: head[f][need:] for f in CPU_FIELDS}
                need = 0
        self._buf_rows -= n
        if len(taken) == 1:
            return taken[0]
        return {f: np.concatenate([p[f] for p in taken])
                for f in CPU_FIELDS}

    def _flush_file(self, n: int) -> None:
        from .native_compaction import NativeCompactionBackend
        from ..tpu.format import write_sst_from_arrays

        sub = self._pop_rows(n)
        bloom = NativeCompactionBackend._bulk_bloom(
            sub, n, self._klen, self._bits_per_key)
        path = self._pf()
        props = write_sst_from_arrays(
            sub, n, path,
            bloom_words=bloom.words,
            block_entries=self._block_entries,
            compression=self._compression,
            bits_per_key=self._bits_per_key,
            planar=True,
        )
        if props is None:
            # widths the planar layout can't express slipped past the
            # window checks — decline, caller takes the non-stream path
            raise _StreamDecline("planar sink declined a file slice")
        self.outputs.append((path, props))
        # accounting: written rows leave the buffer
        remaining = _lanes_nbytes_list(self._buf)
        self._tracker.sub(self._buf_bytes - remaining)
        self._buf_bytes = remaining
        if self._io_budget is not None:
            try:
                size = os.path.getsize(path)
            except OSError:
                size = 0
            if size:
                self._io_budget.throttle(size)

    def finish(self) -> List[Tuple[str, dict]]:
        if self._buf_rows:
            if not self._epf:
                # an all-tombstone resolved stream (kept tombstones,
                # no values): vlen degenerates to 0, as the unsliced
                # width derivation does
                self._vlen = 0 if self._vlen is None else self._vlen
                self._klen = (int(self._buf[0]["key_len"][0])
                              if self._klen is None else self._klen)
                self._derive_widths(self._buf[0],
                                    self._buf[0]["key_len"].shape[0])
            while self._buf_rows > self._epf:
                self._flush_file(self._epf)
            if self._buf_rows:
                self._flush_file(self._buf_rows)
        return self.outputs

    def abandon(self) -> None:
        """Sweep every written output (nothing would ever GC them)."""
        self._tracker.sub(self._buf_bytes)
        self._buf = []
        self._buf_bytes = 0
        self._buf_rows = 0
        for p, _ in self.outputs:
            try:
                os.remove(p)
            except OSError:
                pass
        self.outputs = []


def _lanes_nbytes_list(parts: List[dict]) -> int:
    return int(sum(_lanes_nbytes(p) for p in parts))


def _check_chunk_semantics(lanes: dict, merge_op) -> None:
    """The lanes_decline_reason() preconditions, applied per chunk instead
    of per dataset (probes promise widths; vtype content can only be
    checked once decoded)."""
    if merge_op is None:
        if bool((lanes["vtype"] == _MERGE).any()):
            raise _StreamDecline("MERGE records without an operator")
    else:
        is_del = lanes["vtype"] == _DELETE
        vl = lanes["val_len"][~is_del]
        if len(vl) and not (vl == 8).all():
            raise _StreamDecline("uint64add needs 8-byte values")


def plan_stream(runs, merge_op):
    """Probe every run for block-granular streamability. Returns
    (sources, total, klen, vlen, vw) or None when any run can't stream
    or the runs' widths are incompatible (the in-RAM path decides for
    itself — it has its own declines)."""
    from ..tpu.format import SstBlockLaneSource

    sources = []
    for run in runs:
        if not hasattr(run, "iterate"):
            return None
        src = SstBlockLaneSource.probe(run)
        if src is None:
            return None
        sources.append(src)
    if not sources:
        return None
    klens = {s.klen for s in sources}
    vlens = {s.vlen for s in sources}
    if len(klens) != 1 or len(vlens) != 1:
        return None
    klen, vlen = klens.pop(), vlens.pop()
    if merge_op is not None and vlen != 8:
        return None
    total = sum(s.num_entries for s in sources)
    if total == 0:
        return None
    vw = max(2, (vlen + 3) // 4)
    return sources, total, klen, vlen, vw


def est_row_bytes(vw: int) -> int:
    """Lane bytes per decoded window row (both key byte orders + the
    scalar lanes + the value words)."""
    return 68 + 4 * vw


def maybe_stream_merge(
    runs: List,
    merge_op,
    drop_tombstones: bool,
    path_factory,
    block_bytes: int,
    compression: int,
    bits_per_key: int,
    target_file_bytes: int,
    io_budget=None,
    mem_tracker: Optional[MemTracker] = None,
    memory_budget_bytes: int = 0,
    resolver=None,
) -> Optional[List[Tuple[str, dict]]]:
    """Run the streaming pipeline when the mode and the inputs call for
    it. Returns [(path, props)] (possibly []) on success, None when the
    caller should take the in-RAM/tuple path (not streamable, below the
    auto threshold, mode off, or declined mid-stream — any written
    outputs are swept before returning)."""
    mode = stream_mode()
    if mode == "never":
        return None
    plan = plan_stream(runs, merge_op)
    if plan is None:
        return None
    sources, total, klen, vlen, vw = plan
    budget = CompactionMemoryBudget.get()
    budget_bytes = int(memory_budget_bytes) or budget.budget_bytes
    if mode == "auto":
        from .native_compaction import MAX_DIRECT_ENTRIES

        # the in-RAM path holds per-run parts PLUS their concatenation
        projected = 2 * total * est_row_bytes(vw)
        if projected <= budget_bytes and total <= MAX_DIRECT_ENTRIES:
            return None
    from ..ops.kv_format import UnsupportedBatch

    tracker = mem_tracker or budget.tracker()
    try:
        return _run_pipeline(
            sources, total, klen, vlen, vw, merge_op, drop_tombstones,
            path_factory, block_bytes, compression, bits_per_key,
            target_file_bytes, io_budget, tracker, budget_bytes,
            resolver or CpuChunkResolver())
    except (UnsupportedBatch, _StreamDecline) as e:
        Stats.get().incr("compaction.stream_declines")
        logging.getLogger(__name__).info(
            "streaming merge declined (%s); using in-RAM path", e)
        return None
    finally:
        tracker.close()


def _run_pipeline(
    sources, total: int, klen: int, vlen: int, vw: int, merge_op,
    drop_tombstones: bool, path_factory, block_bytes: int,
    compression: int, bits_per_key: int, target_file_bytes: int,
    io_budget, tracker: MemTracker, budget_bytes: int, resolver,
) -> List[Tuple[str, dict]]:
    from .compaction_scheduler import adaptive_chunk_entries

    nruns = len(sources)
    row_bytes = est_row_bytes(vw)
    chunk_target = default_chunk_entries()
    sink = _FileBufferSink(
        path_factory, block_bytes, compression, bits_per_key,
        target_file_bytes, tracker, io_budget=io_budget,
        plan_klen=klen, plan_vlen=vlen)
    cursors = [_RunCursor(s, vw, klen, tracker) for s in sources]
    carry_parts: List[dict] = []
    carry_key: Optional[bytes] = None
    pending = None           # in-flight resolver handle (double buffer)
    pending_release = 0      # retired window bytes pinned by `pending`
    retired_bytes = 0        # retired windows the NEXT submit will pin
    try:
        with start_span("compact.stream", runs=nruns, entries=total,
                        budget_bytes=budget_bytes):
            while True:
                # window sizing from the ACTUAL headroom left under the
                # ceiling — live bytes already count the sink's file
                # buffer, the in-flight chunk, and windows the double
                # buffer still pins, so refills shrink as any of them
                # grow (degrade, never abort: the floor is one block's
                # granularity). Stall pressure shrinks the chunk too
                # (compaction should hold LESS memory precisely while
                # admissions are being delayed).
                eff_chunk = adaptive_chunk_entries(chunk_target, io_budget)
                headroom = budget_bytes - tracker.process_live()
                # /5: a window generation coexists with its chunk
                # CONCAT copy (same size), the resolved chunk, the
                # sink's file buffer, and (pipelined) the previous
                # generation the double buffer still pins — plus
                # block-granularity rounding on every refill
                w_budget = (headroom // 5) // max(1, nruns * row_bytes)
                w = max(MIN_WINDOW_ENTRIES,
                        min(eff_chunk // max(1, nruns), w_budget))
                for c in cursors:
                    if c.empty and not c.file_done:
                        retired_bytes += c.refill(w)
                cut: Optional[bytes] = None
                for c in cursors:
                    if not c.empty and not c.file_done:
                        k = c.frontier_key()
                        if cut is None or k < cut:
                            cut = k
                parts: List[dict] = []
                if carry_key is not None and (
                        cut is None or carry_key < cut):
                    parts.extend(carry_parts)
                    retired_bytes += _lanes_nbytes_list(carry_parts)
                    carry_parts, carry_key = [], None
                for c in cursors:
                    sl = c.take_lt(cut)
                    if sl is not None:
                        parts.append(sl)
                if not parts:
                    if cut is None:
                        break  # every run exhausted, no carry left
                    # stall: the cut key's group spans the bounding
                    # run's window end — carry its loaded rows raw and
                    # refill before cutting again
                    for c in cursors:
                        sl = c.take_eq(cut)
                        if sl is not None:
                            carry_parts.append(sl)
                            tracker.add(_lanes_nbytes(sl))
                    carry_key = cut
                    continue
                fp.hit("compact.stream.chunk")
                Stats.get().incr("compaction.stream_chunks")
                lanes = {
                    f: np.concatenate([p[f] for p in parts])
                    if len(parts) > 1 else parts[0][f]
                    for f in resolver.fields
                }
                # the multi-part concatenation is a real second copy of
                # the consumed window rows (the in-RAM path counts the
                # same 2x for the same reason); it lives through
                # submit() and is accounted for that span
                concat_bytes = (_lanes_nbytes(lanes)
                                if len(parts) > 1 else 0)
                tracker.add(concat_bytes)
                chunk_n = int(lanes["key_len"].shape[0])
                _check_chunk_semantics(lanes, merge_op)

                def drain_pending():
                    nonlocal pending, pending_release
                    if pending is None:
                        return
                    arrays, count = resolver.collect(pending)
                    sink.append(arrays, count)
                    tracker.sub(pending_release)
                    pending, pending_release = None, 0

                drain_pending()
                pending = resolver.submit(
                    parts, lanes, chunk_n, vw, merge_op, drop_tombstones)
                # both resolvers fully consume the concat inside
                # submit() (CPU resolves it, TPU ships it to device and
                # syncs) — drop our references WITH the accounting, on
                # the pipelined path too, so the freed bytes and the
                # tracker agree before the next window sizing
                tracker.sub(concat_bytes)
                del parts, lanes
                # windows retired before this submit stay pinned by the
                # chunk's views until it is collected
                pending_release, retired_bytes = retired_bytes, 0
                if not getattr(resolver, "pipelined", True):
                    # synchronous resolver: nothing overlaps, release
                    # the consumed windows before the next refill
                    drain_pending()
            if pending is not None:
                arrays, count = resolver.collect(pending)
                sink.append(arrays, count)
                tracker.sub(pending_release)
                pending_release = 0
            outputs = sink.finish()
            Stats.get().incr("compaction.stream_merges")
            return outputs
    except BaseException:
        sink.abandon()
        raise
    finally:
        for c in cursors:
            c.release()
