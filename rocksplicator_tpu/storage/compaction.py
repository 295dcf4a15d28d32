"""Compaction: k-way merge of sorted runs with LSM resolution.

This module defines the **CompactionBackend seam** — the boundary behind
which the TPU offload plugs in (BASELINE.json north star: "L0→Ln compaction
jobs ... ship their key-value blocks to a TPU sidecar"). The default
backend is the CPU heap-merge; ``rocksplicator_tpu.tpu.compaction_service``
registers a TPU backend implementing the same interface.

An input "run" is an iterator of (key, seq, vtype, value) in (key asc,
seq desc) order; the output is the merged, deduplicated stream in the same
order, with per-key resolution:
- newest PUT wins; MERGE operands above it fold into it
- newest DELETE wins; at the bottom level tombstones (and the keys they
  shadow) are dropped entirely
- unresolved MERGE chains are partially merged when the operator allows
"""

from __future__ import annotations

import heapq
import logging
from typing import Iterable, Iterator, List, Optional, Tuple

from ..utils.stats import Stats, tagged
from .merge import MergeOperator, resolve_entry_group

log = logging.getLogger(__name__)

Entry = Tuple[bytes, int, int, bytes]  # key, seq, vtype, value

HOST_FALLBACKS = "tpu.host_fallbacks"


def record_host_fallback(reason: str, detail: str = "",
                         exc_info: bool = False) -> None:
    """Work that was routed to the fast path (the device, the native
    library) ran on slower host code instead. The node keeps going
    (that is the guarantee), but never quietly: every such seam counts
    under ONE family, ``tpu.host_fallbacks reason=<seam>``, and logs at
    ERROR, so a chip run can require every reason to be zero."""
    Stats.get().incr(tagged(HOST_FALLBACKS, reason=reason))
    log.error("%s reason=%s: %s", HOST_FALLBACKS, reason, detail,
              exc_info=exc_info)


def host_fallback_counts() -> dict:
    """{reason: count} of every ``tpu.host_fallbacks`` counter so far."""
    prefix = HOST_FALLBACKS + " reason="
    counters = Stats.get().export_state()["counters"]
    return {name[len(prefix):]: int(c["total"])
            for name, c in counters.items() if name.startswith(prefix)}


class CompactionBackend:
    name = "base"
    # True on backends whose merges are meant to run on an accelerator:
    # the engine counts and logs every job such a backend hands back to
    # host code (record_host_fallback).
    runs_on_device = False
    # True on backends whose ``merge_runs_to_files`` accepts the
    # ``max_subcompactions``/``io_budget`` keywords (key-range
    # subcompactions + foreground-yielding IO budget); the engine only
    # passes them to backends that declare support, so third-party
    # backend signatures stay valid.
    supports_subcompactions = False
    # True on backends that additionally accept the round-17
    # ``mem_tracker``/``memory_budget_bytes`` keywords (streaming
    # bounded-memory merge + peak gauge) — a separate capability so a
    # third-party backend that declared subcompaction support before
    # round 17 keeps its narrower signature valid.
    supports_memory_budget = False

    def merge_runs(
        self,
        runs: List[Iterable[Entry]],
        merge_op: Optional[MergeOperator],
        drop_tombstones: bool,
    ) -> Iterator[Entry]:
        raise NotImplementedError


class CpuCompactionBackend(CompactionBackend):
    """Heap-based k-way merge — the 32-core-CPU baseline the TPU backend is
    benchmarked against. Also carries the DIRECT array sink
    (``merge_runs_to_files``): when every input run reads as lanes and
    widths are uniform, the whole compaction runs array-to-array (lexsort
    merge + segment resolve + planar writer) with no per-entry Python —
    the engine's ``_write_entry_stream`` loop becomes the fallback, not
    the common case."""

    name = "cpu"
    supports_subcompactions = True
    supports_memory_budget = True

    def merge_runs(
        self,
        runs: List[Iterable[Entry]],
        merge_op: Optional[MergeOperator],
        drop_tombstones: bool,
    ) -> Iterator[Entry]:
        # (key asc, seq desc) merge order.
        merged = heapq.merge(*runs, key=lambda e: (e[0], -e[1]))
        return resolve_stream(merged, merge_op, drop_tombstones)

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
    ):
        """[(path, props)], [] for an all-tombstoned result, or None →
        the engine's tuple path. Shared implementation with the native
        backend (storage/native_compaction.direct_merge_runs_to_files);
        the native C resolve is used when the library is loaded, the
        numpy lexsort+reduceat resolve otherwise. Oversized inputs
        stream through the bounded-memory chunked merge
        (storage/stream_merge.py). With ``max_subcompactions > 1`` the
        in-RAM merge splits into parallel key-range slices;
        ``io_budget`` paces output writes; ``mem_tracker`` feeds the
        peak-bytes-materialized gauge."""
        from .native_compaction import direct_merge_runs_to_files

        return direct_merge_runs_to_files(
            runs, merge_op, drop_tombstones, path_factory, block_bytes,
            compression, bits_per_key, target_file_bytes,
            max_subcompactions=max_subcompactions, io_budget=io_budget,
            mem_tracker=mem_tracker,
            memory_budget_bytes=memory_budget_bytes,
        )


def resolve_stream(
    merged: Iterable[Entry],
    merge_op: Optional[MergeOperator],
    drop_tombstones: bool,
) -> Iterator[Entry]:
    """Collapse a (key asc, seq desc)-ordered stream to one entry per key."""
    cur_key: Optional[bytes] = None
    group: List[Entry] = []
    for entry in merged:
        if entry[0] != cur_key:
            if group:
                yield from _resolve_group(group, merge_op, drop_tombstones)
            cur_key = entry[0]
            group = [entry]
        else:
            group.append(entry)
    if group:
        yield from _resolve_group(group, merge_op, drop_tombstones)


def _resolve_group(
    group: List[Entry],
    merge_op: Optional[MergeOperator],
    drop_tombstones: bool,
) -> List[Entry]:
    """group: all entries for one key, newest (highest seq) first. The
    fold semantics live in storage/merge.resolve_entry_group — the single
    source of truth the array resolves are cross-checked against."""
    return resolve_entry_group(group, merge_op, drop_tombstones)
