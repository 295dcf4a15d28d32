"""A bounded, seeking scan over a point-in-time view of one DB.

``DB._scan_view`` takes the view under the DB lock and nothing else
there: the sequence number, every memtable with its sorted keys
(``MemTable.sorted_keys``), and the SST readers the scan may need,
PINNED (``SSTReader.pin``), so that a flush's or a compaction's file GC
meanwhile closes none of them. All the rest runs without the DB lock:

- each source is sought to ``start``: a bisect of a memtable's sorted
  keys, of a file's block index (``SSTReader.iterate``); a level below
  L0 is one source, its files read in key order from the first whose
  last key is at or after ``start``, and a file wholly below ``start``
  or at or above ``end`` is never opened;
- the sources are merged lazily in (key asc, seq desc) order, in the
  order the materializing iterator gave them (memtables, L0, the
  levels), and resolved as it resolved them (``resolve_stream``, then
  ``live_pairs``): MERGE stacks folded by the operator, DELETEs hidden;
- the walk stops at ``limit`` live keys. A scan decodes the blocks its
  answer lies in, and the next one only where the answer ends on a
  block's last row (the resolve looks one entry past a key's stack).
"""

from __future__ import annotations

import heapq
import itertools
from typing import Iterator, List, Optional, Tuple

from .compaction import resolve_stream
from .merge import MergeOperator
from .records import OpType


def _order(entry):
    return entry[0], -entry[1]


class ScanCounts:
    """What one scan read: blocks decoded, files sought (a file whose
    first block was read), entries taken from memtables."""

    __slots__ = ("blocks", "files", "mem_rows")

    def __init__(self) -> None:
        self.blocks = self.files = self.mem_rows = 0


def live_pairs(resolved, merge_op: Optional[MergeOperator]
               ) -> Iterator[Tuple[bytes, bytes]]:
    """(key, value) of every live key of a resolved stream
    (``resolve_stream(..., drop_tombstones=False)``): one entry a key,
    except an unresolved MERGE chain (no partial-merge operator), which
    is folded here as a group, newest first in the stream."""
    for key, group in itertools.groupby(resolved, key=lambda e: e[0]):
        entries = list(group)
        vtype = entries[0][2]
        if vtype == OpType.DELETE:
            continue
        if vtype == OpType.MERGE:
            operands = [e[3] for e in reversed(entries)]  # oldest first
            value = (merge_op.merge(key, None, operands) if merge_op
                     else entries[0][3])
        else:
            value = entries[0][3]
        yield key, value


class ScanView:
    """One DB's state at a sequence number: ``mems`` as ``(memtable,
    its sorted keys)``, ``l0`` the L0 readers in the level's order,
    ``levels`` per level below L0 its readers in key order, each as
    ``(reader, its min_key)``. Every reader is pinned until
    ``release``."""

    def __init__(self, seq: int, mems: list, l0: list, levels: list,
                 merge_op: Optional[MergeOperator]):
        self.seq = seq
        self.mems = mems
        self.l0 = l0
        self.levels = levels
        self.merge_op = merge_op
        self.pinned = [r for r, _lo in l0] + [
            r for files in levels for r, _lo in files]
        for reader in self.pinned:
            reader.pin()

    def release(self) -> None:
        pinned, self.pinned = self.pinned, []
        for reader in pinned:
            reader.unpin()

    def _mem_run(self, mem, keys, start, end, counts: ScanCounts):
        for entry in mem.seek(keys, start, end, self.seq):
            counts.mem_rows += 1
            yield entry

    @staticmethod
    def _file_run(files, start, end, fill_cache: bool, counts: ScanCounts):
        for reader, lo in files:
            if end is not None and lo is not None and lo >= end:
                return
            counts.files += 1
            yield from reader.iterate(start, end, fill_cache, counts)

    def take(self, start: Optional[bytes], end: Optional[bytes],
             limit: Optional[int], fill_cache: bool,
             counts: ScanCounts) -> List[Tuple[bytes, bytes]]:
        """The first ``limit`` (every, where None) live pairs in [start,
        end), in key order."""
        if limit is not None and limit <= 0:
            return []
        runs = [self._mem_run(m, keys, start, end, counts)
                for m, keys in self.mems if keys]
        runs += [self._file_run([f], start, end, fill_cache, counts)
                 for f in self.l0]
        runs += [self._file_run(files, start, end, fill_cache, counts)
                 for files in self.levels if files]
        if not runs:
            return []
        merged = runs[0] if len(runs) == 1 else heapq.merge(*runs,
                                                            key=_order)
        out: List[Tuple[bytes, bytes]] = []
        for pair in live_pairs(resolve_stream(merged, self.merge_op, False),
                               self.merge_op):
            out.append(pair)
            if limit is not None and len(out) >= limit:
                break
        return out
