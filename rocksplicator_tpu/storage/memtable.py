"""In-memory write buffer (memtable).

Reference: RocksDB memtable. Stores per-key op stacks (newest first) so
MERGE operands accumulate correctly before a flush; iteration yields
entries in (key asc, seq desc) order — the SST writer's required order.
"""

from __future__ import annotations

import bisect
from array import array
from itertools import islice, repeat
from typing import Dict, Iterator, List, Optional, Tuple

from .merge import MergeOperator
from .records import BatchColumns, OpType

# entry: (seq, vtype, value), newest first
_Entry = Tuple[int, int, bytes]


class MemTable:
    """Columns, and no container a key or an entry for the collector
    to walk. Every full collection stops the world and visits every
    object that a container on the heap holds: as lists and tuples (a
    list a key, a tuple an entry, four mirror lists) eight full
    memtables are ~900k such visits, +90 ms a collection, and the
    longest of a refresh cycle's collections falls into the bulk load
    that follows the writes (PERF.md section 6, PR 33). So an entry is
    a ROW of byte and machine-integer columns in arrival order, a key's
    stack a chain of rows (``_prev``), and the one dict holds bytes and
    ints alone, which the collector never tracks. The values stay a list of the
    writers' own bytes objects (one visit each): copying 1 KB values
    into a growing buffer cost the record cell's writes more than the
    visits cost anyone."""

    def __init__(self) -> None:
        self._newest: Dict[bytes, int] = {}  # key -> row of its newest entry
        self._prev = array("q")   # row -> the key's next older row, or -1
        self._key_buf = bytearray()
        self._klens = array("I")
        self._vals: List[bytes] = []
        self._vlens = array("I")
        self._seqs = array("Q")
        self._vtypes = bytearray()
        self._bytes = 0
        self._sorted: List[bytes] = []  # sorted_keys(), as last asked
        self.min_seq: Optional[int] = None
        self.max_seq = 0

    def apply(self, key: bytes, seq: int, vtype: int, value: bytes) -> None:
        row = len(self._seqs)
        self._prev.append(self._newest.get(key, -1))
        self._key_buf += key
        self._klens.append(len(key))
        self._vals.append(value)
        self._vlens.append(len(value))
        self._seqs.append(seq)
        self._vtypes.append(vtype)
        self._newest[key] = row  # last: a reader finds whole rows only
        self._bytes += len(key) + len(value) + 16
        if self.min_seq is None:
            self.min_seq = seq
        self.max_seq = max(self.max_seq, seq)

    def apply_batch(self, cols: BatchColumns, start_seq: int) -> None:
        """A whole batch, rows ``start_seq`` onward in the batch's order:
        what ``apply`` op by op leaves, from one extend a column. The
        values are the batch's own objects; ``_newest`` goes LAST, so a
        reader finds whole rows only."""
        n = cols.count
        if not n:
            return
        base = len(self._seqs)
        newest = self._newest
        prev = array("q", map(newest.get, cols.keys, repeat(-1)))
        mine = dict(zip(cols.keys, range(base, base + n)))
        if len(mine) != n:  # a key twice in the batch: its own older row
            seen: Dict[bytes, int] = {}
            for i, key in enumerate(cols.keys):
                older = seen.get(key)
                if older is not None:
                    prev[i] = older
                seen[key] = base + i
        self._prev.extend(prev)
        self._key_buf += cols.key_bytes
        self._klens.extend(cols.klens)
        self._vals.extend(cols.vals)
        self._vlens.extend(cols.vlens)
        self._seqs.extend(range(start_seq, start_seq + n))
        self._vtypes += cols.vtypes
        newest.update(mine)
        self._bytes += len(cols.key_bytes) + sum(cols.vlens) + 16 * n
        if self.min_seq is None:
            self.min_seq = start_seq
        self.max_seq = max(self.max_seq, start_seq + n - 1)

    def _stack(self, key: bytes) -> Iterator[_Entry]:
        """The key's entries, newest first."""
        row = self._newest.get(key, -1)
        while row >= 0:
            yield self._seqs[row], self._vtypes[row], self._vals[row]
            row = self._prev[row]

    def get(
        self, key: bytes, merge_op: Optional[MergeOperator]
    ) -> Tuple[bool, Optional[bytes], List[bytes]]:
        """Returns (resolved, value_or_None, pending_operands).

        resolved=True: value_or_None is the final answer (None = deleted).
        resolved=False: pending_operands are MERGE operands (newest last)
        still awaiting a base value from older levels.
        """
        if key not in self._newest:  # the common miss: no generator
            return False, None, []
        operands: List[bytes] = []
        for _seq, vtype, value in self._stack(key):  # newest -> oldest
            if vtype == OpType.PUT:
                if operands and merge_op:
                    return True, merge_op.merge(key, value, list(reversed(operands))), []
                return True, value, []
            if vtype == OpType.DELETE:
                if operands and merge_op:
                    return True, merge_op.merge(key, None, list(reversed(operands))), []
                return True, None, []
            if vtype == OpType.MERGE:
                operands.append(value)
        return False, None, list(reversed(operands))

    def absorb_older(self, older: "MemTable") -> None:
        """Fold an OLDER memtable's entries beneath this one's (flush-failure
        recovery path): older entries append after newer ones per key."""
        rows = len(self._seqs)
        self._prev.extend(p + rows if p >= 0 else -1 for p in older._prev)
        self._key_buf += older._key_buf
        self._klens.extend(older._klens)
        self._vals.extend(older._vals)
        self._vlens.extend(older._vlens)
        self._seqs.extend(older._seqs)
        self._vtypes += older._vtypes
        for key, row in older._newest.items():
            mine = self._newest.get(key, -1)
            if mine < 0:
                self._newest[key] = row + rows
                continue
            while self._prev[mine] >= 0:  # to my oldest entry of the key
                mine = self._prev[mine]
            self._prev[mine] = row + rows
        self._bytes += older._bytes
        if older.min_seq is not None:
            self.min_seq = (
                older.min_seq if self.min_seq is None
                else min(self.min_seq, older.min_seq)
            )
        self.max_seq = max(self.max_seq, older.max_seq)

    def approximate_bytes(self) -> int:
        return self._bytes

    def __len__(self) -> int:
        return len(self._newest)

    def entries(self) -> Iterator[Tuple[bytes, int, int, bytes]]:
        """(key, seq, vtype, value) in (key asc, seq desc) order."""
        for key in sorted(self._newest):
            for seq, vtype, value in self._stack(key):
                yield key, seq, vtype, value

    def sorted_keys(self) -> List[bytes]:
        """Every key in order: the index a scan seeks in. Kept between
        calls, and only the keys that came since are sorted in: a key is
        never removed and the dict keeps insertion order, so they are its
        newest, and one merge of two sorted runs joins them to the kept
        list (a new list: a scan may hold the old one). The caller holds
        the DB lock: a writer adding a key would break the walk."""
        keys = self._sorted
        if len(keys) != len(self._newest):
            fresh = sorted(islice(self._newest, len(keys), None))
            keys = self._sorted = sorted(keys + fresh) if keys else fresh
        return keys

    def seek(self, keys: List[bytes], start: Optional[bytes],
             end: Optional[bytes], max_seq: int
             ) -> Iterator[Tuple[bytes, int, int, bytes]]:
        """(key, seq, vtype, value) in (key asc, seq desc) order for the
        keys of ``keys`` (``sorted_keys()`` as a scan's view took it) in
        [start, end), leaving out every entry above ``max_seq``. Lazy,
        and safe without the DB lock: what a writer adds meanwhile is
        newer than ``max_seq``, a row is whole before ``_newest`` names
        it, and rows are only ever appended."""
        i = 0 if start is None else bisect.bisect_left(keys, start)
        for j in range(i, len(keys)):
            key = keys[j]
            if end is not None and key >= end:
                return
            row = self._newest[key]
            while row >= 0:
                seq = self._seqs[row]
                if seq <= max_seq:
                    yield key, seq, self._vtypes[row], self._vals[row]
                row = self._prev[row]

    def drain_lanes(self):
        """All entries as UNSORTED fixed-width lane arrays — the
        vectorized flush path (the caller lexsorts once over the key
        words, replacing the pure-Python ``sorted(self._data)`` +
        per-entry repack). Returns ``(lanes, key_bytes_matrix)`` or
        None when the planar lane representation can't express this
        memtable: an empty or over-wide key (keys of DIFFERING length,
        1 to 48 bytes, are lanes like any other: six key words a row up
        to 24 bytes, twelve above), a value wider than the planar u16
        vlen field, or a DELETE carrying a value. Live values may differ
        in width (each row's ``val_len``; the value lanes are as wide as
        the widest, a shorter value zero-tailed). Width checks run
        inline during collection so a disqualifying entry bails before
        any large buffer is built.

        ``lanes`` is the kernel lane dict (key_words_be, key_len,
        seq_hi/lo, vtype, val_words, val_len); the (n, widest key) u8
        key matrix (a shorter key zero-padded; ``lanes["key_len"]`` has
        each row's length) rides along for bulk bloom construction. The
        columns are
        read as arrays where they lie (the key bytes copied once: a
        view that outlives this call would pin the bytearray against
        the next apply), the values joined."""
        import numpy as np

        from .planar import PLANAR_MAX_KLEN, PLANAR_MAX_VLEN, key_word_cols

        n = len(self._seqs)
        if n == 0:
            return None
        # Width checks run over the (cheap, 4n-byte) length lanes before
        # any value-byte buffer is built — one oversized value among a
        # million small ones bails here, not after a giant transient
        # allocation.
        klens = np.frombuffer(self._klens, dtype=np.uint32)
        kmin, klen = int(klens.min()), int(klens.max())  # klen: the widest
        if not 0 < kmin <= klen <= PLANAR_MAX_KLEN:
            return None
        mixed = kmin != klen
        vtype_arr = np.frombuffer(self._vtypes, dtype=np.uint8).astype(
            np.uint32)
        vlens = np.frombuffer(self._vlens, dtype=np.uint32)
        is_del = vtype_arr == 2  # DELETE: no value in the planar layout
        if bool(vlens[is_del].any()):
            return None
        live_vlens = vlens[~is_del]
        vlen = int(live_vlens.max()) if len(live_vlens) else 0  # the widest
        if vlen > PLANAR_MAX_VLEN:
            return None
        vmixed = bool(len(live_vlens)) and int(live_vlens.min()) != vlen
        seq = np.frombuffer(self._seqs, dtype=np.uint64)
        kc = key_word_cols(klen)
        key_buf = np.zeros((n, 4 * kc), dtype=np.uint8)
        flat = np.frombuffer(bytes(self._key_buf), dtype=np.uint8)
        if mixed:
            key_buf[_ragged_rows(klens, len(flat))] = flat
            key_mat = key_buf[:, :klen]
        else:
            key_mat = flat.reshape(n, klen)
            key_buf[:, :klen] = key_mat
        vw = max(2, (vlen + 3) // 4)
        val_buf = np.zeros((n, vw * 4), dtype=np.uint8)
        if vlen:
            # a DELETE brought no bytes: the join is the live rows'
            # values back to back, in arrival order
            joined = np.frombuffer(b"".join(self._vals), dtype=np.uint8)
            if vmixed:
                val_buf[_ragged_rows(vlens, len(joined))] = joined
            else:
                val_buf[~is_del, :vlen] = joined.reshape(-1, vlen)
        lanes = {
            "key_words_be": key_buf.view(">u4").astype(
                np.uint32).reshape(n, kc),
            "key_len": klens.copy(),
            "seq_hi": (seq >> np.uint64(32)).astype(np.uint32),
            "seq_lo": (seq & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            "vtype": vtype_arr,
            "val_words": val_buf.view("<u4").reshape(n, vw),
            "val_len": (vlens.copy() if vmixed
                        else np.where(is_del, 0, vlen).astype(np.uint32)),
        }
        return lanes, key_mat


def _ragged_rows(lens, total: int):
    """Index arrays (rows, columns) that put byte j of a buffer of
    ``total`` bytes, rows of ``lens`` bytes back to back, at the head of
    its own row: byte j lies in row r at column j - (where r's bytes
    start)."""
    import numpy as np

    starts = np.cumsum(lens, dtype=np.int64) - lens
    row = np.repeat(np.arange(len(lens)), lens)
    return row, np.arange(total) - starts[row]
