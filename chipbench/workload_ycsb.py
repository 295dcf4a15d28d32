"""Seeded data and the plain reference for YCSB core workload E on the
record deployment (brianfrankcooper/YCSB ``workloads/workloade``: short
ranges, ``scanproportion=0.95 insertproportion=0.05
requestdistribution=zipfian maxscanlength=100
scanlengthdistribution=uniform``): one slot of a version as a sorted key
list and a rule for its values, against which every scan reply is
compared in its keys, their order, every byte and length of each value,
and its row count.

Nothing here knows the engine; ``workload_rec``'s printable bytes are
imported as they stand. Keys are a pure function of ``(slots, rows,
slot)``, values of ``(seed, slot, key number)``.

- **Keys** are YCSB's (``CoreWorkload.buildKeyName`` with the default
  ``insertorder=hashed`` and ``zeropadding=1``): ``"user"`` and the
  decimal of ``Utils.fnvhash64(keynum)`` (FNV-1a over the number's eight
  low bytes, in Java's signed 64-bit arithmetic, then ``Math.abs``):
  5 to 24 bytes, nearly all 22 or 23, keys of differing length in every
  shard. Slot ``s`` holds the records ``keynum = s + slots * i``, ``i``
  < rows (YCSB's ``recordcount`` is ``slots * rows``), and its unit's
  live inserts, new keys past them: ``keynum = slots * rows + s +
  slots * j``, ``j`` < ``inserts(rows)``.
- **Values** are the record YCSB writes, ``fieldcount=10`` fields of
  ``fieldlength=100`` bytes, as its RocksDB binding stores it
  (``RocksDBClient.serializeValues``): per field a 4-byte big-endian
  name length, the name (``field0`` ... ``field9``), a 4-byte big-endian
  value length, the value. 1,140 bytes a record; the field bytes are
  ``workload_rec``'s printable ASCII of the record's key number.

A unit (one slot of one version): ``inserts(rows)`` live PUTs of new keys
through the write path, then the bulk file of ``rows`` records above
them; no key is written twice, so a version is every one of them.
"""

from __future__ import annotations

import bisect
import struct
from typing import List, Optional, Tuple

import numpy as np

from chipbench import work_model
from chipbench.workload_rec import values as _text

FIELDS, FIELD_BYTES = 10, 100      # YCSB fieldcount, fieldlength
_NAME = [b"field%d" % f for f in range(FIELDS)]
_HEAD = [struct.pack(">i", len(n)) + n + struct.pack(">i", FIELD_BYTES)
         for n in _NAME]
RECORD_BYTES = sum(len(h) + FIELD_BYTES for h in _HEAD)  # 1,140
SCAN_LENGTH_MAX = 100              # YCSB maxscanlength
INSERT_SHARE = 20                  # 1 in 20: YCSB E's insertproportion 0.05
FNV_OFFSET, FNV_PRIME = 0xCBF29CE484222325, 1099511628211
CONTROLS = ("bits32", "fold32")    # run.py's two names; see ScanModel


def inserts(rows: int) -> int:
    """Live inserts a unit brings: 5 % of a version's bulk records."""
    return rows // INSERT_SHARE


def fnvhash64(keynum) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` of each number (below 2^56), as the
    signed 64-bit integers Java's ``Math.abs`` leaves (``Long.MIN_VALUE``
    stays negative)."""
    val = np.asarray(keynum, dtype=np.uint64)
    h = np.full(val.shape, FNV_OFFSET, dtype=np.uint64)
    for _ in range(8):
        h ^= val & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME)
        val = val >> np.uint64(8)
    return np.abs(h.view(np.int64))


def keys_of(keynum) -> List[bytes]:
    return [b"user%d" % h for h in fnvhash64(keynum).tolist()]


def key_lengths(keynum) -> np.ndarray:
    """The length of each number's key, without building it: "user"
    and the decimal digits (and a sign, for ``Long.MIN_VALUE``)."""
    h = fnvhash64(keynum)
    mag = np.where(h < 0, 0, h)
    digits = np.ones(h.shape, dtype=np.int64)
    for k in range(1, 19):
        digits += mag >= 10 ** k
    return 4 + np.where(h < 0, 20, digits)


def slot_keynums(slots: int, rows: int, slot: int) -> np.ndarray:
    """The slot's key numbers in record order: its ``rows`` bulk
    records, then its unit's ``inserts(rows)`` live inserts."""
    bulk = slot + slots * np.arange(rows, dtype=np.int64)
    live = slots * rows + slot + slots * np.arange(inserts(rows),
                                                   dtype=np.int64)
    return np.concatenate([bulk, live])


def records(seed: int, slot: int, keynum) -> np.ndarray:
    """``(n, RECORD_BYTES)`` u8: the serialized record of each key
    number."""
    keynum = np.asarray(keynum, dtype=np.uint64)
    n = len(keynum)
    text = _text(seed, slot, keynum, np.zeros(n, np.uint64),
                 FIELDS * FIELD_BYTES).reshape(n, FIELDS, FIELD_BYTES)
    head = len(_HEAD[0])
    out = np.empty((n, FIELDS, head + FIELD_BYTES), dtype=np.uint8)
    out[:, :, :head] = np.frombuffer(b"".join(_HEAD), np.uint8).reshape(
        FIELDS, head)
    out[:, :, head:] = text
    return out.reshape(n, RECORD_BYTES)


def bulk_rows(seed: int, slots: int, rows: int, slot: int
              ) -> List[Tuple[bytes, bytes]]:
    """The bulk-loaded file: the slot's ``rows`` records in key order."""
    nums = slot_keynums(slots, rows, slot)[:rows]
    keys = keys_of(nums)
    order = sorted(range(rows), key=keys.__getitem__)
    vals = records(seed, slot, nums[order])
    return [(keys[i], vals[j].tobytes()) for j, i in enumerate(order)]


def insert_rows(seed: int, slots: int, rows: int, slot: int
                ) -> List[Tuple[bytes, bytes]]:
    """The unit's live inserts, in key-number order (YCSB's insert
    sequence)."""
    nums = slot_keynums(slots, rows, slot)[rows:]
    vals = records(seed, slot, nums)
    return [(k, vals[j].tobytes()) for j, k in enumerate(keys_of(nums))]


class ScanModel:
    """One slot of a version: its keys sorted, each with its key number;
    ``scan(start, n)`` is a bisect and a slice, ``get`` a bisect.

    ``fault`` ``"exact"`` is the reference. The two controls are faults
    this configuration could have, and must NOT pass for correct (they
    answer under the names ``run.py``'s ``--control`` knows):

    - ``"bits32"``: each value cut to its first 8 bytes, the rest zero —
      a device path that still moved 8-byte values;
    - ``"fold32"``: the unit's live inserts missing — the memtable's rows
      lost under the load that landed above them.
    """

    FAULTS = ("exact",) + CONTROLS

    def __init__(self, seed: int, slots: int, rows: int, slot: int,
                 fault: str = "exact") -> None:
        if fault not in self.FAULTS:
            raise ValueError(f"fault {fault!r}")
        nums = slot_keynums(slots, rows, slot)
        if fault == "fold32":
            nums = nums[:rows]
        keys = keys_of(nums)
        order = sorted(range(len(keys)), key=keys.__getitem__)
        self.keys = [keys[i] for i in order]
        self.nums = nums[order]
        self._seed, self._slot, self._fault = seed, slot, fault

    def _values(self, lo: int, hi: int) -> List[bytes]:
        vals = records(self._seed, self._slot, self.nums[lo:hi])
        if self._fault == "bits32":
            vals[:, 8:] = 0
        return [v.tobytes() for v in vals]

    def scan(self, start: bytes, n: int) -> List[Tuple[bytes, bytes]]:
        """The first ``n`` records at or after ``start``, in key order."""
        lo = bisect.bisect_left(self.keys, start)
        hi = min(lo + max(0, n), len(self.keys))
        return list(zip(self.keys[lo:hi], self._values(lo, hi)))

    def get(self, key: bytes) -> Optional[bytes]:
        i = bisect.bisect_left(self.keys, key)
        if i == len(self.keys) or self.keys[i] != key:
            return None
        return self._values(i, i + 1)[0]

    def __len__(self) -> int:
        return len(self.keys)


def probe_keys(slots: int, rows: int, slot: int, n: int,
               seed: int) -> List[bytes]:
    """The read-back's sample of a slot: ``n`` bulk records, the first
    and last 8 in key order, 64 of the live inserts, and keys never
    written: numbers no unit writes, and keys one byte longer or
    shorter than a written one."""
    rng = np.random.default_rng([seed, slot, 2])
    nums = slot_keynums(slots, rows, slot)
    keys = keys_of(nums)
    ranked = sorted(keys)
    picks = rng.choice(rows, size=min(rows, n), replace=False)
    out = [keys[int(i)] for i in picks] + ranked[:8] + ranked[-8:]
    live = keys[rows:]
    out += [live[int(j)] for j in rng.choice(
        len(live), size=min(64, len(live)), replace=False)]
    out += keys_of(2 * slots * rows + slot + slots * np.arange(8))
    for i in picks[:8]:
        k = keys[int(i)]
        out += [k + b"0", k[:-1], b"user"]
    return list(dict.fromkeys(out))  # each key once


def unit_bytes(config: dict) -> float:
    """Bytes the compaction of one unit of ``config`` has to move, at
    the least: every row in and out with its key at its own length, its
    sequence number and type (``work_model``'s widths) and its 1,140-byte
    record, and the output's bloom filter once; the mean over the
    configuration's slots. Never the launch's padded rows."""
    slots, rows = int(config["slots"]), int(config["rows_per_slot"])
    keys = sum(int(key_lengths(slot_keynums(slots, rows, s)).sum())
               for s in range(slots)) / slots
    per_unit = rows + inserts(rows)  # no key twice: as many out as in
    rest = work_model.row_bytes(0, RECORD_BYTES)
    return (2 * (keys + per_unit * rest) + work_model.bloom_bytes(
        per_unit, int(config["options"]["bits_per_key"])))
