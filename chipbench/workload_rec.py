"""Seeded data and the plain reference for segments of wide records: one
slot as a dict of tags, every value rebuilt from its tag to be compared
byte for byte.

The record sibling of ``workload.py`` (whose keys and seeded streams it
re-uses by import; its counts and probe sample hold as they are; nothing
here knows the engine). Everything
is a pure function of ``(seed, slot)``. A value is a pure function of
``(seed, slot, key, write ordinal)``: ``value_bytes`` printable ASCII
bytes, as YCSB's ``RandomByteIterator`` fills its fields, so a block
compressor finds what it would find in a deployment. The model holds one
small tag per key, ``(ordinal)``, not the kilobyte.

A unit (one slot of one version), in sequence order:

1. ``preload_ops`` — live PUTs through the write path, before the load,
   in one seeded arrival order: ~20 % of the keys the bulk load will
   bring are overwritten (a quarter of them twice); *live-only* keys the
   bulk load does not contain are written three times each, every second
   one a fourth time. The counter unit's counts (``workload.preload_ops``)
   with PUT in MERGE's place, so ``workload.unit_row_counts`` holds;
2. ``bulk_rows`` — the bulk-loaded SST: one PUT per key, landing ABOVE
   the live PUTs (ingest assigns a newer global seqno), so it shadows
   them, while a live-only key keeps its NEWEST PUT.

Write ordinals: the bulk row of a key is ordinal 0; the j-th live PUT in
arrival order is ordinal j + 1.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench.workload import (absent_key, bulk_key, live_counters,  # noqa: F401
                                live_key, _rng)

# the printable ASCII range of YCSB's RandomByteIterator: ' ' .. '~'
_ASCII_LO, _ASCII_SPAN = 32, 95
# a random byte's printable character: 256 -> 95, as evenly as it goes
_PRINTABLE = ((np.arange(256) * _ASCII_SPAN) >> 8).astype(np.uint8) + _ASCII_LO
BULK_ORDINAL = 0
Op = Tuple[bytes, int]  # (key, write ordinal): always a PUT

CONTROLS = ("bits32", "fold32")  # run.py's two names; see RecModel


def _slot_index(key: bytes) -> int:
    """A key's number inside its slot's stream of values: bulk keys
    0..rows-1, live-only keys from 2^24 (no slot has that many rows)."""
    i = int(key[-8:])
    return i if key[5:8] == b"key" else (1 << 24) + i


_M64 = (1 << 64) - 1
_GOLDEN = 0x9E3779B97F4A7C15


def _mix(z: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer on a uint64 array, in place (arithmetic
    modulo 2^64; one scratch array, so a file's 21 MB stay in cache)."""
    t = z >> np.uint64(30)
    for shift, mult in ((27, 0xBF58476D1CE4E5B9), (31, 0x94D049BB133111EB)):
        z ^= t
        z *= np.uint64(mult)
        np.right_shift(z, np.uint64(shift), out=t)
    z ^= t
    return z


def values(seed: int, slot: int, index: np.ndarray, ordinal: np.ndarray,
           value_bytes: int) -> np.ndarray:
    """``(n, value_bytes)`` u8: row j is the value the ``ordinal[j]``-th
    write of the key numbered ``index[j]`` (``_slot_index``) carries. A
    counter-mode splitmix64 stream per (seed, slot, key, ordinal), each
    byte folded into the printable range: rows are independent, and a
    whole file's rows are made in a few numpy passes."""
    words = -(-value_bytes // 8)
    head = (seed * 0xD1342543DE82EF95 + slot * 0xA24BAED4963EE407
            + 0x632BE59BD9B4E019) & _M64
    base = _mix(np.uint64(head)
                + np.asarray(index, dtype=np.uint64) * np.uint64(_GOLDEN)
                + (np.asarray(ordinal, dtype=np.uint64) << np.uint64(40)))
    stream = _mix(np.add.outer(base, np.arange(1, words + 1, dtype=np.uint64)
                               * np.uint64(_GOLDEN)))
    out = stream.view(np.uint8).reshape(len(base), words * 8)[:, :value_bytes]
    return _PRINTABLE[out]


def value(seed: int, slot: int, key: bytes, ordinal: int,
          value_bytes: int) -> bytes:
    """The bytes the ``ordinal``-th write of ``key`` carries."""
    return values(seed, slot, [_slot_index(key)], [ordinal],
                  value_bytes)[0].tobytes()


def bulk_values(seed: int, slot: int, rows: int,
                value_bytes: int) -> np.ndarray:
    """``(rows, value_bytes)`` u8: the bulk file's values, as one matrix
    (row i is ``value(seed, slot, bulk_key(slot, i), BULK_ORDINAL)``)."""
    return values(seed, slot, np.arange(rows), np.zeros(rows, np.uint64),
                  value_bytes)


def bulk_rows(seed: int, slot: int, rows: int,
              value_bytes: int) -> List[Tuple[bytes, bytes]]:
    """The bulk-loaded file: ``rows`` PUTs in key order."""
    vals = bulk_values(seed, slot, rows, value_bytes)
    return [(bulk_key(slot, i), vals[i].tobytes()) for i in range(rows)]


def preload_ops(seed: int, slot: int, rows: int) -> List[Op]:
    """What the write path takes before the load, in arrival order: the
    counter unit's targets, every one a PUT."""
    rng = _rng(seed, slot, 1)
    hit = rng.choice(rows, size=rows // 5, replace=False)
    twice = hit[: len(hit) // 4]
    targets = [bulk_key(slot, int(i)) for i in hit]
    targets += [bulk_key(slot, int(i)) for i in twice]
    for i in range(live_counters(rows)):
        targets += [live_key(slot, i)] * 3
        if i % 2 == 0:
            targets.append(live_key(slot, i))
    order = rng.permutation(len(targets))
    return [(targets[j], n + 1) for n, j in enumerate(order)]


class RecModel:
    """One slot as a dict ``key -> write ordinal``: a PUT sets, absent
    reads None; ``get`` rebuilds the bytes from the tag.

    ``fault`` ``"exact"`` is the reference. The two controls are faults
    this configuration could have, and must NOT pass for correct (they
    answer under the names ``run.py``'s ``--control`` knows):

    - ``"bits32"``: a value cut to its first 8 bytes, the rest zero — a
      device path that still moved 8-byte values;
    - ``"fold32"``: the OLDEST write of a key wins where the newest
      should — the overwritten value surviving the compaction.
    """

    FAULTS = ("exact",) + CONTROLS

    def __init__(self, seed: int, slot: int, value_bytes: int,
                 fault: str = "exact") -> None:
        if fault not in self.FAULTS:
            raise ValueError(f"fault {fault!r}")
        self._seed, self._slot, self._bytes = seed, slot, value_bytes
        self._fault = fault
        self._m: Dict[bytes, int] = {}

    def put(self, key: bytes, ordinal: int) -> None:
        if self._fault == "fold32" and key in self._m:
            return
        self._m[key] = ordinal

    def get(self, key: bytes) -> Optional[bytes]:
        ordinal = self._m.get(key)
        if ordinal is None:
            return None
        v = value(self._seed, self._slot, key, ordinal, self._bytes)
        if self._fault == "bits32":
            v = v[:8] + bytes(len(v) - 8)
        return v

    def __len__(self) -> int:
        return len(self._m)


def slot_model(seed: int, slot: int, rows: int, value_bytes: int,
               live: bool, fault: str = "exact") -> RecModel:
    """The slot after one unit: the live PUTs, then the bulk PUTs."""
    m = RecModel(seed, slot, value_bytes, fault)
    if live:
        for key, ordinal in preload_ops(seed, slot, rows):
            m.put(key, ordinal)
    for i in range(rows):
        # the bulk load lands above every live PUT: under "fold32" the
        # older live PUT of an overwritten key survives it
        m.put(bulk_key(slot, i), BULK_ORDINAL)
    return m
