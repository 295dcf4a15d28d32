"""Seeded data and the plain reference: one slot of a segment as a dict.

Copied from ``rocksplicator_tpu/testing/counter_workload.py`` so that the
yardstick is a file no later PR may change. Everything here is a pure
function of ``(seed, slot)`` and knows nothing of the engine: the driver
sends these operations to the served system and to ``SlotModel`` and
requires the same answers. Data takes the place of weights.

A unit (one slot of one version), in sequence order:

1. ``preload_ops`` — through the write path, before the load, in one
   seeded arrival order:
   - MERGE increments on ~20 % of the keys the bulk load will bring (a
     quarter of them twice);
   - *live-only* counters the bulk load does not contain: three MERGE
     increments each, and on every second one a base PUT somewhere among
     them (so increments fold onto a PUT below them, and a PUT shadows
     the increments below it).
   Values and increments span all 64 bits, so folded sums carry out of
   the low 32 bits and wrap modulo 2^64;
   only where the configuration has live counters;
2. ``bulk_rows`` — the bulk-loaded SST: one PUT per key, landing ABOVE
   the pre-load (ingest assigns a newer global seqno), so it shadows the
   increments under it while the live-only counters fold to their sums.

Counts are exact functions of ``rows`` (never of the seed): every slot of
a deployment compacts to the same row counts, hence one compiled program,
and every seed does the same amount of work.
"""

from __future__ import annotations

import struct
from typing import Dict, List, Optional, Tuple

import numpy as np

MASK64 = (1 << 64) - 1
MASK32 = (1 << 32) - 1
_pack = struct.Struct("<Q").pack

PUT, MERGE = "put", "merge"
Op = Tuple[str, bytes, int]  # (PUT or MERGE, key, uint64 value or delta)


def bulk_key(slot: int, i: int) -> bytes:
    return b"s%03d-key%08d" % (slot, i)


def live_key(slot: int, i: int) -> bytes:
    """A counter created by live traffic only — never bulk-loaded."""
    return b"s%03d-liv%08d" % (slot, i)


def absent_key(slot: int, i: int) -> bytes:
    """A key no operation ever touches (reads must answer None)."""
    return b"s%03d-nil%08d" % (slot, i)


def encode_value(v: int) -> bytes:
    return _pack(v & MASK64)


def _rng(seed: int, slot: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, slot, stream])


def _uint64s(rng: np.random.Generator, n: int) -> List[int]:
    """``n`` integers over all 64 bits, never 0."""
    return [int(v) | 1 for v in rng.integers(0, 1 << 64, n, dtype=np.uint64)]


def bulk_rows(seed: int, slot: int, rows: int) -> List[Tuple[bytes, int]]:
    """The bulk-loaded file: ``rows`` PUTs in key order, values < 2^40."""
    vals = _rng(seed, slot, 0).integers(0, 1 << 40, rows)
    return [(bulk_key(slot, i), int(v)) for i, v in enumerate(vals)]


def live_counters(rows: int) -> int:
    return max(1, rows // 80)


def unit_row_counts(rows: int, live: bool) -> Tuple[int, int]:
    """(rows into, rows out of) the compaction of one unit: in = the bulk
    rows plus every pre-load operation; out = one row per key."""
    if not live:
        return rows, rows
    hit, n_live = rows // 5, live_counters(rows)
    return (rows + hit + hit // 4 + 3 * n_live + (n_live + 1) // 2,
            rows + n_live)


def preload_ops(seed: int, slot: int, rows: int) -> List[Op]:
    """What the write path takes before the load, in arrival order."""
    rng = _rng(seed, slot, 1)
    hit = rng.choice(rows, size=rows // 5, replace=False)
    twice = hit[: len(hit) // 4]
    targets = [(MERGE, bulk_key(slot, int(i))) for i in hit]
    targets += [(MERGE, bulk_key(slot, int(i))) for i in twice]
    for i in range(live_counters(rows)):
        targets += [(MERGE, live_key(slot, i))] * 3
        if i % 2 == 0:
            targets.append((PUT, live_key(slot, i)))
    order = rng.permutation(len(targets))
    values = _uint64s(rng, len(targets))
    return [(targets[j][0], targets[j][1], values[j]) for j in order]


def probe_keys(seed: int, slot: int, rows: int, n: int,
               live: bool) -> List[bytes]:
    """The read-back's sample of a slot: ``n`` bulk keys and the first 8,
    EVERY live-only counter (where the configuration has them), keys
    never written."""
    rng = _rng(seed, slot, 2)
    picks = rng.choice(rows, size=min(rows, n), replace=False)
    out = [bulk_key(slot, int(i)) for i in picks]
    out += [bulk_key(slot, i) for i in range(min(8, rows))]
    if live:
        out += [live_key(slot, i) for i in range(live_counters(rows))]
    out += [absent_key(slot, int(i)) for i in picks[:8]]
    out.append(live_key(slot, rows))  # past the live-only range
    return list(dict.fromkeys(out))  # each key once


class SlotModel:
    """One slot as a dict: PUT sets, MERGE adds, absent reads None.

    ``arithmetic`` ``"exact"`` is the reference: uint64 counters, MERGE
    adds modulo 2^64. The controls are the same semantics in the nearest
    narrower arithmetic, and must NOT pass for correct:

    - ``"bits32"``: values and sums kept to 32 bits;
    - ``"fold32"``: PUTs exact, but a MERGE adds the two 32-bit halves
      apart and loses the carry between them (a 64-bit add done in 32-bit
      lanes, the carry forgotten).
    """

    ARITHMETICS = ("exact", "bits32", "fold32")

    def __init__(self, arithmetic: str = "exact") -> None:
        if arithmetic not in self.ARITHMETICS:
            raise ValueError(f"arithmetic {arithmetic!r}")
        self._m: Dict[bytes, int] = {}
        self._arithmetic = arithmetic
        self._mask = MASK32 if arithmetic == "bits32" else MASK64

    def put(self, key: bytes, value: int) -> None:
        self._m[key] = value & self._mask

    def merge(self, key: bytes, delta: int) -> None:
        old = self._m.get(key, 0)
        if self._arithmetic == "fold32":
            low = ((old & MASK32) + (delta & MASK32)) & MASK32
            high = ((old >> 32) + (delta >> 32)) & MASK32
            self._m[key] = (high << 32) | low
        else:
            self._m[key] = (old + delta) & self._mask

    def apply(self, op: Op) -> None:
        kind, key, value = op
        (self.put if kind == PUT else self.merge)(key, value)

    def get(self, key: bytes) -> Optional[bytes]:
        v = self._m.get(key)
        return None if v is None else _pack(v)

    def __len__(self) -> int:
        return len(self._m)


def slot_model(seed: int, slot: int, rows: int, live: bool,
               arithmetic: str = "exact") -> SlotModel:
    """The slot after one unit: the pre-load, then the bulk PUTs."""
    m = SlotModel(arithmetic)
    if live:
        for op in preload_ops(seed, slot, rows):
            m.apply(op)
    for key, value in bulk_rows(seed, slot, rows):
        m.put(key, value)
    return m
