"""Seeded data and the plain reference for a counter segment under the
counter service's OWN key shape: counter names ``counter-<n>``.

The configuration's copy of ``workload.py`` (whose dict model, with its
two control arithmetics, and whose row counts are imported as they
stand; nothing here knows the engine). Everything is a pure function of
``(seed, slot)``. What differs is the keys. Upstream's stress test
(``examples/counter_service/stress_test.py:37``, the port of
``stress_test.cpp``) names its counters
``f"counter-{rng.randrange(args.counters)}"``: at ``BASELINE.json``'s 1M
counters that is ``counter-0`` … ``counter-999999``, 9 to 14 bytes, and
counter ``n`` lives in shard ``n mod 64`` (``SHARDS``; upstream routes by
a hash of the name: listed under ``assumed``). So slot ``s`` holds

- the bulk-loaded counters ``counter-<s + 64 i>``, ``i`` < rows: at
  15,625 rows six key lengths in every slot (of all 1M names: 10 of 9 B,
  90 of 10, 900 of 11, 9,000 of 12, 90,000 of 13, 900,000 of 14);
- *live-only* counters, which the batch job does not know yet:
  ``counter-<1000000 + s + 64 i>``, 15 bytes, a seventh length;
- keys no operation ever touches: ``counter-<2000000 + s + 64 i>``.

The bulk file is in BYTEWISE key order (``counter-1`` < ``counter-10`` <
``counter-2``), as a batch job's SST writer requires. A unit is
``workload.py``'s: ``preload_ops`` through the write path (MERGE on 20 %
of the bulk keys, a quarter of them twice; three MERGEs on each
live-only counter and a base PUT on every second one), then
``bulk_rows`` above them. Counts are exact functions of ``rows``
(``workload.unit_row_counts``), and so are the key bytes a unit's
compaction moves (``unit_key_bytes``), but for which bulk keys the
seeded MERGEs fall on: those count at the slot's mean key length.
"""

from __future__ import annotations

from typing import List, Tuple

from chipbench.workload import (MERGE, PUT, Op, SlotModel, _rng, _uint64s,  # noqa: F401
                                encode_value, live_counters, unit_row_counts)

SHARDS = 64          # shard = n mod 64, whatever part of them a run holds
LIVE_BASE = 1_000_000    # the first counter the batch job does not know
ABSENT_BASE = 2_000_000  # names no operation ever touches


def name(n: int) -> bytes:
    return b"counter-%d" % n


def bulk_key(slot: int, i: int) -> bytes:
    return name(slot + SHARDS * i)


def live_key(slot: int, i: int) -> bytes:
    """A counter created by live traffic only — never bulk-loaded."""
    return name(LIVE_BASE + slot + SHARDS * i)


def absent_key(slot: int, i: int) -> bytes:
    """A key no operation ever touches (reads must answer None)."""
    return name(ABSENT_BASE + slot + SHARDS * i)


def bulk_rows(seed: int, slot: int, rows: int) -> List[Tuple[bytes, int]]:
    """The bulk-loaded file: ``rows`` PUTs in BYTEWISE key order, values
    < 2^40 (counter ``i`` of the slot has ``workload.bulk_rows``' value)."""
    vals = _rng(seed, slot, 0).integers(0, 1 << 40, rows)
    return sorted((bulk_key(slot, i), int(v)) for i, v in enumerate(vals))


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
    never written — among them names one digit from a written one
    (``counter-1`` beside ``counter-10``: a prefix must read as absent
    where only the longer name was written, and the other way round)."""
    rng = _rng(seed, slot, 2)
    picks = rng.choice(rows, size=min(rows, n), replace=False)
    out = [bulk_key(slot, int(i)) for i in picks]
    out += [bulk_key(slot, i) for i in range(min(8, rows))]
    if live:
        out += [live_key(slot, i) for i in range(live_counters(rows))]
    out += [absent_key(slot, int(i)) for i in picks[:8]]
    out.append(live_key(slot, rows))  # past the live-only range
    # a written name cut by its last digit, or with one more behind it:
    # mostly another shard's counter or nobody's, so absent here (the
    # model says which)
    for i in picks[:8]:
        key = bulk_key(slot, int(i))
        out += [key[:-1], key + b"0"]
    return list(dict.fromkeys(out))  # each key once


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


def name_bytes(lo: int, hi: int) -> int:
    """Bytes of the names ``counter-<n>``, ``lo`` <= n < ``hi``."""
    total, digits, first = 0, 1, 0  # names of ``digits`` digits start at
    while first < hi:               # ``first`` and end before ``last``
        last = 10 ** digits
        n = min(hi, last) - max(lo, first)
        if n > 0:
            total += n * (len(b"counter-") + digits)
        first, digits = last, digits + 1
    return total


def key_bytes_max(rows: int, live: bool) -> int:
    """The longest key a slot of ``rows`` bulk rows holds."""
    longest = SHARDS * rows - 1
    if live:
        longest = LIVE_BASE + SHARDS * live_counters(rows) - 1
    return len(name(longest))


def unit_key_bytes(rows: int, live: bool = True) -> Tuple[float, float]:
    """(key bytes into, key bytes out of) the compaction of one unit,
    from ``rows`` alone (never the seed), for a slot of the segment's
    ``SHARDS``: a slot's bulk keys are a 64th of the names below
    ``64 * rows`` (the slots differ by a few bytes: their mean); a
    live-only counter's key is counted at its own length; the MERGEs on
    bulk keys fall on a seeded sample of them and count at the slot's
    mean key length (90 % of the names have 14 bytes: a seed moves the
    sum by less than a thousandth). Rows as ``workload.unit_row_counts``
    counts them: in = the bulk rows and every pre-load operation, out =
    one row a key."""
    bulk = name_bytes(0, SHARDS * rows) / SHARDS
    if not live:
        return bulk, bulk
    hit, n_live = rows // 5, live_counters(rows)
    live_keys = name_bytes(LIVE_BASE, LIVE_BASE + SHARDS * n_live) / SHARDS
    live_ops = 3 + ((n_live + 1) // 2) / n_live  # operations a counter
    return (bulk + (hit + hit // 4) * bulk / rows + live_ops * live_keys,
            bulk + live_keys)
