"""The counter deployment's seeded workload and its plain reference model.

Source of the deployment: BASELINE.json configs 1-2 ("1M int64 counters",
"counter_service 64 shards, local-FS load_sst ingest + L0→L1
compaction"): 16-byte keys, 8-byte little-endian int64 values, the
uint64-add merge operator (examples/counter_service/options.py).

Everything here is a pure function of ``(seed, shard)`` and knows
nothing of the engine: ``chip_smoke.py`` (and its CPU test) send these
operations to the served system and to ``CounterModel`` — a dict — and
require the same answers. Data takes the place of weights.

A shard's life, in sequence order:

1. ``preload_ops`` — MERGE increments through the write path on ~20 % of
   the keys the bulk load will bring (a quarter of them twice), plus a
   few *live-only* counters the bulk load does not contain;
2. ``bulk_rows`` — the bulk-loaded SST: one PUT per key, landing ABOVE
   the pre-load (ingest assigns a newer global seqno), so it shadows the
   increments under it while the live-only counters fold to their sums;
3. ``burst_ops`` — rounds of MERGE increments on a fixed key set AFTER
   the load, each round sized to fill one memtable, so flushes stack L0
   files whose operands the background L0→L1 compaction folds.
"""

from __future__ import annotations

import struct
from typing import Dict, List, Optional, Tuple

import numpy as np

_MASK = (1 << 64) - 1
_pack = struct.Struct("<Q").pack

Op = Tuple[bytes, int]  # (key, uint64 delta or value)


def bulk_key(shard: int, i: int) -> bytes:
    return b"s%03d-key%08d" % (shard, i)


def live_key(shard: int, i: int) -> bytes:
    """A counter created by live traffic only — never bulk-loaded."""
    return b"s%03d-liv%08d" % (shard, i)


def absent_key(shard: int, i: int) -> bytes:
    """A key no operation ever touches (reads must answer None)."""
    return b"s%03d-nil%08d" % (shard, i)


def encode_value(v: int) -> bytes:
    return _pack(v & _MASK)


def _rng(seed: int, shard: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, shard, stream])


def bulk_rows(seed: int, shard: int, keys: int) -> List[Op]:
    """The bulk-loaded file: ``keys`` PUTs in key order."""
    vals = _rng(seed, shard, 0).integers(0, 1 << 40, keys)
    return [(bulk_key(shard, i), int(v)) for i, v in enumerate(vals)]


def preload_ops(seed: int, shard: int, keys: int) -> List[Op]:
    """MERGE increments before the load. Counts are exact functions of
    ``keys`` (never of the seed), so every shard of a deployment
    compacts to the same entry and key counts — one compiled program."""
    rng = _rng(seed, shard, 1)
    hit = rng.choice(keys, size=keys // 5, replace=False)
    twice = hit[: len(hit) // 4]
    targets = [bulk_key(shard, int(i)) for i in hit]
    targets += [bulk_key(shard, int(i)) for i in twice]
    n_live = max(1, keys // 80)
    for i in range(n_live):  # live-only counters: three increments each
        targets += [live_key(shard, i)] * 3
    order = rng.permutation(len(targets))
    deltas = rng.integers(1, 1000, len(targets))
    return [(targets[j], int(deltas[j])) for j in order]


def burst_ops(seed: int, shard: int, round_no: int,
              burst_keys: int) -> List[Op]:
    """One post-load round: one increment on each of the shard's FIRST
    ``burst_keys`` bulk keys (the caller keeps that within the shard's
    key count) plus its first live-only counter."""
    deltas = _rng(seed, shard, 100 + round_no).integers(
        1, 1000, burst_keys + 1)
    ops = [(bulk_key(shard, i), int(deltas[i])) for i in range(burst_keys)]
    ops.append((live_key(shard, 0), int(deltas[-1])))
    return ops


def probe_keys(seed: int, shard: int, keys: int, n: int) -> List[bytes]:
    """Point-read sample: bulk keys (burst and non-burst), every kind of
    live-only counter, and keys that were never written."""
    rng = _rng(seed, shard, 2)
    picks = rng.choice(keys, size=min(keys, n), replace=False)
    out = [bulk_key(shard, int(i)) for i in picks]
    out += [bulk_key(shard, i) for i in range(min(8, keys))]
    out += [live_key(shard, i) for i in range(max(1, keys // 80))][:16]
    out += [absent_key(shard, int(i)) for i in picks[:8]]
    out.append(live_key(shard, keys))  # past the live-only range
    return list(dict.fromkeys(out))  # each key once


class CounterModel:
    """One shard as a dict: the plain reference for uint64-add counters
    (PUT sets, MERGE adds with 64-bit wraparound, absent reads None)."""

    def __init__(self) -> None:
        self._m: Dict[bytes, int] = {}

    def put(self, key: bytes, value: int) -> None:
        self._m[key] = value & _MASK

    def merge(self, key: bytes, delta: int) -> None:
        self._m[key] = (self._m.get(key, 0) + delta) & _MASK

    def get(self, key: bytes) -> Optional[bytes]:
        v = self._m.get(key)
        return None if v is None else _pack(v)

    def scan(self) -> List[Tuple[bytes, bytes]]:
        return [(k, _pack(v)) for k, v in sorted(self._m.items())]

    def __len__(self) -> int:
        return len(self._m)
