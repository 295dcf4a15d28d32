"""Seeded data and the plain reference for db_bench's MixGraph records
(Cao et al., "Characterizing, Modeling, and Benchmarking RocksDB Key-Value
Workloads at Facebook", FAST '20; the RocksDB wiki's example command:
``-key_size=48 -value_k=0.2615 -value_sigma=25.45 -keyrange_num=30
-keyrange_dist_a=14.18 -keyrange_dist_b=-2.917 -keyrange_dist_c=0.0164
-keyrange_dist_d=-0.08082 -num=50000000``): one slot of a segment as a
dict of tags, every value rebuilt from its tag and compared in every byte
and in its length.

Nothing here knows the engine; ``workload_rec``'s value bytes and its
unit (the counter unit's counts, ``workload.unit_row_counts``) are
imported as they stand. Everything is a pure function of ``(seed,
slot)``; a value's length and bytes are pure functions of ``(seed, slot,
key, write ordinal)``.

- **Keys** are db_bench's: the key number as 8 bytes big-endian, then
  ASCII ``'0'`` up to 48 bytes (bytewise order is number order). The
  50 M keys are cut into ``SHARDS`` shards by ``n mod 2,500``; slot ``s``
  holds the bulk keys ``n = s + 2,500 i``, ``i`` < rows (n < 50 M), and
  *live-only* keys the batch job does not know yet, ``n = 50 M + s +
  2,500 i``.
- **Value lengths** are MixGraph's: a generalized Pareto draw (theta 0,
  k 0.2615, sigma 25.45; ``ceil`` of the inverse CDF at a uniform u),
  set to 10 where it is below 10 and taken modulo 1,024 where it is over
  1,024 (as remembered of ``db_bench_tool.cc``; listed under ``assumed``).
  About 31 % of the values are 10 bytes, the mean is ~37, and a shard of
  ~26,000 values holds a few near 1 KB.
- **Value bytes** are ``workload_rec``'s printable ASCII, cut to the
  value's length.
- **Reads** pick a key range by MixGraph's two-term exponential over 30
  ranges (``a e^(b x) + c e^(d x)`` at x = 1 … 30, the weights shuffled
  over the ranges from the seed, as db_bench shuffles them), then a held
  key uniformly inside it (``read_weights``).

A unit (one slot of one version), in sequence order: ``preload_ops``
(live PUTs through the write path, ``workload_rec``'s counts: ~20 % of
the bulk keys overwritten, a quarter twice; each live-only key three or
four times), then ``bulk_rows`` above them. Write ordinals: a bulk row
is 0; the j-th live PUT in arrival order is j + 1.
"""

from __future__ import annotations

import functools
import struct
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench import work_model
from chipbench.workload import _rng, live_counters, unit_row_counts  # noqa: F401
from chipbench.workload_rec import _M64, _GOLDEN, _mix, values as _text

SHARDS = 2500             # shard = key number mod 2,500
KEY_SPACE = 50_000_000    # db_bench -num
KEY_BYTES = 48            # db_bench -key_size
LIVE_BASE = KEY_SPACE     # the first key number the batch job does not know
ABSENT_BASE = 2 * KEY_SPACE  # key numbers no operation ever touches
VALUE_MIN, VALUE_MAX = 10, 1024  # the clamp, and mix_max_value_size
VALUE_THETA, VALUE_K, VALUE_SIGMA = 0.0, 0.2615, 25.45
KEYRANGE_NUM = 30
KEYRANGE_ABCD = (14.18, -2.917, 0.0164, -0.08082)
BULK_ORDINAL = 0
LIVE_INDEX = 1 << 24      # a live-only key's stream index starts here
STREAM_RANGES = 6         # the rng stream of the ranges' shuffle

CONTROLS = ("bits32", "fold32")  # run.py's two names; see MixModel
Op = Tuple[bytes, int]  # (key, write ordinal): always a PUT

_PAD = b"0" * (KEY_BYTES - 8)
_BE = struct.Struct(">Q")


def key(n: int) -> bytes:
    return _BE.pack(n) + _PAD


def key_number(k: bytes) -> int:
    return _BE.unpack_from(k)[0]


def bulk_key(slot: int, i: int) -> bytes:
    return key(slot + SHARDS * i)


def live_key(slot: int, i: int) -> bytes:
    """A key written by live traffic only — never bulk-loaded."""
    return key(LIVE_BASE + slot + SHARDS * i)


def absent_key(slot: int, i: int) -> bytes:
    """A key no operation ever touches (reads must answer None)."""
    return key(ABSENT_BASE + slot + SHARDS * i)


def _slot_index(k: bytes) -> int:
    """A key's number inside its slot's stream of values: bulk keys
    0 … rows - 1, live-only keys from ``LIVE_INDEX``."""
    n = key_number(k)
    if n >= LIVE_BASE:
        return LIVE_INDEX + (n - LIVE_BASE) // SHARDS
    return n // SHARDS


def size_of(u: np.ndarray) -> np.ndarray:
    """MixGraph's value size at uniform draws ``u`` in (0, 1): the
    generalized Pareto inverse CDF, ceiled; below 10 -> 10, over 1,024 ->
    modulo 1,024."""
    size = np.ceil(VALUE_THETA + VALUE_SIGMA
                   * (np.power(u, -VALUE_K) - 1.0) / VALUE_K)
    size = np.where(size < VALUE_MIN, VALUE_MIN,
                    np.where(size > VALUE_MAX, np.mod(size, VALUE_MAX), size))
    return size.astype(np.int64)


def value_lengths(seed: int, slot: int, index, ordinal) -> np.ndarray:
    """The lengths of the values the ``ordinal[j]``-th write of the key
    numbered ``index[j]`` (``_slot_index``) carries: a splitmix64 draw per
    (seed, slot, key, ordinal), a stream of its own beside the bytes'."""
    head = (seed * 0x9FB21C651E98DF25 + slot * 0xC2B2AE3D27D4EB4F
            + 0x165667B19E3779F9) & _M64
    h = _mix(np.uint64(head)
             + np.asarray(index, dtype=np.uint64) * np.uint64(_GOLDEN)
             + (np.asarray(ordinal, dtype=np.uint64) << np.uint64(40)))
    u = ((h >> np.uint64(11)).astype(np.float64) + 0.5) / float(1 << 53)
    return size_of(u)


def values(seed: int, slot: int, index, ordinal) -> List[bytes]:
    """The values the ``ordinal[j]``-th write of the key numbered
    ``index[j]`` carries, each at its own length."""
    lens = value_lengths(seed, slot, index, ordinal)
    if not len(lens):
        return []
    text = _text(seed, slot, index, ordinal, int(lens.max()))
    return [text[j, :n].tobytes() for j, n in enumerate(lens.tolist())]


def value(seed: int, slot: int, k: bytes, ordinal: int) -> bytes:
    """The bytes the ``ordinal``-th write of ``k`` carries."""
    return values(seed, slot, [_slot_index(k)], [ordinal])[0]


def bulk_rows(seed: int, slot: int, rows: int) -> List[Tuple[bytes, bytes]]:
    """The bulk-loaded file: ``rows`` PUTs in key order."""
    vals = values(seed, slot, np.arange(rows), np.zeros(rows, np.uint64))
    return [(bulk_key(slot, i), vals[i]) for i in range(rows)]


def preload_ops(seed: int, slot: int, rows: int) -> List[Op]:
    """What the write path takes before the load, in arrival order:
    ``workload_rec.preload_ops``' targets over this configuration's keys,
    every one a PUT."""
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


def probe_keys(seed: int, slot: int, rows: int, n: int,
               live: bool) -> List[bytes]:
    """The read-back's sample of a slot: ``n`` bulk keys and the first 8,
    EVERY live-only key (where the configuration has them), keys never
    written — among them keys that share a written key's first 24 bytes,
    or all its bytes but the last, and its first 47 bytes (all absent)."""
    rng = _rng(seed, slot, 2)
    picks = rng.choice(rows, size=min(rows, n), replace=False)
    out = [bulk_key(slot, int(i)) for i in picks]
    out += [bulk_key(slot, i) for i in range(min(8, rows))]
    if live:
        out += [live_key(slot, i) for i in range(live_counters(rows))]
    out += [absent_key(slot, int(i)) for i in picks[:8]]
    out.append(live_key(slot, rows))  # past the live-only range
    for i in picks[:8]:
        k = bulk_key(slot, int(i))
        out += [k[:24] + b"1" * 24, k[:-1] + b"1", k[:-1]]
    return list(dict.fromkeys(out))  # each key once


def bucket_bytes(length: int) -> int:
    """The bytes of a value's row in a launch of the smallest power-of-two
    word width that holds it."""
    words = max(1, -(-length // 4))
    return 4 * (1 << (words - 1).bit_length())


class MixModel:
    """One slot as a dict ``key -> write ordinal``: a PUT sets, absent
    reads None; ``get`` rebuilds the bytes, at their length, from the tag.

    ``fault`` ``"exact"`` is the reference. The two controls are faults
    this configuration could have, and must NOT pass for correct (they
    answer under the names ``run.py``'s ``--control`` knows):

    - ``"bits32"``: the value padded with zeros to its bucket's width (a
      power of two words) — a sink that ignores the value-length plane;
    - ``"fold32"``: the OLDEST write of a key wins where the newest
      should — the overwritten value surviving the compaction.
    """

    FAULTS = ("exact",) + CONTROLS

    def __init__(self, seed: int, slot: int, fault: str = "exact") -> None:
        if fault not in self.FAULTS:
            raise ValueError(f"fault {fault!r}")
        self._seed, self._slot, self._fault = seed, slot, fault
        self._m: Dict[bytes, int] = {}

    def put(self, k: bytes, ordinal: int) -> None:
        if self._fault == "fold32" and k in self._m:
            return
        self._m[k] = ordinal

    def get(self, k: bytes) -> Optional[bytes]:
        ordinal = self._m.get(k)
        if ordinal is None:
            return None
        v = value(self._seed, self._slot, k, ordinal)
        if self._fault == "bits32":
            v += bytes(bucket_bytes(len(v)) - len(v))
        return v

    def __len__(self) -> int:
        return len(self._m)


def slot_model(seed: int, slot: int, rows: int, live: bool,
               fault: str = "exact") -> MixModel:
    """The slot after one unit: the live PUTs, then the bulk PUTs."""
    m = MixModel(seed, slot, fault)
    if live:
        for k, ordinal in preload_ops(seed, slot, rows):
            m.put(k, ordinal)
    for i in range(rows):
        # the bulk load lands above every live PUT: under "fold32" the
        # older live PUT of an overwritten key survives it
        m.put(bulk_key(slot, i), BULK_ORDINAL)
    return m


def range_weights(seed: int) -> np.ndarray:
    """The 30 key ranges' shares of the reads: the two-term exponential
    at x = 1 … 30, shuffled over the ranges from the seed, normalised."""
    a, b, c, d = KEYRANGE_ABCD
    x = np.arange(1, KEYRANGE_NUM + 1, dtype=np.float64)
    w = a * np.exp(b * x) + c * np.exp(d * x)
    w = w[np.random.default_rng([seed, 0, STREAM_RANGES]).permutation(
        KEYRANGE_NUM)]
    return w / w.sum()


def read_weights(seed: int, slots: int, rows: int, live: bool) -> np.ndarray:
    """Each held record's share of the reads, in the client's record
    order (slot by slot: the bulk keys, then the live-only ones): its key
    range's share (``range_weights``) spread evenly over the held records
    whose key numbers fall in it. The ranges cut the span of key numbers
    the deployment holds, 50 M and the live-only keys behind them, into
    30 equal parts."""
    n_live = live_counters(rows) if live else 0
    i = np.arange(rows + n_live, dtype=np.int64)
    base = np.where(i < rows, SHARDS * i, LIVE_BASE + SHARDS * (i - rows))
    numbers = (np.arange(slots, dtype=np.int64)[:, None] + base).ravel()
    span = LIVE_BASE + SHARDS * n_live
    r = numbers * KEYRANGE_NUM // span
    held = np.bincount(r, minlength=KEYRANGE_NUM)
    w = range_weights(seed)
    per = np.divide(w, held, out=np.zeros(KEYRANGE_NUM), where=held > 0)
    out = per[r]
    return out / out.sum()


@functools.lru_cache(maxsize=None)
def mean_value_bytes() -> float:
    """The expected value length under the size rule (``size_of``): the
    Pareto draw's probability of each ceiled size, clamped and wrapped,
    from the rule alone (no seed)."""
    m = np.arange(1, 1 << 22, dtype=np.float64)

    def cdf(x):
        return 1.0 - np.power(1.0 + VALUE_K * x / VALUE_SIGMA, -1.0 / VALUE_K)

    p = cdf(m) - cdf(m - 1.0)
    size = np.where(m < VALUE_MIN, VALUE_MIN,
                    np.where(m > VALUE_MAX, np.mod(m, VALUE_MAX), m))
    return float((p * size).sum() / p.sum())


def unit_bytes(config: dict) -> float:
    """Bytes the compaction of one unit of ``config`` has to move, from
    the row counts and the size rule alone (never the seed, never the
    padded launch): every row in and out with its 48-byte key, its
    sequence number and type (``work_model``'s widths) and its value at
    the rule's mean length, and the output's bloom filter once."""
    rows, live = int(config["rows_per_slot"]), bool(config["live_counters"])
    rows_in, rows_out = unit_row_counts(rows, live)
    row = work_model.row_bytes(KEY_BYTES, 0) + mean_value_bytes()
    return ((rows_in + rows_out) * row + work_model.bloom_bytes(
        rows_out, int(config["options"]["bits_per_key"])))
