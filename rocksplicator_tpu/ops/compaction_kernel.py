"""The TPU merge-resolve kernel: k-way merge + LSM resolution as one sort.

Replaces the reference's CPU heap-merge compaction loop (the HOT LOOP of
SURVEY §3.3) with a fixed-shape array program:

1. one multi-key ``lax.sort`` orders every entry by (validity, key lex asc,
   seq desc) — the k-way merge collapses into a sort because the runs are
   concatenated into one batch. What the sort carries beside its keys
   depends on the value width it is traced with (``value_path``):
   - **riding** (the uint64-add fold, and values up to
     ``RIDE_MAX_VAL_WORDS`` words): every value word is a non-key operand
     of this sort and of the compaction sort below. An operand costs
     nothing to run (a launch of 8 x 8,192 rows takes 2.7-3.5 ms with 2 to
     32 value words riding) and 10-20 s to compile, twice over: 65 s at 2
     words, 380 s at 32 (one v5e, PR 29, tools/value_path_bench.py);
   - **index** (wider values, no merge operator): ONE row-index lane
     rides both sorts and the values are moved once at the end, output
     row i taking the whole input row ``val_row[i]``
     (``gather_value_rows``). With no operator the resolve never rewrites
     a value (newest PUT/DELETE wins), so the move is all values need;
2. key-boundary detection with adjacent-lane compares, then per-segment
   aggregates via cumulative sums + two flagged segmented fills
   (``lax.associative_scan``) — one forward fill of segment-start values,
   one backward fill of segment-end prefix sums. No index gathers;
3. vectorized LSM resolution per key: newest PUT/DELETE wins, MERGE
   operands above the base fold via the uint64-add operator as 16-bit-limb
   prefix-sum differences (carry-safe for < 2^16 operands per key);
4. stream compaction via a second stable sort, again carrying every output
   lane (or the row index) as payload.

**TPU design note:** phases 1-4 are sorts, cumulative/associative scans
and elementwise ops: no per-lane gather, no scatter, no
``jax.ops.segment_*``; static shapes throughout (capacity N in → capacity
N out + count), so the pipeline jits once and vmaps over shards. The one
gather is the index path's move of whole value rows, which XLA keeps a
row gather (W contiguous words a row, ``slice_sizes={1, W}``). Measured
on one v5e (PR 29, tools/value_path_bench.py): 32,768 rows of 1 KB
(33.5 MB read, 33.5 MB written) in 1.04 ms as a program of its own; the
whole pipeline of 8 shards x 32,768 rows of 1 KB (sorts, resolve, bloom,
eight such moves) in 7.99 ms a launch, less than the 9.1 ms of the
counters' riding program at the same capacity. An earlier docstring here
argued against all gathers from a per-LANE reading of rounds 1-2
(16 ms a lane at 131 k rows) that was later withdrawn; a row gather is
another access pattern, and this is its first measurement.

``key_words_le`` is never carried: a little-endian key word is the
byteswap of the big-endian word over the same bytes, so it is recomputed
from the sorted BE lanes with 4 shift/mask ops per word.

Reference semantics being reproduced: compaction.py's resolve_stream
(heap-merge + _resolve_group), pinned by test_tpu_ops parity tests.
"""

from __future__ import annotations

import enum
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .kv_format import KEY_WORDS

# OpType values (storage/records.py) as device constants
_PUT = 1
_DELETE = 2
_MERGE = 3


class MergeKind(enum.Enum):
    # PUT/DELETE only. Batches containing MERGE records without an operator
    # must NOT use this kernel (the backend routes them to the CPU path,
    # which preserves unresolved operand chains like the reference).
    NONE = "none"
    UINT64_ADD = "uint64add"  # the counter operator (merge_operator.h:20-40)


# Widest value, in u32 words, that still rides the two sorts as operands.
# Wider ones (MergeKind.NONE only) take the index path: one row-index
# lane rides, the values are moved once by the resolved order. Measured
# on one v5e with tools/value_path_bench.py (PR 29; a group of 8 shards of
# 8,192 rows, no operator; cold compile s / device ms a launch):
#   words   2: ride  65 / 3.11   index 56 / 3.24
#   words   4: ride  74 / 2.82   index 54 / 2.99
#   words   8: ride 116 / 2.75   index 54 / 3.16
#   words  16: ride 225 / 2.90   index 54 / 3.17
#   words  32: ride 379 / 3.47   index 46 / 3.17
#   words 256: index 51 / 3.35;  words 1024: index 46 / 4.14
# A riding word costs nothing to run and 10-20 s more to COMPILE, twice
# over; the index path costs 0.1-0.4 ms a launch up to 16 words, wins from
# 32, and compiles in the same time whatever the width. 8-byte values keep
# the programs they have; everything wider takes the index path.
RIDE_MAX_VAL_WORDS = 2


def value_path(merge_kind: MergeKind, n_val_words: int) -> str:
    """``"ride"`` or ``"index"``: how a batch's values get from input to
    output order, from what the program is traced with and nothing else.
    The uint64-add fold rewrites value words, so it always rides (its
    values are 8 bytes)."""
    if merge_kind is MergeKind.NONE and n_val_words > RIDE_MAX_VAL_WORDS:
        return "index"
    return "ride"


def bswap32(w: jnp.ndarray) -> jnp.ndarray:
    """Byteswap u32 lanes: the LE word over the same 4 bytes as a BE word."""
    return ((w >> 24) | ((w >> 8) & jnp.uint32(0xFF00))
            | ((w << 8) & jnp.uint32(0xFF0000)) | (w << 24))


def composite_key_lanes(invalid, key_word_lanes, key_len, seq_hi, seq_lo,
                        *, uniform_klen: bool, seq32: bool):
    """THE canonical comparator lane order — (invalid-last, key words BE
    asc, [key_len], [~seq_hi], ~seq_lo) — as a lane list. Every consumer
    of the composite order builds it here so they cannot desync: the
    kernel's sort (_sort_merge_order) and the host paths' sorted-run
    check (storage/native_compaction.py — numpy arrays work too: only
    list-building and ``~`` are used)."""
    keys = [invalid, *key_word_lanes]
    if not uniform_klen:
        keys.append(key_len)
    if not seq32:
        keys.append(~seq_hi)
    keys.append(~seq_lo)
    return keys


def split_composite_lanes(lanes, key_words: int, *, uniform_klen: bool,
                          seq32: bool):
    """Inverse of composite_key_lanes over an ordered lane sequence (the
    comparator lanes, already reordered by a sort/merge). Returns
    (key_word_lanes, key_len_or_None, seq_hi_or_None, seq_lo, valid,
    next_pos) — seq lanes are un-complemented."""
    pos = 1
    key_lanes = list(lanes[pos:pos + key_words])
    pos += key_words
    klen = None
    if not uniform_klen:
        klen = lanes[pos]
        pos += 1
    shi = None
    if not seq32:
        shi = ~lanes[pos]
        pos += 1
    slo = ~lanes[pos]
    pos += 1
    valid = lanes[0] == 0
    return key_lanes, klen, shi, slo, valid, pos


def _sort_merge_order(
    key_words_be: jnp.ndarray,  # (N, 6) u32
    key_len: jnp.ndarray,       # (N,) u32
    seq_hi: jnp.ndarray,
    seq_lo: jnp.ndarray,
    valid: jnp.ndarray,         # (N,) bool
    payload: Tuple[jnp.ndarray, ...],
    uniform_klen: bool = False,
    seq32: bool = False,
    key_words: int = KEY_WORDS,
):
    """One variadic sort into (invalid-last, key asc, seq desc) order,
    carrying ``payload`` lanes through the sort network. Returns
    (key_lanes_sorted, klen_sorted_or_None, seq_hi_sorted_or_None,
    seq_lo_sorted, valid_sorted, payload_sorted).

    The static fast-path flags drop sort operands the batch provably
    doesn't need (callers verify on host): ``uniform_klen`` — all valid
    keys share one length; ``seq32`` — every seq fits 32 bits; and
    ``key_words`` — lanes beyond it are zero for valid rows. Operand
    count barely affects TPU sort cost (measured), but fewer key operands
    still shorten the comparator."""
    invalid_key = jnp.where(valid, jnp.uint32(0), jnp.uint32(1))
    operands = composite_key_lanes(
        invalid_key, (key_words_be[:, w] for w in range(key_words)),
        key_len, seq_hi, seq_lo, uniform_klen=uniform_klen, seq32=seq32)
    num_keys = len(operands)
    operands.extend(payload)
    sorted_ops = lax.sort(tuple(operands), num_keys=num_keys,
                          is_stable=False)
    key_lanes, klen_s, shi_s, slo_s, valid_s, pos = split_composite_lanes(
        sorted_ops, key_words, uniform_klen=uniform_klen, seq32=seq32)
    return key_lanes, klen_s, shi_s, slo_s, valid_s, sorted_ops[pos:]


def _seg_fill_forward(flag: jnp.ndarray, values):
    """Segmented forward fill: every row receives each value as it was at
    its segment's FIRST row. ``flag`` marks segment starts (row 0 must be
    flagged). One flagged associative scan — no index gathers."""
    def comb(a, b):
        af, bf = a[0], b[0]
        return (af | bf,) + tuple(
            jnp.where(bf, bv, av) for av, bv in zip(a[1:], b[1:])
        )

    out = lax.associative_scan(comb, (flag,) + tuple(values))
    return out[1:]


def _seg_fill_backward(flag_last: jnp.ndarray, values):
    """Segmented backward fill: every row receives each value as it is at
    its segment's LAST row (``flag_last`` marks segment ends; the final
    row must be flagged). Same flagged combine as the forward fill, run
    as a reverse scan (reverse=True ≡ flip∘scan∘flip, without the
    materialized flips)."""
    def comb(a, b):
        af, bf = a[0], b[0]
        return (af | bf,) + tuple(
            jnp.where(bf, bv, av) for av, bv in zip(a[1:], b[1:])
        )

    out = lax.associative_scan(comb, (flag_last,) + tuple(values),
                               reverse=True)
    return out[1:]


def _limb_combine(lo16_0, lo16_1, hi16_0, hi16_1):
    """Four u32 limb sums → (lo, hi) u32 64-bit value with carries."""
    l0 = lo16_0 & 0xFFFF
    c0 = lo16_0 >> 16
    s1 = lo16_1 + c0
    l1 = s1 & 0xFFFF
    c1 = s1 >> 16
    s2 = hi16_0 + c1
    l2 = s2 & 0xFFFF
    c2 = s2 >> 16
    s3 = hi16_1 + c2
    l3 = s3 & 0xFFFF  # overflow beyond 64 bits wraps (two's complement)
    return l0 | (l1 << 16), l2 | (l3 << 16)


def _shift_prev(x):
    """y[i] = x[i-1]; y[0] is zero (callers force row 0 themselves)."""
    return jnp.concatenate([jnp.zeros((1,), x.dtype), x[:-1]])


def _shift_next(x):
    """y[i] = x[i+1]; y[n-1] is zero (callers force the last row)."""
    return jnp.concatenate([x[1:], jnp.zeros((1,), x.dtype)])


def resolve_decisions(
    key_lanes, key_len, valid, vtype, val_len, vw_lanes, *,
    merge_kind: MergeKind, drop_tombstones: bool, uniform_klen: bool,
    key_words: int,
):
    """Phases 2-3 on merge-ordered ``(N,)`` lanes: key-boundary
    detection + segmented LSM resolution (cumulative sums and the two
    flagged segmented fills; no index gathers). Returns
    ``(vtype, val_len, vw_lanes, keep, overflow_mask_or_None)``
    — ``keep`` marks each key's representative row for the compaction
    phase; ``overflow_mask`` (UINT64_ADD only) marks rows whose segment
    exceeds the 2^16-operand limb-sum bound."""
    n = valid.shape[0]
    iota = lax.iota(jnp.int32, n)
    n_val_words = len(vw_lanes)
    vw_lanes = list(vw_lanes)

    # --- key boundaries: adjacent compare via a 1-shift; row 0 and
    # invalid rows are forced segment starts --------------------------
    prev_equal = None
    for w in range(key_words):
        eq = key_lanes[w] == _shift_prev(key_lanes[w])
        prev_equal = eq if prev_equal is None else prev_equal & eq
    if not uniform_klen:
        # with uniform lengths, equal words imply equal keys among valid
        # rows (invalid rows get their own segments below regardless)
        prev_equal = prev_equal & (key_len == _shift_prev(key_len))
    new_key = ~prev_equal | (iota == 0) | ~valid
    last_key = _shift_next(new_key) | (iota == n - 1)

    is_put = (vtype == _PUT) & valid
    is_del = (vtype == _DELETE) & valid
    is_merge = (vtype == _MERGE) & valid
    is_base = is_put | is_del

    overflow_mask = None
    if merge_kind is MergeKind.UINT64_ADD:
        # prefix counts of base entries: how many bases strictly before
        # row i within its segment. Segment-start values arrive via ONE
        # forward flagged fill — no index gathers.
        base_incl = jnp.cumsum(is_base.astype(jnp.int32))
        base_excl = base_incl - is_base.astype(jnp.int32)
        base_excl_start, iota_start = _seg_fill_forward(
            new_key, (base_excl, iota))
        base_before = base_excl - base_excl_start
        operand_mask = is_merge & (base_before == 0)
        first_base_mask = is_base & (base_before == 0)

        # Reference parity (merge.py UInt64AddOperator._parse): values
        # whose length is not exactly 8 parse as 0.
        contrib = (
            (operand_mask | (first_base_mask & is_put)) & (val_len == 8)
        )
        lo = vw_lanes[0]
        hi = vw_lanes[1] if n_val_words > 1 else jnp.zeros_like(lo)
        zero = jnp.uint32(0)
        limbs = [
            jnp.where(contrib, lo & 0xFFFF, zero),
            jnp.where(contrib, lo >> 16, zero),
            jnp.where(contrib, hi & 0xFFFF, zero),
            jnp.where(contrib, hi >> 16, zero),
        ]

        # inclusive prefix sums; their value AT THE SEGMENT END comes
        # back to every row via one backward flagged fill. Segment total
        # for a row = end_prefix - (own_prefix - own_x) — all local
        # afterwards.
        pref = [jnp.cumsum(v) for v in limbs + [
            operand_mask.astype(jnp.int32),
            (first_base_mask & is_put).astype(jnp.int32),
            (first_base_mask & is_del).astype(jnp.int32),
        ]] + [iota]
        ends = _seg_fill_backward(last_key, tuple(pref))
        excl = lambda c, x: c - x  # noqa: E731

        sums = [
            ends[i] - excl(pref[i], limbs[i]) for i in range(4)
        ]
        seg_has_operands = (
            ends[4] - excl(pref[4], operand_mask.astype(jnp.int32))
        ) > 0
        seg_base_put = (
            ends[5] - excl(pref[5],
                           (first_base_mask & is_put).astype(jnp.int32))
        ) > 0
        seg_base_del = (
            ends[6] - excl(pref[6],
                           (first_base_mask & is_del).astype(jnp.int32))
        ) > 0
        seg_size = ends[7] - iota_start + 1
        sum_lo, sum_hi = _limb_combine(*sums)

        folded = seg_has_operands
        vw_lanes[0] = jnp.where(folded, sum_lo, lo)
        if n_val_words > 1:
            vw_lanes[1] = jnp.where(folded, sum_hi, hi)
        val_len = jnp.where(folded, jnp.uint32(8), val_len)
        pure_operands = seg_has_operands & ~seg_base_put & ~seg_base_del
        resolved_put = seg_base_put | (seg_has_operands & seg_base_del)
        out_vtype = jnp.where(
            resolved_put | (pure_operands & drop_tombstones),
            jnp.uint32(_PUT),
            jnp.where(pure_operands, jnp.uint32(_MERGE), vtype),
        )
        rep = new_key & valid
        vtype = jnp.where(rep, out_vtype, vtype)
        dropped = seg_base_del & ~seg_has_operands
        # Limb sums are exact only below 2^16 contributing operands per
        # key; flag oversize groups so callers fall back to CPU instead
        # of silently wrapping (generous: 65k updates of ONE key in ONE
        # batch).
        overflow_mask = (seg_size >= (1 << 16)) & valid
    else:
        rep = new_key & valid
        dropped = is_del

    if drop_tombstones:
        keep = rep & ~dropped
    else:
        keep = rep
    return vtype, val_len, vw_lanes, keep, overflow_mask


def resolve_sorted_lanes(
    key_lanes,                  # list of (N,) u32, length == key_words
    key_len,                    # (N,) u32 or None (uniform_klen path)
    seq_hi,                     # (N,) u32 or None (seq32 path)
    seq_lo,                     # (N,) u32
    valid,                      # (N,) bool
    vtype,                      # (N,) u32
    val_len,                    # (N,) u32
    vw_lanes,                   # list of (N,) u32 value-word lanes
    klen_const,                 # scalar u32 (uniform_klen reconstruction)
    *,
    merge_kind: MergeKind,
    drop_tombstones: bool,
    uniform_klen: bool,
    seq32: bool,
    key_words: int,
    val_row=None,               # (N,) u32: the index path's one lane
) -> Dict[str, jnp.ndarray]:
    """Phases 2-4 of the kernel on ALREADY merge-ordered lanes
    ((invalid-last, key asc, seq desc) order): boundary detection,
    segmented LSM resolution, stream compaction.

    ``val_row`` (the index path, ``vw_lanes`` empty): each row's index
    into the caller's value matrix rides the compaction in the value
    lanes' place and comes back as ``val_row`` instead of ``val_words``;
    the caller moves the values once (``gather_value_rows``)."""
    n = seq_lo.shape[0]
    if val_row is not None and (vw_lanes or merge_kind is not MergeKind.NONE):
        raise ValueError("the index path carries no value lane and takes "
                         "no merge operator (a fold rewrites values)")
    seq_hi = seq_hi if seq_hi is not None else jnp.zeros_like(seq_lo)

    vtype, val_len, vw_lanes, keep, overflow_mask = resolve_decisions(
        key_lanes, key_len, valid, vtype, val_len, vw_lanes,
        merge_kind=merge_kind, drop_tombstones=drop_tombstones,
        uniform_klen=uniform_klen, key_words=key_words)
    overflow_risk = (jnp.any(overflow_mask) if overflow_mask is not None
                     else jnp.asarray(False))

    # --- stream compaction: stable sort, output lanes as payload -------
    not_keep = jnp.where(keep, jnp.uint32(0), jnp.uint32(1))
    carried = [val_row] if val_row is not None else vw_lanes
    out_payload = list(key_lanes) + [seq_lo, vtype, val_len] + carried
    if not seq32:
        out_payload.append(seq_hi)
    if not uniform_klen:
        out_payload.append(key_len)
    sorted2 = lax.sort(tuple([not_keep] + out_payload), num_keys=1,
                       is_stable=True)
    count = jnp.sum(keep.astype(jnp.int32))
    live = lax.iota(jnp.int32, n) < count

    def m1(a: jnp.ndarray) -> jnp.ndarray:
        return jnp.where(live, a, jnp.zeros_like(a))

    pos = 1
    out_key_lanes = [m1(sorted2[pos + w]) for w in range(key_words)]
    pos += key_words
    out_seq_lo = m1(sorted2[pos]); pos += 1
    out_vtype = m1(sorted2[pos]); pos += 1
    out_val_len = m1(sorted2[pos]); pos += 1
    out_vw = [m1(sorted2[pos + w]) for w in range(len(carried))]
    pos += len(carried)
    if not seq32:
        out_seq_hi = m1(sorted2[pos]); pos += 1
    else:
        out_seq_hi = jnp.zeros_like(out_seq_lo)
    if not uniform_klen:
        out_key_len = m1(sorted2[pos]); pos += 1
    else:
        out_key_len = jnp.where(live, klen_const, jnp.uint32(0))

    # full-width (6-lane) key matrices; lanes >= key_words are zero by the
    # caller-verified promise, LE lanes are byteswaps of the BE lanes
    zeros_tail = [jnp.zeros_like(out_seq_lo)] * (KEY_WORDS - key_words)
    out_kw_be = jnp.stack(out_key_lanes + zeros_tail, axis=1)
    out_kw_le = jnp.stack(
        [bswap32(w) for w in out_key_lanes] + zeros_tail, axis=1)

    out = {
        "key_words_be": out_kw_be,
        "key_words_le": out_kw_le,
        "key_len": out_key_len,
        "seq_hi": out_seq_hi,
        "seq_lo": out_seq_lo,
        "vtype": out_vtype,
        "val_len": out_val_len,
        "count": count,
        "needs_cpu_fallback": overflow_risk,
    }
    if val_row is not None:
        out["val_row"] = out_vw[0]
    else:
        out["val_words"] = jnp.stack(out_vw, axis=1)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("merge_kind", "drop_tombstones", "uniform_klen",
                     "seq32", "key_words"),
)
def merge_resolve_kernel(
    key_words_be: jnp.ndarray,  # (N, 6) u32
    key_len: jnp.ndarray,       # (N,) u32
    seq_hi: jnp.ndarray,
    seq_lo: jnp.ndarray,
    vtype: jnp.ndarray,         # (N,) u32
    val_words: jnp.ndarray,     # (N, W) u32
    val_len: jnp.ndarray,       # (N,) u32
    valid: jnp.ndarray,         # (N,) bool
    *,
    merge_kind: MergeKind = MergeKind.UINT64_ADD,
    drop_tombstones: bool = True,
    uniform_klen: bool = False,
    seq32: bool = False,
    key_words: int = KEY_WORDS,
) -> Dict[str, jnp.ndarray]:
    """Merge + resolve a concatenated batch of runs (order-free input).

    Returns dense output arrays (capacity N, first ``count`` rows live):
    key_words_be/le, key_len, seq_hi/lo, vtype, val_words, val_len, count.
    (LE key lanes are not an input: they are byteswaps of the BE lanes,
    recomputed on the outputs — callers save the H2D transfer.)
    ``uniform_klen``/``seq32``/``key_words`` are caller-verified fast-path
    promises (see _sort_merge_order); results are identical either way.
    """
    index = value_path(merge_kind, val_words.shape[1]) == "index"
    out = _sort_resolve(
        key_words_be, key_len, seq_hi, seq_lo, vtype, val_words, val_len,
        valid, index=index, merge_kind=merge_kind,
        drop_tombstones=drop_tombstones, uniform_klen=uniform_klen,
        seq32=seq32, key_words=key_words)
    if index:
        out["val_words"] = gather_value_rows(
            val_words, out.pop("val_row"), out["count"])
    return out


def _sort_resolve(key_words_be, key_len, seq_hi, seq_lo, vtype, val_words,
                  val_len, valid, *, index, merge_kind, drop_tombstones,
                  uniform_klen, seq32, key_words):
    """Phases 1-4 with the values' lanes riding both sorts: every word
    of ``val_words`` (the riding path), or with ``index`` ONE lane, each
    row's own index (``val_words`` is not looked at, and the output has
    ``val_row`` in ``val_words``' place)."""
    # uniform_klen reconstruction constant: the one valid key length
    # (input order differs from output order, so the lane itself can't be
    # passed through; invalid rows may carry zero lengths)
    klen_const = jnp.max(jnp.where(valid, key_len, jnp.uint32(0)))

    # --- phase 1: merge-order sort, payload riding the network ---------
    carried = ((lax.iota(jnp.uint32, key_len.shape[0]),) if index else
               tuple(val_words[:, w] for w in range(val_words.shape[1])))
    key_lanes, klen_s, shi_s, slo_s, valid_s, payload = _sort_merge_order(
        key_words_be, key_len, seq_hi, seq_lo, valid,
        (vtype, val_len) + carried,
        uniform_klen=uniform_klen, seq32=seq32, key_words=key_words,
    )
    return resolve_sorted_lanes(
        list(key_lanes), klen_s, shi_s, slo_s, valid_s,
        payload[0], payload[1], [] if index else list(payload[2:]),
        klen_const,
        merge_kind=merge_kind, drop_tombstones=drop_tombstones,
        uniform_klen=uniform_klen, seq32=seq32, key_words=key_words,
        val_row=payload[2] if index else None,
    )


def merge_resolve_rows(key_words_be, key_len, seq_hi, seq_lo, vtype,
                       val_len, valid, *, drop_tombstones: bool,
                       uniform_klen: bool = False, seq32: bool = False,
                       key_words: int = KEY_WORDS):
    """The index path's sorts and resolve, with no value in sight
    (``MergeKind.NONE``: newest PUT/DELETE wins, no value is rewritten).
    The output of ``merge_resolve_kernel`` without ``val_words``, with
    ``val_row`` in its place: for each output row, the input row whose
    value it keeps (0 beyond ``count``). ``gather_value_rows`` moves the
    values; a caller with several shards' values in separate buffers
    (tpu/compaction_service.py) vmaps this and moves each shard's apart."""
    return _sort_resolve(
        key_words_be, key_len, seq_hi, seq_lo, vtype, None, val_len, valid,
        index=True, merge_kind=MergeKind.NONE,
        drop_tombstones=drop_tombstones, uniform_klen=uniform_klen,
        seq32=seq32, key_words=key_words)


def gather_value_rows(val_words: jnp.ndarray, val_row: jnp.ndarray,
                      count) -> jnp.ndarray:
    """The index path's one move: output row i takes the whole value row
    ``val_words[val_row[i]]`` (W contiguous words); rows from ``count``
    on are zero, as the riding path leaves them."""
    live = lax.iota(jnp.int32, val_row.shape[0]) < count
    rows = val_words.at[val_row].get(mode="promise_in_bounds")
    return jnp.where(live[:, None], rows, jnp.zeros_like(rows))
