"""Fused VMEM-resident merge-resolve — ONE kernel, one HBM round-trip.

``merge_resolve_kernel`` (ops/compaction_kernel.py) is four phases:
merge-order sort, boundary detection, segmented LSM resolution, and a
second stable sort for stream compaction. With ``sort_backend="pallas"``
only phase 1 runs in VMEM; phases 2-4 still lower through XLA, so every
intermediate lane (prefix sums, segment fills, the full second sort
network) round-trips HBM — by the round-2 roofline analysis the same
tax the Pallas sort was built to remove.

This kernel runs ALL FOUR phases inside one ``pallas_call``: lanes are
read from HBM once, sorted by the shared bitonic network
(pallas_sort.bitonic_network), resolved with shift-based scans, stream-
compacted by a second in-VMEM bitonic pass (keyed by the packed
``not_keep<<31 | index`` composite — one lane whose unique-index
tiebreak reproduces XLA's ``is_stable=True`` ordering exactly), and
written back once.

Scan primitives: every ``cumsum``/segmented fill from the XLA resolve
is re-expressed as a Hillis-Steele ladder of linear-order shifts on the
(R, 128) lane layout. A shift by d decomposes like a bitonic partner
distance: d >= 128 is a sublane (row) shift, d < 128 is an in-row lane
shift with a one-row carry — all concatenates of VMEM slices, no
gathers. The segmented-fill combine has no identity element, so ladder
steps whose partner falls off the edge are masked with the row index
(``iota >= d`` forward / ``iota < n-d`` backward) instead of shifting
in a pad value.

Semantics are pinned element-exact against ``merge_resolve_kernel``'s
lax path by tests/test_tpu_ops.py parity tests (interpret mode on CPU;
the chip compiles the same network). Reference semantics reproduced:
compaction.py resolve_stream, same as the unfused kernel — see
/root/reference/rocksdb_admin (SST compaction) and SURVEY §3.3.

Opt-in via ``CompactionModel(sort_backend="pallas_fused")`` / the
``sort_backend`` flag; shapes the kernel can't take (non-power-of-two
capacity, N < 256) raise. Status on a v5e: the chip's compiler REFUSES
this kernel today (``Reductions over unsigned integers not implemented``
— the ``jnp.max`` over the u32 overflow mask — and behind it the shared
bitonic network's lane-partner reshape; PERF.md "Chip status",
tests/test_chip_compile.py), so it has only ever run interpreted.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .compaction_kernel import (
    MergeKind, ScanPrims, bswap32, composite_key_lanes,
    resolve_decisions, split_composite_lanes)
from .kv_format import KEY_WORDS
from .pallas_sort import _LANES, _VMEM, bitonic_network


def fused_supported(n: int) -> bool:
    """True when the fused kernel can take capacity ``n`` (the bitonic
    network needs a power of two spanning at least two rows)."""
    return n >= 2 * _LANES and not (n & (n - 1))


# ---------------------------------------------------------------------
# linear-order shift / scan primitives on (R, 128) lanes
# ---------------------------------------------------------------------

def _shift_down(x, d: int):
    """y[i] = x[i-d] in linear order (i = row·128 + lane); zeros/False
    shifted in at the front. d is a power of two, so it is either a
    row multiple (sublane shift) or < 128 (lane shift + row carry)."""
    r = x.shape[0]
    if d % _LANES == 0:
        dr = d // _LANES
        pad = jnp.zeros((dr, _LANES), x.dtype)
        return jnp.concatenate([pad, x[:r - dr]], axis=0)
    prev_tail = jnp.concatenate(
        [jnp.zeros((1, d), x.dtype), x[:-1, _LANES - d:]], axis=0)
    return jnp.concatenate([prev_tail, x[:, :_LANES - d]], axis=1)


def _shift_up(x, d: int):
    """y[i] = x[i+d] in linear order; zeros/False shifted in at the
    back."""
    r = x.shape[0]
    if d % _LANES == 0:
        dr = d // _LANES
        pad = jnp.zeros((dr, _LANES), x.dtype)
        return jnp.concatenate([x[dr:], pad], axis=0)
    next_head = jnp.concatenate(
        [x[1:, :d], jnp.zeros((1, d), x.dtype)], axis=0)
    return jnp.concatenate([x[:, d:], next_head], axis=1)


def _cumsum_tuple(values, n: int):
    """Inclusive linear-order prefix sums of each array, one shared
    Hillis-Steele ladder (shifted-in zeros are the add identity — no
    edge masking needed)."""
    acc = tuple(values)
    d = 1
    while d < n:
        acc = tuple(a + _shift_down(a, d) for a in acc)
        d *= 2
    return acc


def _fill_forward(flag, values, iota, n: int):
    """compaction_kernel._seg_fill_forward on (R, 128) lanes: every row
    receives each value as of its segment's FIRST row (``flag`` marks
    segment starts; row 0 must be flagged)."""
    accf = flag
    accv = tuple(values)
    d = 1
    while d < n:
        sf = _shift_down(accf, d)
        sv = tuple(_shift_down(v, d) for v in accv)
        nf = accf | sf
        # combine(earlier=shifted, later=acc): later's flag wins
        nv = tuple(jnp.where(accf, b, a) for a, b in zip(sv, accv))
        ok = iota >= d  # partner exists; edge rows are already final
        accf = jnp.where(ok, nf, accf)
        accv = tuple(jnp.where(ok, v, b) for v, b in zip(nv, accv))
        d *= 2
    return accv


def _fill_backward(flag_last, values, iota, n: int):
    """compaction_kernel._seg_fill_backward on (R, 128) lanes: every row
    receives each value as of its segment's LAST row (``flag_last``
    marks segment ends; the final row must be flagged)."""
    accf = flag_last
    accv = tuple(values)
    d = 1
    while d < n:
        sf = _shift_up(accf, d)
        sv = tuple(_shift_up(v, d) for v in accv)
        nf = accf | sf
        nv = tuple(jnp.where(accf, b, a) for a, b in zip(sv, accv))
        ok = iota < (n - d)
        accf = jnp.where(ok, nf, accf)
        accv = tuple(jnp.where(ok, v, b) for v, b in zip(nv, accv))
        d *= 2
    return accv


# ---------------------------------------------------------------------
# the fused kernel body
# ---------------------------------------------------------------------

def _fused_kernel(
    num_keys: int, r_rows: int, n_in: int, key_words: int,
    uniform_klen: bool, seq32: bool, merge_kind: MergeKind,
    drop_tombstones: bool, n_val_words: int, *refs,
):
    in_refs = refs[:n_in]
    out_refs = refs[n_in:]
    n = r_rows * _LANES

    # --- phase 1: merge-order bitonic sort, all lanes in VMEM ---------
    lanes = [r[:] for r in in_refs]
    lanes = bitonic_network(lanes, num_keys, r_rows)
    key_lanes, klen, shi, slo, valid, pos = split_composite_lanes(
        lanes, key_words, uniform_klen=uniform_klen, seq32=seq32)
    vtype = lanes[pos]
    val_len = lanes[pos + 1]
    vw = list(lanes[pos + 2:pos + 2 + n_val_words])

    iota = (jax.lax.broadcasted_iota(jnp.int32, (r_rows, _LANES), 0)
            * _LANES
            + jax.lax.broadcasted_iota(jnp.int32, (r_rows, _LANES), 1))

    # --- phases 2-3: ONE copy of the resolve math (compaction_kernel.
    # resolve_decisions), instantiated over the VMEM shift ladders -----
    prims = ScanPrims(
        iota, n,
        lambda x: _shift_down(x, 1),
        lambda x: _shift_up(x, 1),
        lambda values: _cumsum_tuple(values, n),
        lambda flag, values: _fill_forward(flag, values, iota, n),
        lambda flag, values: _fill_backward(flag, values, iota, n),
    )
    vtype, val_len, vw, keep, overflow_mask = resolve_decisions(
        prims, key_lanes, klen, valid, vtype, val_len, vw,
        merge_kind=merge_kind, drop_tombstones=drop_tombstones,
        uniform_klen=uniform_klen, key_words=key_words)
    if overflow_mask is not None:
        ovf_u32 = jnp.max(overflow_mask.astype(jnp.uint32),
                          keepdims=True).reshape(1, 1)
    else:
        ovf_u32 = jnp.zeros((1, 1), jnp.uint32)

    # --- phase 4: stream compaction — second bitonic pass. The keep
    # bit and the unique linear index pack into ONE u32 key lane
    # (n <= 2^22 << 2^31): ordering by the composite == ordering by
    # (not_keep, index), which reproduces the lax path's is_stable=True
    # order exactly while saving a full lane through the network. -----
    not_keep = jnp.where(keep, jnp.uint32(0), jnp.uint32(1))
    sort2_key = (not_keep << 31) | iota.astype(jnp.uint32)
    out_payload: List = list(key_lanes) + [slo, vtype, val_len] + vw
    if not seq32:
        out_payload.append(shi)
    if not uniform_klen:
        out_payload.append(klen)
    sorted2 = bitonic_network([sort2_key] + out_payload, 1, r_rows)

    count = jnp.sum(keep.astype(jnp.int32), keepdims=True).reshape(1, 1)
    live = iota < count
    for ref, x in zip(out_refs[:-1], sorted2[1:]):
        ref[:] = jnp.where(live, x, jnp.zeros_like(x))

    lane_ix = jax.lax.broadcasted_iota(jnp.uint32, (1, _LANES), 1)
    meta = jnp.where(
        lane_ix == 0, count.astype(jnp.uint32),
        jnp.where(lane_ix == 1, ovf_u32, jnp.uint32(0)))
    out_refs[-1][:] = meta


@functools.partial(
    jax.jit,
    static_argnames=("merge_kind", "drop_tombstones", "uniform_klen",
                     "seq32", "key_words", "interpret"),
)
def fused_merge_resolve(
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
    interpret: bool = None,
) -> Dict[str, jnp.ndarray]:
    """Drop-in for ``merge_resolve_kernel`` (same contract, same output
    dict) running every phase in one VMEM residency. Requires capacity
    N to be a power of two >= 256 — callers dispatch via
    ``merge_resolve_kernel(..., sort_backend="pallas_fused")``."""
    n = seq_lo.shape[0]
    if not fused_supported(n):
        raise ValueError(
            f"fused_merge_resolve needs power-of-two N >= {2 * _LANES}, "
            f"got {n}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_val_words = val_words.shape[1]
    r_rows = n // _LANES
    klen_const = jnp.max(jnp.where(valid, key_len, jnp.uint32(0)))

    invalid_key = jnp.where(valid, jnp.uint32(0), jnp.uint32(1))
    operands = composite_key_lanes(
        invalid_key, (key_words_be[:, w] for w in range(key_words)),
        key_len, seq_hi, seq_lo, uniform_klen=uniform_klen, seq32=seq32)
    num_keys = len(operands)
    operands += [vtype, val_len] + [
        val_words[:, w] for w in range(n_val_words)]
    lanes2d = [x.reshape(r_rows, _LANES) for x in operands]
    n_in = len(lanes2d)
    # output lane order mirrors resolve_sorted_lanes' sorted2 payload
    n_out = key_words + 3 + n_val_words
    if not seq32:
        n_out += 1
    if not uniform_klen:
        n_out += 1

    kernel = functools.partial(
        _fused_kernel, num_keys, r_rows, n_in, key_words, uniform_klen,
        seq32, merge_kind, drop_tombstones, n_val_words)
    spec = pl.BlockSpec() if interpret else pl.BlockSpec(memory_space=_VMEM)
    out = pl.pallas_call(
        kernel,
        out_shape=(
            [jax.ShapeDtypeStruct((r_rows, _LANES), jnp.uint32)
             for _ in range(n_out)]
            + [jax.ShapeDtypeStruct((1, _LANES), jnp.uint32)]
        ),
        in_specs=[spec] * n_in,
        out_specs=[spec] * (n_out + 1),
        interpret=interpret,
    )(*lanes2d)

    flat = [x.reshape(n) for x in out[:-1]]
    meta = out[-1]
    count = meta[0, 0].astype(jnp.int32)
    needs_cpu_fallback = meta[0, 1] > 0

    pos = 0
    out_key_lanes = flat[pos:pos + key_words]
    pos += key_words
    out_seq_lo = flat[pos]; pos += 1
    out_vtype = flat[pos]; pos += 1
    out_val_len = flat[pos]; pos += 1
    out_vw = flat[pos:pos + n_val_words]
    pos += n_val_words
    if not seq32:
        out_seq_hi = flat[pos]; pos += 1
    else:
        out_seq_hi = jnp.zeros_like(out_seq_lo)
    live = jax.lax.iota(jnp.int32, n) < count
    if not uniform_klen:
        out_key_len = flat[pos]; pos += 1
    else:
        out_key_len = jnp.where(live, klen_const, jnp.uint32(0))

    zeros_tail = [jnp.zeros_like(out_seq_lo)] * (KEY_WORDS - key_words)
    out_kw_be = jnp.stack(list(out_key_lanes) + zeros_tail, axis=1)
    out_kw_le = jnp.stack(
        [bswap32(w) for w in out_key_lanes] + zeros_tail, axis=1)
    return {
        "key_words_be": out_kw_be,
        "key_words_le": out_kw_le,
        "key_len": out_key_len,
        "seq_hi": out_seq_hi,
        "seq_lo": out_seq_lo,
        "vtype": out_vtype,
        "val_words": jnp.stack(out_vw, axis=1),
        "val_len": out_val_len,
        "count": count,
        "needs_cpu_fallback": needs_cpu_fallback,
    }
