"""VMEM-resident bitonic sort — the Pallas attack on the kernel's
dominant cost.

Round-2 device profiling (PERF.md) put the merge-resolve's two
``lax.sort`` calls at ~9 ms of the 17 ms device time for 8×131k, and
the roofline analysis says a sort-based pipeline should cost ~1-2 ms of
HBM traffic. The gap is XLA's generic bitonic lowering: every
compare-exchange stage round-trips all operand lanes through HBM
(~log²(N)/2 ≈ 153 stages at 131k → hundreds of MB of traffic per
shard). The hand-rolled XLA merge network (ops/merge_network.py) lost
for exactly that reason — per-stage HBM materialization.

This kernel holds EVERY operand lane in VMEM across ALL stages: one HBM
read per lane at entry, 153 in-register/VMEM compare-exchange stages,
one HBM write at exit. Operand budget: 131072 rows × 18 u32 lanes =
9.4 MB < ~16 MB VMEM/core.

Layout: each (N,) u32 lane is viewed as (R, 128) row-major (linear index
i = r·128 + c). A bitonic partner distance d decomposes as:
- d ≥ 128 (row-partner): reshape (R, 128) → (R/2dr, 2, dr, 128) and
  compare-exchange the two middle halves — pure sublane slicing.
- d < 128 (lane-partner): reshape lanes (R, 128) → (R, 128/2d, 2, d)
  and exchange the halves — an in-VMEM lane shuffle, with no HBM
  round-trip (the catastrophic cost XLA pays for minor-dim relayouts
  does not apply inside VMEM).
The ascending/descending direction of stage (k, j) is constant within
each 2^(k+1)-block, expressed as a broadcasted-iota parity mask.

Comparator: lexicographic over the first ``num_keys`` lanes (the
composite_key_lanes order), payload lanes ride the exchanges — the same
payload-through contract as ``lax.sort(operands, num_keys=...)``, which
this function is a drop-in replacement for (N must be a power of two;
the compaction batches are always 2^k capacities).

Opt-in (CompactionModel(sort_backend="pallas") / the ``sort_backend``
flag). Status on a v5e: the chip's compiler REFUSES this kernel today —
the lane-partner stage's reshape splits the minor dim below 128
(``infer-vector-layout: unsupported shape cast``; PERF.md "Chip
status", tests/test_chip_compile.py) — so it has only ever run
interpreted, on the CPU, for the parity tests.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM
_LANES = 128


def _lex_less(a_keys, b_keys):
    """Lexicographic a < b over aligned key-lane lists (u32)."""
    less = None
    eq_prefix = None
    for a, b in zip(a_keys, b_keys):
        this_less = a < b
        this_eq = a == b
        if less is None:
            less, eq_prefix = this_less, this_eq
        else:
            less = less | (eq_prefix & this_less)
            eq_prefix = eq_prefix & this_eq
    return less


def _exchange(lanes, num_keys, asc_mask, lo_half, hi_half):
    """One compare-exchange between two aligned half-views. Returns the
    (new_lo, new_hi) per lane. ``asc_mask`` is True where the enclosing
    bitonic block sorts ascending; views are any equal shape."""
    a_keys = [lo_half(x) for x in lanes[:num_keys]]
    b_keys = [hi_half(x) for x in lanes[:num_keys]]
    b_less = _lex_less(b_keys, a_keys)  # partner belongs before me
    swap = jnp.where(asc_mask, b_less, ~b_less)
    new = []
    for x in lanes:
        a, b = lo_half(x), hi_half(x)
        new.append((jnp.where(swap, b, a), jnp.where(swap, a, b)))
    return new


def _stage(lanes, num_keys, r_rows, k, j):
    """Apply bitonic stage (k, j): partner distance d = 2^j inside
    direction blocks of 2^(k+1). ``lanes`` are (R, 128) u32 arrays."""
    d = 1 << j
    blk = 1 << (k + 1)
    n = r_rows * _LANES
    if d >= _LANES:
        dr = d // _LANES  # row-partner distance
        nb = r_rows // (2 * dr)

        def lo(x):
            return x.reshape(nb, 2, dr, _LANES)[:, 0]

        def hi(x):
            return x.reshape(nb, 2, dr, _LANES)[:, 1]

        # direction: block index of linear i is i // blk; constant across
        # a (dr, 128) tile here because blk >= 2d >= 2·128·dr
        pair_base = jax.lax.broadcasted_iota(
            jnp.uint32, (nb, dr, _LANES), 0) * jnp.uint32(2 * dr * _LANES)
        asc = (pair_base // jnp.uint32(blk)) % 2 == 0
        ex = _exchange(lanes, num_keys, asc, lo, hi)
        out = []
        for (a, b) in ex:
            stacked = jnp.stack([a, b], axis=1)  # (nb, 2, dr, 128)
            out.append(stacked.reshape(r_rows, _LANES))
        return out
    # lane-partner stage: d < 128
    nb = _LANES // (2 * d)

    def lo(x):
        return x.reshape(r_rows, nb, 2, d)[:, :, 0]

    def hi(x):
        return x.reshape(r_rows, nb, 2, d)[:, :, 1]

    row_base = jax.lax.broadcasted_iota(
        jnp.uint32, (r_rows, nb, d), 0) * jnp.uint32(_LANES)
    lane_base = jax.lax.broadcasted_iota(
        jnp.uint32, (r_rows, nb, d), 1) * jnp.uint32(2 * d)
    lane_off = jax.lax.broadcasted_iota(jnp.uint32, (r_rows, nb, d), 2)
    i_lo = row_base + lane_base + lane_off
    asc = (i_lo // jnp.uint32(blk)) % 2 == 0
    ex = _exchange(lanes, num_keys, asc, lo, hi)
    out = []
    for (a, b) in ex:
        stacked = jnp.stack([a, b], axis=2)  # (R, nb, 2, d)
        out.append(stacked.reshape(r_rows, _LANES))
    return out


def bitonic_network(lanes, num_keys: int, r_rows: int):
    """The full bitonic network over (R, 128) u32 lane VALUES (already
    VMEM-resident inside a kernel). Shared by the standalone sort kernel
    and the fused sort+resolve kernel (ops/pallas_resolve.py)."""
    n = r_rows * _LANES
    log_n = n.bit_length() - 1
    for k in range(log_n):
        for j in range(k, -1, -1):
            lanes = _stage(lanes, num_keys, r_rows, k, j)
    return lanes


def _sort_kernel(num_keys: int, r_rows: int, n_lanes: int, *refs):
    """Pallas kernel body: refs = n_lanes input refs + n_lanes output
    refs. Loads all lanes into VMEM values, runs the full bitonic
    network, writes back once."""
    in_refs = refs[:n_lanes]
    out_refs = refs[n_lanes:]
    lanes = [r[:] for r in in_refs]
    lanes = bitonic_network(lanes, num_keys, r_rows)
    for r, x in zip(out_refs, lanes):
        r[:] = x


@functools.partial(
    jax.jit, static_argnames=("num_keys", "interpret"))
def bitonic_sort_lanes(
    operands: Tuple[jnp.ndarray, ...],
    num_keys: int,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """Drop-in for ``lax.sort(operands, num_keys=num_keys)`` on (N,) u32
    lanes with N a power of two ≥ 256. The first ``num_keys`` lanes are
    the lexicographic comparator; the rest ride as payload."""
    n = operands[0].shape[0]
    if n & (n - 1) or n < 2 * _LANES:
        raise ValueError(f"bitonic_sort_lanes needs power-of-two N >= "
                         f"{2 * _LANES}, got {n}")
    for i, x in enumerate(operands):
        if x.dtype != jnp.uint32:
            # silent reinterpretation would order signed lanes differently
            # from lax.sort — enforce the documented u32-lane contract
            raise TypeError(f"operand {i} is {x.dtype}, expected uint32")
    r_rows = n // _LANES
    n_lanes = len(operands)
    lanes2d = [x.reshape(r_rows, _LANES) for x in operands]
    kernel = functools.partial(_sort_kernel, num_keys, r_rows, n_lanes)
    spec = pl.BlockSpec() if interpret else pl.BlockSpec(memory_space=_VMEM)
    out = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((r_rows, _LANES), jnp.uint32)
                   for _ in range(n_lanes)],
        in_specs=[spec] * n_lanes,
        out_specs=[spec] * n_lanes,
        interpret=interpret,
    )(*lanes2d)
    return tuple(x.reshape(n) for x in out)


def sort_lanes(operands: Sequence[jnp.ndarray], num_keys: int,
               backend: str = "lax",
               interpret: bool = None) -> Tuple[jnp.ndarray, ...]:
    """Sort dispatch: ``lax`` = XLA's sort (default), ``pallas`` = the
    VMEM-resident bitonic kernel, which RAISES for shapes it does not
    take (``bitonic_sort_lanes``: power-of-two N >= 256, u32 lanes) — a
    caller that asked for the kernel never gets ``lax.sort`` under its
    name. ``interpret=None`` selects interpreter mode off-chip only (the
    CPU test suite); on a TPU backend it is always the native kernel."""
    ops = tuple(operands)
    if backend == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return bitonic_sort_lanes(ops, num_keys=num_keys,
                                  interpret=interpret)
    if backend != "lax":
        raise ValueError(f"unknown sort backend {backend!r}")
    return jax.lax.sort(ops, num_keys=num_keys, is_stable=False)
