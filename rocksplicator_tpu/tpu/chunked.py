"""Chunked hierarchical TPU merges for batches beyond one kernel launch.

Correctness rests on the engine's run invariant (the same one the mesh
block axis uses): for any key, two input runs' entries occupy disjoint,
ordered sequence ranges (L0 files partition by flush order; deeper levels
are key-disjoint; ingested files carry one global seqno). Under that
invariant LSM resolution is associative:

- a chunk of ONE run holds a contiguous newest-first slice of each key's
  stack, so folding it yields either a resolved base (shadowing the rest)
  or a partial-merge summary strictly newer than the remainder;
- merging two run summaries composes the same way (newest base shadows).

Pipeline: fold each run's chunks bottom-up, then seq-sort and greedily
group summaries into fixed-shape launches, with tombstones kept until the
final pass. Intermediate results stay as packed numpy lanes — no Python
tuples until the caller unpacks the final output.
"""

from __future__ import annotations

import logging
from typing import List, Optional, Tuple

import numpy as np

from ..ops.compaction_kernel import MergeKind, merge_resolve_kernel
from ..ops.kv_format import KVBatch

log = logging.getLogger(__name__)

from ..ops.kv_format import LANE_FIELDS as FIELDS  # noqa: E402 (canonical home)
# kernel INPUT lanes: LE key words are byteswap-derived on device, so they
# are carried between passes (FIELDS — outputs include them for the sinks)
# but never shipped into a launch
INPUT_FIELDS = tuple(f for f in FIELDS if f != "key_words_le")


def run_kernel_arrays(
    batch_arrays: dict, n_valid: int, merge_kind: MergeKind,
    drop_tombstones: bool, pad_to: Optional[int] = None,
    uniform_klen: bool = False, seq32: bool = False,
    key_words: Optional[int] = None, to_host: bool = True,
) -> Tuple[Optional[dict], int]:
    """THE kernel invocation wrapper (shared by the chunked tree and the
    backend's direct file sink): one launch over packed arrays; returns
    (output arrays trimmed to count, count) or (None, 0) on kernel-flagged
    fallback. ``pad_to`` fixes the launch shape so callers reuse one
    compiled kernel. ``to_host=False`` keeps the trimmed outputs as
    DEVICE arrays — the chunked tree feeds them straight into the next
    launch, so intermediate passes never round-trip through host numpy
    (only the count/fallback scalars sync)."""
    import jax.numpy as jnp

    n_rows = batch_arrays["key_len"].shape[0]
    if pad_to is not None and n_rows < pad_to:
        pad = pad_to - n_rows
        # jnp.pad keeps device-resident inputs on device; numpy inputs
        # land there with the launch anyway
        batch_arrays = {
            f: jnp.pad(batch_arrays[f],
                       [(0, pad)] + [(0, 0)] * (batch_arrays[f].ndim - 1))
            for f in INPUT_FIELDS
        }
        n_rows = pad_to
    valid = np.zeros(n_rows, dtype=bool)
    valid[:n_valid] = True
    kw = (key_words if key_words is not None
          else batch_arrays["key_words_be"].shape[1])
    out = merge_resolve_kernel(
        *(jnp.asarray(batch_arrays[f]) for f in INPUT_FIELDS),
        jnp.asarray(valid),
        merge_kind=merge_kind, drop_tombstones=drop_tombstones,
        uniform_klen=uniform_klen, seq32=seq32, key_words=kw,
    )
    if bool(out["needs_cpu_fallback"]):
        return None, 0
    count = int(out["count"])
    if to_host:
        return {f: np.asarray(out[f])[:count] for f in FIELDS}, count
    return {f: out[f][:count] for f in FIELDS}, count


def _concat(parts: List[dict]) -> Tuple[dict, int]:
    import jax.numpy as jnp

    # jnp: device-resident parts concatenate on device (host parts join
    # them there — that is where the next launch reads them)
    merged = {f: jnp.concatenate([p[f] for p in parts]) for f in FIELDS}
    return merged, merged["key_len"].shape[0]


def _batch_to_arrays(batch: KVBatch) -> Tuple[dict, int]:
    n = batch.num_valid()
    return {f: getattr(batch, f)[:n] for f in FIELDS}, n


def _fold_groups(
    parts: List[Tuple[dict, int]], merge_kind: MergeKind,
    launch_entries: int,
) -> Optional[List[Tuple[dict, int]]]:
    """One greedy pass: group consecutive parts up to the launch size and
    fold each group (tombstones kept — not the final pass)."""
    next_level: List[Tuple[dict, int]] = []
    group: List[dict] = []
    group_n = 0

    def flush() -> bool:
        nonlocal group, group_n
        if not group:
            return True
        merged, total = _concat(group)
        out = run_kernel_arrays(merged, total, merge_kind, False,
                                pad_to=launch_entries, to_host=False)
        if out[0] is None:
            return False
        next_level.append(out)
        group, group_n = [], 0
        return True

    for part, pn in parts:
        if group and group_n + pn > launch_entries:
            if not flush():
                return None
        group.append(part)
        group_n += pn
    if not flush():
        return None
    return next_level


def chunked_merge(
    run_batches: List[KVBatch],
    merge_kind: MergeKind,
    drop_tombstones: bool,
    chunk_entries: int,
    launch_entries: int,
) -> Optional[Tuple[dict, int]]:
    """Merge packed per-run batches hierarchically. Returns (final output
    arrays, count), or None when the kernel demands CPU fallback."""
    chunk_entries = min(chunk_entries, launch_entries)
    # 1) per-run: multi-chunk runs reduce to one summary; single-chunk
    #    runs pass through raw (already sorted per the run contract — a
    #    dedup fold would be a wasted full-size launch)
    summaries: List[Tuple[dict, int]] = []
    for batch in run_batches:
        arrays, n = _batch_to_arrays(batch)
        pieces: List[Tuple[dict, int]] = [
            ({f: arrays[f][i:i + chunk_entries] for f in FIELDS},
             min(chunk_entries, n - i))
            for i in range(0, n, chunk_entries)
        ] or [(arrays, 0)]
        while len(pieces) > 1:
            folded = _fold_groups(pieces, merge_kind, launch_entries)
            if folded is None:
                return None
            if len(folded) >= len(pieces):
                return None  # cannot reduce further
            pieces = folded
        summaries.append(pieces[0])

    # 2) merge run summaries hierarchically; the final pass applies the
    #    real tombstone policy. Grouping folds CONSECUTIVE summaries,
    #    which is only associativity-safe for ADJACENT seq intervals —
    #    engine run lists arrive level-ordered ([L0 old..new, L1, ...]),
    #    NOT seq-ordered, so sort summaries by max seq first (runs occupy
    #    globally disjoint seq intervals in this engine).
    def _max_seq(part_n) -> int:
        part, n = part_n
        if n == 0:
            return 0
        hi_lane, lo_lane = part["seq_hi"][:n], part["seq_lo"][:n]
        if isinstance(hi_lane, np.ndarray):
            # host part (single-chunk pass-through): pure numpy, no H2D
            hi64 = hi_lane.astype(np.uint64) << np.uint64(32)
            return int((hi64 | lo_lane.astype(np.uint64)).max())
        # device part (from _fold_groups): scalar reductions + readbacks
        # only — never pull the lanes to host
        import jax.numpy as jnp

        hi = int(jnp.max(hi_lane))
        lo_at = int(jnp.max(jnp.where(
            hi_lane == hi, lo_lane, jnp.uint32(0))))
        return (hi << 32) | lo_at

    summaries.sort(key=_max_seq)
    while True:
        total = sum(n for _p, n in summaries)
        if total <= launch_entries:
            merged, _n = _concat([p for p, _ in summaries])
            return run_kernel_arrays(merged, total, merge_kind,
                                     drop_tombstones, pad_to=launch_entries)
        folded = _fold_groups(summaries, merge_kind, launch_entries)
        if folded is None or len(folded) >= len(summaries):
            return None  # too many distinct keys to converge
        summaries = folded
