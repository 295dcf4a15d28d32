"""Device-mesh sharding for batched compaction.

Mesh axes:
- ``shard``: independent shards (DP-analog) — no communication.
- ``block``: blockwise split of one shard's entries (SP-analog) — each
  device merges its block locally, then an ``all_gather`` over the block
  axis assembles the shard's blocks for the final merge, and a ``psum``
  over the shard axis produces global job stats. Collectives ride ICI on
  real hardware; the same program runs on a virtual CPU mesh in tests.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np


# Per-device working-set target for one block of a shard's compaction job.
# v5e VMEM is ~128 MiB/core; the kernel's sort working set is a small
# multiple of the block's lane bytes, so budget well below that.
BLOCK_BYTES_TARGET = 32 << 20


def derive_block_axis(num_devices: int,
                      shard_bytes: Optional[int] = None,
                      block_bytes_target: int = BLOCK_BYTES_TARGET) -> int:
    """Block-axis size (SP-analog) from device count and job size.

    Picks the smallest power-of-2 divisor of ``num_devices`` whose blocks
    fit ``block_bytes_target`` (more block-parallelism only when a
    shard's job exceeds one device's budget — otherwise devices are
    better spent on the no-communication shard axis). Shards larger than
    block capacity compose with tpu/chunked.py's hierarchical merge.
    Without a ``shard_bytes`` hint: 2 when the device count is even
    (exercises both collectives), else 1."""
    if num_devices <= 1:
        return 1
    if shard_bytes is None:
        return 2 if num_devices % 2 == 0 else 1
    block = 1
    while (
        block < num_devices
        and num_devices % (block * 2) == 0
        and shard_bytes / block > block_bytes_target
    ):
        block *= 2
    return block


def make_mesh(num_devices: Optional[int] = None,
              axis_names: Tuple[str, str] = ("shard", "block"),
              block: Optional[int] = None,
              shard_bytes: Optional[int] = None):
    """2D mesh over the first ``num_devices`` devices. The block axis is
    ``block`` if given, else derived from the job size (see
    derive_block_axis)."""
    import jax

    devices = jax.devices()
    n = num_devices or len(devices)
    devices = devices[:n]
    if block is None:
        block = derive_block_axis(n, shard_bytes)
    if n % block != 0:
        raise ValueError(f"block axis {block} does not divide {n} devices")
    shard = n // block
    arr = np.array(devices).reshape(shard, block)
    return jax.sharding.Mesh(arr, axis_names)


def sharded_compaction_step(mesh, model=None):
    """Returns a jitted step over (S, B, N, ...) arrays: S sharded on the
    ``shard`` axis, B on the ``block`` axis.

    Per (shard, block) tile: local merge-resolve. Then all_gather along
    ``block`` to assemble the shard's blocks, a second merge-resolve over
    the concatenation (entries per block stay sorted, so this is the
    SP merge step), bloom build, and a psum'd global stats reduction.
    Output: final merged arrays per shard (replicated over ``block``),
    bloom words, per-shard counts, and the global count.

    **Required invariant:** a shard's blocks must partition its entries by
    sequence range — every seq in block b strictly newer than every seq in
    block b-1 (the natural layout: blocks are WAL ranges / LSM runs).
    Block-local resolution folds operands into the block's newest base;
    that composes across blocks ONLY under this ordering (a newer block's
    partial fold must not swallow operands that an older block's newer-seq
    base should shadow). ``make_sharded_inputs`` generates compliant data.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..models.compaction_model import CompactionModel
    from ..ops.bloom_tpu import bloom_build_tpu
    from ..ops.compaction_kernel import merge_resolve_kernel

    model = model or CompactionModel()
    merge_kind = model.merge_kind

    def local_step(kwbe, klen, shi, slo, vt, vw, vl, valid):
        # local shapes: (s, 1, N, ...) — one block column per device
        s, b, n = klen.shape
        squeeze = lambda a: a.reshape((s * b, n) + a.shape[3:])

        def run(args, drop):
            return merge_resolve_kernel(
                *args, merge_kind=merge_kind, drop_tombstones=drop)

        # 1) block-local merge (keep tombstones: blocks are partial views)
        local = dict(jax.vmap(lambda *a: run(a, False))(
            squeeze(kwbe), squeeze(klen), squeeze(shi),
            squeeze(slo), squeeze(vt), squeeze(vw), squeeze(vl),
            squeeze(valid),
        ))
        local_fallback = jnp.any(local.pop("needs_cpu_fallback"))
        # LE lanes are byteswap-derived wherever needed — don't pay the
        # all_gather for them
        local.pop("key_words_le")
        # 2) assemble the shard's blocks: all_gather over the block axis
        gathered = {
            k: jax.lax.all_gather(v, "block", axis=1)
            for k, v in local.items()
        }
        nb = gathered["key_len"].shape[1]
        flat = {
            k: v.reshape((s, nb * n) + v.shape[3:])
            for k, v in gathered.items()
            if k != "count"
        }
        # rows beyond each block's count are zero-filled by the scatter —
        # mark them invalid for the final merge
        per_block_counts = gathered["count"]  # (s, nb)
        row_block = jnp.arange(nb * n) // n
        row_in_block = jnp.arange(nb * n) % n
        valid2 = row_in_block[None, :] < per_block_counts[:, row_block]
        # 3) final merge per shard + bloom + stats
        final = dict(jax.vmap(
            lambda *a: merge_resolve_kernel(
                *a, merge_kind=merge_kind,
                drop_tombstones=model.drop_tombstones,
            )
        )(
            flat["key_words_be"], flat["key_len"],
            flat["seq_hi"], flat["seq_lo"], flat["vtype"],
            flat["val_words"], flat["val_len"], valid2,
        ))
        fallback = local_fallback | jnp.any(final.pop("needs_cpu_fallback"))
        out_valid = (
            jnp.arange(nb * n)[None, :] < final["count"][:, None]
        )
        bloom = jax.vmap(
            lambda kw, kl, v: bloom_build_tpu(
                kw, kl, v, num_words=model.num_bloom_words
            )
        )(final["key_words_le"], final["key_len"], out_valid)
        if model.emit_planar:
            # production sink format on-device, per shard (the same
            # encode model.forward emits single-chip): plane words +
            # word-domain checksums for every planar block
            from ..ops.block_encode import (encode_planar_words_tpu,
                                            planar_checksums_tpu)

            planar = jax.vmap(
                lambda kwb, shi, slo, vt, vw: encode_planar_words_tpu(
                    kwb, shi, slo, vt, vw,
                    klen=model.row_klen, vlen=model.row_vlen,
                    seq32=model.seq32,
                    block_entries=model.planar_block_entries,
                )
            )(final["key_words_be"], final["seq_hi"], final["seq_lo"],
              final["vtype"], final["val_words"])
            final["planar_words"] = planar
            final["planar_chk"] = jax.vmap(planar_checksums_tpu)(planar)
        global_count = jax.lax.psum(final["count"].sum(), "shard")
        # any device needing CPU fallback poisons the whole job. Reduce over
        # BOTH axes: local_fallback differs per block column, and out_spec
        # P(None, None) materializes one column's value.
        global_fallback = jax.lax.pmax(
            fallback.astype(jnp.int32), ("shard", "block")
        )
        # re-insert the block axis (replicated) for out_specs
        expand = lambda a: a[:, None]
        return (
            {k: expand(v) for k, v in final.items() if k != "count"},
            expand(bloom),
            expand(final["count"]),
            global_count[None, None],
            global_fallback[None, None],
        )

    in_spec = P("shard", "block")
    final_keys = [
        "key_words_be", "key_words_le", "key_len", "seq_hi",
        "seq_lo", "vtype", "val_words", "val_len",
    ]
    if model.emit_planar:
        final_keys += ["planar_words", "planar_chk"]
    step = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(in_spec,) * 8,
        out_specs=(
            {k: P("shard", None) for k in final_keys},
            P("shard", None),
            P("shard", None),
            P(None, None),
            P(None, None),
        ),
        check_vma=False,
    )
    return jax.jit(step)


def make_sharded_inputs(mesh, shards_per_device: int = 1,
                        entries_per_block: int = 256, model=None,
                        seed: int = 0) -> Dict[str, np.ndarray]:
    """Synthetic (S, B, N, ...) inputs laid out for the mesh."""
    from ..models.compaction_model import synth_counter_batch

    shard_n = mesh.shape["shard"] * shards_per_device
    block_n = mesh.shape["block"]
    n = entries_per_block
    arrays = None
    for s in range(shard_n):
        for b in range(block_n):
            batch = synth_counter_batch(
                n, seed=seed + s * 131 + b,
                start_seq=1 + b * n,
            )
            if arrays is None:
                arrays = {
                    k: np.zeros((shard_n, block_n) + v.shape, v.dtype)
                    for k, v in batch.items()
                }
            for k, v in batch.items():
                arrays[k][s, b] = v
    return arrays


def shard_inputs_on_mesh(mesh, arrays: Dict[str, np.ndarray]):
    """device_put with PartitionSpec("shard", "block") on the leading dims."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P("shard", "block"))
    return {k: jax.device_put(v, sharding) for k, v in arrays.items()}
