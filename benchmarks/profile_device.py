"""Component-level device profiling for the compaction pipeline.

Times pipeline stages and rewrite candidates in isolation on the live
device to locate the wall-clock. Probe sets:

  components — sorts, gathers, scans, bloom, encode, full model
  variants   — rewrite candidates (payload-through-sort, seg-scan bloom,
               encode layouts, scatter)

Every timing ends in ``jax.block_until_ready`` (dispatch is async), with
one warm call before t0, so numbers are per-iteration wall-clock
including the per-dispatch floor (see the ``floor`` probe). Runs on a
TPU only — it is a device profiler, and exits nonzero off-chip.

Usage:  python -m benchmarks.profile_device [--set components|variants|all]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def timeit(fn, args, iters=3, name="?"):
    jax.block_until_ready(fn(*args))
    t0 = time.monotonic()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.monotonic() - t0) / iters
    log(f"{name:<46s} {dt * 1e3:9.2f} ms/iter")
    return dt


def build_inputs(n: int, s: int):
    import jax.numpy as jnp

    from rocksplicator_tpu.models.compaction_model import synth_counter_batch

    shards = [
        synth_counter_batch(n, key_space=n // 8, seed=1234 + i, key_bytes=16)
        for i in range(s)
    ]
    return {k: jnp.asarray(np.stack([b[k] for b in shards]))
            for k in shards[0]}


def probe_components(st, n, iters, results):
    import jax.numpy as jnp
    from jax import lax

    from rocksplicator_tpu.models import CompactionModel
    from rocksplicator_tpu.ops.bloom_tpu import bloom_build_tpu
    from rocksplicator_tpu.ops.compaction_kernel import (
        _sort_merge_order, merge_resolve_kernel)

    small = jnp.arange(1024, dtype=jnp.uint32)
    results["floor"] = timeit(
        jax.jit(lambda x: x + 1), (small,), iters, "floor (tiny launch)")

    u32 = st["seq_lo"]

    def sort2(x):
        iota = lax.iota(jnp.uint32, x.shape[0])
        return lax.sort((x, iota), num_keys=1, is_stable=False)

    results["sort_2op"] = timeit(
        jax.jit(jax.vmap(sort2)), (u32,), iters, "sort 2-op u32 (argsort)")

    def sort_fast(kwb, klen, shi, slo, valid):
        return _sort_merge_order(kwb, klen, shi, slo, valid, (),
                                 uniform_klen=True, seq32=True,
                                 key_words=4)[3]

    results["sort_6key"] = timeit(
        jax.jit(jax.vmap(sort_fast)),
        (st["key_words_be"], st["key_len"], st["seq_hi"], st["seq_lo"],
         st["valid"]),
        iters, "sort 6-key fast path (no payload)")

    idx = jnp.argsort(st["seq_lo"], axis=-1).astype(jnp.uint32)
    jax.block_until_ready(idx)

    def take1d(c, idx):
        return jnp.take_along_axis(c, idx, axis=-1)

    results["take_1d"] = timeit(
        jax.jit(take1d), (u32, idx), iters, "take 1-D (the gather cost)")

    def scans(x):
        iota = lax.iota(jnp.int32, x.shape[0])
        return jnp.cumsum(x) + lax.cummax(jnp.where(x > 0, iota, 0))

    results["scans"] = timeit(
        jax.jit(jax.vmap(scans)), (st["seq_lo"].astype(jnp.int32),),
        iters, "cumsum+cummax")

    model = CompactionModel(capacity=n, uniform_klen=True, seq32=True,
                            key_words=4)
    margs = (st["key_words_be"], st["key_len"],
             st["seq_hi"], st["seq_lo"], st["vtype"], st["val_words"],
             st["val_len"], st["valid"])

    def mrk(*a):
        return merge_resolve_kernel(
            *a, uniform_klen=True, seq32=True, key_words=4)

    results["merge_resolve"] = timeit(
        jax.jit(jax.vmap(mrk)), margs, iters, "merge_resolve_kernel")

    results["bloom"] = timeit(
        jax.jit(jax.vmap(lambda kwl, kl, v: bloom_build_tpu(
            kwl, kl, v, num_words=model.num_bloom_words))),
        (st["key_words_le"], st["key_len"], st["valid"]),
        iters, "bloom_build_tpu")

    results["full_model"] = timeit(
        jax.jit(jax.vmap(model.forward)), margs, iters, "FULL model.forward")


def probe_variants(st, n, iters, results):
    import jax.numpy as jnp
    from jax import lax

    kw = st["key_words_be"]

    def sort10(kw, slo, vt, vw, vl, valid):
        inval = jnp.where(valid, jnp.uint32(0), jnp.uint32(1))
        ops = (inval, kw[:, 0], kw[:, 1], kw[:, 2], kw[:, 3], ~slo,
               vt, vw[:, 0], vw[:, 1], vl)
        return lax.sort(ops, num_keys=6, is_stable=False)

    results["sort_10op_payload"] = timeit(
        jax.jit(jax.vmap(sort10)),
        (kw, st["seq_lo"], st["vtype"], st["val_words"], st["val_len"],
         st["valid"]),
        iters, "sort 10-op (payload-through)")

    # minor-dim materialization: why rows must stay planar
    def stack_rows(slo, shi, vt, vw):
        m = slo.shape[0]
        lanes = [jnp.full((m,), jnp.uint32(16)), slo, shi, vt,
                 vw[:, 0], vw[:, 1]]
        return jnp.stack(lanes, axis=1)

    results["stack_minor6"] = timeit(
        jax.jit(jax.vmap(stack_rows)),
        (st["seq_lo"], st["seq_hi"], st["vtype"], st["val_words"]),
        iters, "stack 6 lanes -> (n, 6) minor-dim")

    def scatter_only(sidx, val):
        out = jnp.zeros(n + 1, dtype=jnp.uint32)
        return out.at[sidx].set(val, mode="drop")[:n]

    sidx = jnp.argsort(st["seq_lo"], axis=-1).astype(jnp.int32)
    jax.block_until_ready(sidx)
    results["scatter_set"] = timeit(
        jax.jit(jax.vmap(scatter_only)), (sidx, st["seq_lo"]),
        iters, "scatter .at[].set one lane")


def probe_mergenet(st, n, iters, results):
    """Full-sort kernel vs the sorted-runs bitonic merge network at the
    bench shape. Run pre-sorting happens OUTSIDE the timed region — real
    compaction inputs (SSTs, memtable dumps) arrive sorted."""
    import jax.numpy as jnp

    from rocksplicator_tpu.ops.compaction_kernel import (
        _sort_merge_order, merge_resolve_kernel)
    from rocksplicator_tpu.ops.merge_network import (
        merge_resolve_runs_kernel, merge_sorted_lanes)

    margs = (st["key_words_be"], st["key_len"], st["seq_hi"], st["seq_lo"],
             st["vtype"], st["val_words"], st["val_len"], st["valid"])

    def mrk(*a):
        return merge_resolve_kernel(
            *a, uniform_klen=True, seq32=True, key_words=4)

    results["kernel_fullsort"] = timeit(
        jax.jit(jax.vmap(mrk)), margs, iters,
        "merge_resolve_kernel (full sort)")

    def presort_runs(runs):
        """(S, n) shard lanes -> (S, R, L) per-run-sorted lanes."""
        L = n // runs

        def sort_one(kwb, klen, shi, slo, vt, vw, vl, valid):
            key_lanes, _, _, slo_s, valid_s, payload = _sort_merge_order(
                kwb, klen, shi, slo, valid,
                (vt, vw[:, 0], vw[:, 1], vl),
                uniform_klen=True, seq32=True, key_words=4)
            kw6 = jnp.stack(
                list(key_lanes) + [jnp.zeros_like(slo_s)] * 2, axis=1)
            # klen/shi come back None from the fast-path sort; rebuild
            # them as the constants the promises assert so every lane in
            # the dict is aligned with the sorted row order
            return {
                "key_words_be": kw6,
                "key_len": jnp.full_like(klen, 16),
                "seq_hi": jnp.zeros_like(shi),
                "seq_lo": slo_s,
                "vtype": payload[0],
                "val_words": jnp.stack(payload[1:3], axis=1),
                "val_len": payload[3],
                "valid": valid_s,
            }

        def shard_to_runs(kwb, klen, shi, slo, vt, vw, vl, valid):
            rs = (kwb.reshape(runs, L, 6), klen.reshape(runs, L),
                  shi.reshape(runs, L), slo.reshape(runs, L),
                  vt.reshape(runs, L), vw.reshape(runs, L, 2),
                  vl.reshape(runs, L), valid.reshape(runs, L))
            return jax.vmap(sort_one)(*rs)

        out = jax.jit(jax.vmap(shard_to_runs))(*margs)
        return jax.block_until_ready(out)

    for runs in (8, 32):
        rst = presort_runs(runs)
        rargs = (rst["key_words_be"], rst["key_len"], rst["seq_hi"],
                 rst["seq_lo"], rst["vtype"], rst["val_words"],
                 rst["val_len"], rst["valid"])

        def tree_only(kwb, slo, valid):
            inval = jnp.where(valid, jnp.uint32(0), jnp.uint32(1))
            lanes = [inval] + [kwb[:, :, w] for w in range(4)] + [~slo]
            return merge_sorted_lanes(lanes, 6)

        results[f"mergenet_tree_only_r{runs}"] = timeit(
            jax.jit(jax.vmap(tree_only)),
            (rst["key_words_be"], rst["seq_lo"], rst["valid"]),
            iters, f"merge tree only ({runs} runs, no payload)")

        def mrrk(*a):
            return merge_resolve_runs_kernel(
                *a, uniform_klen=True, seq32=True, key_words=4)

        results[f"kernel_mergenet_r{runs}"] = timeit(
            jax.jit(jax.vmap(mrrk)), rargs, iters,
            f"merge_resolve_RUNS_kernel ({runs} runs)")


def probe_pallas_sort(st, n, iters, results):
    """lax.sort vs the VMEM-resident Pallas bitonic sort, standalone and
    inside the full merge-resolve kernel (PERF.md round-2 lever: the
    sort's HBM traffic is the dominant device cost)."""
    from rocksplicator_tpu.ops.compaction_kernel import (
        composite_key_lanes, merge_resolve_kernel)
    from rocksplicator_tpu.ops.pallas_sort import sort_lanes

    def lanes_of(kwb, klen, shi, slo, vt, vw, vl, valid):
        inval = jnp.where(valid, jnp.uint32(0), jnp.uint32(1))
        keys = composite_key_lanes(
            inval, (kwb[:, w] for w in range(4)), klen, shi, slo,
            uniform_klen=True, seq32=True)
        payload = [vt, vl] + [vw[:, w] for w in range(vw.shape[1])]
        return keys, payload

    margs = (st["key_words_be"], st["key_len"], st["seq_hi"],
             st["seq_lo"], st["vtype"], st["val_words"], st["val_len"],
             st["valid"])

    for backend in ("lax", "pallas"):
        def sort_only(*a, _b=backend):
            keys, payload = lanes_of(*a)
            return sort_lanes(tuple(keys + payload), num_keys=len(keys),
                              backend=_b)

        results[f"sort_only_{backend}"] = timeit(
            jax.jit(jax.vmap(sort_only)), margs, iters,
            f"10-operand sort, {backend} backend")

    for backend in ("lax", "pallas", "pallas_fused"):
        def full(*a, _b=backend):
            return merge_resolve_kernel(
                *a, uniform_klen=True, seq32=True, key_words=4,
                sort_backend=_b)

        results[f"kernel_{backend}_sort"] = timeit(
            jax.jit(jax.vmap(full)), margs, iters,
            f"merge_resolve_kernel, {backend} sort")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--entries", type=int, default=1 << 17)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--set", default="components",
                    choices=("components", "variants", "mergenet",
                             "pallas", "all"))
    args = ap.parse_args()

    log(f"platform={jax.default_backend()} shards={args.shards} "
        f"entries={args.entries}")
    if jax.default_backend() != "tpu":
        # interpret-mode pallas takes minutes per trace, and a CPU
        # timing under a device probe's name is worse than none
        log("profile_device needs a TPU — aborting")
        sys.exit(3)
    st = build_inputs(args.entries, args.shards)
    results = {}
    if args.set in ("components", "all"):
        probe_components(st, args.entries, args.iters, results)
    if args.set in ("variants", "all"):
        probe_variants(st, args.entries, args.iters, results)
    if args.set in ("mergenet", "all"):
        probe_mergenet(st, args.entries, args.iters, results)
    if args.set in ("pallas", "all"):
        probe_pallas_sort(st, args.entries, args.iters, results)
    print(json.dumps({k: round(v * 1e3, 2) for k, v in results.items()}))


if __name__ == "__main__":
    main()
