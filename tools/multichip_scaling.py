#!/usr/bin/env python
"""Fused-Pallas full-shape evidence: multichip scaling series.

ROADMAP's open item asks for ``pallas_fused`` compile+execute evidence
beyond the bounded dryrun shape. This tool runs the SAME 8-device
sharded compaction step as ``__graft_entry__.dryrun_multichip`` (2D
shard×block mesh, all_gather + psum collectives, full production
pipeline: merge-resolve + bloom + planar encode/checksums) over a
scaling series of entries-per-block, recording per shape:

- ``trace_s`` / ``compile_s`` — AOT ``jit.lower()`` / ``.compile()``
  wall times (the compile-time story the ROADMAP item asks for);
- ``execute_s`` — one post-compile dispatch, blocked to completion;
- ``merged_entries`` + an output content hash (cross-shape sanity: the
  pipeline really ran, outputs are deterministic).

Off-chip the mesh is 8 virtual CPU devices and Pallas runs in interpret
mode, so EXECUTE times scale badly by design — the artifact's claim is
"the fused kernel traces and runs correctly at these shapes under the
collectives" on the CPU, nothing about the chip: the v5e's compiler
refuses the fused kernel today (PERF.md "Chip status"), and these
compile seconds are XLA:CPU's.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/multichip_scaling.py --entries 2048,8192,32768 \
        --out MULTICHIP_r02.json
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def run_shape(n_devices: int, backend: str, entries: int) -> dict:
    import jax
    import numpy as np

    from rocksplicator_tpu.models import CompactionModel
    from rocksplicator_tpu.parallel.mesh import (
        make_mesh,
        make_sharded_inputs,
        shard_inputs_on_mesh,
        sharded_compaction_step,
    )

    mesh = make_mesh(n_devices)
    model = CompactionModel(
        capacity=entries, emit_planar=True, sort_backend=backend)
    step = sharded_compaction_step(mesh, model)
    arrays = make_sharded_inputs(
        mesh, shards_per_device=2, entries_per_block=entries, model=model)
    arrays = shard_inputs_on_mesh(mesh, arrays)
    args = (
        arrays["key_words_be"], arrays["key_len"],
        arrays["seq_hi"], arrays["seq_lo"], arrays["vtype"],
        arrays["val_words"], arrays["val_len"], arrays["valid"],
    )
    t0 = time.perf_counter()
    lowered = step.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    final, bloom, counts, global_count, needs_fallback = compiled(*args)
    jax.block_until_ready(global_count)
    t3 = time.perf_counter()

    counts_np = np.asarray(counts).reshape(-1)
    gc = int(np.asarray(global_count).reshape(-1)[0])
    assert gc > 0 and gc == int(counts_np.sum()), (gc, counts_np)
    assert int(np.asarray(needs_fallback).reshape(-1)[0]) == 0
    h = hashlib.sha256()
    fin = {k: np.asarray(v) for k, v in final.items()}
    fin = {k: (v[:, 0] if v.ndim > 1 and v.shape[1] == 1 else v)
           for k, v in fin.items()}
    for s in range(counts_np.shape[0]):
        c = int(counts_np[s])
        for name in ("key_words_be", "key_len", "seq_hi", "seq_lo",
                     "vtype", "val_words", "val_len"):
            h.update(np.ascontiguousarray(fin[name][s][:c]).tobytes())
    row = {
        "backend": backend,
        "entries_per_block": entries,
        "devices": n_devices,
        "mesh": dict(mesh.shape),
        "shards": int(counts_np.shape[0]),
        "input_entries": int(counts_np.shape[0]) * entries,
        "merged_entries": gc,
        "trace_s": round(t1 - t0, 3),
        "compile_s": round(t2 - t1, 3),
        "execute_s": round(t3 - t2, 3),
        "output_sha256": h.hexdigest()[:16],
    }
    log(f"  {backend}@{entries}: trace {row['trace_s']}s, "
        f"compile {row['compile_s']}s, execute {row['execute_s']}s, "
        f"merged {gc}")
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--entries", default="2048,8192,32768")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--backends", default="pallas_fused")
    ap.add_argument("--out", default="MULTICHIP_r02.json")
    args = ap.parse_args(argv)

    import jax

    shapes = [int(s) for s in args.entries.split(",") if s.strip()]
    backends = [b.strip() for b in args.backends.split(",") if b.strip()]
    result = {
        "series": "pallas_fused_scaling",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "jax": jax.__version__,
        "platform": jax.devices()[0].platform,
        "interpret_mode": jax.devices()[0].platform != "tpu",
        "rows": [],
    }
    for backend in backends:
        for entries in shapes:
            log(f"multichip_scaling: {backend} @ {entries} entries/block")
            result["rows"].append(
                run_shape(args.devices, backend, entries))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(json.dumps({
        "compile_s": {
            f"{r['backend']}@{r['entries_per_block']}": r["compile_s"]
            for r in result["rows"]},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
