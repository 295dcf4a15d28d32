#!/usr/bin/env python3
"""How values should get from input to output order in the device
compaction, measured on the chip this process owns: riding both sorts as
operands, or one row-index lane riding and the values moved once
(``ops/compaction_kernel.value_path``).

    chiprun -- python3 tools/value_path_bench.py [--sweep_rows 8192]

For ``MergeKind.NONE`` pipelines of the service (``_pipeline``: sorts,
resolve, bloom, the move), a fixed group of 8 shards:

- the sweep, at ``(8, --sweep_rows)``: for each width in ``--widths``
  (u32 words) and both paths, compile seconds and device milliseconds a
  launch (median of ``--reps``), the riding path only up to
  ``--ride_max`` words (its compile grows with the operands);
- the deployment's shape, ``(8, --rows, --words)`` on the index path:
  cold compile seconds, milliseconds a launch, the row gather alone as a
  program of its own (one shard), host-to-device and device-to-host
  seconds of one shard's padded value block.

Every program is built here with the path forced, whatever
``RIDE_MAX_VAL_WORDS`` says, so the constant can be read off the table.
Prints one JSON object last. Not a benchmark cell: it sizes a constant.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def lanes(rng, group: int, rows: int, words: int, live: float = 0.8):
    """A group of shards: ``live`` of the rows valid, keys 16 B with one
    in five repeated (so the resolve drops rows), PUTs of full width."""
    n = int(rows * live)
    keys = rng.integers(0, n * 4 // 5, (group, rows)).astype(np.uint32)
    kw = np.zeros((group, rows, 6), np.uint32)
    kw[..., 3] = keys
    kw[..., 0] = 0x73303030
    valid = np.zeros((group, rows), bool)
    valid[:, :n] = True
    seq = np.tile(np.arange(rows, dtype=np.uint32), (group, 1)) + 1
    return {
        "key_words_be": kw, "key_len": np.full((group, rows), 16, np.uint32),
        "seq_hi": np.zeros((group, rows), np.uint32), "seq_lo": seq,
        "vtype": np.ones((group, rows), np.uint32),
        "val_words": rng.integers(0, 1 << 32, (group, rows, words),
                                  dtype=np.uint32),
        "val_len": np.full((group, rows), 4 * words, np.uint32),
        "valid": valid,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sweep_rows", type=int, default=8192)
    ap.add_argument("--widths", default="2,4,8,16,32,256,1024")
    ap.add_argument("--ride_max", type=int, default=32)
    ap.add_argument("--rows", type=int, default=32768)
    ap.add_argument("--words", type=int, default=256)
    ap.add_argument("--reps", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    from rocksplicator_tpu.ops import compaction_kernel as ck
    from rocksplicator_tpu.ops.compaction_kernel import MergeKind
    from rocksplicator_tpu.storage.bloom import num_words_for
    from rocksplicator_tpu.tpu import compaction_service as cs
    from rocksplicator_tpu.tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    group = 8
    rng = np.random.default_rng(7)

    def timed(fn, dev_args):
        t = time.monotonic()
        jax.block_until_ready(fn(*dev_args))
        first = time.monotonic() - t
        reps = []
        for _ in range(args.reps):
            t = time.monotonic()
            jax.block_until_ready(fn(*dev_args))
            reps.append((time.monotonic() - t) * 1e3)
        return round(first, 2), round(statistics.median(reps), 3)

    def pipeline(path: str, rows: int, words: int):
        """The service's pipeline with the value path forced."""
        was = ck.RIDE_MAX_VAL_WORDS
        ck.RIDE_MAX_VAL_WORDS = 0 if path == "index" else 1 << 30
        try:
            svc = cs.TpuCompactionService()
            fn = svc._pipeline(MergeKind.NONE, True,
                               num_words_for(rows, 10), True, True, 4, words)
            host = lanes(rng, group, rows, words)
            put = {k: jax.device_put(v) for k, v in host.items()}
            if path == "index":
                put["val_words"] = tuple(
                    jax.device_put(host["val_words"][s])
                    for s in range(group))
            jax.block_until_ready(put)
            # the first call traces (reading the constant), then compiles
            return timed(fn, [put[name] for name in cs._GROUP_LANES])
        finally:
            ck.RIDE_MAX_VAL_WORDS = was

    out = {"device": dev.device_kind, "sweep_rows": args.sweep_rows,
           "sweep": [], "deployment": {}}
    for words in (int(w) for w in args.widths.split(",")):
        row = {"words": words}
        for path in ("ride", "index"):
            if path == "ride" and words > args.ride_max:
                continue
            first, ms = pipeline(path, args.sweep_rows, words)
            row[path] = {"first_call_s": first, "launch_ms": ms}
        out["sweep"].append(row)
        print(json.dumps(row), flush=True)

    rows, words = args.rows, args.words
    first, ms = pipeline("index", rows, words)
    block = rng.integers(0, 1 << 32, (rows, words), dtype=np.uint32)
    t = time.monotonic()
    on_dev = jax.block_until_ready(jax.device_put(block))
    h2d = time.monotonic() - t
    t = time.monotonic()
    np.asarray(on_dev)
    d2h = time.monotonic() - t
    idx = jax.device_put(rng.permutation(rows).astype(np.uint32))
    gather = jax.jit(ck.gather_value_rows)
    g_first, g_ms = timed(gather, [on_dev, idx, np.int32(rows * 5 // 8)])
    out["deployment"] = {
        "shape": [group, rows, words], "first_call_s": first,
        "launch_ms": ms, "gather_one_shard_ms": g_ms,
        "block_mb": block.nbytes / 1e6, "h2d_s": round(h2d, 4),
        "d2h_s": round(d2h, 4),
        "peak_bytes": (dev.memory_stats() or {}).get("peak_bytes_in_use")}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
