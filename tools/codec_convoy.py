#!/usr/bin/env python3
"""The host codecs alone and eight threads at once, at the shapes of
``counter_64x20k.refresh`` (host clock; no device, no jax):

    python3 tools/codec_convoy.py [--threads 8] [--reps 5]

- **source**: ``read_sst_arrays`` over a 20,000-row row-format bulk file
  (25 zlib blocks, ``global_seqno`` set) plus a 5,875-row PLANAR flush
  file (48 blocks): what ``tpu.lanes.decode`` spans;
- **sink**: ``write_sst_from_arrays(planar=True)`` of 20,250 rows in 164
  blocks of 124 (``tpu.planar.write``) and of 5,875 rows
  (``flush.encode``'s share).

Each is timed for one thread alone and for ``--threads`` threads started
together (the wall time until the last is done, as a dispatch's pool
runs them), once with the whole-file native codecs and once with the
Python block loops. A codec that serialises on the interpreter takes
``threads`` times its time alone; one that does not takes about its time
alone. One JSON line; milliseconds, the median of ``--reps``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

BULK_ROWS, FLUSH_ROWS, OUT_ROWS, BLOCK_ENTRIES = 20000, 5875, 20250, 124


def make_lanes(rows: int, seed: int) -> dict:
    """Sorted 16-byte keys, 8-byte values, 32-bit seqs: the cell's rows."""
    rng = np.random.default_rng(seed)
    keys = np.zeros((rows, 24), dtype=np.uint8)
    keys[:, :8] = np.sort(rng.integers(0, 2 ** 63, rows, dtype=np.uint64)
                          ).astype(">u8").view(np.uint8).reshape(rows, 8)
    keys[:, 8:16] = rng.integers(0, 256, (rows, 8), dtype=np.uint8)
    return {
        "key_words_be": keys.view(">u4").astype(np.uint32).reshape(rows, 6),
        "key_words_le": keys.view("<u4").reshape(rows, 6).copy(),
        "key_len": np.full(rows, 16, dtype=np.uint32),
        "seq_hi": np.zeros(rows, dtype=np.uint32),
        "seq_lo": np.arange(1, rows + 1, dtype=np.uint32),
        "vtype": np.ones(rows, dtype=np.uint32),
        "val_words": rng.integers(0, 2 ** 32, (rows, 2), dtype=np.uint64
                                  ).astype(np.uint32),
        "val_len": np.full(rows, 8, dtype=np.uint32),
    }


def convoy(fn, threads: int, reps: int) -> dict:
    """``fn(i)`` alone, and ``threads`` of it started together."""
    def once(n: int) -> float:
        gate = threading.Barrier(n + 1)
        workers = [threading.Thread(
            target=lambda i=i: (gate.wait(), fn(i))) for i in range(n)]
        for w in workers:
            w.start()
        gate.wait()
        t = time.perf_counter()
        for w in workers:
            w.join()
        return (time.perf_counter() - t) * 1000.0

    once(1)  # warm: page cache, the library, numpy's first calls
    return {"alone_ms": round(statistics.median(
                once(1) for _ in range(reps)), 2),
            f"x{threads}_wall_ms": round(statistics.median(
                once(threads) for _ in range(reps)), 2)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)

    from rocksplicator_tpu.storage.native.binding import get_native
    from rocksplicator_tpu.storage.sst import SSTReader, SSTWriter
    from rocksplicator_tpu.tpu.format import (read_sst_arrays,
                                              write_sst_from_arrays)

    lib = get_native()
    root = tempfile.mkdtemp(prefix="codec-convoy-")
    try:
        bulk = os.path.join(root, "bulk.tsst")
        w = SSTWriter(bulk)
        lanes = make_lanes(BULK_ROWS, 1)
        keys = np.ascontiguousarray(
            lanes["key_words_be"].astype(">u4")).view(np.uint8)
        for i in range(BULK_ROWS):
            w.add(keys[i, :16].tobytes(), 0, 1,
                  lanes["val_words"][i].tobytes())
        w.finish(global_seqno=7)
        flush = os.path.join(root, "flush.tsst")
        write_sst_from_arrays(
            make_lanes(FLUSH_ROWS, 2), FLUSH_ROWS, flush,
            bloom_words=np.zeros(64, dtype=np.uint32),
            block_entries=BLOCK_ENTRIES, planar=True)
        readers = [(SSTReader(bulk), SSTReader(flush))
                   for _ in range(args.threads)]
        out = make_lanes(OUT_ROWS, 3)
        bloom = np.zeros(64, dtype=np.uint32)

        def source(i: int) -> None:
            for r in readers[i]:
                assert read_sst_arrays(r) is not None

        def sink(rows: int):
            def run(i: int) -> None:
                write_sst_from_arrays(
                    out, rows, os.path.join(root, f"out{i}.tsst"),
                    bloom_words=bloom, block_entries=BLOCK_ENTRIES,
                    planar=True)
            return run

        cases = {"source_bulk_and_flush_file": source,
                 f"sink_{OUT_ROWS}_rows": sink(OUT_ROWS),
                 f"sink_{FLUSH_ROWS}_rows": sink(FLUSH_ROWS)}
        result = {"cores": os.cpu_count(), "threads": args.threads,
                  "native_file_codecs": bool(lib and lib.has_file_codecs)}
        for codec in ("native", "python"):
            if codec == "native" and not result["native_file_codecs"]:
                continue
            if lib is not None:
                lib.has_file_codecs = codec == "native"
            result[codec] = {name: convoy(fn, args.threads, args.reps)
                             for name, fn in cases.items()}
        print(json.dumps(result))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
