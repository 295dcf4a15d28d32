#!/usr/bin/env python3
"""One traced run of a chipbench cell, with two looks that the harness
does not take itself (reported, never asserted; the result line stays
the run's own and the last):

    python3 tools/chip_clock_check.py --workload <cell> --seed <n> --trace 1

- **clock check**: the program's spans are put on the recording's clock
  by one mark (``chipbench/run.py`` ``reduce_trace``). If the two clocks
  agree, every ``one_shard`` module event of the slice starts after the
  ``tpu.dispatch`` span that launched it starts, and ends before the
  matching ``tpu.readback`` span ends. Printed: the events checked, the
  least lead and tail, and the largest violation, in microseconds.
- **seconds ``reduce_trace`` takes** after the window (its ``blame`` is
  gaps x spans, and every served RPC records a span and, since PR 37, a
  child a phase), with the records that went into ``blame``: spans, and
  the phase children among them.
- **inside a served RPC** (PR 37): per method (``read``, ``write``,
  ``add_db``, ``clear_db``, the ingest RPC) the window's roots, their
  mean, the count and mean of every phase on them (``hop_in``, ``exec``,
  ``hop_out``, ``reply``; a write's ``parse``, ``commit``, ``ack_wait``;
  the admin plane's ``db.*``: the roots' ``<phase>_ms`` annotations), of
  ``exec_ms - exec_cpu_ms`` (the pool thread held the request and did
  not run: GIL wait, locks, blocking IO), and the share of the roots'
  time that the phases of the root's own level cover (``hop_in + exec +
  hop_out + ack_wait + reply``; the others lie inside ``exec``). This
  look needs no recording: it is printed with ``--trace 0`` too.
- **which host codec ran**: the process's ``codec.native_files`` and
  ``codec.python_files`` counters (set-up and window; ``tpu/format.py``),
  and how many point reads found their block in the block cache.
- **which value path the device compaction took**: the process's
  ``compact.value_path.ride`` / ``compact.value_path.index`` counters
  (shards launched with their values riding the sorts / moved once by
  the resolved order; ``tpu/compaction_service.py``).
- **what the group commit's linger did**: the process's
  ``compact.linger.joined`` / ``.timeouts`` / ``.ms`` counters (siblings
  that joined a leader's batch while it waited for them, lingers that
  ran into their bound, milliseconds lingered in all;
  ``admin/ingest_pipeline.py``) beside the window's
  ``admin.compact.linger`` spans: how many, their mean and their longest.
  This look needs no recording: it is printed with ``--trace 0`` too.
- **who carried the index path's values across the seam**: the
  process's ``seam.values.prestaged`` / ``.restaged`` counters (shards
  whose values the pool thread that decoded them had put on the device
  in the launch's own capacity bucket / whose buffer was of another
  bucket, so that the leader padded and put them again;
  ``tpu/compaction_service.py``) beside the window's per-shard
  ``tpu.h2d.values`` / ``tpu.readback.values`` spans and the leader's
  ``tpu.h2d`` / ``tpu.readback``: how many and their mean; and how they
  crossed: ``compact.value_move.stream`` / ``.rows`` (index-path shards
  whose values moved as one word stream / as zero-tailed rows) and
  ``.overrun`` (places whose values would not fit the stream and kept
  their rows), beside the places the window's ``tpu.compact_stream``
  spans launched by their ``value_move`` annotation.
  ``--trace 0`` too.
- **which shards were cut by key range**: the process's
  ``compact.range_cut.shards`` / ``.places`` counters (shards of more
  rows than one place of the served door's launch holds, and the places
  they were cut into; ``tpu/compaction_service.py``) beside the window's
  ``tpu.range_cut`` spans (how many, their mean) and the places and
  whole shards its ``tpu.compact_stream`` spans launched (``shards``,
  ``dbs``). ``--trace 0`` too.
- **which pass a write batch took into the memtable**: the process's
  ``write.apply.bulk`` / ``write.apply.indexed`` counters (arrived
  frames of one stride, read column-wise off a view of that stride /
  arrived frames of any other shape, read column-wise off one index of
  their op headers: ``storage/records.py``, ``storage/engine.py``)
  beside the process's served ``write`` RPCs (``rpc.write.success``) and
  the window's ``rpc.server.write`` roots (how many, their mean). In a
  cell of keys of one length every batch the client sends is of one
  stride, so ``bulk`` equals the served ``write`` RPCs and ``indexed``
  is 0; a batch of counter NAMES has several strides, so
  ``counter_names_64x15k.refresh`` reads ``indexed`` = every frame and
  ``bulk`` 0 (a BUILT batch, as the admin plane's own metadata puts, is
  no frame and counts under neither). ``--trace 0`` too.
- **how a served write's ack was met**: the process's
  ``write.ack.at_commit`` / ``write.ack.awaited`` counters (acks met
  before the executor half returned, which take no trip through the
  loop / acks the request waited for: a follower's ack, a timeout, a
  fence; ``replication/replicated_db.py`` ``handle_write_request``)
  beside ``rpc.write.success``. Every cell runs at RF 1, so ``at_commit``
  equals the served ``write`` RPCs and ``awaited`` is 0; the
  ``ack_wait`` phase of ``inside a served RPC`` reads ~0. ``--trace 0``
  too.
- **which key shape the shards had**: the process's
  ``compact.key_widths.uniform`` / ``.mixed`` counters (shards through
  the served door whose keys have one length / differ in length:
  ``tpu/compaction_service.py``) and ``flush.key_widths.mixed``
  (memtable flushes of differing key lengths: ``storage/engine.py``)
  beside the places the window's ``tpu.compact_stream`` spans launched
  by their ``key_widths`` annotation and the longest key any of them
  carried (``key_bytes_max``). ``--trace 0`` too.
- **which key lanes and value shape the shards had**: the process's
  ``compact.key_lanes.wide`` (shards through the served door with a key
  over 24 bytes: twelve key words a row), ``compact.value_widths.uniform``
  / ``.mixed`` (shards whose live values share one width / differ in
  width) and ``flush.value_widths.mixed`` (memtable flushes of values of
  differing width) beside the places the window's ``tpu.compact_stream``
  spans launched by their ``key_lanes`` (6 / 12) and ``value_widths``
  annotations, the words of key they use (``key_words``), and their
  real value bytes against the bytes at the launch's value width
  (``value_bytes`` / ``value_bytes_padded``: what a ragged value move
  would save), and the widest value the window's ``tpu.lanes.decode`` /
  ``tpu.planar.write`` / ``flush.encode`` spans carried
  (``value_bytes_max``). 0 / ``uniform`` in the four cells of keys of at
  most 24 bytes and one value width; every shard in
  ``mixgraph_64x20k.refresh``. ``--trace 0`` too.
- **what the engine read for a scan**: the process's ``scan.calls`` /
  ``.rows`` / ``.blocks`` counters (``DB.scan``: the data plane's
  ``scan``, ``storage/engine.py``) beside the window's ``storage.scan``
  spans (how many, their mean, blocks and files a scan by their
  annotations), printed after the served RPCs' phases, where the read
  root's ``exec`` holds them. 0 and none in the five cells whose readers
  only get; in ``ycsb_e_32x20k.refresh`` one span a served scan and 1
  to 3 blocks a file. ``--trace 0`` too.

Arguments are ``chipbench/run.py``'s own.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def clock_check(recording: dict, slice_ns, host_spans: list) -> dict:
    """``host_spans``: ``(name, start_ns, end_ns, span_id, parent_id)`` on
    the recording's clock. A launch group's ``tpu.dispatch`` and
    ``tpu.readback`` are the k-th of each under one ``tpu.compact_stream``
    (or ``tpu.compact_batch``) span; a module event belongs to the pair
    whose dispatch ends nearest to the event's start."""
    from chipbench import trace_reduce as tr
    from rocksplicator_tpu.tpu.compaction_service import PIPELINE_PROGRAM

    lo, hi = slice_ns
    events = [(start, start + dur)
              for plane in recording["planes"]
              if plane["name"].startswith(tr.DEVICE_PLANE)
              for line in plane["lines"] if line["name"] == tr.MODULES_LINE
              for name, start, dur in line["events"]
              if PIPELINE_PROGRAM in name and lo <= start < hi]
    by_parent: dict = {}
    for name, start, end, _sid, parent in sorted(
            host_spans, key=lambda s: s[1]):
        if name in ("tpu.dispatch", "tpu.readback"):
            by_parent.setdefault(parent, {}).setdefault(name, []).append(
                (start, end))
    pairs = [(d, r) for group in by_parent.values()
             for d, r in zip(group.get("tpu.dispatch", ()),
                             group.get("tpu.readback", ()))]
    out = {"module_events": len(events), "launch_pairs": len(pairs)}
    if not events or not pairs:
        return out
    leads, tails, rows = [], [], []
    for e_start, e_end in events:
        dispatch, readback = min(pairs, key=lambda p: abs(p[0][1] - e_start))
        leads.append((e_start - dispatch[0]) / 1e3)
        tails.append((readback[1] - e_end) / 1e3)
        # one row an event, ms: lead, the dispatch span, the module
        # event, the readback span, tail
        rows.append([round(x / 1e6, 3) for x in (
            e_start - dispatch[0], dispatch[1] - dispatch[0],
            e_end - e_start, readback[1] - readback[0],
            readback[1] - e_end)])
    out.update(
        events_ms=rows,
        least_lead_us=round(min(leads), 1),
        median_lead_us=round(sorted(leads)[len(leads) // 2], 1),
        least_tail_us=round(min(tails), 1),
        largest_violation_us=round(max(0.0, -min(leads), -min(tails)), 1))
    return out


PHASED_METHODS = ("read", "write", "add_db", "clear_db",
                  "add_s3_sst_files_to_db")
TOP_PHASES = ("hop_in", "exec", "hop_out", "ack_wait", "reply")


def phase_table(spans: list) -> dict:
    """Per method: the window's roots, their mean, ``{phase: [count,
    mean ms]}`` off the roots' ``<phase>_ms`` annotations, the mean
    ``exec_ms - exec_cpu_ms`` and the share of the roots' time that the
    phases of the root's own level cover."""
    out = {}
    for method in PHASED_METHODS:
        roots = [s for s in spans if s["name"] == "rpc.server." + method]
        if not roots:
            continue
        by_phase: dict = {}
        off_cpu = []
        for s in roots:
            ann = s["annotations"]
            for key, ms in ann.items():
                if key.endswith("_ms") and key not in ("queue_wait_ms",
                                                       "exec_cpu_ms"):
                    by_phase.setdefault(key[:-3], []).append(ms)
            if "exec_ms" in ann and "exec_cpu_ms" in ann:
                off_cpu.append(max(0.0, ann["exec_ms"] - ann["exec_cpu_ms"]))
        total = sum(s["duration_ms"] for s in roots)
        covered = sum(sum(by_phase.get(p, ())) for p in TOP_PHASES)
        out[method] = {
            "roots": len(roots), "mean_ms": round(total / len(roots), 3),
            "phases_count_mean_ms": {
                p: [len(ms), round(sum(ms) / len(ms), 3)]
                for p, ms in sorted(by_phase.items(),
                                    key=lambda kv: -sum(kv[1]))},
            "exec_off_cpu_mean_ms": round(
                sum(off_cpu) / len(off_cpu), 3) if off_cpu else None,
            "covered_pct": round(100.0 * covered / total, 2) if total
            else None}
    return out


def main(argv=None) -> int:
    from chipbench import run as harness
    from chipbench import trace_reduce as tr

    real_reduce, real_reduce_trace = tr.reduce, harness.reduce_trace

    def reduce(recording, slice_ns, host_spans=(), top=10):
        host = list(host_spans)
        harness.say("clock check: " + json.dumps(
            clock_check(recording, slice_ns, host)))
        return real_reduce(recording, slice_ns, host, top)

    def reduce_trace(trace_dir, marks, spans, out_dir=None):
        t = time.monotonic()
        out = real_reduce_trace(trace_dir, marks, spans, out_dir)
        harness.say(f"reduce_trace: {time.monotonic() - t:.2f} s over "
                    f"{len(spans)} records into blame, "
                    f"{sum(1 for s in spans if ':' in s['name'])} of them "
                    f"phases of a root")
        return out

    real_run_cell = harness.run_cell

    def run_cell(*args):
        from rocksplicator_tpu.utils.stats import Stats

        out = real_run_cell(*args)
        harness.say("host codecs, files of the whole process: " + json.dumps(
            {k: Stats.get().get_counter(k)
             for k in ("codec.native_files", "codec.python_files",
                       "storage.block_cache.hit",
                       "storage.block_cache.miss")}))
        harness.say("shards by value path, whole process: " + json.dumps(
            {k: Stats.get().get_counter(k)
             for k in ("compact.value_path.ride",
                       "compact.value_path.index")}))
        return out

    real_read_metrics = harness.read_metrics

    def read_metrics(bench, group, package, cell, run):
        from chipbench.reduce import span_ms
        from rocksplicator_tpu.utils.stats import Stats

        ms = [s["duration_ms"] for s in run.spans
              if s["name"] == "admin.compact.linger"]
        harness.say("group commit's linger: " + json.dumps(dict(
            {"window_spans": len(ms),
             "window_mean_ms": round(sum(ms) / len(ms), 2) if ms else None,
             "window_max_ms": round(max(ms), 2) if ms else None},
            **{"process_" + k: Stats.get().get_counter("compact.linger." + k)
               for k in ("joined", "timeouts", "ms")})))
        seam = {}
        for name in ("tpu.h2d.values", "tpu.readback.values",
                     "tpu.h2d", "tpu.readback"):
            ms = span_ms(run, name)
            seam[name] = [len(ms), round(sum(ms) / len(ms), 2) if ms else None]
        streams = [s["annotations"] for s in run.spans
                   if s["name"] == "tpu.compact_stream"]
        harness.say("values across the seam: " + json.dumps(dict(
            {"window_spans_count_mean_ms": seam},
            **{"window_launched_move_" + w: sum(
                int(a["shards"]) for a in streams
                if a.get("value_move") == w) for w in ("stream", "rows")},
            **{"process_" + k: Stats.get().get_counter("seam.values." + k)
               for k in ("prestaged", "restaged")},
            **{"process_value_move_" + k: Stats.get().get_counter(
                "compact.value_move." + k)
               for k in ("stream", "rows", "overrun")})))
        ms = span_ms(run, "tpu.range_cut")
        harness.say("shards cut by key range: " + json.dumps(dict(
            {"window_spans": len(ms),
             "window_mean_ms": round(sum(ms) / len(ms), 2) if ms else None,
             "window_launched_places": sum(
                 int(a["shards"]) for a in streams),
             "window_launched_dbs": sum(
                 int(a.get("dbs", a["shards"])) for a in streams)},
            **{"process_" + k: Stats.get().get_counter(
                "compact.range_cut." + k) for k in ("shards", "places")})))
        ms = span_ms(run, "rpc.server.write")
        harness.say("write batches by pass: " + json.dumps(dict(
            {"window_spans": len(ms),
             "window_mean_ms": round(sum(ms) / len(ms), 2) if ms else None},
            **{"process_" + k: Stats.get().get_counter(k)
               for k in ("write.apply.bulk", "write.apply.indexed",
                         "rpc.write.success")})))
        harness.say("write acks by how they were met: " + json.dumps(
            {"process_" + k: Stats.get().get_counter(k)
             for k in ("write.ack.at_commit", "write.ack.awaited",
                       "rpc.write.success")}))
        harness.say("shards by key shape: " + json.dumps(dict(
            {"window_launched_" + w: sum(
                int(a["shards"]) for a in streams
                if a.get("key_widths") == w) for w in ("uniform", "mixed")},
            window_key_bytes_max=max(
                (int(a.get("key_bytes_max", 0)) for a in streams),
                default=0),
            **{"process_" + k: Stats.get().get_counter(k)
               for k in ("compact.key_widths.uniform",
                         "compact.key_widths.mixed",
                         "flush.key_widths.mixed")})))
        vmax = {name: max((int(sp["annotations"].get("value_bytes_max", 0))
                          for sp in run.spans if sp["name"] == name),
                         default=0)
                for name in ("tpu.lanes.decode", "tpu.planar.write",
                             "flush.encode")}
        harness.say("shards by key lanes and value shape: " + json.dumps(dict(
            {"window_launched_key_lanes_" + str(w): sum(
                int(a["shards"]) for a in streams
                if a.get("key_lanes") == w) for w in (6, 12)},
            **{"window_launched_values_" + w: sum(
                int(a["shards"]) for a in streams
                if a.get("value_widths") == w) for w in ("uniform", "mixed")},
            window_key_words=sorted({a.get("key_words") for a in streams
                                     if "key_words" in a}),
            window_value_bytes=sum(int(a.get("value_bytes", 0))
                                   for a in streams),
            window_value_bytes_padded=sum(
                int(a.get("value_bytes_padded", 0)) for a in streams),
            window_value_bytes_max=vmax,
            **{"process_" + k: Stats.get().get_counter(k)
               for k in ("compact.key_lanes.wide",
                         "compact.value_widths.uniform",
                         "compact.value_widths.mixed",
                         "flush.value_widths.mixed")})))
        harness.say("inside a served RPC, by method: " + json.dumps(
            phase_table(run.spans)))
        scans = [s for s in run.spans if s["name"] == "storage.scan"]
        per = {k: [s["annotations"][k] for s in scans
                   if k in s["annotations"]] for k in ("blocks", "files")}
        harness.say("scans in the engine: " + json.dumps(dict(
            {"window_spans": len(scans),
             "window_mean_ms": round(sum(s["duration_ms"] for s in scans)
                                     / len(scans), 3) if scans else None},
            **{f"window_{k}_per_scan": round(sum(v) / len(v), 3)
               if v else None for k, v in per.items()},
            **{"process_" + k: Stats.get().get_counter(k)
               for k in ("scan.calls", "scan.rows", "scan.blocks")})))
        return real_read_metrics(bench, group, package, cell, run)

    tr.reduce, harness.reduce_trace = reduce, reduce_trace
    harness.run_cell, harness.read_metrics = run_cell, read_metrics
    try:
        return harness.main(argv)
    finally:
        tr.reduce, harness.reduce_trace = real_reduce, real_reduce_trace
        harness.run_cell = real_run_cell
        harness.read_metrics = real_read_metrics


if __name__ == "__main__":
    sys.exit(main())
