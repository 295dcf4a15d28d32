#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the chip this process owns.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads``) names a configuration and a traffic
mix; this file finds ``configs/<config>.json`` and ``traffic/<traffic>.json``
by those names, the driver by the mix's ``"driver"``
(``drivers/<driver>.py``) and every metric's reader by the metric's name
(``metrics/<name>.py`` end to end, ``layers/<name>.py`` per layer). A
later PR adds cells, mixes, drivers and metrics as files and entries.

Set-up (counted in ``setup_s``): native library, data from ``--seed``,
the node, the driver's warm-up through the window's own calls — every
program shape compiles, or is read from the persistent cache, there.
Then the window of ``--seconds``, the drain, and the read-back that
decides ``correct``. With ``--trace 1`` a slice in the middle of the
window is recorded by the profiler and reduced to the per-layer metrics.

There is no CPU fallback: without a TPU, or with fewer chips than the
cell asks for, the run exits nonzero and prints no result.
``--rehearse`` runs everything on whatever platform jax has, at the
configuration's ``rehearse`` size; it ALWAYS exits nonzero and its
numbers are no measurement. ``--control bits32`` (or ``fold32``) answers
the reads from the reference in narrower arithmetic in the program's
place (``workload.SlotModel``): ``correct`` must come out false (exit
nonzero).

The last line of standard output is the result object; everything else
is on earlier lines.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

SPAN_RING = 1 << 18  # spans the ring holds; a traced run that drops one fails
CONTROLS = ("bits32", "fold32")  # workload.SlotModel's narrower arithmetics
# the profiler's slice of a traced window: where it starts, as a share of
# the window, and its length (at most half of the window)
TRACE_START_SHARE, TRACE_SECONDS = 0.4, 4.0


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def raise_fd_limit() -> int:
    """Two versions of every slot are open at a time (some files each):
    lift the soft limit on open files to the hard one."""
    import resource

    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft != hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    return hard


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json; "
                     f"there are {[c['name'] for c in bench['workloads']]}")


def read_metrics(bench: dict, group: str, package: str, cell: str,
                 run) -> dict:
    """The metrics of ``group`` that this cell reports, each from its own
    reader; a reader that finds nothing to read leaves its metric out."""
    out = {}
    for m in bench[group]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        reader = importlib.import_module(f"chipbench.{package}.{m['name']}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def make_tracer(trace_dir: str, marks: dict):
    """The profiler's slice, as a blocking function ``(t0, seconds)`` for
    a thread beside the window: start, write the clock mark, wait, stop."""
    import jax

    from chipbench.trace_reduce import CLOCK_MARK

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # else every Python call is recorded
    options.enable_hlo_proto = False

    def tracer(t0: float, seconds: float) -> None:
        time.sleep(max(0.0, t0 + TRACE_START_SHARE * seconds
                       - time.monotonic()))
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        marks["wall_ns"] = time.time_ns()
        with jax.profiler.TraceAnnotation(CLOCK_MARK):
            time.sleep(0.001)
        time.sleep(min(TRACE_SECONDS, 0.5 * seconds))
        marks["stop_wall_ns"] = time.time_ns()
        jax.profiler.stop_trace()

    return tracer


def reduce_trace(trace_dir: str, marks: dict, spans: list, out_dir=None):
    """The recorded slice as numbers (see trace_reduce.reduce)."""
    from chipbench import trace_reduce as tr

    recording = tr.load_xplane(tr.newest_xplane(trace_dir))
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "trace_names.txt"), "w") as f:
            f.write("\n".join(tr.names(recording)) + "\n")
        with open(os.path.join(out_dir, "recording.json"), "w") as f:
            json.dump(recording, f)
    mark = tr.clock_mark_ns(recording)
    if mark is None:
        raise RuntimeError("the recording lacks the clock mark")
    offset = marks["wall_ns"] - mark  # wall clock = recording clock + offset
    slice_ns = (mark, mark + marks["stop_wall_ns"] - marks["wall_ns"])
    host = [(s["name"], s["start_ms"] * 1e6 - offset,
             (s["start_ms"] + s["duration_ms"]) * 1e6 - offset,
             s["span_id"], s["parent_id"]) for s in spans]
    return tr.reduce(recording, slice_ns, host)


def run_cell(args, bench: dict, device: dict, on_chip: bool):
    """Set-up, window, read-back. Returns (result object, ok)."""
    import jax

    from chipbench import cluster as cl
    from chipbench.reduce import Run
    from rocksplicator_tpu.observability.collector import SpanCollector
    from rocksplicator_tpu.storage.compaction import host_fallback_counts
    from rocksplicator_tpu.storage.native.binding import get_native
    from rocksplicator_tpu.tpu.compile_cache import configure_compile_cache

    cell = find_cell(bench, args.workload)
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if args.rehearse:
        config.update(config["rehearse"])
    peaks = load_json(HERE, "peaks.json")
    kind = device["kind"]
    if kind not in peaks:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} in "
                         f"peaks.json (an unknown chip is an error)")
    say(f"cell {cell['name']}: {config['slots']} slots x "
        f"{config['rows_per_slot']} rows, traffic {cell['traffic']}, seed "
        f"{args.seed}, {args.seconds} s, trace {args.trace}, device "
        f"{json.dumps(device)}")

    cache_dir = configure_compile_cache()
    say(f"compile cache: {cache_dir}; open files allowed: {raise_fd_limit()}")
    compiles = cl.CompileLog()
    if get_native() is None:
        raise SystemExit("chipbench: the native library did not build; a "
                         "run without it is not a run of this system")
    collector = SpanCollector.get()
    collector.configure(capacity=SPAN_RING)

    root = tempfile.mkdtemp(prefix="chipbench-")
    trace_dir = os.path.join(root, "trace")
    cluster, driver, marks, secs = None, None, {}, {}
    try:
        driver_mod = importlib.import_module(
            f"chipbench.drivers.{traffic['driver']}")
        cluster = cl.Cluster(root, config["options"],
                             int(traffic["in_flight"]))
        driver = driver_mod.make(cluster, root, config, traffic, args.seed,
                                 args.control)
        t = time.monotonic()
        driver.prepare()
        secs["prepare"] = time.monotonic() - t
        t = time.monotonic()
        driver.warm()
        secs["warm"] = time.monotonic() - t
        say(f"set-up: {json.dumps({k: round(v, 2) for k, v in secs.items()})}"
            f" s; xla {json.dumps(compiles.summary())}; persistent cache "
            f"hits={compiles.hits} misses={compiles.misses}")
        compiled_before = len(compiles.programs)
        launches_before = cluster.launches()

        setup_s = time.monotonic() - T_START
        tracer = make_tracer(trace_dir, marks) if args.trace else None
        bounds = driver.run_window(args.seconds, tracer)
        peak = (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        in_window = compiles.summary(since=compiled_before, top=20)
        compared_keys = driver.verify()
        launches = cluster.launches() - launches_before
        fallbacks = host_fallback_counts()
        lo_ms = bounds["wall0"] * 1000.0
        spans = [s for s in collector.snapshot()
                 if lo_ms <= s["start_ms"] < lo_ms + args.seconds * 1000.0]
        dropped = collector.dropped
    finally:
        if driver is not None:
            driver.close()
        if cluster is not None:
            cluster.close()

    try:
        run = Run(config=config, traffic=traffic, seconds=args.seconds,
                  t0=bounds["t0"], setup_s=setup_s, ops=driver.ops,
                  units=driver.units, spans=spans, peaks=peaks[kind])
        if args.trace:
            try:
                run.trace = reduce_trace(trace_dir, marks, spans,
                                         args.dump_trace)
            except ValueError as e:
                if on_chip:
                    raise
                say(f"rehearsal: no device in the recording ({e})")
    finally:
        shutil.rmtree(root, ignore_errors=True)

    streams = [s["annotations"] for s in spans
               if s["name"] == "tpu.compact_stream"]
    attempted = len(run.ops)
    failed = sum(1 for op in run.ops if not op.ok)
    acked = sum(1 for u in run.units if u["acked"] <= run.t1)
    say(f"window: {acked} units acknowledged inside it (whole units only: "
        f"{acked * int(config['rows_per_slot']) / args.seconds:.1f} rows/s), "
        f"{len(run.units)} "
        f"with the drain ({bounds['drained'] - run.t1:.2f} s); rpcs "
        + json.dumps({k: sum(1 for op in run.ops if op.kind == k)
                      for k in ("drop", "add_db", "write", "ingest", "read",
                                "read_back")})
        + f"; read back {compared_keys} keys of {driver.read_back_units} "
        f"units after the close")
    say(f"device launches: {launches} dispatches, {len(streams)} "
        f"tpu.compact_stream spans in the window, (group_size, capacity) "
        f"{sorted({(a.get('group_size'), a.get('capacity')) for a in streams})}")
    say(f"tpu.host_fallbacks: {json.dumps(fallbacks)}")
    say(f"xla compilations inside the window (should be none): "
        f"{json.dumps(in_window)}")
    totals = {}
    for s in spans:
        n, ms = totals.get(s["name"], (0, 0.0))
        totals[s["name"]] = (n + 1, ms + s["duration_ms"])
    say("span totals in the window, name: count, mean ms: " + json.dumps(
        {k: [n, round(ms / n, 2)] for k, (n, ms) in sorted(
            totals.items(), key=lambda kv: -kv[1][1])}))
    say(f"spans: {len(spans)} in the window, {dropped} dropped by the ring; "
        f"peak device bytes: {peak}")
    for line in driver.first_mismatches:
        say(line)

    compared = {
        "mismatched_answers": {"value": driver.mismatches, "limit": 0},
        "failed_rpcs": {"value": driver.rpc_failures, "limit": 0},
        "host_fallbacks": {"value": sum(fallbacks.values()), "limit": 0},
        "device_dispatches": {"value": launches, "at_least": 1},
    }
    if args.trace:
        compared["spans_dropped"] = {"value": dropped, "limit": 0}
    ok = all(c["value"] <= c["limit"] if "limit" in c
             else c["value"] >= c["at_least"] for c in compared.values())

    dev = dict(device, memory_peak_bytes=peak)
    group, package = (("per_layer", "layers") if args.trace
                      else ("end_to_end", "metrics"))
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": read_metrics(bench, group, package, cell["name"],
                                      run),
              "device": dev}
    if args.trace and run.trace is not None:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
        say("xla modules in the slice: " + json.dumps(run.trace["modules"]))
    result["window_compilations"] = in_window["compilations"]
    result["compared"] = compared
    return result, ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="run off-chip at the rehearsal size; exits nonzero")
    ap.add_argument("--control", choices=CONTROLS,
                    help="answer reads from the narrower reference; "
                         "correct must come out false")
    ap.add_argument("--dump_trace", default=None, metavar="DIR",
                    help="with --trace 1: write the recording's names and "
                         "the recording as plain data there")
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_chip = device["platform"] == "tpu" and len(devs) >= cell["chips"]
    if not on_chip and not args.rehearse:
        print(f"chipbench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s), jax found {device}; there is no CPU fallback",
              file=sys.stderr)
        return 2
    logging.basicConfig(level=logging.ERROR,
                        format="%(levelname)s %(name)s: %(message)s")
    if not on_chip:
        # the rehearsal borrows the v5e's row of peaks; nothing it prints
        # is a measurement
        device = dict(device, kind="TPU v5 lite", rehearsal_on=device["kind"])
    result, ok = run_cell(args, bench, device, on_chip)
    for name, c in result["compared"].items():
        print(f"chipbench compared {name}: {json.dumps(c)}", file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        say("REHEARSAL ONLY, not a chip run: " + json.dumps(result))
        return 3 if ok else 1
    print(json.dumps(result), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
