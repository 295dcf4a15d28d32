"""The system under test, as one process serves it: an
``AdminHandler(tpu_compaction=True)`` behind an ``RpcServer`` plus its
``Replicator``. The client side that drives both over the wire is the
traffic driver's (``drivers/``).

Copied from ``chip_smoke.py`` (``Cluster``, ``build_bulk_sst``,
``CompileLog``), where every call here first ran on the chip. From the
program this file takes the served entry points and nothing else.
"""

from __future__ import annotations

import logging
import os

from . import workload as wl



class CompileLog:
    """Every XLA compilation of the process, from jax's own monitoring
    events: name + seconds per program, persistent-cache hits/misses, and
    (from the compiler's debug log) the argument shapes of each."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.programs = []  # (fun_name, seconds, arg shapes)
        self.hits = self.misses = 0
        self._shapes = {}   # fun_name -> arg shapes of its pending compile
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        pxla = logging.getLogger("jax._src.interpreters.pxla")
        pxla.setLevel(logging.DEBUG)
        pxla.propagate = False  # debug records stay out of stderr
        handler = logging.Handler(logging.DEBUG)
        handler.emit = self._record
        pxla.addHandler(handler)

    def _record(self, record) -> None:
        # logged by the compiler just before the program's compile event
        if str(record.msg).startswith("Compiling %s with global shapes"):
            self._shapes[str(record.args[0])] = str(record.args[1])

    def _duration(self, event, secs, **kw) -> None:
        if event == self._BACKEND:
            name = kw.get("fun_name", "?")
            self.programs.append(
                (name, float(secs), self._shapes.pop(name, "")))

    def _event(self, event, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def summary(self, since: int = 0, top: int = 4) -> dict:
        progs = self.programs[since:]
        return {
            "compilations": len(progs),
            "compile_seconds": round(sum(p[1] for p in progs), 3),
            "slowest": [
                {"program": n, "seconds": round(s, 3), "args": shapes[:240]}
                for n, s, shapes in sorted(progs, key=lambda p: -p[1])[:top]],
        }


def options_generator(options: dict):
    """The configuration's ``options`` group as the handler's per-segment
    ``DBOptions`` generator."""
    from rocksplicator_tpu.storage import DBOptions, UInt64AddOperator

    operators = {"uint64add": UInt64AddOperator, None: lambda: None}
    if options["merge_operator"] not in operators:
        raise ValueError(f"merge_operator {options['merge_operator']!r}")

    def generate(segment: str) -> DBOptions:
        return DBOptions(
            merge_operator=operators[options["merge_operator"]](),
            wal_ttl_seconds=float(options["wal_ttl_seconds"]),
            bits_per_key=int(options["bits_per_key"]),
            background_compaction=bool(options["background_compaction"]),
        )

    return generate


class Cluster:
    """One AdminHandler node behind an RpcServer: the system under test.
    ``in_flight`` is the widest fan-out of ingest RPCs the traffic sends:
    the handler's ``max_sst_loading_concurrency`` admits that many. The
    client side lives with the traffic driver."""

    def __init__(self, root: str, options: dict, in_flight: int):
        from rocksplicator_tpu.admin import AdminHandler
        from rocksplicator_tpu.replication import Replicator
        from rocksplicator_tpu.rpc import RpcServer

        self.replicator = Replicator(port=0)
        self.handler = AdminHandler(
            os.path.join(root, "dbs"), self.replicator,
            options_generator=options_generator(options),
            executor_threads=in_flight + 4,
            max_sst_loading_concurrency=in_flight,
            tpu_compaction=True)
        self.server = RpcServer(port=0, ioloop=self.replicator.ioloop)
        self.server.add_handler(self.handler)
        self.server.start()

    def launches(self) -> int:
        """Post-load compaction dispatches so far (program counter)."""
        return len(self.handler._batch_compactor.batch_sizes)

    def close(self) -> None:
        self.server.stop()
        self.handler.close()
        self.replicator.stop()


def build_bulk_sst(store, tmp: str, seed: int, slot: int, rows: int,
                   prefix: str) -> int:
    """The slot's bulk file, written with the plain row-format writer
    (not the array sink under test) and uploaded to ``store`` under
    ``prefix``. Returns its size in bytes."""
    from rocksplicator_tpu.storage import OpType
    from rocksplicator_tpu.storage.sst import SSTWriter

    path = os.path.join(tmp, f"slot{slot}.tsst")
    w = SSTWriter(path)
    for key, value in wl.bulk_rows(seed, slot, rows):
        w.add(key, 0, OpType.PUT, wl.encode_value(value))
    w.finish()
    size = os.path.getsize(path)
    store.put_object(path, f"{prefix}/bulk.tsst")
    os.remove(path)
    return size
