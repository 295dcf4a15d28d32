#!/usr/bin/env python
"""Dispatcher for the host-side benchmark modes.

    python bench.py --macro_bench ...       benchmarks/macro_bench.py
    python bench.py --flush_bench ...       benchmarks/flush_bench.py
    python bench.py --compaction_bench ...  benchmarks/compaction_bench.py

All three run on the CPU and touch no accelerator. There is no
accelerator benchmark yet (ROADMAP Queue 1 item 1 defines it): the
quickest proof that the device path runs on a chip is ``chip_smoke.py``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_MODES = {
    # serving-scale macro-bench (round 13): YCSB-style mixed workload
    # over a 3-replica cluster via router read policies
    "--macro_bench": "benchmarks.macro_bench",
    # engine microbench (round 9): flush / host-compaction / block-cache
    "--flush_bench": "benchmarks.flush_bench",
    # compaction-scheduler A/B (round 16): mixed-load engine slice with
    # the workload-adaptive scheduler interleaved on/off
    "--compaction_bench": "benchmarks.compaction_bench",
}


def main(argv) -> int:
    import importlib

    for flag, module in _MODES.items():
        if flag in argv:
            rest = [a for a in argv if a != flag]
            return importlib.import_module(module).main(rest)
    print("bench.py: no mode flag (one of " + " ".join(_MODES)
          + "); for the device path on a chip run chip_smoke.py",
          file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
