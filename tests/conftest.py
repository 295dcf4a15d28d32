"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding tests run
without TPU hardware (the driver separately dry-runs the multichip path).
Must set env vars before jax is first imported anywhere.
"""

import os
import sys

# Force a hermetic 8-device virtual CPU mesh: the tests never take the
# chip, and TpuCompactionBackend accepts the CPU platform only under this
# explicit setting (tpu/backend.py).
os.environ["JAX_PLATFORMS"] = "cpu"
# dryrun_multichip defaults to the 131k bench shape (driver validation);
# the in-suite mesh test runs a small shape to keep the suite fast
os.environ.setdefault("RSTPU_DRYRUN_ENTRIES", "2048")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The suite's dominant cost is jax-CPU compilation of the kernel shapes,
# identical run to run: share the repo's one persistent compile cache
# (first run pays, reruns load from disk).
from rocksplicator_tpu.tpu.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()

import pytest  # noqa: E402
from _pytest.runner import runtestprotocol  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "flaky_host: known host-noise flake under full-suite load "
        "(passes standalone); retried once so tier-1 signal stays clean",
    )


def pytest_runtest_protocol(item, nextitem):
    """Retry-once guard for @pytest.mark.flaky_host tests: the marked
    tests are timing-sensitive cluster scenarios proven host-noise-flaky
    under full-suite load (they pass standalone — CHANGES.md PR 4); one
    retry reruns setup/call/teardown from scratch, and a real regression
    still fails both attempts."""
    if item.get_closest_marker("flaky_host") is None:
        return None
    hook = item.ihook
    hook.pytest_runtest_logstart(nodeid=item.nodeid, location=item.location)
    reports = runtestprotocol(item, nextitem=nextitem, log=False)
    if any(r.failed for r in reports):
        sys.stderr.write(
            f"\nflaky_host: retrying {item.nodeid} once "
            f"(host-noise guard)\n")
        reports = runtestprotocol(item, nextitem=nextitem, log=False)
    for report in reports:
        hook.pytest_runtest_logreport(report=report)
    hook.pytest_runtest_logfinish(nodeid=item.nodeid, location=item.location)
    return True


@pytest.fixture(autouse=True)
def _fresh_singletons():
    """Reset process-wide singletons between tests."""
    from rocksplicator_tpu.observability.collector import SpanCollector
    from rocksplicator_tpu.rpc.admission import TenantAdmission
    from rocksplicator_tpu.utils.stats import Stats

    Stats.reset_for_test()
    SpanCollector.reset_for_test()
    TenantAdmission.reset_for_test()
    yield


@pytest.fixture()
def file_watcher():
    from rocksplicator_tpu.utils.file_watcher import FileWatcher

    FileWatcher.reset_for_test()
    w = FileWatcher.instance()
    yield w
    FileWatcher.reset_for_test()


def hostile_cases(rng, base: bytes, n: int, rand_max: int = 300,
                  append_max: int = 16):
    """Shared decoder-fuzz input generator: alternates pure-random
    buffers with mutations of a valid stream (truncate / single-bit
    flip / append junk). Used by the RLZ and Kafka wire fuzz tests so
    the strategy can't drift between them."""
    for i in range(n):
        if i % 2 == 0:
            yield rng.randbytes(rng.randrange(0, rand_max))
            continue
        b = bytearray(base)
        op = rng.randrange(3)
        if op == 0:
            b = b[:rng.randrange(len(b))]
        elif op == 1:
            b[rng.randrange(len(b))] ^= 1 << rng.randrange(8)
        else:
            b += rng.randbytes(rng.randrange(append_max))
        yield bytes(b)
