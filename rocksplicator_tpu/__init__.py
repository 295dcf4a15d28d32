"""rocksplicator_tpu — a TPU-native framework for building large-scale sharded,
replicated, LSM-backed stateful services.

Re-imagines pinterest/rocksplicator (C++/Java/RocksDB/Helix) as a TPU-first
system:

- ``storage``     : LSM storage engine (WAL + memtable + TSST files) with a
                    native C++ hot path (reference L0: vendored rocksdb).
- ``replication`` : per-shard leader/follower chained replication with
                    async / semi-sync / sync ack modes (reference
                    rocksdb_replicator/).
- ``admin``       : admin data plane — backup/restore/ingest/compact RPCs
                    (reference rocksdb_admin/).
- ``cluster``     : native control plane — coordination service, state
                    machines, shard-map generation (reference
                    cluster_management/ Java+Helix, rebuilt without a JVM).
- ``tpu``         : the new part — compaction / SST bulk-ingest hot path
                    offloaded to TPU as JAX array programs (k-way merge,
                    bloom construction, block encoding), sharded over a
                    ``jax.sharding.Mesh``.
- ``rpc``         : typed async RPC with zero-copy binary payloads
                    (reference: fbthrift header protocol).
- ``utils``       : stats, flags, timers, watchers, rate limiters, object
                    store (reference common/).
- ``models`` / ``ops`` / ``parallel``: the JAX-facing surface — the
  compaction "model", its kernels, and mesh-sharding helpers.
"""

__version__ = "0.1.0"

# Arm the lock-order watchdog from the environment BEFORE any package
# module constructs a lock (module-level locks are created at their
# module's import, which necessarily follows this one). Zero-cost when
# RSTPU_LOCKWATCH is unset: nothing is imported beyond the tiny module
# and nothing is patched. Chaos-harness child processes inherit the env
# and arm themselves through this same line.
import os as _os

if _os.environ.get("RSTPU_LOCKWATCH"):
    from .testing import lockwatch as _lockwatch

    _lockwatch.maybe_install()
