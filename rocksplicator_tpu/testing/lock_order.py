"""Canonical lock-acquisition order — GENERATED, do not edit.

Regenerate with:
  python -m tools.rstpu_check --emit-lock-order \
      > rocksplicator_tpu/testing/lock_order.py
Verified fresh by `make check` (--check-lock-order).

ORDER is the transitive closure of the static
acquired-while-holding graph (tools/rstpu_check.py pass 1),
keyed by lock construction site: (A, B) present means A is
canonically acquired before B, so a live acquisition of A while
holding B is a violation. RANKS names each known lock and gives
a topological rank for humans reading reports; pairs the static
graph never relates are constrained only by the lockwatch
runtime's dynamic cycle detection.
"""

# construction site (repo-relative file:line) -> (name, rank)
RANKS = {
    "rocksplicator_tpu/replication/ack_window.py:127": ('AckWindow._cond', 0),
    "rocksplicator_tpu/admin/handler.py:182": ('AdminHandler._db_admin_lock', 1),
    "rocksplicator_tpu/admin/ingest_pipeline.py:157": ('BatchCompactor._lock', 2),
    "rocksplicator_tpu/storage/sst.py:99": ('BlockCache._instance_lock', 3),
    "rocksplicator_tpu/storage/sst.py:103": ('BlockCache._lock', 4),
    "rocksplicator_tpu/kafka/network.py:91": ('BrokerHandler._log_lock', 5),
    "rocksplicator_tpu/admin/cdc.py:103": ('CdcAdminHandler._lock', 6),
    "rocksplicator_tpu/admin/cdc.py:42": ('CdcDbWrapper._lock', 7),
    "rocksplicator_tpu/storage/stream_merge.py:127": ('CompactionMemoryBudget._instance_lock', 8),
    "rocksplicator_tpu/storage/stream_merge.py:131": ('CompactionMemoryBudget._lock', 9),
    "rocksplicator_tpu/utils/rate_limiter.py:25": ('ConcurrentRateLimiter._lock', 10),
    "rocksplicator_tpu/cluster/coordinator.py:303": ('CoordinatorServer._snapshot_mutex', 11),
    "rocksplicator_tpu/storage/engine.py:269": ('DB._compaction_mutex', 12),
    "rocksplicator_tpu/utils/dbconfig.py:48": ('DBConfigManager._instance_lock', 13),
    "rocksplicator_tpu/cluster/publishers.py:69": ('DedupPublisher._lock', 14),
    "rocksplicator_tpu/utils/concurrent_map.py:22": ('FastReadMap._write_lock', 15),
    "rocksplicator_tpu/utils/file_watcher.py:44": ('FileWatcher._lock', 16),
    "rocksplicator_tpu/utils/flags.py:34": ('FlagRegistry._lock', 17),
    "rocksplicator_tpu/utils/graceful_shutdown.py:30": ('GracefulShutdownHandler._lock', 18),
    "rocksplicator_tpu/utils/hot_key_detector.py:27": ('HotKeyDetector._lock', 19),
    "rocksplicator_tpu/admin/ingest_pipeline.py:55": ('IngestGate._lock', 20),
    "rocksplicator_tpu/storage/compaction_scheduler.py:118": ('IoBudget._fg_cv', 21),
    "rocksplicator_tpu/storage/compaction_scheduler.py:117": ('IoBudget._fg_lock', 22),
    "rocksplicator_tpu/rpc/ioloop.py:37": ('IoLoop._default_lock', 23),
    "rocksplicator_tpu/replication/iter_cache.py:41": ('IterCache._lock', 24),
    "rocksplicator_tpu/kafka/watcher.py:165": ('KafkaBrokerFileWatcher._lock', 25),
    "rocksplicator_tpu/kafka/watcher.py:191": ('KafkaBrokerFileWatcherManager._lock', 26),
    "rocksplicator_tpu/kafka/wire.py:573": ('KafkaWireBroker._lock', 27),
    "rocksplicator_tpu/kafka/wire.py:861": ('KafkaWireConsumer._lock', 28),
    "rocksplicator_tpu/kafka/wire.py:1090": ('KafkaWireProducer._lock', 29),
    "rocksplicator_tpu/replication/ack_window.py:57": ('MaxNumberBox._cond', 30),
    "rocksplicator_tpu/storage/stream_merge.py:176": ('MemTracker._lock', 31),
    "rocksplicator_tpu/admin/cdc.py:79": ('MemoryPublisher._lock', 32),
    "rocksplicator_tpu/kafka/broker.py:49": ('MockKafkaCluster._cond', 33),
    "rocksplicator_tpu/utils/file_watcher.py:173": ('MultiFilePoller._lock', 34),
    "rocksplicator_tpu/utils/object_lock.py:18": ('ObjectLock._guard', 35),
    "rocksplicator_tpu/cluster/participant.py:76": ('Participant._publish_lock', 36),
    "rocksplicator_tpu/replication/replicated_db.py:182": ('ReplicatedDB._ack_state_lock', 37),
    "rocksplicator_tpu/replication/replicated_db.py:159": ('ReplicatedDB._epoch_lock', 38),
    "rocksplicator_tpu/replication/replicated_db.py:188": ('ReplicatedDB._expiry_lock', 39),
    "rocksplicator_tpu/replication/replicated_db.py:279": ('ReplicatedDB._write_traces_lock', 40),
    "rocksplicator_tpu/replication/replicator.py:46": ('Replicator._instance_lock', 41),
    "rocksplicator_tpu/utils/retry_policy.py:77": ('RetryBudget._lock', 42),
    "rocksplicator_tpu/utils/s3_stub.py:48": ('S3StubServer.lock', 43),
    "rocksplicator_tpu/storage/sst.py:416": ('SSTReader._pin_lock', 44),
    "rocksplicator_tpu/observability/collector.py:54": ('SpanCollector._instance_lock', 45),
    "rocksplicator_tpu/utils/ssl_context_manager.py:57": ('SslContextManager._lock', 46),
    "rocksplicator_tpu/utils/stats.py:249": ('Stats._buffers_lock', 47),
    "rocksplicator_tpu/utils/stats.py:258": ('Stats._dump_lock', 48),
    "rocksplicator_tpu/utils/stats.py:229": ('Stats._instance_lock', 49),
    "rocksplicator_tpu/utils/status_server.py:31": ('StatusServer._instance_lock', 50),
    "rocksplicator_tpu/rpc/admission.py:115": ('TenantAdmission._instance_lock', 51),
    "rocksplicator_tpu/rpc/admission.py:125": ('TenantAdmission._lock', 52),
    "rocksplicator_tpu/rpc/admission.py:67": ('TokenBucket._lock', 53),
    "rocksplicator_tpu/tpu/compaction_service.py:82": ('TpuCompactionService._instance_lock', 54),
    "rocksplicator_tpu/storage/archive.py:63": ('WalArchiver._mutex', 55),
    "rocksplicator_tpu/testing/failpoints.py:129": ('_Site.lock', 56),
    "rocksplicator_tpu/utils/stats.py:200": ('_ThreadBuffer.lock', 57),
    "rocksplicator_tpu/kafka/broker.py:204": ('kafka.broker:_clusters_lock', 58),
    "rocksplicator_tpu/storage/native/binding.py:756": ('storage.native.binding:_native_lock', 59),
    "rocksplicator_tpu/testing/failpoints.py:161": ('testing.failpoints:_lock', 60),
    "rocksplicator_tpu/utils/objectstore.py:379": ('utils.objectstore:_store_cache_lock', 61),
    "rocksplicator_tpu/admin/db_manager.py:20": ('ApplicationDBManager._lock', 62),
    "rocksplicator_tpu/cluster/coordinator.py:296": ('CoordinatorServer._lock', 63),
    "rocksplicator_tpu/storage/engine.py:240": ('DB._lock', 64),
    "rocksplicator_tpu/storage/engine.py:276": ('DB._manifest_mutex', 65),
    "rocksplicator_tpu/utils/file_watcher.py:40": ('FileWatcher._instance_lock', 66),
    "rocksplicator_tpu/cluster/participant.py:75": ('Participant._state_lock', 67),
    "rocksplicator_tpu/utils/stats.py:236": ('Stats._lock', 68),
    "rocksplicator_tpu/storage/compaction_scheduler.py:123": ('IoBudget._lock', 69),
    "rocksplicator_tpu/storage/wal.py:69": ('WalWriter._sync_lock', 70),
}

# static partial order: (acquired-first, acquired-second)
ORDER = {
    ("rocksplicator_tpu/admin/handler.py:182", "rocksplicator_tpu/admin/db_manager.py:20"),
    ("rocksplicator_tpu/cluster/coordinator.py:303", "rocksplicator_tpu/cluster/coordinator.py:296"),
    ("rocksplicator_tpu/cluster/participant.py:76", "rocksplicator_tpu/cluster/participant.py:75"),
    ("rocksplicator_tpu/storage/engine.py:240", "rocksplicator_tpu/storage/compaction_scheduler.py:123"),
    ("rocksplicator_tpu/storage/engine.py:240", "rocksplicator_tpu/storage/wal.py:69"),
    ("rocksplicator_tpu/storage/engine.py:269", "rocksplicator_tpu/storage/compaction_scheduler.py:123"),
    ("rocksplicator_tpu/storage/engine.py:269", "rocksplicator_tpu/storage/engine.py:240"),
    ("rocksplicator_tpu/storage/engine.py:269", "rocksplicator_tpu/storage/engine.py:276"),
    ("rocksplicator_tpu/storage/engine.py:269", "rocksplicator_tpu/storage/wal.py:69"),
    ("rocksplicator_tpu/utils/dbconfig.py:48", "rocksplicator_tpu/utils/file_watcher.py:40"),
    ("rocksplicator_tpu/utils/stats.py:258", "rocksplicator_tpu/utils/stats.py:236"),
}
