PY ?= python

.PHONY: check test test-fast native chip-smoke flush-bench flush-bench-smoke loadsst-bench load-sst-smoke soak-bench repl-bench-smoke transport-bench-smoke macro-bench macro-bench-smoke macro-bench-move-smoke macro-bench-sched-ab macro-bench-hot-shift macro-bench-cdc fleet-bench fleet-smoke metrics-smoke compaction-bench compaction-bench-smoke compaction-remote-bench compaction-remote-smoke stream-merge-bench stream-merge-smoke overload-bench overload-smoke chaos-smoke chaos-failover-smoke reshard-smoke rebalance-smoke cdc-smoke clean

# rstpu-check: the three-pass static suite (lock-order/blocking-under-
# lock, event-loop blocking, failpoint/span/stats registries) over
# rocksplicator_tpu/ — exits nonzero on any unbaselined finding — plus
# a freshness check of the generated canonical lock order that the
# lockwatch runtime asserts (testing/lock_order.py). Also gated in
# tier-1 via tests/test_rstpu_check.py, with broken-fixture teeth.
check:
	$(PY) -m tools.rstpu_check --check-lock-order

test:
	$(PY) -m pytest tests/ -q

# parallel across cores (pytest-xdist); per-process jax compiles also hit
# the persistent compile cache (rocksplicator_tpu/tpu/compile_cache.py)
test-fast:
	$(PY) -m pytest tests/ -q -n auto

native:
	$(MAKE) -C rocksplicator_tpu/storage/native

# the device path end to end on ONE chip, one process: bulk-load ->
# device compaction -> reads vs a dict model. Exits nonzero without a
# TPU (there is no CPU fallback); from the sandbox: chiprun -- python
# chip_smoke.py
chip-smoke:
	$(PY) chip_smoke.py

# round-9 engine microbench: flush / host-compaction / block-cache A/B
# at the PERF.md 200k-entry methodology
flush-bench:
	$(PY) bench.py --flush_bench \
		--out benchmarks/results/flush_bench.json

# fast regression smoke of the same: small memtable, parity asserted on
# every side (drain vs seed flush, array vs tuple compaction), fails
# loudly if the block cache stops hitting
flush-bench-smoke:
	$(PY) bench.py --flush_bench --keys 20000 --reps 2 \
		--cache_gets 4000 \
		--out benchmarks/results/flush_bench_smoke.json

loadsst-bench:
	$(PY) -m benchmarks.load_sst_bench --shards 16

# fast pipelined-ingest regression smoke: few small shards, cpu config
# only (no kernel compiles), fails loudly on any spot-check miss
load-sst-smoke:
	$(PY) -m benchmarks.load_sst_bench --shards 4 --keys_per_shard 2000 \
		--window 4 --configs cpu --trace \
		--out benchmarks/results/load_sst_smoke.json

soak-bench:
	$(PY) -m benchmarks.soak_bench --shards 256

# fast pipelined-replication regression smoke: few shards, few seconds,
# fails loudly if the write window stops pipelining or acked writes lose
repl-bench-smoke:
	$(PY) -m benchmarks.replication_3replica_bench --shards 8 --keys 50 \
		--write_window 64 \
		--out benchmarks/results/replication_3replica_smoke.json

# fast-path transport regression smoke: the same 3-replica bench
# briefly on the uds (vectored sendmsg, 3 processes) and loopback
# (in-process zero-copy, colocated) byte layers — fails loudly on any
# acked-write loss or missed convergence on either fast path
transport-bench-smoke:
	$(PY) -m benchmarks.replication_3replica_bench --shards 8 --keys 50 \
		--write_window 64 --transport uds \
		--out benchmarks/results/transport_smoke_uds.json
	$(PY) -m benchmarks.replication_3replica_bench --shards 8 --keys 50 \
		--write_window 64 --transport loopback \
		--out benchmarks/results/transport_smoke_loopback.json

# round-13 serving-scale macro-bench: YCSB-style mixed workload (zipfian
# keys, tunable get/put/multi_get/scan mix, open-loop Poisson arrival)
# against a 3-process 3-replica cluster via the router's read policies,
# sweeping offered throughput and reporting p50/p99 per op class, plus
# the interleaved leader_only vs follower_ok(max_lag) read-scaling A/B
macro-bench:
	$(PY) bench.py --macro_bench --shards 4 --preload_keys 2000 \
		--rates 300,600,1200,2400 --duration 5 --ab --ab_duration 6 \
		--ab_reps 3 --ab_readers 8 \
		--out benchmarks/results/macro_bench_r13.json

# sub-minute macro-bench smoke: tiny keyspace, 3-point sweep, 1-rep A/B;
# fails loudly on value mismatches, zero follower-served reads, or an
# empty sweep (the artifact shape is also asserted by tier-1 tests)
macro-bench-smoke:
	$(PY) bench.py --macro_bench --shards 2 --preload_keys 400 \
		--rates 150,300,600 --duration 2 --ab --ab_duration 2 \
		--ab_reps 1 --ab_readers 4 \
		--out benchmarks/results/macro_bench_smoke.json

# round-15 live-move macro-bench smoke (~1 min): the mixed-workload
# bench with a 4th spare node and ONE live shard move (snapshot →
# bulk-ingest → WAL-tail catch-up → paused epoch-stamped cutover) of
# shard 0's leader launched mid-phase; the artifact records get p99
# before/during/after the flip and fails loudly if the move fails,
# reads stop serving during it, or reads/writes don't resume after
macro-bench-move-smoke:
	$(PY) bench.py --macro_bench --shards 2 --preload_keys 400 \
		--rates 150 --duration 3 --move_mid_bench \
		--out benchmarks/results/macro_bench_move_smoke.json

# round-16 compaction-scheduler A/B: a mixed-load engine slice of the
# macro-bench (zipfian keys, Poisson open-loop arrivals, write-heavy
# mix accumulating real L0 debt) with the workload-adaptive scheduler
# interleaved ON vs OFF at the same offered throughput — get p99,
# write-stall ms, debt drain, and the scheduler counters per arm
compaction-bench:
	$(PY) bench.py --compaction_bench --keys 30000 --rate 2100 \
		--duration 10 --reps 3 --memtable_kb 32 --target_file_kb 64 \
		--level_base_kb 128 --settle 2.5 --offline_keys 250000 \
		--out benchmarks/results/compaction_bench_r17.json

# sub-minute smoke of the same (tier-1 asserts the artifact shape):
# fails loudly on value mismatches, a pick-less scheduler-on phase, or
# a missing get-p99 pair
compaction-bench-smoke:
	$(PY) bench.py --compaction_bench --keys 6000 --rate 1200 \
		--duration 4 --reps 1 --memtable_kb 32 --target_file_kb 64 \
		--level_base_kb 128 --settle 1 --offline_keys 8000 \
		--min_slice_entries 4096 \
		--out benchmarks/results/compaction_bench_smoke.json

# round-18 disaggregated-compaction A/B: the SAME mixed load with the
# worker tier on vs off (interleaved), compaction merges offloaded
# through the coordinator job ledger to an in-process stateless worker.
# Gates: tier-on serving-node compaction output bytes ~0 (the merge ran
# on the worker: compaction.remote_offloaded_bytes vs .local_output_
# bytes), get p99 recorded in both arms, zero value mismatches, and a
# determinism section proving the remote-installed generation is
# byte-identical (sorted SST sha256 set + full content hash) to the
# local path's on the same input
compaction-remote-bench:
	$(PY) bench.py --compaction_bench --remote_ab --keys 20000 \
		--rate 1800 --duration 8 --reps 3 --memtable_kb 32 \
		--target_file_kb 64 --level_base_kb 128 --settle 2 \
		--out benchmarks/results/compaction_remote_r18.json

# sub-minute smoke of the same (tier-1 asserts the artifact shape) +
# the remote_install chaos tooth: a leader patched to skip the epoch
# gate must be CAUGHT installing a deposed leader's job
compaction-remote-smoke:
	$(PY) bench.py --compaction_bench --remote_ab --keys 4000 \
		--rate 900 --duration 3 --reps 1 --memtable_kb 32 \
		--target_file_kb 64 --level_base_kb 128 --settle 1 \
		--out benchmarks/results/compaction_remote_smoke.json
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --schedules 1 --seed 7 \
		--remote-every 1 \
		--break-guard remote_install --expect-violation --conv-timeout 3

# round-16 serving-SLO acceptance: the SAME 3-process macro-bench
# cluster under a write-heavy mix, whole-cluster interleaved A/B of
# RSTPU_COMPACTION_SCHED=1 vs 0 (children run churn engine options so
# compaction pressure is real), reporting get p99 + fleet write-stall
# totals per arm
macro-bench-sched-ab:
	$(PY) bench.py --macro_bench --sched_ab --shards 2 \
		--preload_keys 4000 --sched_rate 1300 --sched_duration 8 \
		--sched_reps 3 \
		--out benchmarks/results/macro_bench_sched_ab.json

# round-17 streaming bounded-memory merge A/B: one large full
# compaction (lane image many times the configured budget) timed
# through the chunked k-way streaming merge INTERLEAVED against the
# in-RAM single pass on the same runs — outputs checksummed equal per
# rep, the streamed arm's peak_bytes_materialized gated <= budget, the
# in-RAM arm's peak gated OVER it (the ceiling is proven, not assumed)
stream-merge-bench:
	$(PY) -m benchmarks.stream_merge_bench --keys 400000 --runs 3 \
		--reps 3 --budget_kb 2048 --target_file_kb 256 \
		--out benchmarks/results/stream_merge_r17.json

# sub-minute smoke of the same (tier-1 asserts the artifact shape):
# fails loudly on checksum divergence, a streamed peak over budget, an
# input too small to exceed the budget, or a chunk-seam-free stream
stream-merge-smoke:
	$(PY) -m benchmarks.stream_merge_bench --keys 30000 --runs 3 \
		--reps 1 --budget_kb 256 --target_file_kb 32 \
		--chunk_entries 2048 \
		--out benchmarks/results/stream_merge_smoke.json

# round-19 tail-armor acceptance: three interleaved A/Bs on fresh
# 3-process clusters per arm — (1) per-tenant admission with one tenant
# offering 10x its ops/s quota past the serving knee (the gate: the
# well-behaved tenants' pooled p99.9 with armor ON strictly beats OFF,
# their goodput holds, and only the abuser sheds); (2) hedged
# bounded-staleness follower reads against a server-side injected fat
# tail (gates: hedged get p99 strictly better at a <=5% hedge rate,
# zero hedges with RSTPU_HEDGE=0); (3) the unarmed-overhead guard
# (RSTPU_TAIL_ARMOR=0 vs armed-but-idle, write-path mean bounded)
overload-bench:
	$(PY) bench.py --macro_bench --overload_ab --shards 2 \
		--preload_keys 1000 --overload_quota 200 \
		--overload_good_rate 130 --overload_good_tenants 3 \
		--overload_duration 6 --overload_reps 3 \
		--hedge_read_rate 400 --overhead_rate 500 \
		--out benchmarks/results/overload_r19.json

# ~30-second failure-gated smoke of the same (small keyspace, 1 rep,
# shorter phases) in --overload_gates mechanical mode: fails loudly
# if the armor stops shedding the abuser, the killswitch leaks typed
# sheds or hedges, the hedge rate breaks its 5% budget, or any arm
# records a value mismatch. The latency-median comparisons stay on
# the full overload-bench — a 1-rep micro run's serving knee drifts
# too much run-to-run for a strict p99.9 gate to test the armor
# rather than the host.
overload-smoke:
	$(PY) bench.py --macro_bench --overload_ab --shards 2 \
		--preload_keys 400 --overload_quota 80 \
		--overload_good_rate 50 --overload_good_tenants 2 \
		--overload_duration 3 --overload_reps 1 \
		--hedge_read_rate 250 --overhead_rate 200 \
		--overload_gates mechanical \
		--out benchmarks/results/overload_smoke.json

# round-20 hot-shift rebalancer A/B (the autonomy acceptance number,
# ~4 min): mixed zipfian workload whose hot set SHIFTS shards at the
# 1/3 mark, interleaved rebalancer-ON vs OFF on fresh 4-node clusters;
# the ON arm drives the production RebalancerPolicy (EWMA + hysteresis
# + sustain) with DirectShardMove as actuator. A symmetric 3ms
# executor-occupancy read stall (repl.read.serve failpoint) makes the
# per-process serving knee rate-derived, so the A/B measures PLACEMENT
# even on a 1-core host where CPU is zero-sum across processes. Gates:
# final-window get p99 ON strictly < OFF, >=1 successful move AFTER
# the shift (re-detection), zero moves in the OFF arm, zero value
# mismatches, zero acked-write loss (every acked put read back).
macro-bench-hot-shift:
	$(PY) bench.py --macro_bench --hot_shift --shards 4 \
		--preload_keys 500 --hot_rate 520 --hot_duration 5 \
		--hot_reps 2 \
		--out benchmarks/results/macro_bench_hot_shift.json

# round-20 rebalancer chaos smoke (~45s + ~20s tooth): 3 seeded
# schedules (4 nodes / 2 shards) where placement changes are initiated
# by the POLICY loop itself — a policy-detected hot shard moved, a
# policy-detected overwhelming shard range-SPLIT into virtual children,
# and a seam-faulted tick (rebalance.decide/plan/dispatch +
# move.catchup kills, resumed from the durable ledgers) — each holding
# the SEVENTH standing invariant: leaf convergence (splits published in
# __splits__, one leader per CHILD), per-owning-range acked
# readability, parent retired everywhere, bounded convergence. Then the
# split_cutover tooth: a splitter patched to flip on "the snapshot is
# good enough" (observer tail severed, no drain) must be CAUGHT losing
# acked post-snapshot writes on the high child (--expect-violation).
rebalance-smoke:
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --rebalance \
		--schedules 3 --seed 1 \
		--out benchmarks/results/chaos_rebalance_smoke.json
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --rebalance \
		--schedules 1 --seed 7 \
		--break-guard split_cutover --expect-violation

# round-21 CDC streaming-ingest acceptance (~2 min): the 3-process
# macro-bench cluster (churn engine profile so memtable/L0 pressure is
# real) serving a mixed workload while an in-process kafka broker
# feeds every shard's leader-side IngestionWatcher; a baseline serve
# phase then the SAME serve phase with an open-loop CDC producer
# bursting records at the broker. The artifact gates: applied records
# == produced records with zero dedup-skips after drain (exactly-once
# under load), backpressure demonstrably engaging (kafka.cdc.
# paced_sleeps > 0 — gauge-driven fetch pacing, not memtable
# stacking), and produce→readable freshness p50/p99 measured by
# marker probes against a FOLLOWER (the full produce → broker →
# consume → write_many → replicate path).
macro-bench-cdc:
	$(PY) bench.py --macro_bench --cdc --shards 4 --preload_keys 2000 \
		--value_bytes 128 \
		--out benchmarks/results/macro_bench_cdc_r21.json

# round-22 fleet-density macro-bench (~5 min): 10 nodes x 100 shards
# (RF=3 on the interleaved ring — each node leads 10 shards and
# follows 20 from exactly TWO upstream peers) through the scripted
# timeline: baseline, diurnal rate curve, hot-set shift, node SIGKILL
# + restart, live drain (pause → level → promote(epoch+1) → repoint →
# demote per shard, zero acked-write loss), CDC burst (exactly-once
# drain), cooldown (full fleet convergence) — per-phase SLO gates +
# /cluster_stats snapshots in the artifact. Then the mux acceptance
# A/B at fleet shape (8 nodes x 64 shards, interleaved fresh fleets):
# with RSTPU_PULL_MUX=1 the idle replication plane must carry >= 5x
# fewer frames/sec and parked long-polls per node (the ring predicts
# ~S/N = 8x) at equal applied put throughput, zero acked-write loss,
# get p99 no worse. The A/B load window runs at a rate the host can
# absorb without saturating (8 procs + driver share the CPU budget;
# an oversubscribed window turns the p99 gate into a scheduler-noise
# lottery — the idle-window frames/parked ratios don't depend on the
# window rate at all), 3 reps so the median p99 gate isn't decided by
# one noisy rep, a longer load window for more tail samples, and the
# p99 factor at the 2x host-noise bound the other gates in this repo
# use on a 1-CPU container (the smoke uses 3x, the tier-1 test 4x;
# within-arm p99 spread here is routinely >3x between reps).
fleet-bench:
	$(PY) -m benchmarks.fleet_bench --nodes 10 --shards 100 \
		--preload_keys 100 --rate 600 --duration 5 \
		--out benchmarks/results/fleet_bench_r22.json
	$(PY) -m benchmarks.fleet_bench --ab --ab_nodes 8 --ab_shards 64 \
		--ab_reps 3 --ab_rate 150 --ab_load_sec 8 --ab_p99_factor 2 \
		--preload_keys 60 \
		--out benchmarks/results/fleet_mux_ab_r22.json

# tier-1-sized fleet smoke (~3 min): the full timeline at 4 nodes x
# 12 shards, then the mux A/B at the same shape with the factors
# relaxed to 2x (the ring predicts ~3x here; the 5x gate applies to
# the fleet-shaped run above) and the p99 gate widened for the short
# noisy windows. tests/test_fleet_bench.py runs the same harness at a
# smaller shape and asserts the artifact shapes.
fleet-smoke:
	$(PY) -m benchmarks.fleet_bench --nodes 4 --shards 12 \
		--preload_keys 40 --rate 120 --duration 2 --cdc_records 30 \
		--out benchmarks/results/fleet_smoke.json
	$(PY) -m benchmarks.fleet_bench --ab --ab_nodes 4 --ab_shards 12 \
		--preload_keys 40 --ab_reps 2 --ab_rate 150 --ab_load_sec 3 \
		--ab_idle_sec 4 --ab_frames_factor 2 --ab_parked_factor 2 \
		--ab_p99_factor 3 \
		--out benchmarks/results/fleet_smoke_mux_ab.json

# round-14 metrics-plane smoke (<10s): boots one replica in-process,
# scrapes /metrics + /cluster_stats, validates Prometheus text-format
# parseability, the presence of every registered gauge family (engine
# level/amp/debt, replication lag/ack-window, block-cache hit rate),
# and the spectator-path exact histogram merge; also run by tier-1
# (tests/test_metrics_plane.py)
metrics-smoke:
	$(PY) -m tools.metrics_smoke

# seeded chaos smoke (<60s): 20 randomized failpoint schedules against a
# 3-node cluster + the admin ingest path, every schedule checked for the
# three standing invariants (hole-free WAL prefix, zero acked-write
# loss, ingest atomicity/no-partial-meta); then the SAME seeded
# schedules re-run on the uds and loopback byte layers (failpoints arm
# identically on all three transports), the SAME deck re-run with the
# multiplexed pull sessions forced on (RSTPU_PULL_MUX=1 — both chaos
# shards ride ONE session per follower, crossing the repl.mux.serve/
# apply seams), and deliberately-broken guard runs that must be CAUGHT
# (--expect-violation): the wal_hole/meta_first durability teeth plus
# the round-22 mux_misroute tooth (the serve loop files one shard's
# updates under its sibling's section key, seqs restamped so the
# continuity guard can't reject it — the cross-shard invariants must).
# A violation prints the reproducing --seed.
# RSTPU_LOCKWATCH=1 arms the runtime lock-order watchdog in every
# process (parent + spawned replicas inherit the env): each schedule
# also asserts the canonical acquisition order from testing/
# lock_order.py and per-thread held-set discipline, corroborating the
# static rstpu-check result on the exercised paths.
chaos-smoke:
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --schedules 20 --seed 1 \
		--out benchmarks/results/chaos_smoke.json
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --schedules 3 --seed 1 \
		--transport uds \
		--out benchmarks/results/chaos_smoke_uds.json
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --schedules 3 --seed 1 \
		--transport loopback \
		--out benchmarks/results/chaos_smoke_loopback.json
	env RSTPU_LOCKWATCH=1 RSTPU_PULL_MUX=1 $(PY) -m tools.chaos_soak \
		--schedules 6 --seed 3 \
		--out benchmarks/results/chaos_smoke_mux.json
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --schedules 1 --seed 7 \
		--break-guard wal_hole --expect-violation --conv-timeout 3
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --schedules 1 --seed 7 \
		--ingest-every 1 \
		--break-guard meta_first --expect-violation --conv-timeout 10
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --schedules 1 --seed 7 \
		--break-guard mux_misroute --expect-violation --conv-timeout 3

# coordinator-backed failover chaos (~30s + ~20s tooth): >= 15 seeded
# control-plane schedules against Controller + Spectator + 3
# participants — leader crash holding a full AckWindow, participant
# session expiry via coordinator.heartbeat, coordinator primary kill,
# coordinator WAL torn-write — each followed by the FOURTH standing
# invariant (exactly one LEADER per shard, zero acked-write loss across
# the handoff, shard-map convergence within a bounded number of
# controller passes) AND the FIFTH (round 13): bounded-staleness reads
# issued at every replica post-heal — zero served reads may violate the
# client's lag bound, zero reads may come from a deposed lineage (the
# fenced ex-leader is probed directly); then the fencing tooth: a
# leader patched to IGNORE epochs must be CAUGHT acking writes after
# deposition (--expect-violation). A violation prints the reproducing
# --seed.
chaos-failover-smoke:
	$(PY) -m tools.chaos_soak --failover --schedules 15 --seed 1 \
		--out benchmarks/results/chaos_failover_smoke.json
	$(PY) -m tools.chaos_soak --failover --schedules 1 --seed 7 \
		--break-guard fencing --expect-violation

# live-shard-move chaos smoke (~45s): 3 seeded reshard schedules (4
# nodes / 3 replicas; the move step machine killed at its seams,
# participants killed mid-move, coordinator faults) each holding the
# SIXTH standing invariant — exactly one serving lineage per shard,
# zero acked-write loss across the move, bounded convergence, no
# stranded replicas — then the move_flip tooth: a cutover patched to
# force-promote without drain/demote must be CAUGHT by the lineage
# probes (--expect-violation). Full deck: --reshard --schedules 15
# (artifact: benchmarks/results/chaos_reshard.json). A violation
# prints the reproducing --seed.
reshard-smoke:
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --reshard \
		--schedules 3 --seed 1 \
		--out benchmarks/results/chaos_reshard_smoke.json
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --reshard \
		--schedules 1 --seed 7 \
		--break-guard move_flip --expect-violation

# round-21 CDC streaming-ingest chaos smoke (~1 min + ~20s tooth):
# seeded cdc_burst schedules — the exactly-once consumer killed and
# restarted at each of the kafka.fetch / kafka.apply / kafka.checkpoint
# seams mid-batch, a multi-kill burst, and a leader failover
# mid-consume — each holding the EIGHTH standing invariant: applied
# records == produced prefix, exactly once, per partition, on every
# replica of the serving lineage (the WAL-riding watermark is the only
# resume authority). Then the cdc_dedup tooth: a consumer patched to
# commit its checkpoint in a SEPARATE batch after the records
# (at-least-once, the naive design) must be CAUGHT re-applying
# records after a crash between the two (--expect-violation). A
# violation prints the reproducing --seed.
cdc-smoke:
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --cdc \
		--schedules 2 --seed 1 \
		--out benchmarks/results/chaos_cdc_smoke.json
	env RSTPU_LOCKWATCH=1 $(PY) -m tools.chaos_soak --cdc \
		--schedules 1 --seed 7 \
		--break-guard cdc_dedup --expect-violation

clean:
	$(MAKE) -C rocksplicator_tpu/storage/native clean
	find . -name __pycache__ -type d -exec rm -rf {} +
