"""A shard larger than one place of the served door's launch (PR 33):
``compact_dbs_batched`` cuts it at KEYS into consecutive ranges of at
most ``PLACE_ROWS_MAX`` rows, each a place of the fixed group launch,
and installs all of a shard's places together or none.

- the planner's rule (``plan_subcompactions(max_slice_rows=)``) on seeded
  runs: every place within the bound, no key group split, every row in
  exactly one place, places in key order, as few places as fit;
- the served door with the place capacity set small, against the same
  shard uncut AND a plain reference, for uint64-add counters (values
  ride) and 1 KB PUTs (index path), tombstones dropped and kept;
- the decline of a key group over a place, before any program;
- a failed place installs nothing and leaves no file behind;
- a cut compaction builds no program that an uncut one of that capacity
  has not built;
- every output file's filter holds its keys at ``bits_per_key`` or more.

XLA-CPU under conftest.py's explicit JAX_PLATFORMS=cpu.
"""

import heapq
import os
import random
import struct

import pytest

import rocksplicator_tpu.storage.native_compaction as nc
from rocksplicator_tpu.ops.bloom_tpu import bloom_build_tpu
from rocksplicator_tpu.ops.kv_format import pack_entries
from rocksplicator_tpu.storage import DB, DBOptions, OpType
from rocksplicator_tpu.storage.compaction import (host_fallback_counts,
                                                  resolve_stream)
from rocksplicator_tpu.storage.merge import UInt64AddOperator
from rocksplicator_tpu.storage.records import WriteBatch
from rocksplicator_tpu.storage.sst import SSTReader, SSTWriter
from rocksplicator_tpu.testing import failpoints as fp
from rocksplicator_tpu.tpu import compaction_service as cs
from rocksplicator_tpu.utils.stats import Stats

P, D, M = 1, 2, 3
pack64 = struct.Struct("<Q").pack
PLACE = 128  # the place capacity the door is given here
BITS = 10


def key(i: int) -> bytes:
    return b"s000-key%08d" % i


# ---------------------------------------------------------------------------
# the planner's rule, on seeded runs
# ---------------------------------------------------------------------------


def _runs(scenario: str, seed: int):
    """(key asc, seq desc) runs of (key, seq, vtype, value) tuples."""
    rng = random.Random(seed)
    seq = iter(range(1, 1 << 30))
    if scenario == "skewed_duplicates":
        # a few hot keys hold most rows, in every run
        runs = []
        for _ in range(4):
            run = [(key(i), next(seq), M, pack64(1))
                   for i in rng.sample(range(300), 120)]
            for hot in (7, 150, 151, 299):
                run += [(key(hot), next(seq), M, pack64(2))
                        for _ in range(rng.randrange(8, 30))]
            runs.append(run)
    elif scenario == "long_merge_chain":
        # one key's stack is all but a whole place
        runs = [[(key(i), next(seq), P, pack64(i)) for i in range(400)],
                [(key(200), next(seq), M, pack64(1))
                 for _ in range(PLACE - 2)]]
    elif scenario == "tombstones":
        runs = [[(key(i), next(seq), P, pack64(i)) for i in range(500)]]
        runs.append([(key(i), next(seq), D, b"")
                     for i in rng.sample(range(500), 200)])
        runs.append([(key(i), next(seq), M, pack64(3))
                     for i in rng.sample(range(500), 150)])
    elif scenario == "tight":
        # exactly three places' rows, one row a key: three places fit
        runs = [[(key(i), next(seq), P, pack64(i))
                 for i in range(r, 3 * PLACE, 2)] for r in (0, 1)]
    elif scenario == "one_over":
        # one row more than two places hold: three places
        runs = [[(key(i), next(seq), P, pack64(i))
                 for i in range(2 * PLACE + 1)]]
    else:
        raise ValueError(scenario)
    return [sorted(run, key=lambda e: (e[0], -e[1])) for run in runs]


def _parts(runs):
    return [nc.NativeCompactionBackend._arrays_from_entries(run, pack_entries)
            for run in runs]


def _fewest(runs, bound):
    """Places a greedy walk over the merged key groups needs."""
    sizes = {}
    for k, *_ in heapq.merge(*runs):
        sizes[k] = sizes.get(k, 0) + 1
    places, room = 0, 0
    for k in sorted(sizes):
        if sizes[k] > room:
            places, room = places + 1, bound
        room -= sizes[k]
    return places


@pytest.mark.parametrize("scenario", [
    "skewed_duplicates", "long_merge_chain", "tombstones", "tight",
    "one_over"])
def test_planner_holds_every_place_to_the_bound(scenario):
    runs = _runs(scenario, seed=33)
    parts = _parts(runs)
    total = sum(len(r) for r in runs)
    klen = len(key(0))
    assert total > PLACE
    bounds = nc.plan_subcompactions(parts, total, 1, klen,
                                    max_slice_rows=PLACE)
    places = nc.slice_lanes(parts, bounds, klen)
    assert len(places) == len(bounds) + 1 == _fewest(runs, PLACE)
    rows = [p["key_len"].shape[0] for p in places]
    assert max(rows) <= PLACE and sum(rows) == total  # every row, once
    # near-equal wherever whole key groups allow it: no place is left
    # nearly empty beside full ones unless a key group forces it
    where, last = {}, b""
    for i, place in enumerate(places):
        keys = [nc._part_key(place, r, klen) for r in range(rows[i])]
        assert min(keys) > last  # places in key order, key-disjoint
        last = max(keys)
        for k in keys:
            assert where.setdefault(k, i) == i  # no key group is split
    want = {}
    for run in runs:
        for k, *_ in run:
            want[k] = want.get(k, 0) + 1
    assert len(where) == len(want)
    # under the bound the planner does not cut at all
    assert nc.plan_subcompactions(parts, total, 1, klen,
                                  max_slice_rows=total) == []
    assert nc.plan_subcompactions(parts, total, 1, klen) == []


def test_planner_cuts_near_equal_where_key_groups_allow():
    runs = [[(key(i), i + 1, P, pack64(i)) for i in range(330)]]
    parts = _parts(runs)
    bounds = nc.plan_subcompactions(parts, 330, 1, len(key(0)),
                                    max_slice_rows=PLACE)
    rows = [p["key_len"].shape[0]
            for p in nc.slice_lanes(parts, bounds, len(key(0)))]
    assert rows == [110, 110, 110]
    # the parallelism rule and the bound together: the more of the two
    bounds = nc.plan_subcompactions(parts, 330, 5, len(key(0)),
                                    max_slice_rows=PLACE)
    assert len(bounds) + 1 == 3  # MIN_SLICE_ENTRIES keeps five from it


def test_planner_does_not_cut_a_run_whose_keys_do_not_ascend():
    # the bound's rule checks the keys' order alone (the device sorts
    # the rows of a place itself): seqs in any order still cut
    run = [(key(i), 1 + (i * 7) % 300, P, pack64(i)) for i in range(300)]
    klen = len(key(0))
    assert len(nc.plan_subcompactions(_parts([run]), 300, 1, klen,
                                      max_slice_rows=PLACE)) == 2
    run[10], run[200] = run[200], run[10]
    assert nc.plan_subcompactions(_parts([run]), 300, 1, klen,
                                  max_slice_rows=PLACE) == []


def test_planner_says_when_one_key_group_is_over_a_place():
    runs = [sorted([(key(i), i + 1, P, pack64(i)) for i in range(100)]
                   + [(key(50), 1000 + j, M, pack64(1))
                      for j in range(PLACE)],
                   key=lambda e: (e[0], -e[1]))]
    with pytest.raises(nc.KeyGroupOverSlice):
        nc.plan_subcompactions(_parts(runs), 100 + PLACE, 1, len(key(0)),
                               max_slice_rows=PLACE)


# ---------------------------------------------------------------------------
# the served door
# ---------------------------------------------------------------------------


def make_db(path, seed, rows, counters: bool, keep_tombstones=False,
            chain=0):
    """A shard as a refresh unit leaves it before its compaction: live
    writes (for counters MERGE increments, base PUTs and DELETEs; else
    1 KB PUTs and DELETEs) flushed under a bulk file of ``rows`` rows.
    ``chain``: so many MERGE operands on one key besides."""
    rng = random.Random(seed)
    db = DB(str(path), DBOptions(
        merge_operator=UInt64AddOperator() if counters else None,
        bits_per_key=BITS, allow_ingest_behind=keep_tombstones))

    def value():
        return (pack64(rng.randrange(1 << 64)) if counters
                else rng.randbytes(1024))

    wb = WriteBatch()
    for n in range(rows // 3):
        i = rng.randrange(rows + 30)
        if n % 9 == 4:
            wb.delete(key(i))
        elif counters and n % 3:
            wb.merge(key(i), value())
        else:
            wb.put(key(i), value())
    for _ in range(chain):
        wb.merge(key(rows // 2), pack64(1))
    db.write(wb)
    db.flush()
    sst = str(path) + ".bulk.tsst"
    w = SSTWriter(sst)
    for i in range(rows):
        w.add(key(i), 0, OpType.PUT, value())
    w.finish()
    db.ingest_external_file([sst], move_files=True, allow_global_seqno=True)
    return db


def db_files(db):
    return [os.path.join(db.path, n) for level in db._levels for n in level]


def file_entries(paths):
    out = []
    for p in paths:
        r = SSTReader(p)
        out.extend((k, int(t), bytes(v)) for k, _s, t, v in r.iterate())
        r.close()
    return out


def reference(db, drop):
    runs = [list(db._readers[n].iterate())
            for level in db._levels for n in level]
    merged = heapq.merge(*runs, key=lambda e: (e[0], -e[1]))
    return [(k, int(t), bytes(v)) for k, _s, t, v in resolve_stream(
        merged, db.options.merge_operator, drop)]


def compact_traced(dbs):
    """``compact_dbs_batched`` inside a caller's always-on trace, as an
    ingest RPC's is. Returns its verdict and the trace's spans by name."""
    from rocksplicator_tpu.observability.collector import SpanCollector
    from rocksplicator_tpu.observability.span import start_span

    with start_span("test.caller", always=True) as caller:
        verdict = cs.compact_dbs_batched(dbs)
    by_name = {}
    for s in SpanCollector.get().snapshot():
        if s["trace_id"] == caller.trace_id:
            by_name.setdefault(s["name"], []).append(s)
    return verdict, by_name


def check_filters(db, bits=BITS):
    """Every file's filter: no false negative, ``bits`` or more a key."""
    for path in db_files(db):
        r = SSTReader(path)
        keys = [k for k, *_ in r.iterate()]
        assert keys and all(r._bloom.may_contain(k) for k in keys)
        assert 32 * r._bloom.num_words >= bits * len(keys)
        r.close()


@pytest.mark.parametrize("keep", [False, True],
                         ids=["tombstones_dropped", "tombstones_kept"])
@pytest.mark.parametrize("counters", [True, False],
                         ids=["uint64add_ride", "put1k_index"])
def test_cut_shard_compacts_as_the_uncut_launch_and_the_reference(
        counters, keep, tmp_path, monkeypatch):
    rows = 260
    stats = Stats.get()
    sides = {}
    for side, place in (("cut", PLACE), ("uncut", cs.PLACE_ROWS_MAX)):
        monkeypatch.setattr(cs, "PLACE_ROWS_MAX", place)
        dbs = [make_db(tmp_path / f"{side}{n}", 40 + n, rows, counters, keep)
               for n in range(3)]
        want = [reference(db, not keep) for db in dbs]
        was = [int(stats.get_counter("compact.range_cut." + k))
               for k in ("shards", "places")]
        (handled, remaining), spans = compact_traced(
            [(f"db{n}", db) for n, db in enumerate(dbs)])
        assert sorted(handled) == ["db0", "db1", "db2"] and not remaining
        gained = [int(stats.get_counter("compact.range_cut." + k)) - w
                  for k, w in zip(("shards", "places"), was)]
        (launch,) = [s["annotations"] for s in spans["tpu.compact_stream"]]
        (stage,) = spans["admin.compact_stage"]
        assert all(s["parent_id"] == stage["span_id"]
                   for s in spans.get("tpu.range_cut", ()))
        cuts = [s["annotations"] for s in spans.get("tpu.range_cut", ())]
        assert launch["dbs"] == 3
        assert launch["value_path"] == ("ride" if counters else "index")
        if side == "cut":
            # ~345 rows a shard: three places each, nine in two launches
            assert gained == [3, launch["shards"]] and launch["shards"] >= 9
            assert launch["capacity"] == PLACE
            assert len(cuts) == 3 and all(
                c["capacity"] == PLACE and c["places"] >= 3
                and c["rows"] > 2 * PLACE for c in cuts)
            assert all(len(db_files(db)) >= 3 for db in dbs)
        else:
            assert gained == [0, 0] and launch["shards"] == 3 and not cuts
        for db, entries in zip(dbs, want):
            assert file_entries(db_files(db)) == entries  # key order too
            for k, t, v in entries[::7]:
                if t != M:  # (a kept operand stack reads as its fold)
                    assert db.get(k) == (v if t == P else None)
            check_filters(db)
        sides[side] = [file_entries(db_files(db)) for db in dbs]
        for db in dbs:
            db.close()
    assert sides["cut"] == sides["uncut"]


def test_cut_index_shard_prestages_a_buffer_a_place(tmp_path, monkeypatch):
    """The index path's values go up a place, on the thread that cut the
    shard, each in a buffer of the place's capacity: none is re-staged."""
    monkeypatch.setattr(cs, "PLACE_ROWS_MAX", PLACE)
    stats = Stats.get()
    was = [int(stats.get_counter("seam.values." + k))
           for k in ("prestaged", "restaged")]
    db = make_db(tmp_path / "db", 5, 260, counters=False)
    want = reference(db, True)
    handled, _ = cs.compact_dbs_batched([("db", db)])
    assert handled == ["db"]
    now = [int(stats.get_counter("seam.values." + k))
           for k in ("prestaged", "restaged")]
    assert now[0] - was[0] >= 3 and now[1] == was[1]
    assert file_entries(db_files(db)) == want
    db.close()


def test_key_group_over_a_place_declines_before_any_program(
        tmp_path, monkeypatch):
    monkeypatch.setattr(cs, "PLACE_ROWS_MAX", PLACE)
    monkeypatch.setattr(
        cs.TpuCompactionService, "_pipeline",
        lambda self, *a, **k: pytest.fail("a program was built"))
    db = make_db(tmp_path / "db", 9, 260, counters=True, chain=PLACE + 1)
    want = reference(db, True)
    before = db_files(db)
    was = host_fallback_counts().get("key_group_over_place", 0)
    handled, remaining = cs.compact_dbs_batched([("db", db)])
    assert handled == [] and [n for n, _ in remaining] == ["db"]
    assert host_fallback_counts()["key_group_over_place"] == was + 1
    assert db_files(db) == before
    db.compact_range()  # what the caller does with ``remaining``
    assert file_entries(db_files(db)) == want
    db.close()


def _fail_at_the_cut(monkeypatch):
    fp.activate("compact.subcompact", "fail_nth:2")


def _fail_the_second_place_write(monkeypatch):
    real, calls = cs.write_resolved_lanes, []

    def write(*a, **k):
        calls.append(1)
        if len(calls) == 2:
            raise OSError("disk full on the second place")
        return real(*a, **k)

    monkeypatch.setattr(cs, "write_resolved_lanes", write)


@pytest.mark.parametrize("fault", [_fail_at_the_cut,
                                   _fail_the_second_place_write],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_failed_place_installs_nothing(fault, tmp_path, monkeypatch):
    """All of a shard's places or none: the shard goes to the per-db
    path with its files as they were, and no output file is left."""
    monkeypatch.setattr(cs, "PLACE_ROWS_MAX", PLACE)
    db = make_db(tmp_path / "db", 11, 260, counters=True)
    want = reference(db, True)
    before, listed = db_files(db), sorted(os.listdir(db.path))
    fault(monkeypatch)
    try:
        handled, remaining = cs.compact_dbs_batched([("db", db)])
    finally:
        fp.deactivate("compact.subcompact")
    assert handled == [] and [n for n, _ in remaining] == ["db"]
    assert db_files(db) == before
    assert sorted(os.listdir(db.path)) == listed
    db.compact_range()  # the per-db path takes the compaction mutex
    assert file_entries(db_files(db)) == want
    db.close()


def test_cut_compaction_builds_no_program_of_its_own(tmp_path, monkeypatch):
    """After an uncut shard of one place's capacity, a cut shard finds
    every program it needs: the same pipeline cache keys, no new jit
    entry, and no per-file bloom program at all."""
    monkeypatch.setattr(cs, "PLACE_ROWS_MAX", PLACE)
    svc = cs.TpuCompactionService.instance()
    small = make_db(tmp_path / "small", 21, 90, counters=True)  # ~120 rows
    assert cs.compact_dbs_batched([("small", small)])[0] == ["small"]
    keys = set(svc._vmapped_cache)
    sizes = {k: fn._cache_size() for k, fn in svc._vmapped_cache.items()}
    blooms = bloom_build_tpu._cache_size()
    big = make_db(tmp_path / "big", 22, 260, counters=True)
    want = reference(big, True)
    assert cs.compact_dbs_batched([("big", big)])[0] == ["big"]
    assert len(db_files(big)) >= 3
    assert set(svc._vmapped_cache) == keys
    assert {k: fn._cache_size()
            for k, fn in svc._vmapped_cache.items()} == sizes
    assert bloom_build_tpu._cache_size() == blooms
    assert file_entries(db_files(big)) == want
    small.close()
    big.close()


def test_a_place_of_several_files_takes_the_host_bloom(tmp_path,
                                                       monkeypatch):
    """The launch's filter is a whole place's: where a place is split
    into several files (a small ``target_file_bytes``), or the DB asks
    for more bits a key than the launch gives, each file gets a filter
    over exactly its own keys from the host's bulk bloom."""
    monkeypatch.setattr(cs, "PLACE_ROWS_MAX", 2048)
    db = make_db(tmp_path / "db", 31, 4500, counters=True)
    db.options.target_file_bytes = 1024 * 33  # 1,024 rows a file
    db.options.bits_per_key = 14
    want = reference(db, True)
    blooms = bloom_build_tpu._cache_size()
    assert cs.compact_dbs_batched([("db", db)])[0] == ["db"]
    assert bloom_build_tpu._cache_size() == blooms
    assert len(db_files(db)) >= 5
    assert file_entries(db_files(db)) == want
    check_filters(db, bits=14)
    db.close()


def test_device_shard_rows_max_says_what_the_door_takes():
    from rocksplicator_tpu.storage.merge import MergeOperator

    class Custom(MergeOperator):
        def merge(self, key, existing, operands):
            return existing

        def partial_merge(self, key, operands):
            return None

    assert cs.device_shard_rows_max(UInt64AddOperator()) == \
        cs.MAX_BATCHED_DB_ENTRIES > cs.PLACE_ROWS_MAX == 32768
    assert cs.device_shard_rows_max(None) == cs.MAX_BATCHED_DB_ENTRIES
    assert cs.device_shard_rows_max(Custom()) == 0
    # a place is a capacity bucket of the launch
    assert cs._next_pow2(cs.PLACE_ROWS_MAX) == cs.PLACE_ROWS_MAX
