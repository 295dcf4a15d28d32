"""A write batch applied whole (PR 34).

An arrived batch keeps its encoded frame from the wire to the WAL and
enters the memtable's columns in one pass (``decode_batch`` →
``WriteBatch.columns`` → ``MemTable.apply_batch``). Held here against
what the path did before, which the builder API and the per-operation
``MemTable.apply`` still do:

- the memtable a whole-batch apply leaves equals the one the same
  operations leave applied one by one, for every shape of batch;
- the WAL record is byte for byte the built batch's ``encode()``;
- a frame that is not a batch raises ``Corruption`` before anything is
  logged or applied;
- a follower fed the leader's bytes, and the leader's own WAL replayed
  at re-open, end equal;
- a served ``write`` RPC parses on an executor thread, takes the
  column pass and answers as before.

Since PR 36 EVERY arrived frame is read column-wise: a frame of one
stride off a structured view (``"bulk"``), any other off one index of
its op headers (``"indexed"``: the native call, or the same walk in
Python); tuples exist only once someone asks for ``ops()``.
"""

import random
import threading

import numpy as np
import pytest

from rocksplicator_tpu.replication import (
    ReplicaRole, Replicator, StorageDbWrapper, replicated_db)
from rocksplicator_tpu.rpc import IoLoop
from rocksplicator_tpu.rpc.client_pool import RpcClientPool
from rocksplicator_tpu.rpc.errors import RpcApplicationError
from rocksplicator_tpu.storage import (
    DB, DBOptions, OpType, UInt64AddOperator, WriteBatch, decode_batch)
from rocksplicator_tpu.storage.errors import Corruption
from rocksplicator_tpu.storage.memtable import MemTable
from rocksplicator_tpu.storage.native import binding
from rocksplicator_tpu.storage.records import (
    _columns_of, _decode_ops, _index_ops, _indexed_columns, scan_batch_meta)
from rocksplicator_tpu.utils.stats import Stats

PUT, DELETE, MERGE, LOG = (
    OpType.PUT, OpType.DELETE, OpType.MERGE, OpType.LOG_DATA)
TS = 1_700_000_000_123


def _key(r, klen=16):
    return r.getrandbits(klen * 8).to_bytes(klen, "big")


def _val(r, vlen=8):
    return r.getrandbits(vlen * 8).to_bytes(vlen, "little")


def _counters(r, n=512, types=(PUT, MERGE), klen=16, vlen=8):
    return [(r.choice(types), _key(r, klen), _val(r, vlen))
            for _ in range(n)]


def _repeated(r):
    ops = _counters(r, 60)
    hot = ops[7][1]
    for i in (3, 20, 21, 59):  # one key five times in the batch
        ops[i] = (MERGE, hot, _val(r))
    ops[40] = (PUT, ops[2][1], _val(r))
    return ops


def _mixed(r):
    ops = []
    for _ in range(200):
        t = r.choice((PUT, MERGE, DELETE))
        ops.append((t, _key(r), b"" if t is DELETE else _val(r)))
    ops[50] = (DELETE, ops[10][1], b"")
    return ops


def _with_log(at):
    def make(r):
        ops = _counters(r, 64)
        ops.insert({"first": 0, "middle": 32, "last": 64}[at],
                   (LOG, b"", b"\x07" * 8))
        return ops
    return make


def _names(r, n=512, twice=False):
    """Counter names ``counter-<n>`` in arrival order: seven key lengths
    (9 to 15 bytes), a PUT among twenty MERGEs, the leader's stamp
    behind them."""
    ops = []
    for i in range(n):
        digits = i % 7 + 1 if i < 7 else r.choice((5, 6, 6, 6, 6, 7, 7))
        name = b"counter-%d" % r.randrange(10 ** (digits - 1), 10 ** digits)
        ops.append((PUT if r.random() < 0.05 else MERGE, name, _val(r)))
    r.shuffle(ops)
    assert {len(k) for _t, k, _v in ops} == set(range(9, 16))
    if twice:
        ops[400] = (MERGE, ops[17][1], _val(r))
    return ops + [(LOG, b"", TS.to_bytes(8, "little"))]


def _widths_and_deletes(r):
    ops = []
    for _ in range(300):
        t = r.choice((PUT, MERGE, DELETE))
        ops.append((t, _key(r, r.choice((12, 16, 16, 20))),
                    b"" if t is DELETE else _val(r, r.choice((0, 8, 8, 40)))))
    return ops


# name -> (ops from a Random, the pass the frame takes into columns)
CASES = {
    "counters_512": (_counters, "bulk"),
    "one_key_many_times": (_repeated, "bulk"),
    "one_key_only": (lambda r: [(MERGE, b"k" * 16, _val(r))
                                for _ in range(33)], "bulk"),
    "records_1kb": (lambda r: _counters(r, 128, (PUT,), vlen=1024), "bulk"),
    "deletes_only": (lambda r: _counters(r, 40, (DELETE,), vlen=0), "bulk"),
    "single_put": (lambda r: _counters(r, 1), "bulk"),
    "log_data_last": (_with_log("last"), "bulk"),
    "log_data_first": (_with_log("first"), "indexed"),
    "log_data_middle": (_with_log("middle"), "indexed"),
    "put_merge_delete": (_mixed, "indexed"),
    "varying_key_widths": (lambda r: [
        (PUT, _key(r, r.randint(1, 24)), _val(r)) for _ in range(100)],
        "indexed"),
    "varying_value_widths": (lambda r: [
        (PUT, _key(r), _val(r, r.randint(0, 40))) for _ in range(100)],
        "indexed"),
    "width_changes_late": (lambda r: _counters(r, 50) + [
        (PUT, _key(r, 12), _val(r))], "indexed"),
    "empty_keys": (lambda r: _counters(r, 20, klen=0), "indexed"),
    "empty": (lambda r: [], "indexed"),
    "log_data_only": (lambda r: [(LOG, b"", b"x")], "indexed"),
    "names_512": (_names, "indexed"),
    "names_512_one_key_twice": (lambda r: _names(r, twice=True), "indexed"),
    "value_widths_and_deletes": (_widths_and_deletes, "indexed"),
    "log_data_between_two_strides": (lambda r: _counters(r, 40) + [
        (LOG, b"", b"\x07" * 8)] + _counters(r, 40, klen=11), "indexed"),
}


def _build(ops) -> WriteBatch:
    """The builder's batch: its ``encode()`` is the format's reference."""
    wb = WriteBatch()
    for op, key, val in ops:
        {PUT: lambda: wb.put(key, val), MERGE: lambda: wb.merge(key, val),
         DELETE: lambda: wb.delete(key),
         LOG: lambda: wb.put_log_data(val)}[op]()
    return wb


def _apply_per_op(mem: MemTable, ops, seq: int) -> int:
    """What ``_apply_to_memtable`` did before PR 34."""
    for op, key, val in ops:
        if op is LOG:
            continue
        mem.apply(key, seq, op, val)
        seq += 1
    return seq


def _assert_same_memtable(a: MemTable, b: MemTable, uint64: bool):
    assert list(a.entries()) == list(b.entries())
    assert len(a) == len(b)
    assert a.approximate_bytes() == b.approximate_bytes()
    assert (a.min_seq, a.max_seq) == (b.min_seq, b.max_seq)
    merge_op = UInt64AddOperator() if uint64 else None
    for key in {k for k, *_ in a.entries()} | {b"absent"}:
        assert a.get(key, merge_op) == b.get(key, merge_op)
    la, lb = a.drain_lanes(), b.drain_lanes()
    assert (la is None) == (lb is None)
    if la is not None:
        assert la[0].keys() == lb[0].keys()
        for name in la[0]:
            np.testing.assert_array_equal(la[0][name], lb[0][name])
        np.testing.assert_array_equal(la[1], lb[1])


@pytest.mark.parametrize("half_full", [False, True],
                         ids=["fresh", "half_full"])
@pytest.mark.parametrize("case", CASES)
def test_whole_batch_apply_equals_per_operation_apply(case, half_full):
    make, frame_pass = CASES[case]
    r = random.Random(case)
    ops = make(r)
    raw = _build(ops).encode()
    one_by_one, whole = MemTable(), MemTable()
    seq, before = 1, []
    if half_full:  # older rows of some of the batch's keys among them
        live = [o for o in ops if o[0] is not LOG]
        before = _counters(r, 300, vlen=len(live[0][2]) if live else 8) + [
            (MERGE, k, v) for _t, k, v in live[::3]]
        seq = _apply_per_op(one_by_one, before, 1)
        whole.apply_batch(_build(before).columns(), 1)
    arrived = decode_batch(raw)
    cols = arrived.columns()
    assert cols.frame_pass == frame_pass
    assert arrived.count() == cols.count == sum(o[0] is not LOG for o in ops)
    assert len(arrived) == len(ops)
    assert arrived.byte_size() == len(raw)
    assert scan_batch_meta(raw)[0] == cols.count
    end = _apply_per_op(one_by_one, ops, seq)
    whole.apply_batch(cols, seq)
    assert end == seq + cols.count
    widths = {len(v) for t, _k, v in before + ops if t in (PUT, MERGE)}
    _assert_same_memtable(one_by_one, whole, uint64=widths <= {8})
    # the frame itself was never taken apart: tuples only when asked for
    assert arrived._built is None
    assert arrived.encode() == raw
    assert list(arrived.ops()) == ops


def _hide_library(monkeypatch):
    """What a process without the native library runs."""
    monkeypatch.setattr(binding, "_native", None)
    assert not binding.native_available()


WALKERS = ["native", "python"]


@pytest.mark.parametrize("walker", WALKERS)
@pytest.mark.parametrize("case", CASES)
def test_columns_off_the_index_equal_the_tuple_walks(case, walker, monkeypatch):
    """Whatever the frame's shape, the columns read off the index of its
    op headers are, field by field, the ones the walk over its tuples
    gives; so are the ones ``decode_batch`` chose for it."""
    if walker == "python":
        _hide_library(monkeypatch)
    make, frame_pass = CASES[case]
    ops = make(random.Random(case))
    raw = _build(ops).encode()
    want = _columns_of(_decode_ops(raw))
    index = _index_ops(raw, 4, len(ops))
    assert index[2] == len(raw)
    arrived = decode_batch(raw)
    for got, said in ((_indexed_columns(raw, index), "indexed"),
                      (arrived.columns(), frame_pass)):
        assert got.frame_pass == said
        for name in want._fields[:-1]:
            field, ref = getattr(got, name), getattr(want, name)
            if name in ("keys", "vals"):
                field, ref = list(field), list(ref)
            assert field == ref, name
            assert type(field) is type(ref), name
    assert arrived._built is None
    assert arrived.encode() == raw
    assert (arrived.count(), len(arrived), arrived.byte_size()) == (
        want.count, len(ops), len(raw))
    # the stamp is the last 8-byte LOG_DATA, wherever it stands
    stamp = _build(ops).extract_timestamp_ms()
    assert scan_batch_meta(raw) == (want.count, stamp)
    assert arrived.extract_timestamp_ms() == stamp
    assert arrived._built is None


@pytest.mark.parametrize("case", CASES)
def test_stamped_frame_is_the_built_batchs_encoding(case, tmp_path):
    """decode → stamp → write: the WAL holds, byte for byte, what the
    built batch stamped the same encodes to, under the same sequence
    numbers; a re-open replays it to the same memtable."""
    ops = CASES[case][0](random.Random(case))
    raw = _build(ops).encode()
    want = _build(ops).stamp_timestamp_ms(TS).encode()
    arrived = decode_batch(memoryview(raw)).stamp_timestamp_ms(TS)
    assert arrived.encode() == want
    assert arrived.extract_timestamp_ms() == TS
    assert scan_batch_meta(want) == (arrived.count(), TS)
    opts = DBOptions(memtable_bytes=1 << 30)
    db = DB(str(tmp_path / "db"), opts)
    try:
        db.put(b"k" * 16, b"v" * 8)
        assert db.write(arrived) == 2
        assert db.latest_sequence_number() == 1 + arrived.count()
        assert [(s, bytes(b)) for s, b in db.get_updates_since(2)] == [
            (2, want)]
        entries = list(db._mem.entries())
    finally:
        db.close()
    db = DB(str(tmp_path / "db"), opts)
    try:
        assert list(db._mem.entries()) == entries
        assert db.latest_sequence_number() == 1 + arrived.count()
    finally:
        db.close()


def _small_mixed():
    """Four ops of four shapes: a stamp in the middle, an empty value."""
    return _build([(PUT, b"k1", b"v"), (LOG, b"", TS.to_bytes(8, "little")),
                   (MERGE, b"key-two", b"vv"), (DELETE, b"k", b"")]).encode()


def _with_u32(frame: bytes, at: int, value: int) -> bytes:
    return frame[:at] + value.to_bytes(4, "little") + frame[at + 4:]


def _frames():
    r = random.Random(34)
    good = _build(_counters(r, 32)).encode()
    uneven = _build(_mixed(r)).encode()
    small = _small_mixed()
    # small's third op: its type, its key's length, its value's length
    at = small.index(b"key-two") - 5
    klen_at, vlen_at = at + 1, at + 12
    assert small[at] == MERGE and small[vlen_at + 4:vlen_at + 6] == b"vv"
    frames = {
        "three_bytes": good[:3],
        "cut_in_a_header": good[:4 + 33 * 7 + 3],
        "cut_in_a_key": good[:4 + 33 * 20 + 9],
        "cut_in_a_value": good[:-2],
        "one_op_short": good[:-33],
        "a_byte_over": good + b"\x00",
        # nine bytes that would read as an op's header once a stamp's
        # 17 bytes stand behind them
        "an_op_header_over": good + b"\x01" + bytes(4) + b"\x11" + bytes(3),
        "count_too_high": _with_u32(good, 0, 33),
        "count_too_low": _with_u32(good, 0, 31),
        "count_is_a_length": _with_u32(good, 0, len(good)),
        "count_is_the_largest": _with_u32(good, 0, 0xFFFFFFFF),
        "op_type_0": good[:4 + 33 * 5] + b"\x00" + good[4 + 33 * 5 + 1:],
        "op_type_5": good[:4 + 33 * 5] + b"\x05" + good[4 + 33 * 5 + 1:],
        "uneven_cut": uneven[:-1],
        "uneven_over": uneven + b"\x04",
        "uneven_count_too_high": _with_u32(uneven, 0, 201),
        "small_over": small + b"\x00",
        "small_op_type_0": small[:at] + b"\x00" + small[at + 1:],
        "small_op_type_5": small[:at] + b"\x05" + small[at + 1:],
        "small_op_type_255": small[:at] + b"\xff" + small[at + 1:],
        "small_key_runs_past": _with_u32(small, klen_at, len(small)),
        "small_key_runs_to_the_end": _with_u32(
            small, klen_at, len(small) - klen_at - 4),
        "small_key_is_the_largest": _with_u32(small, klen_at, 0xFFFFFFFF),
        "small_key_wraps_32_bits": _with_u32(
            small, klen_at, 0xFFFFFFFF - klen_at - 3),
        "small_value_runs_past": _with_u32(small, vlen_at, len(small)),
        "small_value_is_the_largest": _with_u32(small, vlen_at, 0xFFFFFFFF),
        "small_value_wraps_32_bits": _with_u32(
            small, vlen_at, 0xFFFFFFFF - vlen_at - 3),
        "small_last_value_runs_past": _with_u32(small, len(small) - 4, 1),
        "small_last_value_is_the_largest": _with_u32(
            small, len(small) - 4, 0xFFFFFFFF),
    }
    for cut in range(len(small)):  # truncated at every byte
        frames[f"small_cut_at_{cut:02d}"] = small[:cut]
    return frames


def _open_leader(path):
    rep = Replicator(port=0)
    db = DB(str(path), DBOptions(
        memtable_bytes=1 << 30, merge_operator=UInt64AddOperator()))
    rdb = rep.add_db("seg00000", StorageDbWrapper(db), ReplicaRole.LEADER,
                     replication_mode=0)
    return rep, db, rdb


@pytest.fixture()
def leader(tmp_path):
    rep, db, rdb = _open_leader(tmp_path / "leader")
    yield rep, db, rdb
    rep.stop()
    db.close()


@pytest.fixture(scope="module")
def refusing_leader(tmp_path_factory):
    """One leader for every frame it refuses: nothing of them stays."""
    rep, db, rdb = _open_leader(tmp_path_factory.mktemp("refusing"))
    rdb.write(_build(_counters(random.Random(1), 8)))
    yield rep, db, rdb
    rep.stop()
    db.close()


@pytest.mark.parametrize("walker", WALKERS)
@pytest.mark.parametrize("frame", _frames())
def test_a_frame_that_is_no_batch_is_refused_before_it_is_logged(
        frame, walker, refusing_leader, monkeypatch):
    """``Corruption`` from the parse and from the header scan alike,
    from the native index and from the Python walk alike."""
    if walker == "python":
        _hide_library(monkeypatch)
    _rep, db, rdb = refusing_leader
    seq = db.latest_sequence_number()
    assert seq == 8
    wal = [(s, bytes(b)) for s, b in db.get_updates_since(1)]
    entries = list(db._mem.entries())
    bad = _frames()[frame]
    with pytest.raises(Corruption):
        decode_batch(bad)
    with pytest.raises(Corruption):
        scan_batch_meta(bad)
    with pytest.raises(Corruption):
        rdb._write_encoded(memoryview(bad))
    assert db.latest_sequence_number() == seq
    assert [(s, bytes(b)) for s, b in db.get_updates_since(1)] == wal
    assert list(db._mem.entries()) == entries


def test_the_small_frame_itself_is_a_batch():
    """What the corrupt frames above were cut from."""
    small = _small_mixed()
    assert scan_batch_meta(small) == (3, TS)
    assert decode_batch(small).columns().frame_pass == "indexed"
    assert [t for t, _k, _v in decode_batch(small).ops()] == [
        PUT, LOG, MERGE, DELETE]


@pytest.mark.parametrize("cases", [
    ("counters_512", "one_key_many_times", "counters_512"),
    ("put_merge_delete", "log_data_middle", "varying_value_widths",
     "records_1kb", "empty", "deletes_only"),
], ids=["column_pass", "every_pass"])
def test_a_follower_fed_the_leaders_bytes_ends_equal(cases, leader, tmp_path):
    _rep, ldb, rdb = leader
    for case in cases:
        raw = _build(CASES[case][0](random.Random(case))).encode()
        rdb._write_encoded(memoryview(raw))
    shipped = [(s, bytes(b)) for s, b in ldb.get_updates_since(1)]
    assert len(shipped) == len(cases)
    fdb = DB(str(tmp_path / "follower"), DBOptions(
        memtable_bytes=1 << 30, merge_operator=UInt64AddOperator()))
    try:
        # one pull response's group, then one update alone
        follower = StorageDbWrapper(fdb)
        follower.handle_replicate_updates(
            [{"raw_data": memoryview(b)} for _s, b in shipped[:-1]])
        follower.handle_replicate_response(shipped[-1][1], None)
        assert fdb.latest_sequence_number() == ldb.latest_sequence_number()
        assert list(fdb._mem.entries()) == list(ldb._mem.entries())
        assert [(s, bytes(b))
                for s, b in fdb.get_updates_since(1)] == shipped
    finally:
        fdb.close()


def test_a_built_batch_still_builds_after_it_arrived():
    """put / merge / delete on an arrived batch thaw it; the stamp does
    not (that is the leader's path)."""
    ops = _counters(random.Random(5), 10)
    arrived = decode_batch(_build(ops).encode())
    arrived.stamp_timestamp_ms(TS)
    assert arrived._raw is not None
    assert arrived.columns().frame_pass == "bulk"
    arrived.put(b"late", b"op")
    assert arrived._raw is None and arrived.columns().frame_pass is None
    want = _build(ops).stamp_timestamp_ms(TS).put(b"late", b"op")
    assert arrived.encode() == want.encode()
    assert arrived.count() == 11 and len(arrived) == 12
    assert list(arrived.strip_log_data().ops()) == ops + [
        (PUT, b"late", b"op")]


@pytest.mark.parametrize("shape", ["counters", "names"])
def test_served_write_parses_off_the_loop_and_takes_the_column_pass(
        shape, leader, monkeypatch):
    """A frame of 16-byte keys takes the bulk pass, a frame of counter
    names the index: both are parsed on an executor thread, counted
    under their pass alone, and answered the same."""
    rep, db, rdb = leader
    r = random.Random(512)
    if shape == "counters":
        keys = [_key(r) for _ in range(200)]
    else:
        keys = [b"counter-%d" % r.randrange(10 ** d)
                for d in range(1, 8) for _ in range(30)]
    ops = [(PUT, keys[0], (5).to_bytes(8, "little"))] + [
        (MERGE, r.choice(keys), _val(r, 4) + bytes(4)) for _ in range(511)]
    took, other = (("bulk", "indexed") if shape == "counters"
                   else ("indexed", "bulk"))
    folded = {}
    for _op, key, val in ops:
        folded[key] = folded.get(key, 0) + int.from_bytes(val, "little")
    parsed_on = []
    real = replicated_db.decode_batch

    def decode_batch_on(raw):
        parsed_on.append(threading.get_ident())
        return real(raw)

    monkeypatch.setattr(replicated_db, "decode_batch", decode_batch_on)
    ioloop = IoLoop.default()
    pool = RpcClientPool()

    async def call(method, **args):
        return await pool.call("127.0.0.1", rep.port, method, args)

    async def loop_thread():
        return threading.get_ident()

    stats = Stats.get()
    before = {k: stats.get_counter("write.apply." + k)
              for k in ("bulk", "indexed", "general")}
    try:
        raw = _build(ops).encode()
        reply = ioloop.run_sync(
            call("write", db_name="seg00000", raw_batch=raw), timeout=10)
        assert reply == {"seq": 1, "acked": True, "epoch": rdb.epoch}
        reply = ioloop.run_sync(
            call("write", db_name="seg00000", raw_batch=raw), timeout=10)
        assert reply == {"seq": 513, "acked": True, "epoch": rdb.epoch}
        assert stats.get_counter("write.apply." + took) == before[took] + 2
        assert stats.get_counter("write.apply." + other) == before[other]
        # no arrived frame takes the tuple pass: the counter is gone
        assert stats.get_counter("write.apply.general") == 0 == before[
            "general"]
        assert len(parsed_on) == 2
        assert ioloop.run_sync(loop_thread(), timeout=10) not in parsed_on
        assert threading.get_ident() not in parsed_on
        for key, total in folded.items():
            got = ioloop.run_sync(call(
                "read", db_name="seg00000", op="get", keys=[key]),
                timeout=10)
            # the second batch's PUT starts keys[0] over
            assert int.from_bytes(bytes(got["values"][0]), "little") == (
                total if key == keys[0] else 2 * total)
        # a frame cut short answers the error it did, and logs nothing
        with pytest.raises(RpcApplicationError) as refused:
            ioloop.run_sync(call(
                "write", db_name="seg00000", raw_batch=raw[:-3]), timeout=10)
        assert refused.value.code == "INTERNAL"
        assert "Corruption" in refused.value.message
        assert db.latest_sequence_number() == 1024
        assert len(list(db.get_updates_since(1))) == 2
    finally:
        ioloop.run_sync(pool.close(), timeout=5)
