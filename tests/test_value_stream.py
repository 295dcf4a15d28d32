"""Values of differing width across the compaction seam as ONE word
stream (``tpu/format.py`` ``ValueStream``): each value from a 4-byte
boundary, ``ceil(val_len / 4)`` words, zero bytes past its length; a
row's word offset follows from the ``val_len`` of the rows before it.

- the native source decodes a file's values into the stream and gives
  the words its row mode gives (a PLANAR file with a value-length plane,
  one of one width, a plain row writer's file), as the Python source
  does; a stream that would overrun raises and takes nothing;
- the PLANAR sink takes the stream with its offsets and writes the
  bytes it writes from rows, native and Python, over several files;
  and a compaction's output rows, each at its source row's words in
  the input stream (the merge order taken at the input's offsets),
  against the same rows gathered;
- the served door (``compact_dbs_batched``): a MixGraph-shaped place
  keeps its values on the host as a stream (no value span crosses the
  seam) and writes the files the row path
  writes, byte for byte, on places of duplicate keys across a plain
  writer's bulk file and a PLANAR flush, values of 10 and of 1,024
  bytes, empty values, a place of one row, tombstones kept; a place
  whose stream would overrun keeps its rows and is counted; stream
  and row places never share a launch.

XLA-CPU under conftest.py's explicit JAX_PLATFORMS=cpu.
"""

import random

import numpy as np
import pytest

from rocksplicator_tpu.storage import DB, DBOptions, OpType
from rocksplicator_tpu.storage import native_compaction as nc
from rocksplicator_tpu.storage.compaction import host_fallback_counts
from rocksplicator_tpu.storage.records import WriteBatch
from rocksplicator_tpu.storage.sst import SSTReader, SSTWriter
from rocksplicator_tpu.tpu import compaction_service as cs
from rocksplicator_tpu.tpu import format as fmt
from rocksplicator_tpu.utils.stats import Stats
from test_mixgraph_records import (D, P, check_db, compact_traced, db_files,
                                   lanes_of, mix_key, mix_value,
                                   mixgraph_rows, plain_reference,
                                   python_codecs)

MOVES = ("compact.value_move.stream", "compact.value_move.rows",
         "compact.value_move.overrun")
# what the door reads of a shard besides its values
SMALL_LANES = ("key_words_be", "key_len", "seq_hi", "seq_lo", "vtype",
               "val_len")


def stream_of(val_words, val_len) -> np.ndarray:
    """The plain stream: each row's first ceil(len / 4) words, in order."""
    return np.concatenate([np.zeros(0, np.uint32)] + [
        val_words[i, :(int(n) + 3) // 4] for i, n in enumerate(val_len)])


def value_bytes(stream, val_len):
    """Each row's value bytes off a stream, and whether every byte past a
    value's length in its last word is zero."""
    raw = stream.astype("<u4").tobytes()
    out, tails_zero, at = [], True, 0
    for n in map(int, val_len):
        words = (n + 3) // 4
        out.append(raw[4 * at:4 * at + n])
        tails_zero &= raw[4 * at + n:4 * (at + words)] == bytes(4 * words - n)
        at += words
    return out, tails_zero


# ---------------------------------------------------------------------------
# the source
# ---------------------------------------------------------------------------

def file_rows(kind):
    """(rows, whether the file is PLANAR): MixGraph rows with a DELETE
    every seventh, or the same rows at one width."""
    rows = mixgraph_rows(600, 43)
    if kind == "planar_one_width":
        rows = [(k, s, t, v and bytes(37)) for k, s, t, v in rows]
    return rows, kind != "plain_writer"


def write_file(path, kind):
    rows, planar = file_rows(kind)
    if planar:
        fmt.write_sst_from_arrays(lanes_of(rows), len(rows), str(path),
                                  block_entries=64, planar=True)
    else:
        w = SSTWriter(str(path))
        for k, s, t, v in rows:
            w.add(k, s, OpType(t), v)
        w.finish()
    return rows


@pytest.mark.parametrize("kind", ["planar_value_lengths", "planar_one_width",
                                  "plain_writer"])
def test_the_source_decodes_into_the_stream_what_its_rows_hold(
        kind, tmp_path, monkeypatch):
    path = tmp_path / "f.tsst"
    rows = write_file(path, kind)
    reader = SSTReader(str(path))
    if kind == "plain_writer":
        assert "widths_max" in reader.props
    else:
        assert (len(reader.props["planar"]) > 4) == (
            kind == "planar_value_lengths")
    lanes = fmt.read_sst_arrays(reader)
    want = stream_of(lanes["val_words"], lanes["val_len"])
    values, tails_zero = value_bytes(want, lanes["val_len"])
    assert values == [v for *_, v in rows] and tails_zero
    # the door's read through the native source, and through the Python
    # one where the library is not there (a PLANAR file: the Python
    # source of a plain writer's file of 48-byte keys is the tuple path's)
    for source in ("native", "python")[:1 + (kind != "plain_writer")]:
        with monkeypatch.context() as m:
            if source == "python":
                python_codecs(m)
            was = Stats.get().get_counter("codec.python_files")
            stream = fmt.ValueStream(len(want) + 100)
            stream.words[:] = 0xDEADBEEF  # nothing past the values is read
            _parts, got, total, _vw = nc.read_runs_as_lanes(
                [reader], None, value_stream=lambda runs: stream)
            assert Stats.get().get_counter("codec.python_files") == was + (
                source == "python")
            assert total == len(rows) and "val_words" not in got
            for f in SMALL_LANES:
                assert np.array_equal(got[f], lanes[f]), (source, f)
            assert stream.pos == len(want) and got["val_stream"] is stream.words
            assert np.array_equal(stream.words[:len(want)], want), source
            assert not stream.words[len(want):].any()
            # short by one word: the read raises, the stream takes nothing
            short = fmt.ValueStream(len(want) - 1)
            with pytest.raises(fmt.StreamOverrun):
                nc.read_runs_as_lanes([reader], None,
                                      value_stream=lambda runs: short)
            assert short.pos == 0
    reader.close()


def test_runs_concatenate_into_one_stream(tmp_path):
    """A plain writer's file and a PLANAR one, one after the other into
    one stream (the second from the first's end), as the door reads a
    shard's runs; an entry iterable's rows are appended too."""
    readers = []
    for kind in ("plain_writer", "planar_value_lengths"):
        path = tmp_path / f"{kind}.tsst"
        write_file(path, kind)
        readers.append(SSTReader(str(path)))
    extra = [(b"k" * 16, 1, P, b"x" * 7), (b"l" * 16, 2, D, b"")]
    runs = readers + [extra]
    parts, lanes, total, vw = nc.read_runs_as_lanes(runs, None)
    streamed = nc.read_runs_as_lanes(
        runs, None, value_stream=lambda rs: fmt.ValueStream(16 * 2048))
    s_parts, s_lanes, s_total, s_vw = streamed
    assert (s_total, s_vw) == (total, vw) and total == 1202
    assert all("val_words" not in p for p in s_parts)
    for f in SMALL_LANES:
        assert np.array_equal(s_lanes[f], lanes[f]), f
    want = stream_of(lanes["val_words"], lanes["val_len"])
    got = s_lanes["val_stream"]
    assert len(got) == 16 * 2048
    assert np.array_equal(got[:len(want)], want)
    assert not got[len(want):].any()
    offsets = fmt.stream_offsets(lanes["val_len"])
    assert offsets[-1] == len(want) and offsets[0] == 0
    rows = fmt.with_value_rows(dict(s_lanes, val_off=offsets), total)
    assert np.array_equal(rows["val_words"], lanes["val_words"])
    for r in readers:
        r.close()


# ---------------------------------------------------------------------------
# the sink
# ---------------------------------------------------------------------------

def stream_lanes(arrays, count, junk=64):
    """``arrays`` with their values as a stream (junk past its end, which
    the sink must never read) and the rows' word offsets."""
    want = stream_of(arrays["val_words"], arrays["val_len"][:count])
    words = np.concatenate([want, np.full(junk, 0xA5A5A5A5, np.uint32)])
    out = {f: a for f, a in arrays.items() if f != "val_words"}
    out["val_stream"] = words
    out["val_off"] = fmt.stream_offsets(arrays["val_len"][:count])
    return out


@pytest.mark.parametrize("codec", ["native", "python"])
def test_the_sink_writes_from_the_stream_the_file_it_writes_from_rows(
        codec, tmp_path, monkeypatch):
    rows = sorted(mixgraph_rows(2500, 44) + [
        (mix_key(3 * 2500 + 1), 9999, P, b"y" * 1024),
        (mix_key(5 * 2500 + 1), 9998, P, b"z" * 10)])
    arrays, n = lanes_of(rows), len(rows)
    streamed = stream_lanes(arrays, n)
    if codec == "python":
        python_codecs(monkeypatch)
    a, b = str(tmp_path / "rows.tsst"), str(tmp_path / "stream.tsst")
    props = fmt.write_sst_from_arrays(arrays, n, a, block_entries=64,
                                      compression=1, planar=True)
    assert fmt.write_sst_from_arrays(streamed, n, b, block_entries=64,
                                     compression=1, planar=True) == props
    assert open(a, "rb").read() == open(b, "rb").read()
    # through the door's file sink: several files, each a slice of the
    # stream's offsets
    made = {}
    for side, lanes in (("rows", arrays), ("stream", streamed)):
        names = iter(range(100))
        out = nc.write_resolved_lanes(
            lanes, n, lambda: str(tmp_path / f"{side}-{next(names)}.tsst"),
            4096, 1, 10, 64 * 1024)
        made[side] = [open(p, "rb").read() for p, _props in out]
    assert len(made["rows"]) > 2 and made["rows"] == made["stream"]


# ---------------------------------------------------------------------------
# the merge order's move, in the sink
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", ["native", "python"])
@pytest.mark.parametrize("case", ["mixgraph", "one_row", "ten_and_1024",
                                  "nothing"])
def test_the_sink_writes_at_the_source_words_what_it_writes_from_gathered_rows(
        case, codec, tmp_path, monkeypatch):
    """A compaction's output: ``count`` rows in key order, row i keeping
    input row ``val_row[i]``'s value (the merge order). Written from the
    input stream at each source row's words and from the same values
    gathered as rows: the same file."""
    rng = np.random.default_rng(7)
    n = 64
    in_len = rng.integers(10, 120, n).astype(np.uint32)
    in_len[::5] = 0  # DELETEs: no word
    if case == "ten_and_1024":
        in_len[:] = np.where(np.arange(n) % 2, 10, 1024)
        in_len[3] = 0
    in_type = np.where(in_len == 0, D, P).astype(np.uint32)
    in_type[::10] = P  # and an empty PUT now and then
    count = {"mixgraph": 40, "one_row": 1, "ten_and_1024": 20,
             "nothing": 0}[case]
    val_row = rng.permutation(n)[:count].astype(np.int32)
    rows = np.zeros((n, 256), np.uint32)
    for i, w in enumerate((in_len + 3) // 4):
        rows[i, :w] = rng.integers(1, 2**32, w, dtype=np.uint32)
        tail = int(in_len[i]) % 4
        if tail:  # the bytes past a value's length are zero
            rows[i, w - 1] &= (1 << 8 * tail) - 1
    stream = np.concatenate([stream_of(rows, in_len),
                             np.full(64, 0xA5A5A5A5, np.uint32)])
    out = [(mix_key(3 * i + 1), 100 + i, int(in_type[r]), b"")
           for i, r in enumerate(val_row)]
    gathered = lanes_of(out or [(mix_key(1), 1, P, b"")])
    gathered = {f: a[:count] for f, a in gathered.items()}
    gathered["vtype"] = in_type[val_row]
    gathered["val_len"] = in_len[val_row]
    gathered["val_words"] = rows[val_row]
    at_source = {f: a for f, a in gathered.items() if f != "val_words"}
    at_source["val_stream"] = stream
    at_source["val_off"] = fmt.stream_offsets(in_len)[val_row]
    if codec == "python":
        python_codecs(monkeypatch)
    # the codecs without a stream input read the same rows off it
    widest = max(2, int(((gathered["val_len"] + 3) // 4).max(initial=0)))
    assert np.array_equal(fmt.stream_rows(at_source, count),
                          gathered["val_words"][:, :widest])
    a, b = str(tmp_path / "rows.tsst"), str(tmp_path / "stream.tsst")
    props = fmt.write_sst_from_arrays(gathered, count, a, block_entries=16,
                                      compression=1, planar=True)
    assert fmt.write_sst_from_arrays(at_source, count, b, block_entries=16,
                                     compression=1, planar=True) == props
    assert (props is None) == (count == 0)
    if count:
        assert open(a, "rb").read() == open(b, "rb").read()
        reader = SSTReader(b)
        assert [(k, v) for k, _s, _t, v in reader.iterate()] == [
            (mix_key(3 * i + 1), rows[r].astype("<u4").tobytes()[
                :int(in_len[r])] if in_type[r] != D else b"")
            for i, r in enumerate(val_row)]
        reader.close()


def test_a_flagged_stream_place_recomputes_on_the_host(tmp_path):
    """A place the kernel flags is recomputed on the host from the batch
    it was launched from; a stream place's batch carries no value rows,
    and its recompute writes the file its rows' recompute writes."""
    from rocksplicator_tpu.ops.compaction_kernel import MergeKind
    from rocksplicator_tpu.storage.bloom import num_words_for

    rows = mixgraph_rows(300, 45)
    rng = random.Random(45)
    rows += [(k, s + 1000, P, mix_value(rng)) for k, s, _t, _v in rows[::3]]
    lanes = lanes_of(rows)
    stream = fmt.ValueStream(cs.STREAM_WORDS_PER_ROW * 1024)
    stream.append_rows(lanes["val_words"], lanes["val_len"])
    streamed = {f: a for f, a in lanes.items() if f != "val_words"}
    streamed["val_stream"] = stream.finish()
    svc = cs.TpuCompactionService()
    files = []
    for side in (lanes, streamed):
        res = svc._cpu_recompute(
            cs._LaneBatch(side), MergeKind.NONE, True,
            num_words_for(len(rows), 10), return_arrays=True)
        files.append(str(tmp_path / f"{len(files)}.tsst"))
        assert fmt.write_sst_from_arrays(
            res["arrays"], res["count"], files[-1], block_entries=64,
            planar=True) is not None
    a, b = (open(f, "rb").read() for f in files)
    assert a == b
    reader = SSTReader(files[1])
    assert [(k, int(t), v) for k, _s, t, v in reader.iterate()] == \
        plain_reference(rows)
    reader.close()


# ---------------------------------------------------------------------------
# the served door
# ---------------------------------------------------------------------------

KEYS = [k for k, *_ in mixgraph_rows(220, 5, False)]


def make_shard(path, keys, seed, sizes=None, keep=False):
    """A MixGraph refresh unit's shard before its compaction: live PUTs
    and DELETEs flushed (a PLANAR file), under a plain writer's bulk file
    of every second key, so that keys repeat across the runs; values of
    MixGraph widths, or ``sizes`` in turn. ``keep``: tombstones are
    kept at the bottom. Returns the DB and every entry written."""
    rng = random.Random(seed)
    db = DB(str(path), DBOptions(bits_per_key=10, allow_ingest_behind=keep,
                                 disable_auto_compaction=True))
    entries, seq = [], 0
    wb = WriteBatch()
    for n in range(len(keys)):
        k = rng.choice(keys)
        seq += 1
        if n % 9 == 4:
            wb.delete(k)
            entries.append((k, seq, D, b""))
        else:
            v = mix_value(rng, sizes[n % len(sizes)] if sizes else None)
            wb.put(k, v)
            entries.append((k, seq, P, v))
    db.write(wb)
    db.flush()
    sst = str(path) + ".bulk.tsst"
    w = SSTWriter(sst)
    bulk = []
    for i, k in enumerate(keys[::2]):
        v = mix_value(rng, sizes[(i + 1) % len(sizes)] if sizes else None)
        w.add(k, 0, OpType.PUT, v)
        bulk.append((k, v))
    w.finish()
    db.ingest_external_file([sst], move_files=True, allow_global_seqno=True)
    entries += [(k, seq + 1, P, v) for k, v in bulk]
    return db, entries


def moves():
    return [Stats.get().get_counter(n) for n in MOVES]


def compact_side(root, monkeypatch, keys, sizes=None, keep=False, shards=2,
                 rows=False):
    """``shards`` twin shards through the door; ``rows``: every place
    made to keep its rows (no run says anything of its values). Returns
    the DBs with their entries, the trace's annotations by span name and
    what the three move counters gained."""
    with monkeypatch.context() as m:
        if rows:
            m.setattr(cs, "stream_may_carry", lambda reader: None)
        made = [make_shard(root / f"db{n}", keys, 60 + n, sizes, keep)
                for n in range(shards)]
        was = moves()
        (handled, remaining), spans = compact_traced(
            [(f"db{n}", db) for n, (db, _e) in enumerate(made)])
    assert sorted(handled) == [f"db{n}" for n in range(shards)]
    assert not remaining
    return made, spans, [a - b for a, b in zip(moves(), was)]


# a 1,024-byte value in 32 beside 10-byte (or empty) ones: ~11 words a
# row, inside the stream's 16 (one in two is ~130: the overrun below)
RARE_LARGE = (1024,) + (10,) * 31
CASES = {
    "mixgraph": dict(keys=KEYS),
    "ten_and_1024": dict(keys=KEYS, sizes=RARE_LARGE),
    "empty_beside_large": dict(keys=KEYS, sizes=(1024,) + (0,) * 31),
    "one_row": dict(keys=KEYS[7:8], sizes=(10, 100)),
    "tombstones_kept": dict(keys=KEYS, keep=True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_stream_path_writes_the_row_paths_files(case, tmp_path,
                                                     monkeypatch):
    kw = CASES[case]
    fell = dict(host_fallback_counts())
    stream_side = compact_side(tmp_path / "stream", monkeypatch, **kw)
    rows_side = compact_side(tmp_path / "rows", monkeypatch, rows=True, **kw)
    assert host_fallback_counts() == fell
    for (made, spans, gained), move in ((stream_side, "stream"),
                                        (rows_side, "rows")):
        (launch,) = spans["tpu.compact_stream"]
        assert launch["value_move"] == move and launch["value_path"] == "index"
        assert gained == ([2, 0, 0] if move == "stream" else [0, 2, 0])
        assert all(s["value_move"] == move for s in spans["tpu.lanes.decode"])
        # a stream place's values never cross the seam: no span moves
        # them, and the launch moves none
        crossed = [n for n in ("tpu.h2d.values", "tpu.readback.values")
                   if spans.get(n)]
        if move == "stream":
            assert launch["value_bytes_padded"] == 0 and not crossed
        else:
            assert launch["value_bytes_padded"] > 0
            assert crossed == ["tpu.h2d.values", "tpu.readback.values"]
        for db, entries in made:
            check_db(db, kw["keys"], entries, drop=not kw.get("keep"))
    # the same launch width, the same value bytes, and the same files
    s_launch, r_launch = (side[1]["tpu.compact_stream"][0]
                          for side in (stream_side, rows_side))
    assert s_launch["val_words"] == r_launch["val_words"]
    assert s_launch["value_bytes"] == r_launch["value_bytes"]
    for (a, _ea), (b, _eb) in zip(stream_side[0], rows_side[0]):
        assert [open(p, "rb").read() for p in db_files(a)] == \
            [open(p, "rb").read() for p in db_files(b)]
        a.close()
        b.close()


def test_a_stream_that_would_overrun_keeps_the_rows_and_is_counted(
        tmp_path, monkeypatch):
    """Every second value 1,024 bytes: ~130 words a row, past the
    stream's 16. The places keep their rows, and say so."""
    made, spans, gained = compact_side(tmp_path, monkeypatch, KEYS,
                                       sizes=(10, 1024))
    assert gained == [0, 2, 2]
    (launch,) = spans["tpu.compact_stream"]
    assert launch["value_move"] == "rows" and launch["val_words"] == 256
    assert all(s["value_move"] == "rows" for s in spans["tpu.lanes.decode"])
    for db, entries in made:
        check_db(db, KEYS, entries)
        db.close()


def test_stream_and_row_places_never_share_a_launch(tmp_path, monkeypatch):
    """A MixGraph shard (a stream) beside one of 64-byte values (one
    width: its rows already are its values) launch apart."""
    was = moves()
    shards = [make_shard(tmp_path / "mixed", KEYS, 70),
              make_shard(tmp_path / "one_width", KEYS, 71, sizes=(64, 64))]
    (handled, remaining), spans = compact_traced(
        [(f"db{n}", db) for n, (db, _e) in enumerate(shards)])
    assert sorted(handled) == ["db0", "db1"] and not remaining
    launches = spans["tpu.compact_stream"]
    assert sorted((a["value_move"], a["shards"]) for a in launches) == [
        ("rows", 1), ("stream", 1)]
    assert [a - b for a, b in zip(moves(), was)] == [1, 1, 0]
    for db, entries in shards:
        check_db(db, KEYS, entries)
        db.close()
