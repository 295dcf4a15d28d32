"""WriteBatch: the unit of atomic writes and of replication shipping.

Reference: rocksdb::WriteBatch. The replication layer ships raw batch bytes
to followers (rocksdb_replicator/rocksdb_wrapper.cpp:13-28 deserializes the
raw WriteBatch, re-stamps the timestamp, applies locally), and the leader
stamps a wall-clock timestamp into each batch via ``PutLogData``
(replicated_db.cpp:115-117) which consumes no sequence number. This module
keeps those contracts.

Wire format (little-endian):
    u32 num_ops
    per op:
        u8  op_type
        u32 key_len,  key bytes     (LOG_DATA: key empty)
        u32 val_len,  val bytes

PUT/DELETE/MERGE consume one sequence number each; LOG_DATA consumes none
(mirrors RocksDB, and the engine-assumption tests pin this).
"""

from __future__ import annotations

import enum
import struct
import time
from array import array
from typing import Iterator, List, NamedTuple, Optional, Sequence, Tuple

from .errors import Corruption

_U32 = struct.Struct("<I")
_OPHEAD = struct.Struct("<BI")
_OP_MIN = _OPHEAD.size + _U32.size  # an op of empty key and empty value


class OpType(enum.IntEnum):
    PUT = 1
    DELETE = 2
    MERGE = 3
    LOG_DATA = 4


# Log-data payloads written by the replication layer: 8-byte little-endian
# wall-clock milliseconds (replicated_db.cpp stamps ms for the lag metric).
_TS = struct.Struct("<Q")

_Op = Tuple[OpType, bytes, bytes]
_LOG_DATA = int(OpType.LOG_DATA)
_LOG_DATA_BYTE = bytes((_LOG_DATA,))


class BatchColumns(NamedTuple):
    """A batch's sequence-consuming operations as the memtable's columns
    (``MemTable.apply_batch``); ``LOG_DATA`` is not among them."""

    count: int
    keys: Sequence[bytes]
    vals: Sequence[bytes]
    vtypes: bytes       # an OpType a row
    key_bytes: bytes    # the keys back to back
    klens: array        # "I"
    vlens: array        # "I"
    # the pass that made the columns. An ARRIVED frame (``decode_batch``)
    # is read column-wise, by what its own strides allow: "bulk", a frame
    # of ONE stride, off a structured view of that stride with no per-op
    # step at all; "indexed", any other frame, off one index of its op
    # headers (``_index_ops``). None: a BUILT batch, walked tuple by
    # tuple (``_columns_of``), which no arrived frame ever is.
    frame_pass: Optional[str]


def _columns_of(ops: List[_Op]) -> BatchColumns:
    """The general pass, a BUILT batch's: one walk over its tuples."""
    live = [t for t in ops if t[0] is not OpType.LOG_DATA]
    keys = [t[1] for t in live]
    vals = [t[2] for t in live]
    return BatchColumns(
        len(live), keys, vals, bytes(t[0] for t in live), b"".join(keys),
        array("I", map(len, keys)), array("I", map(len, vals)), None)


class WriteBatch:
    """Built (``put`` / ``merge`` / ``delete``: a list of tuples) or
    ARRIVED (``decode_batch``: the encoded frame, kept as it came). An
    arrived frame of any shape was read column-wise once and never
    becomes tuples unless someone asks for ``ops()``. Three passes make
    ``columns()``, and what the batch is decides which: a frame of ONE
    stride — every operation a PUT / DELETE / MERGE of one key width
    and one value width, then nothing but ``LOG_DATA`` — is read off a
    structured view of that stride (``"bulk"``); any other frame off
    one index of its op headers (``"indexed"``); a built batch is
    walked tuple by tuple (``None``). ``put_log_data`` (the leader's
    time stamp) appends to the frame, so ``encode()`` hands back the
    client's own bytes plus the stamp. Any other mutation thaws the
    batch into its tuples."""

    __slots__ = ("_built", "_raw", "_cols")

    def __init__(self) -> None:
        self._built: Optional[List[_Op]] = []
        self._raw: Optional[bytes] = None
        # an arrived frame's columns (decode_batch), as long as it has
        # its frame
        self._cols: Optional[BatchColumns] = None

    def _thaw(self) -> List[_Op]:
        """An arrived batch gives up its frame for its tuples."""
        self._built = self._tuples()
        self._raw = self._cols = None
        return self._built

    def _set_ops(self, ops: List[_Op]) -> None:
        self._built, self._raw, self._cols = ops, None, None

    # the tuple list under its old name (chipbench's rehearsal cuts a
    # decoded batch in half through it); to read it is to mutate
    _ops = property(_thaw, _set_ops)

    def _append(self, op: _Op) -> "WriteBatch":
        if self._raw is not None:
            self._thaw()
        self._built.append(op)
        return self

    def put(self, key: bytes, value: bytes) -> "WriteBatch":
        return self._append((OpType.PUT, bytes(key), bytes(value)))

    def delete(self, key: bytes) -> "WriteBatch":
        return self._append((OpType.DELETE, bytes(key), b""))

    def merge(self, key: bytes, operand: bytes) -> "WriteBatch":
        return self._append((OpType.MERGE, bytes(key), bytes(operand)))

    def put_log_data(self, blob: bytes) -> "WriteBatch":
        blob = bytes(blob)
        if self._raw is None:
            self._built.append((OpType.LOG_DATA, b"", blob))
            return self
        # an arrived batch stays a frame: one more op behind the others
        self._raw = b"".join((
            _U32.pack(len(self) + 1), memoryview(self._raw)[_U32.size:],
            _OPHEAD.pack(OpType.LOG_DATA, 0), _U32.pack(len(blob)), blob))
        if self._built is not None:
            self._built.append((OpType.LOG_DATA, b"", blob))
        return self

    # -- replication timestamp helpers ------------------------------------

    def stamp_timestamp_ms(self, now_ms: Optional[int] = None) -> "WriteBatch":
        """Leader-side stamp (replicated_db.cpp:115-117)."""
        ts = int(time.time() * 1000) if now_ms is None else now_ms
        return self.put_log_data(_TS.pack(ts))

    def extract_timestamp_ms(self) -> Optional[int]:
        """Last LOG_DATA 8-byte timestamp, if any (follower lag metric)."""
        if self._raw is not None:
            return scan_batch_meta(self._raw)[1]
        for op, _key, val in reversed(self._built):
            if op is OpType.LOG_DATA and len(val) == _TS.size:
                return _TS.unpack(val)[0]
        return None

    def strip_log_data(self) -> "WriteBatch":
        """Copy without LOG_DATA ops (follower re-stamps its own)."""
        out = WriteBatch()
        out._built = [
            t for t in self._tuples() if t[0] is not OpType.LOG_DATA]
        return out

    # -- introspection ----------------------------------------------------

    def _tuples(self) -> List[_Op]:
        """Read-only: an arrived batch keeps its frame."""
        if self._built is None:
            self._built = _decode_ops(self._raw)
        return self._built

    def count(self) -> int:
        """Number of sequence-number-consuming ops."""
        if self._cols is not None:
            return self._cols.count
        return sum(1 for op, _k, _v in self._built if op is not OpType.LOG_DATA)

    def __len__(self) -> int:
        if self._built is not None:
            return len(self._built)
        return _U32.unpack_from(self._raw, 0)[0]

    def ops(self) -> Iterator[_Op]:
        return iter(self._tuples())

    def columns(self) -> BatchColumns:
        """What ``MemTable.apply_batch`` takes."""
        if self._cols is not None:
            return self._cols
        return _columns_of(self._built)

    def byte_size(self) -> int:
        if self._raw is not None:
            return len(self._raw)
        return _U32.size + sum(
            _OPHEAD.size + _U32.size + len(k) + len(v)
            for _op, k, v in self._built
        )

    # -- serialization ----------------------------------------------------

    def encode(self) -> bytes:
        if self._raw is not None:
            return self._raw
        parts = [_U32.pack(len(self._built))]
        for op, key, val in self._built:
            parts.append(_OPHEAD.pack(op, len(key)))
            parts.append(key)
            parts.append(_U32.pack(len(val)))
            parts.append(val)
        return b"".join(parts)


def _uniform_rows(buf: bytes, num_ops: int):
    """The frame's leading ops of ONE stride (the first op's key and
    value widths, any of PUT / DELETE / MERGE) as a structured view
    ``(t, kl, k, vl)`` a row, no byte copied; None where there is none
    (an empty key, as LOG_DATA's, starts none)."""
    import numpy as np

    try:
        _t, klen = _OPHEAD.unpack_from(buf, _U32.size)
        (vlen,) = _U32.unpack_from(buf, _U32.size + _OPHEAD.size + klen)
    except struct.error:
        return None
    stride = _OPHEAD.size + klen + _U32.size + vlen
    rows = min(num_ops, (len(buf) - _U32.size) // stride)
    if klen == 0 or rows <= 0:
        return None
    rec = np.frombuffer(buf, np.dtype({
        "names": ["t", "kl", "k", "vl"],
        "formats": ["u1", "<u4", f"V{klen}", "<u4"],
        "offsets": [0, 1, _OPHEAD.size, _OPHEAD.size + klen],
        "itemsize": stride}), rows, _U32.size)
    # u1 wraps: 0 - 1 = 255, so this is 1 <= t <= 3
    ok = (rec["kl"] == klen) & (rec["vl"] == vlen) & (rec["t"] - 1 < 3)
    if not ok.all():
        rows = int(ok.argmin())
        if rows == 0:
            return None
        rec = rec[:rows]
    return rec, klen, vlen, stride


def _walk_ops(buf: bytes, pos: int, num_ops: int):
    """``_index_ops`` in Python, the native call's reference: ONE loop
    over the headers for where each op starts, the columns read off
    those places by numpy."""
    import numpy as np

    ophead, u32 = _OPHEAD.unpack_from, _U32.unpack_from
    starts = []
    try:
        for _ in range(num_ops):
            starts.append(pos)
            pos += _OP_MIN + ophead(buf, pos)[1]
            pos += u32(buf, pos - _U32.size)[0]
    except struct.error as e:
        raise Corruption(f"bad batch: {e}") from e
    if pos > len(buf):
        raise Corruption("bad batch: an op runs past the frame")
    at = np.array(starts, np.int64)
    data = np.frombuffer(buf, np.uint8)
    types = data[at]
    # u1 wraps: 0 - 1 = 255, so this is 1 <= t <= 4
    if not (types - 1 < 4).all():
        raise Corruption("bad batch: op type outside 1-4")
    cols = np.empty((4, num_ops), np.int64)
    cols[0] = at + _OPHEAD.size
    cols[1] = data[(at + 1)[:, None] + np.arange(_U32.size)].view("<u4")[:, 0]
    cols[2] = cols[0] + cols[1] + _U32.size
    cols[3] = np.append(at[1:], pos) - cols[2]  # up to where the next starts
    return types, cols, pos


def _index_ops(buf: bytes, pos: int, num_ops: int):
    """The headers of ``num_ops`` ops from ``pos`` on, indexed ONCE, in
    frame order: ``(types, cols, end)`` — a u8 op type an op; the
    (4, num_ops) int64 columns key offset, key length, value offset,
    value length; the position behind the last op. ``Corruption`` for a
    type outside 1-4, an op that runs past the frame, a frame too short
    for its op count. One native call where the library is loaded (it
    keeps the GIL: microseconds of C), else the same walk in Python."""
    if num_ops > (len(buf) - pos) // _OP_MIN:
        raise Corruption("bad batch: shorter than its op count")
    from .native.binding import get_native

    lib = get_native()
    if lib is not None:
        return lib.batch_index(buf, pos, num_ops)
    return _walk_ops(buf, pos, num_ops)


def _read_frame(buf: bytes):
    """An arrived frame by its op headers, validated to its last byte:
    ``(uniform, index)``. A frame of ONE stride gives its rows
    (``_uniform_rows``) and the index of the ``LOG_DATA`` behind them
    (None where nothing is); any other frame None and the index of
    every op."""
    if len(buf) < _U32.size:
        raise Corruption("batch too short")
    (num_ops,) = _U32.unpack_from(buf, 0)
    uniform = _uniform_rows(buf, num_ops)
    index, end = None, _U32.size
    if uniform is not None:
        end += len(uniform[0]) * uniform[3]
        behind = num_ops - len(uniform[0])
        # what stands behind the rows is indexed alone only where it can
        # be the stamp (a follower's update, a WAL record)
        if behind and buf[end:end + 1] == _LOG_DATA_BYTE:
            index = _index_ops(buf, end, behind)
            if index[0].tobytes() != _LOG_DATA_BYTE * behind:
                uniform = None
        elif behind:
            uniform = None
    if uniform is None:
        index = _index_ops(buf, _U32.size, num_ops)
    if index is not None:
        end = index[2]
    if end != len(buf):
        raise Corruption("trailing bytes in batch")
    return uniform, index


def scan_batch_meta(data) -> Tuple[int, Optional[int]]:
    """(count, timestamp_ms) from op HEADERS only — no key/value slicing,
    no WriteBatch construction. The replication serve path and the WAL's
    straddler checks need exactly these facts per update: a frame of one
    stride gives them from one array comparison, any other from the
    index of its headers. Validates as ``decode_batch`` does."""
    import numpy as np

    buf = bytes(data)
    uniform, index = _read_frame(buf)
    count = 0 if uniform is None else len(uniform[0])
    if index is None:
        return count, None
    types, cols, _end = index
    stamps = np.flatnonzero((types == _LOG_DATA) & (cols[3] == _TS.size))
    ts = _TS.unpack_from(buf, cols[2, stamps[-1]])[0] if len(stamps) else None
    return count + int(np.count_nonzero(types != _LOG_DATA)), ts


def _decode_ops(buf: bytes) -> List[_Op]:
    """Every op of a frame as a tuple, validated to the last byte."""
    if len(buf) < _U32.size:
        raise Corruption("batch too short")
    (num_ops,) = _U32.unpack_from(buf, 0)
    pos = _U32.size
    ops: List[_Op] = []
    try:
        for _ in range(num_ops):
            op_raw, key_len = _OPHEAD.unpack_from(buf, pos)
            pos += _OPHEAD.size
            key = buf[pos:pos + key_len]
            if len(key) != key_len:
                raise Corruption("truncated key")
            pos += key_len
            (val_len,) = _U32.unpack_from(buf, pos)
            pos += _U32.size
            val = buf[pos:pos + val_len]
            if len(val) != val_len:
                raise Corruption("truncated value")
            pos += val_len
            ops.append((OpType(op_raw), key, val))
    except (struct.error, ValueError) as e:
        raise Corruption(f"bad batch encoding: {e}") from e
    if pos != len(buf):
        raise Corruption("trailing bytes in batch")
    return ops


def _u32s(col) -> array:
    out = array("I")
    out.frombytes(col.astype("<u4").tobytes())
    return out


def _bulk_columns(buf: bytes, uniform) -> BatchColumns:
    """The rows of a frame of ONE stride, read column-wise: numpy over
    the stride for the headers, the types and the key bytes, every key
    and value object sliced once by one ``iter_unpack``."""
    rec, klen, vlen, stride = uniform
    rows = len(rec)
    end = _U32.size + rows * stride
    keys, vals = zip(*struct.iter_unpack(
        f"{_OPHEAD.size}x{klen}s{_U32.size}x{vlen}s",
        memoryview(buf)[_U32.size:end]))
    return BatchColumns(
        rows, keys, vals, rec["t"].tobytes(), rec["k"].tobytes(),
        array("I", (klen,)) * rows, array("I", (vlen,)) * rows, "bulk")


def _indexed_columns(buf: bytes, index) -> BatchColumns:
    """Any frame's columns off the index of its op headers: the types by
    one take, the lengths as they stand, the key and value objects (the
    memtable's dict and value list hold objects) by one slice each, the
    key bytes by one join. ``LOG_DATA`` is dropped."""
    types, cols, _end = index
    live = types != _LOG_DATA
    if not live.all():
        types, cols = types[live], cols[:, live]
    key_off, key_len, val_off, val_len = cols
    keys = [buf[a:b] for a, b in zip(
        key_off.tolist(), (key_off + key_len).tolist())]
    vals = [buf[a:b] for a, b in zip(
        val_off.tolist(), (val_off + val_len).tolist())]
    return BatchColumns(
        len(keys), keys, vals, types.tobytes(), b"".join(keys),
        _u32s(key_len), _u32s(val_len), "indexed")


def decode_batch(data) -> WriteBatch:
    """The ONE parse of an arrived frame: validated to the last byte
    (``Corruption`` otherwise), read column-wise whatever its shape
    (``BatchColumns.frame_pass`` says how), and the batch keeps the
    frame: tuples exist only once someone asks for ``ops()``."""
    batch = WriteBatch()
    batch._raw = raw = bytes(data)
    batch._built = None
    uniform, index = _read_frame(raw)
    batch._cols = (_indexed_columns(raw, index) if uniform is None
                   else _bulk_columns(raw, uniform))
    return batch
