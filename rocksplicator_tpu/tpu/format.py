"""Vectorized array→SST sink for the TPU pipeline.

The kernel emits struct-of-array lanes; turning them into SST files by
materializing Python tuples and re-serializing per entry would dominate the
end-to-end time. For uniform-width rows (the counter workload and most
fixed-schema KV), the block bytes assemble as ONE numpy matrix fill — no
per-entry Python — and the TPU-built bloom bitmap writes straight into the
file (byte-identical format, so readers can't tell).

The PLANAR sink and source (``write_sst_from_arrays(planar=True)``,
``read_sst_arrays``) also take rows whose KEYS differ in length, 1 to
``PLANAR_MAX_KLEN`` (48) bytes mixed in any proportion (counter names
``counter-<n>``): ``planar_widths`` then says ``mixed``, a block whose
rows differ carries a key-length plane behind a header flag
(storage/planar.py), the file's ``planar`` prop gives its widest key
and a fourth member 1. Rows whose VALUES differ in width (db_bench
MixGraph's 10 to 1,024 bytes) do the same with a value-length plane and
a fifth member (``vmixed``). Keys longer than 24 bytes take key lanes of
12 words (``key_word_cols``). Rows of one key length and one value
width write the file they always wrote, byte for byte.

Values may also travel as ONE word stream instead of zero-tailed rows
(``ValueStream``: the served device door's move of values that differ
in width): the source decodes into it, the sink encodes from it with
each row's word offsets, and the file is the same.
"""

from __future__ import annotations

import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..observability.context import current_span
from ..storage.bloom import BloomFilter
from ..storage.errors import Corruption
from ..storage.native.binding import get_file_codecs
from ..storage.planar import (KEY_COLS, PLANAR_MAX_KLEN, PLANAR_MAX_VLEN,
                              decode_planar_block, encode_planar_block,
                              key_word_cols, plane_words, planar_props,
                              planar_props_mixed, planar_props_vmixed)
from ..storage import rlz
from ..storage.sst import (BLOCK_PLANAR, BLOCK_PLANAR_RLZ,
                           BLOCK_PLANAR_ZLIB, COMPRESSION_NONE,
                           COMPRESSION_RLZ, COMPRESSION_ZLIB,
                           ENTRY_FIXED_OVERHEAD, SSTWriter)
from ..utils.checksum import poly_checksum_words
from ..utils.stats import Stats

_ENTRY_FIXED_OVERHEAD = ENTRY_FIXED_OVERHEAD

# Whole-file native codecs (storage/native tsst_planar_encode_file /
# tsst_decode_file_lanes): a file's blocks are encoded, or decoded, by ONE
# call that holds no GIL, where the Python codecs below make a dozen
# interpreter trips per block — eight pool threads at once serialise on
# those. Which codec takes a file is decided by what the code sees: the
# library with the symbols, block codecs it knows, props it understands.
# Everything else stays with the Python codecs. Same bytes, same lanes,
# same checksums verified (tests/test_native.py).
_ROW_CODECS = (COMPRESSION_NONE, COMPRESSION_ZLIB, COMPRESSION_RLZ)
_PLANAR_CODECS = (BLOCK_PLANAR, BLOCK_PLANAR_ZLIB, BLOCK_PLANAR_RLZ)
_NOT_TAKEN = object()  # the native source leaves the file to Python


class StreamOverrun(Exception):
    """The values do not fit the ``ValueStream`` they are decoded into."""


class ValueStream:
    """A place's values as ONE word stream: row by row, each value from
    a 4-byte boundary, ``ceil(val_len / 4)`` words, the bytes past its
    length in its last word zero (a DELETE: no word). A row's word
    offset follows from the ``val_len`` of the rows before it
    (``stream_offsets``), so no offset lane exists. ``words``: the
    buffer, ``capacity`` words (``np.empty``: ``finish`` zeroes what no
    value took); ``pos``: the words taken."""

    __slots__ = ("words", "pos")

    def __init__(self, capacity: int):
        self.words = np.empty(capacity, dtype=np.uint32)
        self.pos = 0

    def append_rows(self, val_words: np.ndarray,
                    val_len: np.ndarray) -> None:
        """Rows of values (zero-tailed ``(n, W)`` words) onto the stream;
        ``StreamOverrun`` where they do not fit."""
        nw = (np.asarray(val_len, dtype=np.int64) + 3) // 4
        need = int(nw.sum())
        if self.pos + need > len(self.words):
            raise StreamOverrun(
                f"{need} words at {self.pos} of {len(self.words)}")
        taken = np.arange(val_words.shape[1]) < nw[:, None]
        self.words[self.pos:self.pos + need] = val_words[taken]
        self.pos += need

    def finish(self) -> np.ndarray:
        """The buffer, zero past the last value."""
        self.words[self.pos:] = 0
        return self.words


def stream_offsets(val_len: np.ndarray) -> np.ndarray:
    """(n + 1,) u64: the word offset of each row's value in its
    ``ValueStream``, and the words in all."""
    off = np.zeros(len(val_len) + 1, dtype=np.uint64)
    np.cumsum((np.asarray(val_len, dtype=np.uint64) + np.uint64(3))
              // np.uint64(4), out=off[1:])
    return off


def stream_rows(arrays: Dict[str, np.ndarray], count: int) -> np.ndarray:
    """The first ``count`` rows' values of stream lanes (``val_stream``,
    and ``val_off``, the word at which each row's value starts) as
    zero-tailed ``(count, W)`` rows, W the widest value's words (2 at
    least): what a codec without a stream input takes. Row r's value is
    ``ceil(val_len / 4)`` words from ``val_off[r]`` on, a DELETE none:
    the offsets need not follow one another (a compaction's output rows
    read their values where its input rows left them)."""
    nw = np.where(arrays["vtype"][:count] == 2, 0,
                  (arrays["val_len"][:count].astype(np.int64) + 3) // 4)
    rows = np.zeros((count, max(2, int(nw.max(initial=0)))), np.uint32)
    taken = np.arange(rows.shape[1]) < nw[:, None]
    # each taken word's source: its row's start, plus its place in the row
    starts = np.cumsum(nw) - nw
    src = np.arange(int(nw.sum())) + np.repeat(
        arrays["val_off"][:count].astype(np.int64) - starts, nw)
    rows[taken] = arrays["val_stream"][src]
    return rows


def with_value_rows(arrays: Dict[str, np.ndarray],
                    count: int) -> Dict[str, np.ndarray]:
    """``arrays`` as they are where their values are rows, else (stream
    lanes) with ``val_words`` rows in place of the stream."""
    if "val_off" not in arrays:
        return arrays
    rows = {f: a for f, a in arrays.items()
            if f not in ("val_stream", "val_off")}
    rows["val_words"] = stream_rows(arrays, count)
    return rows


def stream_may_carry(reader) -> Optional[Tuple[int, bool]]:
    """What a file's props say of its values before it is read:
    ``(widest value bytes, whether its live values differ in width)``,
    or None where they say nothing the native source would take (no
    library, foreign props, a row-format file without the sink's or the
    plain writer's width prop). A PLANAR file says both (its fifth
    member); the plain writer's ``widths_max`` says the widest alone
    (not that its values differ)."""
    if get_file_codecs() is None or not reader.num_entries:
        return None
    props = reader.props
    try:
        if props.get("planar"):
            p = props["planar"]
            return int(p[1]), planar_props_vmixed(p)
        widths = props.get("uniform") or props.get("widths_max")
        if widths:
            return int(widths[1]), False
    except (TypeError, ValueError, IndexError, KeyError):
        pass
    return None


def _count_codec(native: bool) -> None:
    """One file through a lane codec: counted by which codec took it
    (``codec.native_files`` / ``codec.python_files``), and noted on the
    open span as ``native`` — 1 while every file under it went native."""
    Stats.get().incr(
        "codec.native_files" if native else "codec.python_files")
    span = current_span()
    if span is not None and span.sampled:
        span.annotate(
            native=int(native and span.annotations.get("native", 1)))


def uniform_widths(arrays: Dict[str, np.ndarray], count: int):
    """(key_len, val_len) if all live rows share widths, else None."""
    if count == 0:
        return None
    kl = arrays["key_len"][:count]
    vl = arrays["val_len"][:count]
    k0, v0 = int(kl[0]), int(vl[0])
    if (kl == k0).all() and (vl == v0).all() and 0 < k0 <= 24:
        return k0, v0
    return None


def encode_uniform_block(arrays: Dict[str, np.ndarray], start: int, end: int,
                         klen: int, vlen: int) -> bytes:
    """Vectorized entry packing for rows [start, end) with fixed widths."""
    n = end - start
    stride = _ENTRY_FIXED_OVERHEAD + klen + vlen
    out = np.zeros((n, stride), dtype=np.uint8)
    pos = 0
    out[:, pos:pos + 4] = (
        np.full(n, klen, dtype="<u4").view(np.uint8).reshape(n, 4))
    pos += 4
    key_bytes = (
        np.ascontiguousarray(arrays["key_words_be"][start:end].astype(">u4"))
        .view(np.uint8).reshape(n, 24)
    )
    out[:, pos:pos + klen] = key_bytes[:, :klen]
    pos += klen
    seqs = (
        arrays["seq_hi"][start:end].astype(np.uint64) << np.uint64(32)
    ) | arrays["seq_lo"][start:end].astype(np.uint64)
    out[:, pos:pos + 8] = seqs.astype("<u8").view(np.uint8).reshape(n, 8)
    pos += 8
    out[:, pos] = arrays["vtype"][start:end].astype(np.uint8)
    pos += 1
    out[:, pos:pos + 4] = (
        np.full(n, vlen, dtype="<u4").view(np.uint8).reshape(n, 4))
    pos += 4
    if vlen:
        val_bytes = (
            np.ascontiguousarray(arrays["val_words"][start:end].astype("<u4"))
            .view(np.uint8).reshape(n, -1)
        )
        out[:, pos:pos + vlen] = val_bytes[:, :vlen]
    return out.tobytes()


def read_sst_arrays(reader, stream: Optional[ValueStream] = None
                    ) -> Optional[Dict[str, np.ndarray]]:
    """Vectorized SOURCE: decode a sink-written uniform-stride TSST file
    straight into kernel lanes (no per-entry Python). Returns the arrays
    dict (+ implicit count = rows) or None when the file lacks the uniform
    property (flush-written / foreign files use the tuple path).
    ``stream``: the values go onto it (the native source writes them
    there; a Python source's rows are appended) and the lanes have no
    ``val_words``; ``StreamOverrun`` where they do not fit, and a file
    that gives None leaves the stream as it was."""
    planar = bool(reader.props.get("planar"))
    lanes = _read_lanes_native(reader, planar, stream)
    _count_codec(lanes is not _NOT_TAKEN and lanes is not None)
    if lanes is _NOT_TAKEN:
        lanes = (_read_planar_arrays(reader) if planar
                 else _read_uniform_arrays(reader))
        if lanes is not None and stream is not None:
            stream.append_rows(lanes.pop("val_words"), lanes["val_len"])
    # ingestion-time global seqno overrides per-entry seqs, same as the
    # reader's _effective_seq
    if lanes is not None and reader.global_seqno is not None:
        n = len(lanes["seq_lo"])
        lanes["seq_lo"] = np.full(
            n, reader.global_seqno & 0xFFFFFFFF, dtype=np.uint32)
        lanes["seq_hi"] = np.full(
            n, reader.global_seqno >> 32, dtype=np.uint32)
    return lanes


def _read_lanes_native(reader, planar: bool,
                       stream: Optional[ValueStream] = None):
    """The whole file through ONE native call (pread, inflate, transpose
    into the lanes, each block's checksum). ``_NOT_TAKEN`` when there is
    no library or the file is not one it reads (unknown block codec,
    props it does not understand): the Python source decides about
    those. None on width drift (a value width other than the file's,
    a block key wider than the file's widest: the tuple path's file;
    keys of differing length are lanes like any other). The lanes' key
    width (6 or 12 words) and value width follow from the file's props:
    a PLANAR file's widest key and value, a row-format file's
    ``widths_max`` (its values may then differ in width: one pass, each
    row its own ``val_len``). Raises Corruption for a block that does
    not inflate, does not fit its layout, or fails its ``block_chk``
    value; ``StreamOverrun`` where the values do not fit ``stream``."""
    lib = get_file_codecs()
    if lib is None or not reader._index or not reader.num_entries:
        return _NOT_TAKEN
    props = reader.props
    widths = props.get("planar") if planar else props.get("uniform")
    klen = vlen = 0  # row format without the sink's prop: inferred
    key_cols, vmixed = KEY_COLS, False
    try:
        if planar or widths:
            klen, vlen = int(widths[0]), int(widths[1])
            key_cols = key_word_cols(klen)
            vmixed = planar and planar_props_vmixed(widths)
        elif props.get("widths_max"):
            # a row-format file that says its widest key and value (the
            # plain writer's): keys and values of any width up to them
            kmax, vlen = (int(w) for w in props["widths_max"][:2])
            if not 0 < kmax <= PLANAR_MAX_KLEN:
                return _NOT_TAKEN
            key_cols, vmixed = key_word_cols(kmax), True
    except (TypeError, ValueError, IndexError, KeyError):
        return _NOT_TAKEN
    if (planar or widths) and not 0 < klen <= PLANAR_MAX_KLEN:
        return _NOT_TAKEN
    if not 0 <= vlen <= PLANAR_MAX_VLEN:
        return _NOT_TAKEN
    index = np.array([e[1:] for e in reader._index], dtype=np.uint64)
    if not np.isin(index[:, 2],
                   _PLANAR_CODECS if planar else _ROW_CODECS).all():
        return _NOT_TAKEN
    chk_mode, chk_len, want = 0, 0, ()
    spec = reader.block_chk_spec()
    if spec is not None:
        algo, chk_len, want = spec
        if (algo == "poly1w") != planar:
            return _NOT_TAKEN
        chk_mode = 2 if planar else 1
    got, lanes, chks, done = lib.decode_file_lanes(
        reader._fd, index, planar, klen, vlen, int(reader.num_entries),
        chk_mode, chk_len, key_cols, vmixed, stream)
    if got in (-1, -2):
        raise Corruption(
            f"{reader._path}: block {done - 1} "
            + ("could not be read" if got == -1 else "is corrupt"))
    # every block it got through is held to its block_chk value first,
    # as _read_block holds it before anything reads the block
    for i, value in enumerate(want[:done] if chk_mode else ()):
        try:
            value = int(value) & 0xFFFFFFFF
        except (TypeError, ValueError):
            continue  # foreign/crafted prop — treat as absent
        if int(chks[i]) != value:
            raise Corruption(
                f"block {i} checksum mismatch: "
                f"{int(chks[i]):#010x} != {value:#010x}")
    if got == -4:
        return _NOT_TAKEN  # more rows than the footer says
    if got == -6:
        raise StreamOverrun(f"{reader._path}: {len(stream.words)} words")
    return lanes  # None on width drift (-3)


def _read_uniform_arrays(reader) -> Optional[Dict[str, np.ndarray]]:
    """Row-format source path: blocks joined, decoded as one row matrix."""
    from ..ops.kv_format import UnsupportedBatch

    # Validate BEFORE reading the whole file: a file the array path will
    # reject must not pay a full pread+decompress only to be read again
    # by the tuple fallback.
    widths = reader.props.get("uniform")
    if widths:
        klen, vlen = int(widths[0]), int(widths[1])
        if not (0 < klen <= 24) or vlen < 0:
            return None  # foreign/crafted prop — tuple path validates
        blocks = [reader._read_block(i, fill_cache=False)
                  for i in range(len(reader._index))]
    else:
        # No sink prop (flush-written / foreign file): INFER the uniform
        # stride from block 0 so first-level compactions of flush output
        # still decode array-to-array. Probe only block 0 before
        # committing to the full read; the per-row width checks in the
        # shared row decode validate the inference (non-uniform files
        # fail them and take the tuple path).
        if not reader.num_entries or not reader._index:
            return None
        b0 = reader._read_block(0, fill_cache=False)
        inferred = _infer_uniform_widths(b0)
        if inferred is None:
            return None
        klen, vlen = inferred
        blocks = [b0] + [
            reader._read_block(i, fill_cache=False)
            for i in range(1, len(reader._index))
        ]
    raw = b"".join(blocks)
    try:
        return _decode_uniform_rows(raw, klen, vlen)
    except UnsupportedBatch:
        return None  # misaligned/non-uniform — tuple path handles it


class SstBlockLaneSource:
    """Block-granular lane decoder over ONE streamable TSST file — the
    SOURCE side of the bounded-memory chunked merge
    (storage/stream_merge.py). Where :func:`read_sst_arrays`
    materializes the whole file, this decodes an arbitrary block range
    on demand so a compaction's working set stays a fixed window per
    input run regardless of file size.

    Block reads probe the decoded-block LRU but never fill it
    (``fill_cache=False`` — the bulk-scan convention): a large streaming
    compaction must not evict hot serving blocks.

    ``probe`` returns None for files the lane representation can't
    stream (non-uniform rows, foreign layouts); a block that later
    violates the probed layout raises UnsupportedBatch and the caller
    falls back to the non-streaming path."""

    def __init__(self, reader, kind: str, klen: int, vlen: int):
        self.reader = reader
        self.kind = kind  # "planar" | "uniform"
        self.klen = klen
        self.vlen = vlen  # non-delete value width
        self.num_blocks = len(reader._index)
        self.num_entries = int(reader.num_entries)

    @classmethod
    def probe(cls, reader) -> Optional["SstBlockLaneSource"]:
        props = reader.props
        if not reader.num_entries or not reader._index:
            return None
        p = props.get("planar")
        if p:
            try:
                klen, vlen = int(p[0]), int(p[1])
            except (TypeError, ValueError, IndexError, KeyError):
                return None
            if not (0 < klen <= 24) or vlen < 0:
                # the chunked merge's windows are six key words a row: a
                # file of longer keys is read whole
                return None
            if planar_props_mixed(p) or planar_props_vmixed(p):
                # the chunked merge cuts its windows at keys of ONE
                # width, of values of one width: a file whose keys differ
                # in length, or values in width, is read whole
                return None
            return cls(reader, "planar", klen, vlen)
        widths = props.get("uniform")
        if widths:
            try:
                klen, vlen = int(widths[0]), int(widths[1])
            except (TypeError, ValueError, IndexError):
                return None
            if not (0 < klen <= 24) or vlen < 0:
                return None
            return cls(reader, "uniform", klen, vlen)
        # No sink prop (flush-written / foreign): infer the uniform
        # stride from block 0 via the SAME helper read_sst_arrays uses —
        # the per-block width checks in decode_blocks validate the
        # inference on every later block.
        b0 = reader._read_block(0, fill_cache=False)
        inferred = _infer_uniform_widths(b0)
        if inferred is None:
            return None
        return cls(reader, "uniform", *inferred)

    def decode_blocks(self, b0: int, b1: int) -> Dict[str, np.ndarray]:
        """Lane arrays for blocks [b0, b1). Raises UnsupportedBatch when
        a block violates the probed layout (caller declines streaming)."""
        from ..ops.kv_format import UnsupportedBatch

        if self.kind == "planar":
            try:
                parts = [
                    decode_planar_block(
                        self.reader._read_block(i, fill_cache=False))
                    for i in range(b0, b1)
                ]
            except Exception as e:
                raise UnsupportedBatch(f"planar stream decode: {e}")
            lanes = {f: np.concatenate([p[f] for p in parts])
                     for f in parts[0]}
            kl = lanes["key_len"]
            if len(kl) and not (kl == self.klen).all():
                raise UnsupportedBatch("planar stream: klen drift")
            vl = lanes["val_len"][lanes["vtype"] != 2]
            if len(vl) and not (vl == self.vlen).all():
                raise UnsupportedBatch("planar stream: vlen drift")
        else:
            raw = b"".join(
                self.reader._read_block(i, fill_cache=False)
                for i in range(b0, b1))
            lanes = _decode_uniform_rows(raw, self.klen, self.vlen)
        seqno = self.reader.global_seqno
        if seqno is not None:
            n = len(lanes["seq_lo"])
            lanes["seq_lo"] = np.full(
                n, seqno & 0xFFFFFFFF, dtype=np.uint32)
            lanes["seq_hi"] = np.full(n, seqno >> 32, dtype=np.uint32)
        return lanes


def _infer_uniform_widths(b0: bytes):
    """(klen, vlen) of a uniform-stride file inferred from its first
    block (no sink prop: flush-written / foreign files), or None when
    block 0 can't carry a uniform stride. Shared by read_sst_arrays and
    SstBlockLaneSource.probe; the per-row checks in
    _decode_uniform_rows validate the inference on every block."""
    if len(b0) < _ENTRY_FIXED_OVERHEAD:
        return None
    klen = int.from_bytes(b0[:4], "little")
    if not (0 < klen <= 24) or len(b0) < _ENTRY_FIXED_OVERHEAD + klen:
        return None
    # first entry's vlen field sits after klen|key|seq|vtype
    vlen = int.from_bytes(b0[klen + 13:klen + 17], "little")
    if len(b0) % (_ENTRY_FIXED_OVERHEAD + klen + vlen):
        return None
    return klen, vlen


def _decode_uniform_rows(raw: bytes, klen: int,
                         vlen: int) -> Dict[str, np.ndarray]:
    """Uniform-stride row bytes → lane arrays (the row-matrix half of
    read_sst_arrays, shared with the block-range streaming source).
    Raises UnsupportedBatch on per-row width drift."""
    from ..ops.kv_format import UnsupportedBatch

    stride = _ENTRY_FIXED_OVERHEAD + klen + vlen
    if len(raw) % stride:
        raise UnsupportedBatch("uniform stream: stride drift")
    n = len(raw) // stride
    mat = np.frombuffer(raw, dtype=np.uint8).reshape(n, stride)
    pos = 0
    klens = mat[:, pos:pos + 4].copy().view("<u4").reshape(n)
    pos += 4
    key_bytes = mat[:, pos:pos + klen]
    pos += klen
    seqs = mat[:, pos:pos + 8].copy().view("<u8").reshape(n)
    pos += 8
    vtypes = mat[:, pos].astype(np.uint32)
    pos += 1
    vlens = mat[:, pos:pos + 4].copy().view("<u4").reshape(n)
    pos += 4
    val_bytes = mat[:, pos:pos + vlen]
    if not (klens == klen).all() or not (vlens == vlen).all():
        raise UnsupportedBatch("uniform stream: row width drift")
    key_buf = np.zeros((n, 24), dtype=np.uint8)
    key_buf[:, :klen] = key_bytes
    vw = max(2, (vlen + 3) // 4)
    val_buf = np.zeros((n, vw * 4), dtype=np.uint8)
    if vlen:
        val_buf[:, :vlen] = val_bytes
    return {
        "key_words_be": key_buf.view(">u4").astype(np.uint32).reshape(n, 6),
        "key_words_le": key_buf.view("<u4").reshape(n, 6).copy(),
        "key_len": klens.astype(np.uint32),
        "seq_hi": (seqs >> np.uint64(32)).astype(np.uint32),
        "seq_lo": (seqs & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        "vtype": vtypes,
        "val_words": val_buf.view("<u4").reshape(n, vw).copy(),
        "val_len": vlens.astype(np.uint32),
    }


def planar_stride(klen: int, vlen: int, mixed: bool = False,
                  vmixed: bool = False) -> int:
    """Approximate PLANAR bytes per entry (seq32 layout: key + seq_lo +
    vtype + value; ``mixed``: the key at the rows' widest, and its
    length byte; ``vmixed``: the value at the rows' widest, and its two
    length bytes) — block/file sizing only, shared by every sink."""
    return klen + vlen + 9 + bool(mixed) + 2 * bool(vmixed)


def planar_widths(arrays: Dict[str, np.ndarray], count: int):
    """(klen, vlen, mixed, vmixed) for the PLANAR sink, or None where the
    layout can't express the rows. ``klen`` is the rows' widest key,
    ``mixed`` whether their keys differ in length (1 to
    ``PLANAR_MAX_KLEN`` bytes each, in any proportion); ``vlen`` the
    widest live value, ``vmixed`` whether the live values differ in
    width. Laxer than uniform_widths: DELETE rows carry no value in the
    planar layout (val_len derives from vtype on read, or is 0 in the
    value-length plane), so kept tombstones coexist with values of any
    width; a DELETE that carries a value is not expressible."""
    if count == 0:
        return None
    kl = arrays["key_len"][:count]
    k0, k1 = int(kl.min()), int(kl.max())
    if not 0 < k0 <= k1 <= PLANAR_MAX_KLEN:
        return None
    vt = arrays["vtype"][:count]
    vl = arrays["val_len"][:count]
    non_del = vl[vt != 2]
    v0 = v1 = 0
    if len(non_del):
        v0, v1 = int(non_del.min()), int(non_del.max())
    if not (vl[vt == 2] == 0).all():
        return None
    # Header bound (u16 vlen): wider values take the entry-stream sink.
    # The round-2 crash was this check missing — every uniform workload
    # with values >= 256 B died in the header packer (VERDICT r2 #1).
    if v1 > PLANAR_MAX_VLEN:
        return None
    return k1, v1, k0 != k1, v0 != v1


def _key_rows(arrays: Dict[str, np.ndarray], rows, klen: int) -> np.ndarray:
    """(len(rows), klen) u8: the key bytes of the given rows (a key
    shorter than ``klen`` zero-padded, as its lanes are)."""
    kw = np.ascontiguousarray(arrays["key_words_be"][rows].astype(">u4"))
    return kw.view(np.uint8).reshape(len(kw), -1)[:, :klen]


def _keys_of(arrays: Dict[str, np.ndarray], rows, klen: int,
             mixed: bool) -> List[bytes]:
    """The given rows' keys as bytes, each at its own length."""
    flat = _key_rows(arrays, rows, klen).tobytes()
    if not mixed:
        return [flat[i:i + klen] for i in range(0, len(flat), klen)]
    return [flat[i * klen:i * klen + n]
            for i, n in enumerate(arrays["key_len"][rows].tolist())]


def _write_planar(
    arrays: Dict[str, np.ndarray], count: int, path: str,
    bloom_words: Optional[np.ndarray], block_entries: int,
    compression: int, bits_per_key: int, klen: int, vlen: int,
    mixed: bool, vmixed: bool,
    device_words: Optional[np.ndarray],
    device_checksums: Optional[np.ndarray],
) -> Optional[dict]:
    """PLANAR sink body: per-block plane bytes + word-domain checksums.
    Without device-encoded words the native library, where it is, makes
    every block in one call; else ``_planar_blocks`` does, block by
    block. The file is the same. ``mixed``: the rows' keys differ in
    length (``klen`` their widest); ``vmixed``: their live values differ
    in width (``vlen`` the widest); the device's block encoder knows one
    key width and one value width, so its words are not taken then."""
    seq32 = bool((arrays["seq_hi"][:count] == 0).all())
    full_words = plane_words(block_entries, klen, vlen, seq32, mixed, vmixed)
    if mixed or vmixed:
        device_words = device_checksums = None
    lib = get_file_codecs() if device_words is None else None
    encoded = None
    if lib is not None:
        encoded = lib.planar_encode_file(
            arrays, count, klen, vlen, seq32, block_entries, compression,
            mixed, vmixed)
    _count_codec(encoded is not None)
    writer = SSTWriter(path, compression=compression,
                       bits_per_key=bits_per_key)
    try:
        if encoded is None:
            encoded = _planar_blocks(
                arrays, count, block_entries, compression, klen, vlen,
                seq32, full_words, device_words, device_checksums, mixed,
                vmixed)
        payload, offs, sizes, codecs, chks = encoded
        ends = np.minimum(
            np.arange(1, len(offs) + 1) * block_entries, count)
        last_keys = _keys_of(arrays, ends - 1, klen, mixed)
        (first_key,) = _keys_of(arrays, np.arange(1), klen, mixed)
        seqs = (
            arrays["seq_hi"][:count].astype(np.uint64) << np.uint64(32)
        ) | arrays["seq_lo"][:count].astype(np.uint64)
        writer.add_encoded_blocks(
            payload,
            list(zip(last_keys, offs.tolist(), sizes.tolist(),
                     codecs.tolist())),
            num_entries=count, keys=[],
            min_key=first_key, max_key=last_keys[-1],
            min_seq=int(seqs.min()), max_seq=int(seqs.max()),
        )
        if bloom_words is not None:
            bloom = BloomFilter(
                len(bloom_words), np.asarray(bloom_words, dtype=np.uint32)
            )
        else:
            bloom = BloomFilter.build_from_arrays(
                _key_rows(arrays, slice(0, count), klen),
                arrays["key_len"][:count].astype(np.uint64), bits_per_key)
        extra_props = {
            "num_keys": int(count),
            "planar": planar_props(klen, vlen, seq32, mixed, vmixed),
            "block_chk": {
                "algo": "poly1w",
                "block_words": int(full_words),
                "values": chks.tolist(),
            },
        }
        return writer.finish(precomputed_bloom=bloom,
                             extra_props=extra_props)
    except BaseException:
        writer.abandon()
        raise


def _planar_blocks(
    arrays: Dict[str, np.ndarray], count: int, block_entries: int,
    compression: int, klen: int, vlen: int, seq32: bool, full_words: int,
    device_words: Optional[np.ndarray],
    device_checksums: Optional[np.ndarray], mixed: bool = False,
    vmixed: bool = False,
):
    """The Python block loop of the PLANAR sink, in the shape the native
    encoder returns: ``(payload, offsets, sizes, codecs, checksums)``.
    Takes the device planar encoder's words for full blocks."""
    from ..storage.planar import (PLANAR_HEADER, PLANAR_FLAG_SEQ32,
                                  pack_planar_header)

    arrays = with_value_rows(arrays, count)
    payloads: List[bytes] = []
    codecs: List[int] = []
    chks: List[int] = []
    for bi, start in enumerate(range(0, count, block_entries)):
        end = min(start + block_entries, count)
        full = end - start == block_entries
        if device_words is not None and full and bi < len(device_words):
            words = np.ascontiguousarray(device_words[bi], dtype="<u4")
            raw = pack_planar_header(
                block_entries, klen, vlen,
                PLANAR_FLAG_SEQ32 if seq32 else 0,
            ) + words.tobytes()
            if device_checksums is not None and bi < len(
                    device_checksums):
                chks.append(int(device_checksums[bi]))
            else:
                chks.append(poly_checksum_words(words, full_words))
        else:
            raw = encode_planar_block(
                arrays, start, end, klen, vlen, seq32, mixed, vmixed)
            words = np.frombuffer(
                raw, dtype="<u4", offset=PLANAR_HEADER.size)
            chks.append(poly_checksum_words(words, full_words))
        codec = BLOCK_PLANAR
        payload = raw
        if compression == COMPRESSION_ZLIB:
            z = zlib.compress(raw, 1)
            if len(z) < len(raw):
                codec, payload = BLOCK_PLANAR_ZLIB, z
        elif compression == COMPRESSION_RLZ:
            z = rlz.compress(raw)
            if len(z) < len(raw):
                codec, payload = BLOCK_PLANAR_RLZ, z
        payloads.append(payload)
        codecs.append(codec)
    sizes = np.fromiter(map(len, payloads), np.int64, len(payloads))
    return (b"".join(payloads), np.cumsum(sizes) - sizes, sizes,
            np.asarray(codecs), np.asarray(chks, dtype=np.int64))


def _read_planar_arrays(reader) -> Optional[Dict[str, np.ndarray]]:
    """PLANAR source path: per-block plane decode (views + reshapes),
    lanes concatenated across blocks, each at the file's widths (its
    widest key and value, from its props)."""
    try:
        p = reader.props["planar"]
        klen, vlen = int(p[0]), int(p[1])
        parts = [
            decode_planar_block(reader._read_block(i, fill_cache=False),
                                key_word_cols(klen), max(2, (vlen + 3) // 4))
            for i in range(len(reader._index))
        ]
    except Exception:
        return None  # foreign/corrupt planar props — tuple path validates
    if not parts:
        return None
    return {
        f: np.concatenate([p[f] for p in parts])
        for f in parts[0]
    }


def write_sst_from_arrays(
    arrays: Dict[str, np.ndarray],
    count: int,
    path: str,
    bloom_words: Optional[np.ndarray] = None,
    block_entries: int = 1024,
    compression: int = COMPRESSION_ZLIB,
    bits_per_key: int = 10,
    device_rows: Optional[np.ndarray] = None,
    device_checksums: Optional[np.ndarray] = None,
    planar: bool = False,
    device_words: Optional[np.ndarray] = None,
) -> Optional[dict]:
    """Write kernel-output arrays as a TSST file without per-entry Python.
    Returns the props dict, or None when rows aren't uniform-width (caller
    falls back to the tuple path).

    ``device_rows``/``device_checksums``: the on-device block encoder's
    output (ops/block_encode.py) — the (count, stride) byte matrix is
    written as-is (no host re-encoding) and the per-block checksums land
    in the "block_chk" prop, which readers verify on every block read.

    ``planar=True`` writes PLANAR blocks (storage/planar.py): u32 planes
    in kernel lane order — smaller files and no byte interleaving on
    either side; keys of 1 to 48 bytes, of one length or mixed, values
    of one width or mixed (``planar_widths``), as rows or as a
    ``ValueStream``'s words (``val_stream`` and each row's word offset
    ``val_off`` in ``val_words``' place: the same file). ``device_words`` optionally carries the device planar
    encoder's (nblocks, words) matrix for full blocks (the tail block is
    host-packed: its plane lengths differ from the fixed device shape)."""
    if planar:
        widths = planar_widths(arrays, count)
        if widths is None:
            return None
        return _write_planar(
            arrays, count, path, bloom_words, block_entries, compression,
            bits_per_key, *widths, device_words, device_checksums)
    arrays = with_value_rows(arrays, count)
    widths = uniform_widths(arrays, count)
    if widths is None:
        return None
    klen, vlen = widths
    stride = _ENTRY_FIXED_OVERHEAD + klen + vlen
    if device_rows is not None and device_rows.shape != (count, stride):
        return None  # shape mismatch — let the host path handle it
    _count_codec(False)  # the row-format sink has a Python block loop only
    writer = SSTWriter(path, compression=compression,
                       bits_per_key=bits_per_key)
    try:
        key_bytes = _key_rows(arrays, slice(0, count), klen)
        seqs = (
            arrays["seq_hi"][:count].astype(np.uint64) << np.uint64(32)
        ) | arrays["seq_lo"][:count].astype(np.uint64)
        for start in range(0, count, block_entries):
            end = min(start + block_entries, count)
            if device_rows is not None:
                raw = device_rows[start:end].tobytes()
            else:
                raw = encode_uniform_block(arrays, start, end, klen, vlen)
            codec = compression
            if codec == COMPRESSION_ZLIB:
                payload = zlib.compress(raw, 1)
            elif codec == COMPRESSION_RLZ:
                payload = rlz.compress(raw)
            else:
                payload = raw
            if len(payload) >= len(raw):
                codec, payload = 0, raw
            writer.add_encoded_block(
                payload,
                last_key=key_bytes[end - 1].tobytes(),
                num_entries=end - start,
                keys=[],  # bloom comes prebuilt; keys list unused
                min_key=key_bytes[start].tobytes(),
                max_key=key_bytes[end - 1].tobytes(),
                min_seq=int(seqs[start:end].min()),
                max_seq=int(seqs[start:end].max()),
                compressed=False,
                codec=codec,
            )
        if bloom_words is not None:
            bloom = BloomFilter(
                len(bloom_words), np.asarray(bloom_words, dtype=np.uint32)
            )
        else:
            bloom = BloomFilter.build_from_arrays(
                key_bytes, np.full(count, klen, dtype=np.uint64),
                bits_per_key)
        # kernel output has one entry per key; the uniform prop lets the
        # vectorized SOURCE reader decode this file array-to-array
        extra_props = {"num_keys": int(count),
                       "uniform": [int(klen), int(vlen)]}
        if device_checksums is not None:
            extra_props["block_chk"] = {
                "algo": "poly1",
                "block_bytes": block_entries * stride,
                "values": [int(c) for c in device_checksums],
            }
        return writer.finish(
            precomputed_bloom=bloom,
            extra_props=extra_props,
        )
    except BaseException:
        writer.abandon()
        raise
