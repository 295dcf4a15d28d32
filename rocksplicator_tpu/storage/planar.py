"""PLANAR (struct-of-arrays) TSST block codec — host side.

The round-2 device profiling (PERF.md) showed minor-dim byte interleaving
is the most expensive thing a TPU can do with kernel output, while the
kernel's struct-of-array u32 lanes ARE already the data. The planar block
format therefore writes each data block as u32 *planes* in lane order —
on-device "encoding" degenerates to packing one u8 lane (vtype) and
concatenating, files shrink (no per-entry klen/vlen/seq_hi overhead:
41 B/entry → 33 B at 16/8 widths, less with seq32), and block checksums
become pure u32 word math on both sides.

Block layout (all little-endian), after the 16-byte header:

    u32 n_entries | u8 klen | u8 vlen | u8 flags | u8 vlen_hi | u64 0
    key planes   ceil(klen/4) × n u32   (big-endian WORD VALUES — the
                                         kernel's key_words_be lanes)
    klen plane   ceil(n/4) u32          (only when flags & KLENS: each
                                         entry's key length, 4 packed
                                         per word, LE)
    seq_lo plane n u32
    seq_hi plane n u32                  (omitted when flags & SEQ32)
    vtype plane  ceil(n/4) u32          (4 entries packed per word, LE)
    vlen plane   ceil(n/2) u32          (only when flags & VLENS: each
                                         entry's value length as a u16,
                                         2 packed per word, LE)
    val planes   ceil(vlen/4) × n u32   (the kernel's val_words lanes)

Entries within a block are key-ascending in BYTEWISE order (same contract
as entry-stream blocks), which on the planes is: the key words, zero-
padded, as big-endian integers, then the key's length (``b"counter-1"`` <
``b"counter-10"`` < ``b"counter-2"``; a key that is another's prefix, or
differs from it in trailing NUL bytes only, is the shorter and sorts
first). Keys are 1 to ``PLANAR_MAX_KLEN`` (48) bytes: the lanes hold
``key_word_cols(klen)`` words a key, 6 up to 24 bytes and 12 above.
Whether a block carries the key-length plane follows from its rows:
where they share one length the header's ``klen`` is that length and the
flag is clear (the layout every file had before keys of differing length
were taken: such files and blocks read as they always did); where they
differ, ``klen`` is the block's WIDEST key, the key planes are that wide,
a shorter key's tail bytes are zero, and the plane says each entry's own
length. Values follow the same rule: a block whose live rows share one
value width is the old block (``vlen`` that width, a DELETE's length
derived from its vtype); where they differ, ``vlen`` is the block's
widest value, the value planes are that wide, a shorter value's tail
bytes are zero, and the vlen plane (flag VLENS) says each entry's own
length. A file's ``planar`` prop gives its widest key and widest value,
as a fourth member 1 where its keys differ in length and as a fifth 1
where its values differ in width. The codec nibble in the block index
distinguishes planar blocks, so one file could mix encodings; readers
dispatch per block. v1 entry-stream files stay readable unchanged
(golden-format compatibility); planar files are new-format output of the
TPU sink.

Reference seam being reproduced: the SST files rocksdb ingests/compacts
(SURVEY §3.3 addS3SstFilesToDB); the planar layout is the TPU-first
re-design of their data blocks.
"""

from __future__ import annotations

import struct
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

# n, klen, vlen_lo, flags, vlen_hi, reserved. vlen is u16 split across
# bytes 5 (lo) and 7 (hi): byte 7 was a reserved zero in the original
# layout, so every previously-written file reads back with vlen_hi == 0 —
# the widening is backward-compatible. klen stays u8 (bounded at 48, the
# widest key lanes: 12 words).
PLANAR_HEADER = struct.Struct("<IBBBBQ")
PLANAR_FLAG_SEQ32 = 1
PLANAR_FLAG_KLENS = 2  # a key-length plane follows the key planes
PLANAR_FLAG_VLENS = 4  # a value-length plane follows the vtype plane
PLANAR_MAX_KLEN = 48
PLANAR_MAX_VLEN = 0xFFFF
# the key lanes' widths, in u32 words: keys up to 24 bytes are the six
# words every program was built for; longer ones (up to 48) take twelve
KEY_COLS, KEY_COLS_WIDE = 6, 12


def key_word_cols(klen: int) -> int:
    """The key lanes' width, in words, for keys of at most ``klen``
    bytes: 6, or 12 where a key is longer than 24 bytes."""
    return KEY_COLS if klen <= 4 * KEY_COLS else KEY_COLS_WIDE


def key_shape(key_len: np.ndarray) -> Dict[str, object]:
    """What spans say of the keys of the rows they carry (``tpu.lanes.
    decode``, ``tpu.compact_stream``, ``tpu.planar.write``,
    ``flush.encode``): ``key_widths`` ``uniform`` (one length) or
    ``mixed``, and ``key_bytes_max``, the longest."""
    if not len(key_len):
        return {"key_widths": "uniform", "key_bytes_max": 0}
    lo, hi = int(key_len.min()), int(key_len.max())
    return {"key_widths": "uniform" if lo == hi else "mixed",
            "key_bytes_max": hi}


def value_shape(vtype: np.ndarray, val_len: np.ndarray) -> Dict[str, object]:
    """What spans say of the values of the rows they carry (``tpu.lanes.
    decode``, ``tpu.planar.write``, ``flush.encode``): ``value_widths``
    ``uniform`` (the live rows' values share one width; a DELETE carries
    none) or ``mixed``, and ``value_bytes_max``, the widest."""
    live = val_len[vtype != 2]
    if not len(live):
        return {"value_widths": "uniform", "value_bytes_max": 0}
    lo, hi = int(live.min()), int(live.max())
    return {"value_widths": "uniform" if lo == hi else "mixed",
            "value_bytes_max": hi}


def pack_planar_header(n: int, klen: int, vlen: int, flags: int) -> bytes:
    """The ONLY planar-header packer (every sink goes through here so the
    vlen bound is enforced in one place — the round-2 crash was a sink
    packing vlen straight into a 'B' field)."""
    if not (0 < klen <= PLANAR_MAX_KLEN):
        raise ValueError(f"planar klen out of range: {klen}")
    if not (0 <= vlen <= PLANAR_MAX_VLEN):
        raise ValueError(f"planar vlen out of range: {vlen}")
    return PLANAR_HEADER.pack(n, klen, vlen & 0xFF, flags, vlen >> 8, 0)


def unpack_planar_header(raw: bytes) -> Tuple[int, int, int, int]:
    """(n, klen, vlen, flags) with bounds validation → Corruption."""
    from .errors import Corruption

    if len(raw) < PLANAR_HEADER.size:
        raise Corruption(f"planar block: {len(raw)} bytes < header")
    n, klen, vlen_lo, flags, vlen_hi, _ = PLANAR_HEADER.unpack_from(raw, 0)
    vlen = vlen_lo | (vlen_hi << 8)
    if not (0 < klen <= PLANAR_MAX_KLEN):
        raise Corruption(f"planar block: klen {klen} out of range")
    return n, klen, vlen, flags


def plane_words(n: int, klen: int, vlen: int, seq32: bool,
                klens: bool = False, vlens: bool = False) -> int:
    """u32 words of plane data for a planar block of n entries
    (``klens``: with the key-length plane; ``vlens``: with the
    value-length plane)."""
    kw = (klen + 3) // 4
    vw = (vlen + 3) // 4
    return (n * (kw + 1 + (0 if seq32 else 1) + vw)
            + (2 if klens else 1) * ((n + 3) // 4)
            + (((n + 1) // 2) if vlens else 0))


def pack_vlen_plane(val_len: np.ndarray) -> np.ndarray:
    """(n,) value lengths -> (ceil(n/2),) u32, 2 u16 per word LE."""
    v = np.pad(val_len.astype("<u2"), (0, len(val_len) % 2))
    return v.view("<u4").copy()


def pack_vtype_plane(vtype: np.ndarray) -> np.ndarray:
    """(n,) u32 vtype values -> (ceil(n/4),) u32, 4 per word LE (the
    key-length plane packs the same way)."""
    n = len(vtype)
    pad = (-n) % 4
    v = np.pad(vtype.astype(np.uint8), (0, pad))
    return v.view("<u4").copy()


def unpack_vtype_plane(words: np.ndarray, n: int) -> np.ndarray:
    return words.view(np.uint8)[:n].astype(np.uint32)


def block_value_width(vtype: np.ndarray, val_len: np.ndarray,
                      vlen: int) -> Tuple[int, bool]:
    """(header vlen, whether a value-length plane is needed) of a block
    of a file whose values differ in width (``vlen`` the file's widest):
    the block's own widest live value, and the plane only where its live
    rows differ (a block of one width is the block it always was; an
    all-DELETE block keeps the file's width)."""
    live = val_len[vtype != 2]
    if not len(live):
        return vlen, False
    hi = int(live.max())
    return hi, int(live.min()) != hi


def encode_planar_block(
    arrays: Dict[str, np.ndarray], start: int, end: int,
    klen: int, vlen: int, seq32: bool, mixed: bool = False,
    vmixed: bool = False,
) -> bytes:
    """Kernel-output lanes [start, end) -> planar block bytes (numpy —
    the host fallback; the device path produces the identical plane words
    via ops/block_encode.encode_planar_words_tpu). ``mixed``: the rows'
    keys may differ in length (``klen`` is then only their bound): the
    block's own rows decide its header and whether it carries the
    key-length plane. ``vmixed``: the same for the values' widths
    (``vlen`` their bound) and the value-length plane."""
    n = end - start
    flags = PLANAR_FLAG_SEQ32 if seq32 else 0
    lens = vlens = None
    if mixed and n:
        lens = arrays["key_len"][start:end]
        klen = int(lens.max())
        if int(lens.min()) == klen:
            lens = None  # one length: the block every file always had
        else:
            flags |= PLANAR_FLAG_KLENS
    if vmixed and n:
        vlens = arrays["val_len"][start:end]
        vlen, plane = block_value_width(
            arrays["vtype"][start:end], vlens, vlen)
        if plane:
            flags |= PLANAR_FLAG_VLENS
        else:
            vlens = None
    kw = (klen + 3) // 4
    vw = (vlen + 3) // 4
    parts: List[np.ndarray] = [
        np.ascontiguousarray(
            arrays["key_words_be"][start:end, :kw].T).reshape(-1)]
    if lens is not None:
        parts.append(pack_vtype_plane(lens))
    parts.append(arrays["seq_lo"][start:end].astype(np.uint32))
    if not seq32:
        parts.append(arrays["seq_hi"][start:end].astype(np.uint32))
    parts.append(pack_vtype_plane(arrays["vtype"][start:end]))
    if vlens is not None:
        parts.append(pack_vlen_plane(vlens))
    if vw:
        parts.append(np.ascontiguousarray(
            arrays["val_words"][start:end, :vw].T).reshape(-1))
    words = np.concatenate(parts).astype("<u4")
    return pack_planar_header(n, klen, vlen, flags) + words.tobytes()


def decode_planar_block(raw: bytes, key_cols: Optional[int] = None,
                        val_cols: Optional[int] = None,
                        ) -> Dict[str, np.ndarray]:
    """Planar block bytes -> lane arrays (pure views/reshapes). The key
    lanes are ``key_cols`` words wide (default: ``key_word_cols`` of the
    block's ``klen``), the value lanes ``val_cols`` (default: the block's
    own width, at least 2): a caller that joins a file's blocks passes
    the file's widths. Bytes past a row's own key length or value length
    are zero."""
    from .errors import Corruption

    n, klen, vlen, flags = unpack_planar_header(raw)
    seq32 = bool(flags & PLANAR_FLAG_SEQ32)
    klens = bool(flags & PLANAR_FLAG_KLENS)
    vlens = bool(flags & PLANAR_FLAG_VLENS)
    kw = (klen + 3) // 4
    vw = (vlen + 3) // 4
    kc = key_word_cols(klen) if key_cols is None else key_cols
    vc = max(2, vw) if val_cols is None else val_cols
    if kc < kw or vc < vw:
        raise Corruption(
            f"planar block: klen {klen} / vlen {vlen} wider than the "
            f"file's lanes")
    want = PLANAR_HEADER.size + 4 * plane_words(
        n, klen, vlen, seq32, klens, vlens)
    if len(raw) != want:
        raise Corruption(
            f"planar block: {len(raw)} bytes, layout wants {want}")
    words = np.frombuffer(raw, dtype="<u4", offset=PLANAR_HEADER.size)
    pos = 0
    kw_lanes = words[pos:pos + kw * n].reshape(kw, n)
    pos += kw * n
    nv = (n + 3) // 4
    if klens:
        key_len = unpack_vtype_plane(words[pos:pos + nv], n)
        pos += nv
        if n and not (0 < int(key_len.min())
                      and int(key_len.max()) <= klen):
            raise Corruption(
                f"planar block: a key length outside 1..{klen}")
    else:
        key_len = np.full(n, klen, dtype=np.uint32)
    seq_lo = words[pos:pos + n]
    pos += n
    if seq32:
        seq_hi = np.zeros(n, dtype=np.uint32)
    else:
        seq_hi = words[pos:pos + n]
        pos += n
    vtype = unpack_vtype_plane(words[pos:pos + nv], n)
    pos += nv
    if vlens:
        nl = (n + 1) // 2
        val_len = words[pos:pos + nl].view("<u2")[:n].astype(np.uint32)
        pos += nl
        if n and int(val_len.max()) > vlen:
            raise Corruption(
                f"planar block: a value length over {vlen}")
    else:
        val_len = np.where(vtype == 2, 0, vlen).astype(np.uint32)
    val_lanes = words[pos:pos + vw * n].reshape(vw, n)

    key_buf = np.zeros((n, 4 * kc), dtype=np.uint8)
    kb = np.ascontiguousarray(
        kw_lanes.T.astype(">u4")).view(np.uint8).reshape(n, kw * 4)
    key_buf[:, :klen] = kb[:, :klen]
    if klens:  # bytes past a key's own length are not key: zeroed
        key_buf[np.arange(4 * kc, dtype=np.uint32)[None, :]
                >= key_len[:, None]] = 0
    val_words = np.zeros((n, vc), dtype=np.uint32)
    if vw:
        val_words[:, :vw] = val_lanes.T
    if vlens:  # nor are bytes past a value's own length
        vb = val_words.view(np.uint8)
        vb[np.arange(4 * vc, dtype=np.uint32)[None, :]
           >= val_len[:, None]] = 0
    return {
        "key_words_be": key_buf.view(">u4").astype(np.uint32).reshape(n, kc),
        "key_words_le": key_buf.view("<u4").reshape(n, kc).copy(),
        "key_len": key_len,
        "seq_hi": seq_hi.astype(np.uint32),
        "seq_lo": seq_lo.astype(np.uint32),
        "vtype": vtype,
        "val_words": val_words,
        "val_len": val_len,
    }


def iter_planar_block(raw: bytes) -> Iterator[Tuple[bytes, int, int, bytes]]:
    """Planar block -> (key, seq, vtype, value) tuples (the generic
    reader path; array consumers use decode_planar_block directly)."""
    lanes = decode_planar_block(raw)
    n = len(lanes["key_len"])
    klens = lanes["key_len"].tolist()
    kb = (
        np.ascontiguousarray(lanes["key_words_be"].astype(">u4"))
        .view(np.uint8).reshape(n, -1)
    )
    vb = (
        np.ascontiguousarray(lanes["val_words"].astype("<u4"))
        .view(np.uint8).reshape(n, -1)
    )
    seqs = (
        lanes["seq_hi"].astype(np.uint64) << np.uint64(32)
    ) | lanes["seq_lo"].astype(np.uint64)
    vtypes = lanes["vtype"]
    vlens = lanes["val_len"]
    for i in range(n):
        yield (
            kb[i, :klens[i]].tobytes(), int(seqs[i]), int(vtypes[i]),
            vb[i, :int(vlens[i])].tobytes(),
        )


def planar_props(klen: int, vlen: int, seq32: bool,
                 mixed: bool = False, vmixed: bool = False) -> List[int]:
    """The "planar" props value: [klen, vlen, seq32] (ints for JSON),
    a fourth member, 1, where the file's keys differ in length (``klen``
    is then its widest key), and a fifth, 1, where its values differ in
    width (``vlen`` is then its widest value; the fourth is then 0 or 1)."""
    out = [int(klen), int(vlen), int(bool(seq32))]
    if vmixed:
        return out + [int(bool(mixed)), 1]
    return out + [1] * bool(mixed)


def planar_props_mixed(props_value) -> bool:
    """Whether a file's "planar" prop says its keys differ in length."""
    try:
        return len(props_value) > 3 and bool(int(props_value[3]))
    except (TypeError, ValueError):
        return False


def planar_props_vmixed(props_value) -> bool:
    """Whether a file's "planar" prop says its values differ in width."""
    try:
        return len(props_value) > 4 and bool(int(props_value[4]))
    except (TypeError, ValueError):
        return False
