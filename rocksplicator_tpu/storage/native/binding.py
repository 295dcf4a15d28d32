"""ctypes binding for libtsst_native.so.

No pybind11 in the image (environment constraint) — the C ABI + ctypes is
the binding layer. Arrays cross the boundary as numpy buffers.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

from ..errors import Corruption
from ..planar import unpack_planar_header

log = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libtsst_native.so")

_u8p = ctypes.POINTER(ctypes.c_uint8)
_u32p = ctypes.POINTER(ctypes.c_uint32)
_u64p = ctypes.POINTER(ctypes.c_uint64)
_i64p = ctypes.POINTER(ctypes.c_int64)


_SHORT_CALL_BYTES = 64 * 1024

_SRC = os.path.join(_DIR, "tsst_native.cc")
_MAKEFILE = os.path.join(_DIR, "Makefile")


def _so_current() -> bool:
    """True when the .so exists and is at least as new as its inputs
    (source and Makefile — a flag change must trigger a rebuild too)."""
    try:
        so = os.path.getmtime(_SO)
        return so >= os.path.getmtime(_SRC) and so >= os.path.getmtime(_MAKEFILE)
    except OSError:
        return False


def _build() -> bool:
    try:
        subprocess.run(
            ["make", "-C", _DIR, "-s"],
            check=True, capture_output=True, timeout=120,
        )
        return os.path.isfile(_SO)
    except Exception as e:
        detail = getattr(e, "stderr", b"") or b""
        log.error("native build failed: %s %s", e,
                  detail.decode("utf-8", "replace")[-400:])
        return False


class NativeLib:
    def __init__(self, so_path: str):
        lib = ctypes.CDLL(so_path)
        self._lib = lib
        # The same image through PyDLL: its calls KEEP the GIL. For
        # microseconds of C on a request's path (a point lookup, the
        # RLZ transform of a small frame on the event loop): a call
        # that drops the GIL has to win it back behind whichever thread
        # took it, up to a switch interval (5 ms) for ~10 us of work.
        # Everything that runs for long goes through ``lib``.
        held = ctypes.PyDLL(so_path)
        self._held = held
        lib.tsst_crc32.restype = ctypes.c_uint32
        lib.tsst_crc32.argtypes = [_u8p, ctypes.c_uint64]
        lib.tsst_encode_block.restype = ctypes.c_int64
        lib.tsst_encode_block.argtypes = [
            _u8p, _u64p, _u64p, _u8p, _u8p, _u64p,
            ctypes.c_uint64, _u8p, ctypes.c_uint64,
        ]
        lib.tsst_decode_block.restype = ctypes.c_int64
        lib.tsst_decode_block.argtypes = [
            _u8p, ctypes.c_uint64, ctypes.c_uint64,
            _u64p, _u64p, _u64p, _u8p, _u64p, _u64p,
        ]
        held.tsst_get_entries.restype = ctypes.c_int64
        held.tsst_get_entries.argtypes = [
            _u8p, ctypes.c_uint64, _u8p, ctypes.c_uint64, ctypes.c_uint64,
            _u64p, _u8p, _u64p, _u64p, ctypes.POINTER(ctypes.c_int32),
        ]
        # planar lookup may be absent in stale builds; probe and gate
        try:
            held.tsst_planar_get_entries.restype = ctypes.c_int64
            held.tsst_planar_get_entries.argtypes = [
                _u8p, ctypes.c_uint64, _u8p, ctypes.c_uint64,
                ctypes.c_uint64, _u64p, _u8p, _u8p, ctypes.c_uint64,
                _u64p, ctypes.POINTER(ctypes.c_int32),
            ]
            self._has_planar = True
        except AttributeError:
            self._has_planar = False
        # CPU merge-resolve may be absent in stale builds; probe and gate
        try:
            lib.cpu_merge_resolve.restype = ctypes.c_int64
            lib.cpu_merge_resolve.argtypes = [
                _u32p, _u32p, _u64p, _u8p, _u32p, _u32p,
                ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,
                ctypes.c_int32, ctypes.c_int32,
                _u32p, _u32p, _u64p, _u8p, _u32p, _u32p,
            ]
            self.has_merge_resolve = True
        except AttributeError:
            self.has_merge_resolve = False
        try:
            lib.cpu_merge_resolve_runs.restype = ctypes.c_int64
            lib.cpu_merge_resolve_runs.argtypes = [
                _u32p, _u32p, _u64p, _u8p, _u32p, _u32p, _u64p,
                ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,
                ctypes.c_uint32, ctypes.c_int32, ctypes.c_int32,
                _u32p, _u32p, _u64p, _u8p, _u32p, _u32p,
            ]
            self.has_merge_resolve_runs = True
        except AttributeError:
            self.has_merge_resolve_runs = False
        # RLZ codec may be absent in stale builds; probe and gate
        try:
            for dll in (lib, held):
                dll.rlz_compress.restype = ctypes.c_int64
                dll.rlz_compress.argtypes = [
                    _u8p, ctypes.c_uint64, _u8p, ctypes.c_uint64,
                ]
                dll.rlz_decompress.restype = ctypes.c_int64
                dll.rlz_decompress.argtypes = [
                    _u8p, ctypes.c_uint64, _u8p, ctypes.c_uint64,
                ]
            self.has_rlz = True
        except AttributeError:
            self.has_rlz = False
        # whole-file codecs (one GIL-free call per file) may be absent in
        # stale builds; probe and gate
        try:
            lib.tsst_planar_encode_file.restype = ctypes.c_int64
            lib.tsst_planar_encode_file.argtypes = [
                _u32p, ctypes.c_uint32, _u32p, _u32p, _u8p,
                _u32p, ctypes.c_uint32,
                ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,
                ctypes.c_int32, ctypes.c_uint32, ctypes.c_int32,
                _u8p, ctypes.c_uint64, _u64p, _u32p, _u8p, _u32p, _u32p,
                _u32p, _u64p,
            ]
            lib.tsst_decode_file_lanes.restype = ctypes.c_int64
            lib.tsst_decode_file_lanes.argtypes = [
                ctypes.c_int32, _u64p, ctypes.c_uint64, ctypes.c_int32,
                _u32p, _u32p, ctypes.c_uint64, ctypes.c_uint32,
                ctypes.c_uint32, ctypes.c_int32,
                _u32p, _u32p, _u32p, _u32p, _u32p, _u32p, _u32p, _u32p,
                ctypes.c_int32, ctypes.c_uint64, _u32p, _i64p,
                _u32p, ctypes.c_uint64, _u64p,
            ]
            lib.tsst_read_block.restype = ctypes.c_int64
            lib.tsst_read_block.argtypes = [
                ctypes.c_int32, ctypes.c_uint64, ctypes.c_uint64,
                ctypes.c_uint32, ctypes.POINTER(ctypes.c_void_p),
                ctypes.c_int32, ctypes.c_uint64, _u32p,
            ]
            held.tsst_free.restype = None
            held.tsst_free.argtypes = [ctypes.c_void_p]
            self.has_file_codecs = True
        except AttributeError:
            self.has_file_codecs = False
        lib.wal_scan.restype = ctypes.c_int64
        lib.wal_scan.argtypes = [
            _u8p, ctypes.c_uint64, ctypes.c_uint64,
            _u64p, _u64p, _u64p, _i64p,
        ]
        lib.wal_count_records.restype = ctypes.c_int64
        lib.wal_count_records.argtypes = [_u8p, ctypes.c_uint64]
        held.batch_index_ops.restype = ctypes.c_int64
        held.batch_index_ops.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_uint64, ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.bloom_add_many.restype = None
        lib.bloom_add_many.argtypes = [
            _u32p, ctypes.c_uint32, _u8p, _u64p, ctypes.c_uint64,
        ]
        lib.bloom_may_contain.restype = ctypes.c_int32
        lib.bloom_may_contain.argtypes = [
            _u32p, ctypes.c_uint32, _u8p, ctypes.c_uint64,
        ]

    # -- helpers -----------------------------------------------------------

    def _for_bytes(self, n: int):
        """The handle for a byte-codec call over ``n`` bytes: GIL kept
        while the call is short (~1 GB/s: 64 KB is ~60 us), dropped for
        anything longer."""
        return self._held if n <= _SHORT_CALL_BYTES else self._lib

    @staticmethod
    def _u8(arr: np.ndarray):
        return arr.ctypes.data_as(_u8p)

    @staticmethod
    def _u64(arr: np.ndarray):
        return arr.ctypes.data_as(_u64p)

    # -- API ---------------------------------------------------------------

    def crc32(self, data: bytes) -> int:
        buf = np.frombuffer(data, dtype=np.uint8)
        return int(self._lib.tsst_crc32(self._u8(buf), len(buf)))

    def encode_block(
        self, keys: List[bytes], seqs: List[int], vtypes: List[int],
        vals: List[bytes],
    ) -> bytes:
        n = len(keys)
        key_buf = np.frombuffer(b"".join(keys), dtype=np.uint8)
        val_buf = np.frombuffer(b"".join(vals), dtype=np.uint8)
        key_off = np.zeros(n + 1, dtype=np.uint64)
        val_off = np.zeros(n + 1, dtype=np.uint64)
        np.cumsum([len(k) for k in keys], out=key_off[1:])
        np.cumsum([len(v) for v in vals], out=val_off[1:])
        seq_arr = np.asarray(seqs, dtype=np.uint64)
        vt_arr = np.asarray(vtypes, dtype=np.uint8)
        cap = int(key_off[-1] + val_off[-1] + n * 17)
        out = np.empty(cap, dtype=np.uint8)
        if n == 0:
            return b""
        wrote = self._lib.tsst_encode_block(
            self._u8(key_buf if len(key_buf) else np.zeros(1, np.uint8)),
            self._u64(key_off),
            self._u64(seq_arr), self._u8(vt_arr),
            self._u8(val_buf if len(val_buf) else np.zeros(1, np.uint8)),
            self._u64(val_off),
            n, self._u8(out), cap,
        )
        if wrote < 0:
            raise ValueError("encode_block overflow")
        return out[:wrote].tobytes()

    def decode_block(self, raw: bytes) -> List[Tuple[bytes, int, int, bytes]]:
        data = np.frombuffer(raw, dtype=np.uint8)
        max_entries = max(1, len(raw) // 17)
        key_off = np.empty(max_entries, dtype=np.uint64)
        key_len = np.empty(max_entries, dtype=np.uint64)
        seqs = np.empty(max_entries, dtype=np.uint64)
        vtypes = np.empty(max_entries, dtype=np.uint8)
        val_off = np.empty(max_entries, dtype=np.uint64)
        val_len = np.empty(max_entries, dtype=np.uint64)
        n = self._lib.tsst_decode_block(
            self._u8(data), len(raw), max_entries,
            self._u64(key_off), self._u64(key_len),
            self._u64(seqs), self._u8(vtypes),
            self._u64(val_off), self._u64(val_len),
        )
        if n < 0:
            from ..errors import Corruption

            raise Corruption("native block decode failed")
        out = []
        for i in range(n):
            ko, kl = int(key_off[i]), int(key_len[i])
            vo, vl = int(val_off[i]), int(val_len[i])
            out.append((raw[ko:ko + kl], int(seqs[i]), int(vtypes[i]),
                        raw[vo:vo + vl]))
        return out

    def get_entries(self, raw: bytes, key: bytes,
                    max_matches: int = 64) -> Optional[Tuple[list, bool]]:
        """(entries, past_end) for ``key`` in one block: entries are
        (seq, vtype, value) newest-first as stored; past_end means the scan
        proved no later block can hold this key. None = slow path needed."""
        data = np.frombuffer(raw, dtype=np.uint8)
        kbuf = (np.frombuffer(key, dtype=np.uint8) if key
                else np.zeros(1, np.uint8))
        seqs = np.empty(max_matches, dtype=np.uint64)
        vtypes = np.empty(max_matches, dtype=np.uint8)
        val_off = np.empty(max_matches, dtype=np.uint64)
        val_len = np.empty(max_matches, dtype=np.uint64)
        past_end = ctypes.c_int32(0)
        n = self._held.tsst_get_entries(
            self._u8(data), len(raw), self._u8(kbuf), len(key), max_matches,
            self._u64(seqs), self._u8(vtypes), self._u64(val_off),
            self._u64(val_len), ctypes.byref(past_end),
        )
        if n == -1:
            # overflow, not corruption: retry with room for a deeper merge
            # stack instead of falling back to a full block re-decode
            bound = max(1, len(raw) // 17)
            if max_matches < bound:
                return self.get_entries(raw, key, min(bound, max_matches * 8))
            return None
        if n < 0:
            return None
        return (
            [
                (int(seqs[i]), int(vtypes[i]),
                 raw[int(val_off[i]):int(val_off[i]) + int(val_len[i])])
                for i in range(n)
            ],
            bool(past_end.value),
        )

    def planar_get_entries(self, raw: bytes, key: bytes,
                           max_matches: int = 64
                           ) -> Optional[Tuple[list, bool]]:
        """get_entries over a PLANAR block (storage/planar.py): binary
        search in C over the key planes, values reassembled from the
        value planes. None = slow path needed."""
        if not self._has_planar:
            return None
        data = np.frombuffer(raw, dtype=np.uint8)
        kbuf = (np.frombuffer(key, dtype=np.uint8) if key
                else np.zeros(1, np.uint8))
        try:
            _, _, vlen_cap, _ = unpack_planar_header(raw)
        except Corruption:
            return None  # slow path will raise the descriptive error
        seqs = np.empty(max_matches, dtype=np.uint64)
        vtypes = np.empty(max_matches, dtype=np.uint8)
        vals = np.zeros((max_matches, max(1, vlen_cap)), dtype=np.uint8)
        val_len = np.empty(max_matches, dtype=np.uint64)
        past_end = ctypes.c_int32(0)
        n = self._held.tsst_planar_get_entries(
            self._u8(data), len(raw), self._u8(kbuf), len(key),
            max_matches, self._u64(seqs), self._u8(vtypes),
            self._u8(vals), max(1, vlen_cap), self._u64(val_len),
            ctypes.byref(past_end),
        )
        if n == -1:
            if len(raw) >= 16:
                total = int.from_bytes(raw[:4], "little")
                if max_matches < total:
                    return self.planar_get_entries(
                        raw, key, min(total, max_matches * 8))
            return None
        if n < 0:
            return None
        return (
            [
                (int(seqs[i]), int(vtypes[i]),
                 vals[i, :int(val_len[i])].tobytes())
                for i in range(n)
            ],
            bool(past_end.value),
        )

    def merge_resolve(self, kw, klen, seq, vtype, vw, vlen,
                      uint64_add: bool, drop_tombstones: bool):
        """Native LSM merge-resolve (cpu_merge_resolve): inputs are the
        valid-prefix KVBatch lanes; returns (out_kw, out_klen, out_seq,
        out_vtype, out_vw, out_vlen, count). Semantics parity-pinned to
        numpy_merge_resolve (tests/test_native.py)."""
        n = len(klen)
        kwn = kw.shape[1]
        vwn = vw.shape[1]
        kw = np.ascontiguousarray(kw, dtype=np.uint32)
        klen = np.ascontiguousarray(klen, dtype=np.uint32)
        seq = np.ascontiguousarray(seq, dtype=np.uint64)
        vtype = np.ascontiguousarray(vtype, dtype=np.uint8)
        vw = np.ascontiguousarray(vw, dtype=np.uint32)
        vlen = np.ascontiguousarray(vlen, dtype=np.uint32)
        out_kw = np.empty((n, kwn), dtype=np.uint32)
        out_klen = np.empty(n, dtype=np.uint32)
        out_seq = np.empty(n, dtype=np.uint64)
        out_vtype = np.empty(n, dtype=np.uint8)
        out_vw = np.empty((n, vwn), dtype=np.uint32)
        out_vlen = np.empty(n, dtype=np.uint32)
        count = self._lib.cpu_merge_resolve(
            kw.ctypes.data_as(_u32p), klen.ctypes.data_as(_u32p),
            self._u64(seq), self._u8(vtype),
            vw.ctypes.data_as(_u32p), vlen.ctypes.data_as(_u32p),
            n, kwn, vwn, int(uint64_add), int(drop_tombstones),
            out_kw.ctypes.data_as(_u32p), out_klen.ctypes.data_as(_u32p),
            self._u64(out_seq), self._u8(out_vtype),
            out_vw.ctypes.data_as(_u32p), out_vlen.ctypes.data_as(_u32p),
        )
        if count < 0:
            raise ValueError("cpu_merge_resolve failed")
        return (out_kw, out_klen, out_seq, out_vtype, out_vw, out_vlen,
                int(count))

    def merge_resolve_runs(self, kw, klen, seq, vtype, vw, vlen,
                           run_offsets, uint64_add: bool,
                           drop_tombstones: bool):
        """Native k-way merge-resolve over PRE-SORTED runs
        (cpu_merge_resolve_runs): O(n log k) instead of the full-sort
        path's O(n log n). Caller must have verified each run is sorted
        in (key words asc, klen asc, seq desc) order."""
        n = len(klen)
        kwn = kw.shape[1]
        vwn = vw.shape[1]
        kw = np.ascontiguousarray(kw, dtype=np.uint32)
        klen = np.ascontiguousarray(klen, dtype=np.uint32)
        seq = np.ascontiguousarray(seq, dtype=np.uint64)
        vtype = np.ascontiguousarray(vtype, dtype=np.uint8)
        vw = np.ascontiguousarray(vw, dtype=np.uint32)
        vlen = np.ascontiguousarray(vlen, dtype=np.uint32)
        run_offsets = np.ascontiguousarray(run_offsets, dtype=np.uint64)
        out_kw = np.empty((n, kwn), dtype=np.uint32)
        out_klen = np.empty(n, dtype=np.uint32)
        out_seq = np.empty(n, dtype=np.uint64)
        out_vtype = np.empty(n, dtype=np.uint8)
        out_vw = np.empty((n, vwn), dtype=np.uint32)
        out_vlen = np.empty(n, dtype=np.uint32)
        count = self._lib.cpu_merge_resolve_runs(
            kw.ctypes.data_as(_u32p), klen.ctypes.data_as(_u32p),
            self._u64(seq), self._u8(vtype),
            vw.ctypes.data_as(_u32p), vlen.ctypes.data_as(_u32p),
            self._u64(run_offsets),
            n, len(run_offsets) - 1, kwn, vwn,
            int(uint64_add), int(drop_tombstones),
            out_kw.ctypes.data_as(_u32p), out_klen.ctypes.data_as(_u32p),
            self._u64(out_seq), self._u8(out_vtype),
            out_vw.ctypes.data_as(_u32p), out_vlen.ctypes.data_as(_u32p),
        )
        if count < 0:
            raise ValueError("cpu_merge_resolve_runs failed")
        return (out_kw, out_klen, out_seq, out_vtype, out_vw, out_vlen,
                int(count))

    def planar_encode_file(self, arrays, count: int, klen: int, vlen: int,
                           seq32: bool, block_entries: int,
                           compression: int, mixed: bool = False,
                           vmixed: bool = False):
        """Every PLANAR block of one file from lanes ``[0, count)`` in
        ONE call (tsst_planar_encode_file; the GIL is released for all
        of it). Returns ``(payload, offsets, sizes, codecs, checksums)``:
        the blocks' bytes back to back in a u8 array, and per block its
        offset there, size, index codec nibble and ``poly1w`` value.
        None when the lanes are narrower than the widths ask (the Python
        sink says what is wrong with them). ``mixed``: the rows' keys
        differ in length (``klen`` their widest): each block's header
        and key-length plane follow from its own rows. ``vmixed``: the
        same for the values' widths (``vlen`` their widest) and the
        value-length plane. Lanes with ``val_off`` (``count + 1`` word
        offsets) carry their values as ONE word stream, ``val_stream``
        (``tpu/format.py`` ``ValueStream``): the same blocks."""
        kw, vw = (klen + 3) // 4, (vlen + 3) // 4
        kw_be = np.ascontiguousarray(
            arrays["key_words_be"][:count], dtype=np.uint32)
        val_off = arrays.get("val_off")
        if val_off is not None:
            val_off = np.ascontiguousarray(val_off[:count + 1],
                                           dtype=np.uint64)
            val_words = np.ascontiguousarray(arrays["val_stream"],
                                             dtype=np.uint32)
            if len(val_off) != count + 1 or int(val_off[-1]) > len(
                    val_words):
                return None
            val_cols = 0
        else:
            val_words = np.ascontiguousarray(
                arrays["val_words"][:count], dtype=np.uint32)
            if val_words.ndim != 2 or val_words.shape[1] < vw:
                return None
            val_cols = val_words.shape[1]
        if kw_be.ndim != 2 or kw_be.shape[1] < kw:
            return None
        seq_lo = np.ascontiguousarray(
            arrays["seq_lo"][:count], dtype=np.uint32)
        seq_hi = np.ascontiguousarray(
            arrays["seq_hi"][:count], dtype=np.uint32)
        vtype = np.ascontiguousarray(arrays["vtype"][:count], dtype=np.uint8)
        nblocks = (count + block_entries - 1) // block_entries
        per_entry = 4 * (kw + 1 + (0 if seq32 else 1) + vw)
        # a block's payload is never larger than its uncompressed bytes
        cap = count * per_entry + nblocks * (16 + 4) + count
        key_len = None
        if mixed:
            key_len = np.ascontiguousarray(
                arrays["key_len"][:count], dtype=np.uint32)
            cap += count + 4 * nblocks  # the key-length planes
        val_len = None
        if vmixed:
            val_len = np.ascontiguousarray(
                arrays["val_len"][:count], dtype=np.uint32)
            cap += 2 * count + 4 * nblocks  # the value-length planes
        out = np.empty(cap, dtype=np.uint8)
        offs = np.empty(nblocks, dtype=np.uint64)
        sizes = np.empty(nblocks, dtype=np.uint32)
        codecs = np.empty(nblocks, dtype=np.uint8)
        chks = np.empty(nblocks, dtype=np.uint32)
        wrote = self._lib.tsst_planar_encode_file(
            kw_be.ctypes.data_as(_u32p), kw_be.shape[1],
            seq_lo.ctypes.data_as(_u32p), seq_hi.ctypes.data_as(_u32p),
            self._u8(vtype),
            val_words.ctypes.data_as(_u32p), val_cols,
            count, klen, vlen, int(seq32), block_entries, int(compression),
            self._u8(out), cap, self._u64(offs),
            sizes.ctypes.data_as(_u32p), self._u8(codecs),
            chks.ctypes.data_as(_u32p),
            None if key_len is None else key_len.ctypes.data_as(_u32p),
            None if val_len is None else val_len.ctypes.data_as(_u32p),
            None if val_off is None else self._u64(val_off),
        )
        if wrote < 0:
            raise ValueError(f"tsst_planar_encode_file failed ({wrote})")
        return out[:wrote], offs, sizes, codecs, chks

    def decode_file_lanes(self, fd: int, index: np.ndarray, planar: bool,
                          klen: int, vlen: int, rows: int,
                          chk_mode: int = 0, chk_len: int = 0,
                          key_cols: int = 6, vmixed: bool = False,
                          stream=None):
        """Every block of one file -> the eight kernel lanes in ONE call
        (tsst_decode_file_lanes: pread, inflate, transpose; the GIL is
        released for all of it). ``index``: (nblocks, 3) u64 of offset,
        size, codec nibble. ``key_cols``: the key lanes' width in words
        (6, or 12 for keys of 25 to 48 bytes). ``klen == 0`` (row format
        only): the keys have whatever lengths they have (1 to
        ``4 * key_cols`` bytes; ``lanes["key_len"]``), and the value
        width is block 0's first entry's. A PLANAR file's ``klen`` is its
        widest key. ``vmixed``: the values differ in width, ``vlen`` the
        widest (``lanes["val_len"]`` has each row's). ``chk_mode`` 1 / 2:
        also each block's poly1 / poly1w value over ``chk_len``.
        ``stream`` (``tpu/format.py`` ``ValueStream``): the values are
        written into its word stream from its ``pos`` on, which moves past
        them, and the lanes have no ``val_words``.

        Returns ``(status, lanes, checksums, blocks)``: status >= 0 is
        the row count (lanes cut to it); below zero the C routine's code
        (-1 read, -2 corrupt block, -3 width drift, -4 more rows than
        ``rows``, -6 the values overrun ``stream``) and lanes is None.
        ``blocks``: how many blocks it got
        through, the one it stopped at included — that many checksums
        are computed."""
        index = np.ascontiguousarray(index, dtype=np.uint64)
        nblocks = len(index)
        chks = np.zeros(nblocks if chk_mode else 1, dtype=np.uint32)
        klen_io = ctypes.c_uint32(klen)
        vlen_io = ctypes.c_uint32(vlen)
        err_block = ctypes.c_int64(-1)
        val_cols = max(2, (vlen + 3) // 4)
        stream_pos = ctypes.c_uint64(0 if stream is None else stream.pos)
        while True:
            lanes = {
                "key_words_be": np.empty((rows, key_cols), dtype=np.uint32),
                "key_words_le": np.empty((rows, key_cols), dtype=np.uint32),
                "key_len": np.empty(rows, dtype=np.uint32),
                "seq_hi": np.empty(rows, dtype=np.uint32),
                "seq_lo": np.empty(rows, dtype=np.uint32),
                "vtype": np.empty(rows, dtype=np.uint32),
                "val_len": np.empty(rows, dtype=np.uint32),
            }
            if stream is None:
                lanes["val_words"] = np.empty((rows, val_cols),
                                              dtype=np.uint32)
            got = self._lib.tsst_decode_file_lanes(
                fd, self._u64(index), nblocks, int(planar),
                ctypes.byref(klen_io), ctypes.byref(vlen_io), rows,
                val_cols, key_cols, int(vmixed),
                *(lanes[f].ctypes.data_as(_u32p) if f in lanes else None
                  for f in ("key_words_be", "key_words_le", "key_len",
                            "seq_hi", "seq_lo", "vtype", "val_words",
                            "val_len")),
                chk_mode, chk_len, chks.ctypes.data_as(_u32p),
                ctypes.byref(err_block),
                None if stream is None
                else stream.words.ctypes.data_as(_u32p),
                0 if stream is None else len(stream.words),
                ctypes.byref(stream_pos),
            )
            if got != -5:
                break
            # inferred widths want wider value lanes: once more, exact
            val_cols = max(2, (int(vlen_io.value) + 3) // 4)
        if got < 0:
            return int(got), None, chks, int(err_block.value) + 1
        if stream is not None:
            stream.pos = int(stream_pos.value)
        if got < rows:
            lanes = {f: a[:got] for f, a in lanes.items()}
        return int(got), lanes, chks, nblocks

    def read_block(self, fd: int, off: int, size: int, codec: int,
                   chk_mode: int = 0, chk_len: int = 0):
        """One block for a point read: pread + inflate (+ ``chk_mode``
        1 / 2: its poly1 / poly1w value over ``chk_len``) in ONE call
        that drops the GIL once (tsst_read_block). ``(raw bytes,
        checksum)``, or None when the block cannot be read or does not
        inflate: the Python reader then says what is wrong with it."""
        out = ctypes.c_void_p()
        chk = ctypes.c_uint32(0)
        n = self._lib.tsst_read_block(
            fd, off, size, codec, ctypes.byref(out), chk_mode, chk_len,
            ctypes.byref(chk))
        if n < 0:
            return None
        try:
            return ctypes.string_at(out, n), chk.value
        finally:
            self._held.tsst_free(out)

    def rlz_compress(self, data: bytes) -> bytes:
        from ..rlz import max_compressed_len

        src = (np.frombuffer(data, dtype=np.uint8) if data
               else np.zeros(1, np.uint8))
        cap = max_compressed_len(len(data))
        out = np.empty(cap, dtype=np.uint8)
        wrote = self._for_bytes(len(data)).rlz_compress(
            self._u8(src), len(data), self._u8(out), cap)
        if wrote < 0:  # sized by max_compressed_len — cannot happen
            raise ValueError("rlz_compress overflow")
        return out[:wrote].tobytes()

    def rlz_decompress(self, data: bytes, max_out: int) -> Optional[bytes]:
        """Decoded bytes, or None on malformed/oversized input (the
        Python wrapper raises the descriptive error)."""
        src = (np.frombuffer(data, dtype=np.uint8) if data
               else np.zeros(1, np.uint8))
        if len(data) >= 4:
            declared = int.from_bytes(data[:4], "little")
            if declared > max_out:
                return None
        else:
            return None
        # +32 slack enables the decoder's 16-byte wildcopy fast path
        # (it may scribble up to 15 bytes past the logical end)
        out = np.empty(declared + 32, dtype=np.uint8)
        n = self._for_bytes(declared).rlz_decompress(
            self._u8(src), len(data), self._u8(out), declared + 32)
        if n < 0:
            return None
        return out[:n].tobytes()

    def wal_scan(self, raw: bytes) -> Tuple[List[Tuple[int, int, int]], int]:
        """Returns ([(start_seq, body_off, body_len)], bad_crc_at)."""
        data = np.frombuffer(raw, dtype=np.uint8)
        # exact-size output arrays via a cheap structural pre-count (a
        # len/16 upper bound would allocate ~96MB for a 64MiB segment)
        max_records = max(
            1, int(self._lib.wal_count_records(self._u8(data), len(raw)))
        )
        seqs = np.empty(max_records, dtype=np.uint64)
        offs = np.empty(max_records, dtype=np.uint64)
        lens = np.empty(max_records, dtype=np.uint64)
        bad = ctypes.c_int64(-1)
        n = self._lib.wal_scan(
            self._u8(data), len(raw), max_records,
            self._u64(seqs), self._u64(offs), self._u64(lens),
            ctypes.byref(bad),
        )
        return (
            [(int(seqs[i]), int(offs[i]), int(lens[i])) for i in range(n)],
            int(bad.value),
        )

    def batch_index(self, frame: bytes, pos: int, num_ops: int):
        """The headers of ``num_ops`` ops of a WriteBatch frame from
        ``pos`` on (``storage/records.py`` ``_index_ops``, whose caller
        has bounded ``num_ops`` by the frame's length): ``(types, cols,
        end)`` — a u8 type an op; the (4, num_ops) int64 columns key
        offset, key length, value offset, value length; the position
        behind the last op. ``Corruption`` for a type outside 1-4 or an
        op that runs past the frame. GIL kept: microseconds of C on a
        ``write`` RPC's path."""
        types = np.empty(num_ops, np.uint8)
        cols = np.empty((4, num_ops), np.int64)
        end = self._held.batch_index_ops(
            frame, len(frame), pos, num_ops,
            types.ctypes.data, cols.ctypes.data)
        if end < 0:
            raise Corruption(
                "bad batch: " + ("op type outside 1-4" if end == -1
                                 else "an op runs past the frame"))
        return types, cols, end

    def bloom_add_many(self, words: np.ndarray, keys: List[bytes]) -> None:
        n = len(keys)
        if n == 0:
            return
        key_buf = np.frombuffer(b"".join(keys), dtype=np.uint8)
        key_off = np.zeros(n + 1, dtype=np.uint64)
        np.cumsum([len(k) for k in keys], out=key_off[1:])
        self.bloom_add_concat(words, key_buf, key_off, n)

    def bloom_add_concat(self, words: np.ndarray, key_buf: np.ndarray,
                         key_off: np.ndarray, n: int) -> None:
        """bloom_add_many over an already-concatenated key buffer +
        (n+1,) u64 offsets — the no-Python-objects bulk path."""
        key_buf = np.ascontiguousarray(key_buf, dtype=np.uint8)
        key_off = np.ascontiguousarray(key_off, dtype=np.uint64)
        self._lib.bloom_add_many(
            words.ctypes.data_as(_u32p), len(words),
            self._u8(key_buf if len(key_buf) else np.zeros(1, np.uint8)),
            self._u64(key_off), n,
        )

    def bloom_may_contain(self, words: np.ndarray, key: bytes) -> bool:
        buf = np.frombuffer(key, dtype=np.uint8) if key else np.zeros(1, np.uint8)
        return bool(self._lib.bloom_may_contain(
            words.ctypes.data_as(_u32p), len(words), self._u8(buf), len(key)
        ))


def _load() -> Optional[NativeLib]:
    if os.environ.get("RSTPU_DISABLE_NATIVE"):
        return None
    # Never load a .so older than its source: it is either a stale build
    # or a binary of unknown provenance. Rebuild from tsst_native.cc; on
    # build failure fall back to the pure-Python paths, loudly.
    if not _so_current() and not _build():
        _no_native("build failed" + (
            f"; refusing stale/unverified {_SO}"
            if os.path.isfile(_SO) else ""))
        return None
    try:
        return NativeLib(_SO)
    except (OSError, AttributeError) as e:
        _no_native(f"load failed: {e}")
        return None


def _no_native(why: str) -> None:
    """The pure-Python codecs take over — counted and logged at ERROR
    with the other host fallbacks, never quietly."""
    from ..compaction import record_host_fallback

    record_host_fallback("native_lib", why)


_UNSET = object()
_native: object = _UNSET
_native_lock = threading.Lock()


def get_native() -> Optional[NativeLib]:
    """Lazily build+load the native library on first use (not at import).
    Locked: first use happens on hot paths from multiple threads, and two
    concurrent `make` runs could dlopen a partially written .so."""
    global _native
    if _native is _UNSET:
        with _native_lock:
            if _native is _UNSET:
                _native = _load()
    return _native  # type: ignore[return-value]


def get_file_codecs() -> Optional[NativeLib]:
    """The library when it has the whole-file / whole-block codecs
    (``has_file_codecs``), else None: their callers then take the Python
    codecs."""
    lib = get_native()
    return lib if lib is not None and lib.has_file_codecs else None


def rebuild_native() -> NativeLib:
    """Rebuild the library from ``tsst_native.cc`` and load THAT build,
    or raise. For runs that must show the library came from the
    committed source: ``*.so`` is git-ignored and ``_so_current`` trusts
    mtimes, which a copied tree may not preserve. Must run before the
    library's first use in the process (a loaded image cannot be
    swapped)."""
    global _native
    with _native_lock:
        if _native is not _UNSET:
            raise RuntimeError("native library already resolved in "
                               "this process; rebuild first")
        if os.path.isfile(_SO):
            os.remove(_SO)
        if not _build():
            raise RuntimeError("native build failed (see the log)")
        _native = NativeLib(_SO)
        return _native


def native_available() -> bool:
    return get_native() is not None


def __getattr__(name: str):
    # PEP 562: keep `binding.NATIVE` working without import-time side effects.
    if name == "NATIVE":
        return get_native()
    raise AttributeError(name)
