// Native storage hot paths (C ABI, loaded via ctypes).
//
// The reference's entire storage engine is C++ (vendored RocksDB); this
// library provides the byte-crunching loops the Python engine spends its
// CPU time in — TSST block encode/decode, WAL record scanning with CRC,
// and bloom filter build/probe — with the exact same formats as the
// Python implementations (parity-tested). The TPU owns compaction math;
// this owns the host-side byte plumbing.
//
// Formats (must stay in lockstep with sst.py / wal.py / bloom.py):
//   block entry : u32 key_len | key | u64 seq | u8 vtype | u32 val_len | val
//   WAL record  : u64 start_seq | u32 batch_len | u32 crc32(batch) | batch
//   bloom       : register-blocked, FNV-1a over 6 LE u32 prefix words +
//                 length word, murmur fmix32 finalizer, K=6 bits from 5-bit
//                 slices of h2
//   PLANAR block: storage/planar.py (header + u32 planes); whole-file
//                 encode / decode at the end of this file

#include <algorithm>
#include <cerrno>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <vector>

#include <unistd.h>
#include <zlib.h>

extern "C" {

// ---------------------------------------------------------------------------
// crc32 (zlib-compatible, slice-by-1 table; built on first use)
// ---------------------------------------------------------------------------

struct CrcTable {
  uint32_t t[256];
  CrcTable() {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int k = 0; k < 8; k++)
        c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      t[i] = c;
    }
  }
};

static const CrcTable& crc_table() {
  // C++11 magic static: thread-safe one-time init (no unsynchronized
  // flag race between concurrent first callers).
  static const CrcTable table;
  return table;
}

uint32_t tsst_crc32(const uint8_t* data, uint64_t len) {
  const CrcTable& tbl = crc_table();
  uint32_t c = 0xFFFFFFFFu;
  for (uint64_t i = 0; i < len; i++)
    c = tbl.t[(c ^ data[i]) & 0xFF] ^ (c >> 8);
  return c ^ 0xFFFFFFFFu;
}

// ---------------------------------------------------------------------------
// TSST block codec
// ---------------------------------------------------------------------------

static inline void put_u32(uint8_t* p, uint32_t v) { memcpy(p, &v, 4); }
static inline void put_u64(uint8_t* p, uint64_t v) { memcpy(p, &v, 8); }
static inline uint32_t get_u32(const uint8_t* p) { uint32_t v; memcpy(&v, p, 4); return v; }
static inline uint64_t get_u64(const uint8_t* p) { uint64_t v; memcpy(&v, p, 8); return v; }

// Encode n entries into out. keys/vals are concatenated byte arrays with
// per-entry offsets (offsets[n] = total length). Returns bytes written,
// or -1 if out_cap is too small.
int64_t tsst_encode_block(
    const uint8_t* keys, const uint64_t* key_offsets,
    const uint64_t* seqs, const uint8_t* vtypes,
    const uint8_t* vals, const uint64_t* val_offsets,
    uint64_t n, uint8_t* out, uint64_t out_cap) {
  uint64_t pos = 0;
  for (uint64_t i = 0; i < n; i++) {
    uint64_t klen = key_offsets[i + 1] - key_offsets[i];
    uint64_t vlen = val_offsets[i + 1] - val_offsets[i];
    uint64_t need = 4 + klen + 8 + 1 + 4 + vlen;
    if (pos + need > out_cap) return -1;
    put_u32(out + pos, (uint32_t)klen); pos += 4;
    memcpy(out + pos, keys + key_offsets[i], klen); pos += klen;
    put_u64(out + pos, seqs[i]); pos += 8;
    out[pos++] = vtypes[i];
    put_u32(out + pos, (uint32_t)vlen); pos += 4;
    memcpy(out + pos, vals + val_offsets[i], vlen); pos += vlen;
  }
  return (int64_t)pos;
}

// Decode a block: fills per-entry offset/seq/vtype arrays (caller sizes
// them at max_entries) and returns the entry count, or -1 on corruption /
// overflow. Key/value BYTES are not copied — offsets index into `data`.
int64_t tsst_decode_block(
    const uint8_t* data, uint64_t len, uint64_t max_entries,
    uint64_t* key_off, uint64_t* key_len,
    uint64_t* seqs, uint8_t* vtypes,
    uint64_t* val_off, uint64_t* val_len) {
  uint64_t pos = 0, i = 0;
  while (pos < len) {
    if (i >= max_entries) return -1;
    if (pos + 4 > len) return -1;
    uint32_t klen = get_u32(data + pos); pos += 4;
    if (pos + klen + 8 + 1 + 4 > len) return -1;
    key_off[i] = pos; key_len[i] = klen; pos += klen;
    seqs[i] = get_u64(data + pos); pos += 8;
    vtypes[i] = data[pos]; pos += 1;
    uint32_t vlen = get_u32(data + pos); pos += 4;
    if (pos + vlen > len) return -1;
    val_off[i] = pos; val_len[i] = vlen; pos += vlen;
    i++;
  }
  return (int64_t)i;
}

// Point lookup with early exit: walk the (sorted) block once, collect all
// entries for `key` (MERGE stacks span multiple entries), stop as soon as
// a greater key appears. One C call replaces a Python decode of the whole
// block. Returns the match count (0 = absent), -1 when max_matches was too
// small (caller retries bigger), -2 on corruption.
// Sets *past_end=1 iff the scan proved no later entry can match.
int64_t tsst_get_entries(
    const uint8_t* data, uint64_t len,
    const uint8_t* key, uint64_t klen, uint64_t max_matches,
    uint64_t* seqs, uint8_t* vtypes,
    uint64_t* val_off, uint64_t* val_len,
    int32_t* past_end) {
  *past_end = 0;
  uint64_t pos = 0, found = 0;
  while (pos < len) {
    if (pos + 4 > len) return -2;
    uint32_t eklen = get_u32(data + pos); pos += 4;
    if (pos + eklen + 8 + 1 + 4 > len) return -2;
    const uint8_t* ekey = data + pos; pos += eklen;
    uint64_t seq = get_u64(data + pos); pos += 8;
    uint8_t vt = data[pos]; pos += 1;
    uint32_t vlen = get_u32(data + pos); pos += 4;
    if (pos + vlen > len) return -2;
    uint64_t voff = pos; pos += vlen;
    uint64_t minlen = eklen < klen ? eklen : klen;
    int cmp = memcmp(ekey, key, minlen);
    if (cmp == 0 && eklen == klen) {
      if (found >= max_matches) return -1;
      seqs[found] = seq; vtypes[found] = vt;
      val_off[found] = voff; val_len[found] = vlen;
      found++;
    } else if (cmp > 0 || (cmp == 0 && eklen > klen)) {
      *past_end = 1;
      break;  // sorted: nothing later can match
    }
  }
  return (int64_t)found;
}

// ---------------------------------------------------------------------------
// WAL record scan
// ---------------------------------------------------------------------------

// Cheap structural pass (no CRC): count of complete records, so callers
// can allocate exact-size output arrays instead of len/16 upper bounds.
int64_t wal_count_records(const uint8_t* data, uint64_t len) {
  uint64_t pos = 0, i = 0;
  while (pos + 16 <= len) {
    uint32_t blen = get_u32(data + pos + 8);
    if (pos + 16 + blen > len) break;
    pos += 16 + blen;
    i++;
  }
  return (int64_t)i;
}

// Scans records; fills start_seqs/body_offsets/body_lens; returns count.
// Stops at a torn tail. Sets *bad_crc_at to the offset of a CRC-mismatched
// record (else -1) — callers decide whether that is corruption or a tail.
int64_t wal_scan(
    const uint8_t* data, uint64_t len, uint64_t max_records,
    uint64_t* start_seqs, uint64_t* body_offsets, uint64_t* body_lens,
    int64_t* bad_crc_at) {
  *bad_crc_at = -1;
  uint64_t pos = 0, i = 0;
  while (pos + 16 <= len && i < max_records) {
    uint64_t seq = get_u64(data + pos);
    uint32_t blen = get_u32(data + pos + 8);
    uint32_t crc = get_u32(data + pos + 12);
    uint64_t body = pos + 16;
    if (body + blen > len) break;  // torn tail
    if (tsst_crc32(data + body, blen) != crc) {
      *bad_crc_at = (int64_t)pos;
      break;
    }
    start_seqs[i] = seq;
    body_offsets[i] = body;
    body_lens[i] = blen;
    pos = body + blen;
    i++;
  }
  return (int64_t)i;
}

// ---------------------------------------------------------------------------
// write-batch frame index (format: storage/records.py)
// ---------------------------------------------------------------------------

// The headers of `num_ops` ops from `pos` on, in frame order:
//   op : u8 type | u32 key_len | key | u32 val_len | val
// types[i] is op i's type; cols holds four columns of num_ops each: key
// offset, key length, value offset, value length. Returns the position
// behind the last op; -1 for a type outside 1..4, -2 for an op that runs
// past the frame. Lengths are the frame's own words: every sum stays in
// 64 bits and is compared as a remainder, so none can wrap.
int64_t batch_index_ops(
    const uint8_t* data, uint64_t len, uint64_t pos, uint64_t num_ops,
    uint8_t* types, int64_t* cols) {
  int64_t* key_off = cols;
  int64_t* key_len = cols + num_ops;
  int64_t* val_off = cols + 2 * num_ops;
  int64_t* val_len = cols + 3 * num_ops;
  if (pos > len) return -2;
  for (uint64_t i = 0; i < num_ops; i++) {
    if (len - pos < 5) return -2;
    uint8_t t = data[pos];
    if (t < 1 || t > 4) return -1;
    uint64_t kl = get_u32(data + pos + 1);
    pos += 5;
    if (len - pos < kl || len - pos - kl < 4) return -2;
    uint64_t vl = get_u32(data + pos + kl);
    types[i] = t;
    key_off[i] = (int64_t)pos;
    key_len[i] = (int64_t)kl;
    pos += kl + 4;
    if (len - pos < vl) return -2;
    val_off[i] = (int64_t)pos;
    val_len[i] = (int64_t)vl;
    pos += vl;
  }
  return (int64_t)pos;
}

// ---------------------------------------------------------------------------
// bloom (format-identical to storage/bloom.py)
// ---------------------------------------------------------------------------

static inline uint32_t fmix32(uint32_t h) {
  h ^= h >> 16; h *= 0x85EBCA6Bu;
  h ^= h >> 13; h *= 0xC2B2AE35u;
  h ^= h >> 16; return h;
}

static inline void bloom_hash(const uint8_t* key, uint64_t klen,
                              uint32_t* h1, uint32_t* h2) {
  uint8_t prefix[24];
  memset(prefix, 0, 24);
  memcpy(prefix, key, klen < 24 ? klen : 24);
  uint32_t h = 2166136261u;
  for (int w = 0; w < 6; w++) {
    uint32_t word; memcpy(&word, prefix + 4 * w, 4);
    h = (h ^ word) * 16777619u;
  }
  h = (h ^ (uint32_t)klen) * 16777619u;
  *h1 = fmix32(h);
  *h2 = fmix32(h * 0x9E3779B1u + 1u);
}

void bloom_add_many(
    uint32_t* words, uint32_t num_words,
    const uint8_t* keys, const uint64_t* key_offsets, uint64_t n) {
  for (uint64_t i = 0; i < n; i++) {
    uint32_t h1, h2;
    uint64_t klen = key_offsets[i + 1] - key_offsets[i];
    bloom_hash(keys + key_offsets[i], klen, &h1, &h2);
    uint32_t mask = 0;
    for (int j = 0; j < 6; j++) mask |= 1u << ((h2 >> (5 * j)) & 31u);
    words[h1 % num_words] |= mask;
  }
}

int32_t bloom_may_contain(
    const uint32_t* words, uint32_t num_words,
    const uint8_t* key, uint64_t klen) {
  uint32_t h1, h2;
  bloom_hash(key, klen, &h1, &h2);
  uint32_t mask = 0;
  for (int j = 0; j < 6; j++) mask |= 1u << ((h2 >> (5 * j)) & 31u);
  return (words[h1 % num_words] & mask) == mask;
}

// ---------------------------------------------------------------------------
// RLZ1 — fast byte codec (LZ4/snappy-class; format owned by storage/rlz.py)
// ---------------------------------------------------------------------------
//
// The reference compresses SST blocks with Snappy/ZSTD and RPC channels
// with snappy transforms (thrift_client_pool.h:277-284); zlib (the only
// in-image codec) costs real CPU on the ingest path. RLZ1 is a greedy
// LZ77 with a depth-1 hash table — single pass, byte-aligned output,
// decode is a straight copy loop. Format (little-endian):
//
//   u32 raw_len
//   tokens until raw_len bytes are produced:
//     0x01..0x7F        literal run of <tag> bytes (follow inline)
//     0x80|L, u16 dist  match: copy L+4 bytes (4..131) from <dist> back
//                       (1..65535; may overlap itself, copied bytewise)
//
// Worst case (incompressible): 4 + n + ceil(n/127) bytes.

static inline uint32_t rlz_hash(uint32_t v) {
  // Fibonacci multiplicative hash of the next 4 bytes -> table index.
  return (v * 2654435761u) >> 18;  // 14-bit table
}

#define RLZ_TABLE_BITS 14
#define RLZ_MIN_MATCH 4u
#define RLZ_MAX_MATCH 131u
#define RLZ_MAX_DIST 65535u

int64_t rlz_compress(const uint8_t* src, uint64_t n,
                     uint8_t* dst, uint64_t cap) {
  if (n > 0xFFFFFFFFu) return -1;  // raw_len is a u32 header field
  if (cap < 4) return -1;
  put_u32(dst, (uint32_t)n);
  uint64_t w = 4;
  uint32_t table[1u << RLZ_TABLE_BITS];
  for (uint32_t i = 0; i < (1u << RLZ_TABLE_BITS); i++)
    table[i] = 0xFFFFFFFFu;
  uint64_t lit_start = 0;
  uint64_t i = 0;

  // emit pending literals [lit_start, end) in <=127-byte runs
  #define RLZ_FLUSH_LITS(end)                                    \
    do {                                                         \
      uint64_t run = (end) - lit_start;                          \
      while (run > 0) {                                          \
        uint64_t take = run > 127 ? 127 : run;                   \
        if (w + 1 + take > cap) return -1;                       \
        dst[w++] = (uint8_t)take;                                \
        memcpy(dst + w, src + lit_start, take);                  \
        w += take; lit_start += take; run -= take;               \
      }                                                          \
    } while (0)

  while (i + RLZ_MIN_MATCH <= n) {
    uint32_t v = get_u32(src + i);
    uint32_t h = rlz_hash(v);
    uint32_t cand = table[h];
    table[h] = (uint32_t)i;
    if (cand != 0xFFFFFFFFu && i - cand <= RLZ_MAX_DIST &&
        get_u32(src + cand) == v) {
      uint64_t len = RLZ_MIN_MATCH;
      uint64_t max_len = n - i;
      if (max_len > RLZ_MAX_MATCH) max_len = RLZ_MAX_MATCH;
      while (len < max_len && src[cand + len] == src[i + len]) len++;
      RLZ_FLUSH_LITS(i);
      if (w + 3 > cap) return -1;
      dst[w++] = (uint8_t)(0x80u | (len - RLZ_MIN_MATCH));
      uint32_t dist = (uint32_t)(i - cand);
      dst[w++] = (uint8_t)(dist & 0xFF);
      dst[w++] = (uint8_t)(dist >> 8);
      i += len;
      lit_start = i;
      // seed the table at the match tail so back-to-back repeats chain
      if (i + RLZ_MIN_MATCH <= n)
        table[rlz_hash(get_u32(src + i - 1))] = (uint32_t)(i - 1);
    } else {
      i++;
    }
  }
  RLZ_FLUSH_LITS(n);
  #undef RLZ_FLUSH_LITS
  return (int64_t)w;
}

// Returns decoded length, or -1 on malformed/overflowing input. Never
// reads past src+n; writes stay within dst+cap. When ``cap`` exceeds
// raw_len by >= 32 bytes of slack (the Python binding allocates it),
// copies use unconditional 16-byte "wildcopy" chunks that may scribble
// up to 15 bytes past the logical end — never past dst+cap — and are
// overwritten by subsequent tokens or ignored.
int64_t rlz_decompress(const uint8_t* src, uint64_t n,
                       uint8_t* dst, uint64_t cap) {
  if (n < 4) return -1;
  uint64_t raw_len = get_u32(src);
  if (raw_len > cap) return -1;
  uint64_t r = 4, w = 0;
  while (w < raw_len) {
    if (r >= n) return -1;
    uint8_t tag = src[r++];
    if (tag & 0x80u) {
      uint64_t len = (tag & 0x7Fu) + RLZ_MIN_MATCH;
      if (r + 2 > n) return -1;
      uint32_t dist = (uint32_t)src[r] | ((uint32_t)src[r + 1] << 8);
      r += 2;
      if (dist == 0 || dist > w || w + len > raw_len) return -1;
      if (dist >= len && dist >= 16 && w + len + 16 <= cap) {
        // wildcopy: dist >= 16 keeps every 16-byte chunk's read region
        // disjoint from its own write (no memcpy overlap); the tail
        // read tops out at w - dist + len + 15 < w + len + 16 <= cap
        uint64_t k = 0;
        do {
          memcpy(dst + w + k, dst + w - dist + k, 16);
          k += 16;
        } while (k < len);
        w += len;
      } else if (dist >= len) {
        memcpy(dst + w, dst + w - dist, len);  // disjoint: one copy
        w += len;
      } else {
        // overlapping run: replicate the period bytewise
        for (uint64_t k = 0; k < len; k++, w++) dst[w] = dst[w - dist];
      }
    } else {
      if (tag == 0) return -1;
      uint64_t take = tag;
      if (r + take > n || w + take > raw_len) return -1;
      if (w + take + 16 <= cap && r + take + 16 <= n) {
        // wildcopy needs slack on BOTH buffers (the tail chunk reads
        // up to 15 bytes past the literal run inside src)
        uint64_t k = 0;
        do {
          memcpy(dst + w + k, src + r + k, 16);
          k += 16;
        } while (k < take);
      } else {
        memcpy(dst + w, src + r, take);
      }
      r += take;
      w += take;
    }
  }
  return (int64_t)w;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// PLANAR block point lookup (storage/planar.py layout)
// ---------------------------------------------------------------------------
//
// Block: u32 n | u8 klen | u8 vlen_lo | u8 flags | u8 vlen_hi | u64 0
// (vlen = vlen_lo | vlen_hi<<8 — u16, byte 7 was reserved-zero so old
// files read back unchanged), then u32
// planes: key words (BE values, ceil(klen/4) x n; klen up to 48), key
// lengths (only when flags&2: ceil(n/4), 4 packed/word; klen is then the
// block's widest key and a shorter key's tail bytes are zero), seq_lo
// (n), seq_hi (n, absent when flags&1), vtype (ceil(n/4), 4 packed/word),
// value lengths (only when flags&4: ceil(n/2), a u16 each, 2 packed/word;
// vlen is then the block's widest value), value words (LE values,
// ceil(vlen/4) x n). Keys ascending bytewise, which on the planes is the
// zero-padded big-endian words, then the length -> binary search, then
// the contiguous match run (MERGE stacks).
// -2 = malformed.

static inline int planar_cmp_key(
    const uint32_t* kw_planes, uint64_t n, uint64_t i,
    uint32_t bklen, const uint8_t* key, uint64_t klen) {
  // compare entry i's key bytes (BE bytes of each plane word) vs key
  uint64_t min_len = bklen < klen ? bklen : klen;
  for (uint64_t b = 0; b < min_len; b++) {
    uint32_t w; memcpy(&w, (const uint8_t*)(kw_planes + (b / 4) * n + i), 4);
    uint8_t eb = (uint8_t)(w >> (24 - 8 * (b % 4)));
    if (eb != key[b]) return eb < key[b] ? -1 : 1;
  }
  if (bklen == klen) return 0;
  return bklen < klen ? -1 : 1;
}

extern "C" int64_t tsst_planar_get_entries(
    const uint8_t* data, uint64_t len,
    const uint8_t* key, uint64_t klen, uint64_t max_matches,
    uint64_t* seqs, uint8_t* vtypes,
    uint8_t* out_vals, uint64_t vlen_cap, uint64_t* val_lens,
    int32_t* past_end) {
  *past_end = 0;
  if (len < 16) return -2;
  uint32_t n = get_u32(data);
  uint8_t bklen = data[4], flags = data[6];
  uint16_t bvlen = (uint16_t)data[5] | ((uint16_t)data[7] << 8);
  if (bklen == 0 || bklen > 48) return -2;
  uint64_t kw = (bklen + 3) / 4, vw = ((uint64_t)bvlen + 3) / 4;
  int seq32 = flags & 1, klens = flags & 2, vlens = flags & 4;
  uint64_t words = (uint64_t)n * (kw + 1 + (seq32 ? 0 : 1) + vw)
                 + (klens ? 2 : 1) * (((uint64_t)n + 3) / 4)
                 + (vlens ? ((uint64_t)n + 1) / 2 : 0);
  if (len != 16 + 4 * words) return -2;
  if (n == 0) return 0;
  const uint32_t* planes = (const uint32_t*)(data + 16);
  const uint32_t* kwp = planes;
  // an entry's own key length: its byte of the plane, else the block's
  const uint8_t* klp = klens ? (const uint8_t*)(planes + kw * n) : nullptr;
  const uint32_t* seq_lo = planes + kw * n + (klens ? (n + 3) / 4 : 0);
  const uint32_t* seq_hi = seq32 ? nullptr : seq_lo + n;
  const uint8_t* vtp = (const uint8_t*)(seq_lo + n + (seq32 ? 0 : n));
  // an entry's own value length: its u16 of the plane, else the block's
  const uint8_t* vlp = vlens ? vtp + 4 * ((n + 3) / 4) : nullptr;
  const uint32_t* vvp = (const uint32_t*)(
      vtp + 4 * ((n + 3) / 4) + (vlens ? 4 * ((n + 1) / 2) : 0));

  // lower_bound: first index with entry key >= query key
  uint64_t lo = 0, hi = n;
  while (lo < hi) {
    uint64_t mid = (lo + hi) / 2;
    uint32_t ek = klp ? klp[mid] : bklen;
    if (ek > bklen) return -2;
    if (planar_cmp_key(kwp, n, mid, ek, key, klen) < 0) lo = mid + 1;
    else hi = mid;
  }
  uint64_t found = 0;
  for (uint64_t i = lo; i < n; i++) {
    uint32_t ek = klp ? klp[i] : bklen;
    if (ek > bklen) return -2;
    int c = planar_cmp_key(kwp, n, i, ek, key, klen);
    if (c != 0) { if (c > 0) *past_end = 1; break; }
    if (found >= max_matches) return -1;
    uint64_t s = seq_lo[i];
    if (seq_hi) s |= ((uint64_t)seq_hi[i]) << 32;
    seqs[found] = s;
    uint8_t vt = vtp[i];
    vtypes[found] = vt;
    uint64_t vlen = vlp ? (uint64_t)vlp[2 * i] | ((uint64_t)vlp[2 * i + 1] << 8)
                        : (vt == 2) ? 0 : bvlen;
    if (vlen > bvlen || vlen > vlen_cap) return -2;
    for (uint64_t b = 0; b < vlen; b++) {
      uint32_t w; memcpy(&w, (const uint8_t*)(vvp + (b / 4) * n + i), 4);
      out_vals[found * vlen_cap + b] = (uint8_t)(w >> (8 * (b % 4)));
    }
    val_lens[found] = vlen;
    found++;
  }
  return (int64_t)found;
}

// ---------------------------------------------------------------------------
// CPU merge-resolve — the framework's native compaction fallback
// ---------------------------------------------------------------------------
//
// Element-exact parity with tpu/backend.py numpy_merge_resolve (the same
// LSM resolution the TPU kernel computes): order by the canonical
// comparator — (key words asc, key_len asc, seq desc) — then resolve
// each key segment newest-wins with uint64-add operand folding above
// the first base and tombstone dropping. Two entry points share one
// comparator packing and ONE segment-resolve implementation:
//
//   cpu_merge_resolve       — unsorted input: packed-record std::sort
//   cpu_merge_resolve_runs  — PRE-SORTED runs: O(n log k) binary-heap
//                             k-way merge (callers verify sortedness)
//
// This is the single-core CPU path a host without an accelerator runs;
// the numpy implementation remains the fallback when the library is
// absent.

namespace {

// Comparator record: the 9 canonical u32 lanes packed pairwise into 5
// u64s (pairwise packing preserves lexicographic order). e's low half
// carries the input row index (tiebreak + payload lookup).
struct MrRec {
  uint64_t a, b, c, d, e;
  bool operator<(const MrRec& o) const {
    if (a != o.a) return a < o.a;
    if (b != o.b) return b < o.b;
    if (c != o.c) return c < o.c;
    if (d != o.d) return d < o.d;
    return e < o.e;
  }
};

struct MrInput {
  const uint32_t* kw;
  const uint32_t* klen;
  const uint64_t* seq;
  const uint8_t* vtype;
  const uint32_t* vw;
  const uint32_t* vlen;
  uint32_t kwn, vwn;
};

static inline void mr_pack(const MrInput& in, uint64_t i, MrRec* r) {
  const uint32_t* k = in.kw + (size_t)i * in.kwn;
  uint64_t w[6] = {0, 0, 0, 0, 0, 0};
  for (uint32_t x = 0; x < in.kwn; x++) w[x] = k[x];
  r->a = (w[0] << 32) | w[1];
  r->b = (w[2] << 32) | w[3];
  r->c = (w[4] << 32) | w[5];
  r->d = ((uint64_t)in.klen[i] << 32)
      | (uint32_t)~(uint32_t)(in.seq[i] >> 32);
  r->e = ((uint64_t)(uint32_t)~(uint32_t)in.seq[i] << 32) | (uint32_t)i;
}

static inline bool mr_same_key(const MrRec& x, const MrRec& y) {
  return x.a == y.a && x.b == y.b && x.c == y.c
      && (x.d >> 32) == (y.d >> 32);
}

static inline uint64_t mr_val64(const MrInput& in, uint64_t row) {
  uint64_t v = in.vw[(size_t)row * in.vwn];
  if (in.vwn > 1) v |= (uint64_t)in.vw[(size_t)row * in.vwn + 1] << 32;
  return v;
}

struct MrOutput {
  uint32_t* kw;
  uint32_t* klen;
  uint64_t* seq;
  uint8_t* vtype;
  uint32_t* vw;
  uint32_t* vlen;
  uint64_t count = 0;
};

// THE segment resolver (both entry points call exactly this): rows are
// one key's input row indices, newest (highest seq) first.
static void mr_resolve_segment(
    const MrInput& in, const uint64_t* rows, size_t nseg,
    int32_t uint64_add, int32_t drop_tombstones, MrOutput* out) {
  const uint8_t PUT = 1, DEL = 2, MERGE = 3;
  int64_t fb = -1;
  bool has_op = false;
  uint64_t sum = 0;
  for (size_t k = 0; k < nseg; k++) {
    uint64_t row = rows[k];
    uint8_t t = in.vtype[row];
    bool is_base = (t == PUT) || (t == DEL);
    if (is_base && fb < 0) fb = (int64_t)k;
    if (t == MERGE && (fb < 0 || (int64_t)k < fb)) {
      has_op = true;
      if (uint64_add && in.vlen[row] == 8) sum += mr_val64(in, row);
    }
  }
  bool base_is_put = false, base_is_del = false;
  if (fb >= 0) {
    uint64_t fb_row = rows[(size_t)fb];
    base_is_put = in.vtype[fb_row] == PUT;
    base_is_del = in.vtype[fb_row] == DEL;
    if (uint64_add && base_is_put && in.vlen[fb_row] == 8)
      sum += mr_val64(in, fb_row);
  }
  uint64_t rep = rows[0];
  uint8_t ovt = in.vtype[rep];
  uint64_t ovw0 = in.vw[(size_t)rep * in.vwn];
  uint64_t ovw1 = in.vwn > 1 ? in.vw[(size_t)rep * in.vwn + 1] : 0;
  uint32_t ovl = in.vlen[rep];
  bool dropped;
  if (uint64_add) {
    bool pure_operands = has_op && !base_is_put && !base_is_del;
    bool resolved_put = base_is_put || (has_op && base_is_del);
    if (resolved_put || pure_operands) {
      ovw0 = (uint32_t)(sum & 0xFFFFFFFFu);
      ovw1 = (uint32_t)(sum >> 32);
      ovl = 8;
    }
    if (resolved_put) ovt = PUT;
    else if (pure_operands) ovt = drop_tombstones ? PUT : MERGE;
    dropped = base_is_del && !has_op;
  } else {
    dropped = ovt == DEL;
  }
  if (drop_tombstones && dropped) return;
  uint64_t c = out->count;
  memcpy(out->kw + c * in.kwn, in.kw + (size_t)rep * in.kwn, in.kwn * 4);
  out->klen[c] = in.klen[rep];
  out->seq[c] = in.seq[rep];
  out->vtype[c] = ovt;
  // untouched value words beyond [0,1] come from the representative
  memcpy(out->vw + c * in.vwn, in.vw + (size_t)rep * in.vwn, in.vwn * 4);
  out->vw[c * in.vwn] = (uint32_t)ovw0;
  if (in.vwn > 1) out->vw[c * in.vwn + 1] = (uint32_t)ovw1;
  out->vlen[c] = ovl;
  out->count = c + 1;
}

}  // namespace

extern "C" int64_t cpu_merge_resolve(
    const uint32_t* kw, const uint32_t* klen, const uint64_t* seq,
    const uint8_t* vtype, const uint32_t* vw, const uint32_t* vlen,
    uint64_t n, uint32_t kwn, uint32_t vwn,
    int32_t uint64_add, int32_t drop_tombstones,
    uint32_t* out_kw, uint32_t* out_klen, uint64_t* out_seq,
    uint8_t* out_vtype, uint32_t* out_vw, uint32_t* out_vlen) {
  if (n == 0) return 0;
  if (kwn > 6) return -1;  // MrRec packs at most 6 key words
  MrInput in{kw, klen, seq, vtype, vw, vlen, kwn, vwn};
  MrOutput out{out_kw, out_klen, out_seq, out_vtype, out_vw, out_vlen};
  std::vector<MrRec> recs(n);
  for (uint64_t i = 0; i < n; i++) mr_pack(in, i, &recs[i]);
  // MSD bucket pass, then std::sort per bucket: n log(n/2048) instead
  // of n log n. The bucket key is the first 11 VARYING bits of the
  // comparator — real keysets share constant prefixes ("key000...", a
  // tenant id), so the varying-bit window is found by xor-folding each
  // packed word and bucketing just below the first difference. Order
  // is preserved because every more-significant bit is constant across
  // the dataset. Degenerate spreads (one bucket holding >n/2) fall
  // back to the plain whole-array sort.
  const uint32_t BUCKET_BITS = 11;
  const uint32_t NBUCKETS = 1u << BUCKET_BITS;
  bool bucketed = false;
  if (n >= 4096) {
    uint64_t xors[4] = {0, 0, 0, 0};
    for (uint64_t i = 0; i < n; i++) {
      xors[0] |= recs[i].a ^ recs[0].a;
      xors[1] |= recs[i].b ^ recs[0].b;
      xors[2] |= recs[i].c ^ recs[0].c;
      xors[3] |= recs[i].d ^ recs[0].d;
    }
    int word = -1;
    for (int w = 0; w < 4; w++)
      if (xors[w]) { word = w; break; }
    if (word >= 0) {
      int top = 63 - __builtin_clzll(xors[word]);
      uint32_t shift = top >= (int)BUCKET_BITS - 1
          ? (uint32_t)(top - (BUCKET_BITS - 1)) : 0u;
      auto key_of = [&](const MrRec& r) -> uint32_t {
        uint64_t w = word == 0 ? r.a : word == 1 ? r.b
            : word == 2 ? r.c : r.d;
        return (uint32_t)((w >> shift) & (NBUCKETS - 1));
      };
      std::vector<uint64_t> counts(NBUCKETS + 1, 0);
      for (uint64_t i = 0; i < n; i++) counts[key_of(recs[i]) + 1]++;
      uint64_t biggest = 0;
      for (uint32_t b = 1; b <= NBUCKETS; b++)
        if (counts[b] > biggest) biggest = counts[b];
      if (biggest <= n / 2) {
        for (uint32_t b = 0; b < NBUCKETS; b++)
          counts[b + 1] += counts[b];
        std::vector<MrRec> dist(n);
        std::vector<uint64_t> cursor(counts.begin(), counts.end() - 1);
        for (uint64_t i = 0; i < n; i++)
          dist[cursor[key_of(recs[i])]++] = recs[i];
        for (uint32_t b = 0; b < NBUCKETS; b++)
          std::sort(dist.begin() + counts[b],
                    dist.begin() + counts[b + 1]);
        recs.swap(dist);
        bucketed = true;
      }
    }
  }
  if (!bucketed) std::sort(recs.begin(), recs.end());
  std::vector<uint64_t> seg;
  seg.reserve(64);
  uint64_t i = 0;
  while (i < n) {
    uint64_t j = i;
    seg.clear();
    while (j < n && mr_same_key(recs[i], recs[j])) {
      seg.push_back((uint32_t)recs[j].e);
      j++;
    }
    mr_resolve_segment(in, seg.data(), seg.size(), uint64_add,
                       drop_tombstones, &out);
    i = j;
  }
  return (int64_t)out.count;
}

// K-way entry point over PRE-SORTED runs: run boundaries arrive as
// offsets into the concatenated input lanes. A run that is NOT sorted
// would silently merge wrong — the Python wrapper verifies sortedness
// per run (vectorized, cheap) before calling.
extern "C" int64_t cpu_merge_resolve_runs(
    const uint32_t* kw, const uint32_t* klen, const uint64_t* seq,
    const uint8_t* vtype, const uint32_t* vw, const uint32_t* vlen,
    const uint64_t* run_offsets,  // (n_runs+1,) into the n entries
    uint64_t n, uint32_t n_runs, uint32_t kwn, uint32_t vwn,
    int32_t uint64_add, int32_t drop_tombstones,
    uint32_t* out_kw, uint32_t* out_klen, uint64_t* out_seq,
    uint8_t* out_vtype, uint32_t* out_vw, uint32_t* out_vlen) {
  if (n == 0) return 0;
  if (kwn > 6 || n_runs == 0) return -1;
  MrInput in{kw, klen, seq, vtype, vw, vlen, kwn, vwn};
  MrOutput out{out_kw, out_klen, out_seq, out_vtype, out_vw, out_vlen};
  // run cursors + current head record per run; a binary heap of run ids
  // keyed by the head record (k is small — a heap is within noise of a
  // loser tree for k <= 64 and much simpler)
  std::vector<uint64_t> cur(n_runs);
  std::vector<MrRec> head(n_runs);
  std::vector<uint32_t> heap;
  heap.reserve(n_runs);
  for (uint32_t r = 0; r < n_runs; r++) {
    cur[r] = run_offsets[r];
    if (cur[r] < run_offsets[r + 1]) {
      mr_pack(in, cur[r], &head[r]);
      heap.push_back(r);
    }
  }
  auto heap_lt = [&](uint32_t x, uint32_t y) { return head[x] < head[y]; };
  auto sift_down = [&](size_t i) {
    size_t sz = heap.size();
    while (true) {
      size_t l = 2 * i + 1, r = 2 * i + 2, m = i;
      if (l < sz && heap_lt(heap[l], heap[m])) m = l;
      if (r < sz && heap_lt(heap[r], heap[m])) m = r;
      if (m == i) return;
      std::swap(heap[i], heap[m]);
      i = m;
    }
  };
  for (size_t i = heap.size(); i-- > 0;) sift_down(i);

  auto pop_min = [&](uint64_t* row_out, MrRec* rec_out) -> bool {
    if (heap.empty()) return false;
    uint32_t r = heap[0];
    *row_out = cur[r];
    *rec_out = head[r];
    cur[r]++;
    if (cur[r] < run_offsets[r + 1]) {
      mr_pack(in, cur[r], &head[r]);
    } else {
      heap[0] = heap.back();
      heap.pop_back();
    }
    if (!heap.empty()) sift_down(0);
    return true;
  };

  std::vector<uint64_t> seg;
  seg.reserve(64);
  MrRec seg_key{};
  bool have = false;
  uint64_t row;
  MrRec rec;
  while (pop_min(&row, &rec)) {
    if (have && !mr_same_key(seg_key, rec)) {
      mr_resolve_segment(in, seg.data(), seg.size(), uint64_add,
                         drop_tombstones, &out);
      seg.clear();
    }
    seg_key = rec;
    have = true;
    seg.push_back(row);
  }
  if (have)
    mr_resolve_segment(in, seg.data(), seg.size(), uint64_add,
                       drop_tombstones, &out);
  return (int64_t)out.count;
}

// ---------------------------------------------------------------------------
// Whole-file codecs: every block of one TSST file in ONE call
// ---------------------------------------------------------------------------
//
// tpu/format.py's sink and source ran a Python loop per 4-32 KB block
// (a dozen small numpy calls, zlib, the checksum). Eight of them at once
// on a pool serialise on the interpreter; ctypes drops the GIL for a
// whole call, so a file is one trip. Same bytes as the Python codecs
// (parity-tested): block layout as storage/planar.py / sst.py, the
// checksum as utils/checksum.py, zlib level 1 through the libz Python
// itself links.

namespace {

const uint32_t CHK_R = 0x01000193u;  // utils/checksum.py CHK_R
const uint64_t MAX_BLOCK_BYTES = 64ull << 20;  // sst.py's RLZ bound

// r^1..r^n (wrapping u32), grown on demand: one table a call
struct ChkPowers {
  std::vector<uint32_t> p;
  const uint32_t* upto(uint64_t n) {
    uint64_t have = p.size();
    if (have < n) {
      p.resize(n);
      uint32_t v = have ? p[have - 1] : 1u;
      for (uint64_t i = have; i < n; i++) { v *= CHK_R; p[i] = v; }
    }
    return p.data();
  }
};

// poly_checksum_words: sum (w_i + 1) * r^(i+1) over the words zero-
// padded to `length` (a block longer than `length` sums over its own)
static uint32_t chk_words(const uint8_t* data, uint64_t nwords,
                          uint64_t length, ChkPowers* pw) {
  uint64_t total = nwords > length ? nwords : length;
  const uint32_t* p = pw->upto(total);
  uint32_t h = 0;
  for (uint64_t i = 0; i < nwords; i++)
    h += (get_u32(data + 4 * i) + 1u) * p[i];
  for (uint64_t i = nwords; i < total; i++) h += p[i];
  return h;
}

// poly_checksum: the byte-domain variant (row-format device blocks)
static uint32_t chk_bytes(const uint8_t* data, uint64_t n, uint64_t length,
                          ChkPowers* pw) {
  uint64_t total = n > length ? n : length;
  const uint32_t* p = pw->upto(total);
  uint32_t h = 0;
  for (uint64_t i = 0; i < n; i++) h += ((uint32_t)data[i] + 1u) * p[i];
  for (uint64_t i = n; i < total; i++) h += p[i];
  return h;
}

static inline uint64_t planar_plane_words(uint64_t n, uint64_t kw,
                                          uint64_t vw, bool seq32,
                                          bool klens = false,
                                          bool vlens = false) {
  return n * (kw + 1 + (seq32 ? 0 : 1) + vw)
       + (klens ? 2 : 1) * ((n + 3) / 4)
       + (vlens ? (n + 1) / 2 : 0);
}

static bool pread_all(int fd, uint8_t* dst, uint64_t size, uint64_t off) {
  while (size > 0) {
    ssize_t got = pread(fd, dst, size, (off_t)off);
    if (got < 0 && errno == EINTR) continue;
    if (got <= 0) return false;  // error, or the file ends inside a block
    dst += got; size -= (uint64_t)got; off += (uint64_t)got;
  }
  return true;
}


// codec nibbles of the block index (storage/sst.py)
#define TSST_CODEC_NONE 0
#define TSST_CODEC_ZLIB 1
#define TSST_BLOCK_PLANAR 2
#define TSST_BLOCK_PLANAR_ZLIB 3
#define TSST_CODEC_RLZ 4
#define TSST_BLOCK_PLANAR_RLZ 5

// pread + inflate of one block after another, buffers and the zlib
// stream kept between blocks. fetch() returns 0, -1 (the read failed or
// came up short) or -2 (the block does not inflate / unknown codec);
// *raw points into this object's buffers until the next fetch().
struct BlockFetcher {
  std::vector<uint8_t> payload, inflated;
  z_stream zs;
  bool zs_live = false;
  BlockFetcher() { memset(&zs, 0, sizeof(zs)); }
  ~BlockFetcher() { if (zs_live) inflateEnd(&zs); }

  int fetch(int fd, uint64_t off, uint64_t size, uint64_t codec,
            const uint8_t** raw, uint64_t* raw_len) {
    if (size > MAX_BLOCK_BYTES) return -2;
    payload.resize(size);
    if (!pread_all(fd, payload.data(), size, off)) return -1;
    *raw = payload.data();
    *raw_len = size;
    if (codec == TSST_CODEC_ZLIB || codec == TSST_BLOCK_PLANAR_ZLIB) {
      if (!zs_live) {
        if (inflateInit(&zs) != Z_OK) return -2;
        zs_live = true;
      } else if (inflateReset(&zs) != Z_OK) {
        return -2;
      }
      if (inflated.size() < 65536) inflated.resize(65536);
      zs.next_in = payload.data(); zs.avail_in = (uInt)size;
      int z;
      while (true) {
        zs.next_out = inflated.data() + zs.total_out;
        zs.avail_out = (uInt)(inflated.size() - zs.total_out);
        z = inflate(&zs, Z_NO_FLUSH);
        if (z != Z_OK || zs.avail_out != 0) break;
        if (inflated.size() >= MAX_BLOCK_BYTES) break;
        inflated.resize(inflated.size() * 2);
      }
      if (z != Z_STREAM_END) return -2;
      *raw = inflated.data(); *raw_len = zs.total_out;
    } else if (codec == TSST_CODEC_RLZ || codec == TSST_BLOCK_PLANAR_RLZ) {
      if (size < 4) return -2;
      uint64_t declared = get_u32(payload.data());
      if (declared > MAX_BLOCK_BYTES) return -2;
      if (inflated.size() < declared + 32) inflated.resize(declared + 32);
      int64_t got = rlz_decompress(payload.data(), size, inflated.data(),
                                   declared + 32);
      if (got < 0) return -2;
      *raw = inflated.data(); *raw_len = (uint64_t)got;
    } else if (codec != TSST_CODEC_NONE && codec != TSST_BLOCK_PLANAR) {
      return -2;
    }
    return 0;
  }
};

}  // namespace

// PLANAR sink: lanes [0, count) -> every block's payload, back to back
// in `out` (capacity: the blocks' uncompressed bytes; a block is kept
// compressed only when that is smaller). Per block: offset in `out`,
// size, codec nibble, and the poly1w checksum over the uncompressed
// plane words padded to a full block's. `key_len` (null: every key is
// `klen` bytes): each row's own key length, `klen` their widest; a block
// whose rows differ in length gets the key-length plane and flag 2, its
// header's klen and its key planes at the block's own widest key; a
// block whose rows share one length is the block it always was, at that
// length. `val_len` (null: every live value is `vlen` bytes) does the
// same for values, `vlen` their widest: a block whose live rows differ
// in width gets the value-length plane and flag 4, its header's vlen and
// its value planes at the block's own widest value; one of one width is
// the old block at that width (an all-DELETE block: at `vlen`). Keys are
// up to 48 bytes (`kw_cols` words of lanes a row). `val_off` (null: the
// values are rows of `val_cols` words) says the values lie in ONE word
// stream: row r's value is ceil(len / 4) words of `val_words` from
// val_off[r] on, its length `len` its value-length entry (`vlen` where
// there is none) and a DELETE none, each from a 4-byte boundary; the
// offsets need not follow one another (a compaction's output rows read
// their values where its input rows left them). Its planes read zeros
// past them, as a zero-tailed row does: the same blocks. Returns the
// bytes written, -1 when `out_cap` is short, -2 on arguments the layout
// can't take (a key length of 0 or over `klen`, a value over `vlen`,
// among them), -3 when zlib fails.
extern "C" int64_t tsst_planar_encode_file(
    const uint32_t* kw_be, uint32_t kw_cols,
    const uint32_t* seq_lo, const uint32_t* seq_hi, const uint8_t* vtype,
    const uint32_t* val_words, uint32_t val_cols,
    uint64_t count, uint32_t klen, uint32_t vlen, int32_t seq32,
    uint32_t block_entries, int32_t compression,
    uint8_t* out, uint64_t out_cap,
    uint64_t* blk_off, uint32_t* blk_size, uint8_t* blk_codec,
    uint32_t* blk_chk, const uint32_t* key_len, const uint32_t* val_len,
    const uint64_t* val_off) {
  uint64_t fvw = ((uint64_t)vlen + 3) / 4;
  if (klen == 0 || klen > 48 || vlen > 0xFFFFu || block_entries == 0
      || (klen + 3) / 4 > kw_cols || (val_off == nullptr && fvw > val_cols)
      || (!seq32 && seq_hi == nullptr))
    return -2;
  uint64_t full_words = planar_plane_words(
      block_entries, (klen + 3) / 4, fvw, seq32, key_len != nullptr,
      val_len != nullptr);
  ChkPowers pw;
  z_stream zs;
  memset(&zs, 0, sizeof(zs));
  bool zlib_on = compression == TSST_CODEC_ZLIB;
  bool rlz_on = compression == TSST_CODEC_RLZ;
  std::vector<uint8_t> scratch;
  if (zlib_on && deflateInit(&zs, 1) != Z_OK) return -3;
  uint64_t pos = 0;
  int64_t rc = 0;
  for (uint64_t start = 0, bi = 0; start < count;
       start += block_entries, bi++) {
    uint64_t n = std::min<uint64_t>(block_entries, count - start);
    uint32_t bklen = klen;   // this block's header klen: its widest key
    bool klens = false;      // and whether its rows differ in length
    if (key_len != nullptr) {
      uint32_t lo = key_len[start], hi = lo;
      for (uint64_t i = 1; i < n; i++) {
        uint32_t l = key_len[start + i];
        lo = std::min(lo, l); hi = std::max(hi, l);
      }
      if (lo == 0 || hi > klen) { rc = -2; break; }
      bklen = hi; klens = lo != hi;
    }
    uint32_t bvlen = vlen;   // this block's header vlen: its widest value
    bool vlens = false;      // and whether its live rows differ in width
    if (val_len != nullptr) {
      bool any = false;
      uint32_t lo = 0, hi = 0;
      for (uint64_t i = 0; i < n; i++) {
        if (vtype[start + i] == 2) continue;
        uint32_t l = val_len[start + i];
        if (!any) { lo = hi = l; any = true; }
        lo = std::min(lo, l); hi = std::max(hi, l);
      }
      if (any) {
        if (hi > vlen) { rc = -2; break; }
        bvlen = hi; vlens = lo != hi;
      }
    }
    uint64_t kw = (bklen + 3) / 4, vw = ((uint64_t)bvlen + 3) / 4;
    uint64_t words = planar_plane_words(n, kw, vw, seq32, klens, vlens);
    uint64_t raw_len = 16 + 4 * words;
    if (pos + raw_len > out_cap) { rc = -1; break; }
    uint8_t* raw = out + pos;
    put_u32(raw, (uint32_t)n);
    raw[4] = (uint8_t)bklen; raw[5] = (uint8_t)(bvlen & 0xFF);
    raw[6] = (seq32 ? 1 : 0) | (klens ? 2 : 0) | (vlens ? 4 : 0);
    raw[7] = (uint8_t)(bvlen >> 8);
    put_u64(raw + 8, 0);
    uint8_t* w = raw + 16;
    for (uint64_t k = 0; k < kw; k++)
      for (uint64_t i = 0; i < n; i++, w += 4)
        put_u32(w, kw_be[(start + i) * kw_cols + k]);
    if (klens) {
      uint64_t kl_bytes = 4 * ((n + 3) / 4);
      for (uint64_t i = 0; i < n; i++) w[i] = (uint8_t)key_len[start + i];
      memset(w + n, 0, kl_bytes - n);
      w += kl_bytes;
    }
    memcpy(w, seq_lo + start, 4 * n); w += 4 * n;
    if (!seq32) { memcpy(w, seq_hi + start, 4 * n); w += 4 * n; }
    uint64_t vt_bytes = 4 * ((n + 3) / 4);
    memcpy(w, vtype + start, n);
    memset(w + n, 0, vt_bytes - n);
    w += vt_bytes;
    if (vlens) {
      uint64_t vl_bytes = 4 * ((n + 1) / 2);
      for (uint64_t i = 0; i < n; i++) {
        uint32_t l = vtype[start + i] == 2 ? 0u : val_len[start + i];
        w[2 * i] = (uint8_t)(l & 0xFF); w[2 * i + 1] = (uint8_t)(l >> 8);
      }
      memset(w + 2 * n, 0, vl_bytes - 2 * n);
      w += vl_bytes;
    }
    if (val_off != nullptr) {
      // zeroed planes, then each row's own words: past them its planes
      // read zeros, as a zero-tailed row's do (never past the block's
      // width: a live row is no wider, a DELETE has no word)
      memset(w, 0, 4 * vw * n);
      const uint64_t* vo = val_off + start;
      for (uint64_t i = 0; i < n; i++) {
        if (vtype[start + i] == 2) continue;
        uint64_t l = val_len != nullptr ? val_len[start + i] : vlen;
        uint64_t nw = std::min<uint64_t>((l + 3) / 4, vw);
        for (uint64_t k = 0; k < nw; k++)
          put_u32(w + 4 * (k * n + i), val_words[vo[i] + k]);
      }
      w += 4 * vw * n;
    } else {
      for (uint64_t k = 0; k < vw; k++)
        for (uint64_t i = 0; i < n; i++, w += 4)
          put_u32(w, val_words[(start + i) * val_cols + k]);
    }
    blk_chk[bi] = chk_words(raw + 16, words, full_words, &pw);
    uint64_t size = raw_len;
    uint8_t codec = TSST_BLOCK_PLANAR;
    if (zlib_on) {
      if (deflateReset(&zs) != Z_OK) { rc = -3; break; }
      scratch.resize(deflateBound(&zs, raw_len));
      zs.next_in = raw; zs.avail_in = (uInt)raw_len;
      zs.next_out = scratch.data(); zs.avail_out = (uInt)scratch.size();
      if (deflate(&zs, Z_FINISH) != Z_STREAM_END) { rc = -3; break; }
      if (zs.total_out < raw_len) {
        size = zs.total_out; codec = TSST_BLOCK_PLANAR_ZLIB;
      }
    } else if (rlz_on) {
      scratch.resize(4 + raw_len + (raw_len + 126) / 127 + 3);
      int64_t z = rlz_compress(raw, raw_len, scratch.data(),
                               scratch.size());
      if (z >= 0 && (uint64_t)z < raw_len) {
        size = (uint64_t)z; codec = TSST_BLOCK_PLANAR_RLZ;
      }
    }
    if (codec != TSST_BLOCK_PLANAR) memcpy(raw, scratch.data(), size);
    blk_off[bi] = pos; blk_size[bi] = (uint32_t)size; blk_codec[bi] = codec;
    pos += size;
  }
  if (zlib_on) deflateEnd(&zs);
  return rc < 0 ? rc : (int64_t)pos;
}

// Lane source: pread + inflate every block of one file and fill the
// eight kernel lanes (the arrays tpu/format.py's Python decoders
// return). `index` is (nblocks, 3) u64: offset, size, codec nibble.
// The key lanes are `kw_cols` words a row (6, or 12 for keys of 25 to
// 48 bytes), the value lanes `val_cols`.
// planar != 0: PLANAR blocks, widths as the file's props give them (the
// key width is the file's WIDEST key: a block's own may be narrower, and
// with flag 2 each row has its own; key_len says it row by row).
// planar == 0: entry-stream blocks, walked entry by entry. With *klen_io
// given (the sink's "uniform" prop) every key has that length; *klen_io
// == 0 (no such prop: a flush-written, bulk-built or foreign file) takes
// keys of ANY length from 1 to 4 * kw_cols bytes, each row its own
// (key_len says it), and, unless `vmixed`, the value width from block
// 0's first entry.
// vmixed != 0: values of differing widths, up to *vlen_io (the file's
// widest, as its props give it), each row its own (val_len says it; a
// PLANAR block may carry the value-length plane, flag 4, or be of one
// width up to *vlen_io); bytes past a value's length are zero.
// vmixed == 0: every live value is *vlen_io bytes.
// stream != null: the values go to ONE word stream and not to the value
// lanes (`val_words` is not written, `val_cols` not asked): row r's value
// is ceil(val_len[r] / 4) words from stream[*stream_pos] on, from a
// 4-byte boundary, the words a zero-tailed row would hold (a DELETE: no
// word); *stream_pos is moved past them on success. `stream_cap` words
// in all.
// chk_mode 1 / 2: each block's poly1 (bytes)
// / poly1w (plane words) value over `chk_len`, into blk_chk: the caller
// holds them against the file's block_chk prop.
//
// Returns the rows decoded, or
//   -1  a read failed or came up short          (*err_block: which)
//   -2  a block is corrupt: inflate, layout, codec
//   -3  widths drift (a value width other than the file's, a key that
//       the lanes cannot hold or the prop did not promise, an entry
//       that runs past its block): not lanes, the tuple path's
//   -4  more rows than row_cap
//   -5  the inferred value width needs another val_cols: *vlen_io is
//       filled, call again with it
//   -6  the values overrun the stream's `stream_cap` words
extern "C" int64_t tsst_decode_file_lanes(
    int32_t fd, const uint64_t* index, uint64_t nblocks, int32_t planar,
    uint32_t* klen_io, uint32_t* vlen_io, uint64_t row_cap,
    uint32_t val_cols, uint32_t kw_cols, int32_t vmixed,
    uint32_t* kw_be, uint32_t* kw_le, uint32_t* key_len,
    uint32_t* seq_hi, uint32_t* seq_lo, uint32_t* vtype,
    uint32_t* val_words, uint32_t* val_len,
    int32_t chk_mode, uint64_t chk_len, uint32_t* blk_chk,
    int64_t* err_block, uint32_t* stream, uint64_t stream_cap,
    uint64_t* stream_pos) {
  uint32_t klen = *klen_io, vlen = *vlen_io;
  uint64_t spos = stream ? *stream_pos : 0;
  if (kw_cols != 6 && kw_cols != 12) return -2;
  const uint32_t kmax = 4 * kw_cols;  // the longest key the lanes hold
  bool infer = !planar && klen == 0;
  bool one_klen = !infer;  // the prop's promise: every key klen bytes
  if (!infer && (klen == 0 || klen > kmax)) return -2;
  if (vmixed) infer = false;  // the caller gave the widest value
  ChkPowers pw;
  BlockFetcher fetcher;
  uint64_t row = 0;
  int64_t rc = 0;
  for (uint64_t bi = 0; bi < nblocks && rc == 0; bi++) {
    *err_block = (int64_t)bi;
    const uint8_t* raw;
    uint64_t raw_len;
    rc = fetcher.fetch(fd, index[3 * bi], index[3 * bi + 1],
                       index[3 * bi + 2], &raw, &raw_len);
    if (rc < 0) break;

    if (planar) {
      if (raw_len < 16) { rc = -2; break; }
      uint64_t n = get_u32(raw);
      uint32_t bklen = raw[4];
      uint32_t bvlen = (uint32_t)raw[5] | ((uint32_t)raw[7] << 8);
      bool seq32 = raw[6] & 1, klens = raw[6] & 2, vlens = raw[6] & 4;
      if (bklen == 0 || bklen > 48) { rc = -2; break; }
      uint64_t kw = (bklen + 3) / 4, vw = ((uint64_t)bvlen + 3) / 4;
      uint64_t words = planar_plane_words(n, kw, vw, seq32, klens, vlens);
      if (raw_len != 16 + 4 * words) { rc = -2; break; }
      if (chk_mode == 2)
        blk_chk[bi] = chk_words(raw + 16, words, chk_len, &pw);
      if (bklen > klen || (!stream && vw > val_cols)
          || (vmixed ? bvlen > vlen : (bvlen != vlen || vlens))) {
        rc = -3; break;
      }
      if (row + n > row_cap) { rc = -4; break; }
      const uint8_t* kwp = raw + 16;
      const uint8_t* klp = klens ? kwp + 4 * kw * n : nullptr;
      const uint8_t* slo = kwp + 4 * kw * n + (klens ? 4 * ((n + 3) / 4) : 0);
      const uint8_t* shi = seq32 ? nullptr : slo + 4 * n;
      const uint8_t* vtp = slo + 4 * n * (seq32 ? 1 : 2);
      const uint8_t* vlp = vlens ? vtp + 4 * ((n + 3) / 4) : nullptr;
      const uint8_t* vvp = vtp + 4 * ((n + 3) / 4)
                         + (vlens ? 4 * ((n + 1) / 2) : 0);
      for (uint64_t i = 0; i < n; i++) {
        uint64_t r = row + i;
        uint32_t ek = klp ? klp[i] : bklen;  // the row's own key length
        if (ek == 0 || ek > bklen) { rc = -2; break; }
        // bytes of a key word past the key's length are not key: zeroed,
        // as decode_planar_block leaves them
        uint64_t ekw = (ek + 3) / 4;
        uint32_t tail_mask = (ek % 4)
            ? 0xFFFFFFFFu << (8 * (4 - ek % 4)) : 0xFFFFFFFFu;
        for (uint64_t k = 0; k < kw_cols; k++) {
          uint32_t be = 0;
          if (k < ekw) {
            be = get_u32(kwp + 4 * (k * n + i));
            if (k == ekw - 1) be &= tail_mask;
          }
          kw_be[r * kw_cols + k] = be;
          kw_le[r * kw_cols + k] = __builtin_bswap32(be);
        }
        key_len[r] = ek;
        seq_lo[r] = get_u32(slo + 4 * i);
        seq_hi[r] = shi ? get_u32(shi + 4 * i) : 0u;
        uint32_t vt = vtp[i];
        vtype[r] = vt;
        if (stream) {
          uint32_t ev = vt == 2 ? 0u : bvlen;
          if (vlp) {
            ev = (uint32_t)vlp[2 * i] | ((uint32_t)vlp[2 * i + 1] << 8);
            if (ev > bvlen) { rc = -2; break; }
          }
          val_len[r] = ev;
          uint64_t evw = (ev + 3) / 4;
          if (spos + evw > stream_cap) { rc = -6; break; }
          for (uint64_t k = 0; k < evw; k++) {
            uint32_t le = get_u32(vvp + 4 * (k * n + i));
            if (vlp && k + 1 == evw && ev % 4)
              le &= 0xFFFFFFFFu >> (8 * (4 - ev % 4));
            stream[spos + k] = le;
          }
          spos += evw;
          continue;
        }
        if (vlp) {
          // the row's own value length; bytes past it are not value
          uint32_t ev = (uint32_t)vlp[2 * i] | ((uint32_t)vlp[2 * i + 1] << 8);
          if (ev > bvlen) { rc = -2; break; }
          val_len[r] = ev;
          uint64_t evw = (ev + 3) / 4;
          for (uint64_t k = 0; k < val_cols; k++) {
            uint32_t le = k < evw ? get_u32(vvp + 4 * (k * n + i)) : 0u;
            if (k + 1 == evw && ev % 4) le &= 0xFFFFFFFFu >> (8 * (4 - ev % 4));
            val_words[r * val_cols + k] = le;
          }
          continue;
        }
        val_len[r] = vt == 2 ? 0u : bvlen;
        for (uint64_t k = 0; k < val_cols; k++)
          val_words[r * val_cols + k] =
              k < vw ? get_u32(vvp + 4 * (k * n + i)) : 0u;
      }
      if (rc < 0) break;
      row += n;
      continue;
    }

    if (chk_mode == 1) blk_chk[bi] = chk_bytes(raw, raw_len, chk_len, &pw);
    if (infer) {
      // the value width of block 0's first entry is the file's
      if (raw_len < 17) { rc = -3; break; }
      uint32_t k0 = get_u32(raw);
      if (k0 == 0 || k0 > kmax || raw_len < 17 + (uint64_t)k0) {
        rc = -3; break;
      }
      vlen = get_u32(raw + k0 + 13);
      infer = false;
      *vlen_io = vlen;
    }
    uint64_t need_cols = std::max<uint64_t>(2, ((uint64_t)vlen + 3) / 4);
    if (!stream && need_cols != val_cols) { rc = -5; break; }
    // entry: u32 klen | key | u64 seq | u8 vtype | u32 vlen | value
    for (uint64_t pos = 0; pos < raw_len; row++) {
      const uint8_t* e = raw + pos;
      if (raw_len - pos < 17) { rc = -3; break; }
      uint32_t ek = get_u32(e);
      if (ek == 0 || ek > kmax || (one_klen && ek != klen)
          || raw_len - pos < 17 + (uint64_t)ek) {
        rc = -3; break;
      }
      uint32_t ev = get_u32(e + ek + 13);
      if ((vmixed ? ev > vlen : ev != vlen)
          || raw_len - pos < 17 + (uint64_t)ek + ev) {
        rc = -3; break;
      }
      if (row >= row_cap) { rc = -4; break; }
      uint8_t key[48];
      memset(key, 0, kmax);
      memcpy(key, e + 4, ek);
      for (uint32_t k = 0; k < kw_cols; k++) {
        uint32_t le = get_u32(key + 4 * k);
        kw_le[row * kw_cols + k] = le;
        kw_be[row * kw_cols + k] = __builtin_bswap32(le);
      }
      key_len[row] = ek;
      uint64_t seq = get_u64(e + 4 + ek);
      seq_hi[row] = (uint32_t)(seq >> 32);
      seq_lo[row] = (uint32_t)seq;
      vtype[row] = e[ek + 12];
      val_len[row] = ev;
      if (stream) {
        uint64_t evw = ((uint64_t)ev + 3) / 4;
        if (spos + evw > stream_cap) { rc = -6; break; }
        if (evw) stream[spos + evw - 1] = 0;
        memcpy(stream + spos, e + ek + 17, ev);
        spos += evw;
      } else {
        uint32_t* vdst = val_words + row * val_cols;
        memset(vdst, 0, 4 * (size_t)val_cols);
        memcpy(vdst, e + ek + 17, ev);
      }
      pos += 17 + (uint64_t)ek + ev;
    }
  }
  if (rc >= 0 && stream) *stream_pos = spos;
  return rc < 0 ? rc : (int64_t)row;
}

// One block for a point read: pread + inflate in ONE call (the Python
// reader made two that each drop the GIL, os.pread and zlib.decompress,
// and a numpy pass for the checksum). *out is malloc'ed here and freed
// by tsst_free. chk_mode as above; a PLANAR block whose plane bytes are
// not whole words is -2. Returns the block's uncompressed length, -1 /
// -2 as BlockFetcher::fetch.
extern "C" int64_t tsst_read_block(
    int32_t fd, uint64_t off, uint64_t size, uint32_t codec,
    uint8_t** out, int32_t chk_mode, uint64_t chk_len, uint32_t* chk) {
  BlockFetcher fetcher;
  const uint8_t* raw;
  uint64_t raw_len;
  int rc = fetcher.fetch(fd, off, size, codec, &raw, &raw_len);
  if (rc < 0) return rc;
  ChkPowers pw;
  if (chk_mode == 2) {
    if (raw_len < 16 || (raw_len - 16) % 4) return -2;
    *chk = chk_words(raw + 16, (raw_len - 16) / 4, chk_len, &pw);
  } else if (chk_mode == 1) {
    *chk = chk_bytes(raw, raw_len, chk_len, &pw);
  }
  *out = (uint8_t*)malloc(raw_len ? raw_len : 1);
  if (*out == nullptr) return -1;
  memcpy(*out, raw, raw_len);
  return (int64_t)raw_len;
}

extern "C" void tsst_free(uint8_t* p) { free(p); }
