// Native host-side helpers for parquet_tpu.
//
// The TPU absorbs the bulk value decode (kernels/), but three host-side scalar
// walks remain on the critical path and are too branchy for NumPy:
//   1. snappy block (de)compression   (the reference links a Go snappy lib;
//      this implements the public snappy block format from its spec)
//   2. PLAIN byte_array offset scan   (data-dependent 4-byte length chain,
//      reference: type_bytearray.go:24-45)
//   3. hybrid RLE/bit-pack run-header prescan
//      (reference: hybrid_decoder.go:142-165; feeds the device run table)
//
// Exposed as a plain C ABI consumed via ctypes (utils/native.py). All
// functions validate sizes before writing and return -1 on corrupt input.

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <cstddef>
#include <ctime>        // per-stage prepare clocks (chunk_prepare stage_ns)
#include <sys/types.h>  // ssize_t
#include <zlib.h>       // gzip pages in the whole-chunk prepare walk

#include "parquet_tpu_native.h"  // shared ptq_chunk_prepare prototype (pyext)

extern "C" {

// ---------------------------------------------------------------------------
// snappy block format
// ---------------------------------------------------------------------------

size_t ptq_snappy_max_compressed_length(size_t n) {
  // Worst case: all literals (header <= 5 bytes per element, one element) plus
  // copies that are only emitted when profitable (see emit rules), + varint.
  return 32 + n + n / 6;
}

// Tag-dispatch table for the fast decode loop: one lookup replaces the
// per-kind branch ladder. entry = (extra_trailer_bytes << 11) |
// (offset_high_bits << 8) | base_copy_length. Literal tags (kind 0) are
// dispatched before the table is consulted.
static uint16_t g_snappy_tag[256];
static const bool g_snappy_tag_init = [] {
  for (int c = 0; c < 256; c++) {
    uint16_t e = 0;
    switch (c & 3) {
      case 1:  // copy, 1-byte offset trailer, 3 offset bits in the tag
        e = static_cast<uint16_t>((1u << 11) | ((static_cast<uint32_t>(c) >> 5) << 8) |
                                  (((static_cast<uint32_t>(c) >> 2) & 7) + 4));
        break;
      case 2:  // copy, 2-byte little-endian offset
        e = static_cast<uint16_t>((2u << 11) | ((static_cast<uint32_t>(c) >> 2) + 1));
        break;
      case 3:  // copy, 4-byte little-endian offset
        e = static_cast<uint16_t>((4u << 11) | ((static_cast<uint32_t>(c) >> 2) + 1));
        break;
    }
    g_snappy_tag[c] = e;
  }
  return true;
}();
static const uint32_t g_snappy_wordmask[5] = {0, 0xffu, 0xffffu, 0xffffffu,
                                              0xffffffffu};

// Overshooting match copy: writes in 8/16-byte blocks, spilling at most 15
// bytes past out+length into the caller-guaranteed slack. Correct for every
// offset >= 1 (short periods are strided by the first period multiple >= 8).
static inline void snappy_copy_fast(char* op, const char* from, uint32_t length,
                                    uint32_t offset) {
  if (offset >= 8 && length <= 8) {
    std::memcpy(op, from, 8);
  } else if (offset >= 8 && length <= 16) {
    // the dominant op on structured numeric data (e.g. a 7-byte match at
    // offset 8 per int64): two fixed 8-byte moves, no loop, no call.
    // Reading from+8 may touch bytes the first move just wrote — for
    // offset 8..15 those bytes repeat the pattern, which is exactly what
    // the match semantics require.
    std::memcpy(op, from, 8);
    std::memcpy(op + 8, from + 8, 8);
  } else if (offset >= 16) {
    for (uint32_t i = 0; i < length; i += 16) std::memcpy(op + i, from + i, 16);
  } else if (offset >= 8) {
    for (uint32_t i = 0; i < length; i += 8) std::memcpy(op + i, from + i, 8);
  } else {
    // short period: byte-copy one full period multiple >= 8 (<= 14 bytes),
    // then stride by that multiple — still the same pattern, but each
    // 8-byte block is non-overlapping
    uint32_t off2 = offset;
    while (off2 < 8) off2 += offset;
    uint32_t head = off2 < length ? off2 : length;
    for (uint32_t i = 0; i < head; i++) op[i] = from[i];
    for (uint32_t i = head; i < length; i += 8) std::memcpy(op + i, op + i - off2, 8);
  }
}

ssize_t ptq_snappy_decompress(const char* src_c, size_t src_len,
                              char* dst, size_t dst_cap) {
  const uint8_t* src = reinterpret_cast<const uint8_t*>(src_c);
  size_t pos = 0;
  uint64_t expect = 0;
  int shift = 0;
  // preamble: uncompressed length varint
  for (;;) {
    if (pos >= src_len || shift > 63) return -1;
    uint8_t b = src[pos++];
    expect |= static_cast<uint64_t>(b & 0x7f) << shift;
    if (!(b & 0x80)) break;
    shift += 7;
  }
  if (expect > dst_cap) return -1;
  // Fast mode: a destination with >= 64 bytes of physical slack past `expect`
  // (chunk_prepare's scratch/values buffers are allocated that way) lets
  // copies run in overshooting 8/16-byte blocks and lets the tag trailer be
  // read as one unconditional 4-byte load — the decode stays LOGICALLY
  // bounded by `expect`, only the access granularity spills into the slack.
  // Exactly-sized destinations (the public codec entry point) take the
  // byte-exact careful loop below.
  const bool fast = dst_cap >= expect + 64;
  size_t out = 0;
  while (pos < src_len) {
    uint8_t tag = src[pos++];
    uint32_t kind = tag & 3;
    if (kind == 0) {  // literal
      uint32_t len = tag >> 2;
      if (len >= 60) {
        uint32_t extra = len - 59;  // 1..4 length bytes
        if (pos + extra > src_len) return -1;
        len = 0;
        for (uint32_t i = 0; i < extra; i++) len |= static_cast<uint32_t>(src[pos + i]) << (8 * i);
        pos += extra;
      }
      uint64_t n = static_cast<uint64_t>(len) + 1;
      if (pos + n > src_len || out + n > expect) return -1;
      if (fast && n <= 8 && pos + 8 <= src_len) {
        std::memcpy(dst + out, src + pos, 8);
      } else {
        std::memcpy(dst + out, src + pos, n);
      }
      out += n;
      pos += n;
    } else {
      uint32_t length, offset;
      if (fast && pos + 4 <= src_len) {
        // tag-dispatch: one table lookup + one unconditional 4-byte load
        // replaces the per-kind branch ladder (trailer bytes beyond the
        // tag's count are masked off, never consumed)
        const uint16_t e = g_snappy_tag[tag];
        const uint32_t extra = e >> 11;
        uint32_t data;
        std::memcpy(&data, src + pos, 4);
        offset = (e & 0x700u) + (data & g_snappy_wordmask[extra]);
        length = e & 0xffu;
        pos += extra;
      } else if (kind == 1) {
        if (pos + 1 > src_len) return -1;
        length = ((tag >> 2) & 7) + 4;
        offset = (static_cast<uint32_t>(tag >> 5) << 8) | src[pos];
        pos += 1;
      } else if (kind == 2) {
        if (pos + 2 > src_len) return -1;
        length = (tag >> 2) + 1;
        offset = static_cast<uint32_t>(src[pos]) | (static_cast<uint32_t>(src[pos + 1]) << 8);
        pos += 2;
      } else {
        if (pos + 4 > src_len) return -1;
        length = (tag >> 2) + 1;
        offset = static_cast<uint32_t>(src[pos]) | (static_cast<uint32_t>(src[pos + 1]) << 8) |
                 (static_cast<uint32_t>(src[pos + 2]) << 16) | (static_cast<uint32_t>(src[pos + 3]) << 24);
        pos += 4;
      }
      if (offset == 0 || offset > out || out + length > expect) return -1;
      const char* from = dst + out - offset;
      char* op = dst + out;
      if (fast) {
        snappy_copy_fast(op, from, length, offset);
      } else if (offset >= 8) {
        // Non-overlapping at 8-byte granularity for the body (~2x on
        // match-heavy pages vs the byte loop); the sub-8 tail is copied
        // byte-wise so no write ever lands past `expect` — an exactly-sized
        // destination buffer is safe, no out-of-band spare-capacity contract.
        uint32_t wide = length & ~7u;
        for (uint32_t i = 0; i < wide; i += 8) std::memcpy(op + i, from + i, 8);
        for (uint32_t i = wide; i < length; i++) op[i] = from[i];
      } else {
        // overlapping copy must run forward byte-by-byte (RLE-style matches)
        for (uint32_t i = 0; i < length; i++) op[i] = from[i];
      }
      out += length;
    }
  }
  return out == expect ? static_cast<ssize_t>(out) : -1;
}

static inline uint32_t snappy_hash(uint32_t v) {
  return (v * 0x1e35a7bdu) >> 18;  // 14-bit table
}

// Emits one literal element (callers never pass len >= 2^32). Returns false on
// insufficient space in dst.
static bool emit_literal(const uint8_t* src, size_t from, size_t len,
                         char* dst, size_t dst_cap, size_t* out) {
  if (len == 0) return true;
  if (*out + 5 + len > dst_cap) return false;
  size_t n = len - 1;
  if (n < 60) {
    dst[(*out)++] = static_cast<char>(n << 2);
  } else if (n < (1u << 8)) {
    dst[(*out)++] = static_cast<char>(60 << 2);
    dst[(*out)++] = static_cast<char>(n);
  } else if (n < (1u << 16)) {
    dst[(*out)++] = static_cast<char>(61 << 2);
    dst[(*out)++] = static_cast<char>(n);
    dst[(*out)++] = static_cast<char>(n >> 8);
  } else if (n < (1u << 24)) {
    dst[(*out)++] = static_cast<char>(62 << 2);
    dst[(*out)++] = static_cast<char>(n);
    dst[(*out)++] = static_cast<char>(n >> 8);
    dst[(*out)++] = static_cast<char>(n >> 16);
  } else {
    dst[(*out)++] = static_cast<char>(63 << 2);
    dst[(*out)++] = static_cast<char>(n);
    dst[(*out)++] = static_cast<char>(n >> 8);
    dst[(*out)++] = static_cast<char>(n >> 16);
    dst[(*out)++] = static_cast<char>(n >> 24);
  }
  std::memcpy(dst + *out, src + from, len);
  *out += len;
  return true;
}

static bool emit_copy(size_t offset, size_t len, char* dst, size_t dst_cap,
                      size_t* out) {
  while (len > 0) {
    size_t chunk = len > 64 ? 64 : len;
    // keep the final chunk >= 4 (canonical decoders may reject shorter copies)
    if (chunk == 64 && len - chunk > 0 && len - chunk < 4) chunk = 60;
    if (*out + 5 > dst_cap) return false;
    if (chunk >= 4 && chunk <= 11 && offset < 2048) {
      dst[(*out)++] = static_cast<char>(((offset >> 8) << 5) | ((chunk - 4) << 2) | 1);
      dst[(*out)++] = static_cast<char>(offset & 0xff);
    } else if (offset < (1u << 16)) {
      dst[(*out)++] = static_cast<char>(((chunk - 1) << 2) | 2);
      dst[(*out)++] = static_cast<char>(offset & 0xff);
      dst[(*out)++] = static_cast<char>(offset >> 8);
    } else {
      dst[(*out)++] = static_cast<char>(((chunk - 1) << 2) | 3);
      dst[(*out)++] = static_cast<char>(offset & 0xff);
      dst[(*out)++] = static_cast<char>((offset >> 8) & 0xff);
      dst[(*out)++] = static_cast<char>((offset >> 16) & 0xff);
      dst[(*out)++] = static_cast<char>((offset >> 24) & 0xff);
    }
    len -= chunk;
  }
  return true;
}

ssize_t ptq_snappy_compress(const char* src_c, size_t src_len,
                            char* dst, size_t dst_cap) {
  if (dst_cap < ptq_snappy_max_compressed_length(src_len)) return -1;
  const uint8_t* src = reinterpret_cast<const uint8_t*>(src_c);
  size_t out = 0;
  // preamble
  {
    uint64_t v = src_len;
    while (v >= 0x80) { dst[out++] = static_cast<char>(v | 0x80); v >>= 7; }
    dst[out++] = static_cast<char>(v);
  }
  if (src_len == 0) return static_cast<ssize_t>(out);
  constexpr size_t kTableSize = 1 << 14;
  static thread_local uint32_t table[kTableSize];
  std::memset(table, 0, sizeof(table));
  size_t lit_start = 0;
  size_t pos = 0;
  if (src_len >= 8) {
    const size_t limit = src_len - 4;
    // google-snappy's miss-acceleration: after 32 consecutive misses the
    // scan starts stepping 2, then 3, ... bytes at a time — incompressible
    // input (bit-packed dictionary indices, already-compressed blobs) costs
    // ~O(n/step) hash probes instead of one per byte. A found match resets
    // the window. (Output stays valid snappy; the ratio on borderline data
    // trades a hair for a large incompressible-page speedup.)
    uint32_t skip = 32;
    while (pos < limit) {
      uint32_t cur;
      std::memcpy(&cur, src + pos, 4);
      uint32_t h = snappy_hash(cur);
      size_t cand = table[h];
      table[h] = static_cast<uint32_t>(pos);
      uint32_t cv;
      if (cand < pos && pos - cand < (1ull << 32) &&
          (std::memcpy(&cv, src + cand, 4), cv == cur)) {
        // extend match
        size_t len = 4;
        while (pos + len < src_len && src[cand + len] == src[pos + len]) len++;
        size_t offset = pos - cand;
        // Profitability: a far copy costs 5 bytes; only take it when it beats
        // the literal it replaces, which also keeps the advertised
        // max_compressed_length bound valid (no expanding elements).
        if (offset >= (1u << 16) && len < 8) {
          pos++;
          continue;
        }
        if (pos > lit_start &&
            !emit_literal(src, lit_start, pos - lit_start, dst, dst_cap, &out))
          return -1;
        if (!emit_copy(offset, len, dst, dst_cap, &out)) return -1;
        pos += len;
        lit_start = pos;
        skip = 32;
      } else {
        pos += skip++ >> 5;
      }
    }
  }
  if (lit_start < src_len &&
      !emit_literal(src, lit_start, src_len - lit_start, dst, dst_cap, &out))
    return -1;
  return static_cast<ssize_t>(out);
}

// ---------------------------------------------------------------------------
// LZ4 block format (+ the Hadoop framing parquet's legacy LZ4 codec uses)
//
// Implemented from the public LZ4 block format description: sequences of
// [token: literal-length nibble | match-length nibble][literals]
// [2-byte LE match offset][length extension bytes], final sequence literals
// only. Strict bounds validation before every write; -1 on corrupt input.
// ---------------------------------------------------------------------------

size_t ptq_lz4_max_compressed_length(size_t n) {
  // worst case: one literal run (1 token + ceil(n/255) extensions + n bytes)
  return 16 + n + n / 255;
}

ssize_t ptq_lz4_decompress(const char* src_c, size_t src_len,
                           char* dst, size_t expect) {
  const uint8_t* src = reinterpret_cast<const uint8_t*>(src_c);
  size_t pos = 0;
  size_t out = 0;
  if (src_len == 0) return expect == 0 ? 0 : -1;
  while (pos < src_len) {
    uint8_t token = src[pos++];
    // literals
    uint64_t lit = token >> 4;
    if (lit == 15) {
      for (;;) {
        if (pos >= src_len) return -1;
        uint8_t b = src[pos++];
        lit += b;
        if (b != 255) break;
        if (lit > (1ull << 40)) return -1;  // length bomb
      }
    }
    if (pos + lit > src_len || out + lit > expect) return -1;
    std::memcpy(dst + out, src + pos, lit);
    out += lit;
    pos += lit;
    if (pos == src_len) break;  // last sequence carries literals only
    // match
    if (pos + 2 > src_len) return -1;
    uint32_t offset = static_cast<uint32_t>(src[pos]) |
                      (static_cast<uint32_t>(src[pos + 1]) << 8);
    pos += 2;
    if (offset == 0 || offset > out) return -1;
    uint64_t mlen = token & 15;
    if (mlen == 15) {
      for (;;) {
        if (pos >= src_len) return -1;
        uint8_t b = src[pos++];
        mlen += b;
        if (b != 255) break;
        if (mlen > (1ull << 40)) return -1;
      }
    }
    mlen += 4;  // minmatch
    if (out + mlen > expect) return -1;
    const char* from = dst + out - offset;
    char* op = dst + out;
    if (offset >= 8) {
      // non-overlapping at 8-byte granularity; sub-8 tail byte-wise so no
      // write lands past `expect` (same contract as the snappy decoder)
      uint64_t wide = mlen & ~7ull;
      for (uint64_t i = 0; i < wide; i += 8) std::memcpy(op + i, from + i, 8);
      for (uint64_t i = wide; i < mlen; i++) op[i] = from[i];
    } else {
      for (uint64_t i = 0; i < mlen; i++) op[i] = from[i];  // RLE overlap
    }
    out += mlen;
  }
  return out == expect ? static_cast<ssize_t>(out) : -1;
}

static inline uint32_t lz4_hash(uint32_t v) {
  return (v * 2654435761u) >> 19;  // 13-bit table
}

// Append a literal/match length in LZ4's nibble + 255-extension form.
static inline bool lz4_put_len(uint64_t extra, char* dst, size_t dst_cap,
                               size_t* out) {
  while (extra >= 255) {
    if (*out >= dst_cap) return false;
    dst[(*out)++] = static_cast<char>(255);
    extra -= 255;
  }
  if (*out >= dst_cap) return false;
  dst[(*out)++] = static_cast<char>(extra);
  return true;
}

ssize_t ptq_lz4_compress(const char* src_c, size_t src_len,
                         char* dst, size_t dst_cap) {
  if (dst_cap < ptq_lz4_max_compressed_length(src_len)) return -1;
  const uint8_t* src = reinterpret_cast<const uint8_t*>(src_c);
  size_t out = 0;
  size_t lit_start = 0;
  size_t pos = 0;
  constexpr size_t kTableSize = 1 << 13;
  static thread_local uint32_t table[kTableSize];
  // The format forbids matches in the final 12 bytes (spec end-of-block
  // rule: last sequence is literals-only and >= 5 bytes, matches must not
  // start within the last 12) — canonical decoders rely on it.
  if (src_len > 12) {
    std::memset(table, 0, sizeof(table));
    const size_t match_limit = src_len - 12;
    while (pos <= match_limit) {
      uint32_t cur;
      std::memcpy(&cur, src + pos, 4);
      uint32_t h = lz4_hash(cur);
      size_t cand = table[h];
      table[h] = static_cast<uint32_t>(pos);
      uint32_t cv;
      if (cand < pos && pos - cand < (1u << 16) &&
          (std::memcpy(&cv, src + cand, 4), cv == cur)) {
        // extend, but never into the last 5 bytes (they must stay literal)
        size_t max_len = src_len - 5 - pos;
        size_t len = 4;
        while (len < max_len && src[cand + len] == src[pos + len]) len++;
        size_t lit = pos - lit_start;
        uint8_t tok_lit = lit >= 15 ? 15 : static_cast<uint8_t>(lit);
        uint8_t tok_m = (len - 4) >= 15 ? 15 : static_cast<uint8_t>(len - 4);
        if (out >= dst_cap) return -1;
        dst[out++] = static_cast<char>((tok_lit << 4) | tok_m);
        if (tok_lit == 15 && !lz4_put_len(lit - 15, dst, dst_cap, &out))
          return -1;
        if (out + lit > dst_cap) return -1;
        std::memcpy(dst + out, src + lit_start, lit);
        out += lit;
        size_t offset = pos - cand;
        if (out + 2 > dst_cap) return -1;
        dst[out++] = static_cast<char>(offset & 0xff);
        dst[out++] = static_cast<char>(offset >> 8);
        if (tok_m == 15 && !lz4_put_len(len - 4 - 15, dst, dst_cap, &out))
          return -1;
        pos += len;
        lit_start = pos;
      } else {
        pos++;
      }
    }
  }
  // trailing literals (the whole input when src_len <= 12)
  {
    size_t lit = src_len - lit_start;
    uint8_t tok_lit = lit >= 15 ? 15 : static_cast<uint8_t>(lit);
    if (out >= dst_cap) return -1;
    dst[out++] = static_cast<char>(tok_lit << 4);
    if (tok_lit == 15 && !lz4_put_len(lit - 15, dst, dst_cap, &out)) return -1;
    if (out + lit > dst_cap) return -1;
    std::memcpy(dst + out, src + lit_start, lit);
    out += lit;
  }
  return static_cast<ssize_t>(out);
}

// Parquet's legacy LZ4 codec (id 5) is Hadoop-framed on disk: repeated
// [4B BE uncompressed size][4B BE compressed size][raw block]; some writers
// emit bare raw blocks instead. Mirror parquet-cpp: try the framing, fall
// back to one raw block.
ssize_t ptq_lz4_hadoop_decompress(const char* src_c, size_t src_len,
                                  char* dst, size_t expect) {
  const uint8_t* src = reinterpret_cast<const uint8_t*>(src_c);
  size_t pos = 0;
  size_t out = 0;
  bool framed = true;
  while (pos < src_len) {
    if (pos + 8 > src_len) { framed = false; break; }
    uint64_t usz = (static_cast<uint32_t>(src[pos]) << 24) |
                   (static_cast<uint32_t>(src[pos + 1]) << 16) |
                   (static_cast<uint32_t>(src[pos + 2]) << 8) |
                   static_cast<uint32_t>(src[pos + 3]);
    uint64_t csz = (static_cast<uint32_t>(src[pos + 4]) << 24) |
                   (static_cast<uint32_t>(src[pos + 5]) << 16) |
                   (static_cast<uint32_t>(src[pos + 6]) << 8) |
                   static_cast<uint32_t>(src[pos + 7]);
    if (pos + 8 + csz > src_len || out + usz > expect) { framed = false; break; }
    ssize_t got = ptq_lz4_decompress(src_c + pos + 8, csz, dst + out, usz);
    if (got < 0 || static_cast<uint64_t>(got) != usz) { framed = false; break; }
    out += usz;
    pos += 8 + csz;
  }
  if (framed && out == expect) return static_cast<ssize_t>(out);
  return ptq_lz4_decompress(src_c, src_len, dst, expect);
}

// ---------------------------------------------------------------------------
// XXH64 + split-block bloom filter (parquet-format BloomFilter.md)
//
// Implemented from the public xxHash specification and the parquet split-
// block bloom description: 32-byte blocks of 8 uint32 words; a value's
// block comes from the hash's top 32 bits, its 8 bit positions from the
// low 32 bits multiplied by 8 fixed odd salts.
// ---------------------------------------------------------------------------

static const uint64_t XP1 = 0x9E3779B185EBCA87ull;
static const uint64_t XP2 = 0xC2B2AE3D27D4EB4Full;
static const uint64_t XP3 = 0x165667B19E3779F9ull;
static const uint64_t XP4 = 0x85EBCA77C2B2AE63ull;
static const uint64_t XP5 = 0x27D4EB2F165667C5ull;

static inline uint64_t xrotl(uint64_t x, int r) {
  return (x << r) | (x >> (64 - r));
}

static inline uint64_t xread64(const uint8_t* p) {
  uint64_t v;
  std::memcpy(&v, p, 8);
  return v;  // little-endian hosts only (matches the rest of this file)
}

static inline uint32_t xread32(const uint8_t* p) {
  uint32_t v;
  std::memcpy(&v, p, 4);
  return v;
}

uint64_t ptq_xxh64(const uint8_t* p, size_t len, uint64_t seed) {
  const uint8_t* end = p + len;
  uint64_t h;
  if (len >= 32) {
    uint64_t v1 = seed + XP1 + XP2, v2 = seed + XP2, v3 = seed, v4 = seed - XP1;
    const uint8_t* limit = end - 32;
    do {
      v1 = xrotl(v1 + xread64(p) * XP2, 31) * XP1;
      v2 = xrotl(v2 + xread64(p + 8) * XP2, 31) * XP1;
      v3 = xrotl(v3 + xread64(p + 16) * XP2, 31) * XP1;
      v4 = xrotl(v4 + xread64(p + 24) * XP2, 31) * XP1;
      p += 32;
    } while (p <= limit);
    h = xrotl(v1, 1) + xrotl(v2, 7) + xrotl(v3, 12) + xrotl(v4, 18);
    h = (h ^ (xrotl(v1 * XP2, 31) * XP1)) * XP1 + XP4;
    h = (h ^ (xrotl(v2 * XP2, 31) * XP1)) * XP1 + XP4;
    h = (h ^ (xrotl(v3 * XP2, 31) * XP1)) * XP1 + XP4;
    h = (h ^ (xrotl(v4 * XP2, 31) * XP1)) * XP1 + XP4;
  } else {
    h = seed + XP5;
  }
  h += static_cast<uint64_t>(len);
  while (p + 8 <= end) {
    h = xrotl(h ^ (xrotl(xread64(p) * XP2, 31) * XP1), 27) * XP1 + XP4;
    p += 8;
  }
  if (p + 4 <= end) {
    h = xrotl(h ^ (static_cast<uint64_t>(xread32(p)) * XP1), 23) * XP2 + XP3;
    p += 4;
  }
  while (p < end) {
    h = xrotl(h ^ (static_cast<uint64_t>(*p) * XP5), 11) * XP1;
    p++;
  }
  h ^= h >> 33;
  h *= XP2;
  h ^= h >> 29;
  h *= XP3;
  h ^= h >> 32;
  return h;
}

// Hash n fixed-width elements (stride bytes each, contiguous).
void ptq_xxh64_fixed(const uint8_t* src, int64_t n, int stride, uint64_t* out) {
  for (int64_t i = 0; i < n; i++)
    out[i] = ptq_xxh64(src + static_cast<size_t>(i) * stride, stride, 0);
}

// Hash n variable-length elements addressed by int64 offsets[n+1].
void ptq_xxh64_offsets(const uint8_t* data, const int64_t* offsets, int64_t n,
                       uint64_t* out) {
  for (int64_t i = 0; i < n; i++)
    out[i] = ptq_xxh64(data + offsets[i],
                       static_cast<size_t>(offsets[i + 1] - offsets[i]), 0);
}

static const uint32_t BLOOM_SALT[8] = {
    0x47b6137bu, 0x44974d91u, 0x8824ad5bu, 0xa2b7289du,
    0x705495c7u, 0x2df1424bu, 0x9efc4947u, 0x5c6bfb31u};

void ptq_bloom_insert(uint32_t* blocks, int64_t num_blocks,
                      const uint64_t* hashes, int64_t n) {
  for (int64_t i = 0; i < n; i++) {
    uint64_t h = hashes[i];
    uint64_t bi = ((h >> 32) * static_cast<uint64_t>(num_blocks)) >> 32;
    uint32_t x = static_cast<uint32_t>(h);
    uint32_t* b = blocks + bi * 8;
    for (int j = 0; j < 8; j++) b[j] |= 1u << ((x * BLOOM_SALT[j]) >> 27);
  }
}

// out[i] = 1 if hashes[i] might be present.
void ptq_bloom_check(const uint32_t* blocks, int64_t num_blocks,
                     const uint64_t* hashes, int64_t n, uint8_t* out) {
  for (int64_t i = 0; i < n; i++) {
    uint64_t h = hashes[i];
    uint64_t bi = ((h >> 32) * static_cast<uint64_t>(num_blocks)) >> 32;
    uint32_t x = static_cast<uint32_t>(h);
    const uint32_t* b = blocks + bi * 8;
    uint8_t hit = 1;
    for (int j = 0; j < 8; j++)
      hit &= static_cast<uint8_t>((b[j] >> ((x * BLOOM_SALT[j]) >> 27)) & 1);
    out[i] = hit;
  }
}

// ---------------------------------------------------------------------------
// PLAIN byte_array scan: 4-byte LE length + payload, repeated
// ---------------------------------------------------------------------------

// Fills offsets[0..num_values] (compacted) and copies payloads into data_out.
// Returns bytes consumed from src, or -1 on corrupt input / overflow.
ssize_t ptq_byte_array_gather(const char* src, size_t src_len, int64_t num_values,
                              int64_t* offsets, char* data_out, size_t data_cap) {
  size_t pos = 0;
  int64_t total = 0;
  offsets[0] = 0;
  for (int64_t i = 0; i < num_values; i++) {
    if (pos + 4 > src_len) return -1;
    uint32_t len;
    std::memcpy(&len, src + pos, 4);  // little-endian hosts only (x86/arm64)
    pos += 4;
    if (pos + len > src_len) return -1;
    if (static_cast<size_t>(total) + len > data_cap) return -1;
    std::memcpy(data_out + total, src + pos, len);
    pos += len;
    total += len;
    offsets[i + 1] = total;
  }
  return static_cast<ssize_t>(pos);
}

// ---------------------------------------------------------------------------
// hybrid RLE/bit-pack run-header prescan
// ---------------------------------------------------------------------------

// Outputs one row per run. bp_offsets are ABSOLUTE byte offsets into src
// (the caller uses src itself as the packed buffer). Returns the number of
// runs, or -1 on corrupt input, or -2 if max_runs is too small.
ssize_t ptq_prescan_hybrid(const uint8_t* src, size_t src_len, int64_t num_values,
                           int width, uint8_t* is_rle, int64_t* counts,
                           uint64_t* values, int64_t* bp_offsets,
                           size_t max_runs, int64_t* consumed) {
  if (width < 0 || width > 64) return -1;
  const size_t vbytes = (width + 7) / 8;
  size_t pos = 0;
  int64_t produced = 0;
  size_t runs = 0;
  while (produced < num_values) {
    uint64_t header = 0;
    int shift = 0;
    for (;;) {
      if (pos >= src_len || shift > 63) return -1;
      uint8_t b = src[pos++];
      if (shift == 63 && (b & 0x7e)) return -1;  // overflows uint64
      header |= static_cast<uint64_t>(b & 0x7f) << shift;
      if (!(b & 0x80)) break;
      shift += 7;
    }
    if (runs >= max_runs) return -2;
    if (header & 1) {
      uint64_t groups = header >> 1;
      // overflow guards before any multiply (the Python fallback rejects these
      // via arbitrary-precision arithmetic; keep parity)
      if (groups == 0 || groups > (1ull << 40)) return -1;
      uint64_t count = groups * 8;
      uint64_t nbytes = groups * static_cast<uint64_t>(width);
      if (pos + nbytes > src_len) return -1;
      is_rle[runs] = 0;
      counts[runs] = static_cast<int64_t>(count);
      values[runs] = 0;
      bp_offsets[runs] = static_cast<int64_t>(pos);
      pos += nbytes;
      produced += static_cast<int64_t>(count);
    } else {
      uint64_t count = header >> 1;
      if (count == 0 || count > (1ull << 40) || pos + vbytes > src_len) return -1;
      uint64_t v = 0;
      for (size_t i = 0; i < vbytes; i++) v |= static_cast<uint64_t>(src[pos + i]) << (8 * i);
      if (width < 64 && v >= (1ull << width)) return -1;
      pos += vbytes;
      is_rle[runs] = 1;
      counts[runs] = static_cast<int64_t>(count);
      values[runs] = v;
      bp_offsets[runs] = 0;
      produced += static_cast<int64_t>(count);
    }
    runs++;
  }
  *consumed = static_cast<int64_t>(pos);
  return static_cast<ssize_t>(runs);
}

// ---------------------------------------------------------------------------
// bit-stream reader (LSB-first, parquet bit-packed order)
// ---------------------------------------------------------------------------

struct BitReader {
  const uint8_t* src;
  size_t len;
  size_t pos;     // next byte
  uint64_t buf;   // pending bits, LSB first
  int bits;       // number of pending bits
};

static inline void br_init(BitReader* r, const uint8_t* src, size_t len) {
  r->src = src; r->len = len; r->pos = 0; r->buf = 0; r->bits = 0;
}

// Reads `w` bits (0 <= w <= 64). Caller guarantees the underlying payload is
// in bounds (all call sites bounds-check the whole run/miniblock first).
static inline uint64_t br_read(BitReader* r, int w) {
  uint64_t v = 0;
  int got = 0;
  while (got < w) {
    if (r->bits == 0) {
      r->buf = r->src[r->pos++];
      r->bits = 8;
    }
    int take = w - got;
    if (take > r->bits) take = r->bits;
    v |= (r->buf & ((take == 64) ? ~0ull : ((1ull << take) - 1))) << got;
    r->buf >>= take;
    r->bits -= take;
    got += take;
  }
  return v;
}

// ---------------------------------------------------------------------------
// one-shot hybrid RLE/bit-pack decode (prescan + expand fused, host hot path)
// ---------------------------------------------------------------------------

// Decodes `num_values` into out32 or out64 (exactly one non-null). Returns
// bytes consumed, or -1 on corrupt input. Semantics mirror prescan_hybrid +
// expand_runs in ops/rle_hybrid.py (the NumPy reference implementation).
ssize_t ptq_hybrid_decode(const uint8_t* src, size_t src_len, int64_t num_values,
                          int width, uint32_t* out32, uint64_t* out64) {
  if (width < 0 || width > 64) return -1;
  if (width > 32 && out32) return -1;
  const size_t vbytes = (width + 7) / 8;
  size_t pos = 0;
  int64_t produced = 0;
  while (produced < num_values) {
    uint64_t header = 0;
    int shift = 0;
    for (;;) {
      if (pos >= src_len || shift > 63) return -1;
      uint8_t b = src[pos++];
      if (shift == 63 && (b & 0x7e)) return -1;  // overflows uint64
      header |= static_cast<uint64_t>(b & 0x7f) << shift;
      if (!(b & 0x80)) break;
      shift += 7;
    }
    if (header & 1) {
      uint64_t groups = header >> 1;
      if (groups == 0 || groups > (1ull << 40)) return -1;
      uint64_t count = groups * 8;
      uint64_t nbytes = groups * static_cast<uint64_t>(width);
      if (pos + nbytes > src_len) return -1;
      int64_t take = num_values - produced;
      if (static_cast<uint64_t>(take) > count) take = static_cast<int64_t>(count);
      BitReader r;
      br_init(&r, src + pos, nbytes);
      if (out32) {
        for (int64_t i = 0; i < take; i++) out32[produced + i] = static_cast<uint32_t>(br_read(&r, width));
      } else {
        for (int64_t i = 0; i < take; i++) out64[produced + i] = br_read(&r, width);
      }
      pos += nbytes;
      produced += take;
    } else {
      uint64_t count = header >> 1;
      if (count == 0 || count > (1ull << 40) || pos + vbytes > src_len) return -1;
      uint64_t v = 0;
      for (size_t i = 0; i < vbytes; i++) v |= static_cast<uint64_t>(src[pos + i]) << (8 * i);
      if (width < 64 && v >= (1ull << width)) return -1;
      pos += vbytes;
      int64_t take = num_values - produced;
      if (static_cast<uint64_t>(take) > count) take = static_cast<int64_t>(count);
      if (out32) {
        uint32_t v32 = static_cast<uint32_t>(v);
        for (int64_t i = 0; i < take; i++) out32[produced + i] = v32;
      } else {
        for (int64_t i = 0; i < take; i++) out64[produced + i] = v;
      }
      produced += take;
    }
  }
  return static_cast<ssize_t>(pos);
}

// ---------------------------------------------------------------------------
// DELTA_BINARY_PACKED decode (header walk + miniblock unpack + wrapping cumsum)
// ---------------------------------------------------------------------------

static inline bool read_uvarint64(const uint8_t* src, size_t src_len, size_t* pos,
                                  uint64_t* out) {
  uint64_t v = 0;
  int shift = 0;
  for (;;) {
    if (*pos >= src_len || shift > 63) return false;
    uint8_t b = src[(*pos)++];
    if (shift == 63 && (b & 0x7e)) return false;  // overflows uint64
    v |= static_cast<uint64_t>(b & 0x7f) << shift;
    if (!(b & 0x80)) break;
    shift += 7;
  }
  *out = v;
  return true;
}

// Full decode of a DELTA_BINARY_PACKED stream into out (int32 when nbits==32,
// int64 when nbits==64; the buffer must hold the header's value count, which
// is bounded by max_total). Returns bytes consumed, -1 on corrupt input, -3
// if the stream's count exceeds max_total (validation-before-allocation: the
// caller probes the count first via ptq_delta_peek_total).
// Semantics mirror ops/delta.py prescan_delta + decode_delta exactly,
// including wrapping min-delta arithmetic (reference: deltabp_encoder.go:58-61)
// and trailing-miniblock payload rules (reference: deltabp_decoder.go flush()).
ssize_t ptq_delta_decode(const uint8_t* src, size_t src_len, int nbits,
                         int64_t max_total, void* out_v, int64_t* total_out) {
  if (nbits != 32 && nbits != 64) return -1;
  size_t pos = 0;
  uint64_t block_size, mini_count, total_u;
  if (!read_uvarint64(src, src_len, &pos, &block_size)) return -1;
  if (!read_uvarint64(src, src_len, &pos, &mini_count)) return -1;
  if (!read_uvarint64(src, src_len, &pos, &total_u)) return -1;
  uint64_t first_zz;
  if (!read_uvarint64(src, src_len, &pos, &first_zz)) return -1;
  uint64_t first = (first_zz >> 1) ^ (~(first_zz & 1) + 1);  // zigzag decode
  if (block_size == 0 || block_size % 128 != 0 || block_size > (1ull << 20)) return -1;
  if (mini_count == 0 || mini_count > 512 || block_size % mini_count != 0) return -1;
  uint64_t mini_len = block_size / mini_count;
  if (mini_len % 8 != 0) return -1;
  int64_t total = static_cast<int64_t>(total_u);
  if (total_u > (1ull << 62)) return -1;
  if (max_total >= 0 && total > max_total) return -3;
  // plausibility backstop (parity with prescan_delta)
  uint64_t plausible = 1 + (src_len / (1 + mini_count) + 1) * block_size;
  if (total_u > plausible) return -3;
  *total_out = total;

  const uint64_t mask = (nbits == 64) ? ~0ull : ((1ull << nbits) - 1);
  int32_t* out32 = (nbits == 32) ? static_cast<int32_t*>(out_v) : nullptr;
  int64_t* out64 = (nbits == 64) ? static_cast<int64_t*>(out_v) : nullptr;
  uint64_t acc = first & mask;
  if (total > 0) {
    if (out32) out32[0] = static_cast<int32_t>(static_cast<uint32_t>(acc));
    else out64[0] = static_cast<int64_t>(acc);
  }
  int64_t n_deltas = total > 1 ? total - 1 : 0;
  int64_t produced = 0;
  while (produced < n_deltas) {
    uint64_t md_zz;
    if (!read_uvarint64(src, src_len, &pos, &md_zz)) return -1;
    uint64_t min_delta = (md_zz >> 1) ^ (~(md_zz & 1) + 1);
    if (pos + mini_count > src_len) return -1;
    const uint8_t* widths = src + pos;
    pos += mini_count;
    for (uint64_t m = 0; m < mini_count; m++) {
      int64_t remaining = n_deltas - produced;
      if (remaining <= 0) continue;  // unused trailing miniblock: no payload
      int w = widths[m];
      if (w > nbits) return -1;
      uint64_t payload = (mini_len / 8) * static_cast<uint64_t>(w);
      if (pos + payload > src_len) return -1;
      int64_t take = remaining < static_cast<int64_t>(mini_len)
                         ? remaining : static_cast<int64_t>(mini_len);
      BitReader r;
      br_init(&r, src + pos, payload);
      if (out32) {
        uint32_t a = static_cast<uint32_t>(acc);
        uint32_t md32 = static_cast<uint32_t>(min_delta);
        for (int64_t i = 0; i < take; i++) {
          a += static_cast<uint32_t>(br_read(&r, w)) + md32;
          out32[produced + 1 + i] = static_cast<int32_t>(a);
        }
        acc = a;
      } else {
        uint64_t a = acc;
        for (int64_t i = 0; i < take; i++) {
          a += br_read(&r, w) + min_delta;
          out64[produced + 1 + i] = static_cast<int64_t>(a);
        }
        acc = a;
      }
      pos += payload;
      produced += take;
    }
  }
  return static_cast<ssize_t>(pos);
}

// Header probe for pre-allocation: validates the full header (same rules as
// ptq_delta_decode, including the plausibility backstop that bounds the value
// count by the stream length — validation-before-allocation) and returns the
// value count. Returns 0 on success, -1 on corrupt/implausible header.
ssize_t ptq_delta_peek_total(const uint8_t* src, size_t src_len, int64_t* total) {
  size_t pos = 0;
  uint64_t bs, mc, t, fz;
  if (!read_uvarint64(src, src_len, &pos, &bs)) return -1;
  if (!read_uvarint64(src, src_len, &pos, &mc)) return -1;
  if (!read_uvarint64(src, src_len, &pos, &t)) return -1;
  if (!read_uvarint64(src, src_len, &pos, &fz)) return -1;
  if (bs == 0 || bs % 128 != 0 || bs > (1ull << 20)) return -1;
  if (mc == 0 || mc > 512 || bs % mc != 0) return -1;
  if ((bs / mc) % 8 != 0) return -1;
  if (t > (1ull << 62)) return -1;
  uint64_t plausible = 1 + (src_len / (1 + mc) + 1) * bs;
  if (t > plausible) return -1;
  *total = static_cast<int64_t>(t);
  return 0;
}

// ---------------------------------------------------------------------------
// byte-array dictionary gather (ByteArrayData.take hot path)
// ---------------------------------------------------------------------------

// out must hold sum of the gathered lengths (caller computes via new_offsets,
// which it builds with a NumPy cumsum). Returns 0, or -1 on a bad index.
ssize_t ptq_bytearray_take(const char* data, size_t data_len,
                           const int64_t* offsets, int64_t n_src,
                           const int64_t* indices, int64_t n_idx,
                           const int64_t* new_offsets, char* out, size_t out_cap) {
  for (int64_t k = 0; k < n_idx; k++) {
    int64_t i = indices[k];
    if (i < 0 || i >= n_src) return -1;
    int64_t start = offsets[i];
    int64_t len = offsets[i + 1] - start;
    int64_t dst = new_offsets[k];
    if (start < 0 || len < 0 || static_cast<size_t>(start + len) > data_len ||
        static_cast<size_t>(dst + len) > out_cap)
      return -1;
    std::memcpy(out + dst, data + start, len);
  }
  return 0;
}

// PLAIN BYTE_ARRAY encode: [4B LE length][bytes] per value, straight from
// an (offsets, data) column — the write path's hot loop for string chunks.
// out must hold data_len + 4*n bytes.
ssize_t ptq_plain_encode_bytearray(const char* data, size_t data_len,
                                   const int64_t* offsets, int64_t n,
                                   char* out, size_t out_cap) {
  size_t pos = 0;
  for (int64_t i = 0; i < n; i++) {
    int64_t start = offsets[i];
    int64_t len = offsets[i + 1] - start;
    if (start < 0 || len < 0 || static_cast<size_t>(start + len) > data_len)
      return -1;
    if (len > static_cast<int64_t>(UINT32_MAX)) return -1;  // 4B prefix cap
    if (pos + 4 + static_cast<size_t>(len) > out_cap) return -1;
    uint32_t l32 = static_cast<uint32_t>(len);
    std::memcpy(out + pos, &l32, 4);
    std::memcpy(out + pos + 4, data + start, static_cast<size_t>(len));
    pos += 4 + static_cast<size_t>(len);
  }
  return static_cast<ssize_t>(pos);
}

// ---------------------------------------------------------------------------
// DELTA_BINARY_PACKED header-only prescan (device-decode planning hot path)
// ---------------------------------------------------------------------------

// Walks block/miniblock headers only (payload bytes stay packed for the
// device kernel). One table entry per miniblock covering >=1 real delta.
// Semantics mirror ops/delta.py prescan_delta_packed exactly. Returns the
// number of entries M, or -1 corrupt, -2 table overflow, -3 count exceeds
// max_total / implausible.
ssize_t ptq_prescan_delta_packed(const uint8_t* src, size_t src_len, int nbits,
                                 int64_t max_total, uint32_t* widths,
                                 int64_t* byte_starts, int32_t* out_starts,
                                 uint64_t* mins, size_t max_entries,
                                 uint64_t* first_value, int64_t* total_out,
                                 int64_t* consumed) {
  if (nbits != 32 && nbits != 64) return -1;
  size_t pos = 0;
  uint64_t block_size, mini_count, total_u, first_zz;
  if (!read_uvarint64(src, src_len, &pos, &block_size)) return -1;
  if (!read_uvarint64(src, src_len, &pos, &mini_count)) return -1;
  if (!read_uvarint64(src, src_len, &pos, &total_u)) return -1;
  if (!read_uvarint64(src, src_len, &pos, &first_zz)) return -1;
  if (block_size == 0 || block_size % 128 != 0 || block_size > (1ull << 20)) return -1;
  if (mini_count == 0 || mini_count > 512 || block_size % mini_count != 0) return -1;
  uint64_t mini_len = block_size / mini_count;
  if (mini_len % 8 != 0) return -1;
  if (total_u > (1ull << 62)) return -1;
  int64_t total = static_cast<int64_t>(total_u);
  if (max_total < 0) max_total = 0;  // match Python's max(max_total, 0) clamp
  if (total > max_total) return -3;
  uint64_t plausible = 1 + (src_len / (1 + mini_count) + 1) * block_size;
  if (total_u > plausible) return -3;
  const uint64_t mask = (nbits == 64) ? ~0ull : ((1ull << nbits) - 1);
  *first_value = ((first_zz >> 1) ^ (~(first_zz & 1) + 1)) & mask;
  *total_out = total;

  int64_t n_deltas = total > 1 ? total - 1 : 0;
  int64_t produced = 0;
  size_t m = 0;
  while (produced < n_deltas) {
    uint64_t md_zz;
    if (!read_uvarint64(src, src_len, &pos, &md_zz)) return -1;
    uint64_t min_delta = ((md_zz >> 1) ^ (~(md_zz & 1) + 1)) & mask;
    if (pos + mini_count > src_len) return -1;
    const uint8_t* wb = src + pos;
    pos += mini_count;
    for (uint64_t i = 0; i < mini_count; i++) {
      int64_t remaining = n_deltas - produced;
      if (remaining <= 0) continue;  // unused trailing miniblock: no payload
      int w = wb[i];
      if (w > nbits) return -1;
      uint64_t payload = (mini_len / 8) * static_cast<uint64_t>(w);
      if (pos + payload > src_len) return -1;
      if (m >= max_entries) return -2;
      widths[m] = static_cast<uint32_t>(w);
      byte_starts[m] = static_cast<int64_t>(pos);
      out_starts[m] = static_cast<int32_t>(produced);
      mins[m] = min_delta;
      m++;
      pos += payload;
      produced += remaining < static_cast<int64_t>(mini_len)
                      ? remaining : static_cast<int64_t>(mini_len);
    }
  }
  *consumed = static_cast<int64_t>(pos);
  return static_cast<ssize_t>(m);
}

// ---------------------------------------------------------------------------
// Thrift compact-protocol PageHeader parser (one header per page — the hot
// metadata path, SURVEY §7.3.6). Unknown/unneeded fields (statistics) are
// skipped by wire type exactly like generated Thrift readers.
// ---------------------------------------------------------------------------

namespace {

struct CpReader {
  const uint8_t* src;
  size_t len;
  size_t pos;
  bool truncated;  // ran off the window (retry with a larger peek)
};

inline bool cp_byte(CpReader* r, uint8_t* out) {
  if (r->pos >= r->len) { r->truncated = true; return false; }
  *out = r->src[r->pos++];
  return true;
}

inline bool cp_uvarint(CpReader* r, uint64_t* out) {
  uint64_t v = 0;
  int shift = 0;
  for (;;) {
    uint8_t b;
    if (!cp_byte(r, &b)) return false;
    if (shift > 63) return false;
    v |= static_cast<uint64_t>(b & 0x7f) << shift;
    if (!(b & 0x80)) break;
    shift += 7;
  }
  *out = v;
  return true;
}

inline bool cp_zigzag(CpReader* r, int64_t* out) {
  uint64_t u;
  if (!cp_uvarint(r, &u)) return false;
  *out = static_cast<int64_t>((u >> 1) ^ (~(u & 1) + 1));
  return true;
}

bool cp_skip(CpReader* r, int wire, int depth);

// Skip the fields of a struct up to and including STOP.
bool cp_skip_struct(CpReader* r, int depth) {
  if (depth > 16) return false;
  for (;;) {
    uint8_t fh;
    if (!cp_byte(r, &fh)) return false;
    if (fh == 0) return true;  // STOP
    if (!(fh >> 4)) {          // long form: explicit zigzag field id
      int64_t fid;
      if (!cp_zigzag(r, &fid)) return false;
    }
    if (!cp_skip(r, fh & 0x0F, depth)) return false;
  }
}

bool cp_skip(CpReader* r, int wire, int depth) {
  if (depth > 16) return false;
  uint64_t u;
  int64_t s;
  uint8_t b;
  switch (wire) {
    case 1: case 2: return true;        // bool true/false: value in type nibble
    case 3: return cp_byte(r, &b);      // byte
    case 4: case 5: case 6:             // i16/i32/i64: zigzag varint
      return cp_zigzag(r, &s);
    case 7:                             // double: 8 bytes
      if (r->pos + 8 > r->len) { r->truncated = true; return false; }
      r->pos += 8;
      return true;
    case 8:                             // binary: len + bytes
      if (!cp_uvarint(r, &u)) return false;
      // Subtraction form: pos <= len is invariant, so len-pos cannot
      // underflow, and a near-2^64 u cannot wrap the addition-form check.
      if (u > r->len - r->pos) { r->truncated = true; return false; }
      r->pos += u;
      return true;
    case 9: case 10: {                  // list/set: (size<<4)|etype
      if (!cp_byte(r, &b)) return false;
      uint64_t n = b >> 4;
      int etype = b & 0x0F;
      if (n == 15 && !cp_uvarint(r, &n)) return false;
      // Preflight size guard: every element occupies >= 1 wire byte, EXCEPT
      // bool (kind 1/2), whose cp_skip consumes nothing — a lying count
      // there would spin this loop for up to 2^64 iterations (a hang, not
      // an overread). pos <= len is invariant, so len-pos cannot underflow.
      if (n > r->len - r->pos) { r->truncated = true; return false; }
      if (etype == 1 || etype == 2) {   // bool list: 1 byte per element
        r->pos += n;
        return true;
      }
      for (uint64_t i = 0; i < n; i++)
        if (!cp_skip(r, etype, depth + 1)) return false;
      return true;
    }
    case 11: {                          // map: size==0 -> empty, else kv types
      if (!cp_uvarint(r, &u)) return false;
      if (u == 0) return true;
      if (!cp_byte(r, &b)) return false;
      // Same hang guard as list/set: a bool key/value type would make each
      // iteration consume zero bytes, so an adversarial count must be
      // rejected against the remaining window up front.
      if (u > r->len - r->pos) { r->truncated = true; return false; }
      int kt = b >> 4, vt = b & 0x0F;
      for (uint64_t i = 0; i < u; i++) {
        // map bool keys/values occupy one byte each on the wire (unlike
        // bool STRUCT fields, whose value rides the field header)
        if (kt == 1 || kt == 2) {
          if (r->pos >= r->len) { r->truncated = true; return false; }
          r->pos++;
        } else if (!cp_skip(r, kt, depth + 1)) {
          return false;
        }
        if (vt == 1 || vt == 2) {
          if (r->pos >= r->len) { r->truncated = true; return false; }
          r->pos++;
        } else if (!cp_skip(r, vt, depth + 1)) {
          return false;
        }
      }
      return true;
    }
    case 12: return cp_skip_struct(r, depth + 1);
    default: return false;              // unknown wire type: corrupt
  }
}

// Parse one nested header struct, keeping declared fields into keep[fid-1].
// kinds[fid-1] gives the declared type: 'i' int (i16/i32/i64), 'b' bool.
// A field whose wire type mismatches its declaration is skipped by wire type
// (left absent), matching the Python reader's _wire_matches discipline.
bool cp_parse_flat_struct(CpReader* r, int64_t* keep, const char* kinds,
                          int n_keep) {
  int64_t fid = 0;
  for (;;) {
    uint8_t fh;
    if (!cp_byte(r, &fh)) return false;
    if (fh == 0) return true;
    int delta = fh >> 4;
    int wire = fh & 0x0F;
    if (delta) fid += delta;
    else if (!cp_zigzag(r, &fid)) return false;
    char kind = (fid >= 1 && fid <= n_keep) ? kinds[fid - 1] : 0;
    if (kind == 'b' && (wire == 1 || wire == 2)) {
      keep[fid - 1] = (wire == 1) ? 1 : 0;
    } else if (kind == 'i' && wire == 5) {  // exact CT_I32, like _wire_matches
      int64_t v;
      if (!cp_zigzag(r, &v)) return false;
      keep[fid - 1] = v;
    } else {
      if (!cp_skip(r, wire, 0)) return false;
    }
  }
}

}  // namespace

// Slot layout of out[28] (absent = INT64_MIN):
//   0 consumed bytes         1 type    2 uncompressed_size  3 compressed_size
//   4 crc
//   5 v1 present   6..9   v1 {num_values, encoding, def_enc, rep_enc}
//  10 dict present 11..13 dict {num_values, encoding, is_sorted}
//  14 v2 present   15..21 v2 {num_values, num_nulls, num_rows, encoding,
//                             def_len, rep_len, is_compressed}
//  22 index present
// Returns 0 on success, -1 corrupt, -2 window truncated (retry larger).
ssize_t ptq_parse_page_header(const uint8_t* src, size_t src_len, int64_t* out) {
  const int64_t ABSENT = INT64_MIN;
  for (int i = 0; i < 23; i++) out[i] = ABSENT;
  CpReader r{src, src_len, 0, false};
  int64_t fid = 0;
  for (;;) {
    uint8_t fh;
    if (!cp_byte(&r, &fh)) return r.truncated ? -2 : -1;
    if (fh == 0) break;  // STOP
    int delta = fh >> 4;
    int wire = fh & 0x0F;
    if (delta) fid += delta;
    else if (!cp_zigzag(&r, &fid)) return r.truncated ? -2 : -1;
    bool ok = true;
    if (fid >= 1 && fid <= 4 && wire == 5) {  // all i32 fields: exact CT_I32
      int64_t v;
      ok = cp_zigzag(&r, &v);
      if (ok) out[fid] = v;
    } else if (fid == 5 && wire == 12) {
      int64_t keep[4] = {ABSENT, ABSENT, ABSENT, ABSENT};
      ok = cp_parse_flat_struct(&r, keep, "iiii", 4);
      if (ok) { out[5] = 1; for (int i = 0; i < 4; i++) out[6 + i] = keep[i]; }
    } else if (fid == 6 && wire == 12) {
      ok = cp_skip_struct(&r, 1);
      if (ok) out[22] = 1;
    } else if (fid == 7 && wire == 12) {
      int64_t keep[3] = {ABSENT, ABSENT, ABSENT};
      ok = cp_parse_flat_struct(&r, keep, "iib", 3);
      if (ok) { out[10] = 1; for (int i = 0; i < 3; i++) out[11 + i] = keep[i]; }
    } else if (fid == 8 && wire == 12) {
      int64_t keep[7] = {ABSENT, ABSENT, ABSENT, ABSENT, ABSENT, ABSENT, ABSENT};
      ok = cp_parse_flat_struct(&r, keep, "iiiiiib", 7);
      if (ok) { out[14] = 1; for (int i = 0; i < 7; i++) out[15 + i] = keep[i]; }
    } else {
      ok = cp_skip(&r, wire, 0);
    }
    if (!ok) return r.truncated ? -2 : -1;
  }
  out[0] = static_cast<int64_t>(r.pos);
  return 0;
}

// ---------------------------------------------------------------------------
// Whole-chunk prepare walk (one native call per chunk).
//
// The per-page Python loop (header parse -> decompress -> level decode ->
// prescan -> route) is the dominant host cost of the device decode pipeline
// on wide files (reference page walk: chunk_reader.go:182-263). This fuses
// the entire walk: the caller hands the chunk's bytes plus output buffers
// and gets back packed per-page tables ready for vectorized batch assembly.
// Any input the walk cannot handle (unknown codec, corrupt stream, capacity
// overflow) returns a negative code and the caller falls back to the Python
// walk, which reproduces the exact error semantics.
// ---------------------------------------------------------------------------

namespace {

// gzip/zlib inflate with exact-size output (bomb guard: an output larger than
// `expect` fails instead of allocating; mirrors core/compress.py _Gzip).
bool gzip_inflate(const uint8_t* src, size_t src_len, uint8_t* dst, size_t expect) {
  z_stream s;
  std::memset(&s, 0, sizeof(s));
  if (inflateInit2(&s, 15 + 32) != Z_OK) return false;  // auto gzip/zlib header
  s.next_in = const_cast<Bytef*>(src);
  s.avail_in = static_cast<uInt>(src_len);
  s.next_out = dst;
  s.avail_out = static_cast<uInt>(expect);
  int rc = inflate(&s, Z_FINISH);
  bool ok = (rc == Z_STREAM_END && s.total_out == expect && s.avail_in == 0);
  inflateEnd(&s);
  return ok;
}

inline int level_bit_width(int max_level) {
  int w = 0;
  while (max_level) { w++; max_level >>= 1; }  // bit_length
  return w;
}

// Decompress one page block into scratch. Returns 0 ok, -1 corrupt/unknown
// codec, -5 scratch too small (same code contract as ptq_chunk_prepare).
int decompress_page(int codec, const uint8_t* src, size_t src_len,
                    uint8_t* scratch, size_t scratch_cap, size_t expect) {
  if (expect > scratch_cap) return -5;
  if (codec == 1) {
    // pass the PHYSICAL capacity: chunk_prepare allocates scratch with
    // >= 64 bytes of slack past the chunk's uncompressed size, which
    // switches the decoder into overshooting fast mode; the result is
    // still validated against the page's claimed size
    if (ptq_snappy_decompress(reinterpret_cast<const char*>(src), src_len,
                              reinterpret_cast<char*>(scratch), scratch_cap) !=
        static_cast<ssize_t>(expect))
      return -1;
    return 0;
  }
  if (codec == 2) return gzip_inflate(src, src_len, scratch, expect) ? 0 : -1;
  if (codec == 5)  // legacy LZ4: hadoop framing with raw-block fallback
    return ptq_lz4_hadoop_decompress(reinterpret_cast<const char*>(src),
                                     src_len, reinterpret_cast<char*>(scratch),
                                     expect) == static_cast<ssize_t>(expect)
               ? 0
               : -1;
  if (codec == 7)  // LZ4_RAW: one raw block
    return ptq_lz4_decompress(reinterpret_cast<const char*>(src), src_len,
                              reinterpret_cast<char*>(scratch), expect) ==
                   static_cast<ssize_t>(expect)
               ? 0
               : -1;
  return -1;
}

// Hybrid-decode a level stream into uint16, validating every value
// <= max_level (parity with ops/levels.py _check) and counting values equal
// to `target`. Returns bytes consumed, or -1 on corrupt input.
ssize_t decode_levels16(const uint8_t* src, size_t src_len, int64_t n,
                        int max_level, uint16_t* out, int target,
                        int64_t* eq_count) {
  const int width = level_bit_width(max_level);
  const size_t vbytes = (width + 7) / 8;
  size_t pos = 0;
  int64_t produced = 0;
  int64_t eq = 0;
  while (produced < n) {
    uint64_t header = 0;
    int shift = 0;
    for (;;) {
      if (pos >= src_len || shift > 63) return -1;
      uint8_t b = src[pos++];
      if (shift == 63 && (b & 0x7e)) return -1;
      header |= static_cast<uint64_t>(b & 0x7f) << shift;
      if (!(b & 0x80)) break;
      shift += 7;
    }
    if (header & 1) {
      uint64_t groups = header >> 1;
      if (groups == 0 || groups > (1ull << 40)) return -1;
      uint64_t count = groups * 8;
      uint64_t nbytes = groups * static_cast<uint64_t>(width);
      if (pos + nbytes > src_len) return -1;
      int64_t take = n - produced;
      if (static_cast<uint64_t>(take) > count) take = static_cast<int64_t>(count);
      if (width <= 4 && (8 % width) == 0) {
        // levels are almost always width 1 or 2: unpack whole bytes instead
        // of feeding a bit reader one value at a time (the nested-column
        // hot loop — every leaf value decodes max_rep + max_def levels)
        const int per = 8 / width;
        const uint16_t mask = static_cast<uint16_t>((1u << width) - 1);
        const uint8_t* bp = src + pos;
        uint16_t* op = out + produced;
        int64_t full = take / per;
        uint64_t bad = 0;
        for (int64_t b = 0; b < full; b++) {
          uint16_t byte = bp[b];
          for (int j = 0; j < per; j++) {
            uint16_t v = (byte >> (j * width)) & mask;
            op[b * per + j] = v;
            bad |= (v > max_level);
            eq += (v == target);
          }
        }
        for (int64_t i = full * per; i < take; i++) {
          uint16_t v = (bp[i / per] >> ((i % per) * width)) & mask;
          op[i] = v;
          bad |= (v > max_level);
          eq += (v == target);
        }
        if (bad) return -1;
      } else {
        BitReader r;
        br_init(&r, src + pos, nbytes);
        for (int64_t i = 0; i < take; i++) {
          uint64_t v = br_read(&r, width);
          if (v > static_cast<uint64_t>(max_level)) return -1;
          out[produced + i] = static_cast<uint16_t>(v);
          eq += (static_cast<int>(v) == target);
        }
      }
      pos += nbytes;
      produced += take;
    } else {
      uint64_t count = header >> 1;
      if (count == 0 || count > (1ull << 40) || pos + vbytes > src_len) return -1;
      uint64_t v = 0;
      for (size_t i = 0; i < vbytes; i++) v |= static_cast<uint64_t>(src[pos + i]) << (8 * i);
      if (width < 64 && v >= (1ull << width)) return -1;
      if (v > static_cast<uint64_t>(max_level)) return -1;
      pos += vbytes;
      int64_t take = n - produced;
      if (static_cast<uint64_t>(take) > count) take = static_cast<int64_t>(count);
      uint16_t v16 = static_cast<uint16_t>(v);
      for (int64_t i = 0; i < take; i++) out[produced + i] = v16;
      if (static_cast<int>(v) == target) eq += take;
      produced += take;
    }
  }
  if (eq_count) *eq_count = eq;
  return static_cast<ssize_t>(pos);
}

// Per-stage wall clock for the whole-chunk walk. All accounting is skipped
// when the caller passes no stage array (ns == nullptr): production calls pay
// one branch per stage boundary, the bench pays ~25 ns per clock_gettime.
struct StageClock {
  int64_t* ns;
  int64_t t0;
  static inline int64_t now() {
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return static_cast<int64_t>(ts.tv_sec) * 1000000000ll + ts.tv_nsec;
  }
  inline void start() {
    if (ns) t0 = now();
  }
  inline void stop(int slot) {
    if (ns) {
      int64_t t = now();
      ns[slot] += t - t0;
      t0 = t;
    }
  }
};

// stage_ns slots (accumulated nanoseconds)
enum { ST_DECOMPRESS = 0, ST_LEVELS = 1, ST_PRESCAN = 2, ST_COPY = 3, ST_CRC = 4 };

}  // namespace

// Page-table column layout (int64[n_pages][18]); absent fields are 0 unless
// noted. Routes: 0 host-decoded ("other"), 1 dict indices (hybrid run table),
// 2 delta-bp (miniblock table), 3 PLAIN numeric (bytes in values_out),
// 4 empty (no non-null values).
enum {
  PC_KIND = 0,      // 0 data page, 1 dictionary page, 2 index page
  PC_N = 1,         // num_values incl. nulls
  PC_NONNULL = 2,
  PC_ENC = 3,
  PC_ROUTE = 4,
  PC_VOFF = 5,      // offset of this page's value bytes in values_out
  PC_VLEN = 6,
  PC_LVLBASE = 7,   // start index of this page's levels in def_out/rep_out
  PC_RUNS = 8,      // first hybrid run index (route 1)
  PC_RUNE = 9,
  PC_PACKS = 10,    // packed_out byte range of this page's bit-packed payloads
  PC_PACKE = 11,
  PC_MINIS = 12,    // first delta miniblock entry (route 2)
  PC_MINIE = 13,
  PC_DSTART = 14,   // delta_out byte offset of this page's stream
  PC_DCONS = 15,    // bytes of delta stream consumed
  PC_EXTRA = 16,    // route 1: dict index bit width; route 2: stream total
  PC_DFIRST = 17,   // route 2: first value (uint64 bit pattern)
};
#define PT_COLS 18

// Returns n_pages >= 0 on success. Negative: -1 corrupt/unsupported (caller
// falls back to the Python walk for exact errors), -2 page table full,
// -3 hybrid run table full, -4 delta miniblock table full, -5 level/value
// capacity exceeded (metadata understated the chunk), -6 stored page CRC
// mismatch (validate_crc only; definite corruption, not "unsupported").
// err_info (nullable int64[4]) reports {stage, page index, page byte offset
// in the chunk, 0} for any negative return — the structured error channel
// parquet-tool verify and the fallback-ladder counters consume.
ssize_t ptq_chunk_prepare(
    const uint8_t* src, size_t src_len,
    int codec,               // 0 UNCOMPRESSED, 1 SNAPPY, 2 GZIP
    int validate_crc,        // nonzero: verify stored page CRCs in the walk
    int max_def, int max_rep,
    int type_size,           // PLAIN itemsize for numeric types, else 0
    int delta_nbits,         // 32/64 when delta-bp is device-eligible, else 0
    int64_t expected_values, // level buffer capacity (metadata num_values)
    int64_t* pages, size_t max_pages,
    uint16_t* def_out, uint16_t* rep_out,
    uint8_t* values_out, size_t values_cap,
    uint8_t* packed_out, size_t packed_cap,
    uint8_t* delta_out, size_t delta_cap,
    uint8_t* scratch, size_t scratch_cap,
    uint8_t* h_is_rle, int64_t* h_counts, uint64_t* h_values,
    int64_t* h_byteoff, size_t max_runs,
    uint32_t* d_widths, int64_t* d_bytestart, int32_t* d_outstart,
    uint64_t* d_mins, size_t max_minis,
    int64_t* totals, /* [8]: lvl_total, values_used, packed_used, delta_used,
                        runs, minis, has_dict, reserved */
    int64_t* stage_ns, /* nullable [5]: accumulated ns per stage (decompress,
                          levels, prescan, copy, crc) for the bench breakdown */
    int64_t* err_info /* nullable [4]: see above */) {
  StageClock clk{stage_ns, 0};
  size_t pos = 0;
  size_t n_pages = 0;
  int64_t lvl_total = 0;
  size_t values_used = 0, packed_used = 0, delta_used = 0;
  size_t runs = 0, minis = 0;
  bool has_dict = false;
  int64_t slots[23];
  // Failure-context tracking: the walk keeps err[] current (stage, page,
  // page byte offset) so every `return negative` below reports where it
  // died without threading the detail through dozens of return sites.
  int64_t err_local[4];
  int64_t* err = err_info ? err_info : err_local;
  err[0] = PTQ_STAGE_NONE; err[1] = 0; err[2] = 0; err[3] = 0;

  while (pos < src_len) {
    err[0] = PTQ_STAGE_HEADER;
    err[1] = static_cast<int64_t>(n_pages);
    err[2] = static_cast<int64_t>(pos);
    ssize_t hrc = ptq_parse_page_header(src + pos, src_len - pos, slots);
    if (hrc != 0) return -1;  // truncated-within-chunk IS corrupt here
    size_t hlen = static_cast<size_t>(slots[0]);
    int64_t psize = slots[3];
    if (psize < 0 || pos + hlen + static_cast<uint64_t>(psize) > src_len) return -1;
    int64_t usize = slots[2] == INT64_MIN ? 0 : slots[2];
    if (usize < 0) return -1;
    const uint8_t* payload = src + pos + hlen;
    size_t payload_len = static_cast<size_t>(psize);
    pos += hlen + payload_len;
    if (n_pages >= max_pages) return -2;
    if (validate_crc && slots[4] != INT64_MIN) {
      // CRC over the page payload EXACTLY as stored (V1: the compressed
      // block; V2: raw rep+def level streams + compressed values) — the
      // parquet-format contract, byte-for-byte what core/chunk._check_crc
      // computes on the staged path.
      err[0] = PTQ_STAGE_CRC;
      clk.start();
      uLong crc = crc32(0L, Z_NULL, 0);
      size_t off = 0;
      while (off < payload_len) {
        size_t take = payload_len - off;
        if (take > (1u << 30)) take = 1u << 30;  // uInt-safe chunks
        crc = crc32(crc, payload + off, static_cast<uInt>(take));
        off += take;
      }
      clk.stop(ST_CRC);
      if (static_cast<uint32_t>(crc) !=
          static_cast<uint32_t>(static_cast<int64_t>(slots[4])))
        return PTQ_E_CRC;
    }
    int64_t* P = pages + n_pages * PT_COLS;
    std::memset(P, 0, PT_COLS * sizeof(int64_t));

    int64_t ptype = slots[1];
    if (ptype == 2) {  // DICTIONARY_PAGE
      // Must be the FIRST page: later routes assume their values_out regions
      // are contiguous, and a mid-chunk dict page would interleave. The spec
      // puts it first; anything else takes the Python walk.
      if (has_dict || n_pages != 0 || slots[10] != 1) return -1;
      has_dict = true;
      const uint8_t* block = payload;
      size_t block_len = payload_len;
      if (codec != 0) {
        err[0] = PTQ_STAGE_DECOMPRESS;
        clk.start();
        int rc = decompress_page(codec, payload, payload_len, scratch,
                                 scratch_cap, static_cast<size_t>(usize));
        clk.stop(ST_DECOMPRESS);
        if (rc != 0) return rc;
      }
      err[0] = PTQ_STAGE_VALUES;
      if (codec != 0) {
        block = scratch;
        block_len = static_cast<size_t>(usize);
      }
      if (values_used + block_len > values_cap) return -5;
      clk.start();
      std::memcpy(values_out + values_used, block, block_len);
      clk.stop(ST_COPY);
      P[PC_KIND] = 1;
      P[PC_N] = slots[11] == INT64_MIN ? 0 : slots[11];  // dict num_values
      P[PC_ENC] = slots[12] == INT64_MIN ? 0 : slots[12];
      P[PC_VOFF] = static_cast<int64_t>(values_used);
      P[PC_VLEN] = static_cast<int64_t>(block_len);
      values_used += block_len;
      n_pages++;
      continue;
    }
    if (ptype == 1) {  // INDEX_PAGE: skipped (parity with the Python walk)
      P[PC_KIND] = 2;
      n_pages++;
      continue;
    }
    if (ptype != 0 && ptype != 3) return -1;

    // -- data page: levels ---------------------------------------------------
    int64_t n, enc;
    const uint8_t* vsrc;      // value stream start
    size_t vlen;              // value stream length
    int64_t non_null;
    if (ptype == 0) {  // DATA_PAGE (V1): block = levels + values, compressed whole
      if (slots[5] != 1) return -1;
      n = slots[6] == INT64_MIN ? 0 : slots[6];
      enc = slots[7] == INT64_MIN ? -1 : slots[7];
      if (n < 0) return -1;
      const uint8_t* block = payload;
      size_t block_len = payload_len;
      if (codec != 0) {
        // level-free PLAIN numeric pages decompress STRAIGHT into their
        // final values_out slot: no scratch bounce, no second multi-MB
        // memcpy (the PLAIN route below detects the in-place block)
        uint8_t* dst = scratch;
        size_t dcap = scratch_cap;
        if (enc == 0 && type_size > 0 && max_rep == 0 && max_def == 0 &&
            values_used + static_cast<uint64_t>(usize) <= values_cap) {
          dst = values_out + values_used;
          dcap = values_cap - values_used;
        }
        err[0] = PTQ_STAGE_DECOMPRESS;
        clk.start();
        int rc = decompress_page(codec, payload, payload_len, dst, dcap,
                                 static_cast<size_t>(usize));
        clk.stop(ST_DECOMPRESS);
        if (rc != 0) return rc;
        block = dst;
        block_len = static_cast<size_t>(usize);
      }
      size_t cur = 0;
      err[0] = PTQ_STAGE_LEVELS;
      if (lvl_total + n > expected_values) return -5;
      clk.start();
      if (max_rep > 0) {
        if (block_len < cur + 4) return -1;
        uint32_t sz;
        std::memcpy(&sz, block + cur, 4);
        if (cur + 4 + sz > block_len) return -1;
        ssize_t used = decode_levels16(block + cur + 4, sz, n, max_rep,
                                       rep_out + lvl_total, -1, nullptr);
        if (used < 0) return -1;
        cur += 4 + sz;
      }
      non_null = n;
      if (max_def > 0) {
        if (block_len < cur + 4) return -1;
        uint32_t sz;
        std::memcpy(&sz, block + cur, 4);
        if (cur + 4 + sz > block_len) return -1;
        int64_t eq = 0;
        ssize_t used = decode_levels16(block + cur + 4, sz, n, max_def,
                                       def_out + lvl_total, max_def, &eq);
        if (used < 0) return -1;
        cur += 4 + sz;
        non_null = eq;
      }
      clk.stop(ST_LEVELS);
      err[0] = PTQ_STAGE_VALUES;
      vsrc = block + cur;
      vlen = block_len - cur;
    } else {  // DATA_PAGE_V2: levels raw, values optionally compressed
      if (slots[14] != 1) return -1;
      n = slots[15] == INT64_MIN ? 0 : slots[15];
      enc = slots[18] == INT64_MIN ? -1 : slots[18];
      if (n < 0) return -1;
      int64_t def_len = slots[19] == INT64_MIN ? 0 : slots[19];
      int64_t rep_len = slots[20] == INT64_MIN ? 0 : slots[20];
      int64_t is_comp = slots[21];  // absent -> compressed (parity: None => true)
      if (def_len < 0 || rep_len < 0 ||
          static_cast<uint64_t>(def_len) + static_cast<uint64_t>(rep_len) >
              payload_len)
        return -1;
      err[0] = PTQ_STAGE_LEVELS;
      if (lvl_total + n > expected_values) return -5;
      clk.start();
      if (max_rep > 0) {
        if (decode_levels16(payload, static_cast<size_t>(rep_len), n, max_rep,
                            rep_out + lvl_total, -1, nullptr) < 0)
          return -1;
      }
      non_null = n;
      if (max_def > 0) {
        int64_t eq = 0;
        if (decode_levels16(payload + rep_len, static_cast<size_t>(def_len), n,
                            max_def, def_out + lvl_total, max_def, &eq) < 0)
          return -1;
        non_null = eq;
      }
      // FLAT columns only: the V2 header's num_nulls must agree with the
      // decoded levels (parity with decode_data_page_v2's cross-check; for
      // repeated columns foreign writers count nulls differently, so the
      // levels are the only trustworthy source there). A mismatch means the
      // header or the level stream is lying — corrupt, not unsupported.
      if (max_rep == 0 && max_def > 0 && slots[16] != INT64_MIN &&
          n - non_null != slots[16])
        return -1;
      clk.stop(ST_LEVELS);
      const uint8_t* vreg = payload + rep_len + def_len;
      size_t vreg_len = payload_len - static_cast<size_t>(rep_len + def_len);
      if (codec != 0 && (is_comp == INT64_MIN || is_comp != 0)) {
        int64_t vexpect = usize - rep_len - def_len;
        if (vexpect < 0) vexpect = 0;
        // V2 keeps levels outside the compressed region, so PLAIN numeric
        // values can always land directly in values_out (see V1 note)
        uint8_t* dst = scratch;
        size_t dcap = scratch_cap;
        if (enc == 0 && type_size > 0 &&
            values_used + static_cast<uint64_t>(vexpect) <= values_cap) {
          dst = values_out + values_used;
          dcap = values_cap - values_used;
        }
        err[0] = PTQ_STAGE_DECOMPRESS;
        clk.start();
        int rc = decompress_page(codec, vreg, vreg_len, dst, dcap,
                                 static_cast<size_t>(vexpect));
        clk.stop(ST_DECOMPRESS);
        if (rc != 0) return rc;
        vsrc = dst;
        vlen = static_cast<size_t>(vexpect);
      } else {
        vsrc = vreg;
        vlen = vreg_len;
      }
      err[0] = PTQ_STAGE_VALUES;
    }

    P[PC_KIND] = 0;
    P[PC_N] = n;
    P[PC_NONNULL] = non_null;
    P[PC_ENC] = enc;
    P[PC_LVLBASE] = lvl_total;
    lvl_total += n;

    // -- route the value stream ---------------------------------------------
    if (enc == 8 || enc == 2) {  // RLE_DICTIONARY / PLAIN_DICTIONARY
      if (!has_dict) return -1;
      if (non_null == 0) {
        P[PC_ROUTE] = 4;
        n_pages++;
        continue;
      }
      if (vlen < 1) return -1;
      int width = vsrc[0];
      if (width > 32) return -1;
      const uint8_t* stream = vsrc + 1;
      size_t stream_len = vlen - 1;
      // Inline prescan: clamp counts so the page contributes exactly
      // non_null outputs; copy bit-packed payloads (only) into packed_out so
      // batch bit offsets are global (mirrors prescan_hybrid's compaction +
      // the staged walk's clamping, pipeline.py _hybrid_tables_of, in one pass).
      const size_t vbytes = (width + 7) / 8;
      size_t spos = 0;
      int64_t produced = 0;
      size_t run0 = runs, pack0 = packed_used;
      err[0] = PTQ_STAGE_PRESCAN;
      clk.start();
      while (produced < non_null) {
        uint64_t header = 0;
        int shift = 0;
        for (;;) {
          if (spos >= stream_len || shift > 63) return -1;
          uint8_t b = stream[spos++];
          if (shift == 63 && (b & 0x7e)) return -1;
          header |= static_cast<uint64_t>(b & 0x7f) << shift;
          if (!(b & 0x80)) break;
          shift += 7;
        }
        if (runs >= max_runs) return -3;
        int64_t take;
        if (header & 1) {
          uint64_t groups = header >> 1;
          if (groups == 0 || groups > (1ull << 40)) return -1;
          uint64_t count = groups * 8;
          uint64_t nbytes = groups * static_cast<uint64_t>(width);
          if (spos + nbytes > stream_len) return -1;
          take = non_null - produced;
          if (static_cast<uint64_t>(take) > count) take = static_cast<int64_t>(count);
          if (packed_used + nbytes > packed_cap) return -5;
          std::memcpy(packed_out + packed_used, stream + spos, nbytes);
          h_is_rle[runs] = 0;
          h_counts[runs] = take;
          h_values[runs] = 0;
          h_byteoff[runs] = static_cast<int64_t>(packed_used);
          packed_used += nbytes;
          spos += nbytes;
        } else {
          uint64_t count = header >> 1;
          if (count == 0 || count > (1ull << 40) || spos + vbytes > stream_len)
            return -1;
          uint64_t v = 0;
          for (size_t i = 0; i < vbytes; i++)
            v |= static_cast<uint64_t>(stream[spos + i]) << (8 * i);
          if (width < 64 && v >= (1ull << width)) return -1;
          spos += vbytes;
          take = non_null - produced;
          if (static_cast<uint64_t>(take) > count) take = static_cast<int64_t>(count);
          h_is_rle[runs] = 1;
          h_counts[runs] = take;
          h_values[runs] = v;
          h_byteoff[runs] = 0;
        }
        runs++;
        produced += take;
      }
      clk.stop(ST_PRESCAN);
      P[PC_ROUTE] = 1;
      P[PC_RUNS] = static_cast<int64_t>(run0);
      P[PC_RUNE] = static_cast<int64_t>(runs);
      P[PC_PACKS] = static_cast<int64_t>(pack0);
      P[PC_PACKE] = static_cast<int64_t>(packed_used);
      P[PC_EXTRA] = width;
    } else if (enc == 5 && delta_nbits != 0) {  // DELTA_BINARY_PACKED
      uint64_t first = 0;
      int64_t total = 0, consumed = 0;
      size_t mini0 = minis;
      // prescan against max_minis - minis remaining slots
      err[0] = PTQ_STAGE_PRESCAN;
      clk.start();
      ssize_t m = ptq_prescan_delta_packed(
          vsrc, vlen, delta_nbits, non_null, d_widths + minis,
          d_bytestart + minis, d_outstart + minis, d_mins + minis,
          max_minis - minis, &first, &total, &consumed);
      clk.stop(ST_PRESCAN);
      if (m == -2) return -4;
      if (m < 0) return -1;
      err[0] = PTQ_STAGE_VALUES;
      // byte starts are relative to the page's stream: rebase into delta_out
      if (delta_used + static_cast<size_t>(consumed) > delta_cap) return -5;
      clk.start();
      std::memcpy(delta_out + delta_used, vsrc, static_cast<size_t>(consumed));
      clk.stop(ST_COPY);
      for (ssize_t i = 0; i < m; i++)
        d_bytestart[mini0 + i] += static_cast<int64_t>(delta_used);
      P[PC_ROUTE] = 2;
      P[PC_MINIS] = static_cast<int64_t>(mini0);
      P[PC_MINIE] = static_cast<int64_t>(mini0 + m);
      P[PC_DSTART] = static_cast<int64_t>(delta_used);
      P[PC_DCONS] = consumed;
      P[PC_EXTRA] = total;
      P[PC_DFIRST] = static_cast<int64_t>(first);
      delta_used += static_cast<size_t>(consumed);
      minis += static_cast<size_t>(m);
    } else if (enc == 0 && type_size > 0) {  // PLAIN numeric
      size_t need = static_cast<size_t>(non_null) * type_size;
      if (vlen < need) return -1;  // "plain payload too short"
      if (values_used + need > values_cap) return -5;
      if (vsrc != values_out + values_used) {  // direct decompress: in place
        clk.start();
        std::memcpy(values_out + values_used, vsrc, need);
        clk.stop(ST_COPY);
      }
      P[PC_ROUTE] = 3;
      P[PC_VOFF] = static_cast<int64_t>(values_used);
      P[PC_VLEN] = static_cast<int64_t>(need);
      values_used += need;
    } else if (enc == 9 && type_size == 4) {  // BYTE_STREAM_SPLIT, 4-byte
      // Ship the page's interleaved streams RAW (route 5): the transpose is
      // pure layout, and the device does it as a reshape+transpose for free
      // — the host never strides over the bytes at all. 8-byte BSS stays
      // host-side below (TPU x64 emulation cannot bitcast u8x8 lanes).
      size_t need = static_cast<size_t>(non_null) * type_size;
      if (vlen < need) return -1;
      if (values_used + need > values_cap) return -5;
      if (vsrc != values_out + values_used) {
        clk.start();
        std::memcpy(values_out + values_used, vsrc, need);
        clk.stop(ST_COPY);
      }
      P[PC_ROUTE] = 5;
      P[PC_VOFF] = static_cast<int64_t>(values_used);
      P[PC_VLEN] = static_cast<int64_t>(need);
      values_used += need;
    } else if (enc == 9 && type_size > 0) {  // BYTE_STREAM_SPLIT, 8-byte
      // De-interleave the byte streams back to PLAIN little-endian layout
      // in one strided pass; the page then rides the PLAIN device route
      // (the transform is pure layout, so doing it here keeps byte-identity
      // with the host decoder for free).
      size_t need = static_cast<size_t>(non_null) * type_size;
      if (vlen < need) return -1;
      if (values_used + need > values_cap) return -5;
      uint8_t* dstv = values_out + values_used;
      const size_t nn = static_cast<size_t>(non_null);
      clk.start();
      for (int b = 0; b < type_size; b++) {
        const uint8_t* sp = vsrc + static_cast<size_t>(b) * nn;
        for (size_t i = 0; i < nn; i++) dstv[i * type_size + b] = sp[i];
      }
      clk.stop(ST_COPY);
      P[PC_ROUTE] = 3;
      P[PC_VOFF] = static_cast<int64_t>(values_used);
      P[PC_VLEN] = static_cast<int64_t>(need);
      values_used += need;
    } else {  // anything else: stream bytes for the Python host decoder
      if (values_used + vlen > values_cap) return -5;
      clk.start();
      std::memcpy(values_out + values_used, vsrc, vlen);
      clk.stop(ST_COPY);
      P[PC_ROUTE] = 0;
      P[PC_VOFF] = static_cast<int64_t>(values_used);
      P[PC_VLEN] = static_cast<int64_t>(vlen);
      values_used += vlen;
    }
    n_pages++;
  }

  totals[0] = lvl_total;
  totals[1] = static_cast<int64_t>(values_used);
  totals[2] = static_cast<int64_t>(packed_used);
  totals[3] = static_cast<int64_t>(delta_used);
  totals[4] = static_cast<int64_t>(runs);
  totals[5] = static_cast<int64_t>(minis);
  totals[6] = has_dict ? 1 : 0;
  totals[7] = 0;
  return static_cast<ssize_t>(n_pages);
}

// ---------------------------------------------------------------------------
// Write-side encoders. Byte-identical to the NumPy reference encoders in
// ops/rle_hybrid.py / ops/delta.py (the roundtrip + conformance suites are
// the oracle); these exist because the encode loops were the write path's
// dominant cost (reference hot loops: hybrid_encoder.go:55-70,
// deltabp_encoder.go:58-115, chunk_writer.go:174-209).
// ---------------------------------------------------------------------------

namespace {

inline bool put_uvarint(uint8_t* out, size_t cap, size_t* pos, uint64_t v) {
  while (v >= 0x80) {
    if (*pos >= cap) return false;
    out[(*pos)++] = static_cast<uint8_t>(v | 0x80);
    v >>= 7;
  }
  if (*pos >= cap) return false;
  out[(*pos)++] = static_cast<uint8_t>(v);
  return true;
}

inline bool put_zigzag(uint8_t* out, size_t cap, size_t* pos, int64_t v) {
  uint64_t u = (static_cast<uint64_t>(v) << 1) ^ static_cast<uint64_t>(v >> 63);
  return put_uvarint(out, cap, pos, u);
}

struct BitWriter {
  uint8_t* out;
  size_t cap;
  size_t pos;
  unsigned __int128 acc;
  int nbits;
};

inline void bw_init(BitWriter* w, uint8_t* out, size_t cap, size_t pos) {
  w->out = out; w->cap = cap; w->pos = pos; w->acc = 0; w->nbits = 0;
}

inline bool bw_push(BitWriter* w, uint64_t v, int width) {
  w->acc |= static_cast<unsigned __int128>(v) << w->nbits;
  w->nbits += width;
  while (w->nbits >= 8) {
    if (w->pos >= w->cap) return false;
    w->out[w->pos++] = static_cast<uint8_t>(w->acc);
    w->acc >>= 8;
    w->nbits -= 8;
  }
  return true;
}

inline bool bw_flush(BitWriter* w) {
  if (w->nbits > 0) {
    if (w->pos >= w->cap) return false;
    w->out[w->pos++] = static_cast<uint8_t>(w->acc);
    w->acc = 0;
    w->nbits = 0;
  }
  return true;
}

// One bit-packed segment: header (groups<<1)|1 then LSB-first payload,
// zero-padding the final partial group (mirrors _emit_bitpacked). The
// element getter is size-generic so the fused encode walk packs uint16
// level streams and uint32 dictionary indices without first widening them
// to uint64 (the widening copy of a 1M-row index column was measurable).
static inline uint64_t he_get(const void* v, int es, int64_t i) {
  switch (es) {
    case 2: return static_cast<const uint16_t*>(v)[i];
    case 4: return static_cast<const uint32_t*>(v)[i];
    default: return static_cast<const uint64_t*>(v)[i];
  }
}

static bool emit_bitpacked_any(const void* v, int es, int64_t n, int width,
                               uint8_t* out, size_t cap, size_t* pos,
                               bool* bad_value) {
  if (n == 0) return true;
  int64_t padded = (n + 7) & ~7ll;
  if (!put_uvarint(out, cap, pos, ((static_cast<uint64_t>(padded) / 8) << 1) | 1))
    return false;
  if (width <= 16) {
    // fast lane for the common widths (levels and dictionary indices):
    // a full group of 8 values occupies exactly `width` bytes, and 8*16
    // bits fit one 128-bit accumulator — pack per GROUP with a single
    // bounds check and byte-store loop instead of per-value bit pushes
    size_t p = *pos;
    if (p + static_cast<size_t>((padded / 8)) * width > cap) return false;
    int64_t full = n & ~7ll;
    const uint64_t lim = 1ull << width;
    for (int64_t g = 0; g < full; g += 8) {
      unsigned __int128 acc = 0;
      uint64_t over = 0;
      for (int k = 0; k < 8; k++) {
        uint64_t x = he_get(v, es, g + k);
        over |= x;
        acc |= static_cast<unsigned __int128>(x) << (k * width);
      }
      if (over >= lim) { *bad_value = true; return false; }
      for (int b = 0; b < width; b++) {
        out[p++] = static_cast<uint8_t>(acc);
        acc >>= 8;
      }
    }
    if (full < n) {  // trailing partial group, zero-padded to 8
      unsigned __int128 acc = 0;
      for (int64_t i = full; i < n; i++) {
        uint64_t x = he_get(v, es, i);
        if (x >= lim) { *bad_value = true; return false; }
        acc |= static_cast<unsigned __int128>(x) << ((i - full) * width);
      }
      for (int b = 0; b < width; b++) {
        out[p++] = static_cast<uint8_t>(acc);
        acc >>= 8;
      }
    }
    *pos = p;
    return true;
  }
  BitWriter w;
  bw_init(&w, out, cap, *pos);
  for (int64_t i = 0; i < n; i++) {
    uint64_t x = he_get(v, es, i);
    if (width < 64 && (x >> width)) { *bad_value = true; return false; }
    if (!bw_push(&w, x, width)) return false;
  }
  for (int64_t i = n; i < padded; i++)
    if (!bw_push(&w, 0, width)) return false;
  if (!bw_flush(&w)) return false;
  *pos = w.pos;
  return true;
}

// Element-size-generic hybrid encode core — the ONE implementation behind
// ptq_hybrid_encode (es=8) and the fused encode walk (es=2/4), so the two
// cannot drift on bytes.
static ssize_t hybrid_encode_any(const void* vals, int es, int64_t n,
                                 int width, uint8_t* out, size_t out_cap) {
  if (width < 0 || width > 64 || n < 0) return -1;
  size_t pos = 0;
  if (n == 0) return 0;
  if (width == 0) {
    if (!put_uvarint(out, out_cap, &pos, static_cast<uint64_t>(n) << 1)) return -2;
    return static_cast<ssize_t>(pos);
  }
  const int vbytes = (width + 7) / 8;
  bool bad = false;
  int64_t i = 0;
  int64_t seg = 0;  // start of the pending bit-packed segment
  while (i < n) {
    int64_t j = i + 1;
    const uint64_t cur = he_get(vals, es, i);
    while (j < n && he_get(vals, es, j) == cur) j++;
    if (j - i >= 8) {
      // 8-align the RLE window so surrounding bit-packed segments stay
      // multiples of 8 values (mid-stream padding would shift the stream)
      int64_t rle_start = (i + 7) & ~7ll;
      int64_t rle_end = j & ~7ll;
      if (rle_end - rle_start >= 8) {
        if (rle_start > seg &&
            !emit_bitpacked_any(static_cast<const uint8_t*>(vals) + seg * es,
                                es, rle_start - seg, width, out, out_cap,
                                &pos, &bad))
          return bad ? -1 : -2;
        if (width < 64 && (cur >> width)) return -1;
        if (!put_uvarint(out, out_cap, &pos,
                         static_cast<uint64_t>(rle_end - rle_start) << 1))
          return -2;
        if (pos + vbytes > out_cap) return -2;
        for (int b = 0; b < vbytes; b++)
          out[pos++] = static_cast<uint8_t>(cur >> (8 * b));
        seg = rle_end;
      }
    }
    i = j;
  }
  if (seg < n &&
      !emit_bitpacked_any(static_cast<const uint8_t*>(vals) + seg * es, es,
                          n - seg, width, out, out_cap, &pos, &bad))
    return bad ? -1 : -2;
  return static_cast<ssize_t>(pos);
}

}  // namespace

// Hybrid RLE/bit-pack encode of uint64 values at `width` bits. 8-aligned
// stretches of >=8 identical values become RLE runs, everything else is
// bit-packed in groups of 8 (mirrors ops/rle_hybrid.py encode_hybrid
// byte-for-byte). Returns bytes written, -1 on a value that does not fit
// the width, -2 if out_cap is too small.
ssize_t ptq_hybrid_encode(const uint64_t* v, int64_t n, int width,
                          uint8_t* out, size_t out_cap) {
  return hybrid_encode_any(v, 8, n, width, out, out_cap);
}

// Re-pack bit-packed groups of 8 values from w_from to w_to bits a value
// (0 <= w_from <= w_to <= 32): the transfer-side widening of a dictionary
// index page that was written before its chunk's dictionary crossed a power
// of two (kernels/pipeline.py _repack_pages_to_width), so that a chunk
// ships at ONE width. src holds n_groups * w_from bytes, dst takes
// n_groups * w_to. Returns bytes written, -1 on bad args. The worker of
// ptq_repack_pages below, which is what the binding calls.
static ssize_t ptq_repack_width(const uint8_t* src, int64_t n_groups, int w_from,
                         int w_to, uint8_t* dst) {
  if (w_from < 0 || w_to > 32 || w_from > w_to || n_groups < 0) return -1;
  const uint64_t mask = (1ull << w_from) - 1;
  uint64_t in_acc = 0, out_acc = 0;
  int in_bits = 0, out_bits = 0;
  uint8_t* dp = dst;
  for (int64_t i = 0, n = n_groups * 8; i < n; i++) {
    while (in_bits < w_from) {
      in_acc |= static_cast<uint64_t>(*src++) << in_bits;
      in_bits += 8;
    }
    out_acc |= (in_acc & mask) << out_bits;
    in_acc >>= w_from;
    in_bits -= w_from;
    out_bits += w_to;
    while (out_bits >= 8) {
      *dp++ = static_cast<uint8_t>(out_acc);
      out_acc >>= 8;
      out_bits -= 8;
    }
  }
  return dp - dst;
}

// ptq_repack_width over the pages of one chunk, in ONE call (a call per page
// hands the GIL back and forth fifty times a chunk, and on a busy pool each
// return waits for it): page p's packed region is packed[ps[p], pe[p]),
// whole groups at widths[p] bits; it lands in dst at w_to bits, copied as it
// is where widths[p] == w_to, skipped where widths[p] == 0 (no payload: the
// caller turns those runs into RLE runs). ns_out (nullable) takes the wall
// nanoseconds spent in here, clocked inside like ptq_chunk_prepare's
// stage_ns: a clock around the call would also count the wait for the GIL
// on return. Returns bytes written, -1 on bad args, -2 if dst_cap is too
// small.
ssize_t ptq_repack_pages(const uint8_t* packed, const int64_t* ps,
                         const int64_t* pe, const int32_t* widths,
                         int64_t n_pages, int w_to, uint8_t* dst,
                         size_t dst_cap, int64_t* ns_out) {
  const int64_t t0 = ns_out ? StageClock::now() : 0;
  size_t pos = 0;
  for (int64_t p = 0; p < n_pages; p++) {
    const int w = widths[p];
    const int64_t len = pe[p] - ps[p];
    if (len < 0 || w < 0 || w > w_to) return -1;
    if (w == 0) continue;
    if (len % w) return -1;
    const size_t out = static_cast<size_t>(len / w) * w_to;
    if (pos + out > dst_cap) return -2;
    if (w == w_to)
      std::memcpy(dst + pos, packed + ps[p], out);
    else if (ptq_repack_width(packed + ps[p], len / w, w, w_to, dst + pos) < 0)
      return -1;
    pos += out;
  }
  if (ns_out) *ns_out = StageClock::now() - t0;
  return static_cast<ssize_t>(pos);
}

// One part (`bits` of every value: 8, 16 or 32) of the delta frame: a plane
// of n_pad * bits / 32 words in which word j holds slots j, j + L, j + 2L, ...
// (L the plane's length, a power of two), slot s in bits [(s / L) * bits,
// +bits) of word s % L. The layout is stated once, in
// kernels/device_ops.py pack_delta_upload; this struct is its writer.
namespace {
struct FramePlane {
  uint32_t* words;
  int bits;
  int log_len;
  uint32_t mask;
  FramePlane(uint32_t* w, int b, int64_t n_pad) : words(w), bits(b), log_len(0) {
    const int64_t len = b ? n_pad * b / 32 : 1;
    while ((int64_t{1} << log_len) < len) log_len++;
    mask = b >= 32 ? ~0u : (1u << b) - 1;
  }
  inline void put(int64_t slot, uint32_t v) const {
    if (bits == 32)
      words[slot] = v;
    else if (bits)
      words[slot & ((int64_t{1} << log_len) - 1)] |= (v & mask)
                                                     << ((slot >> log_len) * bits);
  }
  // n consecutive slots from `slot`, slot + k taking bits [from_bit, +bits)
  // of v[k]: the plane's words in order at one shift, a stretch a wrap.
  inline void store(int64_t slot, const uint32_t* v, int64_t n, int from_bit) const {
    if (!bits) return;
    const int64_t len = int64_t{1} << log_len;
    while (n > 0) {
      const int64_t at = slot & (len - 1);
      const int64_t m = n < len - at ? n : len - at;
      const int sh = static_cast<int>(slot >> log_len) * bits;  // 0 at 32 bits
      uint32_t* w = words + at;
      for (int64_t k = 0; k < m; k++) w[k] |= ((v[k] >> from_bit) & mask) << sh;
      slot += m;
      v += m;
      n -= m;
    }
  }
};
}  // namespace

// The delta frame of one upload (kernels/device_ops.py pack_delta_upload,
// whose NumPy writer is this function's reference, byte for byte): every
// delta of the miniblocks (widths[m] bits each, counts[m] of them end to end
// from bit bit_starts[m] of `stream`, for the slots out_starts[m]...) is
// read once and written again at ONE static width, position by position:
// slot s takes (raw + mins[m] - ref) mod 2^nbits, its low min(width, 32)
// bits in the first plane of dst and the width - 32 bits above them in the
// second. No value of the column is formed: no prefix sum, no first value.
// dst holds n_pad * width / 32 words and is zeroed here, so slots nobody
// writes (a page's first, those past the total) hold 0. width is one of
// 0, 8, 16, 32 and, at nbits 64, 40, 48, 64; n_pad a power of two >= 32.
// ns_out as ptq_repack_pages'. Returns the deltas written, -1 on bad
// arguments (a miniblock outside the stream or the slots), -3 if a shipped
// value does not fit `width` (the caller's frame of reference is wrong).
ssize_t ptq_delta_frame(const uint8_t* stream, size_t stream_len,
                        const uint32_t* widths, const int64_t* bit_starts,
                        const int64_t* out_starts, const int64_t* counts,
                        const uint64_t* mins, int64_t n_minis, int nbits,
                        uint64_t ref, int width, int64_t n_pad, uint32_t* dst,
                        int64_t* ns_out) {
  const int64_t t0 = ns_out ? StageClock::now() : 0;
  if (nbits != 32 && nbits != 64) return -1;
  if (width < 0 || width > nbits || width % 8 || width == 24 || width == 56)
    return -1;
  if (n_pad < 32 || (n_pad & (n_pad - 1))) return -1;
  const int lo_bits = width < 32 ? width : 32;
  const FramePlane lo(dst, lo_bits, n_pad);
  const FramePlane hi(dst + n_pad * lo_bits / 32, width - lo_bits, n_pad);
  std::memset(dst, 0, static_cast<size_t>(n_pad) * width / 8);
  const uint64_t vmask = nbits == 64 ? ~0ull : 0xFFFFFFFFull;
  const uint64_t stream_bits = static_cast<uint64_t>(stream_len) * 8;
  uint64_t seen = 0;
  int64_t written = 0;
  for (int64_t m = 0; m < n_minis; m++) {
    const int w = static_cast<int>(widths[m]);
    const int64_t n = counts[m], s0 = out_starts[m];
    if (w > nbits || n < 0 || s0 < 0 || s0 + n > n_pad || bit_starts[m] < 0)
      return -1;
    uint64_t bit = static_cast<uint64_t>(bit_starts[m]);
    if (bit + static_cast<uint64_t>(n) * w > stream_bits) return -1;
    const uint64_t off = (mins[m] - ref) & vmask;
    const uint64_t wmask = w >= 64 ? ~0ull : (1ull << w) - 1;
    for (int64_t k = 0; k < n; k++, bit += w) {
      uint64_t raw = 0;
      if (w) {
        const size_t byte = bit >> 3;
        const int sh = static_cast<int>(bit & 7);
        if (byte + 8 <= stream_len) {
          raw = xread64(stream + byte) >> sh;
          // the value's last bit is inside the stream (checked above), so
          // the ninth byte is there whenever the value reaches it
          if (sh + w > 64) raw |= static_cast<uint64_t>(stream[byte + 8]) << (64 - sh);
        } else {  // the stream's last bytes: no 8-byte load fits
          uint64_t acc = 0;
          for (size_t i = byte; i < stream_len; i++)
            acc |= static_cast<uint64_t>(stream[i]) << (8 * (i - byte));
          raw = acc >> sh;
        }
        raw &= wmask;
      }
      const uint64_t v = (raw + off) & vmask;
      seen |= v;
      lo.put(s0 + k, static_cast<uint32_t>(v));
      hi.put(s0 + k, static_cast<uint32_t>(v >> 32));
    }
    written += n;
  }
  if (width < 64 && (seen >> width)) return -3;
  if (ns_out) *ns_out = StageClock::now() - t0;
  return static_cast<ssize_t>(written);
}

// The hybrid frame of one upload (kernels/device_ops.py pack_hybrid_upload,
// whose NumPy writer is this function's reference, byte for byte): the
// values of the runs (counts[r] each, end to end from slot 0) written
// position by position at ONE static width. An RLE run (is_rle[r] != 0)
// puts rle_values[r] into each of its slots; a bit-packed run reads its
// counts[r] values of `width` bits from bit bit_starts[r] of `packed` (the
// chunk's bit-packed groups end to end, no headers) — an RLE run's bit_start
// is read by nobody. Slot s takes its value's low lo_bits bits in the first
// plane of dst and the hi_bits above them in the second (each 0, 1, 2, 4, 8,
// 16 or 32; lo_bits + hi_bits >= width, at most 32); a value is cut to
// lo_bits + hi_bits bits. dst holds n_pad * (lo_bits + hi_bits) / 32 words
// and is zeroed here, so slots past the runs' total hold 0. n_pad is a power
// of two >= 32. ns_out as ptq_repack_pages'. Returns the values written, -1
// on bad arguments (a run outside the payload or the slots).
ssize_t ptq_hybrid_frame(const uint8_t* packed, size_t packed_len,
                         const uint8_t* is_rle, const int64_t* counts,
                         const uint32_t* rle_values, const int64_t* bit_starts,
                         int64_t n_runs, int width, int lo_bits, int hi_bits,
                         int64_t n_pad, uint32_t* dst, int64_t* ns_out) {
  const int64_t t0 = ns_out ? StageClock::now() : 0;
  auto part = [](int b) { return b == 0 || (b <= 32 && !(b & (b - 1))); };
  if (width < 0 || width > 32 || !part(lo_bits) || !part(hi_bits) ||
      lo_bits + hi_bits > 32 || lo_bits + hi_bits < width ||
      (hi_bits && !lo_bits))
    return -1;
  if (n_pad < 32 || (n_pad & (n_pad - 1))) return -1;
  const FramePlane lo(dst, lo_bits, n_pad);
  const FramePlane hi(dst + n_pad * lo_bits / 32, hi_bits, n_pad);
  std::memset(dst, 0, static_cast<size_t>(n_pad) * (lo_bits + hi_bits) / 8);
  const uint64_t payload_bits = static_cast<uint64_t>(packed_len) * 8;
  const uint64_t wmask = (1ull << width) - 1;  // width <= 32
  constexpr int64_t kBlock = 1024;  // values unpacked at a time: 4 KiB, in L1
  uint32_t block[kBlock];
  int64_t slot = 0;
  for (int64_t r = 0; r < n_runs; r++) {
    int64_t n = counts[r];
    if (n < 0 || slot + n > n_pad) return -1;
    const bool rle = is_rle[r] != 0;
    uint64_t bit = 0;
    if (rle) {
      for (int64_t k = 0, m = n < kBlock ? n : kBlock; k < m; k++)
        block[k] = rle_values[r];
    } else {
      if (bit_starts[r] < 0) return -1;
      bit = static_cast<uint64_t>(bit_starts[r]);
      if (bit + static_cast<uint64_t>(n) * width > payload_bits) return -1;
    }
    while (n > 0) {
      const int64_t take = n < kBlock ? n : kBlock;
      for (int64_t k = 0; !rle && k < take; k++, bit += width) {
        const size_t byte = bit >> 3;
        const int sh = static_cast<int>(bit & 7);
        uint64_t raw;
        if (byte + 8 <= packed_len) {
          raw = xread64(packed + byte) >> sh;  // sh + width <= 39: one load
        } else {  // the payload's last bytes: no 8-byte load fits
          uint64_t acc = 0;
          for (size_t i = byte; i < packed_len; i++)
            acc |= static_cast<uint64_t>(packed[i]) << (8 * (i - byte));
          raw = acc >> sh;
        }
        block[k] = static_cast<uint32_t>(raw & wmask);
      }
      lo.store(slot, block, take, 0);
      hi.store(slot, block, take, lo_bits);
      slot += take;
      n -= take;
    }
  }
  if (ns_out) *ns_out = StageClock::now() - t0;
  return static_cast<ssize_t>(slot);
}

// DELTA_BINARY_PACKED encode (mirrors ops/delta.py encode_delta
// byte-for-byte, including wrapping min-delta arithmetic and zero-width
// trailing miniblocks). vals is int32[n] or int64[n] by nbits. Returns
// bytes written, -1 bad args, -2 out_cap too small.
ssize_t ptq_delta_encode(const void* vals, int64_t n, int nbits,
                         int64_t block_size, int64_t mini_count,
                         uint8_t* out, size_t out_cap) {
  if (nbits != 32 && nbits != 64) return -1;
  // mini_count capped at 512 like every decoder (and the widths[] buffer)
  if (block_size <= 0 || mini_count <= 0 || mini_count > 512 ||
      block_size % mini_count)
    return -1;
  const int64_t mini_len = block_size / mini_count;
  if (mini_len % 8) return -1;
  const uint64_t mask = (nbits == 64) ? ~0ull : ((1ull << nbits) - 1);
  const int32_t* v32 = (nbits == 32) ? static_cast<const int32_t*>(vals) : nullptr;
  const int64_t* v64 = (nbits == 64) ? static_cast<const int64_t*>(vals) : nullptr;
  auto get = [&](int64_t i) -> uint64_t {
    return (v32 ? static_cast<uint64_t>(static_cast<uint32_t>(v32[i]))
                : static_cast<uint64_t>(v64[i])) & mask;
  };
  size_t pos = 0;
  if (!put_uvarint(out, out_cap, &pos, static_cast<uint64_t>(block_size))) return -2;
  if (!put_uvarint(out, out_cap, &pos, static_cast<uint64_t>(mini_count))) return -2;
  if (!put_uvarint(out, out_cap, &pos, static_cast<uint64_t>(n))) return -2;
  uint64_t first = n ? get(0) : 0;
  int64_t sfirst = static_cast<int64_t>(first);
  if (nbits < 64 && first >= (1ull << (nbits - 1)))
    sfirst = static_cast<int64_t>(first) - (1ll << nbits);
  if (!put_zigzag(out, out_cap, &pos, sfirst)) return -2;
  if (n <= 1) return static_cast<ssize_t>(pos);

  const int64_t n_deltas = n - 1;
  // per-block delta cache: one subtraction per element instead of re-reading
  // both neighbors in every one of the three scans below (min, width, pack)
  uint64_t dstack[4096];
  uint64_t* dheap = nullptr;
  uint64_t* dbuf = dstack;
  if (block_size > 4096) {
    dheap = static_cast<uint64_t*>(malloc(static_cast<size_t>(block_size) * 8));
    if (!dheap) return -2;
    dbuf = dheap;
  }
  for (int64_t bs = 0; bs < n_deltas; bs += block_size) {
    int64_t blen = n_deltas - bs < block_size ? n_deltas - bs : block_size;
    // one pass: deltas into the cache + signed min of the wrapping deltas
    int64_t min_s = 0;
    uint64_t dmin_u = 0;
    {
      bool have = false;
      uint64_t prev = get(bs);
      for (int64_t k = 0; k < blen; k++) {
        uint64_t cur = get(bs + k + 1);
        uint64_t d = (cur - prev) & mask;
        prev = cur;
        dbuf[k] = d;
        int64_t s = static_cast<int64_t>(d);
        if (nbits < 64 && d >= (1ull << (nbits - 1)))
          s = static_cast<int64_t>(d) - (1ll << nbits);
        if (!have || s < min_s) { have = true; min_s = s; dmin_u = d; }
      }
    }
    if (!put_zigzag(out, out_cap, &pos, min_s)) { free(dheap); return -2; }
    // per-miniblock widths, then payloads
    uint8_t widths[512];
    size_t wpos = pos;
    if (pos + static_cast<size_t>(mini_count) > out_cap) { free(dheap); return -2; }
    pos += static_cast<size_t>(mini_count);
    for (int64_t m = 0; m < mini_count; m++) {
      int64_t mstart = m * mini_len;
      int64_t mlen = blen - mstart;
      if (mlen <= 0) { widths[m] = 0; continue; }
      if (mlen > mini_len) mlen = mini_len;
      uint64_t mx = 0;
      for (int64_t k = 0; k < mlen; k++) {
        uint64_t adj = (dbuf[mstart + k] - dmin_u) & mask;
        if (adj > mx) mx = adj;
      }
      int w = 0;
      while (mx) { w++; mx >>= 1; }
      widths[m] = static_cast<uint8_t>(w);
      if (w == 0) continue;
      BitWriter bw;
      bw_init(&bw, out, out_cap, pos);
      for (int64_t k = 0; k < mini_len; k++) {
        uint64_t adj = 0;
        if (k < mlen) adj = (dbuf[mstart + k] - dmin_u) & mask;
        if (!bw_push(&bw, adj, w)) { free(dheap); return -2; }
      }
      if (!bw_flush(&bw)) { free(dheap); return -2; }
      pos = bw.pos;
    }
    for (int64_t m = 0; m < mini_count; m++) out[wpos + m] = widths[m];
  }
  free(dheap);
  return static_cast<ssize_t>(pos);
}

// Dictionary build over an (offsets, data) byte-array column: open-addressed
// FNV-1a hash, first-occurrence unique order (parity with the Python dict /
// CPython-ext builders). Fills indices[n] and firsts[<=max_uniques+1] (row
// of each unique's first occurrence). Returns the unique count, -2 when it
// exceeds max_uniques (dictionary encoding does not pay), -1 bad input /
// allocation failure.
ssize_t ptq_bytes_dict_indices(const char* data, size_t data_len,
                               const int64_t* offsets, int64_t n,
                               int64_t max_uniques, uint32_t* indices,
                               uint32_t* firsts) {
  if (n < 0 || max_uniques < 0) return -1;
  if (n == 0) return 0;
  // table sized for the unique cap, not n: a high-cardinality column bails
  // out early without a giant allocation
  size_t want = static_cast<size_t>(
      (max_uniques + 2) < n ? (max_uniques + 2) : n);
  size_t tsize = 64;
  while (tsize < want * 2) tsize <<= 1;
  uint32_t* table = static_cast<uint32_t*>(malloc(tsize * sizeof(uint32_t)));
  if (!table) return -1;
  std::memset(table, 0xff, tsize * sizeof(uint32_t));  // 0xffffffff = empty
  const size_t tmask = tsize - 1;
  int64_t uniques = 0;
  for (int64_t i = 0; i < n; i++) {
    int64_t off = offsets[i];
    int64_t len = offsets[i + 1] - off;
    if (off < 0 || len < 0 || static_cast<size_t>(off + len) > data_len) {
      free(table);
      return -1;
    }
    const uint8_t* p = reinterpret_cast<const uint8_t*>(data + off);
    uint64_t h = 1469598103934665603ull;
    for (int64_t b = 0; b < len; b++) h = (h ^ p[b]) * 1099511628211ull;
    size_t slot = static_cast<size_t>(h) & tmask;
    for (;;) {
      uint32_t uid = table[slot];
      if (uid == 0xffffffffu) {
        if (uniques >= max_uniques) {  // would exceed the cutoff: no dict
          free(table);
          return -2;
        }
        table[slot] = static_cast<uint32_t>(uniques);
        firsts[uniques] = static_cast<uint32_t>(i);
        indices[i] = static_cast<uint32_t>(uniques);
        uniques++;
        break;
      }
      int64_t fo = offsets[firsts[uid]];
      int64_t flen = offsets[firsts[uid] + 1] - fo;
      if (flen == len && std::memcmp(data + fo, data + off, len) == 0) {
        indices[i] = uid;
        break;
      }
      slot = (slot + 1) & tmask;
    }
  }
  free(table);
  return static_cast<ssize_t>(uniques);
}

// Lexicographic min/max over an (offsets, data) byte-array column.
// out[0]/out[1] = row index of min/max. Returns 0, -1 on bad input / n == 0.
ssize_t ptq_bytes_minmax(const char* data, size_t data_len,
                         const int64_t* offsets, int64_t n, int64_t* out) {
  if (n <= 0) return -1;
  if (offsets[0] < 0 || offsets[1] < offsets[0] ||
      static_cast<size_t>(offsets[1]) > data_len)
    return -1;  // row 0 is the running min/max base: validate it up front
  int64_t mn = 0, mx = 0;
  for (int64_t i = 1; i < n; i++) {
    int64_t io = offsets[i], il = offsets[i + 1] - io;
    if (io < 0 || il < 0 || static_cast<size_t>(io + il) > data_len) return -1;
    {
      int64_t mo = offsets[mn], ml = offsets[mn + 1] - mo;
      int64_t c = std::memcmp(data + io, data + mo, il < ml ? il : ml);
      if (c < 0 || (c == 0 && il < ml)) mn = i;
    }
    {
      int64_t mo = offsets[mx], ml = offsets[mx + 1] - mo;
      int64_t c = std::memcmp(data + io, data + mo, il < ml ? il : ml);
      if (c > 0 || (c == 0 && il > ml)) mx = i;
    }
  }
  out[0] = mn;
  out[1] = mx;
  return 0;
}

// Dictionary probe over numeric bit patterns (NaN payloads dedup by bits).
// elem_size selects uint32/uint64 elements so 32-bit columns probe their
// buffer in place. Same contract as ptq_bytes_dict_indices: fills indices[n]
// and firsts[<=max_uniques+1]; returns unique count, -2 over the cutoff
// (early exit — no O(n log n) sort for high-cardinality columns), -1 error.
ssize_t ptq_u64_dict_indices(const void* v_raw, int elem_size, int64_t n,
                             int64_t max_uniques, uint32_t* indices,
                             uint32_t* firsts) {
  if (n < 0 || max_uniques < 0) return -1;
  if (elem_size != 4 && elem_size != 8) return -1;
  if (n == 0) return 0;
  const uint32_t* v32 =
      elem_size == 4 ? static_cast<const uint32_t*>(v_raw) : nullptr;
  const uint64_t* v = elem_size == 8 ? static_cast<const uint64_t*>(v_raw) : nullptr;
  auto at = [&](int64_t i) -> uint64_t {
    return v ? v[i] : static_cast<uint64_t>(v32[i]);
  };
  size_t want = static_cast<size_t>(
      (max_uniques + 2) < n ? (max_uniques + 2) : n);
  size_t tsize = 64;
  while (tsize < want * 2) tsize <<= 1;
  uint32_t* table = static_cast<uint32_t*>(malloc(tsize * sizeof(uint32_t)));
  if (!table) return -1;
  std::memset(table, 0xff, tsize * sizeof(uint32_t));
  const size_t tmask = tsize - 1;
  int64_t uniques = 0;
  for (int64_t i = 0; i < n; i++) {
    uint64_t x = at(i);
    uint64_t h = x * 0x9e3779b97f4a7c15ull;
    h ^= h >> 29;
    size_t slot = static_cast<size_t>(h) & tmask;
    for (;;) {
      uint32_t uid = table[slot];
      if (uid == 0xffffffffu) {
        if (uniques >= max_uniques) {  // would exceed the cutoff: no dict
          free(table);
          return -2;
        }
        table[slot] = static_cast<uint32_t>(uniques);
        firsts[uniques] = static_cast<uint32_t>(i);
        indices[i] = static_cast<uint32_t>(uniques);
        uniques++;
        break;
      }
      if (at(firsts[uid]) == x) {
        indices[i] = uid;
        break;
      }
      slot = (slot + 1) & tmask;
    }
  }
  free(table);
  return static_cast<ssize_t>(uniques);
}

// ---------------------------------------------------------------------------
// ptq_chunk_encode: the fused whole-chunk ENCODE walk (the write-side
// inverse of ptq_chunk_prepare). Page split -> def-level hybrid pack ->
// value-stream encode -> block compression -> compact-Thrift page framing,
// all in one GIL-free call; every byte identical to the staged Python
// encoder (sink/encoder.py encode_chunk), which remains the fallback rung
// and the error-semantics oracle.
// ---------------------------------------------------------------------------

namespace {

// Minimal compact-Thrift writer for PageHeader framing (the write twin of
// ptq_parse_page_header). Field ids here are small and ascending, so the
// short-form field header (delta << 4 | wire) always applies.
struct ThriftW {
  uint8_t* out;
  size_t cap;
  size_t pos;
  int last_fid;
  bool ok;
};

inline void th_init(ThriftW* w, uint8_t* out, size_t cap, size_t pos) {
  w->out = out; w->cap = cap; w->pos = pos; w->last_fid = 0; w->ok = true;
}

inline void th_byte(ThriftW* w, uint8_t b) {
  if (w->pos >= w->cap) { w->ok = false; return; }
  w->out[w->pos++] = b;
}

inline void th_field(ThriftW* w, int fid, int wire) {
  th_byte(w, static_cast<uint8_t>(((fid - w->last_fid) << 4) | wire));
  w->last_fid = fid;
}

inline void th_i32(ThriftW* w, int fid, int64_t v) {
  th_field(w, fid, 0x05);  // CT_I32
  if (!w->ok) return;
  if (!put_zigzag(w->out, w->cap, &w->pos, v)) w->ok = false;
}

inline void th_bool(ThriftW* w, int fid, bool v) {
  th_field(w, fid, v ? 0x01 : 0x02);  // value rides the field header
}

inline void th_stop(ThriftW* w) { th_byte(w, 0x00); }

// Compress one raw block into dst. Returns compressed size, -1 unknown
// codec, -5 dst too small / deflate failure (retryable capacity).
ssize_t compress_block_enc(int codec, const uint8_t* raw, size_t raw_len,
                           uint8_t* dst, size_t dst_cap) {
  if (codec == 0) {
    if (raw_len > dst_cap) return -5;
    std::memcpy(dst, raw, raw_len);
    return static_cast<ssize_t>(raw_len);
  }
  if (codec == 1) {
    ssize_t n = ptq_snappy_compress(reinterpret_cast<const char*>(raw),
                                    raw_len, reinterpret_cast<char*>(dst),
                                    dst_cap);
    return n < 0 ? -5 : n;
  }
  if (codec == 2) {
    // the exact parameters CPython's zlib.compressobj(wbits=31) resolves
    // to (default level/memLevel/strategy); both link the same zlib, so
    // the stream — gzip header included — is byte-identical to _Gzip
    z_stream s;
    std::memset(&s, 0, sizeof(s));
    if (deflateInit2(&s, Z_DEFAULT_COMPRESSION, Z_DEFLATED, 31, 8,
                     Z_DEFAULT_STRATEGY) != Z_OK)
      return -5;
    s.next_in = const_cast<Bytef*>(raw);
    s.avail_in = static_cast<uInt>(raw_len);
    s.next_out = dst;
    s.avail_out = static_cast<uInt>(dst_cap);
    int rc = deflate(&s, Z_FINISH);
    ssize_t n = static_cast<ssize_t>(s.total_out);
    deflateEnd(&s);
    return rc == Z_STREAM_END ? n : -5;
  }
  return -1;
}

// stage_ns slots for the encode walk
enum {
  EN_LEVELS = 0,
  EN_VALUES = 1,
  EN_COMPRESS = 2,
  EN_FRAME = 3,
  EN_CRC = 4,
};

}  // namespace

// Standalone gzip compress with the exact parameters the fused encode walk
// uses — exported so the Python side can PROBE byte-identity against
// zlib.compressobj(wbits=31) once at startup (a CPython linked against a
// different zlib build must keep GZIP on the staged encoder). Returns
// compressed size or -1.
ssize_t ptq_gzip_compress(const uint8_t* src, size_t src_len, uint8_t* dst,
                          size_t dst_cap) {
  ssize_t n = compress_block_enc(2, src, src_len, dst, dst_cap);
  return n < 0 ? -1 : n;
}

ssize_t ptq_chunk_encode(
    int route, const uint8_t* values, size_t values_len,
    const int64_t* ba_offsets, int64_t nv, int type_size, int dict_width,
    const uint8_t* dict_raw, size_t dict_raw_len, int64_t dict_num,
    const uint16_t* def_levels, int64_t num_entries, int max_def, int codec,
    int dpv, int with_crc, int64_t per_page, uint8_t* out, size_t out_cap,
    uint8_t* scratch, size_t scratch_cap, int64_t* pages, size_t max_pages,
    int64_t* totals, int64_t* stage_ns, int64_t* err_info) {
  StageClock clk{stage_ns, 0};
  int64_t page_idx = 0;
#define ENC_FAIL(code, stage_)                         \
  do {                                                 \
    if (err_info) {                                    \
      err_info[0] = (stage_);                          \
      err_info[1] = page_idx;                          \
      err_info[2] = 0;                                 \
      err_info[3] = 0;                                 \
    }                                                  \
    return (code);                                     \
  } while (0)

  if (route < 0 || route > 4 || (codec != 0 && codec != 1 && codec != 2) ||
      (dpv != 1 && dpv != 2) || per_page < 1 || num_entries < 0 || nv < 0 ||
      max_def < 0 || (max_def > 0 && def_levels == nullptr) ||
      (max_def == 0 && nv != num_entries))
    ENC_FAIL(PTQ_E_CORRUPT, PTQ_ENC_STAGE_SPLIT);
  if (route == 0 && (type_size < 1 || type_size > 4096))
    ENC_FAIL(PTQ_E_CORRUPT, PTQ_ENC_STAGE_SPLIT);
  if (route == 3 && type_size != 4 && type_size != 8)
    ENC_FAIL(PTQ_E_CORRUPT, PTQ_ENC_STAGE_SPLIT);
  if (route == 4 && type_size != 2)
    ENC_FAIL(PTQ_E_CORRUPT, PTQ_ENC_STAGE_SPLIT);
  if (route == 2 && (dict_width < 0 || dict_width > 32))
    ENC_FAIL(PTQ_E_CORRUPT, PTQ_ENC_STAGE_SPLIT);
  if (route == 1) {
    if (ba_offsets == nullptr || ba_offsets[0] != 0 ||
        static_cast<size_t>(ba_offsets[nv]) > values_len)
      ENC_FAIL(PTQ_E_CORRUPT, PTQ_ENC_STAGE_SPLIT);
  } else {
    size_t es = route == 2 ? 4 : static_cast<size_t>(type_size);
    if (static_cast<size_t>(nv) * es > values_len)
      ENC_FAIL(PTQ_E_CORRUPT, PTQ_ENC_STAGE_SPLIT);
  }

  // scratch splits into a raw-page half and a compressed half: the raw
  // block assembles first (levels + values), then compresses, then the
  // header (whose varints need the compressed size) frames into `out`.
  uint8_t* raw_buf = scratch;
  size_t raw_cap = scratch_cap / 2;
  uint8_t* comp_buf = scratch + raw_cap;
  size_t comp_cap = scratch_cap - raw_cap;

  size_t pos = 0;
  int64_t uncompressed_total = 0;
  int64_t dict_off = -1;
  const int def_width = level_bit_width(max_def);

  // -- leading dictionary page ----------------------------------------------
  if (route == 2 && dict_num > 0) {
    clk.start();
    ssize_t comp = compress_block_enc(codec, dict_raw, dict_raw_len,
                                      comp_buf, comp_cap);
    if (comp < 0) ENC_FAIL(comp == -1 ? PTQ_E_CORRUPT : PTQ_E_CAPACITY,
                           PTQ_ENC_STAGE_COMPRESS);
    clk.stop(EN_COMPRESS);
    uint32_t crc = 0;
    if (with_crc) {
      crc = static_cast<uint32_t>(crc32(0, comp_buf, static_cast<uInt>(comp)));
      clk.stop(EN_CRC);
    }
    ThriftW w;
    th_init(&w, out, out_cap, pos);
    th_i32(&w, 1, 2);                                 // type = DICTIONARY_PAGE
    th_i32(&w, 2, static_cast<int64_t>(dict_raw_len));  // uncompressed size
    th_i32(&w, 3, comp);                              // compressed size
    if (with_crc) th_i32(&w, 4, static_cast<int32_t>(crc));
    th_field(&w, 7, 0x0C);                            // dictionary_page_header
    w.last_fid = 0;
    th_i32(&w, 1, dict_num);
    th_i32(&w, 2, 0);                                 // encoding = PLAIN
    th_bool(&w, 3, false);                            // is_sorted
    th_stop(&w);
    w.last_fid = 7;
    th_stop(&w);
    if (!w.ok || w.pos + static_cast<size_t>(comp) > out_cap)
      ENC_FAIL(PTQ_E_CAPACITY, PTQ_ENC_STAGE_FRAME);
    size_t hdr_len = w.pos - pos;
    std::memcpy(out + w.pos, comp_buf, static_cast<size_t>(comp));
    dict_off = static_cast<int64_t>(pos);
    pos = w.pos + static_cast<size_t>(comp);
    uncompressed_total +=
        static_cast<int64_t>(hdr_len) + static_cast<int64_t>(dict_raw_len);
    clk.stop(EN_FRAME);
    totals[5] = static_cast<int64_t>(hdr_len) + comp;
  } else {
    totals[5] = 0;
  }
  const int64_t data_off = static_cast<int64_t>(pos);

  // -- page split (mirrors _split_pages for flat columns) --------------------
  const int64_t n = num_entries;
  int64_t vpos = 0;  // non-null value cursor
  int64_t a = 0;
  bool first = true;
  while (first || a < n) {
    first = false;
    int64_t b = n;
    if (n > per_page) {
      b = a + per_page;
      if (b > n) b = n;
    }
    // per-page non-null count
    int64_t nn;
    if (max_def > 0) {
      clk.start();
      nn = 0;
      for (int64_t i = a; i < b; i++) nn += (def_levels[i] == max_def);
      clk.stop(EN_LEVELS);
    } else {
      nn = b - a;
    }
    if (vpos + nn > nv) ENC_FAIL(PTQ_E_CORRUPT, PTQ_ENC_STAGE_SPLIT);

    // -- assemble the raw block into raw_buf --------------------------------
    size_t raw_pos = 0;
    size_t def_block_len = 0;
    if (max_def > 0) {
      clk.start();
      if (dpv == 1) {
        if (raw_pos + 4 > raw_cap) ENC_FAIL(PTQ_E_CAPACITY, PTQ_ENC_STAGE_LEVELS);
        raw_pos += 4;  // back-patched length prefix
      }
      ssize_t ln = hybrid_encode_any(def_levels + a, 2, b - a, def_width,
                                     raw_buf + raw_pos, raw_cap - raw_pos);
      if (ln < 0) ENC_FAIL(ln == -1 ? PTQ_E_CORRUPT : PTQ_E_CAPACITY,
                           PTQ_ENC_STAGE_LEVELS);
      def_block_len = static_cast<size_t>(ln);
      if (dpv == 1) {
        uint32_t l32 = static_cast<uint32_t>(def_block_len);
        raw_buf[raw_pos - 4] = static_cast<uint8_t>(l32);
        raw_buf[raw_pos - 3] = static_cast<uint8_t>(l32 >> 8);
        raw_buf[raw_pos - 2] = static_cast<uint8_t>(l32 >> 16);
        raw_buf[raw_pos - 1] = static_cast<uint8_t>(l32 >> 24);
        def_block_len += 4;  // v1 counts the prefix inside the block
      }
      raw_pos += static_cast<size_t>(ln);
      clk.stop(EN_LEVELS);
    }
    size_t values_start = raw_pos;
    clk.start();
    if (route == 0) {
      size_t nbytes = static_cast<size_t>(nn) * type_size;
      if (raw_pos + nbytes > raw_cap) ENC_FAIL(PTQ_E_CAPACITY, PTQ_ENC_STAGE_VALUES);
      std::memcpy(raw_buf + raw_pos, values + vpos * type_size, nbytes);
      raw_pos += nbytes;
    } else if (route == 1) {
      for (int64_t i = vpos; i < vpos + nn; i++) {
        int64_t off = ba_offsets[i];
        int64_t len = ba_offsets[i + 1] - off;
        if (len < 0 || off < 0 ||
            static_cast<size_t>(off + len) > values_len)
          ENC_FAIL(PTQ_E_CORRUPT, PTQ_ENC_STAGE_VALUES);
        if (raw_pos + 4 + static_cast<size_t>(len) > raw_cap)
          ENC_FAIL(PTQ_E_CAPACITY, PTQ_ENC_STAGE_VALUES);
        uint32_t l32 = static_cast<uint32_t>(len);
        raw_buf[raw_pos++] = static_cast<uint8_t>(l32);
        raw_buf[raw_pos++] = static_cast<uint8_t>(l32 >> 8);
        raw_buf[raw_pos++] = static_cast<uint8_t>(l32 >> 16);
        raw_buf[raw_pos++] = static_cast<uint8_t>(l32 >> 24);
        std::memcpy(raw_buf + raw_pos, values + off, static_cast<size_t>(len));
        raw_pos += static_cast<size_t>(len);
      }
    } else if (route == 2) {
      if (raw_pos + 1 > raw_cap) ENC_FAIL(PTQ_E_CAPACITY, PTQ_ENC_STAGE_VALUES);
      raw_buf[raw_pos++] = static_cast<uint8_t>(dict_width);
      ssize_t ln = hybrid_encode_any(
          reinterpret_cast<const uint32_t*>(values) + vpos, 4, nn, dict_width,
          raw_buf + raw_pos, raw_cap - raw_pos);
      if (ln < 0) ENC_FAIL(ln == -1 ? PTQ_E_CORRUPT : PTQ_E_CAPACITY,
                           PTQ_ENC_STAGE_VALUES);
      raw_pos += static_cast<size_t>(ln);
    } else if (route == 4) {
      // BOOLEAN RLE: hybrid stream at width 1 behind a 4-byte LE length
      // prefix (the prefix is part of the VALUE encoding, so unlike def
      // levels it stays in BOTH page versions — ops/levels.py
      // encode_levels_v1 is the byte oracle)
      if (raw_pos + 4 > raw_cap) ENC_FAIL(PTQ_E_CAPACITY, PTQ_ENC_STAGE_VALUES);
      raw_pos += 4;  // back-patched length prefix
      ssize_t ln = hybrid_encode_any(
          reinterpret_cast<const uint16_t*>(values) + vpos, 2, nn, 1,
          raw_buf + raw_pos, raw_cap - raw_pos);
      if (ln < 0) ENC_FAIL(ln == -1 ? PTQ_E_CORRUPT : PTQ_E_CAPACITY,
                           PTQ_ENC_STAGE_VALUES);
      uint32_t l32 = static_cast<uint32_t>(ln);
      raw_buf[raw_pos - 4] = static_cast<uint8_t>(l32);
      raw_buf[raw_pos - 3] = static_cast<uint8_t>(l32 >> 8);
      raw_buf[raw_pos - 2] = static_cast<uint8_t>(l32 >> 16);
      raw_buf[raw_pos - 1] = static_cast<uint8_t>(l32 >> 24);
      raw_pos += static_cast<size_t>(ln);
    } else {  // route 3: DELTA_BINARY_PACKED, one stream per page
      ssize_t ln = ptq_delta_encode(values + vpos * type_size, nn,
                                    type_size * 8, 128, 4,
                                    raw_buf + raw_pos, raw_cap - raw_pos);
      if (ln < 0) ENC_FAIL(ln == -1 ? PTQ_E_CORRUPT : PTQ_E_CAPACITY,
                           PTQ_ENC_STAGE_VALUES);
      raw_pos += static_cast<size_t>(ln);
    }
    clk.stop(EN_VALUES);
    size_t values_raw_len = raw_pos - values_start;

    // -- compress ------------------------------------------------------------
    clk.start();
    ssize_t comp;
    size_t block_len;   // stored block size
    size_t unc_size;    // header's uncompressed_page_size
    if (dpv == 1) {
      comp = compress_block_enc(codec, raw_buf, raw_pos, comp_buf, comp_cap);
      if (comp < 0) ENC_FAIL(comp == -1 ? PTQ_E_CORRUPT : PTQ_E_CAPACITY,
                             PTQ_ENC_STAGE_COMPRESS);
      block_len = static_cast<size_t>(comp);
      unc_size = raw_pos;
    } else {
      // v2: level stream stored RAW ahead of the compressed values block
      comp = compress_block_enc(codec, raw_buf + values_start, values_raw_len,
                                comp_buf, comp_cap);
      if (comp < 0) ENC_FAIL(comp == -1 ? PTQ_E_CORRUPT : PTQ_E_CAPACITY,
                             PTQ_ENC_STAGE_COMPRESS);
      block_len = def_block_len + static_cast<size_t>(comp);
      unc_size = def_block_len + values_raw_len;
    }
    clk.stop(EN_COMPRESS);
    uint32_t crc = 0;
    if (with_crc) {
      if (dpv == 1) {
        crc = static_cast<uint32_t>(
            crc32(0, comp_buf, static_cast<uInt>(comp)));
      } else {
        crc = static_cast<uint32_t>(
            crc32(0, raw_buf, static_cast<uInt>(def_block_len)));
        crc = static_cast<uint32_t>(
            crc32(crc, comp_buf, static_cast<uInt>(comp)));
      }
      clk.stop(EN_CRC);
    }

    // -- frame the PageHeader and copy the block -----------------------------
    if (page_idx >= static_cast<int64_t>(max_pages)) return PTQ_E_PAGES_FULL;
    int encoding =
        route == 2 ? 8 : (route == 3 ? 5 : (route == 4 ? 3 : 0));
    ThriftW w;
    th_init(&w, out, out_cap, pos);
    th_i32(&w, 1, dpv == 1 ? 0 : 3);                 // type
    th_i32(&w, 2, static_cast<int64_t>(unc_size));   // uncompressed size
    th_i32(&w, 3, static_cast<int64_t>(block_len));  // compressed size
    if (with_crc) th_i32(&w, 4, static_cast<int32_t>(crc));
    if (dpv == 1) {
      th_field(&w, 5, 0x0C);  // data_page_header
      w.last_fid = 0;
      th_i32(&w, 1, b - a);   // num_values (level entries)
      th_i32(&w, 2, encoding);
      th_i32(&w, 3, 3);       // definition_level_encoding = RLE
      th_i32(&w, 4, 3);       // repetition_level_encoding = RLE
      th_stop(&w);
      w.last_fid = 5;
    } else {
      th_field(&w, 8, 0x0C);  // data_page_header_v2
      w.last_fid = 0;
      th_i32(&w, 1, b - a);             // num_values
      th_i32(&w, 2, (b - a) - nn);      // num_nulls
      th_i32(&w, 3, b - a);             // num_rows (flat: = entries)
      th_i32(&w, 4, encoding);
      th_i32(&w, 5, static_cast<int64_t>(def_block_len));
      th_i32(&w, 6, 0);                 // repetition_levels_byte_length
      th_bool(&w, 7, true);             // is_compressed
      th_stop(&w);
      w.last_fid = 8;
    }
    th_stop(&w);
    if (!w.ok || w.pos + block_len > out_cap)
      ENC_FAIL(PTQ_E_CAPACITY, PTQ_ENC_STAGE_FRAME);
    size_t hdr_len = w.pos - pos;
    if (dpv == 1) {
      std::memcpy(out + w.pos, comp_buf, block_len);
    } else {
      std::memcpy(out + w.pos, raw_buf, def_block_len);
      std::memcpy(out + w.pos + def_block_len, comp_buf,
                  static_cast<size_t>(comp));
    }
    int64_t* row = pages + page_idx * 8;
    row[0] = static_cast<int64_t>(pos);
    row[1] = static_cast<int64_t>(hdr_len + block_len);
    row[2] = static_cast<int64_t>(hdr_len);
    row[3] = b - a;
    row[4] = nn;
    row[5] = static_cast<int64_t>(unc_size);
    row[6] = 0;
    row[7] = 0;
    pos = w.pos + block_len;
    uncompressed_total +=
        static_cast<int64_t>(hdr_len) + static_cast<int64_t>(unc_size);
    clk.stop(EN_FRAME);
    page_idx++;
    vpos += nn;
    a = b;
  }
  if (max_def == 0 && vpos != nv) ENC_FAIL(PTQ_E_CORRUPT, PTQ_ENC_STAGE_SPLIT);
  totals[0] = static_cast<int64_t>(pos);
  totals[1] = uncompressed_total;
  totals[2] = page_idx;
  totals[3] = dict_off;
  totals[4] = data_off;
  totals[6] = 0;
  totals[7] = 0;
#undef ENC_FAIL
  return static_cast<ssize_t>(page_idx);
}

}  // extern "C"
