#include "crc32c.h"

namespace dtf {
namespace {

// Slice-by-8 tables, generated at first use (thread-safe via static init).
struct Tables {
  uint32_t t[8][256];
  Tables() {
    constexpr uint32_t kPoly = 0x82f63b78u;  // reflected CRC32-C polynomial
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int k = 0; k < 8; ++k) c = (c & 1) ? (kPoly ^ (c >> 1)) : (c >> 1);
      t[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = t[0][i];
      for (int s = 1; s < 8; ++s) {
        c = t[0][c & 0xff] ^ (c >> 8);
        t[s][i] = c;
      }
    }
  }
};

const Tables& tables() {
  static const Tables kTables;
  return kTables;
}

#if defined(__x86_64__)
// Hardware path: the SSE4.2 crc32 instruction computes exactly the
// Castagnoli polynomial.  Compiled with a per-function target attribute so
// the binary stays runnable on pre-SSE4.2 CPUs; dispatched once at startup
// via __builtin_cpu_supports.  Several times the slice-by-8 table path,
// under which CRC verification is a large share of record-reader time.
__attribute__((target("sse4.2")))
uint32_t crc32c_hw(uint32_t crc, const void* data, size_t n) {
  const uint8_t* p = static_cast<const uint8_t*>(data);
  uint64_t c = ~crc;
  while (n > 0 && (reinterpret_cast<uintptr_t>(p) & 7u) != 0) {
    c = __builtin_ia32_crc32qi(static_cast<uint32_t>(c), *p++);
    --n;
  }
  while (n >= 8) {
    uint64_t w;
    __builtin_memcpy(&w, p, 8);
    c = __builtin_ia32_crc32di(c, w);
    p += 8;
    n -= 8;
  }
  while (n > 0) {
    c = __builtin_ia32_crc32qi(static_cast<uint32_t>(c), *p++);
    --n;
  }
  return ~static_cast<uint32_t>(c);
}

bool have_sse42() {
  __builtin_cpu_init();
  return __builtin_cpu_supports("sse4.2");
}
#endif  // __x86_64__

}  // namespace

uint32_t crc32c_sw(uint32_t crc, const void* data, size_t n);

uint32_t crc32c(uint32_t crc, const void* data, size_t n) {
#if defined(__x86_64__)
  static const bool hw = have_sse42();
  if (hw) return crc32c_hw(crc, data, n);
#endif
  return crc32c_sw(crc, data, n);
}

uint32_t crc32c_sw(uint32_t crc, const void* data, size_t n) {
  const auto& tb = tables();
  const uint8_t* p = static_cast<const uint8_t*>(data);
  crc = ~crc;
  // Process unaligned prefix byte-wise.
  while (n > 0 && (reinterpret_cast<uintptr_t>(p) & 7u) != 0) {
    crc = tb.t[0][(crc ^ *p++) & 0xff] ^ (crc >> 8);
    --n;
  }
  // Slice-by-8 main loop.
  while (n >= 8) {
    uint64_t w;
    __builtin_memcpy(&w, p, 8);
    w ^= crc;
    crc = tb.t[7][w & 0xff] ^ tb.t[6][(w >> 8) & 0xff] ^
          tb.t[5][(w >> 16) & 0xff] ^ tb.t[4][(w >> 24) & 0xff] ^
          tb.t[3][(w >> 32) & 0xff] ^ tb.t[2][(w >> 40) & 0xff] ^
          tb.t[1][(w >> 48) & 0xff] ^ tb.t[0][(w >> 56) & 0xff];
    p += 8;
    n -= 8;
  }
  while (n > 0) {
    crc = tb.t[0][(crc ^ *p++) & 0xff] ^ (crc >> 8);
    --n;
  }
  return ~crc;
}

}  // namespace dtf
