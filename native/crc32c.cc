// crc32c (Castagnoli, reflected poly 0x82F63B78) with runtime HW dispatch —
// the role of the reference's src/common/crc32c_intel_fast.c / crc32c_aarch64.c
// per-arch impls behind ceph_crc32c (Checksummer, bufferlist cached crcs).
// Software path: slice-by-8 tables.  HW path: SSE4.2 crc32 instruction.

#include <stddef.h>
#include <stdint.h>
#include <string.h>

#if defined(__x86_64__)
#include <nmmintrin.h>
#endif

static uint32_t T[8][256];
static int t_init = 0;
static int have_sse42 = 0;

// Called once from ct_init() (which Python invokes under a lock) so the
// lazy path below never races; kept lazy too for direct C users.
extern "C" void ct_crc32c_init(void) {
  if (t_init) return;
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t c = i;
    for (int j = 0; j < 8; j++) c = (c & 1) ? (c >> 1) ^ 0x82F63B78u : c >> 1;
    T[0][i] = c;
  }
  for (uint32_t i = 0; i < 256; i++)
    for (int s = 1; s < 8; s++)
      T[s][i] = (T[s - 1][i] >> 8) ^ T[0][T[s - 1][i] & 0xff];
#if defined(__x86_64__)
  have_sse42 = __builtin_cpu_supports("sse4.2") ? 1 : 0;
#endif
  t_init = 1;
}

static uint32_t crc32c_sw(uint32_t crc, const uint8_t* p, size_t len) {
  crc = ~crc;
  while (len >= 8) {
    uint64_t w;
    memcpy(&w, p, 8);
    w ^= crc;
    crc = T[7][w & 0xff] ^ T[6][(w >> 8) & 0xff] ^ T[5][(w >> 16) & 0xff] ^
          T[4][(w >> 24) & 0xff] ^ T[3][(w >> 32) & 0xff] ^
          T[2][(w >> 40) & 0xff] ^ T[1][(w >> 48) & 0xff] ^ T[0][w >> 56];
    p += 8;
    len -= 8;
  }
  while (len--) crc = (crc >> 8) ^ T[0][(crc ^ *p++) & 0xff];
  return ~crc;
}

#if defined(__x86_64__)
__attribute__((target("sse4.2"))) static uint32_t crc32c_hw(uint32_t crc,
                                                            const uint8_t* p,
                                                            size_t len) {
  uint64_t c = ~crc;
  while (len >= 8) {
    uint64_t w;
    memcpy(&w, p, 8);
    c = _mm_crc32_u64(c, w);
    p += 8;
    len -= 8;
  }
  uint32_t c32 = (uint32_t)c;
  while (len--) c32 = _mm_crc32_u8(c32, *p++);
  return ~c32;
}
#endif

extern "C" uint32_t ct_crc32c(uint32_t crc, const uint8_t* data, size_t len) {
  ct_crc32c_init();
#if defined(__x86_64__)
  if (have_sse42) return crc32c_hw(crc, data, len);
#endif
  return crc32c_sw(crc, data, len);
}

// CRC-32C of rows given by address, with an optional copy, in one call:
// for each of n_ops groups, the CRC-32C of its k src rows, then its m par
// rows are copied to dst and their CRC-32C taken, into
// out[group * (k + m) + row] -- src rows first (an encode's carve: data
// rows, then the parity it copies out of the launch buffer, the order a
// shard's digest list has).  Every digest starts from the standard
// initial value.  Row addresses come flattened group-major (src:
// n_ops * k, par / dst: n_ops * m); every row of group i is lens[i]
// bytes.  dst NULL sums the par rows where they lie and copies nothing.
// The copy goes in blocks that the sum then reads while they are in
// cache, so each copied byte is read from memory once.
extern "C" void ct_crc32c_rows(const uint8_t* const* src,
                               const uint8_t* const* par, uint8_t* const* dst,
                               const uint64_t* lens, int n_ops, int k, int m,
                               uint32_t* out) {
  const size_t block = 32 << 10;
  for (int i = 0; i < n_ops; i++) {
    const size_t len = (size_t)lens[i];
    uint32_t* o = out + (size_t)i * (k + m);
    for (int r = 0; r < k; r++) o[r] = ct_crc32c(0, src[(size_t)i * k + r], len);
    for (int r = 0; r < m; r++) {
      const uint8_t* p = par[(size_t)i * m + r];
      if (dst == NULL) {
        o[k + r] = ct_crc32c(0, p, len);
        continue;
      }
      uint8_t* d = dst[(size_t)i * m + r];
      uint32_t c = 0;
      for (size_t off = 0; off < len; off += block) {
        const size_t n = len - off < block ? len - off : block;
        memcpy(d + off, p + off, n);
        c = ct_crc32c(c, d + off, n);
      }
      o[k + r] = c;
    }
  }
}
