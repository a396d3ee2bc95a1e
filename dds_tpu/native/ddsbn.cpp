// ddsbn: native host-side big-number modular arithmetic for dds_tpu.
//
// The framework's native runtime component — the counterpart of the
// closed-source Java crypto jar the reference depends on (`hlib.hj.mlib`,
// see lib/README.txt:1 and utils/SJHomoLibProvider.scala:33-101): all
// host-side Paillier/RSA hot math (client-side encrypt, CRT decrypt, CPU
// replica-side folds) runs here instead of interpreter big-ints. The TPU
// Pallas kernels (ops/mont_mxu.py) remain the data-plane compute path;
// this library serves the principals that hold private keys and hosts
// without an accelerator.
//
// Representation: little-endian arrays of 64-bit words, L words per
// number. All moduli must be odd (Montgomery). Python computes the
// Montgomery constants (n0inv = -n^-1 mod 2^64, R^2 mod n, R^K fixups)
// with big-int ease and passes them in; C++ does only fixed-width CIOS.
//
// CIOS bound audit (standard): inputs canonical < n < 2^(64L); after each
// outer step t < 2n; final t fits L+1 words with t[L] in {0,1}; one
// conditional subtract returns the canonical residue.

#include <cstdint>
#include <cstring>

typedef uint64_t u64;
typedef unsigned __int128 u128;

static const int MAXL = 130;  // up to 8320-bit moduli (Paillier-4096 n^2)

extern "C" {

int ddsbn_abi_version() { return 1; }

// out = a * b * R^-1 mod n   (canonical, < n). t space: internal.
void ddsbn_mont_mul(int L, const u64* n, u64 n0, const u64* a, const u64* b,
                    u64* out) {
  u64 t[MAXL + 2];
  memset(t, 0, (size_t)(L + 2) * sizeof(u64));
  for (int i = 0; i < L; i++) {
    const u64 ai = a[i];
    u64 carry = 0;
    for (int j = 0; j < L; j++) {
      u128 cur = (u128)ai * b[j] + t[j] + carry;
      t[j] = (u64)cur;
      carry = (u64)(cur >> 64);
    }
    u128 s = (u128)t[L] + carry;
    t[L] = (u64)s;
    t[L + 1] += (u64)(s >> 64);

    const u64 m = t[0] * n0;
    u128 cur = (u128)m * n[0] + t[0];
    carry = (u64)(cur >> 64);
    for (int j = 1; j < L; j++) {
      cur = (u128)m * n[j] + t[j] + carry;
      t[j - 1] = (u64)cur;
      carry = (u64)(cur >> 64);
    }
    s = (u128)t[L] + carry;
    t[L - 1] = (u64)s;
    s = (u128)t[L + 1] + (u64)(s >> 64);
    t[L] = (u64)s;
    t[L + 1] = (u64)(s >> 64);  // 0 by the < 2n bound
  }
  // conditional subtract (t has L+1 words, t[L] in {0,1})
  u64 diff[MAXL];
  u64 borrow = 0;
  for (int j = 0; j < L; j++) {
    u128 d = (u128)t[j] - n[j] - borrow;
    diff[j] = (u64)d;
    borrow = (u64)(d >> 64) & 1;
  }
  const bool ge = t[L] || !borrow;
  for (int j = 0; j < L; j++) out[j] = ge ? diff[j] : t[j];
}

// out = prod(cs) mod n over K plain-domain inputs (cs: K rows of L words).
// fix must be R^K mod n (host-computed): the chain of K-1 Montgomery
// multiplies accumulates R^-(K-1), and the final multiply by fix lands the
// result back in the plain domain.
void ddsbn_fold(int L, const u64* n, u64 n0, const u64* cs, long K,
                const u64* fix, u64* out) {
  u64 acc[MAXL];
  memcpy(acc, cs, (size_t)L * sizeof(u64));
  for (long i = 1; i < K; i++)
    ddsbn_mont_mul(L, n, n0, acc, cs + (size_t)i * L, acc);
  ddsbn_mont_mul(L, n, n0, acc, fix, out);
}

// out = base^exp mod n, plain domain in/out. exp given as `nibbles` 4-bit
// digits, MSB-first iteration over exp's little-endian words; r2 = R^2 mod n.
void ddsbn_exp(int L, const u64* n, u64 n0, const u64* r2, const u64* base,
               const u64* exp, int nibbles, u64* out) {
  u64 table[16][MAXL];
  // table[0] = R mod n (Montgomery one) = mont_mul(1, r2)
  u64 one[MAXL];
  memset(one, 0, (size_t)L * sizeof(u64));
  one[0] = 1;
  ddsbn_mont_mul(L, n, n0, one, r2, table[0]);
  ddsbn_mont_mul(L, n, n0, base, r2, table[1]);  // base into Montgomery
  for (int d = 2; d < 16; d++)
    ddsbn_mont_mul(L, n, n0, table[d - 1], table[1], table[d]);

  u64 r[MAXL];
  memcpy(r, table[0], (size_t)L * sizeof(u64));
  for (int idx = nibbles - 1; idx >= 0; idx--) {
    for (int s = 0; s < 4; s++) ddsbn_mont_mul(L, n, n0, r, r, r);
    const int digit = (int)((exp[idx / 16] >> (4 * (idx % 16))) & 0xF);
    ddsbn_mont_mul(L, n, n0, r, table[digit], r);
  }
  ddsbn_mont_mul(L, n, n0, r, one, out);  // back to plain domain
}

// batch modexp with a shared exponent: bases/out are B rows of L words.
void ddsbn_exp_batch(int L, const u64* n, u64 n0, const u64* r2,
                     const u64* bases, long B, const u64* exp, int nibbles,
                     u64* out) {
  for (long i = 0; i < B; i++)
    ddsbn_exp(L, n, n0, r2, bases + (size_t)i * L, exp, nibbles,
              out + (size_t)i * L);
}

}  // extern "C"
