// The benchmark's plain reference for BN254 BLS verification (C++, host).
//
// A COPY of handel_tpu/native/bn254.cc as of PR 25, kept under benchmark/ so
// that the yardstick (keygen from the seed, signing, the pairing check that
// decides `correct`) cannot move with the program. benchmark/reference/
// bn254.py builds and binds it; nothing here is imported from handel_tpu.
// The original's header follows.
//
// Host-native BN254 group arithmetic — the framework's C++ fast path.
//
// Role: the reference gets host-speed field arithmetic from the amd64/arm64
// assembly inside its cloudflare/bn256 dependency (SURVEY.md §2.2); this
// library is the equivalent native layer for the host side of the TPU build:
// keygen, signing, point aggregation, and registry construction at
// 4000-node simulation scale, where the pure-Python scalar oracle
// (ops/bn254_ref.py) is orders of magnitude too slow — plus the host-side
// pairing (Fp6/Fp12 tower, Miller loop, final exponentiation) used by
// BN254PublicKey.verify and the gossip baselines. Batched device
// verification stays on the JAX/Pallas path (ops/).
//
// Design: 4x64-bit limb Montgomery arithmetic (CIOS with __uint128_t),
// Jacobian coordinates for G1 (over Fp, y^2 = x^3 + 3) and G2 (over Fp2 on
// the twist, y^2 = x^3 + b'), double-and-add scalar multiplication.
// Exposed as a flat C ABI for ctypes (handel_tpu/native/__init__.py):
// points cross the boundary as 32-byte little-endian affine coordinates
// plus an infinity flag; scalars as 32-byte little-endian.
//
// Correctness oracle: ops/bn254_ref.py (g1_add/g2_add/g1_mul/g2_mul);
// cross-checked in tests/test_native.py.

#include <cstdint>
#include <cstring>

using u64 = uint64_t;
using u128 = __uint128_t;

namespace {

// ---- Fp: 4x64 Montgomery ----------------------------------------------

struct Fp {
  u64 v[4];
};

static const Fp P = {{0x3c208c16d87cfd47ULL, 0x97816a916871ca8dULL,
                      0xb85045b68181585dULL, 0x30644e72e131a029ULL}};
static const u64 N0 = 0x87d20782e4866389ULL;  // -p^{-1} mod 2^64
static const Fp R2 = {{0xf32cfc5b538afa89ULL, 0xb5e71911d44501fbULL,
                       0x47ab1eff0a417ff6ULL, 0x6d89f71cab8351fULL}};
static const Fp ONE_M = {{0xd35d438dc58f0d9dULL, 0xa78eb28f5c70b3dULL,
                          0x666ea36f7879462cULL, 0xe0a77c19a07df2fULL}};

static inline bool ge_p(const Fp &a) {
  for (int i = 3; i >= 0; --i) {
    if (a.v[i] > P.v[i]) return true;
    if (a.v[i] < P.v[i]) return false;
  }
  return true;  // equal
}

static inline void sub_p(Fp &a) {
  u128 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    u128 d = (u128)a.v[i] - P.v[i] - borrow;
    a.v[i] = (u64)d;
    borrow = (d >> 64) ? 1 : 0;
  }
}

static inline void fp_add(Fp &out, const Fp &a, const Fp &b) {
  u128 carry = 0;
  bool overflow = false;
  for (int i = 0; i < 4; ++i) {
    u128 s = (u128)a.v[i] + b.v[i] + carry;
    out.v[i] = (u64)s;
    carry = s >> 64;
  }
  overflow = carry != 0;
  if (overflow || ge_p(out)) sub_p(out);
}

static inline void fp_sub(Fp &out, const Fp &a, const Fp &b) {
  u128 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    u128 d = (u128)a.v[i] - b.v[i] - borrow;
    out.v[i] = (u64)d;
    borrow = (d >> 64) ? 1 : 0;
  }
  if (borrow) {  // add p back
    u128 carry = 0;
    for (int i = 0; i < 4; ++i) {
      u128 s = (u128)out.v[i] + P.v[i] + carry;
      out.v[i] = (u64)s;
      carry = s >> 64;
    }
  }
}

static inline void fp_neg(Fp &out, const Fp &a) {
  bool zero = !(a.v[0] | a.v[1] | a.v[2] | a.v[3]);
  if (zero) {
    out = a;
    return;
  }
  u128 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    u128 d = (u128)P.v[i] - a.v[i] - borrow;
    out.v[i] = (u64)d;
    borrow = (d >> 64) ? 1 : 0;
  }
}

// CIOS Montgomery multiplication: out = a * b * R^{-1} mod p
static inline void fp_mul(Fp &out, const Fp &a, const Fp &b) {
  u64 t[6] = {0, 0, 0, 0, 0, 0};
  for (int i = 0; i < 4; ++i) {
    // t += a[i] * b
    u128 carry = 0;
    for (int j = 0; j < 4; ++j) {
      u128 s = (u128)a.v[i] * b.v[j] + t[j] + carry;
      t[j] = (u64)s;
      carry = s >> 64;
    }
    u128 s4 = (u128)t[4] + carry;
    t[4] = (u64)s4;
    t[5] = (u64)(s4 >> 64);
    // reduce: m = t[0] * n0 mod 2^64; t += m * p; t >>= 64
    u64 m = t[0] * N0;
    carry = ((u128)m * P.v[0] + t[0]) >> 64;
    for (int j = 1; j < 4; ++j) {
      u128 s = (u128)m * P.v[j] + t[j] + carry;
      t[j - 1] = (u64)s;
      carry = s >> 64;
    }
    u128 s5 = (u128)t[4] + carry;
    t[3] = (u64)s5;
    t[4] = t[5] + (u64)(s5 >> 64);
    t[5] = 0;
  }
  out.v[0] = t[0];
  out.v[1] = t[1];
  out.v[2] = t[2];
  out.v[3] = t[3];
  if (t[4] || ge_p(out)) sub_p(out);
}

static inline void fp_sqr(Fp &out, const Fp &a) { fp_mul(out, a, a); }

static inline bool fp_is_zero(const Fp &a) {
  return !(a.v[0] | a.v[1] | a.v[2] | a.v[3]);
}

static inline void fp_to_mont(Fp &out, const Fp &a) { fp_mul(out, a, R2); }

static inline void fp_from_mont(Fp &out, const Fp &a) {
  Fp one = {{1, 0, 0, 0}};
  fp_mul(out, a, one);
}

// a^e by square-and-multiply (e not secret here: public curve math)
static void fp_pow(Fp &out, const Fp &a, const Fp &e) {
  Fp acc = ONE_M;
  for (int i = 3; i >= 0; --i) {
    for (int b = 63; b >= 0; --b) {
      fp_sqr(acc, acc);
      if ((e.v[i] >> b) & 1) fp_mul(acc, acc, a);
    }
  }
  out = acc;
}

static void fp_inv(Fp &out, const Fp &a) {
  // Fermat: a^(p-2)
  Fp e = P;
  u128 borrow = 2;
  for (int i = 0; i < 4 && borrow; ++i) {
    u128 d = (u128)e.v[i] - borrow;
    e.v[i] = (u64)d;
    borrow = (d >> 64) ? 1 : 0;
  }
  fp_pow(out, a, e);
}

// ---- Fp2 = Fp[i]/(i^2+1) ----------------------------------------------

struct Fp2 {
  Fp c0, c1;
};

static inline void f2_add(Fp2 &o, const Fp2 &a, const Fp2 &b) {
  fp_add(o.c0, a.c0, b.c0);
  fp_add(o.c1, a.c1, b.c1);
}
static inline void f2_sub(Fp2 &o, const Fp2 &a, const Fp2 &b) {
  fp_sub(o.c0, a.c0, b.c0);
  fp_sub(o.c1, a.c1, b.c1);
}
static inline void f2_neg(Fp2 &o, const Fp2 &a) {
  fp_neg(o.c0, a.c0);
  fp_neg(o.c1, a.c1);
}
static inline void f2_mul(Fp2 &o, const Fp2 &a, const Fp2 &b) {
  Fp t0, t1, t2, t3;
  fp_mul(t0, a.c0, b.c0);
  fp_mul(t1, a.c1, b.c1);
  fp_add(t2, a.c0, a.c1);
  fp_add(t3, b.c0, b.c1);
  fp_mul(t2, t2, t3);  // (a0+a1)(b0+b1)
  Fp r0;
  fp_sub(r0, t0, t1);  // a0b0 - a1b1
  fp_sub(t2, t2, t0);
  fp_sub(t2, t2, t1);  // cross
  o.c0 = r0;
  o.c1 = t2;
}
static inline void f2_sqr(Fp2 &o, const Fp2 &a) { f2_mul(o, a, a); }
static inline bool f2_is_zero(const Fp2 &a) {
  return fp_is_zero(a.c0) && fp_is_zero(a.c1);
}
static void f2_inv(Fp2 &o, const Fp2 &a) {
  // 1/(c0 + c1 i) = (c0 - c1 i) / (c0^2 + c1^2)
  Fp n, t0, t1;
  fp_sqr(t0, a.c0);
  fp_sqr(t1, a.c1);
  fp_add(n, t0, t1);
  fp_inv(n, n);
  fp_mul(o.c0, a.c0, n);
  Fp neg;
  fp_neg(neg, a.c1);
  fp_mul(o.c1, neg, n);
}

// ---- generic Jacobian curve ops over a field F -------------------------
// (X, Y, Z): x = X/Z^2, y = Y/Z^3; infinity: Z == 0.

template <typename F>
struct CurveOps {
  void (*add)(F &, const F &, const F &);
  void (*sub)(F &, const F &, const F &);
  void (*mul)(F &, const F &, const F &);
  void (*sqr)(F &, const F &);
  void (*neg)(F &, const F &);
  void (*inv)(F &, const F &);
  bool (*is_zero)(const F &);
  F b;  // curve coefficient (Montgomery form)
};

template <typename F>
struct Jac {
  F X, Y, Z;
  bool inf;
};

template <typename F>
static void jac_double(const CurveOps<F> &ops, Jac<F> &o, const Jac<F> &p) {
  if (p.inf || ops.is_zero(p.Y)) {
    o.inf = true;
    return;
  }
  // alias-safe: o may be the same object as p, so everything is computed
  // into locals and assigned at the end
  F A, B, C, D, t0, t1, X3, Y3, Z3;
  ops.sqr(A, p.X);              // X^2
  ops.sqr(B, p.Y);              // Y^2
  ops.sqr(C, B);                // Y^4
  ops.add(t0, p.X, B);
  ops.sqr(t0, t0);
  ops.sub(t0, t0, A);
  ops.sub(t0, t0, C);
  ops.add(D, t0, t0);           // D = 2((X+B)^2 - A - C)
  ops.add(t0, A, A);
  ops.add(t0, t0, A);           // E = 3A
  F E = t0;
  ops.sqr(t1, E);               // E^2
  ops.sub(t1, t1, D);
  ops.sub(X3, t1, D);           // X3 = E^2 - 2D
  ops.sub(t1, D, X3);
  ops.mul(t1, E, t1);
  F c8;
  ops.add(c8, C, C);
  ops.add(c8, c8, c8);
  ops.add(c8, c8, c8);          // 8C
  ops.sub(Y3, t1, c8);
  ops.mul(t1, p.Y, p.Z);
  ops.add(Z3, t1, t1);          // Z3 = 2YZ
  o.X = X3;
  o.Y = Y3;
  o.Z = Z3;
  o.inf = false;
}

template <typename F>
static void jac_add(const CurveOps<F> &ops, Jac<F> &o, const Jac<F> &p,
                    const Jac<F> &q) {
  if (p.inf) {
    o = q;
    return;
  }
  if (q.inf) {
    o = p;
    return;
  }
  F Z1Z1, Z2Z2, U1, U2, S1, S2, t0;
  ops.sqr(Z1Z1, p.Z);
  ops.sqr(Z2Z2, q.Z);
  ops.mul(U1, p.X, Z2Z2);
  ops.mul(U2, q.X, Z1Z1);
  ops.mul(t0, q.Z, Z2Z2);
  ops.mul(S1, p.Y, t0);
  ops.mul(t0, p.Z, Z1Z1);
  ops.mul(S2, q.Y, t0);
  F H, Rr;
  ops.sub(H, U2, U1);
  ops.sub(Rr, S2, S1);
  if (ops.is_zero(H)) {
    if (ops.is_zero(Rr)) {
      jac_double(ops, o, p);
      return;
    }
    o.inf = true;
    return;
  }
  // alias-safe: o may be p or q; compute into locals, assign at the end
  F HH, HHH, V, X3, Y3, Z3;
  ops.sqr(HH, H);
  ops.mul(HHH, H, HH);
  ops.mul(V, U1, HH);
  ops.sqr(X3, Rr);
  ops.sub(X3, X3, HHH);
  ops.sub(X3, X3, V);
  ops.sub(X3, X3, V);
  ops.sub(t0, V, X3);
  ops.mul(t0, Rr, t0);
  F t1;
  ops.mul(t1, S1, HHH);
  ops.sub(Y3, t0, t1);
  ops.mul(t0, p.Z, q.Z);
  ops.mul(Z3, t0, H);
  o.X = X3;
  o.Y = Y3;
  o.Z = Z3;
  o.inf = false;
}

template <typename F>
static void jac_mul(const CurveOps<F> &ops, Jac<F> &o, const Jac<F> &p,
                    const u64 k[4]) {
  Jac<F> acc;
  acc.inf = true;
  bool started = false;
  for (int i = 3; i >= 0; --i) {
    for (int b = 63; b >= 0; --b) {
      if (started) jac_double(ops, acc, acc);
      if ((k[i] >> b) & 1) {
        if (acc.inf)
          acc = p;
        else
          jac_add(ops, acc, acc, p);
        started = true;
      } else if (!started) {
        continue;
      }
    }
  }
  o = acc;
}

template <typename F>
static void jac_to_affine(const CurveOps<F> &ops, F &x, F &y, bool &inf,
                          const Jac<F> &p) {
  if (p.inf || ops.is_zero(p.Z)) {
    inf = true;
    return;
  }
  F zi, zi2, zi3;
  ops.inv(zi, p.Z);
  ops.sqr(zi2, zi);
  ops.mul(zi3, zi2, zi);
  ops.mul(x, p.X, zi2);
  ops.mul(y, p.Y, zi3);
  inf = false;
}

// instantiate for Fp and Fp2
static const CurveOps<Fp> G1OPS = {fp_add, fp_sub, fp_mul, fp_sqr,
                                   fp_neg, fp_inv, fp_is_zero, Fp{}};
static const CurveOps<Fp2> G2OPS = {f2_add, f2_sub, f2_mul, f2_sqr,
                                    f2_neg, f2_inv, f2_is_zero, Fp2{}};

// ---- byte-buffer marshalling -------------------------------------------

static void load_fp(Fp &out, const uint8_t *b) {
  Fp raw;
  std::memcpy(raw.v, b, 32);  // little-endian limbs
  fp_to_mont(out, raw);
}

static void store_fp(uint8_t *b, const Fp &a) {
  Fp raw;
  fp_from_mont(raw, a);
  std::memcpy(b, raw.v, 32);
}

static void load_g1(Jac<Fp> &p, const uint8_t *xy, int inf) {
  p.inf = inf != 0;
  if (p.inf) return;
  load_fp(p.X, xy);
  load_fp(p.Y, xy + 32);
  p.Z = ONE_M;
}

static void store_g1(uint8_t *xy, int *inf, const Jac<Fp> &p) {
  Fp x, y;
  bool isinf;
  jac_to_affine(G1OPS, x, y, isinf, p);
  *inf = isinf ? 1 : 0;
  if (!isinf) {
    store_fp(xy, x);
    store_fp(xy + 32, y);
  } else {
    std::memset(xy, 0, 64);
  }
}

static void load_g2(Jac<Fp2> &p, const uint8_t *xy, int inf) {
  p.inf = inf != 0;
  if (p.inf) return;
  load_fp(p.X.c0, xy);
  load_fp(p.X.c1, xy + 32);
  load_fp(p.Y.c0, xy + 64);
  load_fp(p.Y.c1, xy + 96);
  p.Z.c0 = ONE_M;
  std::memset(p.Z.c1.v, 0, 32);
}

static void store_g2(uint8_t *xy, int *inf, const Jac<Fp2> &p) {
  Fp2 x, y;
  bool isinf;
  jac_to_affine(G2OPS, x, y, isinf, p);
  *inf = isinf ? 1 : 0;
  if (!isinf) {
    store_fp(xy, x.c0);
    store_fp(xy + 32, x.c1);
    store_fp(xy + 64, y.c0);
    store_fp(xy + 96, y.c1);
  } else {
    std::memset(xy, 0, 128);
  }
}

// ---- pairing: Fp6/Fp12 tower, Miller loop, final exponentiation --------
// Mirrors the scalar oracle (ops/bn254_ref.py): Fp6 = Fp2[v]/(v^3 - xi)
// with xi = 9+i, Fp12 = Fp6[w]/(w^2 - v), inversion-free projective Miller
// loop on the twist, easy+hard-part final exponentiation. This is the host
// verify fast path — the role of the assembly-backed cloudflare/bn256 `Pair`
// in the reference (bn256/cf/bn256.go:92-93).

static inline void f2_scalar_small(Fp2 &o, const Fp2 &a, int k) {
  Fp2 acc = a;
  for (int i = 1; i < k; ++i) f2_add(acc, acc, a);
  o = acc;
}

static inline void f2_mul_xi(Fp2 &o, const Fp2 &a) {
  // (9a0 - a1) + (9a1 + a0) i
  Fp2 nine;
  f2_scalar_small(nine, a, 9);
  Fp r0, r1;
  fp_sub(r0, nine.c0, a.c1);
  fp_add(r1, nine.c1, a.c0);
  o.c0 = r0;
  o.c1 = r1;
}

static inline void f2_conj(Fp2 &o, const Fp2 &a) {
  o.c0 = a.c0;
  fp_neg(o.c1, a.c1);
}

struct Fp6 {
  Fp2 c0, c1, c2;
};
struct Fp12 {
  Fp6 c0, c1;
};

static inline void f6_add(Fp6 &o, const Fp6 &a, const Fp6 &b) {
  f2_add(o.c0, a.c0, b.c0);
  f2_add(o.c1, a.c1, b.c1);
  f2_add(o.c2, a.c2, b.c2);
}
static inline void f6_sub(Fp6 &o, const Fp6 &a, const Fp6 &b) {
  f2_sub(o.c0, a.c0, b.c0);
  f2_sub(o.c1, a.c1, b.c1);
  f2_sub(o.c2, a.c2, b.c2);
}
static inline void f6_neg(Fp6 &o, const Fp6 &a) {
  f2_neg(o.c0, a.c0);
  f2_neg(o.c1, a.c1);
  f2_neg(o.c2, a.c2);
}

static void f6_mul(Fp6 &o, const Fp6 &a, const Fp6 &b) {
  // Toom/Karatsuba interpolation (bn254_ref.f6_mul)
  Fp2 t0, t1, t2, s1, s2, u;
  f2_mul(t0, a.c0, b.c0);
  f2_mul(t1, a.c1, b.c1);
  f2_mul(t2, a.c2, b.c2);
  Fp2 r0, r1, r2;
  // c0 = t0 + xi*((a1+a2)(b1+b2) - t1 - t2)
  f2_add(s1, a.c1, a.c2);
  f2_add(s2, b.c1, b.c2);
  f2_mul(u, s1, s2);
  f2_sub(u, u, t1);
  f2_sub(u, u, t2);
  f2_mul_xi(u, u);
  f2_add(r0, t0, u);
  // c1 = (a0+a1)(b0+b1) - t0 - t1 + xi*t2
  f2_add(s1, a.c0, a.c1);
  f2_add(s2, b.c0, b.c1);
  f2_mul(u, s1, s2);
  f2_sub(u, u, t0);
  f2_sub(u, u, t1);
  Fp2 xt2;
  f2_mul_xi(xt2, t2);
  f2_add(r1, u, xt2);
  // c2 = (a0+a2)(b0+b2) - t0 - t2 + t1
  f2_add(s1, a.c0, a.c2);
  f2_add(s2, b.c0, b.c2);
  f2_mul(u, s1, s2);
  f2_sub(u, u, t0);
  f2_sub(u, u, t2);
  f2_add(r2, u, t1);
  o.c0 = r0;
  o.c1 = r1;
  o.c2 = r2;
}

static inline void f6_mul_v(Fp6 &o, const Fp6 &a) {
  Fp2 t;
  f2_mul_xi(t, a.c2);
  Fp2 c0 = a.c0, c1 = a.c1;
  o.c0 = t;
  o.c1 = c0;
  o.c2 = c1;
}

static void f6_inv(Fp6 &o, const Fp6 &a) {
  Fp2 t0, t1, t2, u, den, inv;
  // t0 = a0^2 - xi*a1*a2
  f2_sqr(t0, a.c0);
  f2_mul(u, a.c1, a.c2);
  f2_mul_xi(u, u);
  f2_sub(t0, t0, u);
  // t1 = xi*a2^2 - a0*a1
  f2_sqr(t1, a.c2);
  f2_mul_xi(t1, t1);
  f2_mul(u, a.c0, a.c1);
  f2_sub(t1, t1, u);
  // t2 = a1^2 - a0*a2
  f2_sqr(t2, a.c1);
  f2_mul(u, a.c0, a.c2);
  f2_sub(t2, t2, u);
  // den = a0*t0 + xi*(a2*t1 + a1*t2)
  Fp2 d1, d2;
  f2_mul(d1, a.c2, t1);
  f2_mul(d2, a.c1, t2);
  f2_add(u, d1, d2);
  f2_mul_xi(u, u);
  f2_mul(den, a.c0, t0);
  f2_add(den, den, u);
  f2_inv(inv, den);
  f2_mul(o.c0, t0, inv);
  f2_mul(o.c1, t1, inv);
  f2_mul(o.c2, t2, inv);
}

static inline void f12_mul(Fp12 &o, const Fp12 &a, const Fp12 &b) {
  Fp6 t0, t1, s0, s1, u;
  f6_mul(t0, a.c0, b.c0);
  f6_mul(t1, a.c1, b.c1);
  Fp6 r0, r1;
  f6_mul_v(u, t1);
  f6_add(r0, t0, u);
  f6_add(s0, a.c0, a.c1);
  f6_add(s1, b.c0, b.c1);
  f6_mul(u, s0, s1);
  f6_sub(u, u, t0);
  f6_sub(r1, u, t1);
  o.c0 = r0;
  o.c1 = r1;
}

static inline void f12_sqr(Fp12 &o, const Fp12 &a) { f12_mul(o, a, a); }

static inline void f12_conj(Fp12 &o, const Fp12 &a) {
  o.c0 = a.c0;
  f6_neg(o.c1, a.c1);
}

static void f12_inv(Fp12 &o, const Fp12 &a) {
  Fp6 t, u, den;
  Fp6 a0sq, a1sq;
  f6_mul(a0sq, a.c0, a.c0);
  f6_mul(a1sq, a.c1, a.c1);
  f6_mul_v(u, a1sq);
  f6_sub(den, a0sq, u);
  f6_inv(den, den);
  f6_mul(o.c0, a.c0, den);
  f6_mul(t, a.c1, den);
  f6_neg(o.c1, t);
}

// gamma_j = xi^(j*(p-1)/6) (raw, converted to Montgomery at init)
static const Fp2 GAMMA_RAW[5] = {
    {{{0xd60b35dadcc9e470ULL, 0x5c521e08292f2176ULL, 0xe8b99fdd76e68b60ULL,
       0x1284b71c2865a7dfULL}},
     {{0xca5cf05f80f362acULL, 0x747992778eeec7e5ULL, 0xa6327cfe12150b8eULL,
       0x246996f3b4fae7e6ULL}}},
    {{{0x99e39557176f553dULL, 0xb78cc310c2c3330cULL, 0x4c0bec3cf559b143ULL,
       0x2fb347984f7911f7ULL}},
     {{0x1665d51c640fcba2ULL, 0x32ae2a1d0b7c9dceULL, 0x4ba4cc8bd75a0794ULL,
       0x16c9e55061ebae20ULL}}},
    {{{0xdc54014671a0135aULL, 0xdbaae0eda9c95998ULL, 0xdc5ec698b6e2f9b9ULL,
       0x063cf305489af5dcULL}},
     {{0x82d37f632623b0e3ULL, 0x21807dc98fa25bd2ULL, 0x0704b5a7ec796f2bULL,
       0x07c03cbcac41049aULL}}},
    {{{0x848a1f55921ea762ULL, 0xd33365f7be94ec72ULL, 0x80f3c0b75a181e84ULL,
       0x05b54f5e64eea801ULL}},
     {{0xc13b4711cd2b8126ULL, 0x3685d2ea1bdec763ULL, 0x9f3a80b03b0b1c92ULL,
       0x2c145edbe7fd8aeeULL}}},
    {{{0x2ea2c810eab7692fULL, 0x425c459b55aa1bd3ULL, 0xe93a3661a4353ff4ULL,
       0x0183c1e74f798649ULL}},
     {{0x24c6b8ee6e0c2c4bULL, 0xb080cb99678e2ac0ULL, 0xa27fb246c7729f7dULL,
       0x12acf2ca76fd0675ULL}}},
};

static Fp2 GAMMA_M[6];  // 1-indexed Montgomery-form gammas
static bool gamma_ready = false;

static void init_gammas() {
  if (gamma_ready) return;
  for (int j = 1; j <= 5; ++j) {
    fp_to_mont(GAMMA_M[j].c0, GAMMA_RAW[j - 1].c0);
    fp_to_mont(GAMMA_M[j].c1, GAMMA_RAW[j - 1].c1);
  }
  gamma_ready = true;
}

static void f12_frobenius(Fp12 &o, const Fp12 &a) {
  // w-degrees (0, 2, 4) in c0 and (1, 3, 5) in c1 (bn254_ref.f12_frobenius)
  Fp2 t;
  f2_conj(o.c0.c0, a.c0.c0);
  f2_conj(t, a.c0.c1);
  f2_mul(o.c0.c1, t, GAMMA_M[2]);
  f2_conj(t, a.c0.c2);
  f2_mul(o.c0.c2, t, GAMMA_M[4]);
  f2_conj(t, a.c1.c0);
  f2_mul(o.c1.c0, t, GAMMA_M[1]);
  f2_conj(t, a.c1.c1);
  f2_mul(o.c1.c1, t, GAMMA_M[3]);
  f2_conj(t, a.c1.c2);
  f2_mul(o.c1.c2, t, GAMMA_M[5]);
}

static const u64 BN_U = 0x44e992b44a6909f1ULL;

static void f12_pow_u64(Fp12 &o, const Fp12 &a, u64 e) {
  Fp12 result, base = a;
  // result = 1
  std::memset(&result, 0, sizeof(result));
  result.c0.c0.c0 = ONE_M;
  while (e) {
    if (e & 1) f12_mul(result, result, base);
    f12_sqr(base, base);
    e >>= 1;
  }
  o = result;
}

struct TwistPt {  // affine twist point, never infinity on this path
  Fp2 x, y;
};

struct ProjPt {
  Fp2 X, Y, Z;
};

// doubling step + tangent line at T evaluated at P (bn254_ref dbl)
static void miller_dbl(ProjPt &T, Fp12 &line, const Fp &xp, const Fp &yp) {
  Fp2 XX, YY, YZ, n, d, XYY, XYYZ, e, t, t2;
  f2_sqr(XX, T.X);
  f2_sqr(YY, T.Y);
  f2_mul(YZ, T.Y, T.Z);
  f2_scalar_small(n, XX, 3);
  f2_add(d, YZ, YZ);
  f2_mul(XYY, T.X, YY);
  f2_mul(XYYZ, XYY, T.Z);
  f2_sqr(e, n);
  Fp2 x8;
  f2_scalar_small(x8, XYYZ, 8);
  f2_sub(e, e, x8);
  ProjPt T3;
  f2_mul(T3.X, e, d);
  Fp2 x12, nn, yyz2;
  f2_scalar_small(x12, XYYZ, 12);
  f2_sqr(nn, n);
  f2_sub(t, x12, nn);
  f2_mul(t, n, t);
  f2_sqr(t2, YY);
  f2_sqr(yyz2, T.Z);
  f2_mul(t2, t2, yyz2);
  f2_scalar_small(t2, t2, 8);
  f2_sub(T3.Y, t, t2);
  f2_sqr(t, d);
  f2_mul(T3.Z, t, d);
  // line: c0 = 2*Y*Z^2*yp, cw = -(3X^2*Z)*xp, cw3 = 3X^3 - 2Y^2*Z
  // (xp/yp are base-field, so Fp2-by-Fp scaling is two fp_muls)
  Fp2 c0, cw, cw3, nZ;
  f2_mul(t, YZ, T.Z);
  f2_add(t, t, t);
  fp_mul(c0.c0, t.c0, yp);
  fp_mul(c0.c1, t.c1, yp);
  f2_mul(nZ, n, T.Z);
  fp_mul(cw.c0, nZ.c0, xp);
  fp_mul(cw.c1, nZ.c1, xp);
  f2_neg(cw, cw);
  Fp2 nX, yyZ;
  f2_mul(nX, n, T.X);
  f2_mul(yyZ, YY, T.Z);
  f2_add(yyZ, yyZ, yyZ);
  f2_sub(cw3, nX, yyZ);
  std::memset(&line, 0, sizeof(line));
  line.c0.c0 = c0;
  line.c1.c0 = cw;
  line.c1.c1 = cw3;
  T = T3;
}

// mixed addition step T + Q + line through them at P (bn254_ref add)
static void miller_add(ProjPt &T, Fp12 &line, const TwistPt &Q, const Fp &xp,
                       const Fp &yp) {
  Fp2 n, d, dd, x2Z, e, t, u;
  f2_mul(t, Q.y, T.Z);
  f2_sub(n, t, T.Y);
  f2_mul(t, Q.x, T.Z);
  f2_sub(d, t, T.X);
  f2_sqr(dd, d);
  f2_mul(x2Z, Q.x, T.Z);
  f2_sqr(e, n);
  f2_mul(e, e, T.Z);
  f2_add(t, T.X, x2Z);
  f2_mul(t, t, dd);
  f2_sub(e, e, t);
  ProjPt T3;
  f2_mul(T3.X, e, d);
  f2_mul(t, x2Z, dd);
  f2_sub(t, t, e);
  f2_mul(t, n, t);
  Fp2 ddd, y2Z;
  f2_mul(ddd, dd, d);
  f2_mul(y2Z, Q.y, T.Z);
  f2_mul(u, y2Z, ddd);
  f2_sub(T3.Y, t, u);
  f2_mul(T3.Z, T.Z, ddd);
  // line: c0 = d*yp, cw = -n*xp, cw3 = n*x2 - d*y2
  Fp2 c0, cw, cw3;
  fp_mul(c0.c0, d.c0, yp);
  fp_mul(c0.c1, d.c1, yp);
  fp_mul(cw.c0, n.c0, xp);
  fp_mul(cw.c1, n.c1, xp);
  f2_neg(cw, cw);
  Fp2 nx2, dy2;
  f2_mul(nx2, n, Q.x);
  f2_mul(dy2, d, Q.y);
  f2_sub(cw3, nx2, dy2);
  std::memset(&line, 0, sizeof(line));
  line.c0.c0 = c0;
  line.c1.c0 = cw;
  line.c1.c1 = cw3;
  T = T3;
}

// MSB-first bits of 6u+2 with the top bit dropped (64 steps)
static const char ATE_BITS[] =
    "1001110101111001011100000011100110111110011101100011101110101000";

static void miller_loop(Fp12 &f, const TwistPt &Q, const Fp &xp,
                        const Fp &yp) {
  init_gammas();
  ProjPt T;
  T.X = Q.x;
  T.Y = Q.y;
  std::memset(&T.Z, 0, sizeof(T.Z));
  T.Z.c0 = ONE_M;
  std::memset(&f, 0, sizeof(f));
  f.c0.c0.c0 = ONE_M;
  Fp12 line;
  for (const char *b = ATE_BITS; *b; ++b) {
    f12_sqr(f, f);
    miller_dbl(T, line, xp, yp);
    f12_mul(f, f, line);
    if (*b == '1') {
      miller_add(T, line, Q, xp, yp);
      f12_mul(f, f, line);
    }
  }
  // Frobenius corrections: q1 = psi(Q), q2 = -psi^2(Q)
  TwistPt q1, q2;
  Fp2 t;
  f2_conj(t, Q.x);
  f2_mul(q1.x, t, GAMMA_M[2]);
  f2_conj(t, Q.y);
  f2_mul(q1.y, t, GAMMA_M[3]);
  f2_conj(t, q1.x);
  f2_mul(q2.x, t, GAMMA_M[2]);
  f2_conj(t, q1.y);
  f2_mul(q2.y, t, GAMMA_M[3]);
  f2_neg(q2.y, q2.y);
  miller_add(T, line, q1, xp, yp);
  f12_mul(f, f, line);
  miller_add(T, line, q2, xp, yp);
  f12_mul(f, f, line);
}

static void final_exp(Fp12 &o, const Fp12 &f_in) {
  init_gammas();
  Fp12 f, t;
  // easy part: f^(p^6-1) = conj(f)*f^-1, then ^(p^2+1)
  f12_inv(t, f_in);
  f12_conj(f, f_in);
  f12_mul(f, f, t);
  Fp12 fr2;
  f12_frobenius(fr2, f);
  f12_frobenius(fr2, fr2);
  f12_mul(f, fr2, f);

  // hard part (Scott et al. chain; bn254_ref.final_exponentiation)
  Fp12 fu, fu2, fu3, fp1, fp2_, fp3;
  f12_pow_u64(fu, f, BN_U);
  f12_pow_u64(fu2, fu, BN_U);
  f12_pow_u64(fu3, fu2, BN_U);
  f12_frobenius(fp1, f);
  f12_frobenius(fp2_, fp1);
  f12_frobenius(fp3, fp2_);
  Fp12 y0, y1, y2, y3, y4, y5, y6;
  f12_mul(y0, fp1, fp2_);
  f12_mul(y0, y0, fp3);
  f12_conj(y1, f);
  f12_frobenius(y2, fu2);
  f12_frobenius(y2, y2);
  f12_frobenius(y3, fu);
  f12_conj(y3, y3);
  f12_frobenius(y4, fu2);
  f12_mul(y4, fu, y4);
  f12_conj(y4, y4);
  f12_conj(y5, fu2);
  f12_frobenius(y6, fu3);
  f12_mul(y6, fu3, y6);
  f12_conj(y6, y6);

  Fp12 t0, t1;
  f12_sqr(t0, y6);
  f12_mul(t0, t0, y4);
  f12_mul(t0, t0, y5);
  f12_mul(t1, y3, y5);
  f12_mul(t1, t1, t0);
  f12_mul(t0, t0, y2);
  f12_sqr(t1, t1);
  f12_mul(t1, t1, t0);
  f12_sqr(t1, t1);
  f12_mul(t0, t1, y1);
  f12_mul(t1, t1, y0);
  f12_sqr(t0, t0);
  f12_mul(o, t0, t1);
}

static bool f12_is_one(const Fp12 &a) {
  Fp12 one;
  std::memset(&one, 0, sizeof(one));
  one.c0.c0.c0 = ONE_M;
  return std::memcmp(&a, &one, sizeof(Fp12)) == 0;
}

}  // namespace

// ---- C ABI --------------------------------------------------------------

extern "C" {

// G1 points: 64-byte affine (x ‖ y), scalars: 32-byte little-endian.
void bn254_g1_add(uint8_t *out, int *out_inf, const uint8_t *a, int a_inf,
                  const uint8_t *b, int b_inf) {
  Jac<Fp> P1, P2, S;
  load_g1(P1, a, a_inf);
  load_g1(P2, b, b_inf);
  jac_add(G1OPS, S, P1, P2);
  store_g1(out, out_inf, S);
}

void bn254_g1_mul(uint8_t *out, int *out_inf, const uint8_t *a, int a_inf,
                  const uint8_t *scalar) {
  Jac<Fp> P1, S;
  load_g1(P1, a, a_inf);
  u64 k[4];
  std::memcpy(k, scalar, 32);
  jac_mul(G1OPS, S, P1, k);
  store_g1(out, out_inf, S);
}

// G2 points: 128-byte affine (x0 ‖ x1 ‖ y0 ‖ y1).
void bn254_g2_add(uint8_t *out, int *out_inf, const uint8_t *a, int a_inf,
                  const uint8_t *b, int b_inf) {
  Jac<Fp2> P1, P2, S;
  load_g2(P1, a, a_inf);
  load_g2(P2, b, b_inf);
  jac_add(G2OPS, S, P1, P2);
  store_g2(out, out_inf, S);
}

void bn254_g2_mul(uint8_t *out, int *out_inf, const uint8_t *a, int a_inf,
                  const uint8_t *scalar) {
  Jac<Fp2> P1, S;
  load_g2(P1, a, a_inf);
  u64 k[4];
  std::memcpy(k, scalar, 32);
  jac_mul(G2OPS, S, P1, k);
  store_g2(out, out_inf, S);
}

// Batch multi-scalar entry points: n independent muls in one call
// (amortizes the ctypes crossing for registry-scale keygen).
void bn254_g1_mul_batch(uint8_t *out, int *out_inf, const uint8_t *pts,
                        const int *infs, const uint8_t *scalars, int n) {
  for (int i = 0; i < n; ++i)
    bn254_g1_mul(out + 64 * i, out_inf + i, pts + 64 * i, infs[i],
                 scalars + 32 * i);
}

void bn254_g2_mul_batch(uint8_t *out, int *out_inf, const uint8_t *pts,
                        const int *infs, const uint8_t *scalars, int n) {
  for (int i = 0; i < n; ++i)
    bn254_g2_mul(out + 128 * i, out_inf + i, pts + 128 * i, infs[i],
                 scalars + 32 * i);
}

// Sum of n G1 points (the host-side Combine fallback when no device).
void bn254_g1_sum(uint8_t *out, int *out_inf, const uint8_t *pts,
                  const int *infs, int n) {
  Jac<Fp> acc, Q;
  acc.inf = true;
  for (int i = 0; i < n; ++i) {
    load_g1(Q, pts + 64 * i, infs[i]);
    jac_add(G1OPS, acc, acc, Q);
  }
  store_g1(out, out_inf, acc);
}

void bn254_g2_sum(uint8_t *out, int *out_inf, const uint8_t *pts,
                  const int *infs, int n) {
  Jac<Fp2> acc, Q;
  acc.inf = true;
  for (int i = 0; i < n; ++i) {
    load_g2(Q, pts + 128 * i, infs[i]);
    jac_add(G2OPS, acc, acc, Q);
  }
  store_g2(out, out_inf, acc);
}

// Product-of-pairings check: prod e(P_i, Q_i) == 1, one shared final
// exponentiation (the reference's verify at bn256/cf/bn256.go:86-98 as a
// single product; same structure as the device kernel's pairing_check).
// g1 points: 64-byte affine x||y little-endian limbs; g2 points: 128-byte
// affine x0||x1||y0||y1. Infinity pairs contribute 1 and are skipped.
int bn254_pairing_check(const uint8_t *g1s, const int *g1_infs,
                        const uint8_t *g2s, const int *g2_infs, int n) {
  init_gammas();
  Fp12 acc;
  std::memset(&acc, 0, sizeof(acc));
  acc.c0.c0.c0 = ONE_M;
  for (int i = 0; i < n; ++i) {
    if (g1_infs[i] || g2_infs[i]) continue;
    Fp xp, yp;
    load_fp(xp, g1s + 64 * i);
    load_fp(yp, g1s + 64 * i + 32);
    TwistPt Q;
    load_fp(Q.x.c0, g2s + 128 * i);
    load_fp(Q.x.c1, g2s + 128 * i + 32);
    load_fp(Q.y.c0, g2s + 128 * i + 64);
    load_fp(Q.y.c1, g2s + 128 * i + 96);
    Fp12 f;
    miller_loop(f, Q, xp, yp);
    f12_mul(acc, acc, f);
  }
  Fp12 out;
  final_exp(out, acc);
  return f12_is_one(out) ? 1 : 0;
}

// e(P, Q) marshaled out as 12 Fp values (c0.c0.c0.c0, c0.c0.c1, ... raw
// little-endian limb order, 384 bytes) — used by the cross-check tests.
void bn254_pairing(uint8_t *out, const uint8_t *g1, const uint8_t *g2) {
  init_gammas();
  Fp xp, yp;
  load_fp(xp, g1);
  load_fp(yp, g1 + 32);
  TwistPt Q;
  load_fp(Q.x.c0, g2);
  load_fp(Q.x.c1, g2 + 32);
  load_fp(Q.y.c0, g2 + 64);
  load_fp(Q.y.c1, g2 + 96);
  Fp12 f, e;
  miller_loop(f, Q, xp, yp);
  final_exp(e, f);
  const Fp2 *coords[6] = {&e.c0.c0, &e.c0.c1, &e.c0.c2,
                          &e.c1.c0, &e.c1.c1, &e.c1.c2};
  for (int i = 0; i < 6; ++i) {
    store_fp(out + 64 * i, coords[i]->c0);
    store_fp(out + 64 * i + 32, coords[i]->c1);
  }
}

// Miller loop only (no final exp) — oracle cross-check seam.
void bn254_miller(uint8_t *out, const uint8_t *g1, const uint8_t *g2) {
  init_gammas();
  Fp xp, yp;
  load_fp(xp, g1);
  load_fp(yp, g1 + 32);
  TwistPt Q;
  load_fp(Q.x.c0, g2);
  load_fp(Q.x.c1, g2 + 32);
  load_fp(Q.y.c0, g2 + 64);
  load_fp(Q.y.c1, g2 + 96);
  Fp12 f;
  miller_loop(f, Q, xp, yp);
  const Fp2 *coords[6] = {&f.c0.c0, &f.c0.c1, &f.c0.c2,
                          &f.c1.c0, &f.c1.c1, &f.c1.c2};
  for (int i = 0; i < 6; ++i) {
    store_fp(out + 64 * i, coords[i]->c0);
    store_fp(out + 64 * i + 32, coords[i]->c1);
  }
}

int bn254_native_version() { return 1; }

}  // extern "C"
