"""Hybrid VPU+MXU Montgomery multiply (kernel family "v2", the one a TPU
serves; ops/montgomery's jnp scans are the portable reference).

A fused CIOS multiply interleaves the schoolbook product with the
Montgomery reduction, so both halves of the work (2 L^2 limb products per
multiply) run as uint32 VPU multiplies, and u32 multiply throughput is
~8x below add/logic throughput on TPU VPUs. v2 separates the two halves
and exploits that the *modulus is shared across the batch*:

- the a*b schoolbook product keeps the only varying*varying math on the
  VPU as a Pallas kernel (L^2 u32 multiplies — half of CIOS), producing a
  redundant 2L-digit accumulator without CIOS's per-step m/shift
  bookkeeping;
- the Montgomery reduction `m = T*N' mod R; t = (T + m*N)/R` is LINEAR in
  the varying operand with batch-constant coefficients (N' = -n^-1 mod R,
  N = n), so both products become matmuls against precomputed Toeplitz
  band matrices of the modulus digits in base 2^8 — int8 MXU work that is
  ~free next to the VPU product;
- carry normalization between stages is Kogge-Stone carry-lookahead in
  plain XLA: O(log L) full-width vector passes instead of an O(L)
  sequential carry scan.

int8 matmuls need inputs in [-128, 127]; digit vectors/matrices live in
[0, 255], so both are split as x = x' + 128*mask (x' signed, mask the 0/1
support): M @ d = M'@d' + 128*(mask_M@d') + (128*M'@1 + 2^14*mask_M@1),
i.e. two int8 matmuls plus a precomputed per-row constant.

The product is plain schoolbook. A fused CIOS family (v1) and a
one-level Karatsuba product (k1) were both built and lost at the served
width on a v5e (L = 256: 2.1 times and 1.7 % slower); no cell or default
ever reached them and PR 31 deleted them. v1 was ahead at L = 64 and k1
at L = 512: PERF.md section 6 has the timings, ROADMAP D2 says when to
recover them from commit a99dd7f.

Replaces the reference's `HomoAdd.sum` / `HomoMult.multiply` folds
(`dds/http/DDSRestServer.scala:385,423,479,518`); exactness is validated
against python int arithmetic in tests/test_mxu.py.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dds_tpu.ops import bignum as bn
from dds_tpu.ops.kernel import fold_fix, halving_tree, interpret_default
from dds_tpu.ops.montgomery import ModCtx

LIMB_BITS = bn.LIMB_BITS          # 16
MASK16 = np.uint32(0xFFFF)
MASK8 = np.int32(0xFF)

# lane tile for the product kernel: swept on a real v5e chip at L=256 —
# 128 lanes beat 256/512/1024 by ~3-10% (smaller tiles keep the (2L, TB)
# accumulator and operand blocks comfortably in VMEM)
PROD_TB = 128
GROUP = 8                         # a-limbs per aligned accumulator update


def _tb_for(L: int) -> int:
    """Lane tile per limb count. Small-limb moduli (RSA-1024: L=64)
    under-fill a 128-lane tile's fixed costs — wider tiles amortize them
    while the (2L, TB) accumulator still fits VMEM easily (L=64, TB=512:
    ~0.3 MB). L=256 (128 lanes) is the r3-measured winner; the small-L
    values are VMEM-fit picks pending the on-chip DDS_PROD_TB sweep
    (e.g. `DDS_PROD_TB=512 python -m benchmarks.product --sizes 1024`).
    CAUTION: DDS_PROD_TB is read at TRACE time and the callers' jit/lru
    caches key on shapes only — sweep with ONE PROCESS PER VALUE, never
    by mutating the env mid-process (stale traces would be re-timed)."""
    from dds_tpu.ops.flags import prod_tb

    env_tb = prod_tb()  # validated: int, > 0, multiple of 128 — loud errors
    if env_tb is not None:
        return env_tb
    if L <= 64:
        return 512
    if L <= 128:
        return 256
    return PROD_TB


# ---------------------------------------------------------------------------
# Pallas schoolbook product: (L, TB) x (L, TB) canonical -> (2L, TB) redundant
# ---------------------------------------------------------------------------


def _accumulate_prod(a_read, b, acc_ref, rows: int, TB: int) -> None:
    """Schoolbook-accumulate a*b into acc_ref ((2*rows + GROUP, TB),
    pre-zeroed). `a_read(i)` yields a's digit row i as (1, TB) (a closure
    over a ref — lets callers aim at a half of a larger operand); `b` is
    the whole (rows, TB) canonical digit value.

    GROUP shifted partial products per loop step keep the dynamic
    accumulator update sublane-aligned; the pad offsets (j / GROUP-j for
    the lo halves, j+1 / GROUP-j-1 for the hi halves) encode the digit
    alignment. Digit bound: each position sums <= rows lo-halves + rows
    hi-halves, each < 2^16, so digits < 2*rows*2^16 = 2^26 for rows = 512
    (Paillier-4096) — comfortably below u32 and carry_norm's < 2^31 input
    bound; no carries inside the loop."""

    def body(g, _):
        base = g * GROUP
        w = jnp.zeros((rows + GROUP, TB), jnp.uint32)
        for j in range(GROUP):
            p = a_read(base + j) * b                      # (rows, TB)
            lo = jnp.pad(p & MASK16, ((j, GROUP - j), (0, 0)))
            hi = jnp.pad(p >> LIMB_BITS, ((j + 1, GROUP - j - 1), (0, 0)))
            w = w + lo + hi
        cur = acc_ref[pl.ds(base, rows + GROUP), :]
        acc_ref[pl.ds(base, rows + GROUP), :] = cur + w
        return 0

    jax.lax.fori_loop(0, rows // GROUP, body, 0)


def _make_prod_kernel(L: int, TB: int):
    """T = a*b as redundant base-2^16 digits, limbs-major (see
    _accumulate_prod for the scheme + digit bounds)."""
    Lacc = 2 * L + GROUP  # top pad so every (L+GROUP)-row update fits

    def kernel(a_ref, b_ref, out_ref, acc_ref):
        acc_ref[:, :] = jnp.zeros((Lacc, TB), jnp.uint32)
        _accumulate_prod(
            lambda i: a_ref[pl.ds(i, 1), :], b_ref[:, :], acc_ref, L, TB
        )
        out_ref[:, :] = acc_ref[0 : 2 * L, :]

    return kernel


@functools.lru_cache(maxsize=None)
def _prod_call(L: int, B: int, TB: int, interpret: bool):
    kernel = _make_prod_kernel(L, TB)
    return pl.pallas_call(
        kernel,
        grid=(B // TB,),
        in_specs=[
            pl.BlockSpec((L, TB), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((L, TB), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((2 * L, TB), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((2 * L, B), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((2 * L + GROUP, TB), jnp.uint32)],
        interpret=interpret,
    )


def _pad_lanes(x, TB: int):
    B = x.shape[1]
    Bp = max(TB, ((B + TB - 1) // TB) * TB)
    if Bp != B:
        x = jnp.pad(x, ((0, 0), (0, Bp - B)))
    return x, B


def prod_lm(a, b, TB: int | None = None, interpret: bool | None = None):
    """Full product of canonical limbs-major operands: (L,B)x(L,B)->(2L,B).

    Handles any L: operands are zero-padded on the limb axis to a multiple
    of GROUP for the kernel (zero top limbs don't change the value) and the
    output is sliced back to 2L rows (the padded product's top rows are
    provably zero). TB=None picks the measured per-L lane tile (_tb_for)."""
    if interpret is None:
        interpret = interpret_default()
    L = a.shape[0]
    if TB is None:
        TB = _tb_for(L)
    Lp = ((L + GROUP - 1) // GROUP) * GROUP
    if Lp != L:
        a = jnp.pad(a, ((0, Lp - L), (0, 0)))
        b = jnp.pad(b, ((0, Lp - L), (0, 0)))
    a, B = _pad_lanes(a, TB)
    b, _ = _pad_lanes(b, TB)
    return _prod_call(Lp, a.shape[1], TB, interpret)(a, b)[: 2 * L, :B]


# ---------------------------------------------------------------------------
# XLA carry normalization (Kogge-Stone) in base 2^16 or 2^8
# ---------------------------------------------------------------------------


def _shift_up(x, k: int):
    """Digit k -> k+1 on the row axis; top rows drop off."""
    return jnp.pad(x, ((k, 0), (0, 0)))[: x.shape[0]]


def carry_norm(x, bits: int = 16):
    """Redundant digits (u32, < 2^31) -> (canonical digits, carry_out).

    x: (rows, B) base-2^bits digits, row 0 least significant. Returns
    canonical digits (< 2^bits) and the (1, B) u32 value carried out past
    the top row. Three local extract passes bound the pending carries to
    one bit; a Kogge-Stone generate/propagate prefix scan resolves the
    remaining ripple in log2(rows) passes.
    """
    mask = jnp.uint32((1 << bits) - 1)
    x = x.astype(jnp.uint32)
    rows = x.shape[0]
    carry_out = jnp.zeros((1, x.shape[1]), jnp.uint32)
    for _ in range(3):
        c = x >> bits
        x = (x & mask) + _shift_up(c, 1)
        carry_out = carry_out + c[-1:]
    # x <= mask + 1 now; resolve the single-bit ripple with carry-lookahead
    c = x >> bits
    s = x & mask
    carry_out = carry_out + c[-1:]
    a = _shift_up(c, 1)                       # pending +1s
    s = s + a                                 # <= mask + 1
    g = s > mask
    p = s == mask
    k = 1
    while k < rows:
        g = g | (p & _shift_up(g, k))
        p = p & _shift_up(p, k)
        k *= 2
    cin = _shift_up(g.astype(jnp.uint32), 1)
    carry_out = carry_out + g[-1:].astype(jnp.uint32)
    return (s + cin) & mask, carry_out


# ---------------------------------------------------------------------------
# Montgomery reduction constants: Toeplitz band matrices in base 2^8
# ---------------------------------------------------------------------------


def _digits8(v: int, count: int) -> np.ndarray:
    return np.array([(v >> (8 * i)) & 0xFF for i in range(count)], np.int32)


def _toeplitz8(digits: np.ndarray, out_rows: int, in_cols: int):
    """M[k, i] = digits[k - i] (0 <= k - i < len), as the int8 pair
    (signed_part, support_mask) with M = signed + 128 * mask."""
    d = np.zeros((out_rows, in_cols), np.int32)
    msk = np.zeros((out_rows, in_cols), np.int8)
    n = len(digits)
    for i in range(in_cols):
        lo, hi = i, min(i + n, out_rows)
        d[lo:hi, i] = digits[: hi - lo]
        msk[lo:hi, i] = 1
    signed = (d - 128 * msk.astype(np.int32)).astype(np.int8)
    return signed, msk


@dataclass(frozen=True, eq=False)
class MxuCtx:
    """Per-modulus constants for the v2 multiply."""

    ctx: ModCtx
    L8: int
    m_signed: np.ndarray = field(repr=False)   # (L8, L8) int8: N' band, mod R
    m_mask: np.ndarray = field(repr=False)
    q_signed: np.ndarray = field(repr=False)   # (2*L8, L8) int8: N band
    q_mask: np.ndarray = field(repr=False)

    @staticmethod
    @functools.lru_cache(maxsize=64)
    def make(ctx: ModCtx) -> "MxuCtx":
        L8 = 2 * ctx.L
        R = 1 << (LIMB_BITS * ctx.L)
        nprime = (-pow(ctx.n, -1, R)) % R
        m_signed, m_mask = _toeplitz8(_digits8(nprime, L8), L8, L8)
        q_signed, q_mask = _toeplitz8(_digits8(ctx.n, L8), 2 * L8, L8)
        return MxuCtx(ctx=ctx, L8=L8, m_signed=m_signed, m_mask=m_mask,
                      q_signed=q_signed, q_mask=q_mask)


def _band_dot(signed, mask, d8):
    """M @ d for digit vectors d8 in [0, 255], via two int8 matmuls.

    M = signed + 128*mask, d = d' + 128*support (support = all-ones over
    the L8 input rows). The constant pieces fold into per-row sums that
    depend only on the matrices, but computing them against the actual
    all-ones support costs nothing extra because XLA folds them — so for
    clarity: M@d = signed@d' + 128*(mask@d') + 128*(signed@ones) +
    2^14*(mask@ones), with the last two terms precomputed at trace time.
    """
    dprime = (d8 - 128).astype(jnp.int8)
    s = jax.lax.dot(signed.astype(jnp.int8), dprime,
                    preferred_element_type=jnp.int32)
    m = jax.lax.dot(mask.astype(jnp.int8), dprime,
                    preferred_element_type=jnp.int32)
    ones = jnp.ones((signed.shape[1], 1), jnp.int8)
    srow = jax.lax.dot(signed.astype(jnp.int8), ones,
                       preferred_element_type=jnp.int32)
    mrow = jax.lax.dot(mask.astype(jnp.int8), ones,
                       preferred_element_type=jnp.int32)
    return s + 128 * m + 128 * srow + (1 << 14) * mrow


def _split8(x16):
    """(L, B) canonical 16-bit digits -> (2L, B) base-2^8 digits (i32)."""
    L, B = x16.shape
    x16 = x16.astype(jnp.int32)
    lo = x16 & MASK8
    hi = x16 >> 8
    return jnp.stack([lo, hi], axis=1).reshape(2 * L, B)


def _merge8(q8):
    """(rows8, B) base-2^8 digits (< 2^11 after pre-pass) -> base-2^16."""
    rows8, B = q8.shape
    pair = q8.reshape(rows8 // 2, 2, B)
    return (pair[:, 0, :] + (pair[:, 1, :] << 8)).astype(jnp.uint32)


def _prenorm8(q, passes: int = 2):
    """Two local base-2^8 extract passes: digits < 2^25 -> < 2^11
    (pass 1: < 2^8 + 2^17, pass 2: < 2^8 + 2^10), so the 8->16 merge
    stays < 2^11*2^8 + 2^11 < 2^20, far from u32 overflow. Carries out of
    the top row cannot occur: all digits are nonnegative and the value
    fits the row span, so the top digit is always below the base."""
    q = q.astype(jnp.uint32)
    for _ in range(passes):
        q = (q & 0xFF) + _shift_up(q >> 8, 1)
    return q


# ---------------------------------------------------------------------------
# the v2 multiply and fold
# ---------------------------------------------------------------------------


def _redc(mctx: MxuCtx, T):
    """Montgomery reduction of the redundant product T (2L, B) -> (L, B)
    canonical, = value(T) * R^-1 mod n, for value(T) < n*R."""
    ctx = mctx.ctx
    L = ctx.L

    Tlo, cL = carry_norm(T[:L])
    Thi = T[L:].at[0:1].add(cL)

    d8 = _split8(Tlo)
    m_red = _band_dot(mctx.m_signed, mctx.m_mask, d8)      # (L8, B) >= 0
    m8, _ = carry_norm(m_red, bits=8)                      # mod R: drop carry

    q_red = _band_dot(mctx.q_signed, mctx.q_mask, m8.astype(jnp.int32))
    q16 = _merge8(_prenorm8(q_red))                        # (2L, B) < 2^19

    s_lo = Tlo + q16[:L]                                   # (T + q) mod R...
    zeros, u = carry_norm(s_lo)                            # ...== 0: digits
    del zeros                                              # provably zero
    t_red = (Thi + q16[L:]).at[0:1].add(u)                 # (T + q) / R
    t, c_top = carry_norm(t_red)                           # t + c_top*R < 2n

    # conditional subtract via complement add: t - N + R
    comp = jnp.asarray((MASK16 - ctx.N).astype(np.uint32))[:, None]
    w = t + comp
    w = w.at[0:1].add(1)
    diff, borrow = carry_norm(w)
    take_diff = (borrow + c_top) >= 1                      # t >= N
    return jnp.where(take_diff, diff, t)


def mul2_lm(mctx: MxuCtx, a, b, interpret: bool | None = None):
    """Montgomery product a*b*R^-1 mod n, limbs-major (L, B) canonical."""
    return _redc(mctx, prod_lm(a, b, interpret=interpret))


# ---------------------------------------------------------------------------
# v2 modexp: 4-bit windowed ladder over mul2_lm (lax.scan over the digits)
# ---------------------------------------------------------------------------


def _pow2_body(mctx: MxuCtx, E: int, interpret: bool):
    """The traced ladder body (un-jitted): callers that already run under a
    transform (jit in _pow2_fn, shard_map in parallel/mesh.py) close over
    this directly."""
    ctx = mctx.ctx

    def run(bases, digits):
        x = bases.T                                           # (L, B)
        shape = x.shape
        r2 = jnp.broadcast_to(jnp.asarray(ctx.R2)[:, None], shape)
        xm = mul2_lm(mctx, x, r2, interpret)                  # to mont
        onem = jnp.broadcast_to(
            jnp.asarray(ctx.one_mont)[:, None], shape
        ).astype(jnp.uint32)
        # windowed table x^0..x^15 in the Montgomery domain (15 multiplies,
        # amortized over E digits; digit 0 multiplies by the identity so the
        # scan body stays branch-free)
        tab = [onem, xm]
        for _ in range(2, 16):
            tab.append(mul2_lm(mctx, tab[-1], xm, interpret))
        table = jnp.stack(tab, axis=0)                        # (16, L, B)
        acc = jnp.take(table, digits[0], axis=0)

        def step(acc, d):
            for _ in range(4):                                # window bits
                acc = mul2_lm(mctx, acc, acc, interpret)
            acc = mul2_lm(mctx, acc, jnp.take(table, d, axis=0), interpret)
            return acc, None

        if E > 1:
            acc, _ = jax.lax.scan(step, acc, digits[1:])
        one = jnp.asarray(bn.ones_batch(1, ctx.L)).T          # (L, 1)
        out = mul2_lm(
            mctx, acc, jnp.broadcast_to(one, shape), interpret
        )                                                     # from mont
        return out.T

    return run


@functools.lru_cache(maxsize=None)
def _pow2_fn(mctx: MxuCtx, E: int, interpret: bool):
    return jax.jit(_pow2_body(mctx, E, interpret))


def pow_mod2(mctx: MxuCtx, bases, exp: int, interpret: bool | None = None):
    """Plain-domain bases^exp mod n via the v2 multiply; (B, L) in/out.
    Contract identical to ModCtx.pow_mod."""
    from dds_tpu.ops.montgomery import _exp_to_digits

    if interpret is None:
        interpret = interpret_default()
    if exp == 0:
        return jnp.asarray(bn.ones_batch(bases.shape[0], mctx.ctx.L))
    digits = jnp.asarray(_exp_to_digits(exp).astype(np.int32))
    from dds_tpu.obs import kprof

    fn = kprof.counted(
        "mont_mxu.pow2", _pow2_fn, mctx, int(digits.shape[0]), interpret
    )
    return fn(jnp.asarray(bases), digits)


@functools.lru_cache(maxsize=None)
def _reduce2_fn(mctx: MxuCtx, P2: int, interpret: bool):
    # the served fold: the yardstick reads its device time by the name
    # `jit_run`, so the jitted function stays `run`
    def run(cs, fix):
        x = halving_tree(
            lambda a, b: mul2_lm(mctx, a, b, interpret), cs.T, axis=1
        )
        x = mul2_lm(mctx, x[:, :1], fix[:, None], interpret)
        return x[:, :1].T

    return jax.jit(run)


def reduce_mul2(mctx: MxuCtx, cs, interpret: bool | None = None):
    """v2 modular product of all K rows of cs ((K, L) plain domain).

    Contract identical to ModCtx.reduce_mul."""
    if interpret is None:
        interpret = interpret_default()
    ctx = mctx.ctx
    cs = jnp.asarray(cs)
    K = cs.shape[0]
    P2 = 1 << max(1, (K - 1).bit_length())
    if P2 != K:
        pad = jnp.broadcast_to(jnp.asarray(ctx.one_mont), (P2 - K, ctx.L))
        cs = jnp.concatenate([cs, pad], axis=0)
    from dds_tpu.obs import kprof

    fn = kprof.counted("mont_mxu.reduce2", _reduce2_fn, mctx, P2, interpret)
    return fn(cs, fold_fix(ctx, K))
