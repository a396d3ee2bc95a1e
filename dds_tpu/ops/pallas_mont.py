"""Pallas TPU kernels for batched Montgomery modular arithmetic.

The compiled hot path behind `ops.montgomery.ModCtx`: the pure-jnp CIOS in
that module is the portable reference; these kernels implement the same
math as single fused Pallas programs so the limb accumulator lives in
VMEM/vregs for the whole multiply instead of round-tripping HBM on every
one of the L scan steps. This is the TPU-native replacement for the
reference system's JVM ``BigInteger`` hot loop (``hlib.hj.mlib`` consumed
via ``utils/SJHomoLibProvider.scala:53-71``; proxy-side folds at
``dds/http/DDSRestServer.scala:385,423,479,518``).

Layout: **limbs-major** ``(L, B)`` uint32 — limbs on the sublane axis,
batch on the lane axis. Both CIOS operands are then in the *same* layout:
the per-step limb broadcast ``a[i, :]`` is a cheap dynamic sublane slice,
and ``b`` is consumed whole; no transposed operand copies anywhere, so
multiply chains (modexp ladders, reduction trees) stay in one layout.

CIOS step (base 2^16, uint32 lanes), accumulator t kept *redundant*
(limbs < 2^26, no carry chains inside the hot loop):

    p   = a_i * b                      (full 32-bit products)
    m   = (t[0] + lo(p)[0]) * n0' mod 2^16
    q   = m * N
    v   = t + lo(p) + lo(q)            (v[0] = 0 mod 2^16 by m's choice)
    t'  = (v >> one limb) + hi(p) + hi(q) + (v[0] >> 16 at limb 0)

Growth audit: t' <= t_shift + 2*(2^16-1) + carry0, carry0 < 2^10+2,
so after L=256 steps limbs stay < 2^26 << 2^32; products a_i*b and m*N
are exact in uint32 because a, b, N are canonical (< 2^16). The final
normalize (one O(L) carry scan) and conditional subtract run in-kernel so
outputs are canonical and chainable.

Reference-parity note: replaces the semantics of `HomoAdd.sum` /
`HomoMult.multiply` aggregate folds; exact math validated against python
`pow`/`*`//`%` in tests/test_pallas.py.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dds_tpu.ops import bignum as bn
from dds_tpu.ops.montgomery import WINDOW, ModCtx, _exp_to_digits

LIMB_BITS = bn.LIMB_BITS
MASK = np.uint32(bn.LIMB_MASK)

MUL_TB = 512  # lane-tile (batch columns) per grid step for the mul kernel
EXP_TB = 256  # smaller for modexp: the 16-entry window table lives in VMEM


def _pad_rows(L: int) -> int:
    """Accumulator sublane count: L plus one overflow limb, 8-aligned."""
    return ((L + 1 + 7) // 8) * 8


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------


def _cios_loop(a_read, b, nb, n0, t0, L):
    """The shared CIOS main loop. `a_read(i)` yields limb row i as (1, TB).

    t0: (Lt, TB) initial accumulator. Returns redundant t (limbs < 2^26).
    """
    Lt, TB = t0.shape
    pad = ((0, Lt - L), (0, 0))

    def body(i, t):
        p = a_read(i) * b                      # (L, TB) sublane-broadcast mul
        lo = p & MASK
        hi = p >> LIMB_BITS
        u0 = t[0:1, :] + lo[0:1, :]
        m = (u0 * n0) & MASK                   # (1, TB)
        q = m * nb                             # (L, TB)
        v = t + jnp.pad(lo + (q & MASK), pad)
        c0 = v[0:1, :] >> LIMB_BITS
        t2 = jnp.concatenate(
            [v[1:, :], jnp.zeros((1, TB), jnp.uint32)], axis=0
        )
        add = jnp.concatenate([c0 + hi[0:1, :], hi[1:, :]], axis=0)
        return t2 + jnp.pad(add + (q >> LIMB_BITS), pad)

    return jax.lax.fori_loop(0, L, body, t0)


def _finalize(t, t_ref, nbx_ref, out_write, L):
    """Normalize redundant t to canonical limbs and conditionally subtract N.

    t: (Lt, TB) redundant value < 2n. t_ref: scratch ref, same shape.
    nbx_ref: (Lt, TB) modulus limbs broadcast (zero rows above L).
    out_write(rows) stores the final (L, TB) canonical result.
    """
    Lt, TB = t.shape
    t_ref[:, :] = t

    def norm(i, carry):
        s = t_ref[pl.ds(i, 1), :] + carry
        t_ref[pl.ds(i, 1), :] = s & MASK
        return s >> LIMB_BITS

    jax.lax.fori_loop(0, Lt, norm, jnp.zeros((1, TB), jnp.uint32))

    # borrow scan for t - N; diff rows < L land in the output buffer
    def sub_step(i, borrow):
        ti = t_ref[pl.ds(i, 1), :].astype(jnp.int32)
        ni = nbx_ref[pl.ds(i, 1), :].astype(jnp.int32)
        d = ti - ni - borrow
        neg = d < 0
        dd = jnp.where(neg, d + (1 << LIMB_BITS), d).astype(jnp.uint32)

        @pl.when(i < L)
        def _():
            out_write(pl.ds(i, 1), dd)

        return neg.astype(jnp.int32)

    borrow = jax.lax.fori_loop(
        0, Lt, sub_step, jnp.zeros((1, TB), jnp.int32)
    )
    return borrow == 1  # (1, TB): True where t < N (keep t, not diff)


def _make_mul_kernel(L: int, Lt: int, TB: int):
    def kernel(n0_ref, a_ref, b_ref, nbx_ref, out_ref, t_ref):
        n0 = n0_ref[0, 0]
        b = b_ref[:, :]
        nb = nbx_ref[0:L, :]
        t = _cios_loop(
            lambda i: a_ref[pl.ds(i, 1), :],
            b,
            nb,
            n0,
            jnp.zeros((Lt, TB), jnp.uint32),
            L,
        )
        lt = _finalize(
            t, t_ref, nbx_ref, lambda ds, v: out_ref.__setitem__((ds, slice(None)), v), L
        )
        out_ref[:, :] = jnp.where(lt, t_ref[0:L, :], out_ref[:, :])

    return kernel


def _make_exp_kernel(L: int, Lt: int, TB: int, E: int):
    """base^exp, all in Montgomery domain: 4-bit-window ladder, shared exp.

    Inputs: base (L, TB) canonical Montgomery-domain; digits (E,) int32
    MSB-first 4-bit digits in SMEM; one_mont (L, TB) broadcast R mod n.
    """

    def kernel(n0_ref, digits_ref, base_ref, nbx_ref, onem_ref, out_ref,
               tab_ref, t_ref, d_ref, a_ref):
        n0 = n0_ref[0, 0]
        nb = nbx_ref[0:L, :]

        def mul(a_val, b_val):
            # stage `a` in VMEM so its limb rows are dynamically sliceable
            a_ref[:, :] = a_val
            t = _cios_loop(
                lambda i: a_ref[pl.ds(i, 1), :],
                b_val,
                nb,
                n0,
                jnp.zeros((Lt, TB), jnp.uint32),
                L,
            )
            lt = _finalize(
                t, t_ref, nbx_ref,
                lambda ds, v: d_ref.__setitem__((ds, slice(None)), v), L
            )
            return jnp.where(lt, t_ref[0:L, :], d_ref[0:L, :])

        base = base_ref[:, :]
        onem = onem_ref[:, :]
        tab_ref[0] = onem
        tab_ref[1] = base
        acc = base
        for d in range(2, 1 << WINDOW):
            acc = mul(acc, base)
            tab_ref[d] = acc

        def digit_step(e, r):
            for _ in range(WINDOW):
                r = mul(r, r)
            digit = digits_ref[e]
            tv = tab_ref[pl.ds(digit, 1), :, :][0]
            return mul(r, tv)

        out_ref[:, :] = jax.lax.fori_loop(0, E, digit_step, onem)

    return kernel


# ---------------------------------------------------------------------------
# pallas_call wrappers (cached per shape)
# ---------------------------------------------------------------------------


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _mul_call(L: int, B: int, TB: int, interpret: bool):
    Lt = _pad_rows(L)
    grid = B // TB
    kernel = _make_mul_kernel(L, Lt, TB)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((L, TB), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((L, TB), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((Lt, TB), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((L, TB), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((L, B), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((Lt, TB), jnp.uint32)],
        interpret=interpret,
    )


@functools.lru_cache(maxsize=None)
def _exp_call(L: int, B: int, TB: int, E: int, interpret: bool):
    Lt = _pad_rows(L)
    grid = B // TB
    kernel = _make_exp_kernel(L, Lt, TB, E)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((E,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((L, TB), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((Lt, TB), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((L, TB), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((L, TB), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((L, B), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((1 << WINDOW, L, TB), jnp.uint32),
            pltpu.VMEM((Lt, TB), jnp.uint32),
            pltpu.VMEM((Lt, TB), jnp.uint32),
            pltpu.VMEM((L, TB), jnp.uint32),
        ],
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# device-level helpers (operate on limbs-major (L, B) jnp values)
# ---------------------------------------------------------------------------


def _nbx(ctx: ModCtx, TB: int) -> np.ndarray:
    """Modulus limbs broadcast to (Lt, TB), zero rows above L."""
    Lt = _pad_rows(ctx.L)
    out = np.zeros((Lt, TB), np.uint32)
    out[: ctx.L, :] = ctx.N[:, None]
    return out

def _n0(ctx: ModCtx) -> np.ndarray:
    return np.full((1, 1), ctx.n0inv, np.uint32)


def _pad_lanes(x, TB: int):
    """Pad (L, B) on the lane axis to a multiple of TB (zeros: harmless,
    pad columns compute garbage that callers slice off)."""
    B = x.shape[1]
    Bp = max(TB, ((B + TB - 1) // TB) * TB)
    if Bp != B:
        x = jnp.pad(x, ((0, 0), (0, Bp - B)))
    return x, B


def mul_lm(ctx: ModCtx, a, b, TB: int = MUL_TB, interpret: bool | None = None):
    """Montgomery product a*b*R^-1 mod n, limbs-major (L, B) canonical."""
    if interpret is None:
        interpret = _interpret_default()
    a, B = _pad_lanes(a, TB)
    b, _ = _pad_lanes(b, TB)
    out = _mul_call(ctx.L, a.shape[1], TB, interpret)(
        _n0(ctx), a, b, _nbx(ctx, TB)
    )
    return out[:, :B]


def exp_lm(ctx: ModCtx, base_mont, digits, TB: int = EXP_TB,
           interpret: bool | None = None):
    """base^exp in Montgomery domain, limbs-major; digits (E,) int32."""
    if interpret is None:
        interpret = _interpret_default()
    base_mont, B = _pad_lanes(base_mont, TB)
    onem = jnp.broadcast_to(jnp.asarray(ctx.one_mont)[:, None], (ctx.L, TB))
    out = _exp_call(ctx.L, base_mont.shape[1], TB, int(digits.shape[0]), interpret)(
        _n0(ctx), digits.astype(jnp.int32), base_mont, _nbx(ctx, TB), onem
    )
    return out[:, :B]


# ---------------------------------------------------------------------------
# public API: batch-major (B, L) in/out, mirroring ModCtx semantics
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _reduce_fn(ctx: ModCtx, P2: int, interpret: bool):
    """Jitted tree-reduction over (P2, L) batch-major input (P2 a power of
    two). The K-dependent R^K domain fixup enters as a runtime argument so
    one compiled executable serves every fold length with the same P2."""
    TB = MUL_TB

    def run(cs, fix):
        x = cs.T                                   # (L, P2)
        w = P2
        while w > 1:
            h = w // 2
            x = mul_lm(ctx, x[:, :h], x[:, h : 2 * h], TB, interpret)
            w = h
        x = mul_lm(ctx, x[:, :1], fix[:, None], TB, interpret)
        return x[:, :1].T                          # (1, L)

    return jax.jit(run)


@functools.lru_cache(maxsize=512)
def _fold_fix(ctx: ModCtx, K: int):
    """Device-resident R^K mod n fixup for a K-term fold (cached: the proxy
    folds the same store size repeatedly, and the host modexp + transfer
    would otherwise be paid on every aggregate)."""
    R = 1 << (LIMB_BITS * ctx.L)
    return jax.device_put(bn.int_to_limbs(pow(R % ctx.n, K, ctx.n), ctx.L))


def reduce_mul(ctx: ModCtx, cs, interpret: bool | None = None):
    """Modular product of all K rows of cs ((K, L) plain domain, K >= 1).

    Same contract as ModCtx.reduce_mul: pads K to a power of two with
    R mod n (the Montgomery identity), tree-reduces with in-VMEM CIOS
    kernels, and folds the accumulated R^-(K-1) fixup (times the pads'
    R factors) into one final multiply. Returns (1, L).
    """
    if interpret is None:
        interpret = _interpret_default()
    cs = jnp.asarray(cs)
    K = cs.shape[0]
    P2 = 1 << max(1, (K - 1).bit_length())
    if P2 != K:
        pad = jnp.broadcast_to(jnp.asarray(ctx.one_mont), (P2 - K, ctx.L))
        cs = jnp.concatenate([cs, pad], axis=0)
    return _reduce_fn(ctx, P2, interpret)(cs, _fold_fix(ctx, K))


@functools.lru_cache(maxsize=None)
def _pow_fn(ctx: ModCtx, E: int, interpret: bool):
    TB = EXP_TB

    def run(bases, digits):
        x = bases.T                                # (L, B)
        r2 = jnp.asarray(ctx.R2)[:, None]
        xm = mul_lm(ctx, x, jnp.broadcast_to(r2, x.shape), TB, interpret)
        r = exp_lm(ctx, xm, digits, TB, interpret)
        one = np.zeros((ctx.L, 1), np.uint32)
        one[0, 0] = 1
        out = mul_lm(ctx, r, jnp.broadcast_to(jnp.asarray(one), r.shape), TB, interpret)
        return out.T

    return jax.jit(run)


def pow_mod(ctx: ModCtx, bases, exp: int, interpret: bool | None = None):
    """Plain-domain bases^exp mod n, shared host-int exponent; (B, L) in/out."""
    if interpret is None:
        interpret = _interpret_default()
    if exp == 0:
        return jnp.asarray(bn.ones_batch(bases.shape[0], ctx.L))
    digits = jnp.asarray(_exp_to_digits(exp).astype(np.int32))
    return _pow_fn(ctx, int(digits.shape[0]), interpret)(jnp.asarray(bases), digits)
