"""Microprofile of the v2 kernel's building blocks: VPU and MXU probes and
the v2 roofline (dev tool, not a config).

All timed functions return a scalar reduction of their output so only 4
bytes cross the host<->device link per call while the full computation
still runs (a slice would let XLA dead-code-eliminate the rest).
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from dds_tpu.bench_key import bench_paillier_key
from dds_tpu.ops.montgomery import ModCtx


def timeit(fn, *args, repeats=5):
    np.asarray(fn(*args))  # warm/compile
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        np.asarray(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def vpu_mul_rate() -> float:
    """Achieved VPU u32 multiply+mask rate (L-independent probe)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, 1 << 16, size=(512, 65536), dtype=np.uint32))

    @jax.jit
    def muls(x):
        y = x
        for _ in range(32):
            y = (y * x) & np.uint32(0xFFFF)
        return y.sum()

    return 32 * x.size / timeit(muls, x)           # u32 mul+mask / s


def roofline(L: int, vpu_rate: float):
    """Per-modmul roofline for the v2 kernel at limb count L (r4 verdict
    #4): from the achieved VPU u32-multiply rate and the MXU int8 MAC
    rate at this L's REDC shape, derive the floor time a v2 Montgomery
    multiply cannot beat.

    v2 cost model per modmul (base-2^16 digits, see ops/mont_mxu):
    - product: L^2 u32 multiplies on the VPU (each with mask/shift/add
      bookkeeping — the measured chain rate already includes one mask per
      multiply, so the bound charges L^2 / chain_rate);
    - REDC: two int8 band matmuls over L8=2L base-2^8 digits:
      L8^2 + 2*L8^2 = 3*(2L)^2 = 12 L^2 int8 MACs on the MXU (balanced
      digits since PR 43: one matmul a band, no signed/mask split);
    - carry normalization: 4 full-width Kogge-Stone passes on the VPU, in
      the multiply's kernel — not charged (the floor is compute-optimistic).
    """
    rng = np.random.default_rng(3)
    Mi = jnp.asarray(rng.integers(-128, 127, size=(4 * L, 2 * L), dtype=np.int8))
    Vi = jnp.asarray(rng.integers(-128, 127, size=(2 * L, 4096), dtype=np.int8))

    @jax.jit
    def mm(M, V):
        return jax.lax.dot(M, V, preferred_element_type=jnp.int32).sum()

    mxu_rate = (4 * L * 2 * L * 4096) / timeit(mm, Mi, Vi)  # int8 MAC/s

    floor_s = (L * L) / vpu_rate + (12 * L * L) / mxu_rate
    return mxu_rate, floor_s


def roofline_report(bits_list=(1024, 2048, 4096)):
    """Print the utilization table for BASELINE.md: moduli of `bits` (so
    L = bits/16 limbs in the direct-modulus case; Paillier folds run at
    2x that for n^2)."""
    from dds_tpu.ops import mont_mxu

    rng = np.random.default_rng(9)
    vpu_rate = vpu_mul_rate()  # L-independent: measure once
    for bits in bits_list:
        n = (1 << bits) - 159  # odd, full-width
        ctx = ModCtx.make(n)
        L = ctx.L
        mctx = mont_mxu.MxuCtx.make(ctx)
        B = 8192
        batch = jnp.asarray(
            rng.integers(0, 1 << 16, size=(B, L), dtype=np.uint32)
        )

        f = jax.jit(lambda x: mont_mxu.mul2_lm(mctx, x.T, x.T).sum())
        t = timeit(f, batch)
        mxu_rate, floor_s = roofline(L, vpu_rate)
        per = t / B
        print(
            f"L={L:4d} ({bits}-bit): v2 modmul {per*1e9:8.1f} ns | "
            f"compute floor {floor_s*1e9:8.1f} ns | utilization "
            f"{floor_s/per*100:5.1f}% | vpu {vpu_rate/1e12:.2f} T mul/s, "
            f"mxu {mxu_rate/1e12:.1f} T MAC/s"
        )


def main():
    key = bench_paillier_key()
    ctx = ModCtx.make(key.nsquare)
    L = ctx.L
    rng = np.random.default_rng(0)

    # VPU elementwise throughput probes (32 chained ops on a 32M tile)
    x = jnp.asarray(rng.integers(0, 1 << 16, size=(512, 65536), dtype=np.uint32))

    @jax.jit
    def muls(x):
        y = x
        for _ in range(32):
            y = (y * x) & np.uint32(0xFFFF)
        return y.sum()

    t_m = timeit(muls, x)
    print(f"u32 mul+mask chain: {64 * x.size / t_m / 1e12:.2f} T elem-ops/s")

    @jax.jit
    def adds(x):
        y = x
        for _ in range(32):
            y = y + x
        return y.sum()

    t_a = timeit(adds, x)
    print(f"u32 add chain:      {32 * x.size / t_a / 1e12:.2f} T elem-ops/s")

    # MXU probes at the Montgomery-reduction shape (XLA level)
    L8 = 2 * L
    Bm = 4096
    Mi = jnp.asarray(rng.integers(-128, 127, size=(2 * L8, L8), dtype=np.int8))
    Vi = jnp.asarray(rng.integers(-128, 127, size=(L8, Bm), dtype=np.int8))

    @jax.jit
    def mm_i8(M, V):
        return jax.lax.dot(M, V, preferred_element_type=jnp.int32).sum()

    t_mm = timeit(mm_i8, Mi, Vi)
    macs = 2 * L8 * L8 * Bm
    print(f"int8 matmul ({2*L8}x{L8})@({L8}x{Bm}): {t_mm*1e3:.2f} ms  "
          f"{macs/t_mm/1e12:.1f} T MAC/s")

    Mf = jnp.asarray(rng.integers(0, 128, size=(2 * L8, L8)).astype(np.float32))
    Vf = jnp.asarray(rng.integers(0, 128, size=(L8, Bm)).astype(np.float32))

    @jax.jit
    def mm_f32(M, V):
        return jax.lax.dot(M, V, preferred_element_type=jnp.float32).sum()

    t_mf = timeit(mm_f32, Mf, Vf)
    print(f"f32 matmul  same shape: {t_mf*1e3:.2f} ms  {macs/t_mf/1e12:.1f} T MAC/s")

    print("\n-- v2 roofline (measured vs compute floor) --")
    roofline_report()


if __name__ == "__main__":
    main()
