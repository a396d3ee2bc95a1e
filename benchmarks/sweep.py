"""BASELINE config #2: Paillier key-size sweep 2048/3072/4096.

For each key size, measures the two homomorphic primitives the proxy's
extended API is built from (`dds/http/DDSRestServer.scala:385,423` and the
scalar path of Paillier):

- batched homomorphic SUM: modular-product fold of K ciphertexts mod n^2
  (cpu python-int fold vs one fused TPU Montgomery tree-reduction over
  device-resident limbs);
- batched scalar-MUL: c^k mod n^2 over a batch of B ciphertexts with a
  shared 64-bit scalar (cpu pow() loop vs one batched TPU modexp ladder).

Both primitives are decrypt-verified on a sub-batch before timing.

Usage: python -m benchmarks.sweep [--k 16384] [--b 256] [--sizes 2048,3072,4096]
"""

from __future__ import annotations

import argparse
import secrets


from benchmarks.common import best_of, emit, sustained_device

SCALAR_BITS = 64


def sweep_one(bits: int, K: int, B: int, repeats: int = 3) -> list[dict]:
    import jax

    from dds_tpu.bench_key import bench_paillier_key
    from dds_tpu.models.backend import CpuBackend, TpuBackend
    from dds_tpu.ops import bignum as bn
    from dds_tpu.ops.montgomery import ModCtx

    key = bench_paillier_key(bits)
    pk = key.public
    n2 = pk.nsquare
    # min_device_batch=0: correctness gates must exercise the DEVICE fold
    # even on small batches (the default adaptive dispatch would route them
    # to the host path)
    cpu, tpu = CpuBackend(), TpuBackend(min_device_batch=0)
    rows = []

    # correctness gates on real ciphertexts
    vals = [secrets.randbelow(1 << 32) for _ in range(16)]
    cts = [pk.encrypt(v) for v in vals]
    assert key.decrypt(tpu.modmul_fold(cts, n2)) == sum(vals)
    k_scalar = secrets.randbits(SCALAR_BITS)
    powed = tpu.powmod_batch(cts[:4], k_scalar, n2)
    for v, c in zip(vals[:4], powed):
        assert key.decrypt(c) == (v * k_scalar) % pk.n

    # ---- SUM fold -------------------------------------------------------
    cs = [secrets.randbelow(n2) for _ in range(K)]
    cpu_s = best_of(lambda: cpu.modmul_fold(cs, n2), repeats)
    cpu_ops = (K - 1) / cpu_s

    ctx = ModCtx.make(n2)
    resident = jax.device_put(bn.ints_to_batch(cs, ctx.L))
    jax.block_until_ready(resident)
    tpu_s = sustained_device(
        lambda: tpu.reduce_mul_device(ctx, resident), repeats=repeats
    )
    tpu_ops = (K - 1) / tpu_s
    rows.append(
        emit(
            f"encrypted SUM ops/sec @ Paillier-{bits}",
            tpu_ops,
            "ops/s",
            tpu_ops / cpu_ops,
            K=K,
            limbs=ctx.L,
            cpu_ops_per_sec=round(cpu_ops, 1),
            tpu_fold_ms=round(tpu_s * 1e3, 2),
            cpu_fold_ms=round(cpu_s * 1e3, 2),
        )
    )

    # ---- scalar-MUL (batched modexp, shared exponent) -------------------
    bases = [secrets.randbelow(n2) for _ in range(B)]
    cpu_s = best_of(lambda: [pow(c, k_scalar, n2) for c in bases], repeats)
    cpu_ops = B / cpu_s

    batch = jax.device_put(bn.ints_to_batch(bases, ctx.L))
    jax.block_until_ready(batch)
    if tpu.pallas:
        from dds_tpu.ops import mont_mxu

        mctx = mont_mxu.MxuCtx.make(ctx)
        run = lambda: mont_mxu.pow_mod2(mctx, batch, k_scalar)
    else:
        run = lambda: ctx.pow_mod(batch, k_scalar)
    tpu_s = sustained_device(run, R=8, repeats=repeats)
    tpu_ops = B / tpu_s
    rows.append(
        emit(
            f"scalar-MUL ops/sec @ Paillier-{bits} ({SCALAR_BITS}-bit scalar)",
            tpu_ops,
            "ops/s",
            tpu_ops / cpu_ops,
            B=B,
            limbs=ctx.L,
            cpu_ops_per_sec=round(cpu_ops, 1),
            tpu_batch_ms=round(tpu_s * 1e3, 2),
            cpu_batch_ms=round(cpu_s * 1e3, 2),
        )
    )
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=16384, help="SUM fold width")
    ap.add_argument("--b", type=int, default=256, help="scalar-MUL batch")
    ap.add_argument("--sizes", default="2048,3072,4096")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    out = []
    for bits in [int(s) for s in args.sizes.split(",")]:
        out += sweep_one(bits, args.k, args.b, args.repeats)
    return out


if __name__ == "__main__":
    main()
