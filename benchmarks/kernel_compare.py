"""Compare kernels v1 (fused CIOS) vs v2 (VPU product + MXU REDC):
fold (tree reduction) AND batch modexp (square-and-multiply ladder).

Correctness-gates v2 against python ints on real device values first,
then times with the sustained pipelined methodology. This is where the
kernel choice in models/backend.py comes from: v2 wins BOTH ops on real
TPU hardware (folds ~2.3x, modexp ~1.7x sustained) — the MXU REDC
removes most of the VPU multiply work, outweighing the per-multiply HBM
round-trips that v1's VMEM-resident ladder avoids.

Usage: python -m benchmarks.kernel_compare [--k 65536] [--bits 2048]
       [--pow-b 256] [--pow-exp-bits 64]
"""

from __future__ import annotations

import argparse
import secrets


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=65536)
    ap.add_argument("--bits", type=int, default=2048)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--pow-b", type=int, default=256, help="modexp batch")
    ap.add_argument("--pow-exp-bits", type=int, default=64)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmarks.common import emit, sustained_device
    from dds_tpu.bench_key import bench_paillier_key
    from dds_tpu.ops import bignum as bn
    from dds_tpu.ops import mont_mxu as mx
    from dds_tpu.ops import pallas_mont as pm
    from dds_tpu.ops.montgomery import ModCtx

    key = bench_paillier_key(args.bits)
    n2 = key.public.nsquare
    ctx = ModCtx.make(n2)
    mctx = mx.MxuCtx.make(ctx)

    # correctness gate on-device: both kernels agree with python ints
    small = [secrets.randbelow(n2) for _ in range(16)]
    want = 1
    for c in small:
        want = want * c % n2
    sb = bn.ints_to_batch(small, ctx.L)
    got1 = bn.batch_to_ints(np.asarray(pm.reduce_mul(ctx, sb)))[0]
    got2 = bn.batch_to_ints(np.asarray(mx.reduce_mul2(mctx, sb)))[0]
    assert got1 == want, "v1 fold wrong on device"
    assert got2 == want, "v2 fold wrong on device"

    cs = [secrets.randbelow(n2) for _ in range(args.k)]
    resident = jax.device_put(bn.ints_to_batch(cs, ctx.L))
    jax.block_until_ready(resident)

    rows = []
    t1 = sustained_device(lambda: pm.reduce_mul(ctx, resident), repeats=args.repeats)
    t2 = sustained_device(lambda: mx.reduce_mul2(mctx, resident), repeats=args.repeats)
    for name, t in (("v1-cios", t1), ("v2-mxu", t2)):
        rows.append(
            emit(
                f"fold kernel {name} @ {args.bits}-bit Paillier (mod n^2)",
                (args.k - 1) / t,
                "ops/s",
                t1 / t,
                K=args.k,
                limbs=ctx.L,
                fold_ms=round(t * 1e3, 3),
                ns_per_modmul=round(t / args.k * 1e9, 1),
            )
        )

    # ---- batch modexp: the same two multiplies under the exp ladder ----
    B = args.pow_b
    exp = secrets.randbits(args.pow_exp_bits) | 1
    bases = [secrets.randbelow(n2) for _ in range(B)]
    bb = jax.device_put(bn.ints_to_batch(bases, ctx.L))
    jax.block_until_ready(bb)
    want_pow = [pow(b, exp, n2) for b in bases[:4]]
    assert bn.batch_to_ints(np.asarray(pm.pow_mod(ctx, bb, exp)))[:4] == want_pow
    assert bn.batch_to_ints(np.asarray(mx.pow_mod2(mctx, bb, exp)))[:4] == want_pow
    p1 = sustained_device(lambda: pm.pow_mod(ctx, bb, exp), repeats=args.repeats)
    p2 = sustained_device(lambda: mx.pow_mod2(mctx, bb, exp), repeats=args.repeats)
    for name, t in (("v1-cios", p1), ("v2-mxu", p2)):
        rows.append(
            emit(
                f"modexp kernel {name} @ {args.bits}-bit Paillier "
                f"({args.pow_exp_bits}-bit exp)",
                B / t,
                "ops/s",
                p1 / t,
                B=B,
                limbs=ctx.L,
                batch_ms=round(t * 1e3, 3),
            )
        )
    return rows


if __name__ == "__main__":
    main()
