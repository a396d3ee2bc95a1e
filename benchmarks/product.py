"""BASELINE config #3: multiplicative-HE PRODUCT aggregate.

The proxy's `MultAll` route folds RSA-multiplicative ciphertexts with
`HomoMult.multiply` (`dds/http/DDSRestServer.scala:505-524`): a modmul
fold mod n. Times that fold cpu vs tpu (one fused Montgomery tree
reduction over device-resident limbs), decrypt-verified first.

The reference ships an RSA-1024 multiplicative key (`client.conf:86`);
we sweep 1024 and 2048.

`--paillier 2048,3072,4096` adds rows for the SumAll fold mod n^2 at those
key sizes (L = 256, 384, 512 limbs; the fixed bench keys), each naming the
a*b product `mont_mxu.product_for(L)` chose and the lane tile it ran at;
`--ladder B` adds the windowed modexp r^n mod n^2 over B bases to each.
The product is a function of L alone; to time the other one at the same L
(the sweep behind `mont_mxu.KARATSUBA_MIN_L`, PERF.md section 6, PR 38),
`--karatsuba-min-l N` sets that module constant before anything is traced,
and DDS_PROD_TB the lane tile: ONE PROCESS PER VALUE, as `_tb_for` says.

Usage: python -m benchmarks.product [--k 16384] [--sizes 1024,2048]
       python -m benchmarks.product --sizes "" --paillier 4096 --k 16384
           [--karatsuba-min-l 1024] [--ladder 256]
"""

from __future__ import annotations

import argparse
import secrets


from benchmarks.common import best_of, emit, sustained_device


def product_one(bits: int, K: int, repeats: int = 3) -> dict:
    import jax

    from dds_tpu.models.backend import CpuBackend, TpuBackend
    from dds_tpu.models.mult import RsaMultKey
    from dds_tpu.ops import bignum as bn
    from dds_tpu.ops.montgomery import ModCtx

    key = RsaMultKey.generate(bits)
    pk = key.public
    # min_device_batch=0: the correctness gate must exercise the device fold
    cpu, tpu = CpuBackend(), TpuBackend(min_device_batch=0)

    # correctness gate: PRODUCT of real ciphertexts decrypts to the product
    vals = [secrets.randbelow(1 << 16) + 1 for _ in range(8)]
    cts = [pk.encrypt(v) for v in vals]
    want = 1
    for v in vals:
        want = want * v % pk.n
    assert key.decrypt(tpu.modmul_fold(cts, pk.n)) == want

    cs = [secrets.randbelow(pk.n) for _ in range(K)]
    cpu_s = best_of(lambda: cpu.modmul_fold(cs, pk.n), repeats)
    cpu_ops = (K - 1) / cpu_s

    ctx = ModCtx.make(pk.n)
    resident = jax.device_put(bn.ints_to_batch(cs, ctx.L))
    jax.block_until_ready(resident)
    tpu_s = sustained_device(
        lambda: tpu.reduce_mul_device(ctx, resident), repeats=repeats
    )
    tpu_ops = (K - 1) / tpu_s
    return emit(
        f"encrypted PRODUCT ops/sec @ RSA-{bits} (MultAll fold)",
        tpu_ops,
        "ops/s",
        tpu_ops / cpu_ops,
        K=K,
        limbs=ctx.L,
        cpu_ops_per_sec=round(cpu_ops, 1),
        tpu_fold_ms=round(tpu_s * 1e3, 2),
        cpu_fold_ms=round(cpu_s * 1e3, 2),
    )


def paillier_fold_one(bits: int, K: int, repeats: int = 3,
                      ladder: int = 0) -> dict:
    """The SumAll fold mod n^2 of the fixed Paillier-`bits` bench key over K
    random residues, checked against python ints, as reduce_mul2 runs it."""
    import jax
    import numpy as np

    from dds_tpu.bench_key import bench_paillier_key
    from dds_tpu.ops import bignum as bn
    from dds_tpu.ops import mont_mxu
    from dds_tpu.ops.montgomery import ModCtx

    pk = bench_paillier_key(bits).public
    n2 = pk.nsquare
    ctx = ModCtx.make(n2)
    mctx = mont_mxu.MxuCtx.make(ctx)
    L = ctx.L
    product = mont_mxu.product_for(L)
    rng = np.random.default_rng(bits)
    cs = [int.from_bytes(rng.bytes(2 * L), "little") % n2 for _ in range(K)]
    resident = jax.device_put(bn.ints_to_batch(cs, L))
    want = 1
    for c in cs:
        want = want * c % n2
    got = bn.limbs_to_int(np.asarray(mont_mxu.reduce_mul2(mctx, resident))[0])
    assert got == want, f"fold mod n^2 wrong at L={L} ({product})"
    fold_s = sustained_device(
        lambda: mont_mxu.reduce_mul2(mctx, resident), repeats=repeats
    )
    detail = dict(
        K=K, limbs=L, product=product,
        lane_tile=mont_mxu.lane_tile(L),
        fold_ms=round(fold_s * 1e3, 4),
    )
    if ladder:
        bases = jax.device_put(bn.ints_to_batch(cs[:ladder], L))
        out = np.asarray(mont_mxu.pow_mod2(mctx, bases[:2], pk.n))
        assert bn.batch_to_ints(out) == [pow(c, pk.n, n2) for c in cs[:2]]
        ladder_s = sustained_device(
            lambda: mont_mxu.pow_mod2(mctx, bases, pk.n), R=2, repeats=repeats
        )
        detail.update(ladder_B=ladder, ladder_ms=round(ladder_s * 1e3, 3))
    return emit(
        f"encrypted SUM ops/sec @ Paillier-{bits} (SumAll fold, {product})",
        (K - 1) / fold_s,
        "ops/s",
        1.0,
        **detail,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=16384)
    ap.add_argument("--sizes", default="1024,2048")
    ap.add_argument("--paillier", default="")
    ap.add_argument("--ladder", type=int, default=0)
    ap.add_argument("--karatsuba-min-l", type=int, default=None)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    if args.karatsuba_min_l is not None:
        from dds_tpu.ops import mont_mxu

        mont_mxu.KARATSUBA_MIN_L = args.karatsuba_min_l
    rows = [
        product_one(int(s), args.k, args.repeats)
        for s in args.sizes.split(",") if s
    ]
    rows += [
        paillier_fold_one(int(s), args.k, args.repeats, args.ladder)
        for s in args.paillier.split(",") if s
    ]
    return rows


if __name__ == "__main__":
    main()
