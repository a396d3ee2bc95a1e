"""Encrypt-grade modexp: FULL-WIDTH (2048-bit) exponent batch benchmark.

The north star names "the modular exponentiations behind encrypt,
decrypt"; the reference's client pays one n-bit-exponent modexp per
encrypted value (`utils/SJHomoLibProvider.scala:74-86`). r4 verdict #3:
no TPU number existed for a 2048-bit-exponent batch modexp — the op that
dominates encrypt/decrypt. This measures r^n mod n^2 (Paillier-2048
obfuscator generation, exponent = n = 2048 bits, modulus = n^2 = 4096
bits, L=256) at batch B for:

- v2:      MXU band-REDC ladder (mont_mxu.pow_mod2) — sustained + single
           dispatch;
- native:  host C++ CIOS (dds_tpu.native.powmod_batch);
- python:  CPython pow() loop (the CPU baseline);
- DJN:     the 448-bit short-exponent host path (what per-op encryption
           uses today) — the honest host contender for bulk encryption.

Also measures batched CRT DECRYPT (PaillierKey.decrypt_batch on the
Sanctum device plane: both half-width CRT legs fused into one dispatch,
secret moduli kept out of the shared caches) vs the per-op host decrypt,
decrypt-verified. benchmarks/decrypt_throughput.py is the dedicated
per-key-size decrypt sweep.

vs_baseline = v2 sustained vs python pow.

Usage: python -m benchmarks.encrypt_modexp [--b 256] [--repeats 3]
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import best_of, emit, sustained_device

METRIC = "encrypt-grade modexp ops/sec @ 2048-bit exponent, Paillier-2048 (r^n mod n^2)"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--b", type=int, default=256)
    ap.add_argument("--pipelined", type=int, default=4)
    args = ap.parse_args(argv)
    B = args.b

    import jax

    from dds_tpu import native
    from dds_tpu.bench_key import bench_paillier_key
    from dds_tpu.ops import bignum as bn
    from dds_tpu.ops import mont_mxu
    from dds_tpu.ops.montgomery import ModCtx

    key = bench_paillier_key()
    pk = key.public
    n, n2 = pk.n, pk.nsquare
    ctx = ModCtx.make(n2)
    mctx = mont_mxu.MxuCtx.make(ctx)
    rng = np.random.default_rng(11)

    rs = [int.from_bytes(rng.bytes(ctx.L), "little") % n2 for _ in range(B)]
    batch = bn.ints_to_batch(rs, ctx.L)
    dev = jax.device_put(batch)

    # correctness first: v2 against python pow on a slice
    want = [pow(r, n, n2) for r in rs[:4]]
    got = bn.batch_to_ints(np.asarray(mont_mxu.pow_mod2(mctx, batch[:4], n)))
    assert got == want, "v2 full-width modexp mismatch"

    # python pow baseline (per-op host loop)
    t_py = best_of(lambda: [pow(r, n, n2) for r in rs[: max(8, B // 32)]], repeats=2)
    py_ops = max(8, B // 32) / t_py

    # DJN short-exponent host path (the current per-op encrypt cost)
    t_djn = best_of(lambda: [pk.blind_fast() for _ in range(32)], repeats=2)
    djn_ops = 32 / t_djn

    # native host C++ batch
    t_nat = best_of(lambda: native.powmod_batch(rs[: max(8, B // 32)], n, n2), repeats=2)
    nat_ops = max(8, B // 32) / t_nat

    # v2 device ladder
    v2_sus = sustained_device(lambda: mont_mxu.pow_mod2(mctx, dev, n), R=args.pipelined)

    def v2_block():
        return np.asarray(mont_mxu.pow_mod2(mctx, dev, n))

    v2_block()
    v2_lat = best_of(v2_block, repeats=2)

    # batched CRT decrypt: Sanctum device path (both half-width legs
    # fused into one dispatch, secret moduli never in the shared caches
    # — benchmarks/decrypt_throughput.py is the dedicated sweep) vs
    # per-op host decrypt, verified
    from dds_tpu.sanctum import SecretBackend, plan_for

    sb = SecretBackend(device=True)
    ms_plain = [int(x) for x in rng.integers(0, 1 << 48, size=B)]
    blinds = [pk.blind() for _ in range(32)]
    cts = [pk.encrypt(m, rn=blinds[i % 32]) for i, m in enumerate(ms_plain)]
    got = key.decrypt_batch(cts, backend=sb, min_batch=1)
    assert got == ms_plain, "batched CRT decrypt mismatch"
    dec_plan = plan_for(key, sb)  # warm plan; timing excludes its compile
    dec_dev = best_of(lambda: dec_plan.decrypt_batch(cts), repeats=2)
    host_slice = cts[: max(8, B // 32)]
    dec_host = best_of(lambda: [key.decrypt(c) for c in host_slice], repeats=2)
    dec_dev_ops = B / dec_dev
    dec_host_ops = len(host_slice) / dec_host

    row = emit(
        METRIC,
        B / v2_sus,
        "ops/s",
        (B / v2_sus) / py_ops,
        B=B,
        exp_bits=n.bit_length(),
        v2_sustained_ops=round(B / v2_sus, 1),
        v2_single_dispatch_ops=round(B / v2_lat, 1),
        native_host_ops=round(nat_ops, 1),
        python_pow_ops=round(py_ops, 1),
        djn_short_exp_host_ops=round(djn_ops, 1),
        v2_ms_per_batch=round(v2_sus * 1e3, 1),
        decrypt_batch_device_ops=round(dec_dev_ops, 1),
        decrypt_host_ops=round(dec_host_ops, 1),
        decrypt_speedup=round(dec_dev_ops / dec_host_ops, 2),
    )
    return [row]


if __name__ == "__main__":
    main()
