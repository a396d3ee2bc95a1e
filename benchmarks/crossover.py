"""Fold crossover curve: device vs host per aggregate width K.

Justifies (or retunes) `TpuBackend.min_device_batch` with data instead
of a guess (r4 verdict #2): for each K it measures

- host:        native/python fold of K ciphertexts mod n^2 (the path
               small aggregates take today);
- device-lat:  ONE blocking device fold (dispatch + fetch) — what a lone
               below-crossover request would pay, host<->device round
               trip included;
- device-sus:  sustained per-fold time with R pipelined dispatches —
               what concurrent serving pays per request.

The printed curve is the BASELINE.md artifact; the crossover points are
where device-lat / device-sus dip below host.

Usage: python -m benchmarks.crossover [--ks 32 64 ... ] [--r 8]
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import best_of, emit, sustained_device

METRIC = "fold crossover: device vs host ms per K-wide aggregate"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ks", type=int, nargs="+",
                    default=[32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384])
    ap.add_argument("--r", type=int, default=8, help="concurrent requests")
    ap.add_argument("--host-only", action="store_true",
                    help="measure only the host fold column (no device "
                    "dispatches — usable while the TPU is unavailable; "
                    "the host side of the curve is valid either way)")
    args = ap.parse_args(argv)

    from dds_tpu import native
    from dds_tpu.bench_key import bench_paillier_key
    from dds_tpu.ops.montgomery import ModCtx

    key = bench_paillier_key()
    n2 = key.public.nsquare
    ctx = ModCtx.make(n2)
    rng = np.random.default_rng(7)

    kmax = max(args.ks)
    cs_int = [int.from_bytes(rng.bytes(ctx.L * 2), "little") % n2 for _ in range(kmax)]

    if not args.host_only:
        # device-path setup only when devices will be used: --host-only
        # must work (and stay cheap) while the TPU is unavailable
        import jax

        from dds_tpu.models.backend import TpuBackend
        from dds_tpu.ops import bignum as bn

        be = TpuBackend(min_device_batch=0)
        kernel = be.kernel if be.pallas else "jnp"
        batch_all = bn.ints_to_batch(cs_int, ctx.L)

    rows = []
    for K in args.ks:
        cs = cs_int[:K]
        host_s = best_of(lambda: native.fold(cs, n2))

        if args.host_only:
            rows.append(
                emit(METRIC, host_s * 1e3, "ms", 0.0, K=K,
                     host_ms=round(host_s * 1e3, 3), host_only=True)
            )
            continue

        batch = np.asarray(batch_all[:K])
        dev = jax.device_put(batch)

        def one_fold():
            return np.asarray(be.reduce_mul_device(ctx, dev))

        one_fold()  # warm/compile
        lat_s = best_of(one_fold)
        sus_s = sustained_device(lambda: be.reduce_mul_device(ctx, dev), R=args.r)

        rows.append(
            emit(
                METRIC,
                host_s * 1e3,
                "ms",
                (host_s / lat_s) if lat_s else 0.0,  # >1 => device latency wins
                K=K,
                host_ms=round(host_s * 1e3, 3),
                device_latency_ms=round(lat_s * 1e3, 3),
                device_sustained_ms=round(sus_s * 1e3, 3),
                r=args.r,
                kernel=kernel,
            )
        )

    if args.host_only:
        return rows

    # name the crossovers for BASELINE.md
    def crossover(field):
        for row in rows:
            d = row["detail"]
            if d[field] < d["host_ms"]:
                return d["K"]
        return None

    print(f"# crossover (device latency < host): K >= {crossover('device_latency_ms')}")
    print(f"# crossover (sustained < host):      K >= {crossover('device_sustained_ms')}")
    return rows


if __name__ == "__main__":
    main()
