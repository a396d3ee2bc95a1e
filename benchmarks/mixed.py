"""BASELINE config #5: OPE range query + Paillier SUM mixed workload.

YCSB-style mix through the full stack (client-side HE, REST proxy, ABD
quorums over the default 9-replica/quorum-5 topology): 20% PutSet, 40% OPE
range searches (Gt/GtEq/Lt/LtEq on the OPE column), 20% SumAll, 10% GetSet,
10% equality search — driven by the schema-aware workload generator, the
same operational-test mechanism the reference uses (SURVEY.md §4.1).

Two YCSB-faithful knobs added in r5 (the config-5 re-spec of r4 verdict
#2, justified by benchmarks/crossover.py's curve):
- `--preload K`: a LOAD PHASE stores K encrypted rows before the timed
  transaction phase (YCSB's own shape), so SumAll folds run at a
  realistic store size instead of the ~40 rows the 200-op mix happens to
  accumulate;
- `--clients N`: N concurrent clients (the reference's `Main.scala:
  166-170`), whose concurrent SumAlls fold on worker threads.

Reports end-to-end aggregate client ops/s per crypto backend.

Usage: python -m benchmarks.mixed [--ops 200] [--preload 4096] [--clients 4]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time

from benchmarks.common import emit

MIX = {
    "put-set": 0.2,
    "search-gt": 0.1, "search-gteq": 0.1, "search-lt": 0.1, "search-lteq": 0.1,
    "sum-all": 0.2,
    "get-set": 0.1,
    "search-eq": 0.1,
}


async def _preload(dep, provider, k: int) -> None:
    """YCSB load phase: store K canonical 8-column rows through PutSet,
    every column encrypted with its schema scheme via the provider (so
    the transaction phase's range/equality searches see real OPE/CHE
    ciphertexts with honest selectivity, not plaintext skew). Only the
    PSSE column bypasses `encrypt_row`, using pooled obfuscators — one
    modmul per row instead of a modexp — to keep the untimed load phase
    cheap; the timed phase is unaffected."""
    from dds_tpu.http.miniserver import http_request

    pk = provider.keys.psse.public
    blinds = [pk.blind() for _ in range(32)]
    host, port = "127.0.0.1", dep.server.cfg.port
    sem = asyncio.Semaphore(64)

    def enc_row(i: int) -> list:
        p = provider
        return [
            p.encrypt(i, "OPE"),
            p.encrypt(f"name-{i}", "CHE"),
            str(pk.encrypt(i, rn=blinds[i % 32])),        # PSSE, pooled
            p.encrypt(2, "MSE"),
            p.encrypt("a", "CHE"), p.encrypt("b", "CHE"), p.encrypt("c", "CHE"),
            p.encrypt(f"blob-{i}", "None"),
        ]

    async def put(i):
        async with sem:
            st, _ = await http_request(
                host, port, "POST", "/PutSet",
                json.dumps({"contents": enc_row(i)}).encode(),
            )
            assert st == 200

    await asyncio.gather(*(put(i) for i in range(k)))


async def _run_backend(backend: str, ops: int, provider, seed: int,
                       force_device: bool, preload: int = 0,
                       clients: int = 1) -> tuple[float, int]:
    from dds_tpu.run import launch, run_workload
    from dds_tpu.utils.config import DDSConfig

    cfg = DDSConfig()
    cfg.proxy.port = 0
    cfg.proxy.crypto_backend = backend
    cfg.recovery.enabled = False       # keep timing clean of proactive restarts
    cfg.client.nr_of_operations = ops
    cfg.client.nr_of_local_clients = clients
    cfg.client.proportions = dict(MIX)

    dep = await launch(cfg)
    if force_device and hasattr(dep.server.backend, "min_device_batch"):
        dep.server.backend.min_device_batch = 0
    try:
        if preload:
            await _preload(dep, provider, preload)
        t0 = time.perf_counter()
        reports = await run_workload(dep, provider=provider, seed=seed)
        wall = time.perf_counter() - t0
        for r in reports:
            assert r.failed == 0, f"{r.failed} ops failed on {backend}"
        total_ops = sum(r.operations for r in reports)
        return total_ops / wall, len(dep.server.stored_keys)
    finally:
        await dep.stop()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", type=int, default=200)
    ap.add_argument("--preload", type=int, default=0,
                    help="YCSB load phase: store this many rows first")
    ap.add_argument("--clients", type=int, default=1,
                    help="concurrent clients (Main.scala:166-170)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument(
        "--force-device", action="store_true",
        help="set the tpu backend's min_device_batch to 0 so every SumAll "
        "fold runs on-device; default keeps the production adaptive "
        "dispatch, which at this workload's stored-set count (< the 1024 "
        "threshold) routes folds to the host path",
    )
    args = ap.parse_args(argv)

    from dds_tpu.bench_key import bench_paillier_key
    from dds_tpu.models.facade import HomoProvider
    from dds_tpu.models.keys import HEKeys

    keys = HEKeys.generate(paillier_bits=512, rsa_bits=1024)  # psse replaced below
    keys = HEKeys(
        ope=keys.ope, che=keys.che, lse=keys.lse,
        psse=bench_paillier_key(), mse=keys.mse, none=keys.none,
    )
    provider = HomoProvider(keys)

    async def go():
        cpu = await _run_backend("cpu", args.ops, provider, args.seed, False,
                                 args.preload, args.clients)
        tpu = await _run_backend("tpu", args.ops, provider, args.seed,
                                 args.force_device, args.preload, args.clients)
        return cpu, tpu

    (cpu_ops, _), (tpu_ops, stored) = asyncio.run(go())
    return [
        emit(
            "mixed OPE-range + Paillier-SUM workload ops/sec (9 replicas, q=5)",
            tpu_ops,
            "ops/s",
            tpu_ops / cpu_ops,
            ops=args.ops,
            preload=args.preload,
            clients=args.clients,
            mix=MIX,
            cpu_ops_per_sec=round(cpu_ops, 1),
            stored_sets=stored,
            fold_path="device (forced)" if args.force_device else
            "adaptive (host below min_device_batch crossover)",
        )
    ]


if __name__ == "__main__":
    main()
