"""BASELINE config #4: 4-replica BFT (f=1) end-to-end encrypted SUM.

Boots the full stack — 4 BFT-ABD replicas (quorum 3 = 2f+1), supervisor,
REST proxy — loads K Paillier-2048 rows through `PutSet` (client-side
encryption, HMAC'd quorum writes), then times `SumAll` requests end-to-end.

Every `SumAll` runs under BFT: with the tag-validated aggregate cache the
proxy validates ALL K cached sets with ONE batched tag-only quorum round
(`AbdClient.read_tags`), then folds the PSSE column homomorphically on the
configured crypto backend. The reference instead re-reads every set through
full 2-round-trip ABD quorums per aggregate (`DDSRestServer.scala:397-446`)
— pass --no-cache to reproduce that behavior. The decrypted result is
checked against the plaintext total before timing.

Two timings per backend:
- sequential: one blocking request at a time (latency, one
  host<->device round trip per request included);
- concurrent: `--concurrency` in-flight requests (serving throughput; the
  proxy folds in worker threads so device dispatches overlap).

Reported value = homomorphic adds/sec at the best throughput
(requests x (K-1) / wall); vs_baseline = tpu/cpu on this host.

Usage: python -m benchmarks.bft_sum [--k 8192] [--requests 6]
       [--concurrency 8] [--no-cache]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time

from benchmarks.common import emit

PSSE_POS = 2  # canonical schema column 2 is PSSE (client.conf:50-61)

# the BASELINE.json north-star metric, shared with bench.py's headline
METRIC = "end-to-end encrypted SUM adds/sec @ Paillier-2048, 4-replica BFT f=1"


def run_both(k: int, requests: int, concurrency: int, cache: bool = True):
    """Measure both backends on one generated row set; returns (cpu, tpu)
    result dicts. The single orchestration shared by this module's CLI and
    bench.py's worker."""
    from dds_tpu.bench_key import bench_paillier_key

    key = bench_paillier_key()
    enc_rows, total = make_rows(k, key)

    async def go():
        cpu = await _bench_backend(
            "cpu", enc_rows, total, requests, concurrency, cache, key
        )
        tpu = await _bench_backend(
            "tpu", enc_rows, total, requests, concurrency, cache, key
        )
        return cpu, tpu

    return asyncio.run(go())


async def _bench_backend(backend: str, enc_rows: list, total: int, requests: int,
                         concurrency: int, cache: bool, key) -> dict:
    from dds_tpu.http.miniserver import http_request
    from dds_tpu.run import launch
    from dds_tpu.utils.config import DDSConfig

    cfg = DDSConfig()
    cfg.replicas.endpoints = [f"replica-{i}" for i in range(4)]
    cfg.replicas.sentinent = []
    cfg.replicas.byz_quorum_size = 3   # 2f+1, f=1
    cfg.replicas.byz_max_faults = 1
    cfg.recovery.enabled = False       # no spares in this topology; keep timing clean
    cfg.proxy.port = 0
    cfg.proxy.crypto_backend = backend

    dep = await launch(cfg)
    dep.server.cfg.aggregate_cache = cache
    try:
        host, port = cfg.proxy.host, dep.server.cfg.port
        pk = key.public
        K = len(enc_rows)

        # ---- load phase: K PutSets through real ABD quorum writes -------
        t0 = time.perf_counter()
        bodies = [json.dumps({"contents": enc}).encode() for enc in enc_rows]
        sem = asyncio.Semaphore(64)  # bound concurrent sockets during load

        async def put(b):
            async with sem:
                return await http_request(host, port, "POST", "/PutSet", b)

        statuses = await asyncio.gather(*(put(b) for b in bodies))
        assert all(s == 200 for s, _ in statuses), "PutSet failures during load"
        put_s = time.perf_counter() - t0

        # ---- verify: SumAll decrypts to the plaintext total -------------
        target = f"/SumAll?position={PSSE_POS}&nsqr={pk.nsquare}"
        t0 = time.perf_counter()
        status, body = await http_request(host, port, "GET", target, timeout=300.0)
        cold_s = time.perf_counter() - t0
        assert status == 200, f"SumAll failed: {status}"
        got = key.decrypt(int(json.loads(body)["result"]))
        assert got == total, f"SumAll decrypts wrong: {got} != {total}"

        async def timed_get():
            status, _ = await http_request(host, port, "GET", target, timeout=300.0)
            assert status == 200

        # ---- sequential latency (tracer-phased) ------------------------
        from dds_tpu.utils.trace import tracer

        tracer.reset()
        seq = []
        for _ in range(requests):
            t0 = time.perf_counter()
            await timed_get()
            seq.append(time.perf_counter() - t0)
        # per-phase split of the sequential requests: validation round
        # (abd.read_tags), audit quorum reads (abd.fetch), fold dispatch
        # (proxy.fold), whole-aggregate bookkeeping (proxy.fetch_stored)
        phases = {
            name: s["mean_ms"]
            for name, s in tracer.summary().items()
            if name in ("abd.read_tags", "abd.fetch", "proxy.fold",
                        "proxy.fetch_stored", "http.GET.SumAll")
            and "mean_ms" in s
        }

        # ---- concurrent serving throughput -----------------------------
        rounds = max(2, requests // 2)
        t0 = time.perf_counter()
        for _ in range(rounds):
            await asyncio.gather(*(timed_get() for _ in range(concurrency)))
        conc_wall = time.perf_counter() - t0
        per_req = conc_wall / (rounds * concurrency)

        best = min(min(seq), per_req)
        return {
            "backend": backend,
            "adds_per_sec": (K - 1) / best,
            "sumall_ms_seq": min(seq) * 1e3,
            "sumall_ms_concurrent": per_req * 1e3,
            "sumall_ms_cold": cold_s * 1e3,
            "putset_ops_per_sec": K / put_s,
            "phase_mean_ms": phases,
        }
    finally:
        await dep.stop()


def make_rows(k: int, key, pool: int = 64) -> tuple[list, int]:
    """K rows with a Paillier-2048 ciphertext at PSSE_POS. Obfuscators come
    from a precomputed r^n pool (`PaillierPublicKey.blind`) so the loader
    costs one modmul per row, not one 2048-bit modexp; the fold workload and
    decrypt verification are unaffected. Non-PSSE columns are short plains —
    the timed SumAll phase folds only the ciphertext column."""
    pk = key.public
    blinds = [pk.blind() for _ in range(min(pool, k))]
    vals = list(range(1, k + 1))
    rows = [
        [i, f"name-{i}", pk.encrypt(v, rn=blinds[i % len(blinds)]),
         2, "a", "b", "c", "blob"]
        for i, v in enumerate(vals)
    ]
    return rows, sum(vals)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=8192, help="stored sets")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--no-cache", action="store_true",
                    help="reference behavior: full ABD re-read per aggregate")
    args = ap.parse_args(argv)

    cache = not args.no_cache
    cpu, tpu = run_both(args.k, args.requests, args.concurrency, cache)
    return [
        emit(
            METRIC,
            tpu["adds_per_sec"],
            "ops/s",
            tpu["adds_per_sec"] / cpu["adds_per_sec"],
            K=args.k,
            quorum=3,
            aggregate_cache=cache,
            concurrency=args.concurrency,
            sustained=True,
            cpu_adds_per_sec=round(cpu["adds_per_sec"], 1),
            tpu_sumall_ms_seq=round(tpu["sumall_ms_seq"], 2),
            tpu_sumall_ms_concurrent=round(tpu["sumall_ms_concurrent"], 2),
            tpu_sumall_ms_cold=round(tpu["sumall_ms_cold"], 2),
            cpu_sumall_ms_seq=round(cpu["sumall_ms_seq"], 2),
            cpu_sumall_ms_concurrent=round(cpu["sumall_ms_concurrent"], 2),
            putset_ops_per_sec=round(tpu["putset_ops_per_sec"], 1),
            tpu_phase_mean_ms=tpu["phase_mean_ms"],
            cpu_phase_mean_ms=cpu["phase_mean_ms"],
        )
    ]


if __name__ == "__main__":
    main()
