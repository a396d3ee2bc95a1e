"""chip_smoke.py — the served SumAll path, once, on the one chip.

The quickest proof that the system still starts on a TPU: one process,
one chip, three phases, any failure a non-zero exit.

    python chip_smoke.py [--seed 0]

- `device`:  jax found a TPU (anything else exits here, before any result
             is printed), the native host bignum library built, and the
             versions and compile-cache directory in effect.
- `kernels`: every Pallas kernel a key size can select compiles for the
             chip (not interpret mode) and is exact against python ints:
             fold and modexp through `TpuBackend` (the v2 family) at
             RSA-1024/2048 and Paillier-2048/4096 widths.
- `serve`:   the main path at deployment size through `run.launch` and the
             REST routes — 4 in-process replicas (f=1, quorum 3), K
             client-encrypted 8-column rows loaded by `POST /PutSet`, then
             `GetSet`, `SumAll`, a further `PutSet`, a `WriteElement`
             overwrite, and more `SumAll`s; every aggregate decrypts to
             the plaintext total AND equals the python-int fold of the
             same ciphertexts, and the spans show the device folded them.

The last line of stdout is one JSON object, `{"ok": true, "device": ...}`.
It measures nothing a PR may claim: the seconds it prints are there to
tell a cold compile cache from a warm one.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import random
import sys
import time

PSSE_POS = 2      # canonical schema column 2 is PSSE (client.conf:50-61)
SERVE_K = 32768   # stored rows: 32 MiB of Paillier-2048 limbs on the device
FOLD_K = 2048     # kernels phase: residues per fold
POW_B = 256       # kernels phase: bases per modexp


def say(phase: str, **fields) -> None:
    print(f"[{phase}] {json.dumps(fields)}", flush=True)


def check(cond: bool, what: str) -> None:
    """A failed phase ends the run; `assert` would vanish under -O."""
    if not cond:
        raise SystemExit(f"FAILED: {what}")


# ------------------------------------------------------------------ device


def phase_device() -> dict:
    import dds_tpu  # noqa: F401 — places the compile cache; precedes jax
    import jax
    import jaxlib
    from importlib import metadata

    platform = jax.default_backend()
    if platform != "tpu":
        raise SystemExit(
            f"FAILED: phase device: jax.default_backend() is {platform!r}, "
            f"not 'tpu'"
        )
    from dds_tpu import native

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    has_native = native.available()
    say("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=metadata.version("libtpu"),
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        native=has_native)
    # false would mean client encryption and host folds silently run on
    # python ints, an order slower, with only a log line to say so
    check(has_native, "phase device: native bignum library "
          "(dds_tpu/native) did not build or load")
    return device


# ----------------------------------------------------------------- kernels


def _moduli() -> list[tuple[str, int]]:
    """The four key widths the deployments use, from the fixed bench keys
    (a modulus is a jit constant: fixed keys hit the compile cache)."""
    from dds_tpu.bench_key import bench_paillier_key

    k1, k2, k4 = (bench_paillier_key(b) for b in (1024, 2048, 4096))
    return [("rsa-1024", k1.n), ("rsa-2048", k2.n),
            ("paillier-2048", k2.nsquare), ("paillier-4096", k4.nsquare)]


def _backend_exact(rng, label: str, n: int, fold_k: int, pow_b: int) -> None:
    """Fold and modexp through TpuBackend on the device, against python."""
    from dds_tpu.models.backend import CpuBackend, TpuBackend

    be = TpuBackend(min_device_batch=0)
    kernel = be.fold_kernel()
    check(be.pallas is True and kernel == "v2", f"{label}/{kernel}: "
          "TpuBackend chose the jnp path, not the compiled Pallas kernels")
    cpu = CpuBackend()
    cs = [rng.randrange(1, n) for _ in range(fold_k)]
    t0 = time.perf_counter()
    got = be.modmul_fold(cs, n)
    fold_s = time.perf_counter() - t0
    check(got == cpu.modmul_fold(cs, n), f"{label}/{kernel}: modmul_fold "
          f"over {fold_k} residues differs from python ints")
    bases = [rng.randrange(1, n) for _ in range(pow_b)]
    exp = rng.getrandbits(64) | (1 << 63)
    t0 = time.perf_counter()
    got = be.powmod_batch(bases, exp, n)
    pow_s = time.perf_counter() - t0
    check(got == cpu.powmod_batch(bases, exp, n), f"{label}/{kernel}: "
          f"powmod_batch over {pow_b} bases differs from python ints")
    say("kernels", modulus=label, L=-(-n.bit_length() // 16), kernel=kernel,
        fold_first_call_s=round(fold_s, 2), pow_first_call_s=round(pow_s, 2))


def phase_kernels(seed: int, fold_k: int = FOLD_K, pow_b: int = POW_B) -> None:
    rng = random.Random(seed)
    moduli = _moduli()
    for label, n in moduli:
        _backend_exact(rng, label, n, fold_k, pow_b)


# ------------------------------------------------------------------- serve


def make_rows(rng, key, k: int) -> tuple[list[list], list[int]]:
    """k 8-column rows with a Paillier ciphertext of a seeded plaintext in
    the PSSE column. Row i's obfuscator is (r0 g^i)^n: a different r for
    every row at one modmul each, and two 2048-bit modexps in all."""
    from dds_tpu.native import powmod_batch

    pk, n, n2 = key.public, key.n, key.nsquare
    rn, step = powmod_batch([rng.randrange(2, n) for _ in range(2)], n, n2)
    plains = [rng.randrange(1 << 32) for _ in range(k)]
    rows = []
    for i, v in enumerate(plains):
        rows.append([i, f"name-{i}", pk.encrypt(v, rn=rn),
                     2, "a", "b", "c", "blob"])
        rn = rn * step % n2
    return rows, plains


async def _serve(seed: int, k: int, warm: int) -> None:
    import jax

    from dds_tpu.bench_key import bench_paillier_key
    from dds_tpu.http.miniserver import http_request
    from dds_tpu.models.backend import CpuBackend
    from dds_tpu.obs import kprof
    from dds_tpu.run import launch
    from dds_tpu.utils.config import DDSConfig
    from dds_tpu.utils.trace import tracer

    rng = random.Random(seed)
    key = bench_paillier_key()   # fixed: n^2 is a constant of the executables
    n, n2 = key.n, key.nsquare
    cpu = CpuBackend()
    t0 = time.perf_counter()
    rows, plains = make_rows(rng, key, k + 1)   # the last one is written late
    encrypt_s = time.perf_counter() - t0

    cfg = DDSConfig()
    cfg.replicas.endpoints = [f"replica-{i}" for i in range(4)]
    cfg.replicas.sentinent = []
    cfg.replicas.byz_quorum_size = 3   # 2f+1, f=1
    cfg.replicas.byz_max_faults = 1
    cfg.recovery.enabled = False       # no spares in this topology
    cfg.proxy.port = 0
    cfg.proxy.crypto_backend = "tpu"
    dep = await launch(cfg)
    try:
        host, port = cfg.proxy.host, dep.server.cfg.port
        be = dep.server.backend
        check((be.name, be.platform, be.pallas) == ("tpu", "tpu", True),
              "phase serve: the proxy's backend is not compiled Pallas on "
              f"a TPU (name={be.name} platform={be.platform} "
              f"pallas={be.pallas})")

        async def call(method, target, body=None):
            data = None if body is None else json.dumps(body).encode()
            # the first SumAll compiles its fold tree inside the request
            status, out = await http_request(host, port, method, target,
                                             data, timeout=600.0)
            check(status == 200, f"phase serve: {method} "
                  f"{target.split('?')[0]} answered {status}")
            return out

        async def put(row):
            return (await call("POST", "/PutSet", {"contents": row})).decode()

        async def get(rkey):
            return json.loads(await call("GET", f"/GetSet/{rkey}"))["contents"]

        sumall = f"/SumAll?position={PSSE_POS}&nsqr={n2}"

        async def sum_all(stored: dict) -> float:
            """One SumAll, held to both references; returns its seconds."""
            t0 = time.perf_counter()
            body = await call("GET", sumall)
            secs = time.perf_counter() - t0
            got = int(json.loads(body)["result"])
            check(key.decrypt(got) == sum(p for _, p in stored.values()) % n,
                  "phase serve: SumAll does not decrypt to the plaintext "
                  "total")
            check(got == cpu.modmul_fold([c for c, _ in stored.values()], n2),
                  "phase serve: SumAll differs from the python-int fold of "
                  "the same ciphertexts")
            return secs

        # ---- load: k PutSets through HMAC'd quorum writes ---------------
        sem = asyncio.Semaphore(64)   # bound concurrent sockets

        async def bounded_put(row):
            async with sem:
                return await put(row)

        t0 = time.perf_counter()
        keys = await asyncio.gather(*(bounded_put(r) for r in rows[:k]))
        load_s = time.perf_counter() - t0
        # what the store should hold: record key -> (ciphertext, plaintext)
        stored = {rk: (r[PSSE_POS], p)
                  for rk, r, p in zip(keys, rows, plains)}
        check(len(stored) == k, "phase serve: PutSet returned duplicate keys")
        say("serve", K=k, encrypt_s=round(encrypt_s, 2),
            load_s=round(load_s, 2), putset_per_s=round(k / load_s, 1))

        # ---- an acknowledged write is read back -------------------------
        for i in (0, k // 2, k - 1):
            check(await get(keys[i]) == rows[i],
                  f"phase serve: GetSet of row {i} differs from what PutSet "
                  "acknowledged")

        tracer.reset()
        kprof.reset()
        first_s = await sum_all(stored)

        # ---- the store changes under the aggregate ----------------------
        new_key = await put(rows[k])
        stored[new_key] = (rows[k][PSSE_POS], plains[k])
        victim = keys[k // 3]
        plain = rng.randrange(1 << 32)
        cipher = key.public.encrypt(plain, r=rng.randrange(2, n))
        await call("PUT", f"/WriteElement/{victim}?position={PSSE_POS}",
                   {"value": cipher})
        stored[victim] = (cipher, plain)
        check((await get(victim))[PSSE_POS] == cipher,
              "phase serve: GetSet after WriteElement does not return the "
              "acknowledged ciphertext")
        check(await get(new_key) == rows[k],
              "phase serve: GetSet of the late PutSet differs")
        # misses the proxy's and the pool's identity memos: the path a
        # live store takes
        changed_s = await sum_all(stored)
        warm_s = [await sum_all(stored) for _ in range(warm)]

        # ---- the device did the folds -----------------------------------
        folds = tracer.events("kernel.fold")
        n_folds = 2 + warm
        check(len(folds) == n_folds, f"phase serve: {len(folds)} kernel.fold "
              f"spans for {n_folds} aggregates")
        check(all(e.meta.get("resident") is True for e in folds),
              "phase serve: a fold did not gather device-resident rows")
        check([e.meta.get("k") for e in folds] == [k] + [k + 1] * (n_folds - 1),
              "phase serve: fold widths do not match the store")
        ksum = kprof.kernel_summary()
        check(ksum["execute_ms"] > 0, "phase serve: no device execute time "
              "was recorded")
        pstats = be.store_for(n2).stats()
        check(pstats["rows"] >= k + 2, "phase serve: the device pool holds "
              f"{pstats['rows']} rows, fewer than the {k + 2} ciphertexts "
              "folded")
        mem = jax.devices()[0].memory_stats()
        check(mem is not None and mem["bytes_in_use"] >= pstats["bytes"],
              "phase serve: the device does not hold the pool's "
              f"{pstats['bytes']} bytes (memory_stats: {mem})")
        say("serve", K=k, sumall_first_s=round(first_s, 3),
            sumall_after_writes_s=round(changed_s, 3),
            sumall_warm_s=[round(s, 4) for s in warm_s],
            kernel_fold_spans=len(folds),
            kernel_execute_ms=ksum["execute_ms"],
            kernel_compile_ms=ksum["compile_ms"], pool=pstats,
            device_bytes_in_use=mem["bytes_in_use"],
            device_peak_bytes=mem.get("peak_bytes_in_use"))
    finally:
        await dep.stop()


def phase_serve(seed: int, k: int = SERVE_K, warm: int = 3) -> None:
    asyncio.run(_serve(seed, k, warm))


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds every residue, plaintext and obfuscator")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    device = phase_device()
    t1 = time.perf_counter()
    phase_kernels(args.seed)
    t2 = time.perf_counter()
    phase_serve(args.seed)
    t3 = time.perf_counter()
    say("done", phases=["device", "kernels", "serve"], seed=args.seed,
        device_s=round(t1 - t0, 1), kernels_s=round(t2 - t1, 1),
        serve_s=round(t3 - t2, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
