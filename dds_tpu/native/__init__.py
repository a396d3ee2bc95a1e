"""Native host-side bignum runtime: build-on-demand C++ CIOS via ctypes.

This package is the framework's native runtime component, standing in for
the reference's closed-source crypto jar (`hlib.hj.mlib`, `lib/README.txt:1`)
on the host side: Paillier/RSA modexp and modmul for the principals that
hold private keys (clients: encrypt/decrypt, `clt/DDSHttpClient.scala:131-134`
trust model) and for accelerator-less hosts. The TPU Pallas kernels in
`ops/mont_mxu.py` remain the batched data-plane path.

The C++ source ships in-package and compiles once on first use with g++
(-O3, native __uint128 CIOS, no external dependencies); the .so is cached
next to the source. Every entry point falls back to python big-ints when
the toolchain is unavailable, so importing this module never fails.

API: `powmod`, `powmod_batch`, `fold` (modular product of a list), all for
odd moduli (Montgomery); even moduli fall back to python.
"""

from __future__ import annotations

import ctypes
import functools
import logging
import os
import pathlib
import subprocess
import threading

import numpy as np

log = logging.getLogger("dds.native")

_SRC = pathlib.Path(__file__).with_name("ddsbn.cpp")
_SO = pathlib.Path(__file__).with_name("_ddsbn.so")
_LOCK = threading.Lock()
_LIB = None
_TRIED = False


def _build() -> pathlib.Path | None:
    if _SO.exists() and _SO.stat().st_mtime >= _SRC.stat().st_mtime:
        return _SO
    # No -march=native: the .so is cached on shared storage and may be
    # loaded by other hosts; generic codegen avoids SIGILL on older ISAs.
    # Compile to a per-process temp and os.replace so concurrent replica
    # processes never observe a truncated library.
    tmp = _SO.with_name(f"_ddsbn.{os.getpid()}.tmp.so")
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-o", str(tmp), str(_SRC)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return _SO
    except (subprocess.SubprocessError, OSError) as e:
        log.warning("native bignum build failed (%s); using python ints", e)
        tmp.unlink(missing_ok=True)
        return None


def _load():
    global _LIB, _TRIED
    if _TRIED:  # lock-free fast path: _LIB is assigned before _TRIED flips
        return _LIB
    with _LOCK:
        if _TRIED:
            return _LIB
        lib = None
        disabled = os.environ.get("DDS_NATIVE", "").strip().lower() in (
            "0", "false", "off", "no")
        so = None if disabled else _build()
        if so is not None:
            try:
                lib = ctypes.CDLL(str(so))
                assert lib.ddsbn_abi_version() == 1
                u64p = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
                lib.ddsbn_mont_mul.argtypes = [
                    ctypes.c_int, u64p, ctypes.c_uint64, u64p, u64p, u64p]
                lib.ddsbn_fold.argtypes = [
                    ctypes.c_int, u64p, ctypes.c_uint64, u64p, ctypes.c_long,
                    u64p, u64p]
                lib.ddsbn_exp.argtypes = [
                    ctypes.c_int, u64p, ctypes.c_uint64, u64p, u64p, u64p,
                    ctypes.c_int, u64p]
                lib.ddsbn_exp_batch.argtypes = [
                    ctypes.c_int, u64p, ctypes.c_uint64, u64p, u64p,
                    ctypes.c_long, u64p, ctypes.c_int, u64p]
            except (OSError, AssertionError, AttributeError) as e:
                log.warning("native bignum load failed (%s); using python ints", e)
                lib = None
        _LIB = lib
        _TRIED = True  # set after _LIB so fast-path readers see a settled value
        return _LIB


def available() -> bool:
    return _load() is not None


MAXL = 130  # must match ddsbn.cpp


def _words(x: int, L: int) -> np.ndarray:
    return np.frombuffer(x.to_bytes(L * 8, "little"), dtype="<u8").copy()


def _unwords(a: np.ndarray) -> int:
    return int.from_bytes(a.tobytes(), "little")


def mont_consts_uncached(n: int) -> tuple[int, int, int]:
    """(L, n0inv, R2 mod n) for odd modulus n — computed fresh, cached
    NOWHERE in this module. The entry point for callers that manage the
    lifetime of SECRET moduli themselves (dds_tpu.sanctum holds these per
    key and drops them with it); the lru-cached `_mont_consts` below must
    only ever see public moduli, because its entries outlive every key
    object (tools/secret_lint.py enforces the split)."""
    L = -(-n.bit_length() // 64)
    R = 1 << (64 * L)
    n0inv = (-pow(n % (1 << 64), -1, 1 << 64)) % (1 << 64)
    return L, n0inv, (R * R) % n


# public-parameter consts cache: bounds repeat host-side Montgomery setup
# for the handful of moduli a process serves (n, n^2, RSA n). Secret
# moduli route through mont_consts_uncached — see its docstring.
_mont_consts = functools.lru_cache(maxsize=256)(mont_consts_uncached)


def _usable(n: int) -> bool:
    return n % 2 == 1 and n > 1 and n.bit_length() <= 64 * MAXL and _load() is not None


def _exp_words(exp: int) -> tuple[np.ndarray, int]:
    """(little-endian u64 words, nibble count) for a positive exponent."""
    nibbles = -(-exp.bit_length() // 4)
    return _words(exp, -(-exp.bit_length() // 64)), nibbles


def powmod(base: int, exp: int, mod: int) -> int:
    """pow(base, exp, mod) on the native path (odd mod); python fallback."""
    if exp < 0 or not _usable(mod):
        return pow(base, exp, mod)
    if exp == 0:
        return 1 % mod
    L, n0, r2 = _mont_consts(mod)
    ew, nibbles = _exp_words(exp)
    out = np.zeros(L, dtype=np.uint64)
    _LIB.ddsbn_exp(L, _words(mod, L), n0, _words(r2, L),
                   _words(base % mod, L), ew, nibbles, out)
    return _unwords(out)


def _exp_batch_impl(bases: list[int], exp: int, mod: int,
                    consts: tuple[int, int, int]) -> list[int]:
    L, n0, r2 = consts
    ew, nibbles = _exp_words(exp)
    bw = np.stack([_words(b % mod, L) for b in bases])
    out = np.zeros_like(bw)
    _LIB.ddsbn_exp_batch(L, _words(mod, L), n0, _words(r2, L),
                         np.ascontiguousarray(bw), len(bases), ew, nibbles, out)
    return [_unwords(out[i]) for i in range(len(bases))]


def powmod_batch(bases: list[int], exp: int, mod: int) -> list[int]:
    """Shared-exponent batch modexp (GIL released for the whole batch).
    PUBLIC moduli only: consts are memoized module-wide (see
    mont_consts_uncached for the secret-material contract)."""
    if exp < 0 or not _usable(mod):
        return [pow(b, exp, mod) for b in bases]
    if exp == 0:
        return [1 % mod] * len(bases)
    if not bases:
        return []
    return _exp_batch_impl(bases, exp, mod, _mont_consts(mod))


def powmod_batch_with_consts(bases: list[int], exp: int, mod: int,
                             consts: tuple[int, int, int] | None) -> list[int]:
    """powmod_batch with CALLER-HELD Montgomery consts (from
    mont_consts_uncached): nothing about `mod` is retained in this module
    after the call — the host fast path for secret CRT moduli. `consts`
    None (or an unusable modulus / toolchain-less host) falls back to
    python pow, which also retains nothing."""
    if consts is None or exp < 0 or not _usable(mod):
        return [pow(b, exp, mod) for b in bases]
    if exp == 0:
        return [1 % mod] * len(bases)
    if not bases:
        return []
    return _exp_batch_impl(bases, exp, mod, consts)


def fold(cs: list[int], mod: int) -> int:
    """prod(cs) % mod (the CPU-side homomorphic-aggregate fold)."""
    if not cs:
        return 1 % mod
    if not _usable(mod):
        acc = 1
        for c in cs:
            acc = acc * c % mod
        return acc
    L, n0, _ = _mont_consts(mod)
    R = 1 << (64 * L)
    fix = _words(pow(R % mod, len(cs), mod), L)
    batch = np.stack([_words(c % mod, L) for c in cs])
    out = np.zeros(L, dtype=np.uint64)
    _LIB.ddsbn_fold(L, _words(mod, L), n0, np.ascontiguousarray(batch),
                    len(cs), fix, out)
    return _unwords(out)
