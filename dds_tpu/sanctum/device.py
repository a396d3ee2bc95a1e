"""Sanctum device leg: the fused CRT-Paillier decrypt dispatch.

The CRT decrypt optimization (arxiv 2506.17935) on the batched limb
kernels, under the secret-material residency rules the public path does
not need:

- **One dispatch for both legs.** The B ciphertext residues mod p^2 and
  mod q^2 stack into a (2B, L) batch over the PER-ROW-modulus kernels
  (``ops.montgomery._mont_mul_rowmod_raw`` / ``_mont_exp_rowdigits_raw``)
  with the fixed per-key exponents p-1 / q-1 pre-decomposed into shared
  MSB-first window digits — two half-width modexps for the price of one
  batched ladder, instead of the two sequential full dispatches the old
  ``powmod_batch`` route paid.
- **No secret ever becomes a compile-time constant.** Every key-derived
  value (moduli limbs, n0inv, R^2, identity, exponent digits) is passed
  as a traced ARGUMENT, so compiled executables — in-memory and
  anywhere XLA may serialize them — contain shapes only.
- **Persistent compile cache bypassed.** Defense in depth on top of the
  above: compiles triggered inside the plane run with the persistent
  JAX compilation cache disabled (``compile_cache_bypass``), so no
  Sanctum executable is ever written to the on-disk cache that
  ``dds_tpu.__init__`` enables for the public kernels.
- **Per-plan jit, per-key lifetime.** Each plan wraps the raw kernel in
  its own ``jax.jit``; the compiled-executable cache hangs off that
  wrapper and dies with the plan (and the key). ``close()`` zero-fills
  the host numpy copies of every secret-derived array.

What the opt-in still exposes — and the host default does not — is
transient device (HBM) residency of p^2/q^2-derived values during the
dispatch; DEPLOY.md "Secret-material trust boundary (Sanctum)" spells
out that trade.
"""

from __future__ import annotations

import contextlib
import threading

import numpy as np
import jax
import jax.numpy as jnp

from dds_tpu.obs import kprof
from dds_tpu.ops import bignum as bn
from dds_tpu.ops.montgomery import (
    ModCtx,
    _exp_to_digits,
    _mont_exp_rowdigits_raw,
    _mont_mul_rowmod_raw,
)

# global (not per-plan): jax's config + cache-module state is process-wide
_BYPASS_LOCK = threading.RLock()


@contextlib.contextmanager
def compile_cache_bypass():
    """Disable the persistent JAX compilation cache around a compile.

    jax latches the cache object at first use, so flipping
    ``jax_compilation_cache_dir`` alone does NOT stop writes once any
    public kernel has compiled; the cache module must also be reset so
    it re-reads the (now empty) dir config. On exit the previous dir is
    restored and the cache reset again, so the next public compile
    re-initializes it normally.

    Process-global by nature (jax config is global): a public kernel
    compiling concurrently in another thread during the window is simply
    not persisted — it recompiles some other day. That failure mode
    loses a little warm-start time; the converse one writes secret-leg
    executables to disk. Fail-safe direction chosen accordingly.
    """
    from jax._src import compilation_cache as _cc

    with _BYPASS_LOCK:
        prev = jax.config.jax_compilation_cache_dir
        try:
            _cc.reset_cache()
            jax.config.update("jax_compilation_cache_dir", None)
            yield
        finally:
            # exactly what was in effect: the operator's directory or the
            # in-checkout default (dds_tpu/__init__), never a new one
            jax.config.update("jax_compilation_cache_dir", prev)
            _cc.reset_cache()


def _fused_crt_raw(bases, N, n0inv, R2, one_mont, digits):
    """Both CRT legs in one batch: rows [0, B) are residues mod p^2,
    rows [B, 2B) residues mod q^2. N/R2/one_mont are (2, L), n0inv (2,),
    digits (E, 2) — one column of exponent digits per leg, repeated to
    per-row form here (inside the trace, so the host passes each secret
    exactly once per call)."""
    twoB, L = bases.shape
    rep = twoB // 2
    Nr = jnp.repeat(N, rep, axis=0)
    n0r = jnp.repeat(n0inv, rep)
    R2r = jnp.repeat(R2, rep, axis=0)
    oner = jnp.repeat(one_mont, rep, axis=0)
    digr = jnp.repeat(digits, rep, axis=1)        # (E, 2B)
    base_m = _mont_mul_rowmod_raw(bases, R2r, Nr, n0r)   # to Montgomery
    r = _mont_exp_rowdigits_raw(base_m, digr, oner, Nr, n0r)
    plain_one = jnp.zeros_like(bases).at[:, 0].set(1)
    return _mont_mul_rowmod_raw(r, plain_one, Nr, n0r)   # from Montgomery


class SecretModCtx:
    """Per-instance Montgomery context for a SECRET odd modulus.

    The deliberate anti-twin of ``ModCtx.make``: plain construction, no
    module-level cache, no jitted entry points of its own (the plan owns
    the jit wrapper), and ``close()`` zero-fills the host limb arrays.
    Built from ``ModCtx.build`` (the uncached constructor) so the two
    families cannot drift numerically.
    """

    def __init__(self, n: int, L: int | None = None):
        ctx = ModCtx.build(n, L)  # uncached; transient, dropped below
        self.L = ctx.L
        # own writable copies: int_to_limbs already copies, but be
        # explicit — close() overwrites these in place
        self.N = np.array(ctx.N, dtype=np.uint32)
        self.n0inv = np.uint32(ctx.n0inv)
        self.R2 = np.array(ctx.R2, dtype=np.uint32)
        self.one_mont = np.array(ctx.one_mont, dtype=np.uint32)
        self.closed = False

    def close(self) -> None:
        for arr in (self.N, self.R2, self.one_mont):
            arr.fill(0)
        self.n0inv = np.uint32(0)
        self.closed = True


class SecretDevicePlan:
    """Per-key fused CRT decrypt plan (the device opt-in).

    Holds the two ``SecretModCtx`` legs, the stacked (2, L) constant
    arrays, the pre-decomposed exponent digit matrix, and a fresh
    ``jax.jit`` wrapper around ``_fused_crt_raw``. Batches pad to the
    next power of two with base 1 (1^e = 1, discarded) so compiled
    shapes stay few even without the persistent cache.
    """

    def __init__(self, key, chunk: int = 4096):
        p, q, n = key.p, key.q, key.n
        hp, hq, qinv = key._crt
        self.p, self.q, self.n = p, q, n
        self.p2, self.q2 = p * p, q * q
        self.hp, self.hq, self.qinv = hp, hq, qinv
        self.chunk = max(1, int(chunk))
        L = max(
            bn.n_limbs_for_bits(self.p2.bit_length()),
            bn.n_limbs_for_bits(self.q2.bit_length()),
        )
        self.L = L
        self.ctx_p = SecretModCtx(self.p2, L)
        self.ctx_q = SecretModCtx(self.q2, L)
        self._N = np.stack([self.ctx_p.N, self.ctx_q.N])
        self._n0 = np.array([self.ctx_p.n0inv, self.ctx_q.n0inv], np.uint32)
        self._R2 = np.stack([self.ctx_p.R2, self.ctx_q.R2])
        self._one = np.stack([self.ctx_p.one_mont, self.ctx_q.one_mont])
        dp = _exp_to_digits(p - 1)
        dq = _exp_to_digits(q - 1)
        E = max(len(dp), len(dq))
        digits = np.zeros((E, 2), np.uint32)  # leading zeros are no-ops
        digits[E - len(dp):, 0] = dp
        digits[E - len(dq):, 1] = dq
        self._digits = digits
        self._fn = jax.jit(_fused_crt_raw)
        self.closed = False

    def decrypt_batch(self, cs: list[int]) -> list[int]:
        if self.closed:
            raise RuntimeError("sanctum plan is closed (key scrubbed)")
        out: list[int] = []
        for i in range(0, len(cs), self.chunk):
            out.extend(self._dispatch(cs[i : i + self.chunk]))
        return out

    def _dispatch(self, cs: list[int]) -> list[int]:
        B = len(cs)
        if B == 0:
            return []
        Bp = 1 << max(0, (B - 1).bit_length())
        pad = [1] * (Bp - B)
        bases = np.concatenate([
            bn.ints_to_batch([c % self.p2 for c in cs] + pad, self.L),
            bn.ints_to_batch([c % self.q2 for c in cs] + pad, self.L),
        ])
        with compile_cache_bypass():
            x = np.asarray(kprof.profiled(
                "sanctum_crt",
                lambda: self._fn(
                    jnp.asarray(bases), jnp.asarray(self._N),
                    jnp.asarray(self._n0), jnp.asarray(self._R2),
                    jnp.asarray(self._one), jnp.asarray(self._digits),
                ),
                B=B,
            ))
        xps = bn.batch_to_ints(x[:B])
        xqs = bn.batch_to_ints(x[Bp : Bp + B])
        from dds_tpu.sanctum.plane import _crt_recombine

        return _crt_recombine(
            xps, xqs, self.p, self.q, self.n, self.hp, self.hq, self.qinv
        )

    def close(self) -> None:
        for arr in (self._N, self._R2, self._one, self._digits):
            arr.fill(0)
        self._n0.fill(0)
        self.ctx_p.close()
        self.ctx_q.close()
        self._fn = None  # drops the per-plan compiled-executable cache
        self.p = self.q = self.n = self.p2 = self.q2 = 0
        self.hp = self.hq = self.qinv = 0
        self.closed = True
