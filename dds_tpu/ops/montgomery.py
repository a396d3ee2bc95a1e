"""Batched Montgomery modular arithmetic (CIOS) on 16-bit limbs.

The core kernel of the whole framework: every Paillier / RSA-multiplicative
homomorphic operation (encrypt, decrypt, homomorphic add = modmul mod n^2,
homomorphic mult = modmul mod n) reduces to batched Montgomery multiplies.
This is the TPU-native replacement for the reference's per-ciphertext JVM
``BigInteger`` folds (``dds/http/DDSRestServer.scala:412-430, 505-524``).

Design (see SURVEY.md §7):

- Numbers live as ``(B, L)`` uint32 arrays of 16-bit limbs (``ops.bignum``).
- ``mont_mul`` is CIOS: a ``lax.scan`` over the L limbs of the first operand;
  each step is fully vectorized over (batch, limbs) with *redundant* carries
  (one vectorized carry pass per step keeps limbs < 2^17, no sequential
  ripple inside the hot loop).
- ``mont_exp`` is a fixed 4-bit-window ladder over a *shared* exponent (all
  batch rows use the same exponent — true for every scheme here: Paillier
  encrypt r^n, decrypt c^lambda, RSA e/d), as a scan over exponent digits.
- ``reduce_mul`` folds K ciphertexts into their modular product with a
  binary tree of mont_muls on plain-domain inputs; the accumulated
  R^-(K-1) factor is fixed up with one extra multiply by a host-computed
  R^K mod n. This makes a K-term homomorphic SUM cost ~1 modmul per term,
  with no domain conversion of the inputs.

Carry-bound argument for the CIOS step (base b = 2^16, uint32 lanes):
limbs enter each step < 2^17 (invariant); adding the lo/hi halves of
``a_i * B`` and ``m * N`` adds < 3 * 2^16; the single vectorized carry pass
at the end of the step restores limbs to < 2^16 + 2^3 < 2^17. All
intermediate values stay < 2^19 << 2^32. The final result is normalized with
one O(L) scan and conditionally reduced below n.
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp

from dds_tpu.ops.bignum import (
    LIMB_BITS,
    LIMB_MASK,
    int_to_limbs,
    n_limbs_for_bits,
    normalize,
    cond_sub,
)
from dds_tpu.ops.kernel import pairwise_tree

WINDOW = 4  # modexp window size (16-entry table)


def _mont_mul_raw(a, b, N, n0inv):
    """CIOS Montgomery multiply. a, b: (B, L) canonical; N: (L,); n0inv scalar.

    Returns (B, L) canonical, < n:  a * b * R^-1 mod n, R = 2^(16 L).
    """
    B, L = a.shape

    def step(t, ai):
        # t: (B, L+1) uint32, limbs < 2^17
        p = ai[:, None] * b                       # (B, L) < 2^32
        t = t.at[:, :-1].add(p & LIMB_MASK)
        t = t.at[:, 1:].add(p >> LIMB_BITS)
        m = (t[:, 0] * n0inv) & LIMB_MASK         # (B,)
        q = m[:, None] * N[None, :]
        t = t.at[:, :-1].add(q & LIMB_MASK)
        t = t.at[:, 1:].add(q >> LIMB_BITS)
        carry0 = t[:, 0] >> LIMB_BITS             # t[:,0] = 0 mod 2^16 by construction
        t = jnp.concatenate([t[:, 1:], jnp.zeros((B, 1), jnp.uint32)], axis=1)
        t = t.at[:, 0].add(carry0)
        c = t[:, :-1] >> LIMB_BITS                # one redundant-carry pass
        t = t.at[:, :-1].set(t[:, :-1] & LIMB_MASK)
        t = t.at[:, 1:].add(c)
        return t, None

    t0 = jnp.zeros((B, L + 1), jnp.uint32)
    t, _ = jax.lax.scan(step, t0, a.T)            # scan over a's limbs
    t, carry = normalize(t)
    del carry                                     # result < 2n < 2^(16L+1): top limb holds it
    N_ext = jnp.concatenate([N, jnp.zeros((1,), jnp.uint32)])
    t = cond_sub(t, N_ext)
    return t[:, :-1]


def _mont_exp_raw(base, exp_digits, one_mont, N, n0inv):
    """Shared-exponent 4-bit-window ladder.

    base: (B, L) in Montgomery domain. exp_digits: (E,) uint32, MSB-first
    4-bit digits. Returns base^exp * R^-(...) correction-free: result is in
    Montgomery domain (base^exp in domain).
    """
    mul = lambda x, y: _mont_mul_raw(x, y, N, n0inv)

    # table[d] = base^d (Montgomery domain), d in [0, 16)
    one_b = jnp.broadcast_to(one_mont, base.shape)
    tab = [one_b, base]
    for _ in range(2, 1 << WINDOW):
        tab.append(mul(tab[-1], base))
    table = jnp.stack(tab, axis=0)                # (16, B, L)

    def step(r, digit):
        for _ in range(WINDOW):
            r = mul(r, r)
        r = mul(r, jnp.take(table, digit, axis=0))
        return r, None

    r, _ = jax.lax.scan(step, one_b, exp_digits)
    return r


def _mont_mul_rowmod_raw(a, b, N, n0inv):
    """CIOS Montgomery multiply with PER-ROW moduli.

    a, b: (B, L) canonical; N: (B, L) — each row's own modulus limbs;
    n0inv: (B,) per-row Montgomery constants. Returns (B, L) canonical,
    row i being a[i] * b[i] * R^-1 mod N[i]. The per-row twin of
    `_mont_mul_raw`: every step is already elementwise over the batch
    axis, so a per-row modulus costs nothing extra — it exists so the
    Sanctum secret-material plane (dds_tpu/sanctum) can run both CRT
    decrypt legs (moduli p^2 and q^2) as ONE stacked dispatch. The
    carry-bound argument at the top of this module holds per row
    unchanged.
    """
    B, L = a.shape

    def step(t, ai):
        p = ai[:, None] * b                       # (B, L) < 2^32
        t = t.at[:, :-1].add(p & LIMB_MASK)
        t = t.at[:, 1:].add(p >> LIMB_BITS)
        m = (t[:, 0] * n0inv) & LIMB_MASK         # (B,)
        q = m[:, None] * N
        t = t.at[:, :-1].add(q & LIMB_MASK)
        t = t.at[:, 1:].add(q >> LIMB_BITS)
        carry0 = t[:, 0] >> LIMB_BITS
        t = jnp.concatenate([t[:, 1:], jnp.zeros((B, 1), jnp.uint32)], axis=1)
        t = t.at[:, 0].add(carry0)
        c = t[:, :-1] >> LIMB_BITS
        t = t.at[:, :-1].set(t[:, :-1] & LIMB_MASK)
        t = t.at[:, 1:].add(c)
        return t, None

    t0 = jnp.zeros((B, L + 1), jnp.uint32)
    t, _ = jax.lax.scan(step, t0, a.T)
    t, carry = normalize(t)
    del carry
    N_ext = jnp.concatenate([N, jnp.zeros((B, 1), jnp.uint32)], axis=1)
    t = cond_sub(t, N_ext)
    return t[:, :-1]


def _mont_exp_rowdigits_raw(base, exp_digits, one_mont, N, n0inv):
    """Per-row-exponent 4-bit-window ladder over per-row moduli.

    base: (B, L) Montgomery domain; exp_digits: (E, B) uint32 MSB-first
    4-bit digits — row b's exponent in column b (pad shorter exponents
    with LEADING zero digits: a zero digit squares the running identity
    and multiplies by table[0] = 1, a no-op); one_mont/N: (B, L);
    n0inv: (B,). Result stays in the Montgomery domain, like
    `_mont_exp_raw`.
    """
    mul = lambda x, y: _mont_mul_rowmod_raw(x, y, N, n0inv)

    tab = [one_mont, base]
    for _ in range(2, 1 << WINDOW):
        tab.append(mul(tab[-1], base))
    table = jnp.stack(tab, axis=0)                # (16, B, L)

    def step(r, digit):                           # digit: (B,)
        for _ in range(WINDOW):
            r = mul(r, r)
        sel = jnp.take_along_axis(
            table, digit.astype(jnp.int32)[None, :, None], axis=0
        )[0]                                      # (B, L): table[digit[b], b]
        return mul(r, sel), None

    r, _ = jax.lax.scan(step, one_mont, exp_digits)
    return r


def _exp_to_digits(exp: int) -> np.ndarray:
    """Python int -> MSB-first 4-bit digit array (at least one digit)."""
    if exp < 0:
        raise ValueError("negative exponent")
    ndig = max(1, -(-exp.bit_length() // WINDOW))
    return np.array(
        [(exp >> (WINDOW * i)) & ((1 << WINDOW) - 1) for i in range(ndig - 1, -1, -1)],
        dtype=np.uint32,
    )


# ModCtx.make's shared cache: an explicit bounded LRU rather than a
# functools.lru_cache so its CONTENTS are inspectable — the Sanctum
# key-hygiene regression test (tests/test_sanctum.py) asserts no
# secret-derived modulus ever lands here, and tools/secret_lint.py
# treats flows into this cache as violations. Secret CRT moduli must
# use dds_tpu.sanctum's per-key SecretModCtx instead: entries here
# outlive every key object.
_CTX_CACHE: "OrderedDict[tuple[int, int | None], ModCtx]" = OrderedDict()
_CTX_CACHE_MAX = 64
_CTX_CACHE_LOCK = threading.Lock()


def cached_moduli() -> list[int]:
    """The moduli currently held by ModCtx.make's shared cache (hygiene
    introspection; see _CTX_CACHE above)."""
    with _CTX_CACHE_LOCK:
        return [k[0] for k in _CTX_CACHE]


@dataclass(frozen=True, eq=False)
class ModCtx:
    """Precomputed Montgomery context for one odd modulus n.

    Holds the device constants for n: limb decomposition N, the Montgomery
    constant n0' = -n^-1 mod 2^16, R^2 mod n (for domain entry) and
    R mod n (the domain's multiplicative identity).
    """

    n: int
    L: int
    N: np.ndarray = field(repr=False)
    n0inv: np.uint32 = field(repr=False)
    R2: np.ndarray = field(repr=False)
    one_mont: np.ndarray = field(repr=False)

    @staticmethod
    def build(n: int, L: int | None = None) -> "ModCtx":
        """An UNCACHED context. Public-parameter callers want `make`;
        this exists for contexts whose lifetime a caller manages itself
        (the Sanctum secret plane builds its per-key twins from the same
        constants without touching the shared cache)."""
        if n % 2 == 0:
            raise ValueError("Montgomery modulus must be odd")
        if L is None:
            L = n_limbs_for_bits(n.bit_length())
        R = 1 << (LIMB_BITS * L)
        if n >= R:
            raise ValueError("modulus does not fit limb count")
        n0inv = np.uint32((-pow(n % (1 << LIMB_BITS), -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS))
        return ModCtx(
            n=n,
            L=L,
            N=int_to_limbs(n, L),
            n0inv=n0inv,
            R2=int_to_limbs((R * R) % n, L),
            one_mont=int_to_limbs(R % n, L),
        )

    @staticmethod
    def make(n: int, L: int | None = None) -> "ModCtx":
        """The cached entry point for PUBLIC moduli (n, n^2, RSA n): one
        shared context (and one set of compiled kernels hanging off it)
        per modulus, process-wide. Never call with secret-derived moduli
        — entries outlive keys; dds_tpu.sanctum owns that case."""
        key = (n, L)
        with _CTX_CACHE_LOCK:
            ctx = _CTX_CACHE.get(key)
            if ctx is not None:
                _CTX_CACHE.move_to_end(key)
                return ctx
        ctx = ModCtx.build(n, L)
        with _CTX_CACHE_LOCK:
            cached = _CTX_CACHE.get(key)
            if cached is not None:  # lost a benign build race: keep the first
                _CTX_CACHE.move_to_end(key)
                return cached
            while len(_CTX_CACHE) >= _CTX_CACHE_MAX:
                _CTX_CACHE.popitem(last=False)
            _CTX_CACHE[key] = ctx
        return ctx

    # -- jitted entry points (cached per context) ---------------------------

    @functools.cached_property
    def _jit_mont_mul(self):
        N, n0inv = jnp.asarray(self.N), jnp.uint32(self.n0inv)
        return jax.jit(lambda a, b: _mont_mul_raw(a, b, N, n0inv))

    @functools.cached_property
    def _jit_mont_exp(self):
        N, n0inv = jnp.asarray(self.N), jnp.uint32(self.n0inv)
        one = jnp.asarray(self.one_mont)
        return jax.jit(
            lambda base, digits: _mont_exp_raw(base, digits, one, N, n0inv)
        )

    @functools.cached_property
    def _jit_tree_reduce(self):
        """Binary-tree modular product of cs (K, L), K a power of two (so
        the tree never pads): prod(cs) * R^-(K-1) mod n."""
        N, n0inv = jnp.asarray(self.N), jnp.uint32(self.n0inv)
        return jax.jit(lambda cs: pairwise_tree(
            lambda a, b: _mont_mul_raw(a, b, N, n0inv), cs, None
        ))

    @functools.cached_property
    def _jit_to_mont(self):
        """Device-resident R^2 closed over; broadcast happens inside jit."""
        N, n0inv = jnp.asarray(self.N), jnp.uint32(self.n0inv)
        R2 = jnp.asarray(self.R2)
        return jax.jit(
            lambda x: _mont_mul_raw(x, jnp.broadcast_to(R2, x.shape), N, n0inv)
        )

    @functools.cached_property
    def _jit_from_mont(self):
        N, n0inv = jnp.asarray(self.N), jnp.uint32(self.n0inv)
        one = np.zeros((self.L,), np.uint32)
        one[0] = 1
        one = jnp.asarray(one)
        return jax.jit(
            lambda x: _mont_mul_raw(x, jnp.broadcast_to(one, x.shape), N, n0inv)
        )

    # -- public API ---------------------------------------------------------

    def mont_mul(self, a, b):
        """(B,L) x (B,L) -> a*b*R^-1 mod n."""
        return self._jit_mont_mul(a, b)

    def to_mont(self, x):
        return self._jit_to_mont(x)

    def from_mont(self, x):
        return self._jit_from_mont(x)

    def mul_mod(self, a, b):
        """Plain-domain a*b mod n: one domain entry + one multiply."""
        return self._jit_mont_mul(self.to_mont(a), b)

    def pow_mod(self, base, exp: int):
        """Plain-domain base^exp mod n with a shared (host-int) exponent."""
        if exp == 0:
            one = np.zeros((base.shape[0], self.L), np.uint32)
            one[:, 0] = 1
            return jnp.asarray(one)
        r = self._jit_mont_exp(self.to_mont(base), jnp.asarray(_exp_to_digits(exp)))
        return self.from_mont(r)

    def reduce_mul(self, cs):
        """Modular product of all K rows of cs (plain domain, K >= 1).

        The homomorphic-SUM / PRODUCT aggregate kernel: pads K to a power of
        two with R mod n (mont_mul's identity), tree-reduces, then fixes the
        accumulated R^-(K-1) with one multiply by R^K mod n.
        """
        K = cs.shape[0]
        P2 = 1 << max(0, (K - 1).bit_length())
        if P2 != K:
            pad = jnp.broadcast_to(jnp.asarray(self.one_mont), (P2 - K, self.L))
            cs = jnp.concatenate([jnp.asarray(cs), pad], axis=0)
        prod = self._jit_tree_reduce(cs)          # prod * R^-(K-1), (1, L)
        R = 1 << (LIMB_BITS * self.L)
        fix = int_to_limbs(pow(R % self.n, K, self.n), self.L)
        return self._jit_mont_mul(prod, jnp.asarray(fix)[None, :])
