"""The kernel seam: the decisions every composite fold shares, in one place.

Two Montgomery multiply families exist: "jnp" (ops/montgomery, the portable
scan kernels: the CPU path and the tests' reference) and "v2" (ops/mont_mxu,
Pallas product, schoolbook or one Karatsuba level by limb count, + MXU
band-matmul REDC with its digit work in Pallas kernels: what a TPU serves). Callers
(models/backend, ops/foldmany, parallel/mesh, resident/plane, ops/predicate)
ask here for the multiply of a family, the two fold-tree shapes, the fold's
domain fix-up, the interpret probe and the bounded cache of jitted callables;
none of them names a kernel module itself.

R-power accounting, shared by both trees: K plain-domain leaves plus any
number of Montgomery-identity pads (R mod n) through any tree shape yield
prod * R^-(K-1) (a pad contributes R, an internal multiply R^-1), so one
final multiply by `fold_fix(ctx, K)` = R^K mod n lands in the plain domain.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp

from dds_tpu.obs import kprof
from dds_tpu.ops import bignum as bn

FAMILIES = ("jnp", "v2")


def check_family(family: str) -> str:
    if family not in FAMILIES:
        raise ValueError(f"unknown kernel family {family!r} (have {FAMILIES})")
    return family


def interpret_default() -> bool:
    """Pallas kernels compile on a TPU and run interpreted anywhere else."""
    return jax.default_backend() != "tpu"


def mont_mul(ctx, family: str, interpret: bool, *, layout: str = "bm"):
    """`family`'s Montgomery multiply a, b -> a*b*R^-1 mod ctx.n as a
    traceable callable over canonical u32 limbs, batch-major (B, L)
    (`layout="bm"`) or limbs-major (L, B) (`"lm"`: v2's native layout, so
    a chain of multiplies pays no transposes)."""
    check_family(family)
    if family == "v2":
        from dds_tpu.ops import mont_mxu

        mctx = mont_mxu.MxuCtx.make(ctx)
        if layout == "lm":
            return lambda a, b: mont_mxu.mul2_lm(mctx, a, b, interpret)
        return lambda a, b: mont_mxu.mul2_lm(mctx, a.T, b.T, interpret).T
    from dds_tpu.ops.montgomery import _mont_mul_raw

    N = jnp.asarray(ctx.N)
    n0inv = jnp.uint32(ctx.n0inv)
    if layout == "lm":
        return lambda a, b: _mont_mul_raw(a.T, b.T, N, n0inv).T
    return lambda a, b: _mont_mul_raw(a, b, N, n0inv)


def product_name(family: str, L: int) -> str:
    """The a*b product `family`'s multiply runs at L limbs, as a fold's span
    names it: what v2 chose from L (mont_mxu.product_for), or "cios" for
    the jnp scans, which interleave the product with the reduction."""
    check_family(family)
    if family == "v2":
        from dds_tpu.ops import mont_mxu

        return mont_mxu.product_for(L)
    return "cios"


def halving_tree(mul, x, axis: int = 0, width: int = 1):
    """Fold `axis` of x by multiplying its lower half by its upper half
    until `width` entries remain; x.shape[axis] / width a power of two."""
    pre = (slice(None),) * axis
    w = x.shape[axis]
    while w > width:
        h = w // 2
        x = mul(x[pre + (slice(None, h),)], x[pre + (slice(h, 2 * h),)])
        w = h
    return x


def pairwise_tree(mul, x, identity):
    """Fold axis 0 of x, any leaf count, by multiplying neighbours; an odd
    level is padded with `identity` (the Montgomery identity R mod n)."""
    while x.shape[0] > 1:
        if x.shape[0] % 2:
            x = jnp.concatenate([x, identity[None, :]], axis=0)
        x = mul(x[0::2], x[1::2])
    return x


@functools.lru_cache(maxsize=512)
def fold_fix(ctx, K: int):
    """Device-resident R^K mod n, the fix-up of a K-term fold (cached: the
    proxy folds the same store size again and again, and the host modexp
    and its transfer would otherwise be paid by every aggregate)."""
    R = 1 << (bn.LIMB_BITS * ctx.L)
    return jax.device_put(bn.int_to_limbs(pow(R % ctx.n, K, ctx.n), ctx.L))


# Jitted callables keyed by what their closures bake in (modulus, family,
# interpret, mesh, ...), never by shapes: jit retraces per shape under one
# entry. Bounded, oldest out: on the serving path the modulus comes from
# the client's `nsqr`, and every new one costs a compile and a retained
# executable. Folds run on proxy worker threads, so insert and eviction
# share a lock.
_FN_CACHE: dict = {}
_FN_CACHE_MAX = 64
_FN_CACHE_LOCK = threading.Lock()


def fn_cache(name: str, key, build):
    """The callable cached under (name, key), built by `build()` on a
    miss; the lookup counts as a compile-cache event of `name`."""
    key = (name, key)
    fn = _FN_CACHE.get(key)
    kprof.cache_event(name, hit=fn is not None)
    if fn is None:
        fn = build()
        with _FN_CACHE_LOCK:
            while len(_FN_CACHE) >= _FN_CACHE_MAX:
                _FN_CACHE.pop(next(iter(_FN_CACHE)), None)
            _FN_CACHE[key] = fn
    return fn
