"""The kernel seam's contract (ops/kernel), over every family that exists.

Known-answer tests: each family's multiply, both fold trees with the
fold's fix-up, and the backend's modexp are held to python `pow` / `* %`
big-int arithmetic. "v2" runs its Pallas product through the interpreter
here (slow), so the modulus is kept small (256-bit); on a TPU the same
paths compile via Mosaic and chip_smoke.py holds them to python ints at
Paillier-2048 scale.
"""

import random

import numpy as np
import pytest

from dds_tpu.ops import bignum as bn
from dds_tpu.ops import kernel
from dds_tpu.ops.montgomery import ModCtx

INTERPRET = True  # compiled only on real TPU hardware

families = pytest.mark.parametrize("family", kernel.FAMILIES)


@pytest.fixture(scope="module")
def ctx():
    rng = random.Random(0xDD5)
    n = rng.getrandbits(256) | (1 << 255) | 1
    return ModCtx.make(n)


@families
def test_mul_matches_python(ctx, family):
    rng = random.Random(1)
    n = ctx.n
    K = 6
    a = [rng.randrange(n) for _ in range(K)]
    b = [rng.randrange(n) for _ in range(K)]
    R_inv = pow(1 << (16 * ctx.L), -1, n)
    want = [x * y * R_inv % n for x, y in zip(a, b)]
    abm, bbm = bn.ints_to_batch(a, ctx.L), bn.ints_to_batch(b, ctx.L)
    bm = kernel.mont_mul(ctx, family, INTERPRET)(abm, bbm)
    assert bn.batch_to_ints(np.asarray(bm)) == want
    lm = kernel.mont_mul(ctx, family, INTERPRET, layout="lm")(abm.T, bbm.T)
    assert bn.batch_to_ints(np.asarray(lm).T) == want


@families
@pytest.mark.parametrize("K", [1, 2, 3, 5, 8])
def test_reduce_mul_matches_python(ctx, family, K):
    """Both trees, any leaf count: an odd K pads, K = 1 folds nothing."""
    rng = random.Random(K)
    n = ctx.n
    cs = [rng.randrange(1, n) for _ in range(K)]
    want = 1
    for c in cs:
        want = want * c % n
    mul = kernel.mont_mul(ctx, family, INTERPRET)
    one_mont = np.asarray(ctx.one_mont)
    fix = np.asarray(kernel.fold_fix(ctx, K))[None, :]
    batch = bn.ints_to_batch(cs, ctx.L)
    P2 = 1 << (K - 1).bit_length()
    padded = np.concatenate([batch, np.tile(one_mont, (P2 - K, 1))])
    halved = mul(kernel.halving_tree(mul, padded), fix)
    assert bn.limbs_to_int(np.asarray(halved)[0]) == want
    paired = mul(kernel.pairwise_tree(mul, batch, one_mont), fix)
    assert bn.limbs_to_int(np.asarray(paired)[0]) == want


@families
@pytest.mark.parametrize("exp", [0, 1, 2, 65537, (1 << 64) + 12345])
def test_pow_mod_matches_python(ctx, family, exp):
    from dds_tpu.models.backend import TpuBackend

    rng = random.Random(exp % 97)
    n = ctx.n
    bases = [rng.randrange(1, n) for _ in range(3)]
    be = TpuBackend(pallas=family == "v2", min_device_batch=0)
    assert be.fold_kernel() == family
    assert be.powmod_batch(bases, exp, n) == [pow(b, exp, n) for b in bases]


def test_a_family_that_does_not_exist_raises(ctx):
    with pytest.raises(ValueError, match="unknown kernel family"):
        kernel.mont_mul(ctx, "cios", INTERPRET)


def test_fn_cache_builds_once_and_evicts_the_oldest_at_its_bound(monkeypatch):
    monkeypatch.setattr(kernel, "_FN_CACHE", {})
    built = []

    def get(i):
        return kernel.fn_cache("t", i, lambda: built.append(i) or f"fn{i}")

    for i in range(kernel._FN_CACHE_MAX):
        assert get(i) == f"fn{i}"
    assert get(0) == "fn0" and built.count(0) == 1   # a hit builds nothing
    get("one more")
    assert len(kernel._FN_CACHE) == kernel._FN_CACHE_MAX
    assert ("t", 0) not in kernel._FN_CACHE          # oldest out
    assert ("t", 1) in kernel._FN_CACHE
    assert get(0) == "fn0" and built.count(0) == 2   # rebuilt on return


def test_backend_pallas_fold_matches_cpu(ctx):
    from dds_tpu.models.backend import CpuBackend, TpuBackend

    rng = random.Random(7)
    n = ctx.n
    cs = [rng.randrange(1, n) for _ in range(9)]
    # min_device_batch=0: a 9-element fold must hit the Pallas kernel, not
    # the adaptive host fallback
    tpu = TpuBackend(pallas=True, min_device_batch=0)
    cpu = CpuBackend()
    assert tpu.modmul_fold(cs, n) == cpu.modmul_fold(cs, n)
    assert tpu.powmod_batch(cs[:2], 65537, n) == cpu.powmod_batch(cs[:2], 65537, n)
