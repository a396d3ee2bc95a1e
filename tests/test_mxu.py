"""Known-answer tests for the hybrid VPU+MXU Montgomery multiply (v2).

Exactness is the whole game: every stage (carry normalization, schoolbook
product, band-matmul reduction, full multiply, fold) is compared against
python int arithmetic. Runs in Pallas interpret mode on the CPU mesh
(tests/conftest.py); the same code paths compile for TPU.
"""

import random

import numpy as np
import jax.numpy as jnp
import pytest

from dds_tpu.ops import bignum as bn
from dds_tpu.ops import mont_mxu as mx
from dds_tpu.ops.montgomery import ModCtx


def _rand_mod(rng, bits):
    while True:
        n = rng.getrandbits(bits) | (1 << (bits - 1)) | 1
        if n % 2:
            return n


def _to_lm(vals, L):
    return jnp.asarray(bn.ints_to_batch(vals, L)).T


def _from_lm(x):
    return bn.batch_to_ints(np.asarray(x).T)


def test_carry_norm_preserves_value_16():
    rng = np.random.default_rng(0)
    rows, B = 24, 3
    x = rng.integers(0, 1 << 31, size=(rows, B), dtype=np.uint32)
    digits, carry = mx.carry_norm(jnp.asarray(x))
    digits, carry = np.asarray(digits), np.asarray(carry)
    for b in range(B):
        want = sum(int(x[k, b]) << (16 * k) for k in range(rows))
        got = sum(int(digits[k, b]) << (16 * k) for k in range(rows))
        got += int(carry[0, b]) << (16 * rows)
        assert got == want
        assert digits[:, b].max() <= 0xFFFF


def test_carry_norm_preserves_value_8():
    rng = np.random.default_rng(1)
    rows, B = 32, 2
    x = rng.integers(0, 1 << 25, size=(rows, B), dtype=np.uint32)
    digits, carry = mx.carry_norm(jnp.asarray(x), bits=8)
    digits, carry = np.asarray(digits), np.asarray(carry)
    for b in range(B):
        want = sum(int(x[k, b]) << (8 * k) for k in range(rows))
        got = sum(int(digits[k, b]) << (8 * k) for k in range(rows))
        got += int(carry[0, b]) << (8 * rows)
        assert got == want
        assert digits[:, b].max() <= 0xFF


def test_prod_lm_matches_python():
    rng = random.Random(2)
    L = 32  # 512-bit operands
    vals_a = [rng.getrandbits(16 * L) for _ in range(4)]
    vals_b = [rng.getrandbits(16 * L) for _ in range(4)]
    T = mx.prod_lm(_to_lm(vals_a, L), _to_lm(vals_b, L), interpret=True)
    digits, carry = mx.carry_norm(T)
    assert int(np.asarray(carry).max()) == 0
    got = _from_lm(digits)
    for g, a, b in zip(got, vals_a, vals_b):
        assert g == a * b


def test_mul2_odd_limb_count():
    """Moduli whose limb count is not a multiple of the kernel's GROUP
    (e.g. 520-bit -> L=33) must work via zero-padded limbs."""
    rng = random.Random(33)
    n = _rand_mod(rng, 520)
    ctx = ModCtx.make(n)
    assert ctx.L % mx.GROUP != 0
    mctx = mx.MxuCtx.make(ctx)
    R = 1 << (16 * ctx.L)
    Rinv = pow(R, -1, n)
    vals_a = [rng.randrange(n) for _ in range(3)]
    vals_b = [rng.randrange(n) for _ in range(3)]
    out = mx.mul2_lm(
        mctx, _to_lm(vals_a, ctx.L), _to_lm(vals_b, ctx.L), interpret=True
    )
    for g, a, b in zip(_from_lm(out), vals_a, vals_b):
        assert g == (a * b * Rinv) % n


@pytest.mark.parametrize("bits", [512, 1024])
def test_mul2_matches_python(bits):
    rng = random.Random(bits)
    n = _rand_mod(rng, bits)
    ctx = ModCtx.make(n)
    mctx = mx.MxuCtx.make(ctx)
    R = 1 << (16 * ctx.L)
    Rinv = pow(R, -1, n)
    vals_a = [rng.randrange(n) for _ in range(5)] + [0, n - 1]
    vals_b = [rng.randrange(n) for _ in range(5)] + [n - 1, n - 1]
    out = mx.mul2_lm(
        mctx, _to_lm(vals_a, ctx.L), _to_lm(vals_b, ctx.L), interpret=True
    )
    for g, a, b in zip(_from_lm(out), vals_a, vals_b):
        assert g == (a * b * Rinv) % n


def test_reduce_mul2_matches_python_and_jnp():
    rng = random.Random(7)
    n = _rand_mod(rng, 512)
    ctx = ModCtx.make(n)
    mctx = mx.MxuCtx.make(ctx)
    for K in (1, 2, 3, 7, 16):
        cs = [rng.randrange(n) for _ in range(K)]
        want = 1
        for c in cs:
            want = want * c % n
        batch = bn.ints_to_batch(cs, ctx.L)
        got2 = bn.batch_to_ints(np.asarray(mx.reduce_mul2(mctx, batch, interpret=True)))[0]
        assert got2 == want, f"v2 fold wrong at K={K}"
        got1 = bn.batch_to_ints(np.asarray(ctx.reduce_mul(batch)))[0]
        assert got1 == want, f"jnp fold wrong at K={K}"


@pytest.mark.parametrize("bits,ebits", [(256, 17), (256, 64), (512, 130)])
def test_pow_mod2_matches_python(bits, ebits):
    """v2 windowed modexp ladder (table + scan over mul2_lm) vs pow()."""
    import random

    from dds_tpu.ops import mont_mxu as mx

    rng = random.Random(bits * 1000 + ebits)
    n = rng.getrandbits(bits) | (1 << (bits - 1)) | 1
    ctx = ModCtx.make(n)
    mctx = mx.MxuCtx.make(ctx)
    bases = [rng.randrange(1, n) for _ in range(5)]
    exp = rng.getrandbits(ebits) | 1
    out = mx.pow_mod2(mctx, bn.ints_to_batch(bases, ctx.L), exp)
    assert bn.batch_to_ints(np.asarray(out)) == [pow(b, exp, n) for b in bases]


def test_pow_mod2_zero_exponent():
    import random

    from dds_tpu.ops import mont_mxu as mx

    rng = random.Random(77)
    n = rng.getrandbits(256) | (1 << 255) | 1
    ctx = ModCtx.make(n)
    mctx = mx.MxuCtx.make(ctx)
    bases = [rng.randrange(1, n) for _ in range(3)]
    out = mx.pow_mod2(mctx, bn.ints_to_batch(bases, ctx.L), 0)
    assert bn.batch_to_ints(np.asarray(out)) == [1, 1, 1]
