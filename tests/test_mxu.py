"""Known-answer tests for the hybrid VPU+MXU Montgomery multiply (v2).

Exactness is the whole game: every stage (carry normalization, both
products, the reduction behind them, full multiply, fold) is compared
against python int arithmetic. Runs in Pallas interpret mode on the CPU mesh
(tests/conftest.py); the same code paths compile for TPU.
"""

import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

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


def _value(T):
    """The integer each lane of a redundant (rows, B) accumulator carries."""
    T = np.asarray(T)
    return [
        sum(int(T[k, b]) << (16 * k) for k in range(T.shape[0]))
        for b in range(T.shape[1])
    ]


@pytest.mark.parametrize("passes,top,rows,B", [
    (1, 1 << 32, 24, 3),      # any u32 digit: one extract pass
    (1, 1 << 27, 520, 2),     # a product's digits, the rows of L = 512's mid
    (0, 1 << 17, 32, 5),      # a sum of two canonical digits and a carry
    (0, 1 << 16, 1, 4),       # one row: nothing to scan
])
def test_carry_norm_preserves_value(passes, top, rows, B):
    rng = np.random.default_rng(rows)
    x = rng.integers(0, top, size=(rows, B), dtype=np.uint64).astype(np.uint32)
    x[:, 0] = top - 1                                  # every digit at the bound
    if rows > 2:
        x[1:, 1] = 0xFFFF                              # one carry ripples to the top
        x[0, 1] = 0x10000 if top > 0x10000 else 0xFFFF
    digits, carry = mx.carry_norm(jnp.asarray(x), passes=passes)
    digits, carry = np.asarray(digits), np.asarray(carry)
    assert int(digits.max()) <= 0xFFFF
    got = [v + (int(c) << (16 * rows)) for v, c in zip(_value(digits), carry[0])]
    assert got == _value(x)


def _product(pairs, L, product):
    """The redundant (2L, B) product T the named in-kernel product leaves
    in VMEM for _redc, from one interpreted program over all B lanes."""
    B = len(pairs)
    product_fn = getattr(mx, f"_product_{product}")

    def body(a_ref, b_ref, out_ref, t_ref, *scratch):
        product_fn(a_ref, b_ref, t_ref, *scratch, L, B)
        out_ref[:, :] = t_ref[0 : 2 * L, :]

    return np.asarray(pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((2 * L, B), jnp.uint32),
        scratch_shapes=mx._product_scratch(product, L, B),
        interpret=True,
    )(_to_lm([a for a, _ in pairs], L), _to_lm([b for _, b in pairs], L)))


def _check_product(T, pairs, L, product):
    """value(T) = a*b, every digit inside the bound _redc's carry passes
    are promised: rows lo-halves and rows hi-halves a digit, and the
    Karatsuba middle term's 2^16."""
    rows = L // 2 if product == "karatsuba1" else L
    assert T.shape == (2 * L, len(pairs))
    assert _value(T) == [a * b for a, b in pairs]
    assert int(T.max()) < rows * (1 << 17) + (1 << 16)


def test_schoolbook_product_matches_python():
    rng = random.Random(2)
    L = 32  # 512-bit operands
    full = (1 << (16 * L)) - 1
    pairs = [(rng.getrandbits(16 * L), rng.getrandbits(16 * L)) for _ in range(4)]
    pairs += [(full, full), (0, full), (1, 1)]
    _check_product(_product(pairs, L, "schoolbook"), pairs, L, "schoolbook")


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


# ---------------------------------------------------------------------------
# the reduction (_redc) behind both products, in the one kernel
# ---------------------------------------------------------------------------


def _tail_modulus(rng, L):
    """An odd modulus in [3/4, 1) of R = 2^(16 L): (T + m*n) / R then lands
    below n, between n and R, and above R (the top carry), so every branch
    of the tail's select is met by random operands."""
    bits = 16 * L
    return rng.getrandbits(bits) | (3 << (bits - 2)) | 1


def _tail_branch(a, b, mctx):
    """Which way the tail's select goes for a*b, by python ints, at the
    rows the kernels run (R' = 2^(16 mctx.L), a shifted up by the pad)."""
    n, L = mctx.ctx.n, mctx.L
    R = 1 << (16 * L)
    T = (a << (16 * (L - mctx.ctx.L))) * b
    t = (T + (T * -pow(n, -1, R)) % R * n) // R
    return "top_carry" if t >= R else "t_ge_n" if t >= n else "t_lt_n"


@pytest.mark.parametrize("lanes", [1, 3, 128, 130])
@pytest.mark.parametrize("L,product", [
    (16, "schoolbook"), (16, "karatsuba1"), (33, "schoolbook"),
    (64, "schoolbook"), (64, "karatsuba1"), (128, "schoolbook"),
    (128, "karatsuba1"),
])
def test_reduction_kernels_match_python(L, product, lanes, monkeypatch):
    """mul2_lm = product, then _redc (carry passes, the two band products,
    the select) against python ints: L a tile's rows, odd (33: padded to 48)
    and wide; one lane, a few, a whole tile and one over; 0, 1, n - 1 and
    R mod n against each other; and, from a tile of lanes up, every branch
    of the tail."""
    monkeypatch.setattr(mx, "KARATSUBA_MIN_L", 0 if product == "karatsuba1" else 1 << 20)
    assert mx.product_for(L) == product
    rng = random.Random(1000 * L + lanes)
    n = _tail_modulus(rng, L)
    ctx = ModCtx.make(n)
    assert ctx.L == L
    mctx = mx.MxuCtx.make(ctx)
    assert mctx.L == -(-L // 16) * 16
    R = 1 << (16 * L)
    Rinv = pow(R, -1, n)
    special = [0, 1, n - 1, R % n]
    pairs = [(rng.randrange(n), rng.randrange(n))]
    pairs += [(x, y) for x in special for y in special]
    pairs += [(rng.randrange(n), rng.randrange(n)) for _ in range(lanes)]
    pairs = pairs[:lanes]
    A, B = [a for a, _ in pairs], [b for _, b in pairs]
    out = mx.mul2_lm(mctx, _to_lm(A, L), _to_lm(B, L), interpret=True)
    assert out.shape == (L, lanes)
    assert _from_lm(out) == [a * b * Rinv % n for a, b in pairs]
    if lanes >= 128:
        want = {"t_lt_n", "t_ge_n"} | ({"top_carry"} if mctx.L == L else set())
        assert {_tail_branch(a, b, mctx) for a, b in pairs} == want


@pytest.mark.parametrize("L", [16, 64])
def test_redc_takes_a_product_at_the_redundant_digit_bound(L):
    """_redc reads a redundant T out of VMEM. Here its digits go to 2^30,
    past anything a product leaves there (_check_product's bound), and its
    value to n*R - 1."""
    rng = random.Random(L)
    n = _tail_modulus(rng, L)
    mctx = mx.MxuCtx.make(ModCtx.make(n))
    R = 1 << (16 * L)
    vals = [rng.randrange(n * R) for _ in range(5)] + [n * R - 1, R - 1, 0]
    B = len(vals)
    T = np.zeros((2 * L, B), np.uint32)
    for lane, v in enumerate(vals):
        d = [int(x) for x in bn.int_to_limbs(v, 2 * L)]
        for k in range(2 * L - 1):      # digit k takes x*2^16 off digit k + 1
            x = min(d[k + 1], (1 << 14) - 1)
            d[k] += x << 16
            d[k + 1] -= x
        T[:, lane] = d
    assert _value(T) == vals and int(T.max()) >= 1 << 29

    def body(t_ref, m_mat, m_const, q_mat, q_const, comp_ref, out_ref):
        mx._redc(t_ref, m_mat, m_const, q_mat, q_const, comp_ref, out_ref, L, B)

    out = pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((L, B), jnp.uint32), interpret=True
    )(jnp.asarray(T), *mctx.operands())
    Rinv = pow(R, -1, n)
    assert _from_lm(out) == [v * Rinv % n for v in vals]


# ---------------------------------------------------------------------------
# the product chosen from L: one Karatsuba level from KARATSUBA_MIN_L up
# ---------------------------------------------------------------------------


def _recombination_operands(rng, L):
    """(a, b) pairs that drive every branch of the recombination: X = 2^(16h)
    splits an operand into halves a0 + a1*X."""
    h = L // 2
    X = 1 << (16 * h)
    full = (1 << (16 * L)) - 1              # all limbs 0xFFFF: both half sums overflow
    r = lambda bits=16 * L: rng.getrandbits(bits)
    return [
        (r(), r()), (r(), r()), (r(), r()),
        (full, full),                       # ca = cb = 1, every cross term
        (full, r()), (r(), full),           # one overflow bit only
        (r(16 * h), r()),                   # a1 = 0
        (r(16 * h) * X, r()),               # a0 = 0
        (0, r()), (1, r()), (r(), 0),
        (X - 1, X - 1),                     # z2 = 0, half sums all-ones, no overflow
        (X, X),                             # z0 = 0, z1 = 1
        (r(16 * h), r(16 * h) * X),         # a1 = b0 = 0: z0 = z2 = 0, mid = z1
        (0, 0),                             # the middle term is 0
        ((X - 1) * X, X - 1),               # a0 = b1 = 0, mid at its widest digits
    ]


@pytest.mark.parametrize("L", [16, 48, 384, 512])
def test_karatsuba_product_matches_python(L):
    pairs = _recombination_operands(random.Random(L), L)
    _check_product(_product(pairs, L, "karatsuba1"), pairs, L, "karatsuba1")


def _threshold_neighbours():
    T = mx.KARATSUBA_MIN_L
    return [T - 2 * mx.GROUP, T, T + 2 * mx.GROUP]


@pytest.mark.parametrize("L", sorted({512, *_threshold_neighbours()}))
def test_mul2_matches_python_on_both_sides_of_the_threshold(L):
    """mul2_lm with the product L chooses, at L = 512 and at the nearest
    limb counts below and above KARATSUBA_MIN_L that the split could take."""
    rng = random.Random(L)
    n = _rand_mod(rng, 16 * L)
    ctx = ModCtx.make(n)
    assert ctx.L == L
    want_product = "karatsuba1" if L >= mx.KARATSUBA_MIN_L else "schoolbook"
    assert mx.product_for(L) == want_product
    mctx = mx.MxuCtx.make(ctx)
    Rinv = pow(1 << (16 * L), -1, n)
    h = L // 2
    X = 1 << (16 * h)
    A = [rng.randrange(n) for _ in range(3)] + [n - 1, 0, 1, n - 1, rng.getrandbits(16 * h), X]
    B = [rng.randrange(n) for _ in range(3)] + [n - 1, n - 1, n - 1, 1, X * rng.getrandbits(16 * h - 1) % n, X]
    out = mx.mul2_lm(mctx, _to_lm(A, L), _to_lm(B, L), interpret=True)
    assert _from_lm(out) == [a * b * Rinv % n for a, b in zip(A, B)]


@pytest.mark.parametrize("L", [33, 40])
def test_a_limb_count_the_split_cannot_take_runs_schoolbook(L, monkeypatch):
    """L odd, or L/2 no multiple of GROUP: schoolbook whatever the
    threshold says, and exact."""
    monkeypatch.setattr(mx, "KARATSUBA_MIN_L", 0)
    assert mx.product_for(L) == "schoolbook"
    assert mx.product_for(L - L % 16 + 16) == "karatsuba1"
    rng = random.Random(L)
    n = _rand_mod(rng, 16 * L)
    ctx = ModCtx.make(n)
    assert ctx.L == L
    Rinv = pow(1 << (16 * L), -1, n)
    A = [rng.randrange(n), n - 1]
    B = [rng.randrange(n), n - 1]
    out = mx.mul2_lm(mx.MxuCtx.make(ctx), _to_lm(A, L), _to_lm(B, L), interpret=True)
    assert _from_lm(out) == [a * b * Rinv % n for a, b in zip(A, B)]


def _pallas_kernels(L):
    """Names of the kernels of the pallas_calls in mul2_lm's jaxpr."""
    import jax

    n = _rand_mod(random.Random(L), 16 * L)
    mctx = mx.MxuCtx.make(ModCtx.make(n))
    x = jax.ShapeDtypeStruct((L, 128), jnp.uint32)
    jaxpr = jax.make_jaxpr(lambda a, b: mx.mul2_lm(mctx, a, b, True))(x, x)
    return [
        e.params["jaxpr"].debug_info.func_name
        for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"
    ]


def test_the_product_is_chosen_by_the_limb_count_alone(monkeypatch):
    """One Pallas dispatch a multiply either way, product and reduction: the
    schoolbook product below the threshold, the Karatsuba one from it up;
    no environment variable and no argument has a say."""
    import inspect

    from dds_tpu.ops import flags

    assert _pallas_kernels(256) == ["mont_mul_schoolbook"]
    assert _pallas_kernels(384) == ["mont_mul_karatsuba1"]
    assert _pallas_kernels(512) == ["mont_mul_karatsuba1"]
    assert mx.product_for(256) == "schoolbook" and mx.product_for(64) == "schoolbook"
    assert mx.product_for(384) == "karatsuba1" and mx.product_for(512) == "karatsuba1"
    for name in ("DDS_KARATSUBA", "DDS_KERNEL", "DDS_PRODUCT", "DDS_PALLAS"):
        monkeypatch.setenv(name, "schoolbook")
    assert _pallas_kernels(512) == ["mont_mul_karatsuba1"]
    assert list(inspect.signature(mx.mul2_lm).parameters) == ["mctx", "a", "b", "interpret"]
    assert list(inspect.signature(mx.product_for).parameters) == ["L"]
    for fn in (mx._reduce2_fn, mx._pow2_fn):
        params = list(inspect.signature(fn.__wrapped__).parameters)
        assert "karatsuba" not in params and "product" not in params
    src = inspect.getsource(flags) + inspect.getsource(mx)
    assert "KARATSUBA" not in inspect.getsource(flags)
    assert "os.environ" not in inspect.getsource(mx)
    assert sorted(set(__import__("re").findall(r"DDS_[A-Z_]+", src))) == [
        "DDS_ANALYTICS_MAX_ROWS", "DDS_PROD_TB", "DDS_SECRET_DEVICE"
    ]
