"""Mesh-sharded ciphertext ops on the virtual 8-device CPU mesh."""

import random

import numpy as np
import pytest

import jax

from dds_tpu.ops import bignum as bn
from dds_tpu.ops.montgomery import ModCtx, _exp_to_digits
from dds_tpu.parallel import make_mesh, sharded_pow_mod
from dds_tpu.parallel.mesh import sharded_reduce_mul_fixed

rng = random.Random(9)


def test_eight_virtual_devices():
    assert len(jax.devices()) == 8


@pytest.mark.parametrize("K", [8, 16, 37])
def test_sharded_reduce_mul_matches_int(K):
    n = rng.getrandbits(512) | (1 << 511) | 1
    ctx = ModCtx.make(n)
    mesh = make_mesh(8)
    cs_int = [rng.randrange(n) for _ in range(K)]
    cs = bn.ints_to_batch(cs_int, ctx.L)
    out = sharded_reduce_mul_fixed(ctx, cs, mesh)
    want = 1
    for c in cs_int:
        want = want * c % n
    assert bn.limbs_to_int(np.asarray(out)[0]) == want


@pytest.mark.parametrize("K", [8, 16, 37])
def test_ring_combine_matches_allgather(K):
    """The ppermute ring combine (ring-attention-style neighbor hops) must
    produce exactly the all_gather tree's result — same product, same
    Montgomery R accounting (D-1 multiplies either way)."""
    n = rng.getrandbits(512) | (1 << 511) | 1
    ctx = ModCtx.make(n)
    mesh = make_mesh(8)
    cs_int = [rng.randrange(n) for _ in range(K)]
    cs = bn.ints_to_batch(cs_int, ctx.L)
    out = sharded_reduce_mul_fixed(ctx, cs, mesh, ring=True)
    want = 1
    for c in cs_int:
        want = want * c % n
    assert bn.limbs_to_int(np.asarray(out)[0]) == want


def test_sharded_pow_mod_matches_int():
    n = rng.getrandbits(256) | (1 << 255) | 1
    ctx = ModCtx.make(n)
    mesh = make_mesh(8)
    exp = rng.getrandbits(64)
    bases_int = [rng.randrange(n) for _ in range(16)]
    bases = bn.ints_to_batch(bases_int, ctx.L)
    out = sharded_pow_mod(ctx, bases, _exp_to_digits(exp), mesh)
    assert bn.batch_to_ints(np.asarray(out)) == [pow(b, exp, n) for b in bases_int]


def test_sharded_matches_single_device_path():
    n = rng.getrandbits(256) | (1 << 255) | 1
    ctx = ModCtx.make(n)
    mesh = make_mesh(8)
    cs = bn.ints_to_batch([rng.randrange(n) for _ in range(24)], ctx.L)
    sharded = sharded_reduce_mul_fixed(ctx, cs, mesh)
    single = ctx.reduce_mul(cs)
    assert np.array_equal(np.asarray(sharded), np.asarray(single))


@pytest.mark.parametrize("D,K", [(3, 12), (5, 11), (7, 21)])
def test_sharded_reduce_non_power_of_two_mesh(D, K):
    """Regression: odd partial counts must pad with the Montgomery identity,
    not silently broadcast a short operand."""
    n = rng.getrandbits(256) | (1 << 255) | 1
    ctx = ModCtx.make(n)
    mesh = make_mesh(D)
    cs_int = [rng.randrange(n) for _ in range(K)]
    out = sharded_reduce_mul_fixed(ctx, bn.ints_to_batch(cs_int, ctx.L), mesh)
    want = 1
    for c in cs_int:
        want = want * c % n
    assert bn.limbs_to_int(np.asarray(out)[0]) == want


# ----------------------------------- scatter-gather tail combine edge cases


def test_combine_partials_empty_partition_raises():
    """An empty per-shard partition is a caller bug (the scatter path
    filters empty groups before dispatch): it must fail loudly, never
    invent a neutral result for an aggregate nobody computed."""
    from dds_tpu.parallel.mesh import combine_partials

    with pytest.raises(ValueError):
        combine_partials([], 97)


def test_combine_partials_single_shard_identity():
    """One shard owning every operand must combine to exactly its own
    partial (reduced mod n) — the S=1 degenerate case the router's
    single-group fast path relies on."""
    from dds_tpu.parallel.mesh import combine_partials

    n = rng.getrandbits(256) | (1 << 255) | 1
    p = rng.randrange(n)
    assert combine_partials([p], n) == p
    assert combine_partials([p + n], n) == p  # unreduced input normalizes


def test_combine_partials_neutral_elements():
    """Neutral-element handling for both aggregate families: a shard whose
    fold saw no effective operands contributes 1 (the modular-product
    identity) for SumAll (mod n^2 ciphertext adds) AND MultAll (mod n
    ciphertext products), and must never perturb the combined result."""
    from dds_tpu.parallel.mesh import combine_partials

    n = rng.getrandbits(128) | (1 << 127) | 1
    for modulus in (n, n * n):  # MultAll-style (n) and SumAll-style (n^2)
        ps = [rng.randrange(1, modulus) for _ in range(3)]
        want = 1
        for p in ps:
            want = want * p % modulus
        assert combine_partials(ps, modulus) == want
        # identity partials interleaved anywhere leave the result unchanged
        assert combine_partials([1] + ps[:1] + [1, 1] + ps[1:], modulus) == want
        assert combine_partials([1, 1, 1], modulus) == 1


@pytest.mark.parametrize("parts", [2, 3, 5, 7])
def test_combine_partials_matches_flat_fold_any_partition(parts):
    """Partition-independence: however K operands split across shards,
    the combined per-shard partials equal the flat fold bit-for-bit —
    the invariant the sharded SumAll/MatVec equality tests build on."""
    from dds_tpu.parallel.mesh import combine_partials

    n = rng.getrandbits(256) | (1 << 255) | 1
    ops = [rng.randrange(1, n) for _ in range(23)]
    flat = 1
    for o in ops:
        flat = flat * o % n
    cuts = sorted(rng.sample(range(1, len(ops)), parts - 1))
    partials = []
    for lo, hi in zip([0] + cuts, cuts + [len(ops)]):
        p = 1
        for o in ops[lo:hi]:
            p = p * o % n
        partials.append(p)
    assert combine_partials(partials, n) == flat


# ------------------------------------------- fast kernels under the mesh

@pytest.mark.parametrize("kernel", ["v2"])
def test_sharded_reduce_runs_fast_kernels(kernel):
    """The shard-local fold must run the v2 Pallas kernels (interpret
    mode on the CPU fabric) and still match python ints — the multi-chip
    path keeps single-chip kernel speed (VERDICT r4 #1)."""
    n = rng.getrandbits(512) | (1 << 511) | 1
    ctx = ModCtx.make(n)
    mesh = make_mesh(8)
    cs_int = [rng.randrange(n) for _ in range(21)]
    cs = bn.ints_to_batch(cs_int, ctx.L)
    out = sharded_reduce_mul_fixed(ctx, cs, mesh, kernel=kernel)
    want = 1
    for c in cs_int:
        want = want * c % n
    assert bn.limbs_to_int(np.asarray(out)[0]) == want


@pytest.mark.parametrize("kernel", ["v2"])
def test_sharded_pow_runs_fast_kernels(kernel):
    n = rng.getrandbits(256) | (1 << 255) | 1
    ctx = ModCtx.make(n)
    mesh = make_mesh(8)
    exp = rng.getrandbits(48)
    bases_int = [rng.randrange(n) for _ in range(16)]
    bases = bn.ints_to_batch(bases_int, ctx.L)
    out = sharded_pow_mod(ctx, bases, _exp_to_digits(exp), mesh, kernel=kernel)
    assert bn.batch_to_ints(np.asarray(out)) == [pow(b, exp, n) for b in bases_int]


def test_sharded_ring_with_v2_kernel():
    """ppermute ring combine composes with the v2 shard-local fold."""
    n = rng.getrandbits(512) | (1 << 511) | 1
    ctx = ModCtx.make(n)
    mesh = make_mesh(8)
    cs_int = [rng.randrange(n) for _ in range(16)]
    out = sharded_reduce_mul_fixed(
        ctx, bn.ints_to_batch(cs_int, ctx.L), mesh, ring=True, kernel="v2"
    )
    want = 1
    for c in cs_int:
        want = want * c % n
    assert bn.limbs_to_int(np.asarray(out)[0]) == want


def test_backend_mesh_dispatches_configured_kernel(monkeypatch):
    """TpuBackend(pallas=True, mesh=...) must hand kernel='v2'
    to the sharded fold/modexp — the wiring the r4 verdict found missing."""
    from dds_tpu.models.backend import TpuBackend
    from dds_tpu.parallel import mesh as pm

    seen = []
    orig_reduce, orig_pow = pm.sharded_reduce_mul_fixed, pm.sharded_pow_mod

    def spy_reduce(*a, **k):
        seen.append(("reduce", k.get("kernel", "jnp")))
        return orig_reduce(*a, **k)

    def spy_pow(*a, **k):
        seen.append(("pow", k.get("kernel", "jnp")))
        return orig_pow(*a, **k)

    monkeypatch.setattr(pm, "sharded_reduce_mul_fixed", spy_reduce)
    monkeypatch.setattr(pm, "sharded_pow_mod", spy_pow)

    n = rng.getrandbits(256) | (1 << 255) | 1
    be = TpuBackend(pallas=True, min_device_batch=0, mesh=make_mesh(4))
    cs = [rng.randrange(n) for _ in range(8)]
    want = 1
    for c in cs:
        want = want * c % n
    assert be.modmul_fold(cs, n) == want
    bases = [rng.randrange(n) for _ in range(4)]
    assert be.powmod_batch(bases, 65537, n) == [pow(b, 65537, n) for b in bases]
    assert ("reduce", "v2") in seen and ("pow", "v2") in seen
    # pallas off -> portable jnp kernels under the mesh
    be_jnp = TpuBackend(pallas=False, min_device_batch=0, mesh=make_mesh(4))
    assert be_jnp.modmul_fold(cs, n) == want
    assert seen[-1] == ("reduce", "jnp")


# ----------------------------------------------------- serving-path wiring

def test_tpu_backend_folds_through_mesh(monkeypatch):
    """TpuBackend(mesh=...) routes reduce_mul_device and powmod_batch
    through the sharded kernels — the serving-path wiring of §5.7."""
    from dds_tpu.models.backend import TpuBackend
    from dds_tpu.parallel import mesh as pm

    calls = {"reduce": 0, "pow": 0}
    orig_reduce, orig_pow = pm.sharded_reduce_mul_fixed, pm.sharded_pow_mod

    def spy_reduce(*a, **k):
        calls["reduce"] += 1
        return orig_reduce(*a, **k)

    def spy_pow(*a, **k):
        calls["pow"] += 1
        return orig_pow(*a, **k)

    monkeypatch.setattr(pm, "sharded_reduce_mul_fixed", spy_reduce)
    monkeypatch.setattr(pm, "sharded_pow_mod", spy_pow)

    n = rng.getrandbits(512) | (1 << 511) | 1
    be = TpuBackend(pallas=False, min_device_batch=0, mesh=make_mesh(4))
    cs = [rng.randrange(n) for _ in range(19)]
    want = 1
    for c in cs:
        want = want * c % n
    assert be.modmul_fold(cs, n) == want
    assert calls["reduce"] == 1

    bases = [rng.randrange(n) for _ in range(7)]  # not divisible by 4: pads
    assert be.powmod_batch(bases, 65537, n) == [pow(b, 65537, n) for b in bases]
    assert calls["pow"] == 1


def test_dds_mesh_env_builds_mesh_lazily(monkeypatch):
    from dds_tpu.models.backend import TpuBackend

    monkeypatch.setenv("DDS_MESH", "4")
    be = TpuBackend(pallas=False, min_device_batch=0)
    assert be.mesh is None  # not built yet
    n = rng.getrandbits(512) | (1 << 511) | 1
    cs = [rng.randrange(n) for _ in range(8)]
    want = 1
    for c in cs:
        want = want * c % n
    assert be.modmul_fold(cs, n) == want
    assert be.mesh is not None and be.mesh.devices.size == 4
