"""Segmented multi-request folds (ops/foldmany): one dispatch, R results."""

import random

import pytest

from dds_tpu.ops import foldmany

rng = random.Random(17)


def _want(f, n):
    acc = 1
    for c in f:
        acc = acc * c % n
    return acc


@pytest.mark.parametrize("kernel", ["jnp", "v2"])
def test_fold_many_ragged_matches_int(kernel):
    n = rng.getrandbits(512) | (1 << 511) | 1
    folds = [
        [rng.randrange(1, n) for _ in range(k)] for k in (1, 3, 8, 13, 40)
    ]
    got = foldmany.fold_many(folds, n, kernel=kernel)
    assert got == [_want(f, n) for f in folds]


def test_fold_many_single_request_and_request_padding():
    n = rng.getrandbits(256) | (1 << 255) | 1
    # R=3 pads the request axis to 4 with dummy folds; results must be exact
    folds = [[rng.randrange(1, n) for _ in range(5)] for _ in range(3)]
    assert foldmany.fold_many(folds, n) == [_want(f, n) for f in folds]
    # R=1 degenerates to a plain fold
    one = [[rng.randrange(1, n) for _ in range(9)]]
    assert foldmany.fold_many(one, n) == [_want(one[0], n)]


def test_backend_fold_many_dispatches_kernel_family():
    from dds_tpu.models.backend import TpuBackend

    n = rng.getrandbits(256) | (1 << 255) | 1
    folds = [[rng.randrange(1, n) for _ in range(4)] for _ in range(2)]
    be = TpuBackend(pallas=True, min_device_batch=0)
    assert be.modmul_fold_many(folds, n) == [_want(f, n) for f in folds]


def test_fold_many_cache_keys_on_interpret(monkeypatch):
    """A backend flipped mid-process must MISS the compiled-fn cache (a
    stale hit would serve a trace with the other interpret mode baked in)."""
    from dds_tpu.ops import kernel
    from dds_tpu.ops.montgomery import ModCtx

    n = rng.getrandbits(256) | (1 << 255) | 1
    ctx = ModCtx.make(n)

    def keys():
        return {k for name, k in kernel._FN_CACHE
                if name == "foldmany" and k[0] == ctx.n}

    interpreted = foldmany._fold_many_fn(ctx, "v2", 2)
    assert foldmany._fold_many_fn(ctx, "v2", 2) is interpreted
    keys_interpreted = keys()
    monkeypatch.setattr(foldmany, "interpret_default", lambda: False)
    assert foldmany._fold_many_fn(ctx, "v2", 2) is not interpreted
    assert keys() - keys_interpreted == {(ctx.n, "v2", 2, False)}


def test_prod_tb_env_flag_validated_loudly(monkeypatch):
    """DDS_PROD_TB typos fail at flag-read with an actionable message, not
    deep inside a trace (ops/flags.prod_tb; used by mont_mxu._tb_for)."""
    from dds_tpu.ops.flags import prod_tb

    monkeypatch.delenv("DDS_PROD_TB", raising=False)
    assert prod_tb() is None
    monkeypatch.setenv("DDS_PROD_TB", "512")
    assert prod_tb() == 512
    for bad in ("12eight", "-128", "0", "100"):
        monkeypatch.setenv("DDS_PROD_TB", bad)
        with pytest.raises(ValueError, match="DDS_PROD_TB"):
            prod_tb()


def test_fold_many_fuzz_against_int():
    """Randomized shapes: R in 1..6 requests, widths 1..70, two moduli
    sizes, both kernels — every segment's product must match python ints
    (guards the elem-major layout + per-request R-power accounting)."""
    for trial in range(6):
        bits = 256 if trial % 2 else 384
        n = rng.getrandbits(bits) | (1 << (bits - 1)) | 1
        folds = [
            [rng.randrange(1, n) for _ in range(rng.randint(1, 70))]
            for _ in range(rng.randint(1, 6))
        ]
        kernel = "v2" if trial % 3 == 0 else "jnp"
        got = foldmany.fold_many(folds, n, kernel=kernel)
        assert got == [_want(f, n) for f in folds], (trial, kernel)
