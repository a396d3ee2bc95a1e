"""The weighted fold's compiled-fn cache (ops/foldmany) and the product's
DDS_PROD_TB flag; `fold_weighted`'s answers are held in test_analytics."""

import random

import pytest

from dds_tpu.ops import foldmany

rng = random.Random(17)


def test_fold_weighted_cache_keys_on_interpret(monkeypatch):
    """A backend flipped mid-process must MISS the compiled-fn cache (a
    stale hit would serve a trace with the other interpret mode baked in)."""
    from dds_tpu.ops import kernel
    from dds_tpu.ops.montgomery import ModCtx

    n = rng.getrandbits(256) | (1 << 255) | 1
    ctx = ModCtx.make(n)

    def keys():
        return {k for name, k in kernel._FN_CACHE
                if name == "fold_weighted" and k[0] == ctx.n}

    interpreted = foldmany._fold_weighted_fn(ctx, "v2")
    assert foldmany._fold_weighted_fn(ctx, "v2") is interpreted
    keys_interpreted = keys()
    monkeypatch.setattr(foldmany, "interpret_default", lambda: False)
    assert foldmany._fold_weighted_fn(ctx, "v2") is not interpreted
    assert keys() - keys_interpreted == {(ctx.n, "v2", False)}


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
