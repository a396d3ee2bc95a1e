"""Start-up and device selection: what keeps a failure to reach the chip
from hiding.

- every Pallas entry point cross-lowers to Mosaic for TPU from this CPU
  host with `interpret=False` (a kernel that only ever ran in interpret
  mode, as a removed fused Karatsuba did with its scatter-adds, fails
  here, before the chip);
- the tpu backend refuses a CPU nobody asked for;
- the compile cache lands where the operator put it, else in the checkout;
- `bench.py` and `chip_smoke.py` print no figure without a TPU.
"""

import os
import pathlib
import random
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from dds_tpu.ops import foldmany
from dds_tpu.ops import mont_mxu as mx
from dds_tpu.ops.montgomery import ModCtx

REPO = pathlib.Path(__file__).resolve().parent.parent

# a small modulus (L=16): lowering cost, not kernel size, is what is tested
CTX = ModCtx.make(random.Random(21).getrandbits(256) | (1 << 255) | 1)
MCTX = mx.MxuCtx.make(CTX)
L = CTX.L


def u32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def lowers_to_mosaic(fn, *shapes) -> None:
    fn = fn if hasattr(fn, "trace") else jax.jit(fn)
    text = fn.trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the lowering"


def test_v2_multiply_lowers_for_tpu():
    lowers_to_mosaic(
        lambda a, b: mx.mul2_lm(MCTX, a, b, False), u32(L, 8), u32(L, 8)
    )


def test_v2_entry_points_lower_for_tpu():
    lowers_to_mosaic(mx._pow2_fn(MCTX, 4, False), u32(8, L), i32(4))
    lowers_to_mosaic(mx._reduce2_fn(MCTX, 8, False), u32(8, L), u32(L))


@pytest.mark.parametrize("kernel", ["v2"])
def test_foldmany_entry_points_lower_for_tpu(kernel, monkeypatch):
    # foldmany picks interpret mode itself from the backend in use
    monkeypatch.setattr(foldmany, "interpret_default", lambda: False)
    lowers_to_mosaic(
        foldmany._fold_many_fn(CTX, kernel, 2), u32(8, L), u32(2, L)
    )
    lowers_to_mosaic(
        foldmany._fold_weighted_fn(CTX, kernel), u32(4, L), i32(2, 2, 4)
    )


def test_tpu_backend_refuses_a_cpu_nobody_asked_for():
    from dds_tpu.models.backend import TpuBackend

    be = TpuBackend()  # this process asked for the CPU (tests/conftest.py)
    assert (be.platform, be.pallas) == ("cpu", False)
    assert be.device_kind == jax.devices()[0].device_kind
    asked = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)  # what a failed libtpu leaves
    try:
        with pytest.raises(RuntimeError, match="found no TPU"):
            TpuBackend()
    finally:
        jax.config.update("jax_platforms", asked)


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(monkeypatch):
    import dds_tpu

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    dds_tpu._place_jax_compilation_cache()
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(REPO / ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    dds_tpu._place_jax_compilation_cache()
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_no_chip_is_a_failure_with_no_figure(script):
    p = subprocess.run(
        [sys.executable, str(REPO / script)], cwd=REPO, timeout=120,
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode != 0
    assert p.stdout == ""
    assert "tpu" in p.stderr.lower()
