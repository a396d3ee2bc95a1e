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


def _paillier_width_mctx(L):
    """A modulus of L limbs (any odd number of that width: lowering reads
    its shape, not its arithmetic)."""
    n = random.Random(L).getrandbits(16 * L) | (1 << (16 * L - 1)) | 1
    return mx.MxuCtx.make(ModCtx.make(n))


# what a multiply may leave outside Mosaic: nothing, but the lane pads and the
# slice back where the batch is no whole tile
MAX_XLA_OPS_A_MULTIPLY = 3


def _xla_ops(text):
    """The operations of a lowering's main function that are neither a
    Mosaic kernel nor a constant (or a constant's broadcast to a column)."""
    import re

    main = text[text.index("func.func public @main"):].split("func.func private")[0]
    ops = re.findall(r"= (?:(?:stablehlo|chlo)\.)?([a-z_]+)", main)
    return ops.count("custom_call"), [
        op for op in ops if op not in ("custom_call", "constant", "broadcast_in_dim")
    ]


@pytest.mark.parametrize("L,lanes", [(256, 256), (512, 256), (256, 130), (512, 1)])
def test_a_multiply_is_one_kernel(L, lanes):
    """Product and reduction lower to ONE Mosaic kernel at Paillier-2048 and
    -4096 widths, int8 band products inside, and the multiply's lowering
    holds nothing else (two pads and a slice for a ragged batch): the 120
    XLA operations a multiply was until PR 43 cannot grow back unseen."""
    mctx = _paillier_width_mctx(L)
    x = u32(L, lanes)
    text = (
        jax.jit(lambda a, b: mx.mul2_lm(mctx, a, b, False))
        .trace(x, x).lower(lowering_platforms=("tpu",)).as_text()
    )
    kernels, xla = _xla_ops(text)
    assert kernels == 1 and "tpu_custom_call" in text
    assert len(xla) <= MAX_XLA_OPS_A_MULTIPLY, xla
    if lanes % 128 == 0:
        assert xla == []


@pytest.mark.parametrize("L,kernel", [(256, "mont_mul_schoolbook"),
                                      (512, "mont_mul_karatsuba1")])
def test_the_served_fold_is_its_fifteen_multiplies_and_little_else(L, kernel):
    """The fold of 16,384 rows at Paillier-2048 and -4096 widths, lowered
    for the TPU from this CPU host (`tools/fold_lowering.py`'s `kernels=15`,
    held here): 15 Mosaic kernels, one a multiply and each the product L
    chooses, the transpose in, two slices a wide level, a lane roll a narrow
    one and the slice out; it was 1,576 operations."""
    mctx = _paillier_width_mctx(L)
    text = (
        mx._reduce2_fn(mctx, 16384, False)
        .trace(u32(16384, L), u32(L))
        .lower(lowering_platforms=("tpu",)).as_text()
    )
    kernels, xla = _xla_ops(text)
    assert kernels == 15 == text.count(f'kernel_name = "{kernel}"')
    assert len(xla) <= 30 and set(xla) <= {"call", "slice", "transpose"}, xla


def test_v2_entry_points_lower_for_tpu():
    lowers_to_mosaic(mx._pow2_fn(MCTX, 4, False), u32(8, L), i32(4))
    lowers_to_mosaic(mx._reduce2_fn(MCTX, 8, False), u32(8, L), u32(L))


def test_the_karatsuba_product_lowers_for_tpu(monkeypatch):
    """From KARATSUBA_MIN_L up the multiply takes the three-product kernel:
    the multiply, the fold and the ladder lower to Mosaic with it inside
    (threshold lowered to L = 32 here: lowering, not size, is tested; a
    modulus of its own, so no cached trace of another test is met)."""
    monkeypatch.setattr(mx, "KARATSUBA_MIN_L", 32)
    ctx = ModCtx.make(random.Random(22).getrandbits(512) | (1 << 511) | 1)
    mctx = mx.MxuCtx.make(ctx)
    assert mx.product_for(ctx.L) == "karatsuba1"
    lowers_to_mosaic(
        lambda a, b: mx.mul2_lm(mctx, a, b, False), u32(ctx.L, 8), u32(ctx.L, 8)
    )
    lowers_to_mosaic(mx._reduce2_fn(mctx, 8, False), u32(8, ctx.L), u32(ctx.L))
    lowers_to_mosaic(mx._pow2_fn(mctx, 4, False), u32(8, ctx.L), i32(4))


@pytest.fixture(scope="module")
def one_v5e_chip():
    """A described (not attached) v5e chip to compile for; the TPU compiler
    is loaded here, inside the one worker that runs this file, never at
    import time."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("L", [256, 384, 512])
def test_the_multiply_compiles_for_a_v5e_at_real_widths(one_v5e_chip, L):
    """Mosaic takes the multiply's kernel (the schoolbook product at
    Paillier-2048 width, the Karatsuba one at -3072 and -4096, the
    reduction with its int8 matmuls behind either) at the lane tile it runs
    at (VMEM, alignment, int8 tiles, no boolean vector shifted): what
    interpret mode and a lowering cannot show, without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    mctx = _paillier_width_mctx(L)
    x = jax.ShapeDtypeStruct((L, 256), jnp.uint32, sharding=one_v5e_chip)
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(
            lambda a, b: mx.mul2_lm(mctx, a, b, False)
        ).lower(x, x).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("kernel", ["v2"])
def test_foldmany_entry_points_lower_for_tpu(kernel, monkeypatch):
    # foldmany picks interpret mode itself from the backend in use
    monkeypatch.setattr(foldmany, "interpret_default", lambda: False)
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
