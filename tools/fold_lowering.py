"""Is the served fold still the same program? Prints, for the single-chip
fold (`mont_mxu._reduce2_fn`, Paillier-2048, L = 256) at P2 = 16384 and 8192,
the sha256 of its TPU lowering as jax prints it, and of the same text with
the source locations taken out of every Mosaic kernel's serialized body.

The first names the checkout: each kernel's body carries the absolute path,
line and call stack of the code that traced it, so two checkouts, or two
line numberings of one file, never agree on it. The second is equal exactly
when the two trees trace the same XLA ops and the same Mosaic kernels in
the same order: run it in both and compare (PR 31 did; PERF.md section 6).

    JAX_PLATFORMS=cpu python tools/fold_lowering.py [out_dir]

No chip is needed (the lowering is cross-platform, as in
tests/test_bringup.py); with `out_dir` the stripped texts are kept there.
"""

from __future__ import annotations

import base64
import hashlib
import pathlib
import re
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def strip_kernel_locations(text: str) -> str:
    """`text` with every Mosaic body (MLIR bytecode, base64) replaced by
    its assembly printed without debug locations."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def asm(m: re.Match) -> str:
        with ctx:
            mod = ir.Module.parse(base64.b64decode(m.group(2)))
            body = mod.operation.get_asm(enable_debug_info=False)
        return m.group(1) + body + m.group(3)

    out, n = _BODY.subn(asm, text)
    if not n:
        raise SystemExit("no Mosaic kernel in the lowering")
    return out


def main(argv: list[str]) -> None:
    import jax
    import jax.numpy as jnp

    from dds_tpu.bench_key import bench_paillier_key
    from dds_tpu.ops import mont_mxu
    from dds_tpu.ops.montgomery import ModCtx

    out_dir = pathlib.Path(argv[1]) if len(argv) > 1 else None
    ctx = ModCtx.make(bench_paillier_key(2048).public.nsquare)
    mctx = mont_mxu.MxuCtx.make(ctx)
    for P2 in (16384, 8192):
        text = (
            mont_mxu._reduce2_fn(mctx, P2, False)
            .trace(jax.ShapeDtypeStruct((P2, ctx.L), jnp.uint32),
                   jax.ShapeDtypeStruct((ctx.L,), jnp.uint32))
            .lower(lowering_platforms=("tpu",))
            .as_text()
        )
        stripped = strip_kernel_locations(text)
        if out_dir is not None:
            out_dir.mkdir(parents=True, exist_ok=True)
            (out_dir / f"fold_{P2}.stripped.txt").write_text(stripped)
        print(f"P2={P2} as_printed={hashlib.sha256(text.encode()).hexdigest()} "
              f"locations_stripped="
              f"{hashlib.sha256(stripped.encode()).hexdigest()} "
              f"kernels={len(_BODY.findall(text))}")


if __name__ == "__main__":
    main(sys.argv)
