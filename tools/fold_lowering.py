"""Is the served fold still the same program? Prints, for the single-chip
fold (`mont_mxu._reduce2_fn`) at Paillier-2048 (L = 256; P2 = 16384 and 8192,
the rows of the five cells that fold there) and at Paillier-4096 (L = 512,
P2 = 16384: `p4096-bft4-sumall-steady`), the sha256 of its TPU lowering as jax
prints it, and of the same text with the source locations taken out of every
Mosaic kernel's serialized body, with the product the multiply chose from L.
A fold's lowering is one Mosaic kernel a multiply (`kernels=15` of 16,384
rows: product and Montgomery reduction in one program since PR 43), a
transpose in, a lane roll at each level narrower than a tile and a slice out.

The first names the checkout: each kernel's body carries the absolute path,
line and call stack of the code that traced it, so two checkouts, or two
line numberings of one file, never agree on it. The second is equal exactly
when the two trees trace the same XLA ops and the same Mosaic kernels in
the same order: run it in both and compare (PR 31 and PR 38 did; PERF.md
section 6 keeps the hashes of the program each PR left).

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
    for bits, P2 in ((2048, 16384), (2048, 8192), (4096, 16384)):
        ctx = ModCtx.make(bench_paillier_key(bits).public.nsquare)
        mctx = mont_mxu.MxuCtx.make(ctx)
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
            (out_dir / f"fold_{ctx.L}_{P2}.stripped.txt").write_text(stripped)
        print(f"P2={P2} as_printed={hashlib.sha256(text.encode()).hexdigest()} "
              f"locations_stripped="
              f"{hashlib.sha256(stripped.encode()).hexdigest()} "
              f"kernels={len(_BODY.findall(text))} limbs={ctx.L} "
              f"product={mont_mxu.product_for(ctx.L)}")


if __name__ == "__main__":
    main(sys.argv)
