"""Weighted modular-product folds in ONE device dispatch.

`fold_weighted` is a per-row product of operands raised to per-(row,
operand) plaintext exponents: the plaintext-ciphertext matrix-
multiplication kernel of the Prism analytics plane (dds_tpu/analytics,
`TpuBackend.matvec`). It shares the compiled-fn cache, the kernel-family
selection and the Montgomery contexts with the aggregate folds of
`ops/kernel`. All rows of one call share one modulus.

Compiled executables retrace per (P2, Rp, D); the operand and row axes
are bucketed to powers of two here so the shape set stays tiny.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from dds_tpu.obs import kprof
from dds_tpu.ops import bignum as bn
from dds_tpu.ops.kernel import fn_cache, halving_tree, interpret_default, mont_mul
from dds_tpu.ops.montgomery import ModCtx


_WINDOW = 4  # digit width of the weighted fold's ladder (16-entry tables)


def _fold_weighted_fn(ctx: ModCtx, kernel: str):
    """Compiled weighted-fold kernel for (ctx, kernel family): shapes are
    NOT in the cache key — jit retraces per (P2, Rp, D) input shape under
    one entry, like mesh's "reduce" keys — but the interpret flag is: it
    is baked into the multiply at trace time, and a backend flipped
    mid-process must not be served a stale trace."""
    interpret = interpret_default()
    L = ctx.L

    def run(cs, digits):
        # cs: (P2, L) plain-domain operands; digits: (D, Rp, P2) int32
        # MSB-first 4-bit windows of each (row, operand) weight. Everything
        # runs in the Montgomery domain (entry via R2, exit via 1), so no
        # R-power bookkeeping is needed: mont_mul is closed over x~ = xR.
        mul = mont_mul(ctx, kernel, interpret)
        one_mont = jnp.asarray(ctx.one_mont)
        one_plain = jnp.asarray(bn.ones_batch(1, L)[0])
        P2 = cs.shape[0]
        Rp = digits.shape[1]
        cs_m = mul(cs, jnp.broadcast_to(jnp.asarray(ctx.R2), cs.shape))
        # table[d, k] = cs[k]^d for d in [0, 16): row-independent, so the
        # per-digit gather below serves every output row from one table
        tab = [jnp.broadcast_to(one_mont, cs.shape), cs_m]
        for _ in range(2, 1 << _WINDOW):
            tab.append(mul(tab[-1], cs_m))
        table = jnp.stack(tab, axis=0)             # (16, P2, L)
        kidx = jnp.arange(P2)[None, :]

        def mul_slabs(a, b):                       # (Rp, h, L) operands
            return mul(a.reshape(-1, L), b.reshape(-1, L)).reshape(a.shape)

        def step(acc, dig):                        # acc (Rp, L); dig (Rp, P2)
            for _ in range(_WINDOW):
                acc = mul(acc, acc)
            sel = table[dig, kidx]                 # (Rp, P2, L)
            # tree fold over the operand axis
            return mul(acc, halving_tree(mul_slabs, sel, axis=1)[:, 0]), None

        acc0 = jnp.broadcast_to(one_mont, (Rp, L))
        acc, _ = jax.lax.scan(step, acc0, digits)
        return mul(acc, jnp.broadcast_to(one_plain, acc.shape))

    return fn_cache(
        "fold_weighted", (ctx.n, kernel, interpret), lambda: jax.jit(run)
    )


def fold_weighted(
    cs: list[int], weights: list[list[int]], modulus: int, kernel: str = "jnp",
    rows=None,
) -> list[int]:
    """Per-row weighted modular products, one device dispatch:

        out[r] = prod_j cs[j] ** weights[r][j]  mod modulus

    The PC-MM kernel behind the Prism analytics plane (arxiv 2504.14497):
    a plaintext-matrix x ciphertext-vector product over Paillier is exactly
    this shape with modulus = n^2 and negative weights pre-encoded as
    n - |w| by the caller (models/paillier.matvec_encode). Weights must be
    non-negative ints below the modulus; rows must all span len(cs).

    Structure: a shared 4-bit-window ladder over the longest weight's
    digits — per digit, 4 batched squarings of the (R, L) accumulator,
    one 16-entry table gather per (row, operand), and a halving tree fold
    over the operand axis — so the work is R*K-wide batched Montgomery
    multiplies end to end, the batch shape the MXU/VPU kernel families
    were built for. Operands pad to a power of two with 1 (weight 0),
    rows pad with all-zero weight vectors; both pads gather the identity
    table entry, so padding never perturbs results.

    Public parameters only (ciphertexts, plaintext weights, a public
    modulus): nothing here touches secret key material, so ModCtx's global
    cache and the persistent compile cache are safe — ADVICE.md's
    secret-CRT-parameter concern does not apply to this path.

    `rows` optionally supplies the operands as an already-device-resident
    (K, L) plain-domain limb array (a Lodestone pool gather,
    dds_tpu/resident): the int -> limb marshaling of `cs` is skipped and
    only the pad rows are host-built. `cs` is still required — it carries
    the operand count and the host-side weight validation.
    """
    ctx = ModCtx.make(modulus)
    K, R_real = len(cs), len(weights)
    if K == 0 or R_real == 0:
        raise ValueError("fold_weighted needs >= 1 operand and >= 1 row")
    for row in weights:
        if len(row) != K:
            raise ValueError(
                f"weight row spans {len(row)} operands, expected {K}"
            )
        for w in row:
            if w < 0 or w >= modulus:
                raise ValueError(
                    "weights must be encoded to [0, modulus) before the "
                    "kernel (negative weights: models/paillier.matvec_encode)"
                )
    P2 = 1 << max(0, (K - 1).bit_length())
    Rp = 1 << max(0, (R_real - 1).bit_length())
    if rows is not None and getattr(rows, "shape", None) == (K, ctx.L):
        arr = jnp.asarray(rows)
        if P2 != K:
            pad = jnp.asarray(bn.ints_to_batch([1] * (P2 - K), ctx.L))
            arr = jnp.concatenate([arr, pad], axis=0)
    else:
        arr = bn.ints_to_batch(list(cs) + [1] * (P2 - K), ctx.L)
    E = max((w.bit_length() for row in weights for w in row), default=0)
    D = max(1, -(-E // _WINDOW))
    digits = np.zeros((D, Rp, P2), np.int32)
    for r, row in enumerate(weights):
        for k, w in enumerate(row):
            for d in range(-(-w.bit_length() // _WINDOW)):
                digits[D - 1 - d, r, k] = (w >> (_WINDOW * d)) & 0xF
    fn = _fold_weighted_fn(ctx, kernel)
    out = kprof.profiled(
        "fold_weighted",
        lambda: fn(jnp.asarray(arr), jnp.asarray(digits)),
        R=R_real, K=K, D=D,
    )
    return [bn.limbs_to_int(row) for row in np.asarray(out)[:R_real]]
