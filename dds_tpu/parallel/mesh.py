"""Mesh-sharded ciphertext arithmetic: the multi-chip scale-out path.

The reference's only parallelism is replication fan-out over Akka remoting
(SURVEY.md §2, "Parallelism inventory"); the TPU-native analogue is
data-parallel batched ciphertext arithmetic sharded over a device mesh
(SURVEY.md §5.7-5.8):

- the K axis (ciphertexts) is sharded across devices ("batch/limb
  parallelism": each ciphertext's limb chain stays device-local so carries
  and Montgomery reductions never cross the interconnect);
- aggregates reduce locally per shard, then combine partial products with
  ONE small collective (`all_gather` of (D, L) partials — modular product
  is not an add, so `psum` does not apply) and a replicated log2(D) tail
  reduction.

The shard-local math runs the SAME kernel family the single-chip path
uses (`kernel=`, a family of ops/kernel: "v2" = VPU product + MXU
band-REDC, "jnp" = the portable scan kernels) — so N chips mean N x the
fast kernel, not N x the portable one. Only the O(D) combine (D-1
multiplies of one residue each) stays on the portable jnp multiply: a
Pallas dispatch per single-row multiply would pad 1 lane to a full tile
and cost more than it saves.

Works identically on a real TPU slice and on the test fabric
(`--xla_force_host_platform_device_count`, Pallas in interpret mode).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dds_tpu.ops import bignum as bn
from dds_tpu.ops.kernel import (
    check_family, fn_cache, fold_fix, halving_tree, interpret_default,
    mont_mul, pairwise_tree,
)
from dds_tpu.ops.montgomery import ModCtx, _mont_exp_raw


def make_mesh(n_devices: int | None = None, axis: str = "batch") -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def group_sharding(mesh: Mesh | None, index: int, axis: str = "batch"):
    """NamedSharding pinning one shard group's resident pool (Lodestone,
    dds_tpu/resident) to its slice of the mesh: group `index` maps round-
    robin onto the mesh's devices, and the pool's (rows, L) buffer lives
    wholly on that device via a one-device sub-mesh + replicated
    PartitionSpec — so the fused sharded fold gathers each group's rows
    where they already are. None (no mesh, or a single device — the test
    fabric) means default placement: exactly the pre-Lodestone buffer."""
    if mesh is None or mesh.devices.size <= 1:
        return None
    dev = mesh.devices.flat[index % mesh.devices.size]
    return NamedSharding(Mesh(np.array([dev]), (axis,)), P())


def combine_partials(partials, modulus: int) -> int:
    """Modular-product tail combine over already-reduced partials — the
    host-integer twin of the replicated log2(D) tree `sharded_reduce_mul`
    runs over gathered per-device partials (`ops/kernel.pairwise_tree`). The
    Constellation scatter-gather path (http/server._fold_aggregate) uses
    it to merge per-shard aggregate folds: every shard group shares one
    Paillier modulus, and the modular product is associative/commutative,
    so S per-shard partials combine bit-for-bit to the single-shard
    result regardless of how the keyspace was partitioned. Kept here, not
    duplicated in shard/, so the two partial-combine paths stay one
    implementation site."""
    parts = [p % modulus for p in partials]
    if not parts:
        raise ValueError("combine_partials needs at least one partial")
    while len(parts) > 1:
        nxt = [
            (parts[i] * parts[i + 1]) % modulus
            for i in range(0, len(parts) - 1, 2)
        ]
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


def sharded_reduce_mul(ctx: ModCtx, cs, mesh: Mesh, axis: str = "batch",
                       ring: bool = False, kernel: str = "jnp"):
    """Modular product of K ciphertexts sharded over `mesh`.

    cs: (K, L) plain-domain, K divisible by mesh size times 1 (padded here
    to a power of two per shard with the Montgomery identity, like
    ModCtx.reduce_mul). Returns (1, L) = prod(cs) * R^-(K-1) mod n,
    replicated; callers fix the R power exactly as ModCtx.reduce_mul does.
    `kernel` picks the shard-local fold family (module docstring).

    Two combine collectives, same result and R accounting (D partials,
    D-1 montgomery multiplies either way):
    - ring=False: ONE all_gather of the (D, L) partials + a replicated
      tail tree — best here because the payload is tiny (L limbs/device);
    - ring=True: D-1 `ppermute` neighbor hops, each device multiplying the
      partial circulating past it — the ring-attention-style ICI pattern
      that wins when per-device payloads are large enough that an
      all_gather would burst-buffer D copies at once.
    """
    check_family(kernel)
    D = mesh.devices.size
    K = cs.shape[0]
    shard = -(-K // D)
    P2 = 1 << max(0, (shard - 1).bit_length())
    total = P2 * D
    if total != K:
        pad = jnp.broadcast_to(jnp.asarray(ctx.one_mont), (total - K, ctx.L))
        cs = jnp.concatenate([jnp.asarray(cs), pad], axis=0)
    interpret = interpret_default()

    def step(local):
        # local: (P2, L) on each device -> (1, L) partial, times R^-(P2-1)
        lm = mont_mul(ctx, kernel, interpret, layout="lm")
        partial = halving_tree(lm, local.T, axis=1).T
        mul = mont_mul(ctx, "jnp", interpret)
        if ring:
            perm = [(d, (d + 1) % D) for d in range(D)]

            def hop(_, acc_msg):
                acc, msg = acc_msg
                msg = jax.lax.ppermute(msg, axis, perm)
                return mul(acc, msg), msg

            acc, _ = jax.lax.fori_loop(0, D - 1, hop, (partial, partial))
            return acc  # equal on every device after D-1 hops
        partials = jax.lax.all_gather(partial, axis, tiled=True)  # (D, L)
        # (1, L), replicated
        return pairwise_tree(mul, partials, jnp.asarray(ctx.one_mont))

    # jitted shard_map executables are cached because the serving path
    # calls these per aggregate, and a closure rebuilt each call would
    # defeat jax.jit's trace cache (it keys on function identity + shapes).
    # NOT keyed on P2: jit retraces per input shape under one entry, and
    # nothing in the closure bakes the shard width
    fn = fn_cache(
        "mesh", ("reduce", ctx.n, mesh, axis, ring, kernel, interpret),
        lambda: jax.jit(jax.shard_map(
            step,
            mesh=mesh,
            in_specs=P(axis),
            out_specs=P(),  # replicated result
            check_vma=False,  # scan carries start replicated inside the shard
        )),
    )
    return fn(cs)


def sharded_reduce_mul_fixed(ctx: ModCtx, cs, mesh: Mesh, axis: str = "batch",
                             ring: bool = False, kernel: str = "jnp"):
    """Like ModCtx.reduce_mul but mesh-sharded: returns prod(cs) mod n (1, L)."""
    prod = sharded_reduce_mul(ctx, cs, mesh, axis, ring, kernel)
    return ctx.mont_mul(prod, fold_fix(ctx, cs.shape[0])[None, :])


def sharded_pow_mod(ctx: ModCtx, bases, exp_digits, mesh: Mesh,
                    axis: str = "batch", kernel: str = "jnp"):
    """Batched modexp with the batch axis sharded across the mesh.

    bases: (B, L) plain domain, B divisible by mesh size. exp_digits:
    (E,) uint32 4-bit MSB-first digits, replicated. Purely data-parallel —
    zero collectives; each device exponentiates its shard on the
    configured kernel family.
    """
    check_family(kernel)
    E = int(exp_digits.shape[0])
    interpret = interpret_default()
    if kernel == "v2":
        from dds_tpu.ops import mont_mxu

        def step(local_bases, digits):
            body = mont_mxu._pow2_body(mont_mxu.MxuCtx.make(ctx), E, interpret)
            return body(local_bases, digits.astype(jnp.int32))
    else:
        def step(local_bases, digits):
            mul = mont_mul(ctx, "jnp", interpret)
            mont = mul(
                local_bases,
                jnp.broadcast_to(jnp.asarray(ctx.R2), local_bases.shape),
            )
            r = _mont_exp_raw(
                mont, digits, jnp.asarray(ctx.one_mont), jnp.asarray(ctx.N),
                jnp.uint32(ctx.n0inv),
            )
            one_plain = jnp.asarray(bn.ones_batch(1, ctx.L)[0])
            return mul(r, jnp.broadcast_to(one_plain, r.shape))

    # E is in the key only for v2: _pow2_body bakes `E > 1` into the trace;
    # the jnp step derives everything from the digits' runtime shape, so
    # one entry per modulus serves every exponent width there
    fn = fn_cache(
        "mesh",
        ("pow", ctx.n, mesh, axis, kernel, interpret,
         E if kernel == "v2" else None),
        lambda: jax.jit(jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P(axis),
            check_vma=False,  # scan carries start replicated inside the shard
        )),
    )
    return fn(bases, jnp.asarray(exp_digits))
