"""Pluggable ciphertext-arithmetic backends: `cpu` (python ints) and `tpu`.

This is the `crypto.backend` switch from BASELINE.json: the query engine
(proxy) performs all its ciphertext math through this interface, using only
*public* parameters (Paillier n^2, RSA modulus) — never private keys,
matching the reference trust model where `HomoAdd.sum`/`HomoMult.multiply`
run proxy-side on ciphertexts (`dds/http/DDSRestServer.scala:385,423,479`).

The "public parameters only" claim is load-bearing, not aspirational:
every modulus handed to these backends lands in `ModCtx.make`'s
process-wide cache and in executables the persistent compile cache
serializes to disk, so SECRET moduli (the Paillier CRT legs p^2/q^2,
RSA p/q) must never enter — the historical `decrypt_batch(backend=...)`
routing that did exactly that was the ADVICE.md medium finding. Anything
touching key material goes through `dds_tpu.sanctum` instead
(`PaillierKey.decrypt_batch` now refuses these backends outright), and
`tools/secret_lint.py` rejects new flows statically.

The TPU backend converts ciphertext batches to (B, L) limb arrays and runs
the tier-0 Montgomery kernels; a K-term aggregate costs ~1 batched modmul
per term (tree reduction + one domain fixup). The CPU backend is the
baseline the bench compares against.
"""

from __future__ import annotations

from typing import Protocol

import numpy as np

from dds_tpu.ops import bignum as bn
from dds_tpu.ops.montgomery import ModCtx


class CryptoBackend(Protocol):
    """Ciphertext-domain modular arithmetic over PUBLIC parameters only
    (secret moduli: dds_tpu.sanctum — see the module docstring)."""

    name: str

    def modmul(self, c1: int, c2: int, modulus: int) -> int: ...

    def modmul_fold(self, cs: list[int], modulus: int) -> int: ...

    def powmod_batch(self, bases: list[int], exp: int, modulus: int) -> list[int]: ...

    def matvec(
        self, cs: list[int], weights: list[list[int]], modulus: int,
        rows: object = None,
    ) -> list[int]: ...


def _host_matvec(
    cs: list[int], weights: list[list[int]], modulus: int, powmod=pow
) -> list[int]:
    """Per-row weighted fold on host ints: out[r] = prod_j cs[j]^w[r][j]
    mod modulus, skipping zero weights (the common case for GroupBySum
    selector rows). Shared by every backend's below-crossover path."""
    out = []
    for row in weights:
        acc = 1
        for c, w in zip(cs, row):
            if w:
                acc = acc * powmod(c, w, modulus) % modulus
        out.append(acc)
    return out


class CpuBackend:
    """Python-int reference backend (the CPU baseline of BASELINE.md)."""

    name = "cpu"

    def modmul(self, c1: int, c2: int, modulus: int) -> int:
        return c1 * c2 % modulus

    def modmul_fold(self, cs: list[int], modulus: int) -> int:
        acc = 1
        for c in cs:
            acc = acc * c % modulus
        return acc

    def powmod_batch(self, bases: list[int], exp: int, modulus: int) -> list[int]:
        return [pow(b, exp, modulus) for b in bases]

    def matvec(
        self, cs: list[int], weights: list[list[int]], modulus: int,
        rows: object = None,
    ) -> list[int]:
        # `rows` (pre-gathered device limbs, Lodestone) is a device-path
        # optimization; the host loop works from the ints either way
        return _host_matvec(cs, weights, modulus)


def _device() -> tuple[str, str]:
    """(platform, device_kind) the tpu backend's kernels will run on.

    jax falls back to the CPU with one warning line when libtpu fails to
    initialise, and every kernel module then quietly picks interpret mode
    or the jnp path, so a proxy configured `crypto_backend = "tpu"` would
    serve from the host with nothing in its spans to say so. Anything but
    a TPU is therefore an error here, unless the process itself asked for
    the CPU (`JAX_PLATFORMS=cpu` / `jax.config.jax_platforms`, as the
    tests and the multi-chip dry run do)."""
    import jax

    platform = jax.default_backend()
    asked = (jax.config.jax_platforms or "").split(",")
    if platform != "tpu" and "cpu" not in asked:
        raise RuntimeError(
            f"crypto backend 'tpu' found no TPU: jax.default_backend() is "
            f"{platform!r} (did libtpu fail to initialise?). Set "
            f"JAX_PLATFORMS=cpu to run these kernels on the CPU on purpose, "
            f"or pick the 'cpu' / 'native' backend."
        )
    return platform, jax.devices()[0].device_kind


class TpuBackend:
    """Batched limb-tensor backend on the tier-0 Montgomery kernels.

    Two kernel families (ops/kernel): on a TPU the compiled Pallas "v2"
    kernels run (ops/mont_mxu); on a CPU the process asked for (tests),
    the portable "jnp" path (ops/montgomery). `pallas=None` takes what is
    observed (Pallas on a TPU); tests pass `pallas=True` to run the v2
    kernels in interpret mode on the CPU. Constructing it anywhere else
    raises (see `_device`). Compiled kernels are cached per modulus via
    ModCtx.make's lru_cache.
    """

    name = "tpu"

    def __init__(self, pallas: bool | None = None,
                 min_device_batch: int | None = None, mesh=None):
        import os

        self.platform, self.device_kind = _device()
        self.pallas = self.platform == "tpu" if pallas is None else pallas
        # Adaptive dispatch: below this fold width the flat device-dispatch
        # latency loses to a host fold, so small aggregates stay on host.
        # 1024 is the crossover of an earlier installation and has not been
        # re-measured on this one (ROADMAP S1); DDS_TPU_MIN_BATCH
        # overrides, 0 forces everything onto the device.
        self.min_device_batch = (
            int(os.environ.get("DDS_TPU_MIN_BATCH", "1024"))
            if min_device_batch is None
            else min_device_batch
        )
        import threading

        # Multi-chip scale-out (SURVEY.md §5.7-5.8): with a jax.sharding
        # Mesh, folds/modexps shard the ciphertext axis over the devices via
        # parallel/mesh.py (limb chains stay device-local; ONE all_gather
        # combines partial products over ICI). Pass mesh= explicitly or set
        # DDS_MESH=N to build an N-device mesh lazily at first use.
        self.mesh = mesh
        self._mesh_n = (
            int(os.environ.get("DDS_MESH", "0")) if mesh is None else 0
        )

        self._stores: dict[int, object] = {}
        self._stores_lock = threading.Lock()  # folds run on proxy threads

    @staticmethod
    def _host_fold(cs: list[int], modulus: int) -> int:
        # native.fold's contract: never fails (python-int fallback inside)
        from dds_tpu import native

        return native.fold(cs, modulus)

    def store_for(self, modulus: int):
        """Per-modulus device-resident cipher store (resident/pool.py)."""
        with self._stores_lock:
            store = self._stores.get(modulus)
            if store is None:
                from dds_tpu.resident.pool import ResidentPool

                ctx = ModCtx.make(modulus)
                store = ResidentPool(
                    modulus, reduce=lambda rows: self.reduce_mul_device(ctx, rows),
                    kernel=self.fold_kernel(),
                )
                self._stores[modulus] = store
            return store

    def modmul_fold_resident(self, cs: list[int], modulus: int) -> int:
        """Fold via the device store: unseen ciphertexts ingest once, the
        aggregate gathers resident rows on-device. Folds narrower than
        min_device_batch run on host (the store is not consulted: a later
        wide aggregate pays ingest for those rows then)."""
        if len(cs) < self.min_device_batch:
            return self._host_fold(cs, modulus)
        return self.store_for(modulus).fold(cs)

    def modmul(self, c1: int, c2: int, modulus: int) -> int:
        # one multiply: a device round-trip can never win
        return c1 * c2 % modulus

    def fold_kernel(self) -> str:
        """The single kernel-family rule (a family of ops/kernel) for the
        flat fold and every composite one — mesh-sharded (parallel/mesh),
        weighted (ops/foldmany) and resident-fused (dds_tpu/resident):
        v2 when pallas is on, the portable jnp scans otherwise, so
        scale-out and batching never silently run a slower kernel."""
        return "v2" if self.pallas else "jnp"

    def resident_plane(self, initial_rows: int = 256,
                       max_rows: int = 1 << 20):
        """A Lodestone ResidentPlane wired to THIS backend's kernel
        family, mesh, and per-pool reduce — so lone-group resident folds
        and fused sharded folds run exactly the kernels the flat paths
        would (one dispatch rule, one kernel rule)."""
        from dds_tpu.resident import ResidentPlane

        def reduce_factory(modulus: int):
            ctx = ModCtx.make(modulus)
            return lambda rows: self.reduce_mul_device(ctx, rows)

        return ResidentPlane(
            kernel=self.fold_kernel(),
            mesh=self._get_mesh(),
            initial_rows=initial_rows,
            max_rows=max_rows,
            reduce_factory=reduce_factory,
        )

    def _get_mesh(self):
        if self.mesh is None and self._mesh_n > 1:
            from dds_tpu.parallel.mesh import make_mesh

            self.mesh = make_mesh(self._mesh_n)
            self._mesh_n = 0
        return self.mesh

    def reduce_mul_device(self, ctx: ModCtx, batch):
        """Modular product over an already-resident (K, L) limb batch.

        The device-level fold entry point shared by modmul_fold, the
        proxy's aggregate routes, and bench.py — one dispatch rule."""
        mesh = self._get_mesh()
        if mesh is not None and mesh.devices.size > 1:
            from dds_tpu.parallel import mesh as pm

            return pm.sharded_reduce_mul_fixed(
                ctx, batch, mesh, kernel=self.fold_kernel()
            )
        if self.pallas:
            from dds_tpu.ops import mont_mxu

            return mont_mxu.reduce_mul2(mont_mxu.MxuCtx.make(ctx), batch)
        return ctx.reduce_mul(batch)

    def modmul_fold(self, cs: list[int], modulus: int) -> int:
        if len(cs) < self.min_device_batch:
            return self._host_fold(cs, modulus)
        ctx = ModCtx.make(modulus)
        batch = bn.ints_to_batch(cs, ctx.L)
        out = self.reduce_mul_device(ctx, batch)
        return bn.limbs_to_int(np.asarray(out)[0])

    def matvec(
        self, cs: list[int], weights: list[list[int]], modulus: int,
        rows: object = None,
    ) -> list[int]:
        """Plaintext-matrix x ciphertext-vector products (Prism / PC-MM):
        one batched weighted-fold dispatch (ops/foldmany.fold_weighted)
        when the R*K cell count clears the device crossover; below it the
        host loop wins for the same dispatch-latency reason small
        aggregates do. `rows` optionally supplies the operands as
        already-gathered device limbs from a Lodestone resident pool, so
        the device path skips host int -> limb marshaling entirely."""
        if len(weights) * len(cs) < self.min_device_batch:
            from dds_tpu.native import powmod

            return _host_matvec(cs, weights, modulus, powmod=powmod)
        from dds_tpu.ops import foldmany

        return foldmany.fold_weighted(
            cs, weights, modulus, kernel=self.fold_kernel(), rows=rows
        )

    def powmod_batch(self, bases: list[int], exp: int, modulus: int) -> list[int]:
        ctx = ModCtx.make(modulus)
        batch = bn.ints_to_batch(bases, ctx.L)
        mesh = self._get_mesh()
        if mesh is not None and mesh.devices.size > 1:
            from dds_tpu.ops.montgomery import _exp_to_digits
            from dds_tpu.parallel import mesh as pm

            D = mesh.devices.size
            B = len(bases)
            padded = -(-B // D) * D
            if padded != B:  # pad with base 1 (1^e = 1), slice after
                import jax.numpy as jnp

                one = np.zeros((padded - B, ctx.L), np.uint32)
                one[:, 0] = 1
                batch = jnp.concatenate([jnp.asarray(batch), jnp.asarray(one)], 0)
            out = pm.sharded_pow_mod(
                ctx, batch, _exp_to_digits(exp), mesh, kernel=self.fold_kernel()
            )
            return bn.batch_to_ints(np.asarray(out)[:B])
        if self.pallas:
            from dds_tpu.ops import mont_mxu

            out = mont_mxu.pow_mod2(mont_mxu.MxuCtx.make(ctx), batch, exp)
        else:
            out = ctx.pow_mod(batch, exp)
        return bn.batch_to_ints(np.asarray(out))


class NativeBackend:
    """Host-side C++ CIOS backend (dds_tpu.native) — the accelerated CPU
    path for hosts without a TPU; falls back to python ints if the native
    library is unavailable."""

    name = "native"

    def modmul(self, c1: int, c2: int, modulus: int) -> int:
        return c1 * c2 % modulus

    def modmul_fold(self, cs: list[int], modulus: int) -> int:
        from dds_tpu import native

        return native.fold(cs, modulus)

    def powmod_batch(self, bases: list[int], exp: int, modulus: int) -> list[int]:
        from dds_tpu import native

        return native.powmod_batch(bases, exp, modulus)

    def matvec(
        self, cs: list[int], weights: list[list[int]], modulus: int,
        rows: object = None,
    ) -> list[int]:
        from dds_tpu.native import powmod

        return _host_matvec(cs, weights, modulus, powmod=powmod)


_BACKENDS = {"cpu": CpuBackend, "tpu": TpuBackend, "native": NativeBackend}


def get_backend(name: str) -> CryptoBackend:
    try:
        return _BACKENDS[name]()
    except KeyError:
        raise ValueError(f"unknown crypto backend {name!r} (have {sorted(_BACKENDS)})")
