"""Lodestone: the mesh-fused device-resident ciphertext plane.

`ResidentPlane` owns one `ResidentPool` per (shard group, modulus) and
turns a sharded aggregate — operand sets partitioned by owning
Constellation group — into ONE device dispatch: per-group rows gather
from their pools, fold locally (a halving tree per group slab), and the
per-group partials merge with the same log2(S) tail tree
`parallel/mesh.sharded_reduce_mul` runs across chips. Before this plane
the proxy dispatched S independent folds per sharded aggregate and
re-marshaled host limbs into every one of them; warm aggregates now
touch host ints only to look up row indices.

Placement: with a multi-device mesh each group's pool pins to its mesh
slice (`parallel/mesh.group_sharding`, NamedSharding/PartitionSpec) and
the fused fold runs the per-group slabs under `shard_map` with one
all_gather of (S, L) partials — the BTS-style lane partitioning where
ciphertext lanes stay memory-resident and host<->device traffic is
index-only. On a single device (the test fabric) everything degrades to
one jit over default-placed buffers: same math, same single dispatch.

R-power accounting for the fused tree is ops/kernel's: K real operands
plus any number of Montgomery-identity pads through any tree shape yield
prod * R^-(K-1); one final multiply by R^K mod n fixes the domain.

The write-path ingest queue (`note_write` / `ingest_pending`) lets the
proxy push committed ciphertexts into existing pools OFF the request's
critical path, debounced — a warm fleet's first post-write
aggregate then pays zero ingest. Content addressing makes this safe: an
ingested row is keyed by its value, so a racing aggregate either finds
the row (identical bytes) or ingests it itself; nothing can go stale.
"""

from __future__ import annotations

import threading

import numpy as np

from dds_tpu.obs import kprof
from dds_tpu.obs.metrics import metrics
from dds_tpu.ops import bignum as bn
from dds_tpu.ops.kernel import (
    FAMILIES, fn_cache, fold_fix, halving_tree, interpret_default, mont_mul,
    pairwise_tree,
)
from dds_tpu.ops.montgomery import ModCtx
from dds_tpu.resident.pool import ResidentPool
from dds_tpu.utils.queues import TimedQueue


def _fused_fold_fn(ctx: ModCtx, S: int, kernel: str, mesh, axis: str):
    """ONE compiled callable per (modulus, S, kernel, interpret, mesh):
    gathers each group's rows from its pool buffer, pads to the common
    power-of-two width with the Montgomery identity, tree-folds every
    group slab, and tail-combines the S partials — all inside a single
    dispatch. Shapes retrace per input under one cache entry."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    interpret = interpret_default()
    use_mesh = (
        mesh is not None and mesh.devices.size > 1
        and S % mesh.devices.size == 0
    )
    L = ctx.L

    def run(bufs, idxs, fix):
        mul = mont_mul(ctx, kernel, interpret)
        one_mont = jnp.asarray(ctx.one_mont)

        def mul_slabs(a, b):                       # (G, h, L) operands
            return mul(a.reshape(-1, L), b.reshape(-1, L)).reshape(a.shape)

        def local_tree(stack):
            # (G, P2, L) -> (G, L): every group slab's halving tree at
            # once, no collectives
            return halving_tree(mul_slabs, stack, axis=1)[:, 0]

        def tail(partials):
            # (S, L) -> (1, L): the combine_partials tail tree, on-device
            return pairwise_tree(mul, partials, one_mont)

        if use_mesh:
            step = jax.shard_map(
                lambda local: tail(
                    jax.lax.all_gather(local_tree(local), axis, tiled=True)
                ),
                mesh=mesh,
                in_specs=P(axis),
                out_specs=P(),  # replicated combined partial
                check_vma=False,
            )
        else:
            step = lambda stack: tail(local_tree(stack))  # noqa: E731
        P2 = 1
        for idx in idxs:
            P2 = max(P2, 1 << max(0, (idx.shape[0] - 1).bit_length()))
        slabs = []
        for buf, idx in zip(bufs, idxs):
            rows = jnp.take(buf, idx, axis=0)
            pad = P2 - rows.shape[0]
            if pad:
                rows = jnp.concatenate(
                    [rows, jnp.broadcast_to(one_mont, (pad, L))], axis=0
                )
            slabs.append(rows)
        return mul(step(jnp.stack(slabs)), fix)

    return fn_cache(
        "resident_fold",
        (ctx.n, S, kernel, interpret, mesh if use_mesh else None, axis),
        lambda: jax.jit(run),
    )


class ResidentPlane:
    """Per-group resident pools + the fused single-dispatch sharded fold.

    `kernel` picks the Montgomery multiply family for the fused fold
    (same rule as the backend's composite paths: v2 on a TPU, the
    portable jnp scans elsewhere). `mesh`/`axis` enable mesh placement;
    None is the single-device fallback. `reduce_factory(modulus)`
    optionally supplies the per-pool single-fold reduce (backends inject
    theirs so lone-group folds use the same kernels as before)."""

    def __init__(self, kernel: str = "jnp", mesh=None, axis: str = "batch",
                 initial_rows: int = 256, max_rows: int = 1 << 20,
                 reduce_factory=None, max_pending: int = 8192):
        self.kernel = kernel if kernel in FAMILIES else "jnp"
        self.mesh = mesh
        self.axis = axis
        self.initial_rows = int(initial_rows)
        self.max_rows = int(max_rows)
        self.max_pending = int(max_pending)
        self._reduce_factory = reduce_factory
        self._lock = threading.Lock()
        # (gid, tenant, modulus) -> pool: Bastion tenant striping puts the
        # tenant id in the pool address, so one tenant overflowing its
        # pool (capacity reset) can never reset another tenant's rows;
        # tenant "" is the legacy/single-tenant stripe
        self._pools: dict[tuple[str, str, int], ResidentPool] = {}
        self._order: dict[str, int] = {}  # gid -> mesh slice index
        # Stratum (dds_tpu/storage): when attached, every pool wires its
        # spill/evict_rank to the tier hierarchy at creation — capacity
        # overflow then demotes to the warm tier instead of resetting
        self.tier_sink = None
        # queued (gid, cipher) write ingests; enqueue-timestamped so the
        # drain can attribute ingest-queue-wait, drops reason-labelled
        self._pending = TimedQueue("lodestone-ingest", maxlen=self.max_pending)

    # ------------------------------------------------------------- topology

    def register_groups(self, gids) -> None:
        """Pin group -> mesh-slice assignment order up front (lazy
        first-use registration works too, but explicit registration keeps
        placement deterministic across proxy restarts)."""
        with self._lock:
            for gid in gids:
                self._order.setdefault(gid, len(self._order))

    def pool(self, gid: str, modulus: int, tenant: str = "") -> ResidentPool:
        with self._lock:
            idx = self._order.setdefault(gid, len(self._order))
            key = (gid, tenant, modulus)
            p = self._pools.get(key)
            if p is None:
                from dds_tpu.parallel.mesh import group_sharding

                injected = self._reduce_factory is not None
                p = self._pools[key] = ResidentPool(
                    modulus,
                    reduce=self._reduce_factory(modulus) if injected else None,
                    kernel=self.kernel if injected else "jnp",
                    initial_rows=self.initial_rows,
                    max_rows=self.max_rows,
                    gid=(f"{gid}|{tenant}" if tenant else gid),
                    sharding=group_sharding(self.mesh, idx, self.axis),
                )
                if self.tier_sink is not None:
                    self.tier_sink.wire_pool(key, p)
            return p

    # ----------------------------------------------------- write-path ingest

    def note_write(self, gid: str, ciphers: list[int],
                   tenant: str = "") -> int:
        """Queue a committed write's ciphertext columns for ingest into
        this group's existing pools FOR THIS TENANT STRIPE (every modulus
        a past aggregate has established). Returns how many were queued;
        with no pool for the (group, tenant) yet there is nothing to
        convert against — the first aggregate ingests as before (a cold
        fleet stays cold-path, but the skipped entries are COUNTED as
        reason="no_pool" drops rather than vanishing silently). A full
        queue rejects with reason="full"; a dropped entry just re-ingests
        lazily at the next fold."""
        if not ciphers:
            return 0
        with self._lock:
            has_pool = any(
                g == gid and t == tenant for g, t, _ in self._pools
            )
        if not has_pool:
            self._pending.drop(len(ciphers), reason="no_pool")
            return 0
        return self._pending.offer_many((gid, tenant, c) for c in ciphers)

    def pending_ingest(self) -> int:
        return self._pending.depth()

    def ingest_pending(self) -> int:
        """Drain the write-ingest queue into the matching pools (run on a
        worker thread, debounced by the proxy).
        Returns rows newly ingested across all pools."""
        batch = self._pending.drain()
        if not batch:
            return 0
        with self._lock:
            pools = list(self._pools.items())
        by_stripe: dict[tuple[str, str], list[int]] = {}
        for gid, tenant, cipher in batch:
            by_stripe.setdefault((gid, tenant), []).append(cipher)
        grew = 0
        for (gid, tenant), ciphers in by_stripe.items():
            for (g, t, _mod), pool in pools:
                if g == gid and t == tenant:
                    grew += pool.ingest(ciphers)
        return grew

    # ------------------------------------------------------------ evaluation

    def fold_groups(
        self, parts: list[tuple[str, list[int]]], modulus: int,
        tenant: str = "",
    ) -> int | None:
        """prod over every group's operands mod `modulus` in ONE fused
        dispatch, or None when any group's operand set cannot fit its
        pool even after a reset (callers fall back to the per-group
        marshaling paths)."""
        import jax.numpy as jnp

        parts = [(gid, ops) for gid, ops in parts if ops]
        if not parts:
            return 1 % modulus
        ctx = ModCtx.make(modulus)
        bufs, idxs, total = [], [], 0
        for gid, ops in parts:
            got = self.pool(gid, modulus, tenant).rows_for(ops)
            if got is None:
                return None
            buf, idx = got
            bufs.append(buf)
            idxs.append(jnp.asarray(idx))
            total += len(ops)
        fn = _fused_fold_fn(ctx, len(parts), self.kernel, self.mesh, self.axis)
        fix = fold_fix(ctx, total)[None, :]
        out = kprof.profiled(
            "resident_fold",
            lambda: fn(tuple(bufs), tuple(idxs), fix),
            k=total, shards=len(parts),
        )
        return bn.limbs_to_int(np.asarray(out)[0])

    def rows_for(self, gid: str, modulus: int, cs: list[int],
                 tenant: str = ""):
        """Gathered device rows (K, L) for `cs` from this group's pool —
        the Prism MatVec operand path — or None when the set is wider
        than the pool (callers marshal host ints as before)."""
        import jax.numpy as jnp

        if not cs:
            return None
        got = self.pool(gid, modulus, tenant).rows_for(cs)
        if got is None:
            return None
        buf, idx = got
        return jnp.take(buf, jnp.asarray(idx), axis=0)

    # --------------------------------------------------------------- surface

    def stats(self) -> dict:
        """Per-pool view for GET /health."""
        import time as _time

        with self._lock:
            pools = dict(self._pools)
        pending = self._pending.depth()
        # reset visibility (the silent fast-path loss): total resets and
        # the age of the most recent one, surfaced so operators see a
        # thrashing pool without scraping metrics or grepping logs
        resets = sum(p.resets for p in pools.values())
        last_ts = max(
            (p._last_reset_ts for p in pools.values()
             if p._last_reset_ts is not None),
            default=None,
        )
        return {
            "kernel": self.kernel,
            "mesh_devices": (
                int(self.mesh.devices.size) if self.mesh is not None else 1
            ),
            "pending_ingest": pending,
            "dropped_pending": self._pending.dropped(),
            "resets": resets,
            "last_reset_age_s": (
                round(_time.time() - last_ts, 1) if last_ts is not None
                else None
            ),
            "tiered": self.tier_sink is not None,
            "pools": [
                {"shard": gid or "-", "tenant": tenant or "-",
                 "modulus_bits": mod.bit_length(), **pool.stats()}
                for (gid, tenant, mod), pool in sorted(
                    pools.items(), key=lambda kv: (kv[0][0], kv[0][1], kv[0][2])
                )
            ],
        }

    def evict_tenant(self, tenant: str) -> int:
        """Drop every pool in `tenant`'s stripe (the data-lifecycle half
        of a crypto-shred: the keys are gone, so the resident rows are
        noise — free the HBM). Returns pools dropped."""
        with self._lock:
            victims = [k for k in self._pools if k[1] == tenant]
            for k in victims:
                self._pools.pop(k, None)
        if victims:
            metrics.inc("dds_tenant_pool_evictions_total",
                        n=len(victims),
                        help="resident pools dropped by tenant eviction "
                             "(crypto-shred data lifecycle)")
        return len(victims)

    def export_gauges(self, registry=metrics) -> None:
        """Scrape-time gauges: dds_resident_{rows,bytes,hit_ratio,resets}
        aggregated per shard label (pools for several moduli sum; the hit
        ratio weights by operands served), plus the write-ingest queue's
        dds_queue_* family."""
        self._pending.export_gauges(registry)
        with self._lock:
            pools = list(self._pools.items())
        per_gid: dict[str, list] = {}
        per_tenant: dict[str, list] = {}
        for (gid, tenant, _mod), pool in pools:
            agg = per_gid.setdefault(gid or "-", [0, 0, 0, [0, 0, 0]])
            agg[0] += pool.resident
            agg[1] += pool.nbytes()
            agg[2] += pool.resets
            for i in range(3):
                agg[3][i] += pool._served[i]
            if tenant:
                tag = per_tenant.setdefault(tenant, [0, 0, 0])
                tag[0] += pool.resident
                tag[1] += pool.nbytes()
                tag[2] += pool.resets
        for tenant, (rows, nbytes, resets) in per_tenant.items():
            registry.set("dds_tenant_resident_rows", rows, tenant=tenant,
                         help="ciphertext rows resident per tenant stripe")
            registry.set("dds_tenant_resident_bytes", nbytes, tenant=tenant,
                         help="device bytes pinned per tenant stripe")
            registry.set("dds_tenant_resident_resets", resets, tenant=tenant,
                         help="pool capacity resets per tenant stripe (one "
                              "tenant's overflow cannot reset another's)")
        for gid, (rows, nbytes, resets, served) in per_gid.items():
            registry.set("dds_resident_rows", rows, shard=gid,
                         help="ciphertext rows resident per shard group")
            registry.set("dds_resident_bytes", nbytes, shard=gid,
                         help="device bytes pinned by resident pools per "
                              "shard group")
            registry.set("dds_resident_resets", resets, shard=gid,
                         help="cumulative resident-pool capacity resets "
                              "per shard group")
            total = sum(served)
            if total:
                registry.set(
                    "dds_resident_hit_ratio", round(served[0] / total, 4),
                    shard=gid,
                    help="fraction of fold operands served from resident "
                         "rows per shard group",
                )
