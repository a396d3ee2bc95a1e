"""Shard-aware storage router: point ops to one group, batches scattered.

Drop-in replacement for a single `AbdClient` at the REST proxy
(`DDSRestServer(abd=ShardRouter(...))`): it exposes the same storage
surface — fetch/write/read_tags plus the breaker/trust views the /health
and /metrics routes read — but resolves each key's owning quorum group
through the `ShardManager`'s active map and delegates to that group's
`AbdClient`. Every delegated client stamps its messages with the map's
epoch (AbdClient.shard_epoch), so replicas can fence stale routes; a
fenced op surfaces as `WrongShardError`, the router refreshes its map
(`refresh` hook — a no-op when the manager is in-process, a /shards pull
in a remote deployment) and the proxy's existing deadline-budgeted retry
re-resolves the owner on the next attempt. No silent misroutes, no new
retry machinery.

`read_tags` — the aggregate cache's validation primitive — is
scatter-gathered: keys partition by owner, each group runs its own
batched tag round concurrently, and the per-key vectors stitch back in
request order. The whole-cache `unchanged` identity contract is
preserved: when EVERY group answers "unchanged" for its slice, the
router returns the caller's `cached_tags` list by identity, so the
proxy's O(1) steady-state aggregate path survives sharding.
"""

from __future__ import annotations

import asyncio
import time
from typing import Optional

from dds_tpu.core.errors import WrongShardError
from dds_tpu.core.quorum_client import AbdClient
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils import sigs
from dds_tpu.utils.retry import Deadline
from dds_tpu.utils.trace import tracer


class _MergedTrust:
    """Read-only union of the per-group trusted-node lists, shaped like
    the TrustedNodesList surface /health and the state gauges consume."""

    def __init__(self, clients: dict[str, AbdClient]):
        self._clients = clients

    def get_trusted(self) -> list[str]:
        out = []
        for c in self._clients.values():
            out.extend(c.replicas.get_trusted())
        return out

    def get_all(self) -> list[str]:
        out = []
        for c in self._clients.values():
            out.extend(c.replicas.get_all())
        return out

    def suspicions(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for c in self._clients.values():
            out.update(c.replicas.suspicions())
        return out


class ShardRouter:
    def __init__(self, manager, clients: dict[str, AbdClient],
                 refresh=None):
        """`clients` maps group id -> that group's AbdClient (each built
        with its own replica set, supervisor, and `cfg.shard` label).
        `refresh` is invoked on every WrongShardError before the retry
        re-resolves — in-process the manager IS current so the default is
        a no-op; a remote router plugs a signed /shards fetch here."""
        self.shard_manager = manager
        self.clients = clients
        self.replicas = _MergedTrust(clients)
        self._refresh = refresh
        # cumulative routed-op count per group id — the Helmsman
        # controller diffs successive snapshots to see per-group load
        # share (hot/cold), so the counters never reset here
        self._op_counts: dict[str, int] = {}
        for gid, c in clients.items():
            # every delegated message carries the ACTIVE map's epoch —
            # late-bound so an activation mid-request stamps correctly
            c.shard_epoch = lambda m=manager: m.current().epoch
            if not c.cfg.shard:
                c.cfg.shard = gid

    # ------------------------------------------------------------- routing

    def owner(self, key: str) -> str:
        return self.shard_manager.current().owner(key)

    def group_ids(self) -> list[str]:
        """Current group ids in construction order — the stable
        group -> mesh-slice assignment Lodestone's resident pools pin
        their device placement by (split-born groups append, so existing
        placements never move)."""
        return list(self.clients)

    def _route(self, key: str) -> tuple[str, AbdClient]:
        gid = self.owner(key)
        client = self.clients.get(gid)
        if client is None:
            raise WrongShardError(key, sent_epoch=self.shard_manager.epoch)
        return gid, client

    def partition_keys(self, keys) -> dict[str, list]:
        """Keys grouped by owning group id (insertion-ordered)."""
        smap = self.shard_manager.current()
        out: dict[str, list] = {}
        for k in keys:
            out.setdefault(smap.owner(k), []).append(k)
        return out

    def _wrong_shard(self, gid: str, err: WrongShardError) -> None:
        metrics.inc(
            "dds_wrong_shard_retries_total", shard=gid,
            help="ops fenced by a replica group and re-routed after a "
                 "shard-map refresh",
        )
        tracer.event("shard.wrong_shard", shard=gid, key=err.key,
                     replica_epoch=err.replica_epoch)
        if self._refresh is not None:
            self._refresh()

    # ----------------------------------------------------------- point ops

    def _charge(self, gid: str, n: int = 1) -> None:
        self._op_counts[gid] = self._op_counts.get(gid, 0) + n

    def load_census(self) -> dict[str, int]:
        """Cumulative routed ops per group, with every CURRENT group
        present (zero-filled) so a cold group is visibly cold."""
        out = {gid: 0 for gid in self.clients}
        out.update(self._op_counts)
        return out

    async def _point(self, op: str, key: str, call):
        gid, client = self._route(key)
        return await self._via(gid, client, op, call)

    async def _via(self, gid: str, client: AbdClient, op: str, call,
                   n: int = 1):
        """`call(client)` as `n` ops charged to group `gid`: timed, and a
        fence from the group refreshes the map before it propagates."""
        self._charge(gid, n)
        t0 = time.perf_counter()
        try:
            return await call(client)
        except WrongShardError as e:
            self._wrong_shard(gid, e)
            raise
        finally:
            metrics.observe(
                "dds_shard_route_seconds", time.perf_counter() - t0,
                shard=gid, op=op,
                help="per-shard storage-op latency at the router",
            )

    async def fetch_set(self, key: str, deadline: Optional[Deadline] = None):
        return (await self.fetch_set_tagged(key, deadline=deadline))[0]

    async def fetch_set_tagged(self, key: str,
                               deadline: Optional[Deadline] = None):
        value, tag, _ = await self.fetch_set_attributed(key, deadline=deadline)
        return value, tag

    async def fetch_set_attributed(self, key: str, exclude=(),
                                   deadline: Optional[Deadline] = None):
        return await self._point(
            "fetch", key,
            lambda c: c.fetch_set_attributed(key, exclude, deadline=deadline),
        )

    async def write_set(self, key: str, value,
                        deadline: Optional[Deadline] = None) -> str:
        return (await self.write_set_tagged(key, value, deadline=deadline))[0]

    async def write_set_tagged(self, key: str, value,
                               deadline: Optional[Deadline] = None):
        return await self._point(
            "write", key,
            lambda c: c.write_set_tagged(key, value, deadline=deadline),
        )

    # ------------------------------------------------------------- batches

    async def fetch_sets_attributed(self, keys, exclude=(),
                                    deadline: Optional[Deadline] = None):
        """Batched quorum read (`AbdClient.fetch_sets_attributed`): the
        keys partitioned by owning group as `read_tags` partitions them,
        one batch a group, gathered; results in request order."""
        keys = list(keys)
        smap = self.shard_manager.current()
        index: dict[str, list[int]] = {}
        for i, k in enumerate(keys):
            index.setdefault(smap.owner(k), []).append(i)

        async def one(gid: str, idxs: list[int]):
            client = self.clients.get(gid)
            if client is None:
                raise WrongShardError(keys[idxs[0]], sent_epoch=smap.epoch)
            sub = [keys[i] for i in idxs]
            return await self._via(
                gid, client, "fetch_batch",
                lambda c: c.fetch_sets_attributed(sub, exclude,
                                                  deadline=deadline),
                n=len(sub))

        results = await asyncio.gather(*(one(g, ix) for g, ix in index.items()))
        out = [None] * len(keys)
        for part, idxs in zip(results, index.values()):
            for i, r in zip(idxs, part):
                out[i] = r
        return out

    async def read_tags(
        self,
        keys: list[str],
        digest: str | None = None,
        fingerprint: bytes | None = None,
        cached_tags: list | None = None,
        deadline: Optional[Deadline] = None,
    ):
        parts = self.partition_keys(keys)
        if len(parts) <= 1:
            # single-group: delegate verbatim so the caller's digest/
            # fingerprint and the `is cached_tags` identity contract pass
            # straight through
            (gid, sub) = next(iter(parts.items())) if parts else (None, [])
            if gid is None:
                return []
            try:
                return await self.clients[gid].read_tags(
                    list(keys), digest=digest, fingerprint=fingerprint,
                    cached_tags=cached_tags, deadline=deadline,
                )
            except WrongShardError as e:
                self._wrong_shard(gid, e)
                raise

        smap = self.shard_manager.current()
        index: dict[str, list[int]] = {}
        for i, k in enumerate(keys):
            index.setdefault(smap.owner(k), []).append(i)

        async def one(gid: str, idxs: list[int]):
            client = self.clients.get(gid)
            if client is None:
                raise WrongShardError(keys[idxs[0]], sent_epoch=smap.epoch)
            self._charge(gid, len(idxs))
            sub_keys = [keys[i] for i in idxs]
            sub_cached = None
            sub_fp = None
            if cached_tags is not None:
                sub_cached = [cached_tags[i] for i in idxs]
                # per-group fingerprint: the caller's covers the WHOLE
                # vector, which no single group can attest
                sub_fp = sigs.tags_fingerprint(sub_cached)
            try:
                return await client.read_tags(
                    sub_keys, fingerprint=sub_fp, cached_tags=sub_cached,
                    deadline=deadline,
                ), sub_cached
            except WrongShardError as e:
                self._wrong_shard(gid, e)
                raise

        results = await asyncio.gather(*(one(g, ix) for g, ix in index.items()))
        if cached_tags is not None and all(
            tags is sub_cached for tags, sub_cached in results
        ):
            return cached_tags  # every group said "unchanged": whole-cache hit
        out = [None] * len(keys)
        for (tags, _), idxs in zip(results, index.values()):
            for i, t in zip(idxs, tags):
                out[i] = t
        return out

    # -------------------------------------------------- health/metrics glue

    @property
    def cfg(self):
        """Group-representative config (quorum size, budgets): groups are
        homogeneous by construction in run.launch; heterogeneous health is
        served per-group by shards_health()."""
        return next(iter(self.clients.values())).cfg

    @property
    def breakers(self) -> dict:
        out = {}
        for c in self.clients.values():
            out.update(c.breakers)
        return out

    def breaker_states(self) -> dict[str, str]:
        out: dict[str, str] = {}
        for c in self.clients.values():
            out.update(c.breaker_states())
        return out

    def breaker_census(self) -> tuple[int, list[float]]:
        """Fleet-wide (trusted coordinator count, refusing-breaker ETAs)
        for the Bulwark controller. Per-group fast-fail needs no router
        code: each delegated AbdClient raises AllBreakersOpenError for ITS
        group when all of that group's coordinators are open past the
        budget — a single dead group degrades its own keys immediately
        without shedding the healthy groups."""
        total, etas = 0, []
        for c in self.clients.values():
            n, e = c.breaker_census()
            total += n
            etas.extend(e)
        return total, etas

    def min_half_open_eta(self) -> float | None:
        _, etas = self.breaker_census()
        positive = [e for e in etas if e > 0]
        return min(positive) if positive else None

    async def stop(self) -> None:
        for c in self.clients.values():
            await c.stop()

    def refresh_from(self, supervisor: str | None = None) -> None:
        """Refresh every group from ITS OWN supervisor (pinned on each
        client's config at build time); the argument — the single
        supervisor a non-sharded proxy would poll — is ignored."""
        for c in self.clients.values():
            if c.cfg.supervisor:
                c.refresh_from(c.cfg.supervisor)

    def shards_health(self) -> dict:
        """Per-group quorum health for GET /health."""
        smap = self.shard_manager.current()
        out = {}
        for gid, c in self.clients.items():
            trusted = c.replicas.get_trusted()
            reachable = [
                n for n in trusted
                if n not in c.breakers or c.breakers[n].allow()
            ]
            out[gid] = {
                "active_replicas": len(trusted),
                "reachable_replicas": len(reachable),
                "quorum_size": c.cfg.quorum_size,
                "degraded": len(reachable) < c.cfg.quorum_size,
                "vnodes": sum(1 for _, g in smap.vnodes if g == gid),
            }
            # Atlas: home-region label (from the signed map) + this
            # client's live lease session, when the group is geo-aware
            region = smap.region_of(gid)
            if region:
                out[gid]["region"] = region
            if c.cfg.lease_enabled:
                out[gid]["lease"] = c.lease_state()
        return out

    def status(self) -> dict:
        """The signed active map + reshard state, for GET /shards."""
        return {
            "state": self.shard_manager.state,
            "map": self.shard_manager.current().to_wire(),
            "groups": {
                gid: sorted(c.replicas.get_all())
                for gid, c in self.clients.items()
            },
        }
