"""REST proxy: the 23-route encrypted query engine.

Counterpart of `dds/http/DDSRestServer.scala:153-948` — same 23 route
names, parameters, JSON shapes and status codes (plus additions of ours:
GET /_trace and the Prism encrypted-analytics family POST /MatVec,
/WeightedSum, /GroupBySum — see dds_tpu/analytics) — rebuilt around two
TPU-first ideas the reference lacks:

- all ciphertext arithmetic goes through the pluggable `CryptoBackend`
  (cpu | tpu); aggregate folds (`SumAll`, `MultAll`) become ONE batched
  tree-reduction over (K, limbs) tensors instead of K sequential
  BigInteger multiplies (`DDSRestServer.scala:412-430, 505-524`);
- storage access goes through the asyncio `AbdClient` quorum functions
  (core/quorum_client.py = `fetchSet`/`writeSet`, `:952-1050`).

Like the reference, the proxy is computation-only: it sees ciphertexts and
per-request public parameters (`nsqr`, `pubkey`), never keys. The other
side of that boundary is enforced too: decryption — the only computation
that touches key material — lives client-side on the Sanctum secret plane
(`dds_tpu/sanctum`), which the shared `CryptoBackend`/`ModCtx` machinery
this server compiles against can no longer carry even by accident
(`PaillierKey.decrypt_batch` refuses public backends;
`tools/secret_lint.py` rejects new flows statically).

Reference quirks deliberately FIXED (SURVEY.md §7 "replicate or fix"):
- `SumAll`/`MultAll`/`Search*` used `length-1 > position`, making the last
  column unreachable; we use `position < length` like `Sum`/`Mult` do.
- `SearchEntry` compared the JSON wrapper's string (`item.toString`)
  instead of the value; we compare the value.
- `SearchEntryAND` matched on 3 *distinct stored values*; we require each
  of the three query values to match (a real conjunction).
"""

from __future__ import annotations

import asyncio
import contextvars
import logging
import math
import ssl
import time
from dataclasses import dataclass, field
from typing import Optional

from dds_tpu.core.admission import AdmissionController, TokenBucket
from dds_tpu.core.errors import (
    AllBreakersOpenError,
    ByzantineError,
    WrongShardError,
)
from dds_tpu.core.quorum_client import AbdClient
from dds_tpu.core.tenant import (CANARY_TENANT, DEFAULT_TENANT, TenantError,
                                 validate_tenant)
from dds_tpu.http import json_protocol as J
from dds_tpu.utils.tasks import supervised_task
from dds_tpu.http.miniserver import HttpServer, Request, Response, http_request
from dds_tpu.http.operand_table import OperandTable
from dds_tpu.models.backend import CryptoBackend, get_backend
from dds_tpu.obs import context as obs_context
from dds_tpu.obs.flight import flight
from dds_tpu.obs.metrics import SIZE_BUCKETS, metrics
from dds_tpu.obs.runtime import LoopSampler
from dds_tpu.obs.slo import SloEngine
from dds_tpu.obs.watchtower import watchtower
from dds_tpu.resident.pool import Operands
from dds_tpu.utils import sigs
from dds_tpu.utils.retry import (
    Deadline,
    DeadlineExceededError,
    RetryPolicy,
    retry_deadline,
)
from dds_tpu.utils.trace import tracer
from dds_tpu.utils.trust import NoTrustedNodesError

log = logging.getLogger("dds.rest")

# The per-request time budget, minted once in handle() and read by every
# nested storage helper (_fetch/_write/_fetch_stored and their audits) —
# deadline PROPAGATION without threading a parameter through 23 routes.
_REQ_DEADLINE: contextvars.ContextVar = contextvars.ContextVar(
    "dds_request_deadline", default=None
)

# The current request's validated tenant (Bastion) — set in handle() next
# to the deadline, read by the ownership checks and the data-plane helpers
# so tenancy needs no parameter threading through 23 routes either.
_REQ_TENANT: contextvars.ContextVar = contextvars.ContextVar(
    "dds_request_tenant", default=DEFAULT_TENANT
)

# transient storage-layer failures worth retrying; anything else (a
# programming error, a bad request) propagates immediately.
# WrongShardError is the Constellation fence: the router refreshes its
# shard map and the retry re-resolves the owner — during a live reshard
# the op stalls inside its Deadline budget until the new map activates,
# then lands on the new group. Never a silent misroute.
_RETRYABLE = (ByzantineError, WrongShardError, asyncio.TimeoutError,
              NoTrustedNodesError, OSError)

# keys one batched re-read carries (`_reread`). A row is about 2 KB on the
# wire at Paillier-2048 and 4.5 KB at 4096, and the reply, the write-back
# and the answer each carry every row of the batch: 256 rows are a frame of
# 0.6 to 1.2 MB, a thirtieth of `TcpNet.MAX_FRAME`, and a handler of a few
# ms on the one loop. The flush after a forged audit (K = 16,384 rows) is
# 64 such batches, gathered.
REREAD_BATCH = 256

# Observability/control routes stay admission-exempt: operators must be
# able to see WHY the system is shedding while it sheds, so /health,
# /metrics, /slo, /shards (and the debug-gated /_trace, and the Meridian
# reshard control route) bypass the Bulwark gate entirely and keep
# answering through a full shed.
_ADMISSION_EXEMPT = frozenset({"health", "metrics", "slo", "shards",
                               "fleet", "profile", "_trace", "_reshard",
                               "_helmsman", "canary"})


@dataclass
class ProxyConfig:
    host: str = "127.0.0.1"
    port: int = 8443
    # Atlas ([fabric] region): the region this proxy process runs in,
    # surfaced on /health so operators (and the geo drills) see which
    # regional vantage a probe answers from
    region: str = ""
    # Deadline-propagated retry (utils/retry): every request gets ONE
    # overall budget minted at the REST edge; quorum attempts + exponential
    # full-jitter backoffs retry inside it, per-attempt timeouts shrink to
    # the remainder, and exhaustion degrades to 503 + Retry-After instead
    # of hanging. retry_backoff is the backoff BASE; retry_attempts > 0
    # restores a hard attempt cap on top (0 = deadline-governed, the
    # chaos-tolerant default).
    request_budget: float = 8.0
    retry_backoff: float = 0.3
    retry_max_delay: float = 2.0
    retry_attempts: int = 0
    # seconds clients should wait before retrying after a 503 (the
    # Retry-After header on every degraded response)
    retry_after_hint: float = 1.0
    # miniserver backstop (0 = off): cancels handlers that somehow outlive
    # the budget — OFF by default because ciphertext compute (device folds,
    # cold compiles) legitimately runs past the STORAGE budget
    handler_timeout: float = 0.0
    crypto_backend: str = "cpu"
    # tag-validated aggregate cache (see _fetch_stored): one batched
    # tag-only quorum round validates all cached sets per aggregate instead
    # of K full ABD re-reads. Off = reference behavior
    # (`DDSRestServer.scala:397-446` re-reads every set, cache-less).
    aggregate_cache: bool = True
    # per-aggregate audit sample: this many cache-served keys are also
    # re-read through a full quorum (random coordinator); any
    # non-corroborated mismatch flushes the cache. Bounds how long a
    # Byzantine COORDINATOR's forgery (valid proxy HMAC over a forged
    # value + the true tag) can persist — without the audit a forged entry
    # would keep validating by tag alone. The bound is probabilistic, and
    # deliberately so: full reads trust a single random coordinator, as the
    # reference's do (`DDSRestServer.scala:952-1000`), so a coordinator
    # holding the proxy secret can always poison the ONE read it serves;
    # what the cache must not add is *persistence*. Even with f colluding
    # coordinators defeating one corroboration round, a forged entry
    # survives future audits only until one samples it through an honest
    # coordinator. Quantified bound (Monte-Carlo-checked in
    # tests/test_tag_cache.py::test_audit_persistence_bound_monte_carlo):
    # detection per aggregate round is geometric with
    #   p = (audit/K) * (n-f)/n
    # (sampled AND audited through an honest coordinator), so expected
    # persistence = K/audit * n/(n-f) rounds — at K=8192, audit=2, n=4,
    # f=1: ~5,461 aggregate rounds; audit=4 halves it, 8 quarters it.
    # Measured throughput cost of raising it: benchmarks/audit_cost.py
    # (each audit key adds one full ABD read per aggregate).
    aggregate_cache_audit: int = 2
    # proxy->proxy key gossip (DDSRestServer.scala:118-136)
    key_sync_enabled: bool = False
    key_sync_warmup: float = 1.0
    key_sync_interval: float = 5.0
    peers: list[str] = field(default_factory=list)  # "host:port"
    # stored_keys durability. The reference keeps the aggregate key set
    # in-memory only (`DDSRestServer.scala:70`), so a proxy restart makes
    # every aggregate silently shrink until re-population — flagged as a
    # do-not-copy quirk (SURVEY.md §7). Two recovery sources, both opt-in:
    # - keys_path: JSON snapshot, written atomically (debounced ~200 ms
    #   after a mutation burst) and loaded at start();
    # - a one-shot GET /_sync pull from each gossip peer at start() (gated
    #   with key_sync_enabled), covering proxies deployed without a disk.
    # The set only names which records aggregates cover — values still come
    # from the replicated store through full quorum reads, so a stale
    # snapshot can at worst omit recent keys until gossip catches up, never
    # serve stale data.
    keys_path: str = ""
    # GET /_trace observability route. Default OFF: it reveals workload
    # shape (route counts, latencies, store size) to anyone who can reach
    # the client-facing listener — the reference gates observability
    # behind debug flags too (dds-system.conf:61-62). launch() enables it
    # for debug deployments.
    trace_route_enabled: bool = False
    # GET /metrics (Prometheus text, obs/metrics). Default ON: scrapers
    # are how the "production-scale" posture monitors this thing, and the
    # aggregated series reveal far less workload shape than /_trace's
    # per-span stats. Deployments that must hide even rates can turn it
    # off (config `obs.metrics_route = false`).
    metrics_route_enabled: bool = True
    # GET /slo (per-route objectives + error-budget burn state, plus the
    # Watchtower audit summary). Default ON for the same reason /metrics
    # is: it is the health surface operators page on, and it reveals no
    # more workload shape than the per-route metric series already do.
    slo_route_enabled: bool = True
    # GET /profile (Chronoscope per-route/per-stage pipe profile +
    # slow-trace exemplars, obs/chronoscope). Default ON like /slo — the
    # per-stage aggregate reveals less workload shape than /_trace; the
    # DDS_OBS_PIPE=0 env kill-switch disables profiling itself.
    profile_route_enabled: bool = True
    # Prism encrypted-analytics routes (analytics/prism.py): POST /MatVec,
    # /WeightedSum, /GroupBySum evaluate plaintext-weight x ciphertext
    # products server-side over public parameters only. The row cap bounds
    # per-request kernel work (DDS_ANALYTICS_MAX_ROWS env overrides it;
    # ops/flags.analytics_max_rows validates whichever wins); the byte cap
    # 413s oversized weight payloads before JSON parsing.
    analytics_enabled: bool = True
    analytics_max_rows: int = 256
    analytics_max_request_bytes: int = 1 << 20
    # Bulwark admission control (core/admission): an AdmissionConfig-shaped
    # object (utils/config.AdmissionConfig, or any duck-typed twin) with
    # enabled=True arms per-tenant/per-class token buckets and the
    # SLO-burn shedding ratchet at the edge — rejections answer 429/503 in
    # microseconds, BEFORE a Deadline is minted. None/disabled = the
    # pre-Bulwark behavior (every request admitted).
    admission: object = None
    # Bastion multi-tenancy (core/tenant, models/tenancy): a TenancyConfig-
    # shaped object with enabled=True makes the x-dds-tenant header an
    # isolation boundary — per-tenant key ownership (cross-tenant access
    # answers a typed 403), tenant-striped Lodestone pools and Spyglass
    # indexes, tenant-filtered aggregates/analytics, weighted-fair
    # admission with burn-driven per-tenant shedding, and per-tenant
    # SLO/usage attribution. None/disabled = the single-tenant behavior
    # byte-for-byte (every plane call maps to the anonymous "" stripe).
    tenancy: object = None
    # Lodestone resident ciphertext plane (dds_tpu/resident): a
    # ResidentConfig-shaped object with enabled=True pins per-shard-group
    # ciphertext limb pools device-side, ingests committed writes off the
    # request path, and turns sharded SumAll/MultAll into ONE fused
    # gather+fold dispatch instead of S per-group marshaling folds.
    # None/disabled = the pre-Lodestone paths exactly.
    resident: object = None
    # Spyglass encrypted search plane (dds_tpu/search): a SearchConfig-
    # shaped object with enabled=True serves Search*/Order*/Range from
    # per-group device-resident DET/OPE column indexes — ONE batched tag
    # round + one predicate kernel dispatch per query instead of the
    # legacy full-keyspace scan. None/disabled = the legacy scan exactly.
    search: object = None
    # Stratum tiered ciphertext storage (dds_tpu/storage): a
    # StorageConfig-shaped object with enabled=True layers a host-pinned
    # warm cache and an HMAC'd log-structured segment store under the
    # Lodestone pools, replacing capacity resets with eviction-to-warm
    # and splitting folds into resident + streamed-from-tier legs.
    # None/disabled = Lodestone-only behavior exactly.
    storage: object = None
    # active-replica refresh from supervisor (DDSRestServer.scala:139-147)
    replica_refresh_interval: float = 5.0
    supervisor: Optional[str] = None
    # Meridian (dds_tpu/fabric): cap on the `wait` a /shards long-poll may
    # request (If-None-Match + ?wait=N gossip — see the shards route), and
    # the POST /_reshard operator route gate (enabled on proxies launched
    # with a fabric controller; drives a cross-host Rebalancer.split)
    shards_wait_cap: float = 60.0
    reshard_route_enabled: bool = False
    # Heliograph active canary plane (dds_tpu/obs/heliograph): a
    # HeliographConfig-shaped object with enabled=True runs a supervised
    # prober owning the reserved __heliograph__ tenant, driving verified
    # golden transactions against this proxy's own edge (and any
    # configured targets). None/disabled = no prober; canary-tagged
    # traffic is still clamped + rate-bounded at the edge either way.
    heliograph: object = None
    ssl_server_context: object = None
    ssl_client_context: object = None


def _fold_after_wait(fold, operands: list[int], modulus: int,
                     t_call: float, t_done: list):
    """The worker thread's side of `DDSRestServer._fold`: note
    how long the call waited for this thread, fold, and leave in
    `t_done[0]` the instant the result was ready."""
    t_run = time.perf_counter()
    tracer.record("dispatch.thread_wait", (t_run - t_call) * 1e3,
                  _ctx=obs_context.child(), _t_end=t_run)
    try:
        return fold(operands, modulus)
    finally:
        t_done[0] = time.perf_counter()


async def _cancel_task(task: asyncio.Task) -> None:
    """Cancel a background task and swallow its CancelledError."""
    task.cancel()
    try:
        await task
    except asyncio.CancelledError:
        pass


def _pick_outside(n: int, gone, want: int) -> list[int]:
    """Up to `want` distinct positions of range(n) outside the set `gone`,
    uniformly: the audit's sample among the entries the tag round
    confirmed, without listing them when few are gone."""
    import random

    want = min(want, n - len(gone))
    if 2 * len(gone) > n:
        return random.sample([i for i in range(n) if i not in gone], want)
    out: set[int] = set()
    while len(out) < want:
        i = random.randrange(n)
        if i not in gone:
            out.add(i)
    return list(out)


class DDSRestServer:
    def __init__(self, abd: AbdClient, config: ProxyConfig | None = None,
                 local_replicas: dict | None = None,
                 slo: SloEngine | None = None,
                 gossip=None, reshard=None, fleet=None, helmsman=None):
        self.abd = abd
        self.cfg = config or ProxyConfig()
        # Meridian wiring: `gossip` is an EpochGossipHub parked /shards
        # long-polls sleep on (None = conditional GETs answer immediately);
        # `reshard` is the reshard controller behind POST /_reshard (gated
        # by reshard_route_enabled) — either an object with async
        # `split(source, target)` / `merge(source)` plus `retry_after()`
        # and `phase`, or a bare legacy split callable; `fleet` is the
        # Panopticon FleetCollector serving GET /fleet/* (None everywhere
        # but a fleet-enabled proxy role — the routes 404); `helmsman` is
        # the fleet autoscaler (report in /health, pin via /_helmsman)
        self._gossip = gossip
        self._reshard = reshard
        self._fleet = fleet
        self.helmsman = helmsman
        # one plan at a time: the in-flight (action, source, target) and
        # its task — identical repeats attach to it (idempotent), any
        # other reshape answers 409 + a phase-derived Retry-After
        self._reshard_inflight: dict | None = None
        # per-route SLO accounting (obs/slo): every request is classified
        # good/bad in handle(); run.launch passes an engine built from the
        # [obs] config, tests get the defaults
        self.slo = slo or SloEngine()
        # endpoint -> BFTABDNode for replicas hosted in THIS process (the
        # live dict from run.launch — redeploys mutate it in place), so
        # /health and /metrics can export the Aegis recovery surface:
        # anti-entropy divergence/sync age and snapshot generation/age
        self.local_replicas = local_replicas
        self.backend: CryptoBackend = get_backend(self.cfg.crypto_backend)
        self.stored_keys: set[str] = set()
        # key -> (tag, value): every entry comes from a COMPLETED quorum op
        # (read with write-back, or write), so value@tag is known to be
        # written to a full quorum — the invariant the tag-validation read
        # path relies on for linearizability.
        self._cache: dict[str, tuple] = {}
        # the aggregate hot path's state (http/operand_table): sorted keys,
        # the (tag, value) entry per key, parsed operand columns and their
        # pool rows, kept across aggregates and patched by the keys in
        # `_dirty` (those whose cache entry moved since the table last took
        # them in). A change of the key set or a cache flush drops it; the
        # next aggregate builds it again. The tag-validation quorum round
        # and the audit still run on EVERY aggregate: the table skips
        # recomputation, never revalidation.
        self._stored_version = 0   # bumps on stored_keys add/discard/sync
        self._table: OperandTable | None = None
        self._dirty: set[str] = set()
        self._http = HttpServer(
            self.cfg.host, self.cfg.port, self.handle, self.cfg.ssl_server_context,
            handler_timeout=self.cfg.handler_timeout,
        )
        self._tasks: list[asyncio.Task] = []
        self._loop_sampler: LoopSampler | None = None
        self._keys_dirty = False
        self._keys_saver: asyncio.Task | None = None
        # Constellation: a ShardRouter (duck-typed via its shard_manager)
        # turns point routes into one-group ops and aggregates into
        # scatter-gather per-shard folds; a plain AbdClient leaves every
        # path exactly as before
        self._shards = getattr(abd, "shard_manager", None)
        self._owner_memo: tuple | None = None    # pairs identity -> (gid, ops)
        # Lodestone (dds_tpu/resident): per-group device-resident pools +
        # the fused single-dispatch sharded fold. Built from the
        # ResidentConfig-shaped cfg.resident; None when disabled — every
        # gate below is a cheap is-None check. The plane rides the
        # backend's kernel family/mesh when the backend exposes them
        # (TpuBackend.resident_plane); host backends get the portable
        # jnp plane (same math, same single dispatch).
        rescfg = self.cfg.resident
        self._resident = None
        self._resident_min_fold = 0
        self._resident_write_ingest = False
        self._resident_ingest_window = 0.005
        self._ingest_task: asyncio.Task | None = None
        if rescfg is not None and getattr(rescfg, "enabled", False):
            initial = getattr(rescfg, "initial_rows", 256)
            max_rows = getattr(rescfg, "max_rows", 65536)
            if hasattr(self.backend, "resident_plane"):
                self._resident = self.backend.resident_plane(initial, max_rows)
            else:
                from dds_tpu.resident import ResidentPlane

                self._resident = ResidentPlane(
                    initial_rows=initial, max_rows=max_rows
                )
            mf = getattr(rescfg, "min_fold", 0)
            self._resident_min_fold = (
                mf if mf > 0 else getattr(self.backend, "min_device_batch", 0)
            )
            self._resident_write_ingest = getattr(rescfg, "write_ingest", True)
            self._resident_ingest_window = max(
                0.0, getattr(rescfg, "ingest_window", 0.005)
            )
            group_ids = getattr(self.abd, "group_ids", None)
            if group_ids is not None:
                # deterministic group -> mesh-slice placement up front
                self._resident.register_groups(group_ids())
        # Spyglass (dds_tpu/search): per-group search indexes over the
        # DET/OPE column families, written from the request path (queued,
        # debounced — the Lodestone ingest pattern) and validated per
        # query with one batched read_tags round. None when disabled —
        # every Search*/Order*/Range gate below is a cheap is-None check
        # that falls through to the legacy scan.
        scfg = self.cfg.search
        self._search = None
        self._search_write_ingest = False
        self._search_ingest_window = 0.005
        self._search_ingest_task: asyncio.Task | None = None
        if scfg is not None and getattr(scfg, "enabled", False):
            from dds_tpu.search import SearchPlane

            self._search = SearchPlane(
                max_pending=getattr(scfg, "max_pending", 8192)
            )
            self._search_write_ingest = getattr(scfg, "write_ingest", True)
            self._search_ingest_window = max(
                0.0, getattr(scfg, "ingest_window", 0.005)
            )
            group_ids = getattr(self.abd, "group_ids", None)
            if group_ids is not None:
                self._search.register_groups(group_ids())
        # Stratum (dds_tpu/storage): the tier planner under Lodestone.
        # Built only when a resident plane exists — the hot tier IS the
        # pool; attach() rewires pool overflow from reset to eviction
        # and routes folds through the hot+warm+cold split. None when
        # disabled — every gate below is a cheap is-None check.
        stcfg = self.cfg.storage
        self._stratum = None
        if (
            stcfg is not None
            and getattr(stcfg, "enabled", False)
            and self._resident is not None
        ):
            from dds_tpu.storage import Stratum

            self._stratum = Stratum(
                self._resident,
                getattr(stcfg, "dir", "./stratum"),
                warm_bytes=getattr(stcfg, "warm_bytes", 64 << 20),
                chunk_rows=getattr(stcfg, "chunk_rows", 256),
                promote_score=getattr(stcfg, "promote_score", 2.0),
                max_promote=getattr(stcfg, "max_promote", 256),
                half_life=getattr(stcfg, "half_life", 60.0),
                keep=getattr(stcfg, "keep", 3),
                compact_segments=getattr(stcfg, "compact_segments", 8),
            )
            if self._search is not None:
                # Spyglass selections feed the tier directory: keys a
                # query keeps finding hold their fold rows hot
                self._search.touch_sink = self._stratum.touch_keys
        # Prism analytics engine (analytics/prism): same backend, same
        # public-parameter boundary; sharded proxies hand it the router's
        # owner resolver so weighted folds scatter-gather like SumAll,
        # and the resident plane so MatVec operands gather from pinned
        # rows instead of re-marshaling host ints
        if self.cfg.analytics_enabled:
            from dds_tpu.analytics import Prism
            from dds_tpu.ops.flags import analytics_max_rows

            self.prism: Prism | None = Prism(
                backend=self.backend,
                max_rows=analytics_max_rows(self.cfg.analytics_max_rows),
                owner=(self.abd.owner if self._shards is not None else None),
                resident=self._resident,
            )
        else:
            self.prism = None
        self._column_memo: tuple | None = None  # pairs identity -> columns
        # Bulwark (core/admission): the admission gate + shed ratchet, fed
        # by the SLO engine's burn alerts and the storage layer's breaker
        # census. None when admission is off — every gate below is a
        # cheap is-None check.
        # Bastion (core/tenant + models/tenancy): tenancy makes the
        # validated x-dds-tenant header an isolation boundary. The server
        # holds NO tenant keys (the TenantKeyring is client-side, like the
        # Sanctum decrypt plane) — its tenancy surface is ownership
        # enforcement (typed 403s), plane striping, tenant-filtered
        # aggregates, and attribution. `_tenant_owner` maps each stored
        # key to the tenant whose PutSet claimed it; it persists inside
        # the stored-keys snapshot (backward-compatible: legacy list
        # snapshots load as ownerless keys).
        tcfg = self.cfg.tenancy
        self._tenancy_enabled = bool(
            tcfg is not None and getattr(tcfg, "enabled", False)
        )
        self._tenant_owner: dict[str, str] = {}
        self._tenant_pairs_memo: dict[str, tuple] = {}
        acfg = self.cfg.admission
        self.admission: AdmissionController | None = None
        if acfg is not None and getattr(acfg, "enabled", False):
            self.admission = AdmissionController.from_config(
                acfg, alerts=self.slo.alerts, breakers=self._breaker_census,
                tenancy=(tcfg if self._tenancy_enabled else None),
            )
        # Heliograph (obs/heliograph): the prober itself starts in
        # start() (it needs the resolved listen port), but the canary
        # admission carve-out exists UNCONDITIONALLY: anything claiming
        # the __heliograph__ identity bypasses tenant-fair admission yet
        # passes this dedicated bucket, so neither a wedged prober nor an
        # outsider squatting on the canary tenant can self-DoS the edge
        # (the reserved id grants zero data access beyond the canary's
        # own keyspace — see _tenant_pairs).
        hcfg = self.cfg.heliograph
        self.heliograph = None
        self._canary_bucket = TokenBucket(
            float(getattr(hcfg, "rate", 20.0) or 20.0),
            float(getattr(hcfg, "burst", 40.0) or 40.0),
        )
        # keys the canary tenant owns, tracked in BOTH tenancy modes: the
        # aggregate/search/analytics planes must never fold canary rows
        # into user answers (nor user rows into canary ground truth —
        # that scoping is what makes decrypt-and-compare sound).
        self._canary_keys: set[str] = set()

    # ------------------------------------------------------------ lifecycle

    async def start(self) -> None:
        self._load_keys()
        await self._http.start()
        self.cfg.port = self._http.port  # resolve OS-assigned port 0
        # the loop this proxy serves on: its lag, the collector's pauses
        # and a stall are the host runtime's share of every latency
        self._loop_sampler = LoopSampler()
        self._loop_sampler.start()
        if self.cfg.key_sync_enabled and self.cfg.peers:
            await self._bootstrap_keys_from_peers()
            self._tasks.append(supervised_task(self._key_sync_loop(),
                                               name="proxy.key_sync"))
        if self.cfg.supervisor:
            if self.abd.cfg.supervisor is None:
                self.abd.cfg.supervisor = self.cfg.supervisor  # pin ActiveReplicas source
            self._tasks.append(supervised_task(self._replica_refresh_loop(),
                                               name="proxy.replica_refresh"))
        if self.admission is not None:
            self._tasks.append(supervised_task(self._admission_loop(),
                                               name="proxy.admission"))
        hcfg = self.cfg.heliograph
        if hcfg is not None and getattr(hcfg, "enabled", False):
            # deferred import: the prober pulls the whole client crypto
            # stack, which most deployments (and tests) never need
            from dds_tpu.obs.heliograph import Heliograph

            self.heliograph = Heliograph(
                hcfg, self._canary_targets(hcfg), slo=self.slo,
                watchtower=watchtower,
                ssl_context=self.cfg.ssl_client_context,
            )
            self.heliograph.start()

    def _canary_targets(self, hcfg) -> list:
        """Probe targets: this proxy's own loopback edge first (the
        resolved port — start() runs after the listener binds), then any
        configured "host:port" / "region=host:port" entries — per-region
        / per-group targeting for fleets."""
        from dds_tpu.clt.canary import CanaryTarget, parse_canary_targets

        host = self.cfg.host
        if host in ("0.0.0.0", "::", ""):
            host = "127.0.0.1"
        targets = [CanaryTarget(host, self.cfg.port,
                                region=self.cfg.region or "")]
        extra, bad = parse_canary_targets(getattr(hcfg, "targets", []))
        for entry in bad:
            log.warning("heliograph: skipping malformed target %r", entry)
        return targets + extra

    async def stop(self) -> None:
        if self.heliograph is not None:
            self.heliograph.stop()
        if self._loop_sampler is not None:
            await self._loop_sampler.stop()
            self._loop_sampler = None
        for t in self._tasks:
            t.cancel()
        for t in self._tasks:
            try:
                await t
            except asyncio.CancelledError:
                pass
        self._tasks.clear()
        if self._ingest_task is not None:
            await _cancel_task(self._ingest_task)
            self._ingest_task = None
        if self._search_ingest_task is not None:
            await _cancel_task(self._search_ingest_task)
            self._search_ingest_task = None
        if self._keys_saver is not None:
            await _cancel_task(self._keys_saver)
            self._keys_saver = None
        if self._keys_dirty:
            self._write_keys_snapshot()  # flush pending mutations on shutdown
        await self.abd.stop()   # its breakers' probes
        await self._http.stop()

    # ------------------------------------------------- stored_keys recovery

    def _load_keys(self) -> None:
        if not self.cfg.keys_path:
            return
        import json as _json
        import pathlib

        p = pathlib.Path(self.cfg.keys_path)
        if not p.exists():
            return
        try:
            keys = _json.loads(p.read_text())
        except (OSError, ValueError) as e:
            log.warning("ignoring unreadable stored-keys snapshot %s: %s", p, e)
            return
        owners = {}
        if isinstance(keys, dict):
            # Bastion snapshot shape: {"keys": [...], "tenants": {key: t}}
            owners = keys.get("tenants") or {}
            keys = keys.get("keys")
        if not isinstance(keys, list):  # hand-edited / corrupted snapshot
            log.warning("ignoring malformed stored-keys snapshot %s", p)
            return
        for k in keys:
            if isinstance(k, str):
                self.stored_keys.add(k)
        if isinstance(owners, dict):
            for k, t in owners.items():
                if isinstance(k, str) and isinstance(t, str):
                    self._tenant_owner[k] = t
        self._stored_version += 1
        log.info("recovered %d stored keys from %s", len(self.stored_keys), p)

    def _write_keys_snapshot(self) -> None:
        """Atomic write (tmp + rename): a crash mid-write must leave the
        previous snapshot intact, not a truncated JSON file."""
        import json as _json
        import os
        import pathlib

        self._keys_dirty = False
        p = pathlib.Path(self.cfg.keys_path)
        if self._tenant_owner:
            # ownership rides the snapshot: a restarted proxy must keep
            # refusing cross-tenant access to keys written before the crash
            body = {"keys": sorted(self.stored_keys),
                    "tenants": dict(self._tenant_owner)}
        else:
            body = sorted(self.stored_keys)  # legacy shape, byte-identical
        try:
            p.parent.mkdir(parents=True, exist_ok=True)
            tmp = p.with_name(p.name + ".tmp")
            tmp.write_text(_json.dumps(body))
            os.replace(tmp, p)
        except OSError as e:
            log.warning("stored-keys snapshot to %s failed: %s", p, e)

    def _save_keys_soon(self) -> None:
        """Debounced snapshot: coalesce a PutSet burst into one write."""
        if not self.cfg.keys_path:
            return
        self._keys_dirty = True
        if self._keys_saver is not None and not self._keys_saver.done():
            return

        async def _saver():
            while self._keys_dirty:
                await asyncio.sleep(0.2)
                # off-loop: a large stored_keys set must not stall request
                # handling during the write (stop() keeps the synchronous
                # call — the loop is tearing down anyway)
                await asyncio.to_thread(self._write_keys_snapshot)

        self._keys_saver = supervised_task(_saver(), name="proxy.keys_saver")

    async def _bootstrap_keys_from_peers(self) -> None:
        """One-shot key pull at start: a restarted proxy must not wait for
        a peer's next gossip push to see the store's aggregate keys.
        Pulls run concurrently so N dead peers cost one timeout, not N;
        any failure is opportunistic-best-effort — it must never turn a
        recovery optimization into a boot failure."""

        async def pull(peer: str) -> None:
            host, _, port = peer.partition(":")
            try:
                status, body = await http_request(
                    host, int(port), "GET", "/_sync",
                    ssl_context=self.cfg.ssl_client_context, timeout=5.0,
                )
                if status != 200:
                    return
                import json as _json

                before = len(self.stored_keys)
                for k in J.parse_keys(_json.loads(body)):
                    self._note_stored(k)
                log.info(
                    "bootstrapped %d stored keys from peer %s",
                    len(self.stored_keys) - before, peer,
                )
            except (OSError, ValueError, EOFError, asyncio.TimeoutError) as e:
                # EOFError covers IncompleteReadError (peer closed mid-body)
                log.debug("stored-keys bootstrap from %s failed: %s", peer, e)

        await asyncio.gather(*(pull(p) for p in self.cfg.peers))

    async def _key_sync_loop(self) -> None:
        await asyncio.sleep(self.cfg.key_sync_warmup)
        while True:
            for peer in self.cfg.peers:
                host, _, port = peer.partition(":")
                try:
                    import json as _json

                    await http_request(
                        host,
                        int(port),
                        "POST",
                        "/_sync",
                        _json.dumps(J.keys_result(sorted(self.stored_keys))).encode(),
                        ssl_context=self.cfg.ssl_client_context,
                        timeout=5.0,
                    )
                except ssl.SSLError as e:
                    # loud: under mutual TLS this usually means the peer has
                    # a different CA (per-node dev certs on a multi-host
                    # deployment — see SecurityConfig.tls_ca)
                    log.warning("key-sync peer %s TLS failure: %s", peer, e)
                except OSError:
                    log.debug("key-sync peer %s unreachable", peer)
                except asyncio.TimeoutError:
                    log.debug("key-sync peer %s timed out", peer)
            await asyncio.sleep(self.cfg.key_sync_interval)

    async def _replica_refresh_loop(self) -> None:
        while True:
            self.abd.refresh_from(self.cfg.supervisor)
            await asyncio.sleep(self.cfg.replica_refresh_interval)

    # ----------------------------------------------------------- ABD access

    def _request_deadline(self) -> Deadline:
        """The current request's budget; helpers invoked outside a request
        context (tests, background tasks) get a fresh full budget."""
        dl = _REQ_DEADLINE.get()
        return dl if dl is not None else Deadline(self.cfg.request_budget)

    def _retry_policy(self) -> RetryPolicy:
        attempts = self.cfg.retry_attempts
        return RetryPolicy(
            base=self.cfg.retry_backoff,
            max_delay=self.cfg.retry_max_delay,
            max_attempts=(attempts + 1) if attempts > 0 else None,
        )

    async def _retry(self, f, deadline: Deadline):
        return await retry_deadline(
            f, deadline, self._retry_policy(), retry_on=_RETRYABLE
        )

    def _cache_put(self, key: str, tag, value) -> None:
        """Remember a completed op's (tag, value); newest tag wins (two
        interleaved ops on one key may resolve out of order here)."""
        if tag is None or not self.cfg.aggregate_cache:
            return
        cur = self._cache.get(key)
        if cur is None or cur[0] < tag:
            self._cache[key] = (tag, value)
            if self._table is not None:
                # a mark, no parse and no hash of the row: the next
                # aggregate takes the entry into its operand table
                self._dirty.add(key)

    def _flush_cache(self) -> None:
        self._cache.clear()
        self._table = None   # its entries were the cache's: build anew
        self._dirty.clear()
        if self._search is not None:
            # the search index inherits the cache's completed-op trust
            # argument, so an audit-triggered flush voids it too: the next
            # query rebuilds every entry from full quorum reads
            self._search.invalidate()

    def _note_stored(self, key: str) -> None:
        if key not in self.stored_keys:
            self.stored_keys.add(key)
            self._stored_version += 1
            self._save_keys_soon()

    # ------------------------------------------------------ Bastion tenancy

    def _req_tenant(self) -> str:
        """The current request's validated tenant (helpers invoked outside
        a request context — tests, background tasks — read the default)."""
        return _REQ_TENANT.get()

    def _plane_tenant(self, tenant: str | None = None) -> str:
        """Tenant id as the data planes see it: the default tenant maps to
        the anonymous "" stripe, so single-tenant deployments keep their
        pool keys, group indexes, and gauge label sets byte-identical."""
        if not self._tenancy_enabled:
            return ""
        t = tenant if tenant is not None else _REQ_TENANT.get()
        return "" if t == DEFAULT_TENANT else t

    def _key_tenant(self, key: str) -> str | None:
        """The tenant a key belongs to. Stored keys without an ownership
        record are legacy (pre-Bastion) data and belong to the default
        tenant; keys neither recorded nor stored are unclaimed (None) —
        free for any tenant's first write to claim."""
        t = self._tenant_owner.get(key)
        if t is not None:
            return t
        return DEFAULT_TENANT if key in self.stored_keys else None

    def _note_owner(self, key: str) -> None:
        """Record the writing tenant as `key`'s owner (first writer wins;
        _tenant_denied refuses the write before this runs otherwise).
        Canary ownership is tracked in BOTH tenancy modes: the visibility
        scoping in `_tenant_pairs` / `_tenant_stored_keys` depends on it
        (canary rows must never pollute user aggregates, untenanted
        deployments included)."""
        tenant = _REQ_TENANT.get()
        if tenant == CANARY_TENANT and key not in self._canary_keys:
            self._canary_keys.add(key)
            self._tenant_pairs_memo.clear()
        if not self._tenancy_enabled:
            return
        if self._tenant_owner.get(key) != tenant:
            self._tenant_owner[key] = tenant
            self._tenant_pairs_memo.clear()
            self._save_keys_soon()

    def _tenant_denied(self, *keys: str) -> Response | None:
        """Typed 403 when the request's tenant owns none of `keys` it
        touches; None admits. Unclaimed keys admit (a first PutSet claims
        one; reads of a nonexistent key 404 as always); stored keys
        without a record are legacy data under the default tenant, and
        nowhere else. The refusal is explicit and attributed: requests
        are NEVER silently served another tenant's ciphertexts."""
        if not self._tenancy_enabled:
            return None
        tenant = _REQ_TENANT.get()
        for key in keys:
            owner = self._key_tenant(key)
            if owner is not None and owner != tenant:
                metrics.inc(
                    "dds_tenant_denied_total", tenant=tenant,
                    help="cross-tenant key accesses refused with 403",
                )
                flight.record("tenant_denied", tenant=tenant, key=key)
                return Response.json(
                    {"error": "cross-tenant access denied",
                     "tenant": tenant, "key": key},
                    status=403,
                )
        return None

    def _full_view(self) -> bool:
        """Whether this request's tenant sees every stored record. Without
        Bastion that is everyone but the Heliograph canary, as long as no
        canary key is stored: the canary tenant sees exactly its own
        population (what makes decrypt-and-compare exact) and everyone
        else everything BUT it."""
        return (not self._tenancy_enabled and not self._canary_keys
                and _REQ_TENANT.get() != CANARY_TENANT)

    def _tenant_pairs(self, pairs: list[tuple[str, list]]) -> list:
        """The aggregate/search view filtered to the request tenant's own
        records (`_full_view`: the same list identity, so every
        downstream pairs-identity memo stays warm). Memoized per (tenant,
        pairs identity): between writes each tenant's filtered view is
        state-identical, and its stable identity is what the column and
        partition memos key on."""
        if self._full_view():
            return pairs
        tenant = _REQ_TENANT.get()
        memo = self._tenant_pairs_memo.get(tenant)
        if memo is not None and memo[0] is pairs:
            return memo[1]
        if self._tenancy_enabled:
            own = self._key_tenant
            filtered = [(k, v) for k, v in pairs if own(k) == tenant]
        else:
            ck = self._canary_keys
            mine = tenant == CANARY_TENANT
            filtered = [(k, v) for k, v in pairs if (k in ck) == mine]
        self._tenant_pairs_memo[tenant] = (pairs, filtered)
        return filtered

    def _tenant_stored_keys(self) -> list[str]:
        """Sorted stored keys scoped to the request tenant (the Spyglass
        query universe); tenancy off = all stored keys, as before."""
        tenant = _REQ_TENANT.get()
        if not self._tenancy_enabled:
            ck = self._canary_keys
            if tenant == CANARY_TENANT:
                return sorted(k for k in self.stored_keys if k in ck)
            if not ck:
                return sorted(self.stored_keys)
            return sorted(k for k in self.stored_keys if k not in ck)
        own = self._key_tenant
        return sorted(k for k in self.stored_keys if own(k) == tenant)

    def _sync_table(self) -> OperandTable:
        """The operand table an aggregate starts on: the current one with
        every cache entry that moved since it last looked taken in (O(moved
        keys)); when the key set changed, the old table grown by its new
        keys (`OperandTable.grown`: copies and splices of K pointers, the
        added rows parsed) if none of its keys went and the added are at
        most an eighth of them (`len(added) * 8 <= len(table.keys)`: past
        that the splices cost what a sort does); else one built anew (a
        sort of K keys and K cache probes): keys removed, too many added,
        the cache flushed or off, no table yet. `assembly.state` times it,
        with the tag list and fingerprint of the round to come, whenever
        there is something to do; `built` says which, `added` how many of
        the keys the table before did not have."""
        table = self._table
        build = (table is None
                 or table.stored_version != self._stored_version
                 or not self.cfg.aggregate_cache)
        if not build and not self._dirty:
            return table
        with tracer.span("assembly.state") as sm:
            built, added = "patched", ()
            if build:
                grown = None
                if table is not None and self.cfg.aggregate_cache:
                    added = self.stored_keys - table.index.keys()
                    if (len(added) * 8 <= len(table.keys)
                            and table.index.keys() <= self.stored_keys):
                        grown = OperandTable.grown(
                            table, added, self._cache, self._stored_version)
                if grown is None:
                    built, grown = "anew", OperandTable(
                        sorted(self.stored_keys), self._cache,
                        self._stored_version,
                    )
                    self._dirty.clear()   # its entries are the cache's
                else:
                    built = "grown"
                table = self._table = grown
            self._take_dirty(table)
            table.round_args()
            sm["k"] = len(table.keys)
            sm["cached"] = len(table.keys) - table.uncached
            sm["built"], sm["added"] = built, len(added)
        return table

    def _take_dirty(self, table: OperandTable) -> None:
        """Patch `table` by the keys whose cache entry moved, if it is
        still the current table: an aggregate that began on a key set
        since replaced finishes on its own, which `_cache_put` no longer
        marks for."""
        if table is self._table and self._dirty:
            index, cache = table.index, self._cache
            table.apply([(index[k], cache[k]) for k in self._dirty
                         if k in index and k in cache])
            self._dirty.clear()

    async def _reread(self, keys: list[str], audit: int) -> list:
        """Full ABD re-reads of `keys` (`audit` of them are the audit's
        sample, the rest stale) as ONE batched quorum round through one
        coordinator (`AbdClient.fetch_sets_attributed`), in batches of
        `REREAD_BATCH` gathered when there are more, each under a
        coordinator drawn on its own. Under a read lease each key is one
        hop to the holder already, so those stay single reads, gathered.
        Exceptions come back in place."""
        with tracer.span("assembly.reread", stale=len(keys) - audit,
                         audit=audit):
            if self.abd.cfg.lease_enabled:
                return await asyncio.gather(
                    *(self._fetch_tagged(k) for k in keys),
                    return_exceptions=True,
                )
            chunks = [keys[i:i + REREAD_BATCH]
                      for i in range(0, len(keys), REREAD_BATCH)]
            if len(chunks) == 1:
                try:
                    return await self._fetch_batch(chunks[0])
                except Exception as e:  # noqa: BLE001 - the caller raises it
                    return [e] * len(keys)
            parts = await asyncio.gather(
                *(self._fetch_batch(c) for c in chunks),
                return_exceptions=True,
            )
            return [r for chunk, part in zip(chunks, parts)
                    for r in ([part] * len(chunk)
                              if isinstance(part, BaseException) else part)]

    async def _fetch_batch(self, keys: list[str]) -> list:
        dl = self._request_deadline()
        results = await self._retry(
            lambda: self.abd.fetch_sets_attributed(keys, deadline=dl), dl
        )
        for key, (value, tag, _coord) in zip(keys, results):
            self._cache_put(key, tag, value)
        return results

    async def _fetch_tagged(self, key: str, exclude=()):
        dl = self._request_deadline()
        value, tag, coord = await self._retry(
            lambda: self.abd.fetch_set_attributed(key, exclude, deadline=dl), dl
        )
        self._cache_put(key, tag, value)
        return value, tag, coord

    async def _fetch(self, key: str):
        return (await self._fetch_tagged(key))[0]

    async def _write(self, key: str, value):
        dl = self._request_deadline()
        k, tag = await self._retry(
            lambda: self.abd.write_set_tagged(key, value, deadline=dl), dl
        )
        self._cache_put(key, tag, value)
        self._note_resident_write(key, value)
        self._note_search_write(key, tag, value)
        return k

    # --------------------------------------------- Lodestone write ingest

    def _note_resident_write(self, key: str, value) -> None:
        """Queue a committed write's ciphertext columns for resident-pool
        ingest (dds_tpu/resident) — OFF the request's critical path,
        debounced — so a warm fleet's first post-write
        aggregate gathers every row device-side with zero ingest.
        Content addressing keeps this unconditionally safe: the full
        quorum read still decides which ciphertexts fold; the pool only
        pre-pays their limb conversion + transfer."""
        plane = self._resident
        if plane is None or not self._resident_write_ingest or not value:
            return
        ciphers = []
        for col in value:
            if isinstance(col, bool):
                continue
            if isinstance(col, int):
                ciphers.append(col)
            elif isinstance(col, str):
                try:
                    ciphers.append(int(col))
                except ValueError:
                    continue  # non-numeric column: never an aggregate operand
        if not ciphers:
            return
        gid = self.abd.owner(key) if self._shards is not None else ""
        tenant = self._plane_tenant()
        if self._stratum is not None:
            # popularity signal only (pure dict math, loop-safe): the
            # rewrite of a tiered row warms its directory score so the
            # next fold promotes it instead of streaming it, and the
            # key->cipher mapping lets later Spyglass hits do the same
            self._stratum.note_write(gid, ciphers, tenant=tenant, key=key)
        if plane.note_write(gid, ciphers, tenant=tenant):
            self._resident_ingest_soon()

    def _resident_ingest_soon(self) -> None:
        """Debounced drain: coalesce a write burst into few ingest
        dispatches (the _save_keys_soon pattern), each on a worker
        thread so limb conversion never stalls request handling."""
        if self._ingest_task is not None and not self._ingest_task.done():
            return

        async def _drain():
            while self._resident.pending_ingest():
                await asyncio.sleep(self._resident_ingest_window)
                await asyncio.to_thread(self._resident.ingest_pending)

        self._ingest_task = supervised_task(_drain(),
                                            name="proxy.resident_ingest")

    def tier_pressure(self) -> float:
        """Blended hot+warm occupancy in [0, 1] for Helmsman's
        pool-pressure signal: how close the fullest pool is to its
        max_rows, or the warm cache to its byte budget, whichever is
        tighter. 0.0 when Stratum is disabled — the autoscaler then
        steers on burn/queue alone, exactly as before."""
        if self._stratum is None:
            return 0.0
        try:
            return float(self._stratum.pressure())
        except Exception:
            return 0.0

    # ----------------------------------------- Spyglass encrypted search

    def _note_search_write(self, key: str, tag, value) -> None:
        """Queue a committed write's (tag, value) for search-index upsert
        (dds_tpu/search) — OFF the request path, like the resident
        ingest. value None (RemoveSet) becomes a tombstone so the index
        never resurrects a deleted record. A full queue is safe: the key
        just reads stale at the next query and is repaired there."""
        plane = self._search
        if plane is None or not self._search_write_ingest:
            return
        gid = self.abd.owner(key) if self._shards is not None else ""
        if plane.note_write(gid, key, tag, value,
                            tenant=self._plane_tenant()):
            self._search_ingest_soon()

    def _search_ingest_soon(self) -> None:
        """Debounced drain, one task at a time (the _resident_ingest_soon
        pattern): coalesce a write burst into few index-upsert batches on
        a worker thread."""
        if (self._search_ingest_task is not None
                and not self._search_ingest_task.done()):
            return
        # capture the plane: the drain sleeps between batches, and the
        # attribute can be unplugged (shutdown, tests) while it does
        plane = self._search

        async def _drain():
            while plane.pending_ingest():
                await asyncio.sleep(self._search_ingest_window)
                await asyncio.to_thread(plane.ingest_pending)

        self._search_ingest_task = supervised_task(
            _drain(), name="proxy.search_ingest"
        )

    def _search_owner(self, key: str) -> str:
        return self.abd.owner(key) if self._shards is not None else ""

    async def _spy_validate(self) -> list[str]:
        """Freshness for one indexed query: validate every stored key's
        index entry with ONE batched `read_tags` fingerprint round (the
        `_fetch_stored` linearizability argument verbatim — entries come
        from completed quorum ops, and honest replies can never deflate
        the quorum-max tag below a completed write). Only stale or
        missing keys take full ABD reads, re-ingesting as they land.
        Returns the sorted stored keys; afterwards every one has a
        validated index entry, so indexed results are bit-for-bit the
        legacy scan's."""
        plane = self._search
        pt = self._plane_tenant()
        keys = self._tenant_stored_keys()
        if not keys:
            return keys
        cached: list[str] = []
        cached_tags: list = []
        missing: list[str] = []
        for k in keys:
            t = plane.tag(self._search_owner(k), k, tenant=pt)
            if t is None:
                missing.append(k)
            else:
                cached.append(k)
                cached_tags.append(t)
        stale = list(missing)
        if cached:
            try:
                dl = self._request_deadline()
                digest = sigs.key_from_set(cached)
                fp = sigs.tags_fingerprint(cached_tags)
                tags = await self._retry(
                    lambda: self.abd.read_tags(
                        cached, digest=digest, fingerprint=fp,
                        cached_tags=cached_tags, deadline=dl,
                    ),
                    dl,
                )
                if tags is not cached_tags:
                    # identity return = every vote said "unchanged";
                    # otherwise compare per key
                    stale.extend(
                        k for k, t, ct in zip(cached, tags, cached_tags)
                        if t != ct
                    )
            except Exception as e:  # validation trouble => full refetch
                log.debug("search tag validation failed (%s); refetch", e)
                stale = list(keys)
        if stale:
            results = await asyncio.gather(
                *(self._fetch_tagged(k) for k in stale),
                return_exceptions=True,
            )
            for k, r in zip(stale, results):
                if isinstance(r, Exception):
                    raise r
                value, tag, _coord = r
                plane.upsert(self._search_owner(k), k, tag, value, tenant=pt)
        metrics.inc(
            "dds_search_index_total", max(0, len(keys) - len(stale)),
            outcome="hit", help="Spyglass index keys per query by outcome",
        )
        metrics.inc(
            "dds_search_index_total", max(0, len(stale) - len(missing)),
            outcome="stale", help="Spyglass index keys per query by outcome",
        )
        metrics.inc(
            "dds_search_index_total", len(missing), outcome="miss",
            help="Spyglass index keys per query by outcome",
        )
        return keys

    def _spy_partition(self, keys: list[str]) -> dict[str, list[str]]:
        """Stored keys by owning shard group (one anonymous group when
        unsharded) — the scatter side of a query's per-group dispatch."""
        if self._shards is None:
            return {"": keys}
        parts: dict[str, list[str]] = {}
        for k in keys:
            parts.setdefault(self.abd.owner(k), []).append(k)
        return parts

    async def _spy_filter(self, evalfn) -> list[str]:
        """One indexed selection query: validate, dispatch `evalfn` per
        group CONCURRENTLY (each group's predicate kernel runs on a
        worker thread), union the key sets, and return them in
        sorted-key order — exactly the legacy scan's output order."""
        keys = await self._spy_validate()
        if not keys:
            return []
        parts = self._spy_partition(keys)
        pt = self._plane_tenant()
        with tracer.span("proxy.search_eval", k=len(keys),
                         shards=len(parts)):
            sets = await asyncio.gather(
                *(
                    asyncio.to_thread(
                        evalfn, self._search.group(gid, tenant=pt)
                    )
                    for gid in parts
                )
            )
        selected = set().union(*sets)
        hits = [k for k in keys if k in selected]
        self._search.note_selected(hits, pt)
        return hits

    async def _spy_order(self, pos: int, descending: bool) -> list[str]:
        """One indexed order-by query: per-group device-sorted runs
        merged host-side. Run elements are (comparable, key) with the
        comparable negated for descending order, so `heapq.merge`
        reproduces the global stable sort — ties in ascending key order,
        like the legacy stable `sorted` over sorted-key pairs."""
        import heapq

        keys = await self._spy_validate()
        if not keys:
            return []
        parts = self._spy_partition(keys)
        pt = self._plane_tenant()
        with tracer.span("proxy.search_eval", k=len(keys),
                         shards=len(parts)):
            runs = await asyncio.gather(
                *(
                    asyncio.to_thread(
                        self._search.group(gid, tenant=pt).eval_order,
                        pos, descending,
                    )
                    for gid in parts
                )
            )
        stored = set(keys)
        ordered = [k for _, k in heapq.merge(*runs) if k in stored]
        self._search.note_selected(ordered, pt)
        return ordered

    @staticmethod
    def _page_params(req: Request) -> tuple[int, int | None]:
        """`offset`/`limit` pagination params (every search/order route,
        both paths): non-negative ints, ValueError -> 400 via handle()."""
        off = int(req.query.get("offset", 0))
        if off < 0:
            raise ValueError("offset must be >= 0")
        lim = req.query.get("limit")
        lim = int(lim) if lim is not None else None
        if lim is not None and lim < 0:
            raise ValueError("limit must be >= 0")
        return off, lim

    @staticmethod
    def _page_response(keyset: list[str],
                       page: tuple[int, int | None]) -> Response:
        off, lim = page
        end = None if lim is None else off + lim
        return Response.json(J.keys_result(keyset[off:end]))

    @staticmethod
    def _count_search(route: str, path: str) -> None:
        metrics.inc(
            "dds_search_requests_total", route=route, path=path,
            help="search/order/range requests by evaluation path",
        )

    async def _order_route(self, name: str, req: Request) -> Response:
        pos = self._pos(req)
        page = self._page_params(req)
        descending = name == "OrderLS"
        if self._search is not None:
            self._count_search(name, "indexed")
            return self._page_response(
                await self._spy_order(pos, descending), page
            )
        self._count_search(name, "legacy")
        pairs = await self._fetch_visible()
        # records without the column are EXCLUDED (the Search* convention)
        # instead of the old silent float("-inf") coercion; non-integer
        # columns raise -> 400, like every Search* int cast
        rows = [(int(v[pos]), k) for k, v in pairs if pos < len(v)]
        ordered = [
            k for _, k in
            sorted(rows, key=lambda t: t[0], reverse=descending)
        ]
        return self._page_response(ordered, page)

    async def _eq_route(self, name: str, req: Request) -> Response:
        from dds_tpu.models.det import DetKey

        pos = self._pos(req)
        item = str(J.parse_item(req.json()))
        page = self._page_params(req)
        want_eq = name == "SearchEq"
        if self._search is not None:
            self._count_search(name, "indexed")
            keyset = await self._spy_filter(
                lambda idx: idx.eval_eq(pos, item, want_eq)
            )
            return self._page_response(keyset, page)
        self._count_search(name, "legacy")
        pairs = await self._fetch_visible()
        keyset = [
            k for k, v in pairs
            if pos < len(v) and DetKey.compare(str(v[pos]), item) == want_eq
        ]
        return self._page_response(keyset, page)

    _CMP_OPS = {"SearchGt": "gt", "SearchGtEq": "ge",
                "SearchLt": "lt", "SearchLtEq": "le"}

    async def _cmp_route(self, name: str, req: Request) -> Response:
        pos = self._pos(req)
        item = int(J.parse_item(req.json()))
        page = self._page_params(req)
        if self._search is not None:
            self._count_search(name, "indexed")
            keyset = await self._spy_filter(
                lambda idx: idx.eval_compare(pos, self._CMP_OPS[name], item)
            )
            return self._page_response(keyset, page)
        self._count_search(name, "legacy")
        pairs = await self._fetch_visible()
        op = {
            "SearchGt": lambda e: e > item,
            "SearchGtEq": lambda e: e >= item,
            "SearchLt": lambda e: e < item,
            "SearchLtEq": lambda e: e <= item,
        }[name]
        keyset = [k for k, v in pairs if pos < len(v) and op(int(v[pos]))]
        return self._page_response(keyset, page)

    async def _range_route(self, req: Request) -> Response:
        pos = self._pos(req)
        lo_bound, hi_bound = J.parse_range(req.json())
        page = self._page_params(req)
        if self._search is not None:
            self._count_search("Range", "indexed")
            keyset = await self._spy_filter(
                lambda idx: idx.eval_range(pos, lo_bound, hi_bound)
            )
            return self._page_response(keyset, page)
        self._count_search("Range", "legacy")
        pairs = await self._fetch_visible()
        keyset = [
            k for k, v in pairs
            if pos < len(v) and lo_bound <= int(v[pos]) <= hi_bound
        ]
        return self._page_response(keyset, page)

    async def _entry_route(self, name: str, req: Request) -> Response:
        from dds_tpu.models.det import DetKey

        if name == "SearchEntry":
            vals = [str(J.parse_item(req.json()))]
        else:
            vals = [str(x) for x in J.parse_triplet(req.json())]
        mode = "all" if name == "SearchEntryAND" else "any"
        page = self._page_params(req)
        if self._search is not None:
            self._count_search(name, "indexed")
            keyset = await self._spy_filter(
                lambda idx: idx.eval_entry(vals, mode)
            )
            return self._page_response(keyset, page)
        self._count_search(name, "legacy")
        pairs = await self._fetch_visible()
        if mode == "all":
            keyset = [
                k for k, v in pairs
                if all(any(DetKey.compare(str(e), q) for e in v)
                       for q in vals)
            ]
        else:
            keyset = [
                k for k, v in pairs
                if any(DetKey.compare(str(e), q) for q in vals for e in v)
            ]
        return self._page_response(keyset, page)

    async def _fetch_visible(self) -> list[tuple[str, list]]:
        """`_fetch_stored` scoped to the request tenant (Bastion): the
        quorum/tag machinery still validates the FULL stored view (one
        shared round, whoever asks), then the tenant filter projects the
        caller's own records. Tenancy off returns the identical list."""
        return self._tenant_pairs(await self._fetch_stored())

    async def _fetch_stored(self) -> list[tuple[str, list]]:
        """Every stored (key, value), for the search/order/analytics
        routes: `_fetch_table` validates the view, then one O(K) pass over
        references lists it (`OperandTable.pairs`, the same list object
        until an entry moves). The aggregates do not come through here:
        they fold the table's operand column."""
        return (await self._fetch_table()).pairs()

    async def _fetch_table(self) -> OperandTable:
        """The operand table, validated for this request.

        With the aggregate cache on, ONE batched tag-only quorum round
        (`AbdClient.read_tags`) validates all cached entries: a cached value
        is served only when the quorum-max tag EQUALS its cached tag, which
        is linearizable because cached values come from completed ops (fully
        written back at that tag) and any completed later write would show a
        higher tag in every quorum (they intersect in an honest replica) —
        honest replies can therefore never DEFLATE the max below a completed
        write. What a credentialed Byzantine replica CAN do is confirm a
        cache entry that a Byzantine coordinator planted (by reporting the
        planted tag, or by echoing the request fingerprint as `unchanged`);
        that forgery class does not come from the tag round at all — a
        planting coordinator could always confirm its own tag — and is
        bounded by the per-round audit (see aggregate_cache_audit). Keys
        that fail validation (or were never cached) take the full ABD read,
        refilling the cache.

        The stale keys and the audit's sample are re-read together, as one
        batched ABD read (`_reread`). Each audited key is still read
        through a full quorum under a coordinator drawn uniformly from the
        same set as a single read's, so a key's chance of being audited
        by an honest coordinator is what it was; what changed is that one
        aggregate's keys share the draw. The corroborating re-read
        (`_audit_verdict`) stays a single read through ANOTHER coordinator.

        The reference re-reads every set through full quorums per aggregate
        (`DDSRestServer.scala:397-446`); this replaces K 2-round-trip reads
        with 1 light round + reads for just the stale keys.

        What it costs. O(K): a copy of the tag list and a join and hash
        of its kept fields when an entry moved since the last round, and
        the replicas' side of the round. O(rows that moved): everything
        else, the tag comparisons included when the quorum saw a tag move:
        the round's reply names the positions its max moved and the table
        the ones it moved since the round was made, and
        `OperandTable.stale` compares at those. One pass of K comparisons
        is left for a round that cannot say (no fingerprint, a failed
        round, entries without a tag, more than `MOVED_VERSIONS` versions
        of the table since): `assembly.validate_tags` says which in `path`.
        Entries the proxy's own completed operations changed are taken
        from the cache by key (`_sync_table`), stale and audited keys are
        re-read through full quorums, and only those rows are parsed into
        the operand columns and looked up in the pool. O(K) of parsing
        comes back, under `assembly.state` and `assembly.operands`, when
        the table is built anew: a change of the key set (`PutSet` of a new
        key, `RemoveSet`, key sync), a cache flush after a forged audit, a
        first request for a column.
        """
        with tracer.span("proxy.fetch_stored"):
            return await self._fetch_table_traced()

    async def _fetch_table_traced(self) -> OperandTable:
        table = self._sync_table()
        keys = table.keys
        if not keys:
            return table
        reply = None
        sent = table.round_args() if self.cfg.aggregate_cache else None
        if sent is not None:
            _, _, cached, digest, fp, cached_tags = sent
            try:
                dl = self._request_deadline()
                reply = await self._retry(
                    lambda: self.abd.read_tags(
                        cached, digest=digest, fingerprint=fp,
                        cached_tags=cached_tags, deadline=dl,
                    ),
                    dl,
                )
            except Exception as e:  # validation trouble => plain full fetch
                log.debug("tag validation failed (%s); full refetch", e)
                sent = None
        # identity return: every quorum vote said "unchanged". If no entry
        # moved since an aggregate last settled this table either, only the
        # audit remains: the steady-state aggregate does O(1) bookkeeping.
        steady = (sent is not None and reply is sent[5]
                  and not table.uncached
                  and table.version == table.settled and not self._dirty)
        if steady:
            stale: list[int] = []
            audit = _pick_outside(len(keys), (), self.cfg.aggregate_cache_audit)
        else:
            with tracer.span("assembly.validate_tags", k=len(keys)) as vm:
                # a write completed during the round: take it in first, so
                # that its entry is held to the round's tag like any other
                self._take_dirty(table)
                stale, vm["path"] = table.stale(sent, reply)
                vm["stale"] = len(stale)
            with tracer.span("assembly.pick_stale", k=len(keys)) as pm:
                audit = _pick_outside(len(keys), set(stale),
                                      self.cfg.aggregate_cache_audit)
                pm["stale"], pm["audit"] = len(stale), len(audit)

        # audit sample: re-read a few cache-served keys through a full
        # quorum under a (random) coordinator. A value mismatch at the SAME
        # tag means some past coordinator forged a cached value — flush
        # everything. A mismatch at a strictly NEWER tag is usually a benign
        # write that landed between the tag-validation round and the audit
        # re-read — but the newer tag is reported by the audited read
        # itself, so it is corroborated by an independent re-read before
        # being exempted from the flush.
        pre = {keys[i]: table.entries[i] for i in audit}
        reread = [keys[i] for i in stale] + list(pre)
        results = await self._reread(reread, len(pre)) if reread else []
        fetched = {}
        for k, r in zip(reread, results):
            if isinstance(r, Exception):
                raise r
            fetched[k] = r  # (value, tag, coordinator)
        # cache effectiveness: keys served from the tag-validated cache vs
        # re-read through full quorums (audit re-reads count as misses —
        # they cost a full ABD round either way — except in the steady
        # aggregate, which counts every key a hit)
        miss = 0 if steady else len(reread)
        metrics.inc("dds_tag_cache_total", len(keys) - miss,
                    outcome="hit", help="aggregate tag-cache keys by outcome")
        if not steady:
            metrics.inc("dds_tag_cache_total", miss, outcome="miss",
                        help="aggregate tag-cache keys by outcome")
        forged = await self._audit_verdict(list(pre), pre, fetched)
        if forged:
            log.warning("aggregate cache audit mismatch: flushing cache")
            self._flush_cache()
            # serve only quorum-read data this round
            remaining = [k for k in keys if k not in fetched]
            more = await self._reread(remaining, 0)
            for k, r in zip(remaining, more):
                if isinstance(r, Exception):
                    raise r
                fetched[k] = r
        elif steady and table.version == table.settled and not self._dirty:
            return table
        # patch: the rows re-read, as the cache holds them now (the newest
        # completed op on the key: this round's read or a later one), and
        # whatever else completed meanwhile. No await from here to the
        # caller's snapshot of the column.
        with tracer.span("assembly.pairs", k=len(keys)) as am:
            index, cache = table.index, self._cache
            am["patched"] = table.apply([
                # a read the cache does not keep (no tag, cache off) is
                # served this round and is stale for the next
                (index[k], cache.get(k) or (None, r[0]))
                for k, r in fetched.items()
            ])
            self._take_dirty(table)
            table.settled = table.version
        return table

    async def _audit_verdict(
        self, audit: list[str], pre: dict, fetched: dict
    ) -> list[str]:
        """Forged/suspect classification of the audit's sample.

        `pre[k] = (tag, value)` is what the cache served; `fetched[k] =
        (value, tag, coordinator)` is the audit's full quorum re-read. A
        value mismatch at the cached tag (or below) means some past
        coordinator forged a cached value -> forged. A strictly NEWER
        (value, tag) is usually a benign write that landed between the
        tag-validation round and the audit re-read — but the newer tag came
        from the very read being audited, so it is attacker-controllable:
        corroborate each with ONE more full quorum read through a DIFFERENT
        coordinator (the audited read's is excluded). Benign only if that
        independent read reproduces the same (value, tag); a failed
        corroboration degrades to the conservative flush rather than
        failing the aggregate."""
        forged, suspect = [], []
        for k in audit:
            value, tag, _coord = fetched[k]
            pre_tag, pre_value = pre[k]
            if value == pre_value:
                continue
            if tag is None or tag <= pre_tag:
                forged.append(k)
            else:
                suspect.append(k)
        if suspect:
            checks = await asyncio.gather(
                *(
                    self._fetch_tagged(k, exclude=(fetched[k][2],))
                    for k in suspect
                ),
                return_exceptions=True,
            )
            for k, r in zip(suspect, checks):
                if isinstance(r, Exception) or r[:2] != fetched[k][:2]:
                    forged.append(k)
        return forged

    # -------------------------------------------------------------- routing

    def _breaker_census(self) -> tuple[int, list[float]]:
        """(trusted coordinator count, refusing-breaker half-open ETAs)
        from whatever storage client is behind this proxy; a client
        without the surface (test stubs) reads as healthy."""
        census = getattr(self.abd, "breaker_census", None)
        return census() if census is not None else (0, [])

    def _derive_retry_after(self, *candidates: float | None) -> int:
        """Satellite of ISSUE 7: Retry-After derived from actual recovery
        state — the nearest breaker half-open probe plus any
        caller-supplied candidate (token-bucket refill ETA, fast-fail
        ETA) — instead of the static config constant, which only remains
        as the fallback when nothing measurable is pending."""
        vals = [c for c in candidates if c is not None and 0 < c < math.inf]
        _, etas = self._breaker_census()
        vals.extend(e for e in etas if e > 0)
        eta = min(vals) if vals else self.cfg.retry_after_hint
        return max(1, math.ceil(eta))

    def _admission_reject(self, d, route: str, method: str) -> Response:
        """Format one Bulwark rejection: 429 (per-tenant throttle) or 503
        (shed). No Deadline was minted and no storage work ran — the
        request fails in microseconds with an honest Retry-After."""
        if d.status == 429:
            retry_after = max(1, math.ceil(d.retry_after)) \
                if 0 < d.retry_after < math.inf \
                else max(1, math.ceil(self.cfg.retry_after_hint))
        else:
            retry_after = self._derive_retry_after(d.retry_after)
        metrics.inc(
            "dds_http_requests_total", route=route or "root",
            method=method, status=str(d.status),
            help="REST requests by route and status",
        )
        # shed 503s burn the route's SLO budget (they are ours); throttle
        # 429s are the tenant's own rate and do not
        self.slo.observe(route or "root", d.status, 0.0)
        return Response(
            d.status,
            f"admission rejected ({d.reason})".encode(),
            headers={"Retry-After": str(retry_after)},
        )

    async def _admission_loop(self) -> None:
        """Controller heartbeat: decide() ticks the ratchet lazily under
        traffic, but recovery (un-shedding) must also happen when the
        shed class is the ONLY traffic — this timer guarantees
        evaluations keep flowing either way."""
        interval = max(0.05, self.admission.eval_interval)
        while True:
            await asyncio.sleep(interval)
            self.admission.evaluate()

    def _tenant_reject(self, e: TenantError, route: str,
                       method: str) -> Response:
        """Typed 400 for a malformed x-dds-tenant header: charset and
        length are clamped at the edge so wire garbage never becomes a
        metrics label, a pool stripe, or an ownership identity — and a
        garbled id never silently falls back into another keyspace."""
        metrics.inc(
            "dds_http_requests_total", route=route or "root",
            method=method, status="400",
            help="REST requests by route and status",
        )
        metrics.inc(
            "dds_tenant_header_rejects_total", reason=e.reason,
            help="malformed x-dds-tenant headers refused with 400",
        )
        return Response.json(
            {"error": "invalid tenant header", "reason": e.reason},
            status=400,
        )

    async def handle(self, req: Request) -> Response:
        route = req.path.split("/", 2)[1] if "/" in req.path else req.path
        header = self.admission.tenant_header \
            if self.admission is not None else "x-dds-tenant"
        try:
            tenant = validate_tenant(req.headers.get(header))
        except TenantError as e:
            return self._tenant_reject(e, route, req.method)
        adm_ms = None
        decision = None
        if tenant == CANARY_TENANT:
            # Heliograph carve-out: canary probes must get through WHILE
            # the fleet sheds (black-box evidence is worth the most
            # exactly then), so they bypass tenant-fair admission — but
            # through an explicit, rate-bounded gate: the dedicated
            # bucket 429s anything over the configured probe budget, so
            # the prober (or a canary-tenant squatter) can never self-DoS
            # the edge. Rejections are typed and counted.
            if (route not in _ADMISSION_EXEMPT
                    and not self._canary_bucket.try_acquire()):
                metrics.inc(
                    "dds_canary_throttled_total", route=route or "root",
                    help="canary requests refused by the rate-bounded "
                         "admission carve-out",
                )
                eta = self._canary_bucket.refill_eta()
                return Response(
                    429, b"canary rate bound exceeded",
                    headers={"Retry-After": (
                        "60" if not math.isfinite(eta)
                        else str(max(1, math.ceil(eta))))},
                )
        elif self.admission is not None and route not in _ADMISSION_EXEMPT:
            t_adm = time.perf_counter()
            decision = self.admission.decide(route, tenant)
            adm_ms = (time.perf_counter() - t_adm) * 1e3
            if not decision.admitted:
                return self._admission_reject(decision, route, req.method)
        # Trace root minted at the edge (or stitched under an upstream
        # caller's x-dds-trace header): every span recorded below — quorum
        # rounds, replica handlers scheduled over the transport, kernel
        # phases — links into this request's tree via obs.context.
        upstream = obs_context.from_header(req.headers.get("x-dds-trace", ""))
        ctx = obs_context.child(upstream) if upstream else obs_context.root()
        # ONE budget per request: every storage helper below reads it from
        # the context var, so nested retries and per-attempt timeouts all
        # shrink toward the same edge deadline
        token = _REQ_DEADLINE.set(Deadline(self.cfg.request_budget))
        ttoken = _REQ_TENANT.set(tenant)
        t0 = time.perf_counter()
        status = 500
        try:
            with tracer.span(f"http.{req.method}.{route or 'root'}", _ctx=ctx):
                if adm_ms is not None:
                    # decided before the trace root existed — backdate it
                    # into the tree as the admission stage, at its true end
                    tracer.record("proxy.admission", adm_ms,
                                  _ctx=obs_context.child(),
                                  _t_end=t_adm + adm_ms / 1e3)
                resp = await self._route(req)
            status = resp.status
            return resp
        except (ValueError, KeyError, TypeError) as e:
            status = 400
            return Response.text(f"bad request: {e}", 400)
        except (DeadlineExceededError, NoTrustedNodesError,
                AllBreakersOpenError) as e:
            # graceful degradation: the quorum is unreachable within the
            # budget — tell the client WHEN to come back instead of hanging
            # or aborting opaquely. AllBreakersOpenError is the fast-fail
            # variant: it arrives in microseconds with the probe ETA.
            status = 503
            log.warning("degraded %s %s: %s", req.method, req.path, e)
            if isinstance(e, DeadlineExceededError):
                kind = "deadline_exceeded"
            elif isinstance(e, AllBreakersOpenError):
                kind = "all_breakers_open"
            else:
                kind = "no_trusted_nodes"
            metrics.inc(
                "dds_degraded_total", route=route or "root", kind=kind,
                help="requests degraded to 503 (budget exhausted / no quorum)",
            )
            # the faulting request's whole span tree, frozen for post-mortem
            await flight.record_async(
                kind, trace_id=ctx.trace_id, route=route or "root",
                method=req.method, error=str(e),
            )
            return self._unavailable(str(e), getattr(e, "eta", None))
        except Exception:
            log.exception("route failure %s %s", req.method, req.path)
            return Response(500)
        finally:
            _REQ_DEADLINE.reset(token)
            _REQ_TENANT.reset(ttoken)
            dur = time.perf_counter() - t0
            metrics.observe(
                "dds_http_request_seconds", dur,
                route=route or "root", method=req.method,
                help="REST request latency by route",
            )
            metrics.inc(
                "dds_http_requests_total", route=route or "root",
                method=req.method, status=str(status),
                help="REST requests by route and status",
            )
            if status != 304 and tenant != CANARY_TENANT:
                # a 304 is a deliberately-parked gossip long-poll (or a
                # free freshness probe) — its held duration is the design,
                # not latency badness, so it must not burn SLO budget.
                # Canary traffic is excluded wholesale: the prober feeds
                # its own synthetic canary.<kind> streams from VERIFIED
                # outcomes, and synthetic load must never dilute (or
                # burn) user-facing route objectives.
                self.slo.observe(
                    route or "root", status, dur,
                    tenant=(tenant if self._tenancy_enabled else None),
                )
            if self._tenancy_enabled and tenant != CANARY_TENANT:
                # Bastion attribution: the admitted request's outcome
                # feeds the burn-shed window (a flooding tenant's 5xxs
                # accumulate against ITS identity, not the fleet's), and
                # Chronoscope's per-tenant usage ledger
                if decision is not None:
                    self.admission.note_outcome(
                        tenant, decision.klass, status < 500
                    )
                from dds_tpu.obs.chronoscope import chronoscope
                chronoscope.note_usage(tenant, route or "root", dur)

    def _unavailable(self, why: str, eta: float | None = None) -> Response:
        return Response(
            503,
            f"service unavailable: {why}".encode(),
            headers={"Retry-After": str(self._derive_retry_after(eta))},
        )

    async def _route(self, req: Request) -> Response:
        parts = [p for p in req.path.split("/") if p]
        if not parts:
            return Response(404)
        name, arg = parts[0], (parts[1] if len(parts) > 1 else None)
        m = req.method

        match (m, name):
            case ("GET", "GetSet") if arg:
                if (denied := self._tenant_denied(arg)) is not None:
                    return denied
                value = await self._fetch(arg)
                if value is None:
                    return Response(404)
                return Response.json(J.dds_set(value))

            case ("POST", "PutSet"):
                body = req.json()
                if body is None:
                    key, value = sigs.random_key(), None
                else:
                    value = J.parse_set(body)
                    key = sigs.key_from_set(value)
                # content addressing makes cross-tenant PutSet of identical
                # content a key collision — first writer owns, the replay
                # by another tenant is refused like any cross-tenant access
                if (denied := self._tenant_denied(key)) is not None:
                    return denied
                await self._write(key, value)
                self._note_stored(key)
                self._note_owner(key)
                return Response.text(key)

            case ("DELETE", "RemoveSet") if arg:
                if (denied := self._tenant_denied(arg)) is not None:
                    return denied
                await self._write(arg, None)
                if arg in self.stored_keys:
                    self.stored_keys.discard(arg)  # stop aggregating/gossiping
                    self._stored_version += 1
                    self._save_keys_soon()
                if self._tenant_owner.pop(arg, None) is not None:
                    self._tenant_pairs_memo.clear()
                if arg in self._canary_keys:
                    self._canary_keys.discard(arg)
                    self._tenant_pairs_memo.clear()
                return Response(200)

            case ("PUT", "AddElement") if arg:
                if (denied := self._tenant_denied(arg)) is not None:
                    return denied
                item = J.parse_item(req.json())
                value = await self._fetch(arg)
                if value is None:
                    return Response(404)
                await self._write(arg, value + [item])
                return Response(200)

            case ("GET", "ReadElement") if arg:
                if (denied := self._tenant_denied(arg)) is not None:
                    return denied
                pos = self._pos(req)
                value = await self._fetch(arg)
                if value is None or pos > len(value) - 1:
                    return Response(404)
                return Response.json({"value": value[pos]})

            case ("PUT", "WriteElement") if arg:
                if (denied := self._tenant_denied(arg)) is not None:
                    return denied
                pos = self._pos(req)
                item = J.parse_item(req.json())
                value = await self._fetch(arg)
                if value is None:
                    return Response(404)
                new = list(value)
                if pos > len(new) - 1:
                    new.append(item)
                else:
                    new[pos] = item
                await self._write(arg, new)
                return Response(200)

            case ("POST", "IsElement") if arg:
                if (denied := self._tenant_denied(arg)) is not None:
                    return denied
                item = J.parse_item(req.json())
                value = await self._fetch(arg)
                if value is None:
                    return Response(404)
                # deterministic-HE compare degenerates to ciphertext equality
                found = any(str(elem) == str(item) for elem in value)
                return Response.json(J.value_result(found))

            # ---------------- ciphertext-compute aggregates ----------------

            case ("GET", "Sum"):
                return await self._pair_aggregate(req, "nsqr")

            case ("GET", "SumAll"):
                return await self._fold_aggregate(req, "nsqr")

            case ("GET", "Mult"):
                return await self._pair_aggregate(req, "pubkey")

            case ("GET", "MultAll"):
                return await self._fold_aggregate(req, "pubkey")

            # ------------- encrypted search (Spyglass indexed or legacy scan)

            case ("GET", "OrderLS") | ("GET", "OrderSL"):
                return await self._order_route(name, req)

            case ("POST", "SearchEq") | ("POST", "SearchNEq"):
                return await self._eq_route(name, req)

            case ("POST", "SearchGt") | ("POST", "SearchGtEq") | (
                "POST",
                "SearchLt",
            ) | ("POST", "SearchLtEq"):
                return await self._cmp_route(name, req)

            case ("POST", "Range"):
                return await self._range_route(req)

            case ("POST", "SearchEntry") | ("POST", "SearchEntryOR") | (
                "POST",
                "SearchEntryAND",
            ):
                return await self._entry_route(name, req)

            # ---------------- Prism encrypted analytics (PC-MM) ----------------

            case ("POST", "MatVec") | ("POST", "WeightedSum") | (
                "POST",
                "GroupBySum",
            ) if self.prism is not None:
                return await self._analytics(name, req)

            case ("POST", "_sync"):
                for k in J.parse_keys(req.json()):
                    self._note_stored(k)
                return Response(204)

            case ("GET", "_sync") if self.cfg.key_sync_enabled:
                # bootstrap pull: a (re)starting peer fetches the aggregate
                # key set instead of waiting for the next gossip push.
                # Gated like the push side: with gossip off this would hand
                # any client the full record-key set (workload shape) — the
                # same rationale that keeps /_trace off by default.
                return Response.json(J.keys_result(sorted(self.stored_keys)))

            case ("GET", "health"):
                # liveness/degradation probe: active-replica view, quorum
                # requirement, and per-coordinator breaker states. Always
                # on — it reveals cluster health, not workload shape (the
                # /_trace gating rationale does not apply).
                trusted = self.abd.replicas.get_trusted()
                breakers = self.abd.breaker_states()
                # reachable = trusted minus nodes whose breaker refuses
                # traffic right now (open, pre-half-open)
                reachable = [
                    n for n in trusted
                    if n not in self.abd.breakers or self.abd.breakers[n].allow()
                ]
                shards = None
                if self._shards is not None:
                    # sharded: the merged replica pool says nothing about
                    # quorum health — each GROUP must hold its own quorum
                    shards = self.abd.shards_health()
                    degraded = any(s["degraded"] for s in shards.values())
                else:
                    degraded = len(reachable) < self.abd.cfg.quorum_size
                health = {
                    "status": "degraded" if degraded else "ok",
                    "active_replicas": len(trusted),
                    "reachable_replicas": len(reachable),
                    "quorum_size": self.abd.cfg.quorum_size,
                    "breakers": breakers,
                    "stored_keys": len(self.stored_keys),
                    "request_budget": self.cfg.request_budget,
                }
                if self.cfg.region:
                    health["region"] = self.cfg.region
                if self._tenancy_enabled:
                    # Bastion surface: ownership footprint + who is
                    # currently shedding themselves (never the fleet)
                    health["tenants"] = {
                        "owned_keys": len(self._tenant_owner),
                        "shed": (self.admission.shed_tenants()
                                 if self.admission is not None else []),
                    }
                if shards is not None:
                    health["shards"] = shards
                    health["shard_epoch"] = self._shards.epoch
                    health["reshard_state"] = self._shards.state
                if self._resident is not None:
                    # Lodestone surface: per-pool residency, HBM bytes,
                    # reset churn, and the pending write-ingest queue
                    health["resident"] = self._resident.stats()
                if self._stratum is not None:
                    # Stratum surface: per-tier rows/bytes, directory
                    # residency counts, hit/eviction/cold-read tallies,
                    # and the blended occupancy pressure
                    health["storage"] = self._stratum.stats()
                if self._search is not None:
                    # Spyglass surface: per-group indexed keys/packs and
                    # the pending ingest queue
                    health["search"] = self._search.stats()
                if self.helmsman is not None:
                    # Helmsman surface: pin state, budget, streaks, and
                    # the recent decision history
                    health["helmsman"] = self.helmsman.report()
                recovery = self._recovery_status()
                if recovery is not None:
                    health["recovery"] = recovery
                # Heliograph surface: last probe age + per-kind verdicts,
                # read from in-memory ledger state only. A disabled or
                # wedged prober degrades this section to "disabled" /
                # "stale" — it can never block or slow the health probe.
                health["canary"] = (
                    self.heliograph.health_section()
                    if self.heliograph is not None else {"status": "disabled"}
                )
                resp = Response.json(health, status=503 if degraded else 200)
                if degraded:
                    resp.headers["Retry-After"] = str(self._derive_retry_after())
                return resp

            case ("GET", "metrics") if self.cfg.metrics_route_enabled:
                # Prometheus text exposition (obs/metrics). State gauges
                # (breakers, suspicion, membership) are sampled at scrape
                # time — cheaper than updating them on every transition,
                # and scrape-time freshness is all a gauge promises.
                self._sample_state_gauges()
                return Response(
                    200,
                    metrics.render().encode(),
                    content_type="text/plain; version=0.0.4; charset=utf-8",
                )

            case ("GET", "shards") if self._shards is not None:
                # operator inspection + Meridian gossip: the ACTIVE signed
                # map (epoch + HMAC, verifiable against the intranet
                # secret), reshard state, and per-group membership. Always
                # on when sharded — like /health it reveals topology, not
                # workload shape. Conditional freshness: `If-None-Match:
                # "<epoch>"` answers a near-free 304 when the epoch is
                # unchanged, and `?wait=N` parks the request on the gossip
                # hub so remote routers get the next epoch bump as a push
                # instead of hot-polling (see dds_tpu/fabric/gossip).
                return await self._shards_route(req)

            case ("POST", "_reshard") if (
                self.cfg.reshard_route_enabled and self._reshard is not None
            ):
                # operator control: drive a live split or merge through
                # the reshard controller. Body {"source": gid[, "target":
                # gid][, "action": "split"|"merge"]}; answers the
                # activated epoch, 409 {"aborted"} when the plan aborted
                # safely (old map back in force), or 409 {"busy"} + a
                # phase-derived Retry-After while a DIFFERENT plan holds
                # the controller. Repeating an identical request is
                # idempotent: in flight it attaches to the running plan;
                # completed it answers the current map.
                return await self._reshard_route(req)

            case ("POST", "_helmsman") if (
                self.cfg.reshard_route_enabled and self.helmsman is not None
            ):
                # manual override: {"pin": true} freezes the fleet shape
                # (autoscaling halts, dead-group promotion keeps running),
                # {"pin": false} resumes. Answers the controller report.
                body = req.json() or {}
                pin = body.get("pin")
                if not isinstance(pin, bool):
                    return Response.text("body must set pin: true|false",
                                         400)
                (self.helmsman.pin if pin else self.helmsman.unpin)()
                return Response.json(self.helmsman.report())

            case ("GET", "canary"):
                # Heliograph report: per-kind last verdicts/latencies,
                # typed-outcome counts, failure exemplars (trace ids
                # resolve via /_trace and /fleet/incidents), region
                # unreachable streaks. Admission-exempt like /health —
                # the canary view must answer while the canary is the
                # only thing still seeing the problem.
                if self.heliograph is None:
                    return Response.json({"enabled": False})
                return Response.json(self.heliograph.report())

            case ("GET", "slo") if self.cfg.slo_route_enabled:
                # per-route objective/burn state (obs/slo) plus the
                # Watchtower audit summary — the automated-verdict
                # surface: what is burning budget, what invariants broke,
                # and (when Bulwark is armed) what admission is doing
                # about it
                body = {"slo": self.slo.report(), "audit": watchtower.stats()}
                if self.admission is not None:
                    body["admission"] = self.admission.report()
                return Response.json(body)

            case ("GET", "fleet") if self._fleet is not None and arg:
                # Panopticon federation (obs/panopticon): every fleet
                # process's telemetry, served from the proxy's collector.
                # Admission-exempt like /metrics — the fleet views must
                # answer WHILE the fleet sheds.
                if arg == "metrics":
                    # relabeled merge of every source's exposition, each
                    # sample tagged host/role/shard, staleness-marked per
                    # source (dds_fleet_source_age_seconds/_stale)
                    self._sample_state_gauges()
                    self._fleet.sample_gauges()
                    return Response(
                        200,
                        self._fleet.fleet_metrics().encode(),
                        content_type=(
                            "text/plain; version=0.0.4; charset=utf-8"
                        ),
                    )
                if arg == "slo":
                    # per-host reports + fleet rollup: worst-of and
                    # sum-of burn per route/window, resident-pool
                    # pressure per group, shed level per host
                    return Response.json(self._fleet.fleet_slo())
                if arg == "incidents":
                    # fleet-wide flight incidents correlated by trace id,
                    # plus the collector-fed Watchtower's verdicts
                    tid = req.query.get("trace_id") or None
                    return Response.json(self._fleet.fleet_incidents(tid))
                if arg == "profile":
                    # Chronoscope rollup: every host's dds_pipe_* gauges
                    # (carried by the shipped metrics_text) merged into
                    # the fleet-wide bottleneck-stage verdict
                    self._sample_state_gauges()
                    return Response.json(self._fleet.fleet_profile())
                if arg == "canary":
                    # Heliograph rollup: every host's dds_canary_* gauges
                    # (carried by the shipped metrics_text) merged into
                    # per-host verdicts + the fleet-wide worst-of view,
                    # with failure exemplar trace ids resolvable against
                    # GET /fleet/incidents?trace_id=...
                    self._sample_state_gauges()
                    return Response.json(self._fleet.fleet_canary())
                return Response(404)

            case ("GET", "profile") if self.cfg.profile_route_enabled:
                # Chronoscope (obs/chronoscope): the per-route/per-stage
                # critical-path profile + slow-trace exemplars. ?fmt=folded
                # serves flamegraph folded text instead of the JSON
                # waterfall. Admission-exempt like /slo: the profile must
                # answer while the pipe is the problem.
                from dds_tpu.obs.chronoscope import chronoscope

                if req.query.get("fmt") == "folded":
                    return Response(
                        200, chronoscope.folded().encode(),
                        content_type="text/plain; charset=utf-8",
                    )
                return Response.json(chronoscope.profile())

            case ("GET", "_trace") if self.cfg.trace_route_enabled:
                # live observability (SURVEY §5.5): per-span timing summary
                # (count/total/mean/p50/p95 ms) from utils/trace; occurrence
                # counts are obs.metrics' (GET /metrics).
                # Config-gated (reveals workload shape); no ciphertexts or
                # keys leave — span metadata is aggregate timing only.
                return Response.json(
                    {
                        "spans": tracer.summary(),
                        "stored_keys": len(self.stored_keys),
                    }
                )

        return Response(404)

    async def _reshard_route(self, req: Request) -> Response:
        import asyncio as _aio

        from dds_tpu.shard.rebalance import ReshardAborted
        from dds_tpu.utils.tasks import supervised_task

        body = req.json() or {}
        action = body.get("action", "split")
        if action not in ("split", "merge"):
            return Response.text("action must be split or merge", 400)
        source = body.get("source")
        if not isinstance(source, str) or not source:
            return Response.text("missing source group", 400)
        target = body.get("target")
        ctl = self._reshard
        split_fn = getattr(ctl, "split", ctl)
        merge_fn = getattr(ctl, "merge", None)
        if action == "merge" and merge_fn is None:
            return Response.text("merge is not supported by this "
                                 "controller", 400)

        smap = self._shards.current()
        # COMPLETED idempotency: the shape this request asks for already
        # holds, so answer the current map instead of failing the replay
        done = (
            (action == "split" and isinstance(target, str)
             and target in smap.groups and source in smap.groups)
            or (action == "merge" and source not in smap.groups)
        )
        if done and self._reshard_inflight is None:
            return Response.json({"epoch": smap.epoch,
                                  "groups": list(smap.groups),
                                  "idempotent": True})

        key = (action, source, target)
        inflight = self._reshard_inflight
        if inflight is not None and inflight["key"] != key:
            # a DIFFERENT plan holds the controller: refuse honestly,
            # with a Retry-After derived from its phase
            ra = getattr(ctl, "retry_after", None)
            retry = float(ra()) if callable(ra) else 5.0
            resp = Response.json(
                {"busy": {"action": inflight["key"][0],
                          "source": inflight["key"][1],
                          "target": inflight["key"][2]},
                 "phase": getattr(ctl, "phase", None)}, status=409,
            )
            resp.headers["Retry-After"] = str(max(1, int(retry + 0.5)))
            return resp
        if inflight is not None:
            task = inflight["task"]  # identical repeat: attach, no new plan
        else:
            async def run():
                # exceptions become results so an attached repeat sees
                # the same outcome instead of racing exception retrieval
                try:
                    if action == "merge":
                        return "ok", await merge_fn(source)
                    return "ok", await split_fn(source, target)
                except ReshardAborted as e:
                    return "aborted", str(e)
                except ValueError as e:
                    # operator error (unknown group, taken target): the
                    # request is wrong, not the fleet
                    return "invalid", str(e)

            task = supervised_task(run(), name=f"reshard-{action}-{source}")
            rec = {"key": key, "task": task}
            self._reshard_inflight = rec
            task.add_done_callback(
                lambda _t, rec=rec: (
                    setattr(self, "_reshard_inflight", None)
                    if self._reshard_inflight is rec else None
                )
            )
        # shield: an impatient client disconnecting must not cancel a
        # half-streamed migration
        status, result = await _aio.shield(task)
        if status == "invalid":
            return Response.text(result, 400)
        if status == "aborted":
            return Response.json(
                {"aborted": result, "epoch": self._shards.epoch}, status=409,
            )
        new_map = result if hasattr(result, "epoch") else self._shards.current()
        return Response.json(
            {"epoch": new_map.epoch, "groups": list(new_map.groups)}
        )

    async def _shards_route(self, req: Request) -> Response:
        """GET /shards with conditional-get + long-poll gossip semantics."""
        etag = req.headers.get("if-none-match", "").strip().strip('"')
        fresh = etag and etag == str(self._shards.epoch)
        if fresh:
            try:
                wait = float(req.query.get("wait", 0) or 0)
            except ValueError:
                wait = 0.0
            if wait > 0 and self._gossip is not None:
                await self._gossip.wait_change(
                    min(wait, self.cfg.shards_wait_cap)
                )
            if etag == str(self._shards.epoch):
                return Response(
                    304, headers={"ETag": f'"{self._shards.epoch}"'}
                )
        resp = Response.json(self.abd.status())
        resp.headers["ETag"] = f'"{self._shards.epoch}"'
        return resp

    _BREAKER_STATE_CODE = {"closed": 0, "half_open": 1, "open": 2}

    def _sample_state_gauges(self) -> None:
        """Refresh scrape-time gauges: breaker + suspicion state per
        coordinator, membership counts, store size."""
        for node, state in self.abd.breaker_states().items():
            metrics.set(
                "dds_breaker_state", self._BREAKER_STATE_CODE.get(state, -1),
                node=node.rsplit("/", 1)[-1],
                help="per-coordinator breaker: 0=closed 1=half_open 2=open",
            )
        for node, strikes in self.abd.replicas.suspicions().items():
            metrics.set(
                "dds_replica_suspicion", strikes, node=node.rsplit("/", 1)[-1],
                help="permanent protocol-violation strikes per replica",
            )
        metrics.set(
            "dds_trusted_replicas", len(self.abd.replicas.get_trusted()),
            help="replicas under the 3-strike suspicion limit",
        )
        metrics.set("dds_stored_keys", len(self.stored_keys),
                    help="aggregate key-set size")
        if self._tenancy_enabled and self._tenant_owner:
            counts_t: dict[str, int] = {}
            for k in self.stored_keys:
                t = self._key_tenant(k)
                if t == CANARY_TENANT:
                    continue  # synthetic keyspace, not a tenant footprint
                counts_t[t] = counts_t.get(t, 0) + 1
            for t, n in counts_t.items():
                metrics.set(
                    "dds_tenant_stored_keys", n, tenant=t,
                    help="stored aggregate keys per tenant (proxy view)",
                )
        if self._shards is not None:
            smap = self._shards.current()
            metrics.set("dds_shard_epoch", smap.epoch,
                        help="active shard-map epoch")
            metrics.set(
                "dds_shard_reshard_state",
                1 if self._shards.state == "resharding" else 0,
                help="0=stable 1=resharding",
            )
            metrics.set("dds_shard_groups", len(smap.groups),
                        help="quorum groups in the active shard map")
            counts = {g: 0 for g in smap.groups}
            for k in self.stored_keys:  # the proxy's aggregate-key view
                counts[smap.owner(k)] = counts.get(smap.owner(k), 0) + 1
            for gid, n in counts.items():
                metrics.set(
                    "dds_shard_keys", n, shard=gid,
                    help="stored aggregate keys per shard (proxy view)",
                )
        # Bulwark admission surface: shed level is set at transition time
        # too, but a scrape between transitions still deserves the truth
        if self.admission is not None:
            metrics.set(
                "dds_admission_shed_level", self.admission.shed_level,
                help="Bulwark shed level (0=none; higher sheds lower "
                     "priority classes first)",
            )
        if self._resident is not None:
            # Lodestone gauges: dds_resident_{rows,bytes,hit_ratio,
            # resets}{shard=...}, aggregated per group at scrape time
            self._resident.export_gauges(metrics)
        if self._stratum is not None:
            # Stratum gauges: dds_tier_{rows,bytes}{tier,shard} — tier
            # occupancy per shard group at scrape time
            self._stratum.export_gauges(metrics)
        if self._search is not None:
            # Spyglass gauges: dds_search_{index_keys,index_packs,
            # pending_ingest,...}, per group at scrape time
            self._search.export_gauges(metrics)
        # Chronoscope pipe profile (dds_pipe_*): per-route/per-stage
        # critical-path self-times
        from dds_tpu.obs.chronoscope import chronoscope
        chronoscope.export_gauges(metrics)
        # registry self-observation: label sets folded into `overflow`
        # across all families — attribution decays silently once this
        # moves, so dashboards must be able to alarm on it directly
        metrics.set(
            "dds_metrics_dropped_series", metrics.overflow_total(),
            help="total label sets dropped into overflow series by the "
                 "per-family cardinality cap",
        )
        # Heliograph canary gauges: last verdict / last-ok age per probe
        # kind, rotating failure exemplars, region unreachable streaks
        if self.heliograph is not None:
            self.heliograph.export_gauges(metrics)
        # SLO burn/budget gauges + audit backlog (scrape-time freshness is
        # all a gauge promises; the violation COUNTER increments at
        # detection time in the auditor itself)
        self.slo.export_gauges(metrics)
        wt = watchtower.stats()
        metrics.set("dds_audit_traces_audited", wt["traces_audited"],
                    help="traces audited by the Watchtower since start")
        metrics.set("dds_audit_pending_traces", wt["pending_traces"],
                    help="in-flight traces buffered awaiting audit")
        # Aegis recovery surface (local replicas only): anti-entropy
        # divergence + sync age, snapshot generation + age
        for node in (self.local_replicas or {}).values():
            stats = node.antientropy.stats()
            metrics.set(
                "dds_antientropy_divergent_buckets",
                stats["divergent_buckets"], replica=node.name,
                help="divergent Merkle buckets seen in the last sync round",
            )
            if stats["last_sync_age"] is not None:
                metrics.set(
                    "dds_antientropy_last_sync_age_seconds",
                    stats["last_sync_age"], replica=node.name,
                    help="seconds since the last completed anti-entropy round",
                )
            sm = node.snapshot_meta
            if sm.get("generation") is not None:
                metrics.set(
                    "dds_snapshot_generation", sm["generation"],
                    replica=node.name,
                    help="latest snapshot generation written or loaded",
                )
            if sm.get("saved_at"):
                metrics.set(
                    "dds_snapshot_age_seconds",
                    max(0.0, time.time() - sm["saved_at"]), replica=node.name,
                    help="seconds since this replica's snapshot was written",
                )

    def _recovery_status(self) -> dict | None:
        """Per-local-replica Aegis view for /health: anti-entropy sync
        state and snapshot durability state."""
        if not self.local_replicas:
            return None
        out = {}
        for node in self.local_replicas.values():
            stats = node.antientropy.stats()
            sm = node.snapshot_meta
            out[node.name] = {
                "merkle_root": node.merkle.root()[:16],
                "tracked_keys": len(node.merkle),
                "anti_entropy": {
                    "rounds": stats["rounds"],
                    "repaired_keys": stats["repaired_keys"],
                    "divergent_buckets": stats["divergent_buckets"],
                    "last_sync_age": stats["last_sync_age"],
                    "running": stats["running"],
                },
                "snapshot": {
                    "generation": sm.get("generation"),
                    "age": (
                        max(0.0, round(time.time() - sm["saved_at"], 3))
                        if sm.get("saved_at") else None
                    ),
                    "verify_failures": metrics.value(
                        "dds_snapshot_verify_failures_total",
                        replica=node.name,
                    ) or 0,
                },
            }
        return out

    # ----------------------------------------------------- aggregate helpers

    async def _pair_aggregate(self, req: Request, modparam: str) -> Response:
        """`Sum` / `Mult`: combine one position of two records."""
        key1, key2 = req.query["key1"], req.query["key2"]
        if (denied := self._tenant_denied(key1, key2)) is not None:
            return denied
        pos = self._pos(req)
        mod = req.query.get(modparam)
        set1, set2 = await asyncio.gather(self._fetch(key1), self._fetch(key2))
        if set1 is None or set2 is None:
            return Response(404)
        if len(set1) - 1 < pos or len(set2) - 1 < pos:
            return Response(404)
        c1, c2 = int(set1[pos]), int(set2[pos])
        if mod:
            result = self.backend.modmul(c1, c2, self._parse_modulus(mod, modparam))
        else:
            result = c1 + c2 if modparam == "nsqr" else c1 * c2
        return Response.json(J.value_result(str(result)))

    async def _fold_aggregate(self, req: Request, modparam: str) -> Response:
        """`SumAll` / `MultAll`: fold one position across ALL stored records.

        This is the north-star workload (SURVEY.md §3.4): on the tpu
        backend the fold is one batched Montgomery tree-reduction.
        """
        pos = self._pos(req)
        mod = req.query.get(modparam)
        table = await self._fetch_table()
        with tracer.span("assembly.operands") as om:
            if self._full_view():
                # the table's column: the list the last aggregate folded,
                # or a copy with the rows that moved since parsed in
                operands, outcome = table.column(pos)
            else:
                # a tenant's view: parsed per request (the filtered pairs
                # list itself is memoized per tenant)
                pairs = self._tenant_pairs(table.pairs())
                operands = [int(v[pos]) for _, v in pairs if pos < len(v)]
                outcome = "rebuilt"
            metrics.inc(
                "dds_operand_table_total", outcome=outcome,
                help="aggregates by what their operand column cost: reused "
                     "as it was, patched by the rows that moved, grown by "
                     "the rows of new keys, or parsed whole (rebuilt)",
            )
            om["k"], om["memo"] = len(operands), outcome == "reused"
        if not operands:
            return Response(404)
        metrics.observe(
            "dds_fold_batch_size", len(operands), buckets=SIZE_BUCKETS,
            help="aggregate fold width (operand count)",
        )
        if mod:
            modulus = self._parse_modulus(mod, modparam)
            result = None
            # the per-owner partitions still read whole rows: listed when a
            # plane or a shard map asks, before anything is awaited
            pairs = (
                self._tenant_pairs(table.pairs())
                if self._resident is not None or self._shards is not None
                else None
            )
            if (
                self._resident is not None
                and len(operands) >= self._resident_min_fold
            ):
                # Lodestone: route per-owner operand sets to their group
                # pools and run ONE fused gather+fold dispatch (per-group
                # local tree + the combine_partials tail tree, on-device)
                # instead of S separate marshaling folds. Falls through
                # (None) only when an operand set is wider than its pool
                # even after a reset.
                parts = self._owner_operands(pairs, pos)
                # Stratum routes the same call through the tier planner:
                # resident leg fused as before, warm/cold legs streamed
                # and merged exactly. Without it, plane folds directly.
                folder = (
                    self._stratum.fold_groups
                    if self._stratum is not None
                    else self._resident.fold_groups
                )
                with tracer.span("proxy.resident_fold", k=len(operands),
                                 shards=len(parts),
                                 backend=self.backend.name):
                    result = await asyncio.to_thread(
                        folder, parts, modulus, self._plane_tenant(),
                    )
            if result is not None:
                return Response.json(J.value_result(str(result)))
            shard_ops = (
                [g for _, g in self._owner_operands(pairs, pos)]
                if self._shards is not None else None
            )
            if shard_ops is not None and len(shard_ops) > 1:
                # Constellation scatter-gather: one fold per shard, run
                # CONCURRENTLY on worker threads, then the partials merge
                # with the mesh plane's modular-product tail combine — all
                # shards share one Paillier modulus, so the result is
                # bit-identical to the unsharded fold.
                from dds_tpu.parallel.mesh import combine_partials

                with tracer.span("proxy.scatter_fold", k=len(operands),
                                 shards=len(shard_ops),
                                 backend=self.backend.name):
                    partials = await asyncio.gather(
                        *(self._fold(g, modulus) for g in shard_ops)
                    )
                    result = combine_partials(
                        [int(p) for p in partials], modulus
                    )
            else:
                # device-resident path when the backend has a cipher store:
                # quorum/tag validation above is still authoritative; the
                # store only memoizes limb conversion + transfer
                # (resident/pool.py). The fold runs in a worker thread so
                # concurrent aggregate requests overlap their device
                # dispatches (and the event loop keeps serving) instead of
                # serializing on a blocking fetch.
                with tracer.span("proxy.fold", k=len(operands),
                                 backend=self.backend.name):
                    result = await self._fold(operands, modulus)
        elif modparam == "nsqr":
            result = sum(operands)
        else:
            result = 1
            for o in operands:
                result *= o
        return Response.json(J.value_result(str(result)))

    # ------------------------------------------------- Prism analytics routes

    def _columns(self, pairs, pos: int) -> tuple[list[str], list[int]]:
        """(keys, ciphertexts) of every stored record holding position
        `pos`, in sorted-key order — the operand column order the analytics
        routes expose (and echo back as `keys` so clients can line their
        weight matrices up). Memoized per pairs-identity; the ciphertexts
        are an `Operands` list, which keeps its pool rows with it."""
        memo = self._column_memo
        if memo is not None and memo[0] is pairs and memo[1] == pos:
            return memo[2], memo[3]
        keys = [k for k, v in pairs if pos < len(v)]
        ciphers = Operands(int(v[pos]) for _, v in pairs if pos < len(v))
        self._column_memo = (pairs, pos, keys, ciphers)
        return keys, ciphers

    async def _analytics(self, name: str, req: Request) -> Response:
        """`MatVec` / `WeightedSum` / `GroupBySum`: server-side
        Enc(W @ x) over the stored records' position-`pos` ciphertexts
        (analytics/prism.py). Validation failures raise ValueError ->
        400 via handle(); the body-size cap answers 413 before JSON
        parsing so an oversized weight blob never costs a parse."""
        cap = self.cfg.analytics_max_request_bytes
        if cap > 0 and len(req.body) > cap:
            return Response(
                413,
                f"analytics request body exceeds {cap} bytes".encode(),
            )
        pos = self._pos(req)
        n, n2 = self.prism.parse_nsqr(req.query["nsqr"])
        pairs = await self._fetch_visible()
        keys, ciphers = self._columns(pairs, pos)
        if not ciphers:
            return Response(404)
        body = req.json()
        labels = None
        if name == "MatVec":
            rows = J.parse_weight_matrix(body)
        elif name == "WeightedSum":
            rows = [J.parse_weight_row(body)]
        else:  # GroupBySum: 0/1 selector rollups over record keys
            labels, rows = self.prism.selector_rows(J.parse_groups(body), keys)
        encoded = self.prism.encode_weights(rows, n, cols=len(ciphers))
        out = await self.prism.evaluate(
            name, keys, ciphers, encoded, n2, tenant=self._plane_tenant()
        )
        if name == "WeightedSum":
            return Response.json({"result": str(out[0]), "keys": keys})
        if labels is not None:
            return Response.json(
                {"result": {lb: str(c) for lb, c in zip(labels, out)}}
            )
        return Response.json(
            {"result": [str(c) for c in out], "keys": keys}
        )

    def _owner_operands(self, pairs, pos: int) -> list[tuple[str, list[int]]]:
        """Aggregate operands partitioned by owning shard group, with the
        group id attached (the Lodestone pool key). Unsharded proxies get
        one anonymous group. Memoized per pairs-identity: between writes
        the partition is state-identical, and each group's `Operands` list
        carries the rows its pool resolved it to, so a warm aggregate
        looks nothing up."""
        memo = self._owner_memo
        if memo is not None and memo[0] is pairs and memo[1] == pos:
            return memo[2]
        groups: dict[str, list[int]] = {}
        for k, v in pairs:
            if pos < len(v):
                gid = self.abd.owner(k) if self._shards is not None else ""
                groups.setdefault(gid, []).append(int(v[pos]))
        out = [(gid, Operands(g)) for gid, g in groups.items() if g]
        self._owner_memo = (pairs, pos, out)
        return out

    async def _fold(self, operands: list[int], modulus: int):
        """One aggregate's fold on a worker thread, by the backend's fold
        (the device-store-aware variant where the backend has one), with
        the two waits of the hop as spans: for a free thread
        (`dispatch.thread_wait`, recorded by the worker at its first line;
        `to_thread` copies the context, so it lands in the request's tree)
        and for the event loop to take the coroutine up again once the
        worker returned (`dispatch.resume_wait`)."""
        fold = getattr(
            self.backend, "modmul_fold_resident", self.backend.modmul_fold
        )
        t_call = time.perf_counter()
        t_done = [t_call]
        try:
            return await asyncio.to_thread(
                _fold_after_wait, fold, operands, modulus, t_call, t_done)
        finally:
            t_back = time.perf_counter()
            tracer.record("dispatch.resume_wait", (t_back - t_done[0]) * 1e3,
                          _ctx=obs_context.child(), _t_end=t_back)

    @staticmethod
    def _pos(req: Request) -> int:
        """Parse the `position` query param; negative values are rejected
        (python negative indexing must not leak ciphertext columns)."""
        pos = int(req.query["position"])
        if pos < 0:
            raise ValueError("position must be >= 0")
        return pos

    @staticmethod
    def _parse_modulus(mod: str, modparam: str) -> int:
        """`nsqr` arrives as decimal n^2; `pubkey` as decimal RSA modulus n.

        (The reference ships an X509-encoded RSA key blob for `pubkey`
        (`DDSRestServer.scala:474-477`); our wire format is the bare modulus
        — same information, no Java key serialization.)
        """
        return int(mod)
