"""System bootstrap: the `Main.scala` equivalent.

Builds the full deployment from one typed config — transport, supervisor,
replicas (putting sentinels to sleep), REST proxy, N workload clients, and
the Trudy attack trigger — mirroring the boot call stack in SURVEY.md §3.1.

Run a self-contained node + workload:

    python -m dds_tpu.run --ops 100 --backend tpu
    python -m dds_tpu.run --config configs/default.toml

`--backend tpu` needs a TPU (or `JAX_PLATFORMS=cpu`, asking for the CPU on
purpose), and folds reach the device only from 1,024 stored rows up, so
this 100-op workload folds on the host: `chip_smoke.py` at the repo root
is the run that serves from the chip.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import random
import threading
from dataclasses import dataclass, field

from dds_tpu.clt.client import ClientConfig, DDSHttpClient
from dds_tpu.clt.generator import generate
from dds_tpu.clt.instructions import Digest
from dds_tpu.core import messages as M
from dds_tpu.utils.sigs import generate_nonce as sigs_generate_nonce
from dds_tpu.utils.tasks import supervised_task
from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
from dds_tpu.core.supervisor import BFTSupervisor, SupervisorConfig
from dds_tpu.core.transport import InMemoryNet, TcpNet
from dds_tpu.hosts import NODEHOST, ReplicaProcesses
from dds_tpu.http.server import DDSRestServer, ProxyConfig
from dds_tpu.obs.metrics import PROTOCOL_FAMILIES, metrics
from dds_tpu.obs.slo import SloEngine
from dds_tpu.malicious.trudy import AttackType, Trudy, parse_attack
from dds_tpu.models.facade import HomoProvider
from dds_tpu.utils.config import DDSConfig

log = logging.getLogger("dds.run")

SUPERVISOR_NAME = "supervisor"


@dataclass
class Deployment:
    cfg: DDSConfig
    net: object
    replicas: dict[str, BFTABDNode]
    supervisor: BFTSupervisor
    server: DDSRestServer
    trudy: Trudy
    ssl_client: object = None
    _stoppables: list = field(default_factory=list)
    # Constellation (shard.enabled): the sharded-plane handle — per-group
    # ShardGroup list lives on constellation.groups; `replicas` above is
    # the merged view (snapshots / anti-entropy / health reuse it as-is)
    constellation: object = None
    # the victims of the attack `launch` fired itself (`attacks.at_launch`);
    # None when it fired none, and `run_workload` then fires its own
    launch_victims: list | None = None
    # `transport.replica_processes`: the children that hold the replicas
    # (`replicas` above is then empty and `supervisor` None)
    hosts: ReplicaProcesses | None = None

    async def stop(self) -> None:
        if self.hosts is not None:
            # first: their last counters come over the transport
            await self.hosts.stop()
        if self.constellation is not None:
            await self.constellation.stop()
        if self.supervisor is not None:
            await self.supervisor.stop()
        await self.server.stop()
        for s in self._stoppables:
            await s.stop()
        # the Watchtower was configured for THIS deployment's quorum
        # geometry; left attached it would audit a later deployment (or a
        # test harness's cluster) against the wrong q/n and cry wolf
        from dds_tpu.obs.watchtower import watchtower

        if self.cfg.obs.audit_enabled:
            watchtower.detach()
        # Chronoscope is a process-wide singleton like the Watchtower:
        # detach so a later deployment (or test) starts with a clean feed
        from dds_tpu.obs.chronoscope import chronoscope

        chronoscope.detach()
        chronoscope.reset()
        from dds_tpu.obs import runtime

        runtime.remove_gc()


def _log_backend(server: DDSRestServer) -> None:
    """Say once where the proxy's ciphertext math runs: the tpu backend
    records the device it found at construction (and refuses a silent CPU
    fallback there); cpu/native run on the host."""
    be = server.backend
    log.info(
        "crypto backend %s: platform=%s device_kind=%s", be.name,
        getattr(be, "platform", "host"), getattr(be, "device_kind", "-"),
    )


async def launch(cfg: DDSConfig | None = None) -> Deployment:
    cfg = cfg or DDSConfig()
    if cfg.attacks.at_launch and not cfg.attacks.enabled:
        raise ValueError(
            "attacks.at_launch is set but attacks.enabled is not: the "
            "replicas would ignore the attack this launch is asked to fire"
        )
    started: list[ReplicaProcesses] = []
    try:
        dep = await _launch(cfg, started)
    except BaseException:
        for hosts in started:   # no child outlives a launch that failed
            await hosts.stop()
        raise
    if cfg.attacks.at_launch:
        try:
            await _attack_at_launch(dep)
        except BaseException:
            await dep.stop()
            raise
    return dep


async def _attack_at_launch(dep: Deployment) -> None:
    """`attacks.at_launch`: what the upstream's `Main.scala:187-193` does,
    once, with the deployment serving and nobody else there to fire:
    `attacks.type` at up to `byz_max_faults` replicas drawn by
    random.Random(attacks.chaos_seed), so the same seed over the same
    endpoints names the same victims. Returns once the local victims have
    taken the attack, so the first request already meets it."""
    cfg = dep.cfg
    if dep.trudy is None:
        raise ValueError(
            "attacks.at_launch is set but this process hosts no replica "
            "group to attack (fabric role)"
        )
    attack = parse_attack(cfg.attacks.type)
    dep.trudy._rng = random.Random(cfg.attacks.chaos_seed)
    victims = dep.trudy.trigger(attack)
    dep.launch_victims = victims
    log.warning(
        "attack at launch: %s at %s (attacks.chaos_seed %d)",
        attack.value, [v.rsplit("/", 1)[-1] for v in victims],
        cfg.attacks.chaos_seed,
    )
    # `Crash` and `Compromise` travel as messages: a victim in this
    # process has taken one when it is off the transport, or byzantine
    if attack is AttackType.CRASH:
        def taken(node):
            return not dep.net.has_endpoint(node.addr)
    elif attack is AttackType.BYZANTINE:
        def taken(node):
            return node.behavior == "byzantine"
    else:
        return
    local = [dep.replicas[v] for v in victims if v in dep.replicas]
    for _ in range(200):
        if all(taken(node) for node in local):
            return
        await asyncio.sleep(0.005)
    raise RuntimeError(
        f"attack at launch: {attack.value} never reached {victims}"
    )


def _replica_processes(cfg: DDSConfig, net, ssl_client) -> ReplicaProcesses:
    """`transport.replica_processes`: refuse what this launch cannot
    place; else the (not yet started) children of this launch."""
    if cfg.shard.enabled:
        raise ValueError(
            "transport.replica_processes places one quorum group; a sharded "
            "fleet is placed by its [fabric] roles"
        )
    if (cfg.replicas.addresses or cfg.replicas.local
            or cfg.replicas.supervisor_address):
        raise ValueError(
            "transport.replica_processes chooses replicas.addresses, "
            "replicas.local and replicas.supervisor_address itself: name "
            "hosts with them, or let it place processes on this machine"
        )
    if cfg.security.node_public_keys:
        raise ValueError(
            "transport.replica_processes takes free ports at launch, so no "
            "address can be in security.node_public_keys beforehand"
        )
    return ReplicaProcesses(cfg, net, ssl_client)


async def _launch(cfg: DDSConfig, started: list) -> Deployment:
    stoppables = []

    # Atlas [retry]: the per-region deadline/backoff overrides for THIS
    # process's [fabric] region land directly on the effective [proxy]
    # settings, so every downstream consumer (single-group boot, the
    # constellation, the Meridian roles) sees the derived budgets without
    # per-call-site plumbing. DEPLOY.md "Geo-distribution (Atlas)"
    # documents the rtt-ms derivation.
    if cfg.fabric.region:
        for k, v in cfg.retry.overrides_for(cfg.fabric.region).items():
            setattr(cfg.proxy, k, v)

    # Bastion [tenancy]: the metrics-cardinality ceiling applies process-
    # wide before any tenant-labeled series exists — a tenant flood must
    # overflow into the guard bucket, never balloon the registry
    if cfg.tenancy.enabled:
        from dds_tpu.obs.metrics import metrics as _metrics

        _metrics.max_series = int(cfg.tenancy.metrics_max_series)

    # Telescope wiring: hand the process-wide flight recorder its incident
    # directory (it stays disabled without one — fault-path disk writes
    # are opt-in)
    if cfg.obs.flight_dir:
        from dds_tpu.obs.flight import flight

        flight.configure(
            dir=cfg.obs.flight_dir,
            max_incidents=cfg.obs.flight_max_incidents,
            min_interval=cfg.obs.flight_min_interval,
        )

    # mutual TLS on the HTTP hops (SURVEY §2.14/§2.20 posture, configurable)
    sec = cfg.security
    ssl_server = ssl_client = None
    intranet_server = intranet_client = None
    if sec.tls_enabled or sec.intranet_tls_enabled:
        from dds_tpu.utils import tlsutil

        if sec.tls_ca and sec.tls_cert and sec.tls_key:
            ca, cert, key = sec.tls_ca, sec.tls_cert, sec.tls_key
        else:
            # dev fallback: per-node CA — single-host only (see SecurityConfig)
            paths = tlsutil.generate_ca_and_cert(
                sec.tls_dir,
                hosts=(cfg.proxy.host, cfg.transport.host, "localhost"),
            )
            ca, cert, key = paths["ca"], paths["cert"], paths["key"]
        if sec.tls_enabled:
            ssl_server = tlsutil.server_context(cert, key, ca)
            ssl_client = tlsutil.client_context(
                ca, cert, key, verify_hostname=sec.tls_verify_hostname
            )
        if sec.intranet_tls_enabled:
            # replica fabric mutual TLS — the netty-SSL intranet of the
            # reference (`dds-system.conf:18-58`): every hop presents a
            # CA-signed cert in both directions, giving the sender-keyed
            # quorum votes transport-level authenticity on top of frame MACs
            intranet_server = tlsutil.server_context(cert, key, ca)
            intranet_client = tlsutil.client_context(
                ca, cert, key, verify_hostname=sec.tls_verify_hostname
            )

    # transport fabric (SURVEY.md §5.8: control plane stays on CPU/asyncio)
    if cfg.transport.kind == "tcp":
        node_key = peer_keys = None
        if cfg.security.node_public_keys:
            from dds_tpu.utils import nodeauth

            if not cfg.security.node_key_path:
                raise ValueError(
                    "security.node_public_keys set but node_key_path empty"
                )
            node_key = nodeauth.load_or_create(cfg.security.node_key_path)
            peer_keys = nodeauth.registry(cfg.security.node_public_keys)
        net = TcpNet(
            cfg.transport.host,
            cfg.transport.port,
            ssl_server=intranet_server,
            ssl_client=intranet_client,
            frame_secret=cfg.security.transport_frame_secret.encode() or None,
            node_key=node_key,
            peer_keys=peer_keys,
            advertise=cfg.transport.advertise,
        )
        await net.start()
        cfg.transport.port = net.port  # resolve OS-assigned port 0
        stoppables.append(net)
        # Every endpoint must be a routable `host:port/name` address
        # (`TcpNet.split`): names map through `replicas.addresses`, the
        # per-host topology of `dds-system.conf:113-128`; unmapped names
        # live in this process. Always the ADVERTISED address — frames this
        # process signs carry it as src, and peers verify src against their
        # node_public_keys registry.
        local_hostport = net.advertised
        if peer_keys is not None and local_hostport not in cfg.security.node_public_keys:
            await net.stop()  # fail-fast must not leak the bound listener
            raise ValueError(
                f"per-node identity is on but this process's advertised "
                f"address {local_hostport!r} is not in "
                f"security.node_public_keys — peers could never verify its "
                f"frames (set transport.advertise to the registered address, "
                f"or register this one)"
            )

        def full(name: str) -> str:
            return f"{cfg.replicas.addresses.get(name, local_hostport)}/{name}"

    else:
        if cfg.transport.replica_processes:
            raise ValueError(
                "transport.replica_processes needs transport.kind = \"tcp\": "
                "processes meet over sockets"
            )
        net = InMemoryNet()
        local_hostport = None

        def full(name: str) -> str:
            return name

    hosts = None
    if cfg.transport.replica_processes:
        # `Main.scala:90-99` on one machine: every replica endpoint in a
        # child process (dds_tpu/hosts.py). The children start now and are
        # awaited once this process's own proxy stands.
        hosts = _replica_processes(cfg, net, intranet_client)
        started.append(hosts)   # whatever fails from here on, `launch` ends them
        hosts.spawn()

    if cfg.attacks.chaos_enabled:
        # seeded fault fabric: every send traverses the ChaosNet schedule,
        # and Nemesis (below) gains partition/delay/flood/heal attacks.
        # The inner transport stays in `stoppables`; ChaosNet.stop only
        # cancels its own deferred deliveries.
        from dds_tpu.core.chaos import ChaosNet

        net = ChaosNet(net, seed=cfg.attacks.chaos_seed)
        stoppables.append(net)
        if cfg.chaos.profiles:
            # Atlas [chaos.profiles]: named WAN link matrix between
            # region pairs. Endpoint -> region assignments arrive later
            # (the constellation builder registers placements), which is
            # fine — links key on region names and resolve per send.
            from dds_tpu.geo import wan as _wan

            _wan.apply_profiles(net, cfg.chaos.profiles,
                                scale=cfg.chaos.scale)

    if cfg.shard.enabled:
        if cfg.transport.kind == "tcp":
            # Meridian (dds_tpu/fabric): the multi-host shard fabric —
            # per-[fabric]-role this process hosts the whole constellation,
            # one quorum group, or a remote proxy, over the authenticated
            # TcpNet, with the signed shard map distributed via
            # GET /shards bootstrap + epoch gossip
            from dds_tpu.fabric.deploy import launch_meridian

            try:
                return await launch_meridian(
                    cfg, net, stoppables, ssl_server, ssl_client
                )
            except Exception:
                # fail-fast must not leak the bound listener (or a chaos
                # wrapper's timers) — mirror the nodeauth check above
                for s in reversed(stoppables):
                    try:
                        await s.stop()
                    except Exception:
                        pass
                raise
        # Constellation: S independent quorum groups behind a shard router
        # over the in-process fabric (the shard-map install step is an
        # in-process config push; see utils/config.ShardConfig)
        return await _launch_constellation(
            cfg, net, stoppables, ssl_server, ssl_client
        )

    rcfg = ReplicaConfig(
        quorum_size=cfg.replicas.byz_quorum_size,
        nonce_increment=cfg.security.nonce_challenge_increment,
        abd_mac_secret=cfg.security.abd_mac_secret.encode(),
        proxy_mac_secret=cfg.security.proxy_mac_secret.encode(),
        debug=cfg.debug,
        allow_fault_injection=cfg.attacks.enabled,
    )

    endpoints = [full(e) for e in cfg.replicas.endpoints]
    sentinent_names = set(cfg.replicas.sentinent)
    sentinent = [full(e) for e in cfg.replicas.endpoints if e in sentinent_names]
    active = [e for e in endpoints if e not in set(sentinent)]

    # `Main.scala:90-99`: a process spawns only ITS replicas; the rest of
    # the quorum is reached over the fabric. Default = every name mapped to
    # this process (memory transport: all of them).
    if cfg.replicas.local:
        local_names = set(cfg.replicas.local)
    elif local_hostport is not None:
        local_names = {
            n for n in cfg.replicas.endpoints
            if cfg.replicas.addresses.get(n, local_hostport) == local_hostport
        }
    else:
        local_names = set(cfg.replicas.endpoints)

    sup_local = (
        local_hostport is None
        or not cfg.replicas.supervisor_address
        or cfg.replicas.supervisor_address == local_hostport
    )
    sup_addr = (
        SUPERVISOR_NAME
        if local_hostport is None
        else f"{cfg.replicas.supervisor_address or local_hostport}/{SUPERVISOR_NAME}"
    )

    replicas = {
        full(e): BFTABDNode(full(e), endpoints, sup_addr, net, rcfg)
        for e in cfg.replicas.endpoints
        if e in local_names
    }
    for e in sentinent:
        if e in replicas:
            replicas[e].behavior = "sentinent"  # Main.scala:96-98

    # optional snapshot restore + periodic save (core/snapshot.py v2:
    # authenticated generations; corrupt/forged files are quarantined by
    # load_all, never allowed to abort this boot)
    snap_secret = None
    if cfg.recovery.snapshot_dir:
        from dds_tpu.core import snapshot as snap

        snap_secret = snap.derive_secret(
            (cfg.recovery.snapshot_secret or cfg.security.abd_mac_secret).encode(),
            cfg.security.node_key_path or None,
        )
        restored = snap.load_all(
            replicas, cfg.recovery.snapshot_dir, secret=snap_secret
        )
        if restored:
            log.info("restored %d replica snapshots from %s", restored,
                     cfg.recovery.snapshot_dir)

    def _start_antientropy(node: BFTABDNode) -> None:
        node.antientropy.configure(
            interval=cfg.recovery.anti_entropy_interval,
            jitter=cfg.recovery.anti_entropy_jitter,
        )
        node.antientropy.start()

    def _rebuild_local(endpoint: str) -> None:
        old = replicas.get(endpoint)
        if old is not None:
            old.antientropy.cancel()  # the replaced node's loop must die
        replicas[endpoint] = BFTABDNode(endpoint, endpoints, sup_addr, net, rcfg)
        if cfg.recovery.anti_entropy_enabled:
            _start_antientropy(replicas[endpoint])

    # per-host node agent: honors the supervisor's Redeploy for replicas
    # THIS process owns — the `Main` process is what re-instantiates
    # actors in the reference's remote deployment too
    # (`BFTSupervisor.scala:130-149`). A target still on the transport is
    # NOT rebuilt (a stray/duplicate Redeploy must not wipe a live
    # replica's state); either way the agent acks so the supervisor's
    # reseed can proceed.
    # It also answers a launcher that keeps its replicas in child processes
    # (`transport.replica_processes`) with this process's protocol counters,
    # and in that launcher takes the children's answers in.
    async def _nodehost(sender: str, msg) -> None:
        if isinstance(msg, M.Redeploy) and msg.endpoint in replicas:
            if net.has_endpoint(msg.endpoint):
                log.info("nodehost: %s is alive, not rebuilding", msg.endpoint)
            else:
                log.info(
                    "nodehost rebuilding %s (asked by %s)", msg.endpoint, sender
                )
                _rebuild_local(msg.endpoint)
            net.send(full(NODEHOST), sender, M.Redeployed(msg.endpoint))
        elif isinstance(msg, M.CountersRequest):
            net.send(full(NODEHOST), sender,
                     M.Counters(metrics.counters(PROTOCOL_FAMILIES)))
        elif isinstance(msg, M.Counters) and hosts is not None:
            hosts.on_counters(sender, msg)

    net.register(full(NODEHOST), _nodehost)

    async def redeploy(endpoint: str) -> None:
        """Supervisor redeploy hook: rebuild locally when this process owns
        the endpoint, else ask the owning host's node agent over the
        fabric and await its Redeployed ack (retrying a couple of times —
        a silently dropped frame must not leave the supervisor reseeding
        a node that was never rebuilt)."""
        if endpoint in replicas:
            if not net.has_endpoint(endpoint):
                _rebuild_local(endpoint)
            return
        hostport = endpoint.rsplit("/", 1)[0]
        ack: asyncio.Future = asyncio.get_event_loop().create_future()
        tmp = full(f"redeploy-ack-{sigs_generate_nonce()}")

        async def on_ack(sender: str, msg) -> None:
            if (
                isinstance(msg, M.Redeployed)
                and msg.endpoint == endpoint
                and not ack.done()
            ):
                ack.set_result(True)

        net.register(tmp, on_ack)
        try:
            for _ in range(3):
                net.send(tmp, f"{hostport}/{NODEHOST}", M.Redeploy(endpoint))
                try:
                    await asyncio.wait_for(asyncio.shield(ack), 1.0)
                    return
                except asyncio.TimeoutError:
                    continue
            log.warning("redeploy of %s was never acknowledged", endpoint)
        finally:
            net.unregister(tmp)

    supervisor = None
    if sup_local:
        supervisor = BFTSupervisor(
            sup_addr,
            active,
            sentinent,
            net,
            SupervisorConfig(
                quorum_size=cfg.replicas.byz_quorum_size,
                proactive_recovery_warmup=cfg.recovery.warm_up,
                proactive_recovery_interval=cfg.recovery.interval,
                sentinent_awake_timeout=cfg.recovery.sentinent_awake_timeout,
                crashed_recovery_timeout=cfg.recovery.crashed_recovery_timeout,
                proactive_recovery_enabled=cfg.recovery.enabled,
                verified_transfer=cfg.recovery.verified_transfer,
                manifest_timeout=cfg.recovery.manifest_timeout,
                state_chunk_keys=cfg.recovery.state_chunk_keys,
                abd_mac_secret=cfg.security.abd_mac_secret.encode(),
                debug=cfg.debug,
            ),
            redeploy=redeploy,
            # the draw of a spare comes from the deployment's one seed, as
            # Trudy's victims do: a run's membership history is a function
            # of its configuration
            rng=random.Random(cfg.attacks.chaos_seed),
        )
        supervisor.start()

    abd = AbdClient(
        full("proxy-0"),
        net,
        active,
        AbdClientConfig(
            proxy_mac_secret=cfg.security.proxy_mac_secret.encode(),
            nonce_increment=cfg.security.nonce_challenge_increment,
            request_timeout=cfg.proxy.intranet_request_timeout,
            abd_mac_secret=cfg.security.abd_mac_secret.encode(),
            quorum_size=cfg.replicas.byz_quorum_size,
            breaker_threshold=cfg.proxy.breaker_threshold,
            breaker_reset=cfg.proxy.breaker_reset,
            breaker_probe_timeout=cfg.proxy.breaker_probe_timeout,
            fast_fail_all_open=cfg.admission.fast_fail,
        ),
    )
    server = DDSRestServer(
        abd,
        ProxyConfig(
            host=cfg.proxy.host,
            port=cfg.proxy.port,
            region=cfg.fabric.region,
            request_budget=cfg.proxy.request_budget,
            retry_backoff=cfg.proxy.retry_backoff,
            retry_max_delay=cfg.proxy.retry_max_delay,
            retry_attempts=cfg.proxy.retry_attempts,
            retry_after_hint=cfg.proxy.retry_after_hint,
            handler_timeout=cfg.proxy.handler_timeout,
            crypto_backend=cfg.proxy.crypto_backend,
            key_sync_enabled=cfg.proxy.key_sync_enabled,
            key_sync_warmup=cfg.proxy.key_sync_warm_up,
            key_sync_interval=cfg.proxy.key_sync_interval,
            peers=cfg.proxy.remote_peers,
            keys_path=cfg.proxy.stored_keys_path,
            replica_refresh_interval=cfg.proxy.replica_refresh_interval,
            supervisor=sup_addr,
            trace_route_enabled=cfg.debug or cfg.obs.trace_route,
            metrics_route_enabled=cfg.obs.metrics_route,
            slo_route_enabled=cfg.obs.slo_route,
            analytics_enabled=cfg.analytics.enabled,
            analytics_max_rows=cfg.analytics.max_rows,
            analytics_max_request_bytes=cfg.analytics.max_request_bytes,
            admission=cfg.admission,
            tenancy=cfg.tenancy,
            resident=cfg.resident,
            search=cfg.search,
            storage=cfg.storage,
            heliograph=cfg.heliograph,
            ssl_server_context=ssl_server,
            ssl_client_context=ssl_client,
        ),
        local_replicas=replicas,
        slo=SloEngine.from_obs(cfg.obs),
    )
    if hosts is not None:
        # the children came up while the backend above was made
        await hosts.ready()
    await server.start()
    _log_backend(server)
    if hosts is not None:
        # ... and the supervisor, in the first child, has told this proxy
        # who is active (`_replica_refresh_loop` asked when it started)
        for _ in range(200):
            if abd._preferred:
                break
            await asyncio.sleep(0.01)
        else:
            raise RuntimeError(
                f"the supervisor at {sup_addr} never named the active "
                "replicas"
            )

    # Merkle anti-entropy loops: one pull agent per local replica, on a
    # jittered timer so the fleet's rounds spread out instead of thundering
    if cfg.recovery.anti_entropy_enabled:
        for node in replicas.values():
            _start_antientropy(node)

        class _AntiEntropyStopper:
            async def stop(self):
                for node in replicas.values():
                    await node.antientropy.stop()

        stoppables.append(_AntiEntropyStopper())

    if cfg.attacks.chaos_enabled:
        from dds_tpu.malicious.trudy import Nemesis

        trudy = Nemesis(net, active, cfg.replicas.byz_max_faults,
                        addr=full("trudy"))
    else:
        trudy = Trudy(net, active, cfg.replicas.byz_max_faults,
                      addr=full("trudy"))
    dep = Deployment(cfg, net, replicas, supervisor, server, trudy, ssl_client,
                     stoppables, hosts=hosts)

    # per-process identity: the dds_process_info gauge on /metrics and the
    # flight recorder's incident headers (obs/panopticon correlates by it)
    from dds_tpu.obs.flight import flight as _flight
    from dds_tpu.obs.panopticon import process_info

    _identity = {"host": local_hostport or "local", "role": "single"}
    if cfg.fabric.region:
        _identity["region"] = cfg.fabric.region
    _flight.configure(identity=_identity)
    process_info(role="single", region=cfg.fabric.region)

    if cfg.recovery.snapshot_dir and cfg.recovery.snapshot_interval > 0:
        from dds_tpu.core import snapshot as snap

        async def _snapshot_loop():
            while True:
                await asyncio.sleep(cfg.recovery.snapshot_interval)
                # off-loop: serializing large repositories must not stall
                # ABD handling or recovery timers
                await asyncio.to_thread(
                    snap.save_all, dict(dep.replicas),
                    cfg.recovery.snapshot_dir,
                    snap_secret, cfg.recovery.snapshot_keep,
                )

        task = supervised_task(_snapshot_loop(), name="run.snapshot_loop")

        class _TaskStopper:
            async def stop(self):
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass

        stoppables.append(_TaskStopper())

    # Watchtower: the online invariant auditor rides the process tracer.
    # Attached LAST — once nothing else in this launch can fail — so an
    # aborted boot never leaves a mis-configured global auditor behind
    # (Deployment.stop detaches it again). Quorum-intersection checks are
    # only sound when every replica's handler spans land in THIS process's
    # ring; a multi-host topology (names mapped to other hosts) keeps the
    # tag/repair/state-machine checks and drops the quorum ones.
    if cfg.obs.audit_enabled:
        from dds_tpu.obs.watchtower import watchtower
        from dds_tpu.utils.trace import tracer as _tracer

        n_active = len(cfg.replicas.endpoints) - len(cfg.replicas.sentinent)
        all_local = not cfg.replicas.addresses and not cfg.replicas.local
        watchtower.configure(
            quorum_size=cfg.replicas.byz_quorum_size,
            n_replicas=n_active,
            check_quorum=cfg.obs.audit_quorum_checks and all_local,
        )
        watchtower.attach(_tracer)
    # Chronoscope rides the same process tracer (every span is local in a
    # single-process launch); DDS_OBS_PIPE=0 keeps it dormant
    from dds_tpu.obs.chronoscope import chronoscope

    chronoscope.attach()
    # the collector's pauses: noted lock-free, drained by the proxy's loop
    # sampler into dds_gc_pause_seconds_total and runtime.gc spans
    from dds_tpu.obs import runtime

    runtime.install_gc()
    return dep


def shard_configs(cfg: DDSConfig):
    """(ReplicaConfig, SupervisorConfig, AbdClientConfig) for one quorum
    group of a Constellation — shared by the single-process sharded boot
    below and the Meridian multi-host roles (dds_tpu/fabric/deploy),
    which must derive IDENTICAL per-group stacks in every process."""
    sh = cfg.shard
    rcfg = ReplicaConfig(
        quorum_size=sh.quorum_size,
        nonce_increment=cfg.security.nonce_challenge_increment,
        abd_mac_secret=cfg.security.abd_mac_secret.encode(),
        proxy_mac_secret=cfg.security.proxy_mac_secret.encode(),
        debug=cfg.debug,
        allow_fault_injection=cfg.attacks.enabled,
    )
    sup_cfg = SupervisorConfig(
        quorum_size=sh.quorum_size,
        proactive_recovery_warmup=cfg.recovery.warm_up,
        proactive_recovery_interval=cfg.recovery.interval,
        sentinent_awake_timeout=cfg.recovery.sentinent_awake_timeout,
        crashed_recovery_timeout=cfg.recovery.crashed_recovery_timeout,
        proactive_recovery_enabled=cfg.recovery.enabled,
        verified_transfer=cfg.recovery.verified_transfer,
        manifest_timeout=cfg.recovery.manifest_timeout,
        state_chunk_keys=cfg.recovery.state_chunk_keys,
        abd_mac_secret=cfg.security.abd_mac_secret.encode(),
        debug=cfg.debug,
    )
    abd_cfg = AbdClientConfig(
        proxy_mac_secret=cfg.security.proxy_mac_secret.encode(),
        nonce_increment=cfg.security.nonce_challenge_increment,
        request_timeout=cfg.proxy.intranet_request_timeout,
        abd_mac_secret=cfg.security.abd_mac_secret.encode(),
        quorum_size=sh.quorum_size,
        breaker_threshold=cfg.proxy.breaker_threshold,
        breaker_reset=cfg.proxy.breaker_reset,
        breaker_probe_timeout=cfg.proxy.breaker_probe_timeout,
        fast_fail_all_open=cfg.admission.fast_fail,
        # Atlas read-local lease client knobs ([geo]); region + per-group
        # lease_ttl/replica_regions are stamped by the constellation
        # builder, which is also what flips lease_enabled on
        lease_renew_margin=cfg.geo.lease_renew_margin,
        local_read_timeout=cfg.geo.local_read_timeout,
    )
    return rcfg, sup_cfg, abd_cfg


def proxy_config(cfg: DDSConfig, supervisor, ssl_server, ssl_client,
                 **overrides) -> ProxyConfig:
    """The sharded proxy's ProxyConfig from the config tree (no gossip
    peers baked in — the Meridian roles layer those via `overrides`)."""
    kw = dict(
        host=cfg.proxy.host,
        port=cfg.proxy.port,
        region=cfg.fabric.region,
        request_budget=cfg.proxy.request_budget,
        retry_backoff=cfg.proxy.retry_backoff,
        retry_max_delay=cfg.proxy.retry_max_delay,
        retry_attempts=cfg.proxy.retry_attempts,
        retry_after_hint=cfg.proxy.retry_after_hint,
        handler_timeout=cfg.proxy.handler_timeout,
        crypto_backend=cfg.proxy.crypto_backend,
        keys_path=cfg.proxy.stored_keys_path,
        replica_refresh_interval=cfg.proxy.replica_refresh_interval,
        supervisor=supervisor,
        trace_route_enabled=cfg.debug or cfg.obs.trace_route,
        metrics_route_enabled=cfg.obs.metrics_route,
        slo_route_enabled=cfg.obs.slo_route,
        analytics_enabled=cfg.analytics.enabled,
        analytics_max_rows=cfg.analytics.max_rows,
        analytics_max_request_bytes=cfg.analytics.max_request_bytes,
        admission=cfg.admission,
        tenancy=cfg.tenancy,
        resident=cfg.resident,
        search=cfg.search,
        storage=cfg.storage,
        heliograph=cfg.heliograph,
        # operator reshape control (POST /_reshard, /_helmsman) — gated
        # exactly like the Meridian proxy role; without a reshard
        # controller wired the routes still 404
        reshard_route_enabled=cfg.fabric.admin_routes,
        ssl_server_context=ssl_server,
        ssl_client_context=ssl_client,
    )
    kw.update(overrides)
    return ProxyConfig(**kw)


class ConstellationReshard:
    """POST /_reshard controller for the in-process constellation: the
    same surface the Meridian controller presents (async split/merge +
    phase/retry_after for the route's 409 handling), delegating to the
    Constellation. An omitted split target lets the Constellation name
    the new group; naming one makes the request replayable (the route's
    completed-idempotency check needs the target to recognize a done
    split)."""

    def __init__(self, const):
        self._const = const

    @property
    def phase(self):
        return self._const.rebalancer.phase

    def retry_after(self) -> float:
        return self._const.rebalancer.retry_after()

    async def split(self, source: str, target: str | None = None):
        await self._const.split(source, target)
        return self._const.manager.current()

    async def merge(self, source: str):
        await self._const.merge(source)
        return self._const.manager.current()


async def _launch_constellation(cfg: DDSConfig, net, stoppables,
                                ssl_server, ssl_client) -> Deployment:
    """shard.enabled boot: S quorum groups + ShardRouter behind the proxy.

    Each group mirrors the single-group stack (replicas, spares,
    supervisor, anti-entropy, Trudy) with namespaced endpoints over the
    one transport; the REST server talks to the ShardRouter, which routes
    point ops by the signed epoch-versioned ShardMap and scatter-gathers
    aggregates. The Watchtower audits every group against ITS OWN quorum
    geometry via the per-group geometry table."""
    from dds_tpu.shard import build_constellation

    sh = cfg.shard
    rcfg, sup_cfg, abd_cfg = shard_configs(cfg)
    const = build_constellation(
        net,
        shard_count=sh.count,
        vnodes_per_group=sh.vnodes_per_group,
        secret=cfg.security.abd_mac_secret.encode(),
        manifest_timeout=sh.manifest_timeout,
        ack_timeout=sh.ack_timeout,
        chunk_keys=sh.migrate_chunk_keys,
        fence_lease=sh.fence_lease,
        journal_dir=sh.plan_dir or None,
        n_active=sh.replicas_per_group,
        n_sentinent=sh.sentinent_per_group,
        quorum=sh.quorum_size,
        max_faults=sh.max_faults,
        rcfg=rcfg,
        sup_cfg=sup_cfg,
        abd_cfg=abd_cfg,
        chaos=cfg.attacks.chaos_enabled,
        # Atlas: region-aware placement + read-local leases ([geo]); the
        # builder signs the region assignment onto the shard map and
        # homes this process's proxies at [fabric] region
        regions=list(cfg.geo.regions) if cfg.geo.enabled else None,
        placement=cfg.geo.placement,
        lease_ttl=cfg.geo.lease_ttl if cfg.geo.enabled else 0.0,
        client_region=cfg.fabric.region,
    )
    if sh.plan_dir:
        # a previous process may have died mid-reshard: resolve the
        # journaled plan (roll back before commit, forward after) before
        # any traffic or new plan touches the fleet
        await const.rebalancer.recover(const.group)
    replicas: dict[str, BFTABDNode] = {}
    for g in const.groups:
        replicas.update(g.replicas)

    if cfg.recovery.enabled:
        for g in const.groups:
            g.supervisor.start()
    if cfg.recovery.anti_entropy_enabled:
        for g in const.groups:
            for node in g.replicas.values():
                node.antientropy.configure(
                    interval=cfg.recovery.anti_entropy_interval,
                    jitter=cfg.recovery.anti_entropy_jitter,
                )
                if cfg.geo.enabled and g.replica_regions:
                    # Atlas: cross-region pull pairing — a biased share
                    # of rounds reaches across the WAN, extra-jittered so
                    # regional fleets don't thunder over the slow links
                    node.antientropy.configure(
                        regions=g.replica_regions,
                        cross_region_bias=cfg.geo.cross_region_bias,
                        cross_jitter=cfg.geo.cross_jitter,
                    )
                node.antientropy.start()

    server = DDSRestServer(
        const.router,
        proxy_config(cfg, const.groups[0].supervisor.addr,
                     ssl_server, ssl_client),
        local_replicas=replicas,
        slo=SloEngine.from_obs(cfg.obs),
        reshard=ConstellationReshard(const),
    )
    await server.start()
    _log_backend(server)

    if cfg.helmsman.enabled:
        from dds_tpu.fleet import Helmsman

        admission = server.admission
        hm = Helmsman.from_config(
            cfg.helmsman,
            load_census=const.router.load_census,
            slo_alerts=server.slo.alerts,
            shed_level=(lambda a=admission: a.shed_level if a else 0),
            breaker_census=const.router.breaker_census,
            split=(lambda gid, c=const: c.split(gid)),
            merge=(lambda gid, c=const: c.merge(gid)),
            promote=(lambda gid, c=const: c.promote(gid)),
            moved_bytes=lambda r=const.rebalancer: r.moved_bytes_total,
            reshard_busy=lambda r=const.rebalancer: r.lock.locked(),
            # Bastion: per-tenant burn attribution on every decision —
            # worst window per tenant, from the SLO engine's tenant bins
            tenant_burns=(lambda s=server.slo: {
                t: max(b) for t, b in s.tenant_burns().items() if b
            }) if cfg.tenancy.enabled else None,
            # Atlas: gid -> home region, read live so split-born groups
            # (which inherit the victim's region) appear without rewiring
            regions=(lambda c=const: {
                g.gid: g.home_region for g in c.groups if g.home_region
            }) if cfg.geo.enabled else None,
            # Heliograph: sustained canary unreachability from a region is
            # black-box promotion evidence — the probes exercise the real
            # serving path, so they fire even while heartbeats stay green
            canary_unreachable=(lambda s=server: (
                s.heliograph.unreachable_regions()
                if s.heliograph is not None else set()
            )) if cfg.heliograph.enabled else None,
            # Stratum: blended hot+warm tier occupancy — HBM-full now
            # reads as pressure the controller can split away, instead
            # of a silent pool reset the fleet never sees
            pool_pressure=(lambda s=server: s.tier_pressure())
            if cfg.storage.enabled else None,
        )
        if admission is not None:
            admission.subscribe(hm.on_admission)
        server.helmsman = hm
        hm.start()
        stoppables.append(hm)

    dep = Deployment(cfg, net, replicas, None, server,
                     const.groups[0].trudy, ssl_client, stoppables,
                     constellation=const)
    from dds_tpu.obs.flight import flight as _flight
    from dds_tpu.obs.panopticon import process_info

    _identity = {"host": "local", "role": "constellation"}
    if cfg.fabric.region:
        _identity["region"] = cfg.fabric.region
    _flight.configure(identity=_identity)
    process_info(role="constellation", region=cfg.fabric.region)
    if cfg.obs.audit_enabled:
        from dds_tpu.obs.watchtower import watchtower
        from dds_tpu.utils.trace import tracer as _tracer

        watchtower.configure(
            quorum_size=sh.quorum_size,
            n_replicas=sh.replicas_per_group,
            check_quorum=cfg.obs.audit_quorum_checks,
            group_geometry={
                g.gid: (g.quorum_size, len(g.active)) for g in const.groups
            },
            # Atlas: lease-tagged single-hop reads are audited against
            # the live lease tables instead of the quorum-size bound
            lease_lookup=(lambda name, c=const: any(
                g.lease_table is not None and g.lease_table.held_by(name)
                for g in c.groups
            )) if cfg.geo.enabled and cfg.geo.lease_ttl > 0 else None,
        )
        watchtower.attach(_tracer)
    from dds_tpu.obs.chronoscope import chronoscope

    chronoscope.attach()
    return dep


def mint_node_keys(count: int, directory: str = "certs",
                   hosts: list[str] | None = None,
                   host: str = "127.0.0.1", base_port: int = 2552) -> str:
    """Provision per-process transport identities for an N-process fleet:
    one Ed25519 key file per process (born 0600, existing files reused so
    re-running never rotates keys under a live fleet) plus the
    `[security]` TOML stanza wiring the public-key registry — the manual,
    error-prone step of DEPLOY.md §1 as one command:

        python -m dds_tpu.run --mint-node-keys 3 --mint-dir certs \\
            --mint-hosts 10.0.0.1:2552,10.0.0.2:2552,10.0.0.3:2552

    Returns (and `main` prints) the stanza; paste it into every process's
    config and point each process's `node-key-path` at ITS key file."""
    import pathlib

    from dds_tpu.utils import nodeauth

    if hosts:
        hostports = [
            hp if ":" in hp else f"{hp}:{base_port}" for hp in hosts
        ]
    else:
        hostports = [f"{host}:{base_port + i}" for i in range(count)]
    if count and hosts and len(hostports) != count:
        raise ValueError(
            f"--mint-node-keys {count} but {len(hostports)} hosts given"
        )
    d = pathlib.Path(directory)
    lines = ["# Meridian node identities — minted by --mint-node-keys.",
             "# Per process: set security.node-key-path to ITS OWN file:"]
    registry = []
    for i, hp in enumerate(hostports):
        path = d / f"node_{i}.key"
        key = nodeauth.load_or_create(path)
        lines.append(f"#   process {i} ({hp}): node-key-path = {str(path)!r}")
        registry.append(f'"{hp}" = "{nodeauth.public_hex(key)}"')
    lines.append("")
    lines.append("[security.node-public-keys]")
    lines.extend(registry)
    return "\n".join(lines) + "\n"


def load_provider(cfg: DDSConfig) -> HomoProvider:
    """Client HE keys per config: inline blob > keys file > fresh generation
    (persisted back to the file when a path is configured) — the
    `client.conf:81-88` reproducibility contract: a restarted client can
    re-attach to an existing store and still decrypt it."""
    import pathlib

    from dds_tpu.models.keys import HEKeys

    c = cfg.client
    if c.he_keys_inline:
        keys = HEKeys.from_json(c.he_keys_inline)
    elif c.he_keys_path and pathlib.Path(c.he_keys_path).exists():
        keys = HEKeys.from_json(pathlib.Path(c.he_keys_path).read_text())
    else:
        keys = HEKeys.generate(c.paillier_bits, c.rsa_bits)
        if c.he_keys_path:
            from dds_tpu.utils.nodeauth import write_secret_file

            # born 0600: these private keys decrypt the whole store
            write_secret_file(pathlib.Path(c.he_keys_path), keys.to_json())
    bulk = None
    if c.bulk_encrypt_backend:
        from dds_tpu.models.backend import get_backend

        bulk = get_backend(c.bulk_encrypt_backend)
    # Sanctum posture for the decrypt CRT legs: host-only unless the
    # operator explicitly opted in ([crypto] secret-device, or the
    # DDS_SECRET_DEVICE twin — validated loudly HERE, at construction,
    # per the DDS_PROD_TB pattern, so a typo'd opt-in/out never silently
    # changes where key material computes).
    from dds_tpu.ops.flags import secret_device

    secret = None
    if secret_device(default=cfg.crypto.secret_device):
        from dds_tpu.sanctum import SecretBackend

        secret = SecretBackend(device=True)
    return HomoProvider(
        keys, fast_blinding=c.fast_blinding, bulk_backend=bulk,
        secret_backend=secret,
    )


async def run_workload(dep: Deployment, provider: HomoProvider | None = None,
                       seed: int | None = None):
    """Spawn the configured clients and drive generated digests; returns reports."""
    cfg = dep.cfg
    provider = provider or load_provider(cfg)
    rng = random.Random(seed)
    if dep.trudy is not None:
        dep.trudy._rng = rng  # make --seed reproduce attack victim selection
    dt = cfg.client.data_table
    if (cfg.attacks.enabled and dep.trudy is not None
            and dep.launch_victims is None):
        # fire mid-run like the reference (Main.scala:187-193): the workload
        # below must complete correct quorums against a damaged cluster.
        # Not a second time where `launch` fired already (at_launch): two
        # draws could name more than f victims
        asyncio.get_event_loop().call_later(
            0.1, lambda: dep.trudy.trigger(cfg.attacks.type)
        )
    runs = []
    for i in range(cfg.client.nr_of_local_clients):
        client = DDSHttpClient(
            provider,
            ClientConfig(
                proxies=[f"{cfg.proxy.host}:{dep.server.cfg.port}"],
                request_timeout=cfg.client.http_requests_timeout,
                fixed_columns=dt.fixed_nr_of_columns,
                schema=dt.fixed_columns_hcrypt,
                ssl_context=dep.ssl_client,
            ),
            rng=random.Random(rng.getrandbits(64)),
        )
        ops = generate(
            cfg.client.nr_of_operations,
            cfg.client.proportions or None,
            dt.max_nr_of_columns,
            dt.fixed_columns_mappings,
            dt.fixed_columns_hcrypt,
            rng=random.Random(rng.getrandbits(64)),
        )
        runs.append(client.execute(Digest(ops)))
    # clients run concurrently, like the reference's N client actors
    return list(await asyncio.gather(*runs))


def _die_with_parent() -> None:
    """Exit when stdin reaches its end, however the process that held its
    other end went (SIGKILL included: the kernel closes what it held). A
    thread's blocking read, so a busy event loop delays nothing."""
    def watch() -> None:
        try:
            while os.read(0, 4096):
                pass
        except OSError:
            pass
        os._exit(0)

    threading.Thread(target=watch, name="dds-parent-watch",
                     daemon=True).start()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="Run a DDS node + workload")
    ap.add_argument("--config", help="TOML/JSON config path")
    ap.add_argument("--ops", type=int, help="override nr-of-operations")
    ap.add_argument("--backend", choices=["cpu", "tpu", "native"], help="crypto backend")
    ap.add_argument("--port", type=int, help="proxy port (0 = auto)")
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--serve", action="store_true", help="keep serving after workload")
    ap.add_argument("--role", help="override [fabric] role (all | proxy | group:N)")
    ap.add_argument("--die-with-parent", action="store_true",
                    help="exit when stdin ends: the launcher of a replica "
                         "process (transport.replica_processes) holds its "
                         "other end and nobody else does")
    ap.add_argument("--mint-node-keys", type=int, metavar="N",
                    help="provision N per-process Ed25519 node keys + the "
                         "security.node-public-keys TOML stanza, then exit")
    ap.add_argument("--mint-dir", default="certs",
                    help="directory for --mint-node-keys files")
    ap.add_argument("--mint-hosts", default="",
                    help="comma-separated host:port per process for "
                         "--mint-node-keys (default 127.0.0.1:2552+i)")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO, format="%(name)s %(message)s")
    if args.mint_node_keys is not None:
        hosts = [h for h in args.mint_hosts.split(",") if h.strip()]
        print(mint_node_keys(args.mint_node_keys, args.mint_dir,
                             hosts or None), end="")
        return
    if args.die_with_parent:
        _die_with_parent()
    cfg = DDSConfig.load(args.config) if args.config else DDSConfig()
    if args.ops is not None:
        cfg.client.nr_of_operations = args.ops
    if args.backend:
        cfg.proxy.crypto_backend = args.backend
    if args.port is not None:
        cfg.proxy.port = args.port
    if args.role:
        cfg.fabric.role = args.role

    async def go():
        dep = await launch(cfg)
        try:
            # group-role fabric processes host replicas, not clients; a
            # proxy launched without a workload (ops 0) also just serves
            runs_workload = (
                dep.trudy is not None and cfg.client.nr_of_operations > 0
            )
            if cfg.shard.enabled and cfg.transport.kind == "tcp":
                from dds_tpu.fabric.deploy import parse_role

                if parse_role(cfg.fabric.role)[0] == "group":
                    runs_workload = False
            if runs_workload:
                reports = await run_workload(dep, seed=args.seed)
                for i, r in enumerate(reports):
                    print(
                        f"client {i}: {r.operations} ops in {r.wall_seconds:.2f}s "
                        f"-> {r.ops_per_second:.1f} ops/s "
                        f"({r.succeeded} ok, {r.not_found} miss, {r.failed} failed)"
                    )
            if args.serve:
                print(
                    f"serving on {dep.server.cfg.host}:{dep.server.cfg.port} "
                    f"(ctrl-c to stop)", flush=True,
                )
                await asyncio.Event().wait()
        finally:
            await dep.stop()

    asyncio.run(go())


if __name__ == "__main__":
    main()
