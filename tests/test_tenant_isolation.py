"""Bastion REST-surface isolation tests: the tenant boundary end to end.

Small real stacks (InMemoryNet quorum + DDSRestServer) exercise the
edges the unit suite can't: the `x-dds-tenant` header clamp answering
typed 400s, cross-tenant key access answering typed 403s, per-tenant
aggregate/order scoping, two tenants' concurrent folds over one modulus
each getting their own sum, and the tenant surfaces on /health and
/metrics.

The closing drill is the ISSUE's chaos acceptance: a client-side
`TenantKeyring` rotates and then crypto-shreds one tenant's keys in the
middle of live multi-tenant traffic. Other tenants stay linearizable
(their ciphertexts and homomorphic folds still decrypt to the right
plaintexts), the shredded tenant's ciphertexts become permanently
undecryptable with the typed refusal, and the Watchtower — auditing
every quorum op throughout — reports ZERO verdicts: key lifecycle is a
client-domain event, invisible to storage invariants.
"""

import asyncio
import contextlib
import json
import math
import threading

import pytest

from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.http.miniserver import http_request
from dds_tpu.http.server import DDSRestServer, ProxyConfig
from dds_tpu.models.tenancy import TenantKeyring, TenantShredded
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils.config import AdmissionConfig, DDSConfig, TenancyConfig
from dds_tpu.utils.trace import tracer

pytestmark = pytest.mark.tenancy


@contextlib.asynccontextmanager
async def tenancy_stack(acfg: AdmissionConfig | None = None, n=4, quorum=3):
    from dds_tpu.core.replica import BFTABDNode, ReplicaConfig

    net = InMemoryNet()
    rcfg = ReplicaConfig(quorum_size=quorum)
    addrs = [f"replica-{i}" for i in range(n)]
    replicas = {a: BFTABDNode(a, addrs, "supervisor", net, rcfg)
                for a in addrs}
    abd = AbdClient("proxy-0", net, addrs,
                    AbdClientConfig(request_timeout=2.0, quorum_size=quorum))
    server = DDSRestServer(abd, ProxyConfig(
        host="127.0.0.1", port=0, admission=acfg,
        tenancy=TenancyConfig(enabled=True),
    ))
    await server.start()
    try:
        yield server, replicas
    finally:
        await server.stop()


async def _put(server, contents, tenant=None, expect=200):
    headers = {"x-dds-tenant": tenant} if tenant else None
    status, body = await http_request(
        "127.0.0.1", server.cfg.port, "POST", "/PutSet",
        json.dumps({"contents": contents}).encode(),
        headers=headers, timeout=10.0,
    )
    assert status == expect, body
    return body.decode()


async def _get(server, method, target, tenant=None, body=None):
    headers = {"x-dds-tenant": tenant} if tenant else None
    return await http_request(
        "127.0.0.1", server.cfg.port, method, target, body,
        headers=headers, timeout=10.0,
    )


# --------------------------------------------------- edge: the header clamp


def test_malformed_tenant_header_is_typed_400():
    async def go():
        async with tenancy_stack() as (server, _):
            before = metrics.value(
                "dds_tenant_header_rejects_total",
                reason="must match [A-Za-z0-9][A-Za-z0-9._-]*") or 0
            for bad in ("no spaces", "-lead", 'quo"te', "a" * 70):
                status, body = await _get(server, "GET", "/health",
                                          tenant=bad)
                assert status == 400
                err = json.loads(body)
                assert err["error"] == "invalid tenant header"
                assert err["reason"]
            after = metrics.value(
                "dds_tenant_header_rejects_total",
                reason="must match [A-Za-z0-9][A-Za-z0-9._-]*") or 0
            assert after == before + 3  # the length reject has its own reason

    asyncio.run(go())


def test_absent_header_is_the_default_tenant():
    async def go():
        async with tenancy_stack() as (server, _):
            key = await _put(server, ["123"])  # no header -> "default"
            status, body = await _get(server, "GET", f"/GetSet/{key}")
            assert status == 200
            assert json.loads(body)["contents"] == ["123"]
            # the explicit spelling is the same identity, not a stranger
            status, _ = await _get(server, "GET", f"/GetSet/{key}",
                                   tenant="default")
            assert status == 200

    asyncio.run(go())


# ------------------------------------------------- keyspace ownership: 403s


def test_cross_tenant_access_is_typed_403():
    async def go():
        async with tenancy_stack() as (server, _):
            key = await _put(server, ["7", "8"], tenant="alice")
            before = metrics.value("dds_tenant_denied_total",
                                   tenant="bob") or 0
            status, body = await _get(server, "GET", f"/GetSet/{key}",
                                      tenant="bob")
            assert status == 403
            err = json.loads(body)
            assert err == {"error": "cross-tenant access denied",
                           "tenant": "bob", "key": key}
            # mutations are refused the same way — a 403, not a quiet no-op
            status, _ = await _get(server, "DELETE", f"/RemoveSet/{key}",
                                   tenant="bob")
            assert status == 403
            assert (metrics.value("dds_tenant_denied_total", tenant="bob")
                    or 0) == before + 2
            # the owner is untouched by the attempts
            status, body = await _get(server, "GET", f"/GetSet/{key}",
                                      tenant="alice")
            assert status == 200
            assert json.loads(body)["contents"] == ["7", "8"]
            status, _ = await _get(server, "DELETE", f"/RemoveSet/{key}",
                                   tenant="alice")
            assert status == 200

    asyncio.run(go())


def test_aggregates_and_order_are_tenant_scoped():
    async def go():
        async with tenancy_stack() as (server, _):
            a_keys = [await _put(server, [v], tenant="alice")
                      for v in ("3", "5")]
            b_keys = [await _put(server, [v], tenant="bob")
                      for v in ("7", "11", "13")]
            # each tenant's SumAll folds ONLY its own records
            status, body = await _get(server, "GET", "/SumAll?position=0",
                                      tenant="alice")
            assert status == 200 and json.loads(body)["result"] == "8"
            status, body = await _get(server, "GET", "/SumAll?position=0",
                                      tenant="bob")
            assert status == 200 and json.loads(body)["result"] == "31"
            # the ordered keyset view is the tenant's own keys, nobody else's
            status, body = await _get(server, "GET", "/OrderLS?position=0",
                                      tenant="alice")
            assert status == 200
            assert set(json.loads(body)["keyset"]) == set(a_keys)
            status, body = await _get(server, "GET", "/OrderLS?position=0",
                                      tenant="bob")
            assert status == 200
            assert set(json.loads(body)["keyset"]) == set(b_keys)

    asyncio.run(go())


# ------------------------------- isolation scopes operands, not the fold


class _MeetingFoldBackend:
    """Fold backend whose folds wait for each other on a barrier, so the
    test holds two tenants' folds in flight at the same instant."""

    name = "stub-meeting"

    def __init__(self):
        self.meet = threading.Barrier(2, timeout=30)
        self.widths: list[int] = []

    def modmul_fold(self, ops, modulus):
        self.widths.append(len(ops))
        self.meet.wait()
        return math.prod(ops) % modulus


def test_two_tenants_concurrent_folds_over_one_modulus_get_their_own_sums():
    """Acceptance: tenant isolation scopes the OPERANDS — two tenants'
    folds over the same modulus, in flight together, each fold its own
    tenant's rows and nothing of the other's."""
    M = (1 << 64) + 13

    async def go():
        async with tenancy_stack() as (server, _):
            a_vals = [3, 5]
            b_vals = [7, 11, 13]
            for v in a_vals:
                await _put(server, [str(v)], tenant="alice")
            for v in b_vals:
                await _put(server, [str(v)], tenant="bob")
            stub = server.backend = _MeetingFoldBackend()
            tracer.reset()
            (st_a, body_a), (st_b, body_b) = await asyncio.gather(
                _get(server, "GET", f"/SumAll?position=0&nsqr={M}",
                     tenant="alice"),
                _get(server, "GET", f"/SumAll?position=0&nsqr={M}",
                     tenant="bob"),
            )
            assert st_a == 200 and st_b == 200
            assert json.loads(body_a)["result"] == str(math.prod(a_vals) % M)
            assert json.loads(body_b)["result"] == str(math.prod(b_vals) % M)
            # one fold a tenant, each as wide as that tenant's rows
            assert sorted(stub.widths) == [2, 3]
            spans = tracer.events("proxy.fold")
            assert sorted(e.meta.get("k") for e in spans) == [2, 3]

    asyncio.run(go())


# ------------------------------------------------- observability surfaces


def test_health_and_metrics_expose_tenant_surfaces():
    async def go():
        acfg = AdmissionConfig(enabled=True, eval_interval=1e9)
        async with tenancy_stack(acfg) as (server, _):
            key = await _put(server, ["1"], tenant="alice")
            await _put(server, ["2"], tenant="bob")
            await _get(server, "GET", f"/GetSet/{key}", tenant="bob")  # 403
            status, body = await _get(server, "GET", "/health")
            assert status == 200
            health = json.loads(body)
            assert health["tenants"] == {"owned_keys": 2, "shed": []}
            status, body = await _get(server, "GET", "/metrics")
            assert status == 200
            text = body.decode()
            assert 'dds_tenant_stored_keys{tenant="alice"} 1' in text
            assert 'dds_tenant_stored_keys{tenant="bob"} 1' in text
            assert "dds_tenant_denied_total" in text

    asyncio.run(go())


def test_chronoscope_attributes_usage_per_tenant():
    from dds_tpu.obs.chronoscope import chronoscope

    async def go():
        async with tenancy_stack() as (server, _):
            key = await _put(server, ["5"], tenant="alice")
            for _ in range(3):
                await _get(server, "GET", f"/GetSet/{key}", tenant="alice")
            await _get(server, "GET", "/SumAll?position=0", tenant="bob")

    was = chronoscope.enabled
    chronoscope.reset()
    chronoscope.enabled = True
    try:
        asyncio.run(go())
        usage = chronoscope.tenant_usage()
    finally:
        chronoscope.enabled = was
        chronoscope.reset()
    assert set(usage) >= {"alice", "bob"}
    # PutSet + 3 GetSets for alice; the lone aggregate for bob
    assert usage["alice"]["requests"] == 4
    assert usage["bob"]["requests"] == 1
    assert usage["alice"]["seconds"] > 0
    assert "GetSet" in usage["alice"]["top_routes"]
    assert "SumAll" in usage["bob"]["top_routes"]


# ----------------------------------------------- the chaos shred drill


def _drill_cfg(flight_dir: str) -> DDSConfig:
    cfg = DDSConfig()
    cfg.replicas.endpoints = [f"replica-{i}" for i in range(4)]
    cfg.replicas.sentinent = []
    cfg.replicas.byz_quorum_size = 3
    cfg.replicas.byz_max_faults = 1
    cfg.proxy.port = 0
    cfg.recovery.enabled = False
    cfg.recovery.anti_entropy_enabled = False
    cfg.obs.audit_enabled = True  # the Watchtower rides along, armed
    cfg.obs.flight_dir = flight_dir
    cfg.tenancy.enabled = True
    return cfg


def test_shred_chaos_drill_other_tenants_linearizable_zero_verdicts(tmp_path):
    """Acceptance (chaos drill): rotate then crypto-shred one tenant's
    keys in the middle of live multi-tenant traffic. Surviving tenants'
    reads and homomorphic folds stay linearizable, the shredded tenant's
    ciphertexts — still faithfully served by the keyless server — are
    permanently undecryptable with the typed refusal, and the Watchtower
    audits the whole run to ZERO verdicts."""
    import pathlib

    from dds_tpu.obs.flight import flight
    from dds_tpu.obs.watchtower import watchtower
    from dds_tpu.run import launch

    # the auditor is the process's: what an earlier test's deployment left
    # in it (the same keys under other tags) is not this run's
    watchtower.reset()
    flight_dir = str(tmp_path / "drill")
    kr = TenantKeyring(paillier_bits=512, rsa_bits=512, grace=300.0)
    plains = {"alice": [3, 14, 15], "bob": [92, 65], "victim": [35, 89, 79]}

    async def go():
        dep = await launch(_drill_cfg(flight_dir))
        server = dep.server

        stored: dict[str, list[tuple[str, int, int]]] = {}
        for tenant, values in plains.items():
            rows = []
            for m in values:
                ct, ver = kr.encrypt(tenant, m)
                key = await _put(server, [str(ct)], tenant=tenant)
                rows.append((key, ct, ver))
            stored[tenant] = rows

        async def read_back(tenant, key, want_ct):
            status, body = await _get(server, "GET", f"/GetSet/{key}",
                                      tenant=tenant)
            assert status == 200
            assert json.loads(body)["contents"] == [str(want_ct)]

        async def fold(tenant):
            n2 = kr.keys_for(tenant).psse.nsquare
            status, body = await _get(
                server, "GET", f"/SumAll?position=0&nsqr={n2}",
                tenant=tenant)
            assert status == 200
            return int(json.loads(body)["result"])

        async def churn(tenant):
            for key, ct, _ in stored[tenant]:
                await read_back(tenant, key, ct)

        # live traffic from every tenant, with the victim's key lifecycle
        # firing mid-stream: rotate (old epoch keeps decrypting inside
        # grace -> re-encrypt-on-read migrates a row), then the shred
        await asyncio.gather(churn("alice"), churn("bob"), churn("victim"))
        assert kr.rotate("victim") == 2
        k0, ct0, v0 = stored["victim"][0]
        ct_new, v_new, migrated = kr.reencrypt("victim", ct0, v0)
        assert migrated and v_new == 2
        assert kr.decrypt("victim", ct_new, v_new) == plains["victim"][0]
        await asyncio.gather(churn("alice"), churn("victim"), churn("bob"))
        assert kr.shred("victim")["epochs_scrubbed"] == 2
        await asyncio.gather(churn("alice"), churn("bob"))

        # survivors are linearizable END TO END: the served fold is the
        # homomorphic sum and still decrypts to the right plaintext
        for tenant in ("alice", "bob"):
            enc_sum = await fold(tenant)
            assert kr.decrypt(tenant, enc_sum) == sum(plains[tenant])

        # the keyless server still serves the shredded tenant's bytes —
        # deletion happened in the key domain, and it is total
        _, ct_v, v_v = stored["victim"][1]
        status, body = await _get(server, "GET",
                                  f"/GetSet/{stored['victim'][1][0]}",
                                  tenant="victim")
        assert status == 200
        assert json.loads(body)["contents"] == [str(ct_v)]
        for attempt in (lambda: kr.decrypt("victim", ct_v, v_v),
                        lambda: kr.decrypt("victim", ct_new, v_new),
                        lambda: kr.encrypt("victim", 1)):
            with pytest.raises(TenantShredded):
                attempt()

        verdicts = watchtower.verdicts()
        await dep.stop()
        return verdicts

    try:
        verdicts = asyncio.run(go())
    finally:
        flight.configure(dir="")  # launch() armed the global recorder
    assert verdicts == [], verdicts

    # the lifecycle is flight-recorded for the auditor
    index = pathlib.Path(flight_dir) / "index.jsonl"
    kinds = [json.loads(line)["kind"]
             for line in index.read_text().splitlines()]
    assert "tenant_rotate" in kinds
    assert "tenant_shred" in kinds
