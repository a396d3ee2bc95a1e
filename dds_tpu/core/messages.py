"""Wire messages for the BFT-ABD protocol, supervisor, and proxy contract.

Counterpart of the reference's three API files (`dds/api/ABDAPI.scala`,
`InternalAPI.scala`, `SupervisorAPI.scala`) and the small data models under
`dds/core/models/`. Serialization is tagged canonical JSON (language-neutral)
instead of Java/Akka serialization.

A "set" (the stored value) is a plain JSON list or None; tags order writes.
Tag ordering deviation (documented per SURVEY.md §7): the reference breaks
seq ties arbitrarily (`BFTABDNode.scala:185-188`); we order by (seq, id) —
the standard ABD total order — so write-back is deterministic.
"""

from __future__ import annotations

import base64
import json
from dataclasses import MISSING, dataclass, fields
from typing import Any, Optional

DDSSet = list  # a stored record: JSON-safe list of column values


@dataclass(frozen=True, order=True)
class ABDTag:
    seq: int
    id: str


# --------------------------------------------------------------------------
# proxy <-> replica intermediate API (InternalAPI.scala)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class IRead:
    key: str


@dataclass(frozen=True)
class IWrite:
    key: str
    set: Optional[DDSSet]


@dataclass(frozen=True)
class IReadReply:
    key: str
    set: Optional[DDSSet]
    # tag of the returned value (the write-back tag). Lets the proxy keep a
    # tag-validated aggregate cache. Covered by the proxy HMAC (tags are
    # predictable, so an unsigned tag could be swapped in transit). Cache
    # VALIDATION does not trust this field or its (single, possibly
    # Byzantine) coordinator at all — freshness comes from the proxy's own
    # quorum tag broadcast (AbdClient.read_tags), which a minority can only
    # inflate (spurious re-fetch), never deflate (stale serve); forged
    # VALUES from a Byzantine coordinator are bounded by the cache audit
    # (see http/server.py cache notes).
    tag: Optional[ABDTag] = None


@dataclass(frozen=True)
class IWriteReply:
    key: str
    tag: Optional[ABDTag] = None  # the tag the coordinator wrote (see above)


@dataclass(frozen=True)
class IReadBatch:
    """Many keys read as ONE ABD round through one coordinator (an
    aggregate's re-reads: the stale keys and the audit's sample). Per key
    it is `IRead`: the maximum-tag value of a verified quorum, stored at a
    quorum before the answer. The proxy MAC covers the key list's digest
    (`sigs.key_from_set`) and the nonce."""

    keys: tuple


@dataclass(frozen=True)
class IReadBatchReply:
    # one IReadReply per requested key, in the request's order; the
    # envelope's proxy MAC covers the keys' digest, every value and tag
    replies: tuple


@dataclass(frozen=True)
class Envelope:
    call: Any          # one of the I* messages above
    nonce: int
    signature: bytes
    # Constellation shard-map epoch the SENDER routed under (-1 =
    # unsharded). Fenced at the replica: a group that does not own the
    # key under ITS current map answers WrongShard instead of serving, so
    # a stale map can never silently misroute an op during a reshard.
    epoch: int = -1


# --------------------------------------------------------------------------
# replica <-> replica ABD protocol (ABDAPI.scala)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ReadTag:
    key: str
    nonce: int


@dataclass(frozen=True)
class TagReply:
    tag: ABDTag
    key: str
    value: Optional[DDSSet]
    signature: bytes
    nonce: int


@dataclass(frozen=True)
class Write:
    tag: ABDTag
    key: str
    value: Optional[DDSSet]
    signature: bytes
    nonce: int


@dataclass(frozen=True)
class WriteAck:
    key: str
    nonce: int


@dataclass(frozen=True)
class Read:
    key: str
    nonce: int


@dataclass(frozen=True)
class ReadTagBatch:
    """Tag-phase-only quorum read over many keys at once (no Write phase
    follows), broadcast by the PROXY itself (AbdClient.read_tags) so no
    single coordinator can deflate the max. Replies carry tags, never
    contents. `signature` is the proxy MAC over (keys-digest, nonce):
    replicas answer (and burn an anti-replay nonce) only for holders of
    the proxy secret. This is the aggregate-cache validation op the
    reference lacks — it re-reads every stored set through full ABD
    quorums per aggregate instead (`dds/http/DDSRestServer.scala:397-446`).

    The key set is NAMED by `digest` (what the MAC covers) and `count`;
    `keys` is empty unless the request teaches the set: a replica that
    holds the digest's keys answers from them, one that does not says
    `KeySetUnknown` and is sent the keys, once, under a nonce of their own."""

    keys: tuple
    nonce: int
    signature: bytes = b""
    # sha256 fingerprint of the proxy's cached tag vector for `keys` (in
    # request order). A replica whose own vector fingerprints identically
    # answers with a tiny `unchanged` reply instead of re-serializing and
    # MACing all K tags — the steady-state fast path that keeps aggregate
    # freshness validation O(1) per side when nothing was written.
    fingerprint: Optional[bytes] = None
    # shard-map epoch, same fencing contract as Envelope.epoch
    epoch: int = -1
    # fingerprint of the vector the proxy last verified FROM THE REPLICA IT
    # SENDS THIS TO (each replica gets its own, under the one nonce). A
    # replica that remembers that state of its kept vector answers with a
    # `delta` reply: the positions replaced since. None, or a base it does
    # not remember: the full reply.
    base: Optional[bytes] = None
    # `sigs.key_from_set` of the key set, in request order, and how many
    # keys it has. With `keys` empty and `count` > 0 the request is named:
    # answerable only by a replica that holds the digest's keys. ("" is a
    # frame of the schema before these fields: a replica does not answer it.)
    digest: str = ""
    count: int = 0


@dataclass(frozen=True)
class KeySetUnknown:
    """Replica -> proxy: "I hold no key set under `digest`" in answer to
    an authenticated named `ReadTagBatch` (request nonce `nonce`, which it
    spends). Signed with the intranet MAC over (digest, nonce) under a
    domain tag of its own (`sigs.abd_keyset_unknown_signature`). No vote:
    the proxy answers that sender alone with the keys."""

    digest: str
    nonce: int
    signature: bytes


@dataclass(frozen=True)
class TagBatchReply:
    # ABDTag per key in the request's order; empty if unchanged; in a delta
    # (`base` set) the tag now held at each of `positions`
    tags: tuple
    digest: str
    signature: bytes
    nonce: int
    # unchanged=True: "my tag vector fingerprints to `fingerprint`, which
    # equals the one you sent" — signature then covers (fingerprint, digest,
    # nonce) via abd_batch_unchanged_signature. A full reply (unchanged=
    # False) also carries the replica's fingerprint so the proxy can adopt
    # it for its next request.
    unchanged: bool = False
    fingerprint: Optional[bytes] = None
    # delta reply (`base` is not None): "since my vector fingerprinted to
    # `base` (the one your request named), the positions in `positions`
    # (ascending, each once) were replaced; they now hold `tags`, and the
    # vector fingerprints to `fingerprint`" — signature via
    # abd_batch_delta_signature over all of it.
    base: Optional[bytes] = None
    positions: tuple = ()


@dataclass(frozen=True)
class ReadReply:
    tag: ABDTag
    key: str
    value: Optional[DDSSet]
    signature: bytes
    nonce: int


@dataclass(frozen=True)
class BatchEntry:
    """One key's part of a batched read or write-back: `ReadReply`'s and
    `Write`'s fields less the nonce, which the batch states once.
    `signature` is `sigs.abd_signature(value, tag, nonce)` by the replica
    that reported the entry, so a write-back carries it on unchanged."""

    tag: ABDTag
    key: str
    value: Optional[DDSSet]
    signature: bytes


@dataclass(frozen=True)
class ReadBatch:
    """Coordinator -> replicas: `Read` with a key list where a key was."""

    keys: tuple
    nonce: int


@dataclass(frozen=True)
class ReadBatchReply:
    # one BatchEntry per key of the ReadBatch, in its order
    entries: tuple
    nonce: int


@dataclass(frozen=True)
class WriteBatch:
    """Coordinator -> replicas: the write-back of the keys on which a
    ReadBatch's quorum disagreed, each entry as it was reported."""

    entries: tuple
    nonce: int


@dataclass(frozen=True)
class WriteBatchAck:
    nonce: int


# --------------------------------------------------------------------------
# supervisor protocol (SupervisorAPI.scala)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Suspect:
    replica: str       # endpoint of the suspected replica
    nonce: int


@dataclass(frozen=True)
class Awake:
    """Supervisor -> spare: wake up and hand over your state. With
    `chunk_keys` > 0 the answer is streamed: `StateChunk`s of kind
    "state" under `session`, `chunk_keys` keys each and a slice of the
    nonces in every one, then a `State` that carries no data and says how
    many chunks there were. Without, the whole state as one `State` (the
    reference's form, and what `verified_transfer` off keeps)."""

    session: int = 0
    chunk_keys: int = 0


@dataclass(frozen=True)
class State:
    data: dict         # key -> {"tag": [seq, id], "value": set|None}
    nonces: list[int]
    # the streamed answer's last frame: `total` chunks went under `session`
    session: int = 0
    total: int = 0


@dataclass(frozen=True)
class Sleep:
    data: dict
    nonces: list[int]


@dataclass(frozen=True)
class Complying:
    pass


@dataclass(frozen=True)
class Kill:
    """Control message: hard-restart the replica with empty state.

    The reference uses Akka `Kill` + the guardian's restart strategy
    (`BFTSupervisor.scala:115`, `BFTSupervisorStrategy.scala:8-10`); our
    transport delivers an explicit control message the node host honors.
    """


@dataclass(frozen=True)
class Redeploy:
    """Supervisor -> node-host agent: rebuild a fresh replica at `endpoint`
    (the host owning it re-instantiates and re-registers the node). The
    TCP analogue of the reference's remote actor deployment on a dead
    host (`BFTSupervisor.scala:130-149`, RemoteScope). Authentication is
    the transport's (frame MAC / mutual TLS / node signatures), the same
    trust the in-protocol Kill/Sleep control messages ride."""

    endpoint: str


@dataclass(frozen=True)
class Redeployed:
    """Node-host agent -> supervisor: the Redeploy target is registered
    (freshly rebuilt, or found already alive — idempotent success)."""

    endpoint: str


@dataclass(frozen=True)
class RequestReplicas:
    pass


@dataclass(frozen=True)
class ActiveReplicas:
    replicas: list[str]


# --------------------------------------------------------------------------
# Aegis recovery plane: verified state transfer + Merkle anti-entropy
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class StateDigestRequest:
    """Supervisor -> replica (or spare): send your signed state manifest.
    Answered by healthy AND sentinent nodes — the supervisor cross-checks a
    quorum of manifests before any recovery seeding, and ranks spares by
    manifest freshness."""

    nonce: int


@dataclass(frozen=True)
class StateDigest:
    """Replica -> supervisor: manifest = {key: [tag.seq, tag.id,
    value-digest]} over every tracked repository entry, HMAC-signed with
    the signer address bound in (utils/sigs.manifest_signature)."""

    manifest: dict
    nonce: int
    signature: bytes


@dataclass(frozen=True)
class SleepBegin:
    """Supervisor -> recovering node: verified-reseed header. `digests` is
    the collected quorum of manifests, each `[signer, manifest, nonce,
    signature-hex]`; the node re-verifies every HMAC and accepts a seeded
    entry only when its (tag, value-digest) is attested by at least
    `support` (= f+1) distinct signers. `total` StateChunk frames follow
    (any order — transports reorder)."""

    digests: list
    session: int
    total: int
    support: int
    nonces: list


@dataclass(frozen=True)
class StateChunk:
    """One slice of the seeding state: {key: {"tag": [seq, id], "value":
    set|None}}. Chunked so a large repository streams as bounded frames
    instead of one giant Sleep (TcpNet.MAX_FRAME)."""

    session: int
    seq: int
    entries: dict
    # which ingest path owns the session: "recovery" (SleepBegin reseed,
    # replaces the repository), "migrate" (ShardMigrateBegin, merges
    # verified entries store-if-newer) or "state" (a woken spare's answer
    # to a chunked `Awake`, on its way to the supervisor). Typed so a chunk
    # that races its header can never complete the WRONG kind of session.
    kind: str = "recovery"
    # this chunk's slice of the seeder's nonce table (kinds "state" and
    # "recovery"): the table travels with the state, in bounded frames
    nonces: tuple = ()


@dataclass(frozen=True)
class MerkleRootRequest:
    nonce: int


@dataclass(frozen=True)
class MerkleRoot:
    """Anti-entropy phase 1 reply: root hash over the replica's (key ->
    tag, value-digest) index + tracked-entry count, HMAC-signed."""

    root: str
    count: int
    nonce: int
    signature: bytes


@dataclass(frozen=True)
class MerkleBucketRequest:
    nonce: int


@dataclass(frozen=True)
class MerkleBuckets:
    """Phase 2 reply: the per-bucket digest vector (hex per bucket)."""

    digests: list
    nonce: int
    signature: bytes


@dataclass(frozen=True)
class MerkleKeysRequest:
    buckets: list
    nonce: int


@dataclass(frozen=True)
class MerkleKeys:
    """Phase 3 reply: {key: [seq, id, value-digest]} for the requested
    divergent buckets — tags + digests only, values never travel here."""

    entries: dict
    nonce: int
    signature: bytes


@dataclass(frozen=True)
class RepairRequest:
    keys: list
    nonce: int


@dataclass(frozen=True)
class RepairReply:
    """Phase 4 reply: {key: {"tag": [seq, id], "value": set|None, "sig":
    hex}} where each sig is the standard ABD HMAC over (value, tag,
    nonce) — the same authenticity bar as a protocol Write, validated
    before store-if-newer."""

    entries: dict
    nonce: int


# --------------------------------------------------------------------------
# Constellation sharding plane (dds_tpu/shard)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class WrongShard:
    """Replica -> proxy: epoch fence rejection. The addressed group does
    not own `key` under the replica's current shard map (epoch `epoch`).
    `nonce` correlates: the challenge nonce for an Envelope op, the
    request nonce for a ReadTagBatch. Signed with the proxy MAC over
    (key, nonce, ["wrong-shard", epoch]) so an in-path attacker cannot
    forge fence storms that stall the router with fake refreshes."""

    key: str
    epoch: int
    nonce: int
    signature: bytes


@dataclass(frozen=True)
class ShardMigrateBegin:
    """Rebalancer -> new-group replica: verified shard-migration header.
    Same attestation frame as SleepBegin — `digests` is a quorum of
    HMAC-signed state manifests from the SOURCE group, `support` the
    distinct-signer threshold (>= f+1) — but the receiver MERGES attested
    entries store-if-newer instead of replacing its repository, stays in
    its current behavior, and only accepts entries its own shard map says
    it owns at `epoch`. `total` StateChunk(kind="migrate") frames follow."""

    digests: list
    session: int
    total: int
    support: int
    epoch: int


@dataclass(frozen=True)
class ShardMigrateAck:
    """New-group replica -> rebalancer: migration session result.
    `accepted` counts entries installed (or already held at >= the
    attested tag); `rejected` counts entries that failed the digest
    quorum or fell outside the replica's owned keyspace."""

    session: int
    accepted: int
    rejected: int


# --------------------------------------------------------------------------
# Meridian multi-host fabric control plane (dds_tpu/fabric)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ShardMapInstall:
    """Controller -> group fabric agent: install `map` (ShardMap wire
    dict) into the group's FENCING state — the cross-host freeze step of
    a live reshard. The map is HMAC-signed with the intranet secret and
    re-verified by the receiving agent, so the frame only has to be
    delivered, not trusted; `force` permits the abort path's epoch
    rollback. `lease` > 0 installs the map provisionally for that many
    seconds (shard/shardmap.ShardState fence lease): if the reshard
    driver dies before committing, the group heals back to its last
    committed map instead of staying fenced forever. Rides the
    authenticated transport like the Kill/Redeploy control messages."""

    map: dict
    force: bool
    nonce: int
    lease: float = 0.0


@dataclass(frozen=True)
class ShardMapActivate:
    """Controller -> group fabric agent: adopt `map` as the ACTIVE
    routing map this process serves at GET /shards (and fences under,
    epoch-forward). Broadcast to every group after a reshard activates so
    remote long-pollers see the bump at their next gossip wake."""

    map: dict
    nonce: int


@dataclass(frozen=True)
class ShardMapAck:
    """Agent -> controller: install/activate outcome. `epoch` is the
    agent's fencing epoch after the attempt; ok=False carries the reason
    (bad signature, backwards epoch) so the rebalancer can abort."""

    nonce: int
    epoch: int
    ok: bool
    error: str = ""


@dataclass(frozen=True)
class ShardExportRequest:
    """Controller -> agent: export replica `endpoint`'s repository as
    migration seed DATA (one ShardExport frame; receivers re-verify every
    entry against the attested manifest quorum, so this is bandwidth, not
    trust). Bounded by TcpNet.MAX_FRAME — shard/rebalance chunks the
    verified subset before streaming it to the target group."""

    endpoint: str
    nonce: int


@dataclass(frozen=True)
class ShardExport:
    nonce: int
    entries: dict


@dataclass(frozen=True)
class ShardPruneRequest:
    """Controller -> agent: drop repository entries the group no longer
    owns under its CURRENT fencing map (post-activation cleanup)."""

    nonce: int


@dataclass(frozen=True)
class ShardPruned:
    nonce: int
    dropped: int


# --------------------------------------------------------------------------
# Atlas geo plane: read leases + region-local reads (dds_tpu/geo)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class LeaseRequest:
    """Proxy -> the replica homed in the proxy's region: grant (or renew)
    the region's read lease on yourself for `ttl` seconds. Signed with
    the ABD MAC over the (region, ttl) manifest so only quorum members /
    secret holders can move the group into pinned-quorum geometry (a
    forged grant would be a free availability attack: every quorum
    would wait on the forger's chosen replica)."""

    region: str
    ttl: float
    nonce: int
    signature: bytes


@dataclass(frozen=True)
class LeaseGrant:
    """Replica -> proxy: the lease is installed in the group's shared
    LeaseTable (ok=True) or refused (ok=False: no table wired, or the
    replica is not this region's designated holder). `token` is the
    table-minted HMAC capability LocalRead must echo; `expires` is in
    the GRANTING side's clock — the proxy derives its own renew horizon
    from `ttl` it requested, never from a remote clock."""

    region: str
    replica: str
    token: str
    expires: float
    ok: bool
    nonce: int
    signature: bytes


@dataclass(frozen=True)
class LeaseRevoke:
    """Admin/supervisor -> any group replica: drop `region`'s lease from
    the shared table. Same manifest-MAC bar as LeaseRequest. The current
    holder finds out the hard way (its next LocalRead is refused), which
    is exactly the fallback path the client must survive anyway."""

    region: str
    nonce: int
    signature: bytes


@dataclass(frozen=True)
class LocalRead:
    """Proxy -> lease-holding replica: answer `key` from local state
    under the lease capability `token` — no quorum round. Only valid
    while the table says (region, replica, token) is the active lease;
    anything else is refused with ok=False so the proxy falls back to a
    full cross-region quorum read instead of timing out."""

    key: str
    region: str
    token: str
    nonce: int
    signature: bytes
    epoch: int = -1


@dataclass(frozen=True)
class LocalReadReply:
    tag: Optional[ABDTag]
    key: str
    value: Optional[DDSSet]
    ok: bool
    nonce: int
    signature: bytes


# --------------------------------------------------------------------------
# Panopticon fleet telemetry (dds_tpu/obs/panopticon)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class TelemetryBatch:
    """Shipper -> collector: one batch of fleet telemetry from a non-proxy
    process. `spans` is a list of completed span trees (each a list of
    `utils.trace.event_dict` dicts), `incidents` flight-recorder index
    entries, `metrics_text` the source's full Prometheus exposition, and
    `slo` its SloEngine report. `mac` is HMAC-SHA256 over the canonical
    JSON of the payload with the fleet telemetry secret — an extra
    integrity layer above the frame MAC, so a collector can accept
    batches relayed through untrusted hops. Integrity only: a Byzantine
    HOST can still sign lies about its own stats (DEPLOY.md "Fleet
    observability"). The list/dict fields ride opaque on purpose — span
    meta is workload-derived and must never decode as protocol objects."""

    host: str
    role: str
    shard: str
    seq: int
    ts: float
    spans: list
    incidents: list
    metrics_text: str
    slo: dict
    dropped: int          # spool drops at the SOURCE since process start
    mac: bytes
    # Atlas region label of the shipping process ("" = unplaced). Covered
    # by the payload MAC like every other field; the collector surfaces
    # it on federated metrics and incident correlation.
    region: str = ""


@dataclass(frozen=True)
class TelemetryAck:
    """Collector -> shipper: batch `seq` landed (ok=False = bad MAC or
    malformed — the shipper counts rejects but never retries a reject:
    a batch the collector refuses once will be refused again)."""

    seq: int
    ok: bool
    error: str = ""


@dataclass(frozen=True)
class CountersRequest:
    """Launcher -> a replica process's node-host agent: send your protocol
    counters (`transport.replica_processes`, dds_tpu/hosts.py)."""


@dataclass(frozen=True)
class Counters:
    """Node-host agent -> launcher: every series of the families in
    `obs.metrics.PROTOCOL_FAMILIES` as this process has counted it since
    it started, `[name, help, labels, value]` each. Cumulative, so a lost
    report costs nothing: the launcher adds what grew since the last one.
    Rides the authenticated transport like Redeploy; a process can lie
    about its own counts and about nothing else."""

    samples: list


# --------------------------------------------------------------------------
# fault injection backdoor (malicious/MaliciousAttack.scala:34)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Compromise:
    pass


@dataclass(frozen=True)
class Crash:
    """Fault-injection control: the node tears its endpoint off the
    transport and goes silent — the PoisonPill analogue that also works
    across the TCP fabric (the reference's Trudy holds in-process
    ActorRefs, `Trudy.scala:14-32`). A harness backdoor like Compromise,
    not a production message."""


# --------------------------------------------------------------------------
# serialization: tagged canonical JSON
# --------------------------------------------------------------------------

_TYPES = {
    cls.__name__: cls
    for cls in (
        IRead, IWrite, IReadReply, IWriteReply, Envelope,
        IReadBatch, IReadBatchReply,
        ReadTag, TagReply, Write, WriteAck, Read, ReadReply,
        BatchEntry, ReadBatch, ReadBatchReply, WriteBatch, WriteBatchAck,
        ReadTagBatch, TagBatchReply, KeySetUnknown,
        Suspect, Awake, State, Sleep, Complying, Kill,
        Redeploy, Redeployed, RequestReplicas, ActiveReplicas, Compromise,
        Crash,
        StateDigestRequest, StateDigest, SleepBegin, StateChunk,
        MerkleRootRequest, MerkleRoot, MerkleBucketRequest, MerkleBuckets,
        MerkleKeysRequest, MerkleKeys, RepairRequest, RepairReply,
        WrongShard, ShardMigrateBegin, ShardMigrateAck,
        ShardMapInstall, ShardMapActivate, ShardMapAck,
        ShardExportRequest, ShardExport, ShardPruneRequest, ShardPruned,
        LeaseRequest, LeaseGrant, LeaseRevoke, LocalRead, LocalReadReply,
        TelemetryBatch, TelemetryAck, CountersRequest, Counters,
    )
}


_CLASSES = frozenset(_TYPES.values())
# what JSON carries as it is: the bulk of a key tuple or a positions vector
_PLAIN = frozenset({str, int, float, bool, type(None), list, dict})


def _enc(v):
    if type(v) in _PLAIN:
        return v
    if isinstance(v, bytes):
        return {"__b64__": base64.b64encode(v).decode()}
    if isinstance(v, ABDTag):
        return {"__tag__": [v.seq, v.id]}
    if type(v) in _CLASSES:
        return to_dict(v)
    return v


def _dec(v):
    if isinstance(v, dict):
        if "__b64__" in v:
            return base64.b64decode(v["__b64__"])
        if "__tag__" in v:
            return ABDTag(int(v["__tag__"][0]), str(v["__tag__"][1]))
        if "__msg__" in v:
            return from_dict(v)
    return v


def to_dict(msg) -> dict:
    # element-wise coding applies ONLY to the tuple-typed protocol fields
    # (tag vectors / key tuples of the batch messages). Stored set contents
    # (list fields) stay opaque: recursing into them would let a crafted
    # client column value (e.g. {"__msg__": ...}) be (de)coded as a protocol
    # object inside the receive path, before any MAC validation.
    d = {"__msg__": type(msg).__name__}
    for f in fields(msg):
        v = getattr(msg, f.name)
        if f.type == "tuple" and isinstance(v, (list, tuple)):
            d[f.name] = [_enc(x) for x in v]
        else:
            d[f.name] = _enc(v)
    return d


def from_dict(d: dict):
    cls = _TYPES[d["__msg__"]]
    kwargs = {}
    for f in fields(cls):
        if f.name not in d and f.default is not MISSING:
            continue  # written by a version that lacked the field
        v = d[f.name]
        if f.type == "tuple" and isinstance(v, list):  # JSON has no tuples
            v = tuple(_dec(x) for x in v)
        else:
            v = _dec(v)
        kwargs[f.name] = v
    return cls(**kwargs)


def dumps(msg) -> bytes:
    return json.dumps(to_dict(msg), separators=(",", ":")).encode()


def loads(raw: bytes):
    return from_dict(json.loads(raw))
