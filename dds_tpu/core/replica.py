"""BFT-ABD replica: quorum-replicated register with HMAC auth + anti-replay.

Counterpart of `dds/core/BFTABDNode.scala` — same three behaviors
(healthy / sentinent / byzantine), same two-phase quorum protocol, same
suspicion triggers — re-expressed as a plain async message handler over the
`core.transport` fabric instead of an Akka actor.

Protocol summary (healthy):
- proxy `Envelope(IWrite)` -> broadcast `ReadTag`; on quorum of `TagReply`
  take max tag, bump seq, broadcast `Write`; on quorum of `WriteAck` answer
  the proxy with `IWriteReply` under challenge nonce = client nonce + inc.
- proxy `Envelope(IRead)` -> broadcast `Read`; on quorum of `ReadReply`
  take max (tag, value, signature), broadcast write-back `Write` with the
  *original* signature; on quorum of `WriteAck` answer `IReadReply`.
- proxy `Envelope(IReadBatch)` -> the same read over a key list, as one
  round: broadcast `ReadBatch`; on quorum of `ReadBatchReply` take each
  key's max entry; the keys on which the quorum disagreed are written back
  in one `WriteBatch` (original signatures), and on quorum of
  `WriteBatchAck` (or at once, when every key was settled) answer
  `IReadBatchReply`. An aggregate's re-reads come this way.
- every inbound protocol message is HMAC-verified and nonce-replay-checked;
  violations raise `Suspect` votes to the supervisor
  (`BFTABDNode.scala:137,158,165,212,219,250,298,319,326`).

Deviations (documented per SURVEY.md §7): tags order by (seq, id) rather
than seq-with-arbitrary-tie-break; the ABD HMAC signs the true `tag.seq`
(reference signs `seq + 1`, `Utils.scala:33`).
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from dataclasses import dataclass, field

from dds_tpu.core import messages as M
from dds_tpu.core.antientropy import AntiEntropy, MerkleIndex
from dds_tpu.core.transport import Transport
from dds_tpu.obs.flight import flight
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils import sigs
from dds_tpu.utils.trace import tracer
from dds_tpu.utils.trust import TrustedNodesList

log = logging.getLogger("dds.replica")


@dataclass
class ReplicaConfig:
    quorum_size: int = 5
    nonce_increment: int = 1
    abd_mac_secret: bytes = b"intranet-abd-secret"
    proxy_mac_secret: bytes = b"rest2abd"
    debug: bool = False
    # honor the Crash/Compromise fault-injection backdoors. True is the
    # harness default (tests drive faults directly); deployments built by
    # run.launch() set it from `attacks.enabled`, so a production config
    # without attack simulation ignores injected faults entirely — one
    # credentialed peer must not be able to kill replicas past f.
    allow_fault_injection: bool = True


@dataclass
class _Outgoing:
    client: str
    call: object
    client_nonce: int
    expired: bool = False
    # sender -> (tag, value, signature). The reference accumulates a set of
    # reply *tuples* (`OutgoingRequestState.scala:14`), which counts
    # duplicate replies from one replica as distinct quorum votes (JVM
    # byte-array identity equality) — a replay could forge a quorum. We key
    # by sender, like its write quorum already does.
    read_quorum: dict = field(default_factory=dict)
    write_quorum: set = field(default_factory=set)
    set_to_read: object = None
    set_to_write: object = None
    tag_to_reply: object = None  # tag returned to the proxy (read max / written)


# key sets whose tag vector a replica keeps (one per distinct aggregate
# key set a proxy revalidates); past it the oldest goes
MAX_TAG_VECTORS = 8
# stores a kept vector may trail before it is dropped instead of patched:
# a key set nobody asked about for that long is cheaper built anew, and
# the log of stored keys stays bounded
MAX_TAG_VECTOR_LAG = 1 << 16
# a kept vector remembers the positions it replaced, for delta replies,
# until they outnumber a quarter of its keys (a delta that long is no
# cheaper than the full reply); a small key set keeps this many
MIN_DELTA_HISTORY = 64


class _TagVector:
    """What the ReadTagBatch reply for one key set is made of, kept across
    repository changes and patched by the keys stored since: the keys'
    digest and the keys it stands for (a named request carries the digest
    alone), key -> position, the tag per position and its
    `sigs.tag_field`, the joined blob the MAC covers and its fingerprint.
    `seen` is how far into the replica's log of stored keys the vector has
    been brought.

    For delta replies it also keeps `moved`, the positions it replaced, in
    order, and `marks`: the fingerprint of each state it sealed -> how far
    `moved` had got (counted from the first position ever replaced;
    `trimmed` of them are gone from the front). A proxy that names a
    remembered fingerprint as its base is owed the positions after that
    mark. Equal fingerprints are equal vectors, so the newest mark of a
    fingerprint serves."""

    __slots__ = ("digest", "keys", "index", "tags", "fields", "reply_tags",
                 "blob", "fingerprint", "seen", "moved", "marks", "trimmed")

    def __init__(self, keys: tuple, digest: str, repository: dict,
                 blank: tuple, seen: int):
        self.digest = digest
        self.keys = keys
        self.index = {k: i for i, k in enumerate(keys)}
        # read without materializing default entries in the repository
        self.tags = [repository.get(k, blank)[0] for k in keys]
        self.fields = [sigs.tag_field(t) for t in self.tags]
        self.seen = seen
        self.moved: list[int] = []
        self.marks: dict[bytes, int] = {}
        self.trimmed = 0
        self.seal()

    def seal(self) -> None:
        """One copy, one join, one hash: the reply's parts of the tags and
        fields as they now stand, and the mark a later delta starts from."""
        self.reply_tags = tuple(self.tags)
        self.blob = sigs.fields_blob(self.fields)
        self.fingerprint = sigs.blob_fingerprint(self.blob)
        self.marks[self.fingerprint] = self.trimmed + len(self.moved)

    def patch(self, stored: list, repository: dict) -> int:
        """Take in the keys `stored` since `seen` (the log's tail): replace
        the tag and field of those in the set whose tag moved, then seal
        once. Returns how many were replaced."""
        index, tags, fields = self.index, self.tags, self.fields
        moved = self.moved
        changed = 0
        for key in stored:
            i = index.get(key)
            if i is not None and tags[i] is not (tag := repository[key][0]):
                tags[i] = tag
                fields[i] = sigs.tag_field(tag)
                moved.append(i)
                changed += 1
        self.seen += len(stored)
        if changed:
            self.seal()
            keep = max(len(tags) // 4, MIN_DELTA_HISTORY)
            if len(moved) > keep:
                # forget the older half: a base from before it is owed
                # the full reply
                cut = len(moved) - keep // 2
                del moved[:cut]
                self.trimmed += cut
                self.marks = {fp: at for fp, at in self.marks.items()
                              if at >= self.trimmed}
        return changed

    def delta_since(self, base) -> tuple | None:
        """The positions replaced since the state that fingerprinted to
        `base`, ascending and each once; None when that state is not
        remembered (never sealed here, or trimmed away)."""
        at = self.marks.get(base) if isinstance(base, bytes) else None
        if at is None:
            return None
        return tuple(sorted(set(self.moved[at - self.trimmed:])))


class _Reseed:
    """One verified reseed in flight at the replica being put to sleep:
    the header, the chunks that came before the manifests were settled,
    and what the chunks taken in so far have built: the repository to be,
    its Merkle index beside it, the entries refused, the nonces."""

    __slots__ = ("begin", "sender", "verified", "early", "seen",
                 "repository", "merkle", "rejected", "nonces")

    def __init__(self):
        self.begin: M.SleepBegin | None = None
        self.sender: str | None = None
        self.verified: dict | None = None   # settled manifests, once known
        self.early: dict[int, M.StateChunk] = {}
        self.seen: set[int] = set()
        self.repository: dict[str, tuple] = {}
        self.merkle = MerkleIndex()
        self.rejected: list[str] = []
        self.nonces: list[int] = []


class BFTABDNode:
    """One replica endpoint. `addr` must appear in `replicas`."""

    def __init__(
        self,
        addr: str,
        replicas: list[str],
        supervisor: str,
        net: Transport,
        config: ReplicaConfig | None = None,
        shard=None,
    ):
        self.addr = addr
        self.name = addr.rsplit("/", 1)[-1]
        self.all_replicas = list(replicas)
        self.supervisor = supervisor
        self.net = net
        self.cfg = config or ReplicaConfig()
        self.behavior = "healthy"
        # monotonic floor for tags this coordinator mints: two concurrent
        # writes coordinated here could otherwise read the same quorum-max
        # and mint the SAME (seq+1, self) tag for different values (latent
        # in the reference too, `BFTABDNode.scala:194`); the floor keeps
        # locally-minted tags unique. Deviation documented per SURVEY.md §7.
        self._seq_floor = 0
        self.repository: dict[str, tuple[M.ABDTag, object]] = {}
        self.outgoing: dict[int, _Outgoing] = {}
        self.incoming: dict[int, bool] = {}  # nonce -> expired
        self.siblings = TrustedNodesList(replicas)
        # bumped on every observable repository change (stored Write, Sleep
        # reseed, Kill wipe, snapshot restore)
        self.repo_version = 0
        # keys digest -> _TagVector: what each key set's ReadTagBatch reply
        # is made of, and the keys the digest stands for, kept across stored
        # writes. What a digest names is a function of the keys alone and
        # could outlive a reseed; it is kept IN the vector and goes with it
        # all the same (one table, one bound, and a reseed, wipe or restore
        # costs each key set one `KeySetUnknown` and one carried request,
        # next to a transfer of the whole state). `_store` logs the key it
        # changed (`_stored_since`, only while a vector is kept) and a round
        # patches its vector by the keys logged since it last looked:
        # O(keys written since) per round, nothing when none was. The
        # vectors answer for `_vector_version`, which moves with
        # `repo_version` on every change that names its key; a change that
        # names none (reseed, wipe, prune, a bare `repo_version` bump) leaves
        # it behind, and the vectors are dropped before they are next read
        self._tag_vectors: dict[str, _TagVector] = {}
        self._stored_since: list[str] = []
        self._vector_version = 0
        # Aegis: incremental (key -> tag, value-digest) hash index — the
        # source of StateDigest manifests and the anti-entropy tree
        self.merkle = MerkleIndex()
        # per-replica sync agent; run.launch (or a test) starts its loop
        self.antientropy = AntiEntropy(self)
        # verified-reseed sessions in flight: session -> _Reseed
        # (SleepBegin and StateChunks may arrive in any order)
        self._recovery_sessions: dict[int, _Reseed] = {}
        # keys stored since a `Kill` emptied this replica, until the
        # reseed that follows is whole (it keeps them); None otherwise
        self._since_kill: set[str] | None = None
        # Constellation: the group's shared fencing state (shard.ShardState
        # duck-type: group_id / epoch / owns(key)). None = unsharded, no
        # fencing. Shard-migration sessions buffer separately from
        # recovery reseeds — completing one must never replace the
        # repository or flip behavior.
        self.shard = shard
        self._migrate_sessions: dict[int, dict] = {}
        # last snapshot save/load bookkeeping (core/snapshot fills it;
        # exported via /health + scrape-time gauges)
        self.snapshot_meta: dict = {}
        # Atlas read-lease geometry: the group's shared geo.LeaseTable
        # (None = leases off). While any lease is active, every quorum
        # this coordinator closes must include the holders — that is the
        # whole safety argument for region-local reads (dds_tpu/geo).
        self.lease_table = None
        net.register(addr, self.handle)

    # ------------------------------------------------------------------ util

    def _state(self, key: str) -> tuple[M.ABDTag, object]:
        if key not in self.repository:
            self.repository[key] = (M.ABDTag(0, self.name), None)
        return self.repository[key]

    def _send(self, dest: str, msg) -> None:
        self.net.send(self.addr, dest, msg)

    def _suspect(self, endpoint: str, **why) -> None:
        tracer.event("replica.suspect", by=self.name, suspect=endpoint, **why)
        metrics.inc(
            "dds_suspect_votes_total", suspect=endpoint.rsplit("/", 1)[-1],
            help="Suspect votes raised toward the supervisor",
        )
        self._send(self.supervisor, M.Suspect(endpoint, sigs.generate_nonce()))

    def _reject(self, sender: str, msg, reason: str, text: str,
                suspect: bool = True) -> None:
        """This replica, healthy, refuses `msg`: one count a message, by
        `reason` (bad_mac | unknown_nonce | repeated_nonce | wrong_phase).
        A refusal that is evidence against an authenticated peer also
        votes `sender` suspect, and the vote's event names the message
        class and the reason; one that could come from anybody (a bad
        proxy MAC) is counted and nothing more."""
        self._debug(text)
        metrics.inc(
            "dds_replica_rejected_total", reason=reason,
            help="messages a healthy replica refused, by reason",
        )
        if suspect:
            self._suspect(sender, msg=type(msg).__name__, reason=reason)

    def _debug(self, text: str) -> None:
        if self.cfg.debug:
            log.info("%s: %s", self.name, text)

    def _broadcast(self, msg) -> None:
        for sibling in self.siblings.get_trusted():
            self._send(sibling, msg)

    def _store(self, key: str, tag: M.ABDTag, value) -> None:
        """The ONLY place stored tags change: bump the version and name
        the key, so that kept tag vectors patch it in before their next
        reply."""
        self.repository[key] = (tag, value)
        self.repo_version += 1
        self._vector_version += 1
        if self._tag_vectors:
            self._stored_since.append(key)
        if self._since_kill is not None:
            self._since_kill.add(key)
        self.merkle.update(key, tag, value)

    def _drop_tag_vectors(self) -> None:
        """The repository changed in a way that names no key: nothing kept
        describes it. The next round of each key set builds anew, from
        keys the proxy is asked for again (`KeySetUnknown`)."""
        self._tag_vectors.clear()
        self._stored_since.clear()
        self._vector_version = self.repo_version

    def _install_repository(self, repository: dict,
                            merkle: MerkleIndex | None = None) -> None:
        """Replace the whole repository (reseed / snapshot restore): bump
        the version, drop the kept tag vectors, and take `merkle` as its
        index where the caller built one beside it, else rebuild it."""
        self.repository = repository
        self.repo_version += 1
        self._drop_tag_vectors()
        if merkle is None:
            self.merkle.rebuild(repository)
        else:
            self.merkle = merkle

    @property
    def reseeding(self) -> bool:
        """Between a `Kill` and the last chunk of the reseed it opens."""
        return self._since_kill is not None

    def _wipe(self) -> None:
        self.repository = {}
        self.outgoing = {}
        self.incoming = {}
        self.repo_version += 1
        self._drop_tag_vectors()
        self.merkle.rebuild({})
        self._recovery_sessions.clear()
        self._since_kill = set()

    def _quorum_met(self, responders) -> bool:
        """Quorum gate for the rounds this coordinator closes. Plain
        `>= quorum_size` — except while read leases are out, when the
        quorum must ALSO contain every active holder: a leased replica
        then stores each acked write (and each fast-path-readable value)
        before the round completes, so its local reads can never trail an
        acked cross-region write. A dead holder stalls rounds at most one
        lease TTL (expiry drops it from `holders()`)."""
        if len(responders) < self.cfg.quorum_size:
            return False
        if self.lease_table is None:
            return True
        holders = self.lease_table.holders()
        if not holders:
            return True
        names = {s.rsplit("/", 1)[-1] for s in responders}
        return holders <= names

    def _entries_verify(self, entries, nonce: int) -> bool:
        """Every entry of a batch is a `BatchEntry` under a valid ABD
        signature for `nonce`: `ReadReply`'s and `Write`'s check, per
        entry. One bad entry refuses the message."""
        secret = self.cfg.abd_mac_secret
        return isinstance(entries, tuple) and all(
            isinstance(e, M.BatchEntry) and isinstance(e.key, str)
            and isinstance(e.tag, M.ABDTag)
            and sigs.validate_abd_signature(
                secret, e.value, e.tag, nonce, e.signature)
            for e in entries
        )

    def _answer_read_batch(self, req: _Outgoing) -> None:
        """The batched read is complete (every key settled, or the
        write-back at a quorum): one `IReadBatchReply` under the challenge
        nonce, its proxy MAC over the keys' digest, every value and tag."""
        cfg = self.cfg
        req.expired = True
        challenge = req.client_nonce + cfg.nonce_increment
        best = req.set_to_read
        sig = sigs.proxy_signature(
            cfg.proxy_mac_secret, sigs.key_from_set(list(req.call.keys)),
            challenge, [[e.value, sigs.tag_payload(e.tag)] for e in best],
        )
        self._send(req.client, M.Envelope(
            M.IReadBatchReply(tuple(
                M.IReadReply(e.key, e.value, tag=e.tag) for e in best)),
            challenge, sig,
        ))

    def _shard_fenced(self, key: str) -> bool:
        """True when this group must NOT serve `key` under its current
        shard map (Constellation epoch fencing). Unsharded nodes never
        fence."""
        return self.shard is not None and not self.shard.owns(key)

    def _note_storage_fence(self, what: str, key: str) -> None:
        """A write (`what`: the message class) reached storage for a key
        this group does not own: counted and traced, never stored."""
        metrics.inc(
            "dds_shard_fenced_total", shard=str(self.shard.group_id),
            msg=what,
            help="requests fenced for keys outside the group's shard map",
        )
        tracer.event("shard.fence", replica=self.name, key=key,
                     epoch=self.shard.epoch, msg=what)

    def _reply_wrong_shard(self, dest: str, key: str, nonce: int,
                           sent_epoch: int, what: str) -> None:
        """Typed, signed fence rejection: tells the proxy its map is
        stale (or a reshard is in flight) so it refreshes and re-routes
        under its existing Deadline budget — the no-silent-misroutes leg
        of a live reshard."""
        epoch = self.shard.epoch
        sig = sigs.proxy_signature(
            self.cfg.proxy_mac_secret, key, nonce, ["wrong-shard", epoch]
        )
        metrics.inc(
            "dds_shard_fenced_total", shard=str(self.shard.group_id),
            msg=what,
            help="requests fenced for keys outside the group's shard map",
        )
        tracer.event("shard.fence", replica=self.name, key=key,
                     epoch=epoch, sent_epoch=sent_epoch, msg=what)
        self._send(dest, M.WrongShard(key, epoch, nonce, sig))

    @staticmethod
    def _count_keyset(outcome: str) -> None:
        metrics.inc(
            "dds_replica_keyset_total", outcome=outcome,
            help="ReadTagBatch requests by what the replica held of the "
                 "named key set (refused: carried keys of another digest)",
        )

    def _tag_vector(self, keys: tuple, vec: _TagVector | None,
                    digest: str) -> tuple[_TagVector, str, int]:
        """(vector, outcome, tags replaced) for an AUTHENTICATED
        ReadTagBatch: `vec`, the vector the probe found kept under this
        digest (None when it found none, and `keys` are then the request's
        own, hashed to the digest), brought up to the repository.
        `reused` when no key of the set was stored since it last looked,
        `patched` when those that were had their tag and field replaced
        (and one join and one hash redone), `rebuilt` when there was none
        to patch: a first round for the key set, or one after a change
        that named no key."""
        if self._vector_version != self.repo_version:
            self._drop_tag_vectors()
            vec = None
        log = self._stored_since
        if vec is None:
            while len(self._tag_vectors) >= MAX_TAG_VECTORS:
                del self._tag_vectors[next(iter(self._tag_vectors))]
            vec = self._tag_vectors[digest] = _TagVector(
                keys, digest, self.repository,
                (M.ABDTag(0, self.name), None), len(log),
            )
            return vec, "rebuilt", 0
        if vec.seen == len(log):
            return vec, "reused", 0
        changed = vec.patch(log[vec.seen:], self.repository)
        # the log is needed only as far back as the vector furthest behind;
        # one that fell too far behind goes instead of holding it
        behind = [d for d, v in self._tag_vectors.items()
                  if v.seen < len(log)]
        if not behind or len(log) > MAX_TAG_VECTOR_LAG:
            for d in behind:
                del self._tag_vectors[d]
            log.clear()
            for v in self._tag_vectors.values():
                v.seen = 0
        return vec, "patched" if changed else "reused", changed

    # ------------------------------------------------------------- dispatch

    async def handle(self, sender: str, msg) -> None:
        # Per-replica span: the message arrived in a task whose contextvars
        # were copied at send time (InMemoryNet) or restored from the
        # frame's `tc` field (TcpNet), so this span slots into the
        # originating request's trace tree — the per-replica attribution a
        # process-global ring could never give. `replica` meta identifies
        # WHICH replica served each quorum leg.
        meta = {
            "replica": self.name, "msg": type(msg).__name__,
            "behavior": self.behavior,
        }
        # per-key attribution where the protocol message names one: lets
        # the Watchtower auditor (and a human reading an incident) tie a
        # phase participant to the record it touched
        key = getattr(msg, "key", None)
        if isinstance(key, str):
            meta["key"] = key
        with tracer.span("replica.handle", **meta):
            await self._dispatch(sender, msg)

    async def _dispatch(self, sender: str, msg) -> None:
        if isinstance(msg, (M.Crash, M.Compromise)):
            # fault-injection backdoors (Trudy): honored only when the
            # deployment enables attack simulation
            if not self.cfg.allow_fault_injection:
                self._debug(f"ignoring injected {type(msg).__name__}")
                return
            if isinstance(msg, M.Crash):
                self.net.unregister(self.addr)  # go silent, any behavior
                return
        if self.behavior == "healthy":
            await self._healthy(sender, msg)
        elif self.behavior == "sentinent":
            await self._sentinent(sender, msg)
        else:
            await self._byzantine(sender, msg)

    # -------------------------------------------------------------- healthy

    async def _healthy(self, sender: str, msg) -> None:
        cfg = self.cfg
        match msg:
            case M.Envelope(call, nonce, signature):
                if nonce in self.outgoing:
                    self._reject(sender, msg, "repeated_nonce",
                                 "invalid nonce from proxy - repeated",
                                 suspect=False)
                    return
                req = _Outgoing(sender, call, nonce)
                match call:
                    case M.IRead(key):
                        if not sigs.validate_proxy_signature(
                            cfg.proxy_mac_secret, key, nonce, signature
                        ):
                            self._reject(sender, msg, "bad_mac",
                                         "invalid proxy signature",
                                         suspect=False)
                        elif self._shard_fenced(key):
                            # fence AFTER authentication (an unauthenticated
                            # probe must not learn the keyspace layout) and
                            # burn the request so a replay cannot re-ask
                            req.expired = True
                            self._reply_wrong_shard(
                                sender, key, nonce + cfg.nonce_increment,
                                msg.epoch, "IRead",
                            )
                        else:
                            self._broadcast(M.Read(key, nonce))
                    case M.IReadBatch(keys):
                        if not (
                            isinstance(keys, tuple)
                            and all(isinstance(k, str) for k in keys)
                            and sigs.validate_proxy_signature(
                                cfg.proxy_mac_secret,
                                sigs.key_from_set(list(keys)), nonce,
                                signature)
                        ):
                            self._reject(sender, msg, "bad_mac",
                                         "invalid proxy signature (read batch)",
                                         suspect=False)
                        elif (bad := next(
                            (k for k in keys if self._shard_fenced(k)), None
                        )) is not None:
                            req.expired = True
                            self._reply_wrong_shard(
                                sender, bad, nonce + cfg.nonce_increment,
                                msg.epoch, "IReadBatch",
                            )
                        else:
                            self._broadcast(M.ReadBatch(keys, nonce))
                    case M.IWrite(key, value):
                        if not sigs.validate_proxy_signature(
                            cfg.proxy_mac_secret, key, nonce, signature, value
                        ):
                            self._reject(sender, msg, "bad_mac",
                                         "invalid proxy signature",
                                         suspect=False)
                        elif self._shard_fenced(key):
                            req.expired = True
                            self._reply_wrong_shard(
                                sender, key, nonce + cfg.nonce_increment,
                                msg.epoch, "IWrite",
                            )
                        else:
                            req.set_to_write = value
                            self._broadcast(M.ReadTag(key, nonce))
                    case _:
                        log.error("unexpected API call from proxy: %r", call)
                self.outgoing[nonce] = req

            case M.ReadTag(key, nonce):
                if nonce in self.incoming:
                    self._reject(sender, msg, "repeated_nonce",
                                 "invalid nonce - repeated")
                    return
                self.incoming[nonce] = False
                tag, contents = self._state(key)
                sig = sigs.abd_signature(cfg.abd_mac_secret, contents, tag, nonce)
                self._send(sender, M.TagReply(tag, key, contents, sig, nonce))

            case M.ReadTagBatch(keys, nonce, psig, pfp):
                # sent straight by the proxy (AbdClient.read_tags), not by a
                # coordinator: authenticate the request BEFORE burning an
                # anti-replay nonce, or unauthenticated traffic could both
                # enumerate tags (write-activity oracle) and grow the nonce
                # set without bound. The request names its key set by the
                # digest its MAC covers; the kept vectors are PROBED
                # read-only by it (one string's hash, no key is hashed or
                # compared) and only built, patched or evicted after the
                # MAC verifies — pre-auth traffic must not be able to evict
                # the hot vector, grow the table or teach it a key set
                digest = msg.digest
                if not digest or not isinstance(digest, str):
                    return   # the schema before `digest`: no name, no answer
                kept = self._tag_vectors.get(digest)
                if not sigs.validate_proxy_signature(
                    cfg.proxy_mac_secret, digest, nonce, psig
                ):
                    self._reject(sender, msg, "bad_mac",
                                 "invalid proxy signature (tag batch)",
                                 suspect=False)
                    return
                if nonce in self.incoming:
                    self._reject(sender, msg, "repeated_nonce",
                                 "invalid nonce - repeated (tag batch)")
                    return
                if kept is not None:
                    # whatever keys the request carried, the digest's own
                    # are the ones kept: hashed once, when they were learned
                    keys, held = kept.keys, "known"
                elif not keys and msg.count > 0:
                    # named, and nothing held under the name (never taught,
                    # evicted, or dropped with a reseed): say so under this
                    # replica's MAC and spend the nonce; the proxy answers
                    # with the keys under a nonce of their own
                    self.incoming[nonce] = True
                    self._count_keyset("unknown")
                    self._send(sender, M.KeySetUnknown(
                        digest, nonce, sigs.abd_keyset_unknown_signature(
                            cfg.abd_mac_secret, digest, nonce)))
                    return
                else:
                    keys, held = tuple(keys), "learned"
                    if sigs.key_from_set(list(keys)) != digest:
                        # keys that are not the named set's: refused like a
                        # bad signature, nothing burnt, built or evicted
                        self._debug("carried keys do not hash to the digest")
                        self._count_keyset("refused")
                        return
                self._count_keyset(held)
                if self.shard is not None:
                    bad = next(
                        (k for k in keys if self._shard_fenced(k)), None
                    )
                    if bad is not None:
                        # batch replies correlate by the REQUEST nonce
                        self.incoming[nonce] = True
                        self._reply_wrong_shard(
                            sender, bad, nonce, msg.epoch, "ReadTagBatch"
                        )
                        return
                t0 = time.perf_counter()
                vec, outcome, changed = self._tag_vector(keys, kept, digest)
                fp = vec.fingerprint
                # tag-only phase: no Write follows, so the nonce is spent now
                self.incoming[nonce] = True
                if pfp is not None and pfp == fp:
                    # steady-state fast path: assert vector equality by
                    # fingerprint instead of shipping/MACing all K tags
                    reply = M.TagBatchReply(
                        (), digest,
                        sigs.abd_batch_unchanged_signature(
                            cfg.abd_mac_secret, fp, digest, nonce),
                        nonce, unchanged=True, fingerprint=fp,
                    )
                elif (at := vec.delta_since(msg.base)) is not None:
                    # the proxy holds this replica's vector as of `base`:
                    # ship and MAC the positions replaced since, from the
                    # kept tags and fields
                    tags, fields = vec.tags, vec.fields
                    reply = M.TagBatchReply(
                        tuple([tags[i] for i in at]), digest,
                        sigs.abd_batch_delta_signature(
                            cfg.abd_mac_secret, msg.base, fp, at,
                            [fields[i] for i in at], digest, nonce),
                        nonce, fingerprint=fp, base=msg.base, positions=at,
                    )
                else:
                    # the MAC covers the kept blob: no tag is formatted
                    reply = M.TagBatchReply(
                        vec.reply_tags, digest,
                        sigs.abd_batch_blob_signature(
                            cfg.abd_mac_secret, vec.blob, digest, nonce),
                        nonce, fingerprint=fp,
                    )
                metrics.inc(
                    "dds_replica_tag_vector_total", outcome=outcome,
                    help="authenticated ReadTagBatch rounds by what the "
                         "kept tag vector needed",
                )
                if changed:
                    metrics.inc(
                        "dds_replica_tag_vector_keys_total", changed,
                        outcome="patched",
                        help="tags replaced in a kept tag vector",
                    )
                if outcome != "reused":
                    tracer.record(
                        "replica.tag_vector",
                        (time.perf_counter() - t0) * 1e3, replica=self.name,
                        k=len(keys), changed=changed, outcome=outcome,
                    )
                self._send(sender, reply)

            case M.TagReply(tag, key, value, signature, nonce):
                if not sigs.validate_abd_signature(
                    cfg.abd_mac_secret, value, tag, nonce, signature
                ):
                    self._reject(sender, msg, "bad_mac",
                                 "invalid ABD signature")
                    return
                req = self.outgoing.get(nonce)
                if req is None:
                    self._reject(sender, msg, "unknown_nonce",
                                 "invalid nonce - unknown")
                    return
                if req.expired:
                    self._debug("invalid nonce - expired (late quorum reply)")
                    return
                if not isinstance(req.call, M.IWrite):
                    # a reply type must match its request's phase: a forged
                    # TagReply against a read/tag-read nonce would otherwise
                    # pollute that quorum accumulator
                    self._reject(sender, msg, "wrong_phase",
                                 "TagReply for a non-write request")
                    return
                req.read_quorum[sender] = (tag, value, signature)
                if len(req.read_quorum) >= cfg.quorum_size:
                    max_tag = max(t for t, _, _ in req.read_quorum.values())
                    req.read_quorum = {}
                    self._seq_floor = max(self._seq_floor, max_tag.seq) + 1
                    new_tag = M.ABDTag(self._seq_floor, self.name)
                    req.tag_to_reply = new_tag
                    sig = sigs.abd_signature(
                        cfg.abd_mac_secret, req.set_to_write, new_tag, nonce
                    )
                    self._broadcast(M.Write(new_tag, key, req.set_to_write, sig, nonce))

            case M.Write(tag, key, value, signature, nonce):
                if not sigs.validate_abd_signature(
                    cfg.abd_mac_secret, value, tag, nonce, signature
                ):
                    self._reject(sender, msg, "bad_mac",
                                 "invalid ABD signature")
                    return
                if nonce not in self.incoming:
                    self._reject(sender, msg, "unknown_nonce",
                                 "invalid nonce - unknown")
                    return
                if self.incoming[nonce]:
                    self._debug("invalid nonce - expired at Write (late quorum reply)")
                    return
                self.incoming[nonce] = True
                if self._shard_fenced(key):
                    # storage-layer fence: a Write minted under a stale
                    # epoch (coordinator raced the map install) is neither
                    # stored nor acked — the op can't reach quorum, the
                    # client retries, and the retry fences at the
                    # coordinator. Zero stale-epoch writes ever land.
                    self._note_storage_fence("Write", key)
                    return
                cur_tag, _ = self._state(key)
                if cur_tag < tag:
                    self._store(key, tag, value)
                self._send(sender, M.WriteAck(key, nonce))

            case M.WriteAck(key, nonce):
                req = self.outgoing.get(nonce)
                if req is None:
                    self._reject(sender, msg, "unknown_nonce",
                                 "invalid nonce - unknown")
                    return
                if req.expired:
                    self._debug("invalid nonce - expired at WriteAck (late reply)")
                    return
                if not isinstance(req.call, (M.IRead, M.IWrite)):
                    self._reject(sender, msg, "wrong_phase",
                                 "WriteAck for a request with no write phase")
                    return
                req.write_quorum.add(sender)
                if self._quorum_met(req.write_quorum):
                    req.write_quorum = set()
                    req.expired = True
                    challenge = req.client_nonce + cfg.nonce_increment
                    match req.call:
                        case M.IRead(k):
                            # the MAC covers the tag too: tags are
                            # predictable, so an unsigned tag could be
                            # swapped in transit to poison tag-validated
                            # caching at the proxy
                            sig = sigs.proxy_signature(
                                cfg.proxy_mac_secret,
                                k,
                                challenge,
                                [req.set_to_read, sigs.tag_payload(req.tag_to_reply)],
                            )
                            self._send(
                                req.client,
                                M.Envelope(
                                    M.IReadReply(
                                        k, req.set_to_read, tag=req.tag_to_reply
                                    ),
                                    challenge,
                                    sig,
                                ),
                            )
                        case M.IWrite(k, _):
                            sig = sigs.proxy_signature(
                                cfg.proxy_mac_secret,
                                k,
                                challenge,
                                sigs.tag_payload(req.tag_to_reply),
                            )
                            self._send(
                                req.client,
                                M.Envelope(
                                    M.IWriteReply(k, tag=req.tag_to_reply),
                                    challenge,
                                    sig,
                                ),
                            )

            case M.Read(key, nonce):
                if nonce in self.incoming:
                    self._reject(sender, msg, "repeated_nonce",
                                 "invalid nonce - repeated")
                    return
                self.incoming[nonce] = False
                tag, contents = self._state(key)
                sig = sigs.abd_signature(cfg.abd_mac_secret, contents, tag, nonce)
                self._send(sender, M.ReadReply(tag, key, contents, sig, nonce))

            case M.ReadReply(tag, key, value, signature, nonce):
                if not sigs.validate_abd_signature(
                    cfg.abd_mac_secret, value, tag, nonce, signature
                ):
                    self._reject(sender, msg, "bad_mac",
                                 "invalid ABD signature")
                    return
                req = self.outgoing.get(nonce)
                if req is None:
                    self._reject(sender, msg, "unknown_nonce",
                                 "invalid nonce - unknown")
                    return
                if req.expired:
                    self._debug("invalid nonce - expired at ReadReply (late reply)")
                    return
                if not isinstance(req.call, M.IRead):
                    self._reject(sender, msg, "wrong_phase",
                                 "ReadReply for a non-read request")
                    return
                req.read_quorum[sender] = (tag, value, signature)
                if self._quorum_met(req.read_quorum):
                    entries = list(req.read_quorum.values())
                    max_tag, max_val, max_sig = max(entries, key=lambda e: e[0])
                    req.read_quorum = {}
                    req.set_to_read = max_val
                    req.tag_to_reply = max_tag
                    if all(t == max_tag for t, _, _ in entries):
                        # Standard ABD read optimization (deviation from the
                        # reference, which always writes back): every quorum
                        # member already reported (max_tag, value), so the
                        # value IS stored at a full quorum and the write-back
                        # phase adds nothing — any later read's quorum
                        # intersects this one. Answer the proxy directly.
                        # (A Byzantine member forging an equal tag with a
                        # different value needs the intranet MAC secret —
                        # with which it could equally poison the write-back
                        # path, so the threat model is unchanged.)
                        req.expired = True
                        challenge = req.client_nonce + cfg.nonce_increment
                        k = req.call.key
                        sig = sigs.proxy_signature(
                            cfg.proxy_mac_secret,
                            k,
                            challenge,
                            [max_val, sigs.tag_payload(max_tag)],
                        )
                        self._send(
                            req.client,
                            M.Envelope(
                                M.IReadReply(k, max_val, tag=max_tag),
                                challenge,
                                sig,
                            ),
                        )
                        return
                    # ABD write-back phase, re-using the original signature
                    self._broadcast(M.Write(max_tag, key, max_val, max_sig, nonce))

            case M.ReadBatch(keys, nonce):
                if not (isinstance(keys, tuple)
                        and all(isinstance(k, str) for k in keys)):
                    self._debug("ReadBatch whose keys are no tuple of strings")
                    return
                if nonce in self.incoming:
                    self._reject(sender, msg, "repeated_nonce",
                                 "invalid nonce - repeated")
                    return
                self.incoming[nonce] = False
                entries = []
                for key in keys:
                    tag, contents = self._state(key)
                    entries.append(M.BatchEntry(
                        tag, key, contents, sigs.abd_signature(
                            cfg.abd_mac_secret, contents, tag, nonce)))
                self._send(sender, M.ReadBatchReply(tuple(entries), nonce))

            case M.ReadBatchReply(entries, nonce):
                if not self._entries_verify(entries, nonce):
                    self._reject(sender, msg, "bad_mac",
                                 "invalid ABD signature")
                    return
                req = self.outgoing.get(nonce)
                if req is None:
                    self._reject(sender, msg, "unknown_nonce",
                                 "invalid nonce - unknown")
                    return
                if req.expired:
                    self._debug("invalid nonce - expired at ReadBatchReply (late reply)")
                    return
                if not isinstance(req.call, M.IReadBatch) or (
                    tuple(e.key for e in entries) != req.call.keys
                ):
                    # another request's phase, or not the keys asked for
                    self._reject(sender, msg, "wrong_phase",
                                 "ReadBatchReply for a request it does not answer")
                    return
                if req.set_to_read is not None:
                    self._debug("ReadBatchReply after the read quorum (late reply)")
                    return
                req.read_quorum[sender] = entries
                if self._quorum_met(req.read_quorum):
                    votes = list(req.read_quorum.values())
                    req.read_quorum = {}
                    # per key: the quorum's maximum entry; a key on which
                    # every member reported it is settled (the read
                    # optimisation above), the others are written back
                    # together, each under its original signature
                    best, back = [], []
                    for column in zip(*votes):
                        top = max(column, key=lambda e: e.tag)
                        best.append(top)
                        if any(e.tag != top.tag for e in column):
                            back.append(top)
                    req.set_to_read = best
                    for outcome, n in (("settled", len(best) - len(back)),
                                       ("written_back", len(back))):
                        if n:
                            metrics.inc(
                                "dds_read_batch_keys_total", n,
                                outcome=outcome,
                                help="keys of batched reads by whether the "
                                     "quorum agreed on them or they were "
                                     "written back first",
                            )
                    if back:
                        self._broadcast(M.WriteBatch(tuple(back), nonce))
                    else:
                        self._answer_read_batch(req)

            case M.WriteBatch(entries, nonce):
                if not self._entries_verify(entries, nonce):
                    self._reject(sender, msg, "bad_mac",
                                 "invalid ABD signature")
                    return
                if nonce not in self.incoming:
                    self._reject(sender, msg, "unknown_nonce",
                                 "invalid nonce - unknown")
                    return
                if self.incoming[nonce]:
                    self._debug("invalid nonce - expired at WriteBatch (late quorum reply)")
                    return
                self.incoming[nonce] = True
                bad = next((e.key for e in entries
                            if self._shard_fenced(e.key)), None)
                if bad is not None:
                    # storage-layer fence, as for Write: nothing stored,
                    # nothing acked
                    self._note_storage_fence("WriteBatch", bad)
                    return
                for e in entries:
                    if self._state(e.key)[0] < e.tag:
                        self._store(e.key, e.tag, e.value)
                self._send(sender, M.WriteBatchAck(nonce))

            case M.WriteBatchAck(nonce):
                req = self.outgoing.get(nonce)
                if req is None:
                    self._reject(sender, msg, "unknown_nonce",
                                 "invalid nonce - unknown")
                    return
                if req.expired:
                    self._debug("invalid nonce - expired at WriteBatchAck (late reply)")
                    return
                if not isinstance(req.call, M.IReadBatch) or (
                    req.set_to_read is None
                ):
                    self._reject(sender, msg, "wrong_phase",
                                 "WriteBatchAck for a request with no "
                                 "batched write phase")
                    return
                req.write_quorum.add(sender)
                if self._quorum_met(req.write_quorum):
                    req.write_quorum = set()
                    self._answer_read_batch(req)

            case M.LeaseRequest(region, ttl, nonce, signature):
                if not sigs.validate_manifest_signature(
                    cfg.abd_mac_secret, "lease-request",
                    {"region": region, "ttl": ttl}, nonce, signature,
                ):
                    self._reject(sender, msg, "bad_mac",
                                 "invalid lease-request signature",
                                 suspect=False)
                    return
                if nonce in self.incoming:
                    self._reject(sender, msg, "repeated_nonce",
                                 "invalid nonce - repeated (lease request)")
                    return
                self.incoming[nonce] = True
                ok = self.lease_table is not None
                token, expires = "", 0.0
                if ok:
                    lease = self.lease_table.grant(region, self.name,
                                                   float(ttl))
                    token, expires = lease.token, lease.expires
                    tracer.event("geo.lease_grant", replica=self.name,
                                 region=region, ttl=float(ttl))
                rsig = sigs.manifest_signature(
                    cfg.abd_mac_secret, "lease-grant",
                    {"region": region, "replica": self.name, "token": token,
                     "expires": expires, "ok": ok}, nonce,
                )
                self._send(sender, M.LeaseGrant(region, self.name, token,
                                                expires, ok, nonce, rsig))

            case M.LeaseRevoke(region, nonce, signature):
                if not sigs.validate_manifest_signature(
                    cfg.abd_mac_secret, "lease-revoke",
                    {"region": region}, nonce, signature,
                ):
                    self._reject(sender, msg, "bad_mac",
                                 "invalid lease-revoke signature",
                                 suspect=False)
                    return
                if nonce in self.incoming:
                    self._reject(sender, msg, "repeated_nonce",
                                 "invalid nonce - repeated (lease revoke)")
                    return
                self.incoming[nonce] = True
                if self.lease_table is not None:
                    self.lease_table.revoke(region)
                    tracer.event("geo.lease_revoke", replica=self.name,
                                 region=region)

            case M.LocalRead(key, region, token, nonce, signature):
                if not sigs.validate_proxy_signature(
                    cfg.proxy_mac_secret, key, nonce, signature,
                    ["local-read", region],
                ):
                    self._reject(sender, msg, "bad_mac",
                                 "invalid proxy signature (local read)",
                                 suspect=False)
                    return
                if nonce in self.incoming:
                    self._reject(sender, msg, "repeated_nonce",
                                 "invalid nonce - repeated (local read)")
                    return
                self.incoming[nonce] = True
                served = (
                    self.lease_table is not None
                    and self.lease_table.valid(region, self.name, token)
                    and not self._shard_fenced(key)
                )
                if served:
                    tag, value = self._state(key)
                else:
                    # typed refusal (bad/expired/revoked lease, or a fence):
                    # the proxy falls back to a full quorum read NOW instead
                    # of timing out a WAN round-trip first
                    tag, value = None, None
                metrics.inc(
                    "dds_geo_local_reads_total",
                    result="served" if served else "refused",
                    replica=self.name,
                    help="lease-backed region-local reads by outcome",
                )
                rsig = sigs.proxy_signature(
                    cfg.proxy_mac_secret, key, nonce,
                    [served, value,
                     sigs.tag_payload(tag) if tag is not None else None],
                )
                self._send(sender, M.LocalReadReply(tag, key, value, served,
                                                    nonce, rsig))

            case M.Sleep(data, nonces):
                # legacy unverified reseed (kept for deployments that turn
                # verified_transfer off): the seeding state is trusted
                # verbatim — the blind spot the SleepBegin path closes
                self._install_repository({
                    k: (M.ABDTag(v["tag"][0], v["tag"][1]), v["value"])
                    for k, v in data.items()
                })
                for n in nonces:
                    self.incoming[int(n)] = True
                self._since_kill = None
                self._debug("going to sleep")
                self._send(sender, M.Complying())
                self.behavior = "sentinent"

            case M.SleepBegin():
                await self._recovery_begin(sender, msg)

            case M.ShardMigrateBegin():
                self._migrate_ingest(sender, msg)

            case M.StateChunk():
                if msg.kind == "migrate":
                    self._migrate_ingest(sender, msg)
                elif msg.kind == "recovery":
                    self._recovery_chunk(msg)

            case M.StateDigestRequest(nonce):
                self._answer_manifest(sender, nonce)

            case (M.MerkleRootRequest() | M.MerkleBucketRequest()
                  | M.MerkleKeysRequest() | M.RepairRequest() | M.MerkleRoot()
                  | M.MerkleBuckets() | M.MerkleKeys() | M.RepairReply()):
                self.antientropy.handle(sender, msg)

            case M.Kill():
                # guardian-restart semantics: fresh empty state, healthy
                self._wipe()
                self.behavior = "healthy"
                self._debug("killed and restarted")

            case M.Compromise():
                self.behavior = "byzantine"

            case _:
                self._debug(f"unhandled {type(msg).__name__}")

    # ------------------------------------------------------------ sentinent

    async def _sentinent(self, sender: str, msg) -> None:
        cfg = self.cfg
        match msg:
            case M.Write(tag, key, value, signature, nonce):
                if not sigs.validate_abd_signature(
                    cfg.abd_mac_secret, value, tag, nonce, signature
                ):
                    self._debug("invalid ABD signature (sentinent)")
                    return
                if nonce in self.incoming:
                    self._debug("invalid nonce - repeated (sentinent)")
                    return
                self.incoming[nonce] = True
                if self._shard_fenced(key):
                    return  # same storage fence as the healthy path
                cur_tag, _ = self._state(key)
                if cur_tag < tag:
                    self._store(key, tag, value)

            case M.WriteBatch(entries, nonce):
                if not self._entries_verify(entries, nonce):
                    self._debug("invalid ABD signature (sentinent)")
                    return
                if nonce in self.incoming:
                    self._debug("invalid nonce - repeated (sentinent)")
                    return
                self.incoming[nonce] = True
                for e in entries:
                    if not self._shard_fenced(e.key) and (
                        self._state(e.key)[0] < e.tag
                    ):
                        self._store(e.key, e.tag, e.value)

            case M.Awake(session, chunk_keys):
                self._debug("waking up")
                with tracer.span("recovery.state_build", replica=self.name,
                                 keys=len(self.repository),
                                 nonces=len(self.incoming)):
                    self._answer_awake(sender, session, chunk_keys)
                self.behavior = "healthy"

            case M.StateDigestRequest(nonce):
                # the supervisor's spare-freshness probe and the verified-
                # transfer quorum both reach spares too
                self._answer_manifest(sender, nonce)

            case (M.MerkleRootRequest() | M.MerkleBucketRequest()
                  | M.MerkleKeysRequest() | M.RepairRequest() | M.MerkleRoot()
                  | M.MerkleBuckets() | M.MerkleKeys() | M.RepairReply()):
                # spares sync too: a snapshot-restored sentinent converges
                # before it is ever promoted
                self.antientropy.handle(sender, msg)

            case M.ShardMigrateBegin():
                # spares of a NEW group ingest the migration too, so a
                # later promotion starts warm instead of divergent
                self._migrate_ingest(sender, msg)

            case M.StateChunk() if msg.kind == "migrate":
                self._migrate_ingest(sender, msg)

            case M.StateChunk() if (msg.kind == "recovery"
                                    and msg.session in self._recovery_sessions):
                # a reseed this replica began: it sleeps from the header on
                self._recovery_chunk(msg)

            case M.Kill():
                self._wipe()
                self.behavior = "healthy"

    # ------------------------------------------------------------ byzantine

    async def _byzantine(self, sender: str, msg) -> None:
        """Simulated compromise, mirroring `BFTABDNode.scala:420-469`:
        garbage replies, replays, forged writes, omissions — and note the
        attacker DOES hold the real MAC key (kept per the reference threat
        model, SURVEY.md §7)."""
        cfg = self.cfg
        match msg:
            case M.Envelope(_, _, _):
                # protocol violation: bare reply, not an Envelope
                self._send(sender, M.IReadReply("2eikd094akldslcnu94342", None))

            case M.ReadTag(key, nonce):
                garbage = [1, "i am ", "trudy", None]
                for _ in range(4):  # replay x4 with empty signature
                    self._send(
                        sender,
                        M.TagReply(M.ABDTag(0, self.name), key, garbage, b"", nonce),
                    )

            case M.ReadTagBatch(keys, nonce, _):
                # inflated tags under an empty signature, replayed x2: the
                # proxy drops these on MAC failure; even if the tags landed
                # they could only force spurious cache re-fetches
                fake = tuple(M.ABDTag(1 << 30, self.name)
                             for _ in range(msg.count or len(keys)))
                for _ in range(2):
                    self._send(sender, M.TagBatchReply(fake, "forged", b"", nonce))

            case M.TagReply(_, key, _, _, nonce) | M.ReadReply(_, key, _, _, nonce):
                # forge a write to every replica under a random tag
                tag = M.ABDTag(random.getrandbits(31), sender.rsplit("/", 1)[-1])
                sig = sigs.abd_signature(cfg.abd_mac_secret, None, tag, nonce + 1)
                for replica in self.all_replicas:
                    self._send(replica, M.Write(tag, key, None, sig, nonce + 1))

            case M.Write(_, key, _, _, nonce):
                self._send(sender, M.WriteAck(key, nonce))

            case M.WriteAck(_, _):
                pass  # omission

            case M.Read(key, nonce):
                tag = M.ABDTag(random.getrandbits(31), sender.rsplit("/", 1)[-1])
                self._send(
                    sender,
                    M.ReadReply(tag, key, [",test,", 31, True], b"10010100110010", nonce),
                )

            case M.ReadBatch(keys, nonce):
                # as for Read, per key: a random tag, garbage, a bad signature
                rid = sender.rsplit("/", 1)[-1]
                self._send(sender, M.ReadBatchReply(tuple(
                    M.BatchEntry(M.ABDTag(random.getrandbits(31), rid), key,
                                 [",test,", 31, True], b"10010100110010")
                    for key in keys), nonce))

            case M.ReadBatchReply(entries, nonce):
                # as for ReadReply: forged writes under a random tag
                tag = M.ABDTag(random.getrandbits(31), sender.rsplit("/", 1)[-1])
                sig = sigs.abd_signature(cfg.abd_mac_secret, None, tag, nonce + 1)
                forged = M.WriteBatch(tuple(
                    M.BatchEntry(tag, e.key, None, sig) for e in entries
                    if isinstance(e, M.BatchEntry)), nonce + 1)
                for replica in self.all_replicas:
                    self._send(replica, forged)

            case M.WriteBatch(_, nonce):
                self._send(sender, M.WriteBatchAck(nonce))

            case M.WriteBatchAck(_):
                pass  # omission

            case M.Kill():
                self._wipe()
                self.behavior = "healthy"

    # ------------------------------------------------- verified state seed

    MAX_RECOVERY_SESSIONS = 4

    def _answer_manifest(self, sender: str, nonce: int) -> None:
        """One `StateDigestRequest` answered: the signed manifest of every
        tracked entry (O(K): the listing, its canonical form, one MAC)."""
        with tracer.span("recovery.manifest", replica=self.name,
                         keys=len(self.merkle)):
            manifest = self.merkle.manifest()
            sig = sigs.manifest_signature(
                self.cfg.abd_mac_secret, self.addr, manifest, nonce
            )
            self._send(sender, M.StateDigest(manifest, nonce, sig))

    def _answer_awake(self, sender: str, session: int,
                      chunk_keys: int) -> None:
        """The state a woken spare hands the supervisor. Chunked
        (`chunk_keys` > 0): `StateChunk`s of kind "state", each with its
        slice of the nonce table, then a `State` without data that says how
        many there were. Else the reference's one `State`.

        The nonces are every one this replica has seen, not the newest N:
        a nonce carries no time and nothing bounds how late a captured
        message may be replayed, so any cut would be a guess at the
        replayer's patience. They travel in bounded frames instead."""
        entries = [
            (k, {"tag": [t.seq, t.id], "value": v})
            for k, (t, v) in self.repository.items()
        ]
        nonces = list(self.incoming)
        if chunk_keys <= 0:
            self._send(sender, M.State(dict(entries), nonces))
            return
        total = max(1, -(-len(entries) // chunk_keys))
        per = -(-len(nonces) // total)
        for seq in range(total):
            self._send(sender, M.StateChunk(
                session, seq,
                dict(entries[seq * chunk_keys:(seq + 1) * chunk_keys]),
                "state", tuple(nonces[seq * per:(seq + 1) * per])))
        self._send(sender, M.State({}, [], session, total))

    def _reseed(self, session: int) -> "_Reseed":
        """The verified reseed under `session`, begun on its first frame.
        Sessions are bounded: a flood of bogus session ids evicts
        oldest-first instead of growing without bound."""
        sess = self._recovery_sessions.get(session)
        if sess is None:
            while len(self._recovery_sessions) >= self.MAX_RECOVERY_SESSIONS:
                self._recovery_sessions.pop(next(iter(self._recovery_sessions)))
            sess = self._recovery_sessions[session] = _Reseed()
        return sess

    def _reseeding(self, session: int, sess: "_Reseed") -> bool:
        """Whether `sess` is still this replica's to go on with after it
        gave up the loop: not wiped away (`Kill`) and not evicted."""
        return self._recovery_sessions.get(session) is sess

    async def _recovery_begin(self, sender: str, msg: M.SleepBegin) -> None:
        """The header of a verified reseed: settle what the relayed
        manifests attest, one signer a pass of the loop (each is O(K): its
        canonical form, its MAC, its K votes), then take in the chunks that
        came before that was known, one a pass. Chunks that come after are
        taken in as they arrive (`_recovery_chunk`).

        The replica sleeps from the header on, not from the last chunk:
        between the two its repository is what was written since the
        `Kill` and nothing else, and a replica that votes in quorums with
        that is one vote short for everybody. Asleep it stores the writes
        that reach it, acknowledges none and answers no read, and what it
        stored joins the seeded state when that is whole."""
        sess = self._reseed(msg.session)
        if sess.begin is not None:
            return
        sess.begin, sess.sender = msg, sender
        self.behavior = "sentinent"
        votes: dict[tuple, set] = {}
        for item in msg.digests:
            with tracer.span("recovery.install", replica=self.name,
                             stage="manifest"):
                _tally_manifest(votes, item, self.cfg.abd_mac_secret)
            await asyncio.sleep(0)
            if not self._reseeding(msg.session, sess):
                return
        sess.verified = _settle_manifest(votes, msg.support)
        while sess.early:
            seq = next(iter(sess.early))
            self._install_chunk(sess, seq, sess.early.pop(seq))
            await asyncio.sleep(0)
            if not self._reseeding(msg.session, sess):
                return
        self._try_complete_recovery(msg.session, sess)

    def _recovery_chunk(self, msg: M.StateChunk) -> None:
        """One `StateChunk` of a verified reseed; transports reorder, so
        completion is by count, not order."""
        sess = self._reseed(msg.session)
        if sess.verified is None:
            sess.early[int(msg.seq)] = msg
            return
        self._install_chunk(sess, int(msg.seq), msg)
        self._try_complete_recovery(msg.session, sess)

    def _install_chunk(self, sess: "_Reseed", seq: int,
                       msg: M.StateChunk) -> None:
        """Verify, digest and index one chunk against the settled
        manifests: an entry is kept only as the (tag, value digest) that
        `support` signers attest. The spare's state is data, not truth."""
        if seq in sess.seen or not 0 <= seq < sess.begin.total:
            return
        sess.seen.add(seq)
        verified, accepted = sess.verified, 0
        with tracer.span("recovery.install", replica=self.name,
                         stage="chunk", keys=len(msg.entries)):
            for key, e in msg.entries.items():
                try:
                    tag = M.ABDTag(int(e["tag"][0]), str(e["tag"][1]))
                    value = e["value"]
                except (KeyError, TypeError, ValueError, IndexError):
                    sess.rejected.append(key)
                    continue
                vd = sigs.value_digest(value)
                if verified.get(key) == (tag.seq, tag.id, vd):
                    sess.repository[key] = (tag, value)
                    sess.merkle.update(key, tag, value, vd)
                    accepted += 1
                else:
                    sess.rejected.append(key)
            sess.nonces.extend(msg.nonces)
        for outcome, n in (("accepted", accepted),
                           ("rejected", len(msg.entries) - accepted)):
            if n:
                metrics.inc(
                    "dds_recovery_seeded_entries_total", n, outcome=outcome,
                    help="entries of verified reseeds, by what the digest "
                         "quorum said of them",
                )

    def _try_complete_recovery(self, session: int, sess: "_Reseed") -> None:
        begin = sess.begin
        if (begin is None or sess.verified is None
                or len(sess.seen) < begin.total):
            return
        self._recovery_sessions.pop(session, None)
        repository, rejected = sess.repository, sess.rejected
        # what was written HERE since the `Kill`, each write under its
        # coordinator's MAC (acknowledged before the header came, stored
        # asleep after it), is newer than any seed and stays
        for key in self._since_kill or ():
            tag, value = self.repository.get(key, (None, None))
            seeded = repository.get(key)
            if tag is not None and (seeded is None or seeded[0] < tag):
                repository[key] = (tag, value)
                sess.merkle.update(key, tag, value)
        self._since_kill = None
        self._install_repository(repository, sess.merkle)
        for n in (*begin.nonces, *sess.nonces):
            self.incoming[int(n)] = True
        if rejected:
            log.warning(
                "%s: verified reseed rejected %d/%d entries (digest quorum "
                "mismatch) — anti-entropy will repair the holes",
                self.name, len(rejected), len(rejected) + len(repository),
            )
            tracer.event("recovery.rejected_entries", replica=self.name,
                         rejected=len(rejected), accepted=len(repository))
            metrics.inc(
                "dds_recovery_rejected_entries_total", len(rejected),
                replica=self.name,
                help="seeded entries rejected by the digest quorum",
            )
            flight.record(
                "recovery_digest_mismatch", replica=self.name,
                rejected=sorted(rejected)[:32], accepted=len(repository),
            )
            self.antientropy.kick()
        self._debug(
            f"reseeded with {len(repository)} verified entries "
            f"({len(rejected)} rejected); going to sleep"
        )
        self._send(sess.sender, M.Complying())
        self.behavior = "sentinent"

    def _verified_manifest(self, digests: list, support: int) -> dict:
        return verified_manifest(digests, support, self.cfg.abd_mac_secret)

    # -------------------------------------------------- shard migration

    MAX_MIGRATE_SESSIONS = 4

    def _migrate_ingest(self, sender: str, msg) -> None:
        """Buffer one frame of a Constellation key migration (header or a
        kind="migrate" StateChunk). Same reorder-tolerant, bounded session
        buffering as recovery — but completion MERGES, never replaces."""
        sess = self._migrate_sessions.get(msg.session)
        if sess is None:
            while len(self._migrate_sessions) >= self.MAX_MIGRATE_SESSIONS:
                self._migrate_sessions.pop(next(iter(self._migrate_sessions)))
            sess = self._migrate_sessions[msg.session] = {
                "begin": None, "sender": None, "chunks": {},
            }
        if isinstance(msg, M.ShardMigrateBegin):
            sess["begin"] = msg
            sess["sender"] = sender
        else:
            sess["chunks"][int(msg.seq)] = msg.entries
        self._try_complete_migration(msg.session)

    def _try_complete_migration(self, session: int) -> None:
        sess = self._migrate_sessions.get(session)
        begin = sess["begin"]
        if begin is None:
            return
        chunks = sess["chunks"]
        if sum(1 for s in chunks if 0 <= s < begin.total) < begin.total:
            return
        verified = self._verified_manifest(begin.digests, begin.support)
        accepted = rejected = 0
        for seq in range(begin.total):
            for key, e in chunks[seq].items():
                try:
                    tag = M.ABDTag(int(e["tag"][0]), str(e["tag"][1]))
                    value = e["value"]
                except (KeyError, TypeError, ValueError, IndexError):
                    rejected += 1
                    continue
                # the receiving group only takes keys its OWN map assigns
                # it — a Byzantine rebalancer cannot use a migration to
                # park foreign keys on this group
                if self.shard is not None and not self.shard.owns(key):
                    rejected += 1
                    continue
                want = verified.get(key)
                if want != (tag.seq, tag.id, sigs.value_digest(value)):
                    rejected += 1
                    continue
                cur_tag = self.repository.get(key, (M.ABDTag(0, self.name),
                                                    None))[0]
                if cur_tag < tag:
                    self._store(key, tag, value)
                accepted += 1  # installed, or already at/above the attested tag
        self._migrate_sessions.pop(session, None)
        metrics.inc(
            "dds_shard_migrated_keys_total", accepted, replica=self.name,
            help="verified keys accepted during shard migrations",
        )
        if rejected:
            tracer.event("shard.migrate_rejected", replica=self.name,
                         rejected=rejected, accepted=accepted)
            flight.record(
                "shard_migrate_rejected", replica=self.name,
                rejected=rejected, accepted=accepted, session=session,
            )
        self._debug(
            f"shard migration {session}: {accepted} accepted, "
            f"{rejected} rejected"
        )
        self._send(sess["sender"], M.ShardMigrateAck(session, accepted,
                                                     rejected))

    def drop_unowned(self) -> int:
        """Prune repository entries outside this group's shard map (after
        a migration activates). Returns the number of keys dropped."""
        if self.shard is None:
            return 0
        doomed = [k for k in self.repository if not self.shard.owns(k)]
        for k in doomed:
            del self.repository[k]
        if doomed:
            self.repo_version += 1
            self._drop_tag_vectors()
            self.merkle.rebuild(self.repository)
        return len(doomed)

    # ---------------------------------------------------------------- admin

    def export_state(self) -> dict:
        return {
            k: {"tag": [t.seq, t.id], "value": v} for k, (t, v) in self.repository.items()
        }


def verified_manifest(digests: list, support: int, secret: bytes) -> dict:
    """Cross-check a relayed manifest quorum: verify every HMAC (the
    signer address is bound into it, so a relay cannot re-attribute)
    and keep only entries attested identically by >= `support` (= f+1)
    distinct signers — at least one of which is then honest, so no
    single Byzantine spare or relay can smuggle a forged entry. Shared
    by verified recovery reseeds (which take it a signer at a time),
    shard-migration ingest, and the rebalancer's source-side planning
    (shard/rebalance)."""
    votes: dict[tuple, set] = {}
    for item in digests:
        _tally_manifest(votes, item, secret)
    return _settle_manifest(votes, support)


def _tally_manifest(votes: dict, item, secret: bytes) -> None:
    """One relayed manifest: its HMAC verified, its entries added to
    `votes` under their signer. A bad MAC or a malformed item adds
    nothing."""
    try:
        signer, manifest, nonce, sighex = item
        if not sigs.validate_manifest_signature(
            secret, str(signer), manifest,
            int(nonce), bytes.fromhex(sighex),
        ):
            return
    except (TypeError, ValueError):
        return
    for key, ent in manifest.items():
        try:
            attested = (str(key), int(ent[0]), str(ent[1]), str(ent[2]))
        except (TypeError, ValueError, IndexError):
            continue
        votes.setdefault(attested, set()).add(str(signer))


def _settle_manifest(votes: dict, support: int) -> dict:
    """Per key the newest (seq, id, value digest) that `support` distinct
    signers attested identically."""
    verified: dict[str, tuple] = {}
    for (key, seq, tid, vd), signers in votes.items():
        if len(signers) < support:
            continue
        cur = verified.get(key)
        if cur is None or (seq, tid) > (cur[0], cur[1]):
            verified[key] = (seq, tid, vd)
    return verified
