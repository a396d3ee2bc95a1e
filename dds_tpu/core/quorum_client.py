"""Proxy-side ABD access: nonce-challenged, HMAC-verified quorum reads/writes.

Counterpart of the `fetchSet` / `writeSet` functions inside the reference
proxy (`dds/http/DDSRestServer.scala:952-1000, 1002-1050`): pick a random
trusted replica as coordinator, send a signed `Envelope(IRead/IWrite)`,
await the enveloped reply, and verify (a) the challenge nonce is the request
nonce + increment, (b) the proxy HMAC over the reply, (c) the echoed key.
Every protocol violation increments local suspicion on the coordinator
(3 strikes excludes it permanently — `utils/TrustedNodesList.scala:23-29`)
and raises a typed Byzantine exception; mere timeouts instead trip a
per-replica circuit breaker (utils/retry.CircuitBreaker) that steers
the next picks elsewhere and self-heals via half-open probes, so replicas
cut off by a (healed) partition regain coordination without a restart.
The probe is the proxy's own (`AbdClient._probe_loop`): while a breaker is
not closed one background task asks its target for the tags of no keys
every `breaker_reset` seconds, and no user's request is routed to the
target, nor is it asked in a tag round, while enough others allow.
Callers may pass a `Deadline` so each attempt's timeout shrinks to the
remaining request budget instead of a fixed 5 s per layer.

Reply correlation mirrors Akka ask semantics: a junk reply from the asked
coordinator (wrong shape, bare message) resolves the outstanding request and
is then rejected by validation, rather than stalling until timeout.
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass
from typing import Optional

from dds_tpu.core import messages as M
from dds_tpu.core.errors import (
    AllBreakersOpenError,
    ByzFailedNonceChallengeError,
    ByzInvalidKeyError,
    ByzInvalidSignatureError,
    ByzUnknownReplyError,
    WrongShardError,
)
from dds_tpu.core.transport import Transport
from dds_tpu.obs import context as obs_context
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils.retry import CircuitBreaker, Deadline, DeadlineExceededError
from dds_tpu.utils.tasks import supervised_task
from dds_tpu.utils.trace import tracer
from dds_tpu.utils import sigs
from dds_tpu.utils.trust import TrustedNodesList

log = logging.getLogger("dds.quorum_client")

# key sets (by keys digest) whose senders' verified tag vectors a proxy
# keeps between rounds; past it the one used longest ago goes
MAX_TAG_ROUNDS = 4
# a sender's kept vector may differ from the caller's list at a quarter of
# the positions (at least this many) before it is dropped for a full reply
MIN_KEPT_DIFF = 64
# key sets (by keys digest) of which a proxy remembers which replicas have
# answered for them, and so are sent the digest without the keys; as many
# as a replica keeps (`replica.MAX_TAG_VECTORS`); past it the one used
# longest ago goes, and its next round carries the keys
MAX_NAMED_SETS = 8
# rounds whose quorum is met stay open this many deep for the votes that
# come after it (see `AbdClient._on_late_tag_reply`)
MAX_LATE_ROUNDS = 8
# the vote of a replica whose vector equals the round's reference list:
# it differs from it nowhere (see `_TagRound`)
_SAME: dict = {}
# the key set a probe asks the tags of (see `AbdClient._probe`)
_NO_KEYS = sigs.key_from_set([])


def _differing(old: list, new: list) -> list[int]:
    """Positions at which two lists of one length hold unequal tags. The
    lists mostly share their objects: slices compare at C speed (identity
    first), and only a slice that differs is walked."""
    out = []
    for a in range(0, len(old), 128):
        b = a + 128
        if old[a:b] != new[a:b]:
            out.extend(i for i in range(a, min(b, len(old)))
                       if old[i] is not new[i] and old[i] != new[i])
    return out


class MergedTags(list):
    """What `AbdClient.read_tags` returns when the quorum's max moved a
    tag: the merged list, and in `moved` the positions at which it differs
    from the caller's `cached_tags` (in no order), or None where the round
    had no such list to hold it against. Everywhere else it holds
    `cached_tags`' own objects."""

    __slots__ = ("moved",)


class _KeptVectors:
    """Per key set, what the proxy has verified from each replica: sender
    -> (fingerprint, diff), the vector that sender last attested, held
    sparsely as "`ref` except `diff[position]`". `ref` is the proxy's own
    copy of the caller's `cached_tags` (which fingerprints to
    `fingerprint`), re-based when the caller's list moves; `gen` counts the
    re-bases, so that a round in flight over one knows its diffs no longer
    fit. A record is never altered, only replaced: a round keeps the ones
    it sent as bases.

    Records are keyed by SENDER and a delta is only ever applied to the
    record of the replica that sent it: the fingerprint in a delta is its
    sender's claim (nobody re-hashes K fields to check it), so a base
    looked up by value would let one replica's claim stand in for
    another's vector."""

    __slots__ = ("ref", "fingerprint", "epoch", "gen", "senders")

    def __init__(self, cached_tags: list, fingerprint: bytes, epoch: int):
        self.ref = list(cached_tags)
        self.fingerprint = fingerprint
        self.epoch = epoch
        self.gen = 0
        self.senders: dict[str, tuple] = {}

    def rebase(self, cached_tags: list, fingerprint: bytes) -> None:
        """The caller's list moved: one pass finds where, and each sender's
        diff takes in those positions (its vector did not move)."""
        ref = self.ref
        moved = _differing(ref, cached_tags)
        room = max(len(ref) // 4, MIN_KEPT_DIFF)
        for sender, (fp, diff) in list(self.senders.items()):
            diff = dict(diff)
            for i in moved:
                held = diff.pop(i, ref[i])
                if held != cached_tags[i]:
                    diff[i] = held
            if len(diff) > room:
                # a replica whose votes never make the quorum falls behind
                # without end: past what a delta is worth, start it anew
                del self.senders[sender]
            else:
                self.senders[sender] = (fp, diff)
        for i in moved:
            ref[i] = cached_tags[i]
        self.fingerprint = fingerprint
        self.gen += 1


class _TagRound:
    """One `read_tags` round in flight. A vote is the sender's vector held
    sparsely against the round's reference list `ref`: {position: tag}
    where it differs (`_SAME` where it does not). `ref` is the caller's
    `cached_tags` when the round carries a fingerprint, else the first full
    reply accepted. `kept`, `gen` and `bases` (sender -> the record whose
    fingerprint this request named to it) tie the round to the key set's
    `_KeptVectors`; `kept` is None for a round without a fingerprint.
    `asked` is who the round was sent to: once its quorum is met it stays
    open for the votes of the others (`AbdClient._on_late_tag_reply`).
    `nonces` are the request nonces the round answers to: its own, and one
    more for each replica that said `KeySetUnknown` and was sent the keys;
    `carried` is who has been sent the keys in this round (each once);
    `heard` is who of `asked` sent the round anything at all, whatever it
    was worth: the others were silent (`AbdClient._close_late`)."""

    __slots__ = ("fut", "votes", "digest", "keys", "fingerprint", "ref",
                 "kept", "gen", "bases", "verify_ms", "kinds", "asked",
                 "late", "nonces", "carried", "epoch", "heard")

    def __init__(self, fut, digest, keys, fingerprint, ref, kept, bases,
                 asked, nonce, epoch):
        self.fut = fut
        self.asked = asked
        self.nonces = [nonce]
        self.epoch = epoch
        self.carried: set[str] = set()
        self.late: set[str] = set()   # who answered after the quorum
        self.heard: set[str] = set()
        self.votes: dict[str, dict] = {}
        self.digest, self.keys, self.fingerprint = digest, keys, fingerprint
        self.ref = ref
        self.kept = kept
        self.gen = kept.gen if kept is not None else 0
        self.bases = bases
        self.verify_ms = 0.0
        self.kinds = {"unchanged": 0, "delta": 0, "full": 0}


@dataclass
class AbdClientConfig:
    proxy_mac_secret: bytes = b"rest2abd"
    nonce_increment: int = 1
    request_timeout: float = 5.0
    supervisor: str | None = None  # only accept ActiveReplicas from here
    # read_tags broadcasts ReadTagBatch to the replicas itself and verifies
    # each reply's intranet MAC, so it needs the ABD secret + quorum size
    # (the proxy lives inside the intranet in the reference too —
    # `dds-system.conf:94` puts both secrets in the one shared config)
    abd_mac_secret: bytes = b"intranet-abd-secret"
    quorum_size: int = 5
    # per-coordinator circuit breaker (utils/retry.CircuitBreaker): transient
    # unreachability (ask timeouts) trips it and self-heals via half-open
    # probes, while cryptographic protocol violations ALSO land on the
    # permanent 3-strike suspicion counter. Splitting the two is what lets
    # a healed partition serve again without a proxy restart.
    breaker_threshold: int = 3
    breaker_reset: float = 2.0
    # what the proxy's own probe of a target behind an open breaker waits
    # for its one answer: a probe is nobody's request, so it has no reason
    # to wait a user's `request_timeout`
    breaker_probe_timeout: float = 1.0
    # Constellation shard label for this client's metric series (empty =
    # unsharded, series keep their historical label sets)
    shard: str = ""
    # Bulwark fast-fail (core/admission): when EVERY trusted coordinator's
    # breaker is open and none will half-open within the caller's
    # remaining Deadline budget, raise AllBreakersOpenError immediately
    # instead of burning the budget on attempts that are provably futile.
    # The guard is deliberately that narrow: while a probe still fits the
    # budget, the degraded try (which may close a breaker) proceeds as
    # before, so nothing heals slower.
    fast_fail_all_open: bool = True
    # Atlas read-local leases (dds_tpu/geo): when enabled and an in-region
    # replica is known, reads first try a single-hop LocalRead against the
    # TTL-leased holder; any refusal, timeout, or validation failure drops
    # the lease session and the read falls back to the full cross-region
    # quorum path below — leases are a latency optimisation, never a
    # correctness dependency.
    lease_enabled: bool = False
    region: str = ""  # this proxy's home region ("" = geo-unaware)
    # replica addr (or bare name) -> region label, as placed by shard.fabric
    replica_regions: Optional[dict] = None
    lease_ttl: float = 2.0
    lease_renew_margin: float = 0.5  # renew when lease remaining < margin
    local_read_timeout: float = 0.75  # LocalRead budget before fallback


class AbdClient:
    def __init__(
        self,
        addr: str,
        net: Transport,
        replicas: list[str],
        config: AbdClientConfig | None = None,
    ):
        self.addr = addr
        self.net = net
        self.cfg = config or AbdClientConfig()
        self.replicas = TrustedNodesList(replicas)
        # replica addr -> CircuitBreaker (created on first failure path)
        self.breakers: dict[str, CircuitBreaker] = {}
        # replica addr -> the task that probes it while its breaker is
        # not closed (`_probe_loop`); probe nonce -> its one-replica round
        self._probe_tasks: dict[str, asyncio.Task] = {}
        self._probes: dict[int, _TagRound] = {}
        # challenge nonce -> (future, coordinator)
        self._pending: dict[int, tuple[asyncio.Future, str]] = {}
        self._preferred: list[str] = []  # supervisor's freshest-half view
        # tag-broadcast nonce -> the round in flight
        self._pending_tags: dict[int, _TagRound] = {}
        # nonce -> a round whose quorum is met, while replies are still owed
        self._late_tags: dict[int, _TagRound] = {}
        # keys digest -> what each replica's vector was last verified to be
        self._kept_vectors: dict[str, _KeptVectors] = {}
        # keys digest -> the replicas that have answered for it with a
        # verified vote: they hold the digest's keys and are sent the
        # digest alone, until one says `KeySetUnknown`
        self._keyset_holders: dict[str, set[str]] = {}
        # Constellation: when a ShardRouter owns this client it installs a
        # supplier for the ACTIVE map epoch; every Envelope/ReadTagBatch is
        # stamped with it so replicas can fence stale routes. None = -1 =
        # unsharded (replicas without a shard state ignore the field).
        self.shard_epoch: Optional[callable] = None
        # Atlas lease session: {"target", "replica", "token", "renew_at",
        # "expires"} while we hold an in-region read lease, else None.
        # Client-side expiry is measured from SEND time, so it is always
        # conservative w.r.t. the holder's table clock.
        self._lease: Optional[dict] = None
        self._lease_retry_at = 0.0  # grant backoff after a refusal/timeout
        # lease/local-read request nonce -> future (replies echo it)
        self._pending_lease: dict[int, asyncio.Future] = {}
        self._now = time.monotonic  # test hook (fake-clock schedules)
        net.register(addr, self.handle)

    async def handle(self, sender: str, msg) -> None:
        if isinstance(msg, M.Envelope) and msg.nonce in self._pending:
            fut, _ = self._pending[msg.nonce]
            if not fut.done():
                fut.set_result(msg)
            return
        if isinstance(msg, M.TagBatchReply):
            # correlated by REQUEST nonce. A reply to a round that is over
            # (its quorum was met by the others: over sockets the last
            # one's frame is decoded whenever the loop gets to it) ends
            # HERE: fallen through to the junk-reply path it would resolve
            # an Envelope op this sender happens to coordinate, and strike
            # an honest replica for being last
            if msg.nonce in self._pending_tags:
                self._on_tag_batch_reply(sender, msg)
            elif msg.nonce in self._late_tags:
                self._on_late_tag_reply(sender, msg, self._late_tags[msg.nonce])
            elif msg.nonce in self._probes:
                self._on_probe_reply(sender, msg, self._probes[msg.nonce])
            return
        if isinstance(msg, M.KeySetUnknown):
            # correlated by REQUEST nonce, and ends HERE like a late vote:
            # one that comes after its round is remembered and strikes
            # nobody; one that matches no round kept is dropped
            rnd = (self._pending_tags.get(msg.nonce)
                   or self._late_tags.get(msg.nonce))
            if rnd is not None:
                self._on_keyset_unknown(sender, msg, rnd)
            return
        if isinstance(msg, M.WrongShard):
            # shard fence rejection: resolve the matching outstanding
            # request (Envelope ops correlate by challenge nonce, tag
            # batches by request nonce). Handled BEFORE the junk-reply
            # fallthrough — a fence from a replica that also coordinates
            # another in-flight op must not resolve THAT op as junk and
            # earn the honest replica a suspicion strike.
            if msg.nonce in self._pending:
                fut, _ = self._pending[msg.nonce]
                if not fut.done():
                    fut.set_result(msg)
            elif msg.nonce in self._pending_tags:
                self._on_wrong_shard_batch(sender, msg)
            return
        if isinstance(msg, (M.LeaseGrant, M.LocalReadReply)):
            # correlate by REQUEST nonce (like TagBatchReply). Unmatched
            # (late) lease replies are dropped HERE — they must never fall
            # through to the junk-reply path and strike an honest replica
            # that also coordinates an outstanding Envelope op.
            entry = self._pending_lease.get(msg.nonce)
            if entry is not None and not entry.done():
                entry.set_result(msg)
            return
        if isinstance(msg, M.ActiveReplicas):
            if self.cfg.supervisor is not None and sender != self.cfg.supervisor:
                log.warning("ignoring ActiveReplicas from non-supervisor %s", sender)
                return
            if msg.replicas:
                # the supervisor serves only the freshest HALF of the active
                # list (coordinator load-balancing, DDSRestServer.scala:139-147)
                # — merge, don't reset: broadcasts (read_tags) need the whole
                # quorum membership, which a partial view must not shrink
                known = set(self.replicas.get_all())
                joined = [r for r in msg.replicas if r not in known]
                if joined:
                    metrics.inc(
                        "dds_membership_changes_total", len(joined),
                        **self._mlabels(kind="joined"),
                        help="endpoints an ActiveReplicas named that the "
                             "proxy did not know (a promoted spare)",
                    )
                self.replicas.merge(msg.replicas)
                self._preferred = list(msg.replicas)
            return
        # junk from a coordinator we are waiting on resolves that request
        # (Akka-ask semantics); validation will reject it.
        for nonce, (fut, coord) in list(self._pending.items()):
            if coord == sender and not fut.done():
                fut.set_result(msg)
                return
        log.debug("unmatched message from %s: %s", sender, type(msg).__name__)

    def _breaker(self, node: str) -> CircuitBreaker:
        b = self.breakers.get(node)
        if b is None:
            b = self.breakers[node] = CircuitBreaker(
                self.cfg.breaker_threshold, self.cfg.breaker_reset,
                name=node.rsplit("/", 1)[-1],
            )
        return b

    def _unsettled(self) -> tuple:
        """The replicas whose breaker is open or half-open: whether one
        is back is its probe's to find out (`_probe_loop`)."""
        return tuple(n for n, b in self.breakers.items() if not b.settled)

    def _breaker_failed(self, node: str) -> None:
        """One breaker failure of `node`; a breaker this leaves open gets
        its probe, unless it has one."""
        b = self._breaker(node)
        b.record_failure()
        if b.settled:
            return
        task = self._probe_tasks.get(node)
        if task is None or task.done():
            task = self._probe_tasks[node] = supervised_task(
                self._probe_loop(node, b),
                name=f"abd.probe:{node.rsplit('/', 1)[-1]}")
            task.add_done_callback(lambda t: self._probe_ended(node, t))

    def _probe_ended(self, node: str, task: asyncio.Task) -> None:
        if self._probe_tasks.get(node) is task:
            del self._probe_tasks[node]

    def _breaker_answered(self, node: str) -> None:
        """A verified answer from `node`: whatever was held against it is
        over. A replica nothing was ever held against has no breaker."""
        b = self.breakers.get(node)
        if b is not None:
            b.record_success()

    async def _probe_loop(self, target: str, b: CircuitBreaker) -> None:
        """The proxy's own look whether `target` is back, for as long as
        its breaker is not closed: every `breaker_reset` seconds (when the
        breaker turns half-open) one probe; a verified answer closes the
        breaker and ends the task, silence or a refused answer re-opens
        it with a fresh timer. A replica struck out is nobody's to probe:
        strikes do not heal. Whoever else resolves the breaker meanwhile
        (the degraded try of a request that found every coordinator
        refused, a vote that came late) is taken at its word."""
        while not b.settled and target in self.replicas.get_trusted():
            eta = b.half_open_eta()
            if eta > 0:
                await asyncio.sleep(eta)
            elif await self._probe(target):
                b.record_success()
            elif b.state == b.HALF_OPEN:
                b.record_failure()

    async def _probe(self, target: str) -> bool:
        """One authenticated request that costs an honest replica O(1):
        the tags of no keys (`ReadTagBatch` over the empty set, to `target`
        alone and under a nonce of its own), its answer MAC-verified as
        any vote is. True for a verified answer within
        `breaker_probe_timeout`."""
        cfg = self.cfg
        nonce = sigs.generate_nonce()
        fut: asyncio.Future = asyncio.get_event_loop().create_future()
        rnd = self._probes[nonce] = _TagRound(
            fut, _NO_KEYS, (), None, None, None, {}, frozenset((target,)),
            nonce, self._epoch())
        outcome = "silent"
        with tracer.span("abd.probe",
                         target=target.rsplit("/", 1)[-1]) as meta:
            try:
                self.net.send(self.addr, target, M.ReadTagBatch(
                    (), nonce,
                    sigs.proxy_signature(cfg.proxy_mac_secret, _NO_KEYS,
                                         nonce),
                    None, rnd.epoch, None, _NO_KEYS, 0))
                verified = await asyncio.wait_for(
                    fut, cfg.breaker_probe_timeout)
                outcome = "answered" if verified else "refused"
            except asyncio.TimeoutError:
                pass
            finally:
                del self._probes[nonce]
            meta["ok"] = outcome == "answered"
        metrics.inc(
            "dds_breaker_probes_total", **self._mlabels(outcome=outcome),
            help="the proxy's own probes of replicas behind an open "
                 "breaker, by what came back",
        )
        return outcome == "answered"

    def _on_probe_reply(self, sender: str, msg: M.TagBatchReply,
                        rnd: _TagRound) -> None:
        """The answer to a probe: a full reply over no keys under the
        replica's MAC, or it is refused, and strikes its sender as a vote
        that was waited for does."""
        if sender not in rnd.asked or rnd.fut.done():
            return
        verified = self._vote_full(msg, rnd) is not None
        if not verified:
            self.replicas.increment_suspicion(sender)
        rnd.fut.set_result(verified)

    async def stop(self) -> None:
        """End the probes: nothing of this client is left pending."""
        tasks = list(self._probe_tasks.values())
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    def breaker_states(self) -> dict[str, str]:
        """Current breaker state per coordinator (for the /health route)."""
        return {n: b.state for n, b in sorted(self.breakers.items())}

    def breaker_census(self) -> tuple[int, list[float]]:
        """(trusted coordinator count, half-open ETAs of the ones whose
        breaker currently refuses traffic) — the breaker-health signal the
        Bulwark shedding controller and the Retry-After derivation read."""
        trusted = self.replicas.get_trusted()
        etas = []
        for n in trusted:
            b = self.breakers.get(n)
            if b is not None and not b.allow():
                etas.append(b.half_open_eta())
        return len(trusted), etas

    def min_half_open_eta(self) -> float | None:
        """Nearest half-open probe among refusing breakers (None = no
        breaker is refusing, or none exist)."""
        _, etas = self.breaker_census()
        positive = [e for e in etas if e > 0]
        return min(positive) if positive else None

    def _coord_failed(self, coord: str) -> None:
        """A coordinator answered with a PROTOCOL VIOLATION: permanent
        suspicion strike (cryptographic evidence, never decays) plus a
        breaker failure (steers the next pick away immediately)."""
        self.replicas.increment_suspicion(coord)
        metrics.inc(
            "dds_coordinator_violations_total", node=coord.rsplit("/", 1)[-1],
            help="protocol violations observed per coordinator",
        )
        tracer.event("abd.coordinator_violation", node=coord)
        self._breaker_failed(coord)

    def _mlabels(self, **labels) -> dict:
        """Metric labels, plus the shard label when this client serves one
        group of a constellation (unsharded series stay label-stable)."""
        if self.cfg.shard:
            labels["shard"] = self.cfg.shard
        return labels

    def _epoch(self) -> int:
        return self.shard_epoch() if self.shard_epoch is not None else -1

    def _check_wrong_shard(self, reply, coord: str, keys: tuple,
                           challenge: int):
        """Validate a WrongShard fence reply for an Envelope op over
        `keys` (one, or a batch's). A valid fence raises WrongShardError
        (no suspicion — the replica behaved correctly); a forged one is a
        protocol violation like any other."""
        if not isinstance(reply, M.WrongShard):
            return
        cfg = self.cfg
        if (
            reply.nonce != challenge
            or reply.key not in keys
            or not sigs.validate_proxy_signature(
                cfg.proxy_mac_secret, reply.key, reply.nonce, reply.signature,
                ["wrong-shard", reply.epoch],
            )
        ):
            self._coord_failed(coord)
            raise ByzInvalidSignatureError(coord)
        self._breaker(coord).record_success()
        raise WrongShardError(reply.key, replica_epoch=reply.epoch,
                              sent_epoch=self._epoch())

    @staticmethod
    def _note_verify(op: str, t0: float) -> None:
        """Record reply-HMAC verification as its own `abd.verify` span —
        Chronoscope's hmac-verify stage, carved out of quorum-rtt so crypto
        cost is never misread as network cost."""
        cur = obs_context.current()
        tracer.record(
            "abd.verify", (time.perf_counter() - t0) * 1e3,
            _ctx=obs_context.child(cur) if cur is not None else None, op=op,
        )

    def _attempt_timeout(self, deadline: Optional[Deadline]) -> float:
        """Per-attempt timeout, clipped to the caller's remaining budget."""
        if deadline is None:
            return self.cfg.request_timeout
        timeout = deadline.timeout(self.cfg.request_timeout)
        if timeout <= 0:
            raise DeadlineExceededError(
                f"no budget left for a quorum attempt ({deadline!r})",
                elapsed=deadline.elapsed(),
            )
        return timeout

    async def _ask(self, call, nonce: int, signature: bytes, exclude=(),
                   deadline: Optional[Deadline] = None, op: str = "ask"):
        # route around open breakers; defer_to falls back to the full
        # trusted set when everything is excluded (a degraded try beats
        # instant failure, and a success closes the breaker again)
        blocked = tuple(n for n, b in self.breakers.items() if not b.allow())
        self._maybe_fast_fail(blocked, deadline, op)
        timeout = self._attempt_timeout(deadline)
        unsettled = self._unsettled()
        pick = self.replicas.defer_to
        if all(n in unsettled for n in self.replicas.get_trusted()):
            # nobody is settled: the degraded try, half-open first
            coordinator = pick(tuple(exclude) + blocked,
                               prefer=self._preferred)
        else:
            # a half-open breaker is its probe's to resolve, not a user's
            # request's: open or half-open, the last choice of all
            coordinator = pick(tuple(exclude) + unsettled,
                               prefer=self._preferred)
            if coordinator in unsettled:
                coordinator = pick(unsettled, prefer=self._preferred)
        challenge = nonce + self.cfg.nonce_increment
        fut: asyncio.Future = asyncio.get_event_loop().create_future()
        self._pending[challenge] = (fut, coordinator)
        t0 = time.perf_counter()
        try:
            self.net.send(
                self.addr, coordinator,
                M.Envelope(call, nonce, signature, epoch=self._epoch()),
            )
            try:
                reply = await asyncio.wait_for(fut, timeout)
            except asyncio.TimeoutError:
                metrics.inc(
                    "dds_quorum_timeouts_total", **self._mlabels(
                        op=op, node=coordinator.rsplit("/", 1)[-1],
                    ),
                    help="quorum rounds that timed out per coordinator",
                )
                metrics.inc(
                    "dds_request_timeouts_total", **self._mlabels(op=op),
                    help="requests that waited out a coordinator, by "
                         "operation",
                )
                # transient unreachability: breaker only — the permanent
                # suspicion counter is reserved for protocol violations, so
                # a healed partition's replicas regain coordination without
                # a restart (deviation from the reference, which struck on
                # every timeout and could never un-strike)
                self._breaker_failed(coordinator)
                raise
            metrics.observe(
                "dds_quorum_rtt_seconds", time.perf_counter() - t0,
                **self._mlabels(op=op),
                help="proxy->coordinator quorum round-trip time",
            )
            return reply, coordinator, challenge
        finally:
            self._pending.pop(challenge, None)

    def _maybe_fast_fail(self, blocked: tuple, deadline: Optional[Deadline],
                         op: str) -> None:
        """Bulwark fast-fail: when EVERY trusted coordinator's breaker is
        refusing traffic and the nearest half-open probe lies beyond the
        caller's remaining budget, no attempt in this request can succeed
        — each would time out against a target the breaker already ruled
        out, and the budget cannot outlive the earliest probe. Degrade NOW
        with the typed error (microseconds) instead of burning the
        Deadline. While any probe still fits the budget the degraded try
        proceeds exactly as before."""
        if not self.cfg.fast_fail_all_open or deadline is None:
            return
        trusted = self.replicas.get_trusted()
        if not trusted or any(n not in blocked for n in trusted):
            return
        eta = min(self.breakers[n].half_open_eta() for n in trusted)
        if eta < deadline.remaining():
            return
        metrics.inc(
            "dds_fast_fail_total", **self._mlabels(op=op),
            help="requests degraded instantly: all coordinator breakers "
                 "open past the remaining budget",
        )
        tracer.event("abd.fast_fail", op=op, eta=round(eta, 4),
                     targets=len(trusted))
        raise AllBreakersOpenError(eta, len(trusted))

    async def fetch_set(self, key: str, deadline: Optional[Deadline] = None):
        """Quorum read; returns the stored set (list) or None."""
        return (await self.fetch_set_tagged(key, deadline=deadline))[0]

    async def fetch_set_tagged(self, key: str, deadline: Optional[Deadline] = None):
        """Quorum read; returns (set|None, tag) — the tag of the value the
        coordinator wrote back, for tag-validated caching."""
        value, tag, _ = await self.fetch_set_attributed(key, deadline=deadline)
        return value, tag

    async def fetch_set_attributed(self, key: str, exclude=(),
                                   deadline: Optional[Deadline] = None):
        """Quorum read; returns (set|None, tag, coordinator). `exclude`
        steers coordinator choice away from given nodes so an audit's
        corroborating re-read goes through a different coordinator than
        the read it is checking. `deadline` clips the attempt to the
        caller's remaining budget."""
        nonce = sigs.generate_nonce()
        sig = sigs.proxy_signature(self.cfg.proxy_mac_secret, key, nonce)
        # validation runs INSIDE the span so a committed op's span carries
        # its audit facts (ok/key/tag) — the Watchtower auditor
        # (obs/watchtower) scopes each op's quorum participants to this
        # span's subtree and checks per-key tag monotonicity from the
        # annotated tag; a failed attempt records the span without `ok`
        # and is never audited as a commit.
        cfg = self.cfg
        with tracer.span("abd.fetch") as span_meta:
            # Atlas fast path: one hop to the in-region lease holder.
            # Skipped when the caller steers coordinators (`exclude` means
            # an audit wants an INDEPENDENT quorum read, not a lease echo);
            # any miss falls through to the quorum round below.
            if cfg.lease_enabled and not exclude:
                local = await self._local_fetch(key, span_meta, deadline)
                if local is not None:
                    return local
            reply, coord, challenge = await self._ask(
                M.IRead(key), nonce, sig, exclude, deadline, op="fetch"
            )
            span_meta["coordinator"] = coord
            self._check_wrong_shard(reply, coord, (key,), challenge)

            match reply:
                case M.Envelope(M.IReadReply(k, value, tag), rnonce, rsig):
                    if rnonce != challenge:
                        self._coord_failed(coord)
                        raise ByzFailedNonceChallengeError(coord)
                    t_v = time.perf_counter()
                    verified = sigs.validate_proxy_signature(
                        cfg.proxy_mac_secret, k, rnonce, rsig,
                        [value, sigs.tag_payload(tag)],
                    )
                    self._note_verify("read", t_v)
                    if not verified:
                        self._coord_failed(coord)
                        raise ByzInvalidSignatureError(coord)
                    if k != key:
                        self._coord_failed(coord)
                        raise ByzInvalidKeyError(coord)
                    self._breaker(coord).record_success()
                    span_meta["ok"] = True
                    span_meta["op"] = "read"
                    span_meta["key"] = key
                    if tag is not None:
                        span_meta["seq"] = tag.seq
                        span_meta["tag_id"] = tag.id
                    return value, tag, coord
                case _:
                    self._coord_failed(coord)
                    raise ByzUnknownReplyError(coord)

    async def fetch_sets_attributed(self, keys, exclude=(),
                                    deadline: Optional[Deadline] = None):
        """Quorum read of many keys as ONE round through one coordinator
        (`IReadBatch`); returns [(set|None, tag, coordinator)] in request
        order. Per key it is `fetch_set_attributed`'s read: the quorum's
        maximum-tag value, at a quorum before it is answered. Breakers,
        the preferred half, `exclude` and `deadline` steer the one
        coordinator as they steer a single read's. An aggregate's re-reads
        come this way; a point operation and a lease read do not."""
        keys = tuple(keys)
        if not keys:
            return []
        cfg = self.cfg
        digest = sigs.key_from_set(list(keys))
        nonce = sigs.generate_nonce()
        sig = sigs.proxy_signature(cfg.proxy_mac_secret, digest, nonce)
        metrics.inc(
            "dds_read_batch_rounds_total", **self._mlabels(),
            help="batched quorum reads (IReadBatch) sent by the proxy",
        )
        # validated INSIDE the span, as `abd.fetch` is: `reads` carries
        # each key's audit facts [key, seq, tag_id] for the Watchtower
        with tracer.span("abd.fetch_batch", k=len(keys)) as span_meta:
            reply, coord, challenge = await self._ask(
                M.IReadBatch(keys), nonce, sig, exclude, deadline,
                op="fetch_batch",
            )
            span_meta["coordinator"] = coord
            self._check_wrong_shard(reply, coord, keys, challenge)

            match reply:
                case M.Envelope(M.IReadBatchReply(replies), rnonce, rsig):
                    if rnonce != challenge:
                        self._coord_failed(coord)
                        raise ByzFailedNonceChallengeError(coord)
                    shaped = isinstance(replies, tuple) and all(
                        isinstance(r, M.IReadReply)
                        and isinstance(r.tag, (M.ABDTag, type(None)))
                        for r in replies)
                    t_v = time.perf_counter()
                    verified = shaped and sigs.validate_proxy_signature(
                        cfg.proxy_mac_secret, digest, rnonce, rsig,
                        [[r.set, sigs.tag_payload(r.tag)] for r in replies],
                    )
                    self._note_verify("read_batch", t_v)
                    if not verified:
                        self._coord_failed(coord)
                        raise ByzInvalidSignatureError(coord)
                    if tuple(r.key for r in replies) != keys:
                        self._coord_failed(coord)
                        raise ByzInvalidKeyError(coord)
                    self._breaker(coord).record_success()
                    span_meta["ok"] = True
                    span_meta["op"] = "read"
                    span_meta["reads"] = [
                        [r.key, r.tag.seq, r.tag.id] for r in replies
                        if r.tag is not None
                    ]
                    return [(r.set, r.tag, coord) for r in replies]
                case _:
                    self._coord_failed(coord)
                    raise ByzUnknownReplyError(coord)

    async def write_set(self, key: str, value,
                        deadline: Optional[Deadline] = None) -> str:
        """Quorum write (value=None removes); returns the key on success."""
        return (await self.write_set_tagged(key, value, deadline=deadline))[0]

    async def write_set_tagged(self, key: str, value,
                               deadline: Optional[Deadline] = None):
        """Quorum write; returns (key, tag) where tag is the tag written."""
        nonce = sigs.generate_nonce()
        sig = sigs.proxy_signature(self.cfg.proxy_mac_secret, key, nonce, value)
        cfg = self.cfg
        with tracer.span("abd.write") as span_meta:
            reply, coord, challenge = await self._ask(
                M.IWrite(key, value), nonce, sig, (), deadline, op="write"
            )
            span_meta["coordinator"] = coord
            self._check_wrong_shard(reply, coord, (key,), challenge)

            match reply:
                case M.Envelope(M.IWriteReply(k, tag), rnonce, rsig):
                    if rnonce != challenge:
                        self._coord_failed(coord)
                        raise ByzFailedNonceChallengeError(coord)
                    t_v = time.perf_counter()
                    verified = sigs.validate_proxy_signature(
                        cfg.proxy_mac_secret, k, rnonce, rsig,
                        sigs.tag_payload(tag),
                    )
                    self._note_verify("write", t_v)
                    if not verified:
                        self._coord_failed(coord)
                        raise ByzInvalidSignatureError(coord)
                    if k != key:
                        self._coord_failed(coord)
                        raise ByzInvalidKeyError(coord)
                    self._breaker(coord).record_success()
                    span_meta["ok"] = True
                    span_meta["op"] = "write"
                    span_meta["key"] = key
                    if tag is not None:
                        span_meta["seq"] = tag.seq
                        span_meta["tag_id"] = tag.id
                    return k, tag
                case _:
                    self._coord_failed(coord)
                    raise ByzUnknownReplyError(coord)

    # ------------------------------------------------- Atlas read-local leases

    def _local_replica(self) -> Optional[str]:
        """The trusted in-region replica eligible to hold our read lease
        (first in trusted order — deterministic for seeded fleets)."""
        cfg = self.cfg
        if not cfg.lease_enabled or not cfg.region or not cfg.replica_regions:
            return None
        for addr in self.replicas.get_trusted():
            name = addr.rsplit("/", 1)[-1]
            region = cfg.replica_regions.get(
                addr, cfg.replica_regions.get(name, ""))
            if region == cfg.region:
                return addr
        return None

    def lease_state(self) -> Optional[dict]:
        """Current lease session for /health: {replica, remaining} or None."""
        lease = self._lease
        if lease is None:
            return None
        remaining = lease["expires"] - self._now()
        if remaining <= 0:
            return None
        return {"replica": lease["replica"], "region": self.cfg.region,
                "remaining": round(remaining, 3)}

    def invalidate_lease(self) -> None:
        """Drop the lease session; the next read goes full-quorum (and may
        re-acquire after the grant backoff)."""
        self._lease = None

    async def _ask_lease(self, target: str, msg, nonce: int, timeout: float):
        """One lease-plane round trip, correlated by request nonce."""
        fut: asyncio.Future = asyncio.get_event_loop().create_future()
        self._pending_lease[nonce] = fut
        try:
            self.net.send(self.addr, target, msg)
            return await asyncio.wait_for(fut, timeout)
        finally:
            self._pending_lease.pop(nonce, None)

    async def _ensure_lease(self) -> Optional[dict]:
        """Grant-or-renew the region read lease. None = no lease available
        right now (no in-region replica, refusal, or inside the backoff)."""
        cfg = self.cfg
        lease, now = self._lease, self._now()
        if lease is not None and now < lease["renew_at"]:
            return lease
        if lease is None and now < self._lease_retry_at:
            return None
        target = self._local_replica()
        if target is None:
            self._lease = None
            return None
        nonce = sigs.generate_nonce()
        sig = sigs.manifest_signature(
            cfg.abd_mac_secret, "lease-request",
            {"region": cfg.region, "ttl": cfg.lease_ttl}, nonce)
        sent_at = now
        try:
            grant = await self._ask_lease(
                target, M.LeaseRequest(cfg.region, cfg.lease_ttl, nonce, sig),
                nonce, cfg.local_read_timeout)
        except asyncio.TimeoutError:
            grant = None
        if (
            not isinstance(grant, M.LeaseGrant)
            or not grant.ok
            or grant.region != cfg.region
            or not sigs.validate_manifest_signature(
                cfg.abd_mac_secret, "lease-grant",
                {"region": grant.region, "replica": grant.replica,
                 "token": grant.token, "expires": grant.expires,
                 "ok": grant.ok}, nonce, grant.signature)
        ):
            self._lease = None
            self._lease_retry_at = self._now() + cfg.lease_renew_margin
            metrics.inc(
                "dds_geo_lease_failures_total", **self._mlabels(),
                help="lease grant/renew attempts that were refused, "
                     "timed out, or failed validation",
            )
            return None
        # expiry measured from SEND time: always conservative vs the
        # holder's own table clock, so we stop using the token strictly
        # before the holder stops honouring it
        self._lease = {
            "target": target,
            "replica": grant.replica,
            "token": grant.token,
            "renew_at": sent_at + cfg.lease_ttl - cfg.lease_renew_margin,
            "expires": sent_at + cfg.lease_ttl,
        }
        return self._lease

    async def _local_fetch(self, key: str, span_meta: dict,
                           deadline: Optional[Deadline]):
        """Lease fast path for one read: single hop to the in-region
        holder. Returns (value, tag, holder) or None — None means "take
        the full quorum path", never an error."""
        cfg = self.cfg
        lease = await self._ensure_lease()
        if lease is None:
            return None
        timeout = cfg.local_read_timeout
        if deadline is not None:
            timeout = min(timeout, deadline.remaining())
            if timeout <= 0:
                return None
        nonce = sigs.generate_nonce()
        sig = sigs.proxy_signature(cfg.proxy_mac_secret, key, nonce,
                                   ["local-read", cfg.region])
        t0 = time.perf_counter()
        try:
            reply = await self._ask_lease(
                lease["target"],
                M.LocalRead(key, cfg.region, lease["token"], nonce, sig,
                            epoch=self._epoch()),
                nonce, timeout)
        except asyncio.TimeoutError:
            # holder unreachable: drop the session (the table-side TTL
            # unpins the group's quorums on its own) and go full-quorum
            self._lease = None
            self._lease_retry_at = self._now() + cfg.lease_renew_margin
            metrics.inc(
                "dds_geo_local_read_fallbacks_total",
                **self._mlabels(reason="timeout"),
                help="lease reads that fell back to a full quorum round",
            )
            return None
        if (
            not isinstance(reply, M.LocalReadReply)
            or reply.key != key
            or not sigs.validate_proxy_signature(
                cfg.proxy_mac_secret, reply.key, reply.nonce, reply.signature,
                [reply.ok, reply.value,
                 sigs.tag_payload(reply.tag) if reply.tag is not None
                 else None])
        ):
            # a garbled/forged local reply is cryptographic evidence like
            # any other protocol violation
            self.replicas.increment_suspicion(lease["target"])
            self._lease = None
            metrics.inc(
                "dds_geo_local_read_fallbacks_total",
                **self._mlabels(reason="invalid"),
                help="lease reads that fell back to a full quorum round",
            )
            return None
        if not reply.ok:
            # typed refusal: the lease was revoked/expired table-side (or
            # the key is fenced) — degrade to full quorum immediately
            self._lease = None
            self._lease_retry_at = self._now() + cfg.lease_renew_margin
            metrics.inc(
                "dds_geo_local_read_fallbacks_total",
                **self._mlabels(reason="refused"),
                help="lease reads that fell back to a full quorum round",
            )
            return None
        metrics.observe(
            "dds_quorum_rtt_seconds", time.perf_counter() - t0,
            **self._mlabels(op="local_read"),
            help="proxy->coordinator quorum round-trip time",
        )
        span_meta["ok"] = True
        span_meta["op"] = "read"
        span_meta["key"] = key
        # Watchtower reads these two: `lease` switches the span from the
        # strict quorum-intersection bound to the documented lease-window
        # invariant, `replica` is what the lease_lookup is checked against
        span_meta["lease"] = True
        span_meta["replica"] = lease["replica"]
        if reply.tag is not None:
            span_meta["seq"] = reply.tag.seq
            span_meta["tag_id"] = reply.tag.id
        return reply.value, reply.tag, lease["target"]

    def _on_wrong_shard_batch(self, sender: str, msg: M.WrongShard) -> None:
        """A replica fenced a ReadTagBatch: the whole round fails with
        WrongShardError (the router re-partitions against a fresh map). A
        forged fence earns the sender a suspicion strike instead."""
        rnd = self._pending_tags[msg.nonce]
        fut = rnd.fut
        if fut.done():
            return
        if (
            msg.key not in rnd.keys
            or not sigs.validate_proxy_signature(
                self.cfg.proxy_mac_secret, msg.key, msg.nonce, msg.signature,
                ["wrong-shard", msg.epoch],
            )
        ):
            self.replicas.increment_suspicion(sender)
            return
        fut.set_exception(WrongShardError(
            msg.key, replica_epoch=msg.epoch, sent_epoch=self._epoch()
        ))

    def _on_tag_batch_reply(self, sender: str, msg: M.TagBatchReply) -> None:
        """Verify one reply and count it: each `_vote_*` returns None for a
        reply to refuse, else (the sender's vector as a diff against the
        round's reference list, the fingerprint it is attested under)."""
        rnd = self._pending_tags[msg.nonce]
        if rnd.fut.done():
            return self._on_late_tag_reply(sender, msg, rnd)
        if sender in rnd.votes:
            return
        rnd.heard.add(sender)
        t0 = time.perf_counter()
        kind, vote = self._verify_vote(sender, msg, rnd)
        rnd.verify_ms += (time.perf_counter() - t0) * 1e3
        if vote is None:
            self.replicas.increment_suspicion(sender)
            return
        rnd.votes[sender], attested = vote
        self._keep_vote(sender, attested, rnd.votes[sender], rnd)
        self._holds_keyset(sender, rnd)
        self._breaker_answered(sender)
        rnd.kinds[kind] += 1
        if kind == "delta":
            metrics.inc(
                "dds_tag_round_delta_entries_total", len(msg.positions),
                **self._mlabels(),
                help="tags carried by accepted delta replies to ReadTagBatch",
            )
        if len(rnd.votes) >= self.cfg.quorum_size:
            rnd.fut.set_result(list(rnd.votes.values()))

    def _verify_vote(self, sender: str, msg: M.TagBatchReply, rnd: _TagRound):
        if msg.unchanged:
            return "unchanged", self._vote_unchanged(msg, rnd)
        if msg.base is not None:
            return "delta", self._vote_delta(sender, msg, rnd)
        return "full", self._vote_full(msg, rnd)

    @staticmethod
    def _keep_vote(sender: str, attested, diff: dict, rnd: _TagRound) -> None:
        kept = rnd.kept
        if attested is not None and kept is not None and kept.gen == rnd.gen:
            # what this sender's vector is now verified to be: made from
            # the record this round named as its base and nothing else, so
            # it is a state the sender attested whichever of two concurrent
            # rounds lands last. Not kept once the reference list was
            # re-based under the round: its diff no longer fits
            kept.senders[sender] = (attested, diff)

    def _on_late_tag_reply(self, sender: str, msg: M.TagBatchReply,
                           rnd: _TagRound) -> None:
        """A vote that comes after its round's quorum moves no answer, but
        it is verified like the others and kept as what the proxy holds of
        that sender: the next round names it as the sender's base and is
        owed a delta. Dropped unverified, the replica that answers last
        (over sockets: any of them) would be named no base round after
        round, and ship, format and MAC all K tags each time for a vote
        nobody counts. A late vote that fails verification is not kept,
        and strikes nobody: it was not waited for."""
        kept = rnd.kept
        if sender in rnd.asked:
            rnd.heard.add(sender)   # whatever it is worth: not silent
        if (
            kept is None or kept.gen != rnd.gen or sender not in rnd.asked
            or sender in rnd.votes or sender in rnd.late
        ):
            return
        rnd.late.add(sender)
        kind, vote = self._verify_vote(sender, msg, rnd)
        if vote is not None:
            diff, attested = vote
            self._keep_vote(sender, attested, diff, rnd)
            self._holds_keyset(sender, rnd)
            self._breaker_answered(sender)
            metrics.inc(
                "dds_tag_round_late_votes_total", **self._mlabels(kind=kind),
                help="ReadTagBatch votes verified and kept after their "
                     "round's quorum was met, by reply kind",
            )
        if len(rnd.votes) + len(rnd.late) >= len(rnd.asked):
            self._close_late(rnd)

    def _close_late(self, rnd: _TagRound) -> None:
        """A round's late window is over: everybody has answered, or
        `MAX_LATE_ROUNDS` newer rounds have met their quorum since. Who of
        the asked sent it nothing of any kind in all that time was silent,
        which is one breaker failure, as a coordinator's timeout is; it
        strikes nobody. A replica the proxy meets only as a participant
        is found dead this way, then skipped and probed like any other."""
        for nonce in rnd.nonces:
            self._late_tags.pop(nonce, None)
        silent = rnd.asked - rnd.heard
        if silent:
            for node in silent.intersection(self.replicas.get_trusted()):
                self._breaker_failed(node)

    def _holds_keyset(self, sender: str, rnd: _TagRound) -> None:
        """A verified vote: its sender holds the keys of the round's
        digest, and is owed no more than the digest from now on."""
        holders = self._keyset_holders.get(rnd.digest)
        if holders is not None:
            holders.add(sender)

    def _on_keyset_unknown(self, sender: str, msg: M.KeySetUnknown,
                           rnd: _TagRound) -> None:
        """A replica holds no keys under the digest a request named (it was
        reseeded, or evicted the set). Authenticated, it is remembered: the
        next request to that sender carries the keys. While the round is
        still waiting, that next request is sent now, to that sender alone
        and under a nonce it has not seen: at most once per replica and
        round. A replica that was sent the keys in this round already
        (first, or in answer to an earlier `unknown`) gets nothing more: it
        gives no vote this round, and is not struck. A forged or wrongly
        MAC'd `unknown` moves nothing."""
        if sender in rnd.asked:
            rnd.heard.add(sender)
        if (
            sender not in rnd.asked
            or msg.digest != rnd.digest
            or not isinstance(msg.signature, bytes)
            or not sigs.validate_abd_keyset_unknown_signature(
                self.cfg.abd_mac_secret, msg.digest, msg.nonce,
                msg.signature)
        ):
            return
        holders = self._keyset_holders.get(rnd.digest)
        if holders is not None:
            holders.discard(sender)
        if rnd.fut.done() or sender in rnd.votes or sender in rnd.carried:
            return
        nonce = sigs.generate_nonce()
        rnd.nonces.append(nonce)
        self._pending_tags[nonce] = rnd
        self._request_tags(rnd, sender, nonce, sigs.proxy_signature(
            self.cfg.proxy_mac_secret, rnd.digest, nonce), carry=True)

    def _request_tags(self, rnd: _TagRound, replica: str, nonce: int,
                      sig: bytes, carry: bool) -> None:
        """One `ReadTagBatch` to one replica under `nonce` and its MAC
        `sig`: the digest and the count always, the keys when `carry`.
        Each replica is named the vector last verified from IT as the
        base of a delta; none kept, none named."""
        if carry:
            rnd.carried.add(replica)
            if type(rnd.keys) is not tuple:
                rnd.keys = tuple(rnd.keys)
        base = rnd.bases.get(replica)
        metrics.inc(
            "dds_tag_round_requests_total",
            **self._mlabels(keys="carried" if carry else "named"),
            help="ReadTagBatch requests sent, by whether they carried the "
                 "keys or named them by digest alone",
        )
        self.net.send(self.addr, replica, M.ReadTagBatch(
            rnd.keys if carry else (), nonce, sig, rnd.fingerprint,
            rnd.epoch, base[0] if base is not None else None,
            rnd.digest, len(rnd.keys)))

    def _holders_for(self, digest: str, trusted: list) -> set:
        """Who of `trusted` has answered for this digest, newest digest
        last; a digest not remembered starts with nobody, so its round
        carries the keys to everyone."""
        holders = self._keyset_holders.pop(digest, None)
        if holders is None:
            while len(self._keyset_holders) >= MAX_NAMED_SETS:
                del self._keyset_holders[next(iter(self._keyset_holders))]
            holders = set()
        else:
            holders.intersection_update(trusted)
        self._keyset_holders[digest] = holders
        return holders

    def _vote_unchanged(self, msg, rnd: _TagRound):
        """"My vector equals the fingerprint you sent": only meaningful
        when we sent one and it matches; MAC covers (fp, digest, nonce)."""
        fp = rnd.fingerprint
        if (
            fp is None
            or msg.fingerprint != fp
            or msg.digest != rnd.digest
            or not sigs.validate_abd_batch_unchanged_signature(
                self.cfg.abd_mac_secret, fp, msg.digest, msg.nonce,
                msg.signature,
            )
        ):
            return None
        return _SAME, fp

    def _vote_full(self, msg, rnd: _TagRound):
        """All K tags: verified by formatting each for the MAC, as ever,
        then held against the round's reference list. Re-anchors what is
        kept of this sender, under the fingerprint of the very bytes the
        MAC covered."""
        if msg.digest != rnd.digest or len(msg.tags) != len(rnd.keys):
            return None
        blob = sigs.tags_blob(msg.tags)
        if not sigs.validate_abd_batch_blob_signature(
            self.cfg.abd_mac_secret, blob, msg.digest, msg.nonce,
            msg.signature,
        ):
            return None
        tags = list(msg.tags)
        if rnd.ref is None:
            rnd.ref = tags
            return _SAME, None
        diff = {i: tags[i] for i in _differing(rnd.ref, tags)}
        return diff, (sigs.blob_fingerprint(blob)
                      if rnd.kept is not None else None)

    def _vote_delta(self, sender: str, msg, rnd: _TagRound):
        """The positions this sender replaced since the vector we last
        verified FROM IT: accepted only against the base this request named
        to this sender, positions in range and strictly ascending, one
        MAC over the entries carried; applied to that sender's record and
        to no other."""
        base = rnd.bases.get(sender)
        positions, tags = msg.positions, msg.tags
        reason = None
        if base is None or msg.base != base[0]:
            reason = "unknown_base"
        elif (
            len(positions) != len(tags)
            or not all(type(p) is int for p in positions)
            or not all(isinstance(t, M.ABDTag) for t in tags)
            or any(b <= a for a, b in zip(positions, positions[1:]))
            or (positions and not (0 <= positions[0]
                                   and positions[-1] < len(rnd.keys)))
        ):
            reason = "bad_positions"
        elif (
            msg.digest != rnd.digest
            or not isinstance(msg.fingerprint, bytes)
            or not sigs.validate_abd_batch_delta_signature(
                self.cfg.abd_mac_secret, base[0], msg.fingerprint,
                positions, tags, msg.digest, msg.nonce, msg.signature)
        ):
            reason = "bad_mac"
        if reason is not None:
            metrics.inc(
                "dds_tag_round_delta_discarded_total",
                **self._mlabels(reason=reason),
                help="delta replies to ReadTagBatch refused, by reason",
            )
            return None
        ref = rnd.ref
        diff = dict(base[1])
        for i, tag in zip(positions, tags):
            if tag is ref[i] or tag == ref[i]:
                diff.pop(i, None)
            else:
                diff[i] = tag
        return diff, msg.fingerprint

    def _kept_for(self, digest: str, fingerprint: bytes, cached_tags: list,
                  trusted: list) -> _KeptVectors:
        """The key set's kept vectors, brought to this round: made anew for
        a key set not kept (or kept under another shard-map epoch, or
        another length), re-based when the caller's list moved, and without
        the senders no longer trusted."""
        epoch = self._epoch()
        kept = self._kept_vectors.pop(digest, None)
        if (
            kept is None
            or kept.epoch != epoch
            or len(kept.ref) != len(cached_tags)
        ):
            while len(self._kept_vectors) >= MAX_TAG_ROUNDS:
                del self._kept_vectors[next(iter(self._kept_vectors))]
            kept = _KeptVectors(cached_tags, fingerprint, epoch)
        elif kept.fingerprint != fingerprint:
            kept.rebase(cached_tags, fingerprint)
        self._kept_vectors[digest] = kept   # newest last
        for gone in kept.senders.keys() - set(trusted):
            del kept.senders[gone]
        return kept

    async def read_tags(
        self,
        keys: list[str],
        digest: str | None = None,
        fingerprint: bytes | None = None,
        cached_tags: list | None = None,
        deadline: Optional[Deadline] = None,
    ) -> list[M.ABDTag]:
        """Batched freshness probe: the quorum-max tag per key via ONE
        tag-only round broadcast by the proxy ITSELF — `ReadTagBatch` fans
        out to every trusted replica, each reply's intranet MAC is verified
        here, and the per-key max is taken over the first `quorum_size`
        valid reply vectors. No single coordinator is trusted: any quorum
        intersects a completed write's quorum in an honest replica, so the
        max can never be deflated below the newest completed write's tag —
        a lying replica can only inflate it, forcing a spurious re-fetch,
        never a stale serve. That argument keys votes by SENDER, so it is
        only as strong as the transport's sender authenticity: in-process
        delivery (InMemoryNet) or per-node mutual TLS on TcpNet; a shared
        frame secret alone does not stop a credentialed replica from
        stuffing the vote with spoofed senders. Cheap because no set
        contents travel — the cache-validation primitive behind the
        proxy's aggregate cache.

        Steady-state fast path: pass `fingerprint` (sha256 of `cached_tags`
        via sigs.tags_fingerprint) and replicas whose vector matches answer
        `unchanged` without shipping K tags; an unchanged vote stands for
        `cached_tags` itself in the quorum max (fingerprint equality is
        vector equality). Deflation-resistance is unchanged — a replica
        hiding a newer completed write behind a false `unchanged` is
        outvoted by the honest quorum-intersection replica, whose full
        reply carries the higher tag. What an unchanged echo DOES hand a
        credentialed liar is a way to confirm the caller's cached vector
        without knowing it — relevant only when that vector already holds
        a tag a Byzantine coordinator planted, a forgery the planter could
        always confirm itself; the caller's audit (not this round) is what
        bounds that class either way. `digest` may be passed in when the
        caller already computed the keys digest (it is part of the request
        MAC either way).

        Between the two, what a round costs follows what moved. With a
        fingerprint the proxy keeps, per key set and per SENDER, the vector
        it last verified from that replica (`_KeptVectors`) and names its
        fingerprint to that replica alone as the request's `base`; a
        replica that remembers the state answers with a `delta`: the
        positions it replaced since, one MAC over those. A full reply
        (first round, a reseeded replica, a base trimmed away) is verified
        as ever and re-anchors its sender. A vote that comes after the
        quorum is verified and kept all the same (`_on_late_tag_reply`),
        so whoever answers last is named its base next time too. Votes are
        held as "`cached_tags`
        except at these positions", and the per-key max runs over the
        union of those positions only; the result is element for element
        the max over the same votes taken whole. Deflation-resistance is
        again unchanged: the honest quorum-intersection replica's chain
        starts at a fully verified reply and every link carries its MAC,
        and a delta is applied to its own sender's record only, so a liar
        can misstate no vote but its own, which it always could.

        What comes back: `cached_tags` ITSELF, by identity, when the
        quorum's max moved no tag of it (every vote "unchanged", or none
        newer): the callers' all-fresh signal. Else a list of the K maxima
        which, in a round with a fingerprint, is a `MergedTags` whose
        `moved` names the positions at which it differs from `cached_tags`
        (the ones the merge wrote, at no cost beyond an append each) and
        which holds `cached_tags`' own objects everywhere else: a caller
        that keeps its own record of what it moved (`OperandTable.stale`)
        decides from those positions alone; one that compares all K still
        can. A round without a fingerprint has no list of the caller's to
        differ from: a plain list, or `moved` None.

        The request names the key set by `digest` (what its MAC covers)
        and carries the K keys only to a replica that has not yet answered
        for that digest with a verified vote (`_keyset_holders`: first
        round, newly trusted, forgotten past `MAX_NAMED_SETS`). A replica
        that holds no keys under the digest says `KeySetUnknown` under its
        MAC and is sent the keys, alone, inside the same round and under a
        nonce of their own, at most once per replica and round
        (`_on_keyset_unknown`). Which form a request takes follows what
        this proxy has observed of that replica and digest, nothing else.
        Deflation-resistance is unchanged once more: a replica adopts
        carried keys only if they hash to the digest the proxy MAC'd, so a
        vote is over the caller's key set or it is no vote; an `unknown`
        is none, and no replica's claim stands in for another's. The one
        lever a Byzantine replica gains is `unknown` every round: it costs
        the proxy one carried request to that replica per round (what
        every replica cost in every round before), never a vote of the
        honest quorum, and strikes nobody, since an honest replica that
        was reseeded or evicted the set says it too, once.

        Who is asked follows the breakers: a trusted replica whose breaker
        is open or half-open is left out of the round (`asked`) while the
        others still number `quorum_size`, and everyone is asked when they
        do not. The quorum a round needs does not move, so a skipped
        replica lowers nothing: it could only have been a vote beyond the
        first `quorum_size`, or none. A breaker opens on a coordinator's
        timeouts (`_ask`) or on `breaker_threshold` rounds in a row that
        met their quorum, kept their late window open to the end and
        heard nothing at all from the replica (`_close_late`); a verified
        vote, late or not, is a breaker success."""
        trusted = self.replicas.get_trusted()
        if len(trusted) < self.cfg.quorum_size:
            raise ByzUnknownReplyError(
                f"only {len(trusted)} trusted replicas < quorum {self.cfg.quorum_size}"
            )
        if fingerprint is not None and cached_tags is None:
            raise ValueError("fingerprint requires cached_tags")
        # the broadcast needs quorum_size replies, so a fabric whose every
        # coordinator breaker is open past the budget is as futile here as
        # for a point op — same fast-fail
        self._maybe_fast_fail(
            tuple(n for n, b in self.breakers.items() if not b.allow()),
            deadline, "read_tags",
        )
        timeout = self._attempt_timeout(deadline)
        nonce = sigs.generate_nonce()
        if digest is None:
            digest = sigs.key_from_set(list(keys))
        fut: asyncio.Future = asyncio.get_event_loop().create_future()
        try:
            with tracer.span("abd.read_tags", k=len(keys)) as rm:
                t0 = time.perf_counter()
                if fingerprint is not None and keys:
                    kept = self._kept_for(digest, fingerprint, cached_tags,
                                          trusted)
                    bases = dict(kept.senders)
                else:
                    kept, bases = None, {}
                # a replica behind a breaker that is not closed is its
                # probe's to ask, while the others can make a quorum: it
                # is sent nothing, carried nothing, owed nothing late
                unsettled = self._unsettled()
                asked = [r for r in trusted if r not in unsettled]
                if len(asked) < self.cfg.quorum_size:
                    asked = trusted
                elif len(asked) < len(trusted):
                    metrics.inc(
                        "dds_tag_round_skipped_total",
                        len(trusted) - len(asked), **self._mlabels(),
                        help="ReadTagBatch requests not sent to a replica "
                             "behind an open breaker",
                    )
                rnd = self._pending_tags[nonce] = _TagRound(
                    fut, digest, keys, fingerprint,
                    cached_tags if kept is not None else None, kept, bases,
                    frozenset(asked), nonce, self._epoch())
                # a replica that has answered for this digest holds its
                # keys and is sent the digest alone; the others (first
                # round, newly trusted, said `unknown`) are sent the keys
                holders = self._holders_for(digest, trusted)
                sig = sigs.proxy_signature(self.cfg.proxy_mac_secret, digest,
                                           nonce)
                for replica in asked:
                    self._request_tags(rnd, replica, nonce, sig,
                                       carry=replica not in holders)
                votes = await asyncio.wait_for(fut, timeout)
                t1 = time.perf_counter()
                metrics.observe(
                    "dds_quorum_rtt_seconds", t1 - t0,
                    **self._mlabels(op="read_tags"),
                    help="proxy->coordinator quorum round-trip time",
                )
                rm.update(rnd.kinds, carried=len(rnd.carried))
                for kind, n in rnd.kinds.items():
                    if n:
                        metrics.inc(
                            "dds_tag_round_votes_total", n,
                            **self._mlabels(kind=kind),
                            help="accepted ReadTagBatch votes by reply kind",
                        )
                tracer.record("abd.read_tags.verify", rnd.verify_ms,
                              _ctx=obs_context.child(), _t_end=t1)
                if not keys:
                    return []
                merged = self._merge_votes(rnd.ref, votes, cached_tags)
                tracer.record(
                    "abd.read_tags.merge", (time.perf_counter() - t1) * 1e3,
                    _ctx=obs_context.child())
                return merged
        finally:
            rnd = self._pending_tags.pop(nonce, None)
            if rnd is not None:
                for extra in rnd.nonces[1:]:
                    self._pending_tags.pop(extra, None)
                if (
                    rnd.kept is not None and fut.done()
                    and not fut.cancelled() and fut.exception() is None
                    and len(rnd.votes) + len(rnd.late) < len(rnd.asked)
                ):
                    # quorum met, replies still owed: open for them a while
                    while len(self._late_tags) >= MAX_LATE_ROUNDS:
                        self._close_late(
                            self._late_tags[next(iter(self._late_tags))])
                    for owed in rnd.nonces:
                        self._late_tags[owed] = rnd

    @staticmethod
    def _merge_votes(ref, votes: list, cached_tags: list | None) -> list:
        """The per-key max over the quorum's votes, each `ref` except at
        the positions of its dict: computed where some vote differs from
        `ref`, `ref`'s own tag elsewhere. The caller's own list BY IDENTITY
        when the max moves nothing (no vote differs, or those that do are
        older): callers use `result is cached_tags` as the all-fresh
        signal. Else a `MergedTags`: a copy of `ref` with the max written
        at the positions it moved, which the copy names (`moved`) when
        `ref` is the caller's list, so that the caller need not compare K
        tags to find them."""
        out, moved = None, []
        for i in set().union(*votes):
            at = ref[i]
            top = max(v.get(i, at) for v in votes)
            if top != at:
                if out is None:
                    out = MergedTags(ref)
                out[i] = top
                moved.append(i)
        if out is None:
            return cached_tags if cached_tags is not None else list(ref)
        out.moved = moved if ref is cached_tags else None
        return out

    def refresh_from(self, supervisor: str) -> None:
        """Ask the supervisor for the freshest active replicas (fire & forget;
        the `ActiveReplicas` reply lands in `handle`)."""
        self.net.send(self.addr, supervisor, M.RequestReplicas())
