"""Tag-read protocol + proxy aggregate-cache tests.

The batched tag-only quorum read (`ReadTagBatch`, broadcast by the proxy
itself) and the proxy's tag-validated aggregate cache replace the
reference's per-aggregate full re-read of every stored set
(`dds/http/DDSRestServer.scala:397-446`). These tests pin the safety
argument: a cached value is served only when the quorum-max tag equals its
cached tag, so external writes are always observed and Byzantine replicas
can at worst force spurious re-fetches.
"""

import asyncio
import json

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core.errors import ByzantineError
from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils import sigs

from tests.test_core import Cluster, run
from tests.test_rest import PROVIDER, call, rest_stack


# ------------------------------------------------------------ protocol level

def test_read_tags_matches_completed_writes():
    async def go():
        c = Cluster()
        await c.client.write_set("k1", [1])
        await c.client.write_set("k2", [2])
        v1, t1 = await c.client.fetch_set_tagged("k1")
        v2, t2 = await c.client.fetch_set_tagged("k2")
        assert v1 == [1] and v2 == [2]
        tags = await c.client.read_tags(["k1", "k2"])
        assert tags == [t1, t2]
        # a new write must advance the quorum-max tag for that key only
        await c.client.write_set("k1", [10])
        tags2 = await c.client.read_tags(["k1", "k2"])
        assert tags2[0] > t1 and tags2[1] == t2

    run(go())


def test_read_tags_unknown_key_is_zero_seq():
    async def go():
        c = Cluster()
        (tag,) = await c.client.read_tags(["never-written"])
        assert tag.seq == 0

    run(go())


def test_write_reply_tag_matches_quorum():
    """The tag returned by write_set_tagged is exactly what a subsequent
    tag read observes (the cache-update invariant)."""

    async def go():
        c = Cluster()
        _, wtag = await c.client.write_set_tagged("k", [7])
        assert wtag is not None and wtag.seq >= 1
        tags = await c.client.read_tags(["k"])
        assert tags == [wtag]

    run(go())


def test_read_tags_resists_tag_deflation_by_credentialed_minority():
    """The attack the coordinator-mediated tag read was vulnerable to: a
    Byzantine minority holding REAL MAC keys under-reports tags, trying to
    make the proxy serve a superseded cached value. read_tags broadcasts
    itself and maxes over a quorum of verified replies, and any quorum
    intersects the completed write's quorum in an honest replica — so the
    deflated vectors can never lower the result."""
    from dds_tpu.utils import sigs as S

    async def go():
        c = Cluster()  # n=7, q=5, f=2
        await c.client.write_set("k", [1])
        await c.client.write_set("k", [2])  # tag seq >= 2 now
        tags = await c.client.read_tags(["k"])
        true_tag = tags[0]
        assert true_tag.seq >= 2

        secret = c.rcfg.abd_mac_secret

        async def deflate(msg):
            if isinstance(msg, M.TagBatchReply):
                zero = (M.ABDTag(0, "forger"),) * len(msg.tags)
                sig = S.abd_batch_signature(secret, zero, msg.digest, msg.nonce)
                return M.TagBatchReply(zero, msg.digest, sig, msg.nonce)
            return msg

        # two credentialed liars deflate every tag reply on the wire
        c.net.link_filters[("replica-5", "proxy-0")] = deflate
        c.net.link_filters[("replica-6", "proxy-0")] = deflate
        for _ in range(10):
            got = await c.client.read_tags(["k"])
            assert got[0] == true_tag  # never deflated below the true max

    run(go())


def test_read_tags_tolerates_byzantine_minority():
    async def go():
        c = Cluster()  # n=7, q=5, f=2
        await c.client.write_set("k", [3])
        _, t = await c.client.fetch_set_tagged("k")
        for addr in ("replica-5", "replica-6"):
            c.replicas[addr].behavior = "byzantine"
        for _ in range(20):  # byzantine coordinator draws raise; honest wins
            try:
                tags = await c.client.read_tags(["k"])
                break
            except (ByzantineError, asyncio.TimeoutError):
                continue
        else:
            raise AssertionError("read_tags never succeeded past byzantine minority")
        assert tags == [t]

    run(go())


def test_tag_messages_serialization_roundtrip():
    msgs = [
        M.ReadTagBatch(("a", "b"), 42, b"\x07"),
        M.ReadTagBatch(("a", "b"), 42, b"\x07", b"\xfe" * 32),
        M.TagBatchReply((M.ABDTag(3, "r2"),), "digest", b"\x01\x02", 42),
        M.TagBatchReply((), "digest", b"\x01", 42, unchanged=True,
                        fingerprint=b"\xaa" * 32),
    ]
    for m in msgs:
        assert M.loads(M.dumps(m)) == m


def test_tags_blob_packing_is_injective():
    """Tag ids come off the wire uncharset-checked: the packed MAC input
    must stay injective even when ids embed the delimiter characters
    (regression: 'seq:id' joined by ';' let two distinct vectors collide)."""
    from dds_tpu.utils import sigs as S

    a = (M.ABDTag(1, "x;9:y"), M.ABDTag(2, "z"))
    b = (M.ABDTag(1, "x"), M.ABDTag(9, "y;2:z"))
    assert S.tags_blob(a) != S.tags_blob(b)
    assert S.tags_fingerprint(a) != S.tags_fingerprint(b)


def test_a_fingerprint_from_kept_fields_is_the_vectors_own():
    """The proxy's operand table keeps one `tag_field` per key and joins
    them: what the replicas hash from their own tags, delimiters in ids
    and the empty vector included."""
    from dds_tpu.utils import sigs as S

    for tags in ((), (M.ABDTag(0, ""),),
                 (M.ABDTag(1, "x;9:y"), M.ABDTag(2, "z"), M.ABDTag(7, ":;")),
                 tuple(M.ABDTag(i, f"proxy-{i % 3}") for i in range(50))):
        fields = [S.tag_field(t) for t in tags]
        assert ";".join(fields).encode() == S.tags_blob(tags)
        assert S.fields_fingerprint(fields) == S.tags_fingerprint(tags)


def test_read_tags_fingerprint_fast_path_identity():
    """Steady state: when every quorum vote is `unchanged`, read_tags
    returns the caller's cached_tags list BY IDENTITY (the all-fresh
    signal) — and after any write the fingerprint no longer matches, so
    the result is a fresh list carrying the advanced tag."""
    from dds_tpu.utils import sigs as S

    async def go():
        c = Cluster()
        await c.client.write_set("k1", [1])
        await c.client.write_set("k2", [2])
        keys = ["k1", "k2"]
        cached = await c.client.read_tags(keys)
        fp = S.tags_fingerprint(cached)
        digest = S.key_from_set(keys)
        got = await c.client.read_tags(
            keys, digest=digest, fingerprint=fp, cached_tags=cached
        )
        assert got is cached  # every replica answered `unchanged`
        await c.client.write_set("k1", [10])
        got2 = await c.client.read_tags(
            keys, digest=digest, fingerprint=fp, cached_tags=cached
        )
        assert got2 is not cached
        assert got2[0] > cached[0] and got2[1] == cached[1]

    run(go())


def test_forged_unchanged_vote_cannot_hide_a_newer_write():
    """A credentialed minority echoing `unchanged` (valid MAC over the
    proxy's own fingerprint) while a newer write completed: the quorum
    intersects the write's quorum in honest replicas whose full replies
    carry the higher tag, so the max still advances."""
    from dds_tpu.utils import sigs as S

    async def go():
        c = Cluster()  # n=7, q=5, f=2
        await c.client.write_set("k", [1])
        keys = ["k"]
        cached = await c.client.read_tags(keys)
        fp = S.tags_fingerprint(cached)
        digest = S.key_from_set(keys)
        secret = c.rcfg.abd_mac_secret

        async def fake_unchanged(msg):
            if isinstance(msg, M.TagBatchReply):
                sig = S.abd_batch_unchanged_signature(
                    secret, fp, msg.digest, msg.nonce
                )
                return M.TagBatchReply((), msg.digest, sig, msg.nonce,
                                       unchanged=True, fingerprint=fp)
            return msg

        c.net.link_filters[("replica-5", "proxy-0")] = fake_unchanged
        c.net.link_filters[("replica-6", "proxy-0")] = fake_unchanged

        await c.client.write_set("k", [2])  # the write the liars try to hide
        for _ in range(10):
            got = await c.client.read_tags(
                keys, digest=digest, fingerprint=fp, cached_tags=cached
            )
            assert got[0] > cached[0]  # never masked by the forged votes

    run(go())


def test_unsolicited_unchanged_vote_is_rejected():
    """An `unchanged` reply when the proxy sent NO fingerprint (or a
    different one) must not count as a vote — otherwise a replica could
    assert equality to a vector nobody named."""
    from dds_tpu.utils import sigs as S

    async def go():
        c = Cluster()
        await c.client.write_set("k", [1])
        secret = c.rcfg.abd_mac_secret

        async def always_unchanged(msg):
            if isinstance(msg, M.TagBatchReply):
                bogus = b"\x99" * 32
                sig = S.abd_batch_unchanged_signature(
                    secret, bogus, msg.digest, msg.nonce
                )
                return M.TagBatchReply((), msg.digest, sig, msg.nonce,
                                       unchanged=True, fingerprint=bogus)
            return msg

        c.net.link_filters[("replica-0", "proxy-0")] = always_unchanged
        tags = await c.client.read_tags(["k"])  # no fingerprint sent
        assert tags[0].seq >= 1
        # the forger earned a strike, honest replicas carried the quorum
        assert c.client.replicas._strikes["replica-0"] >= 1

    run(go())


def test_crafted_column_values_stay_opaque():
    """Stored set contents are client data: codec markers inside them must
    survive as plain data, never be decoded as protocol objects (that would
    crash or transform messages in the receive path before MAC checks)."""
    row = [1, {"__msg__": "nope"}, {"__tag__": [5, "x"]}, {"__b64__": "AA=="}]
    env = M.Envelope(M.IWrite("k", row), 1, b"s")
    assert M.loads(M.dumps(env)) == env


def test_unauthenticated_tag_batch_is_ignored():
    """A ReadTagBatch without a valid proxy MAC gets no reply and burns no
    anti-replay nonce (else unauthenticated traffic could enumerate tags
    and grow the nonce set without bound)."""

    async def go():
        c = Cluster()
        await c.client.write_set("k", [1])
        target = c.replicas["replica-0"]
        before = dict(target.incoming)
        got = []
        c.net.register("intruder", lambda s, m: (got.append(m), asyncio.sleep(0))[1])
        c.net.send("intruder", "replica-0", M.ReadTagBatch(("k",), 999, b"bogus"))
        await c.net.quiesce()
        assert got == []
        assert target.incoming == before

    run(go())


def test_unauthenticated_tag_batch_cannot_evict_memo_cache():
    """The replica's kept tag vectors are probed read-only before the
    proxy MAC verifies and built, patched or evicted only after:
    unauthenticated traffic with rotating bogus key sets must neither grow
    the table nor evict or patch the hot vector of the legitimate
    aggregate."""

    async def go():
        c = Cluster()
        await c.client.write_set("k", [1])
        await c.client.read_tags(["k"])  # builds each replica's vector
        await c.client.write_set("k", [2])  # logged, not yet patched in
        await c.net.quiesce()
        target = c.replicas["replica-0"]
        before = dict(target._tag_vectors)
        assert before  # the legit vector is resident
        vec = before[sigs.key_from_set(["k"])]
        state = (vec.seen, vec.tags[:], vec.fingerprint,
                 target._stored_since[:])
        assert state[3] == ["k"]
        c.net.register("intruder", lambda s, m: asyncio.sleep(0))
        for i in range(12):  # > the cache's eviction bound
            c.net.send(
                "intruder", "replica-0",
                M.ReadTagBatch((f"bogus-{i}",) * 4, 1000 + i, b"bad"),
            )
        await c.net.quiesce()
        assert target._tag_vectors == before
        assert (vec.seen, vec.tags, vec.fingerprint,
                target._stored_since) == state

    run(go())


def test_read_tags_fails_fast_below_quorum():
    async def go():
        c = Cluster()
        await c.client.write_set("k", [1])
        for r in ("replica-0", "replica-1", "replica-2"):
            for _ in range(3):
                c.client.replicas.increment_suspicion(r)
        try:
            await c.client.read_tags(["k"])
        except ByzantineError:
            return
        raise AssertionError("read_tags should fail fast below quorum")

    run(go())


def test_in_transit_tag_substitution_is_rejected():
    """Reply tags are covered by the proxy HMAC: an attacker on the
    replica->proxy channel who swaps in a guessed (predictable) tag must
    trigger ByzInvalidSignatureError, not poison the tag-validated cache."""
    from dataclasses import replace

    async def go():
        c = Cluster()
        await c.client.write_set("k", [1])

        async def swap_tag(msg):
            if isinstance(msg, M.Envelope):
                inner = msg.call
                if isinstance(inner, (M.IReadReply, M.IWriteReply)) and inner.tag:
                    forged = M.ABDTag(inner.tag.seq + 1, inner.tag.id)
                    return replace(msg, call=replace(inner, tag=forged))
            return msg

        c.net.link_filters["proxy-0"] = swap_tag
        for op in (lambda: c.client.fetch_set_tagged("k"),
                   lambda: c.client.write_set_tagged("k", [2])):
            try:
                await op()
            except ByzantineError:
                continue
            raise AssertionError("forged reply tag was accepted")

    run(go())


def test_read_skips_writeback_when_quorum_agrees():
    """Standard ABD read optimization: when every quorum member reports the
    same (tag, value), the value is already at a full quorum and the read
    answers without the write-back phase; a divergent member still triggers
    the repairing write-back."""

    async def go():
        c = Cluster()
        await c.client.write_set("k", [1])
        await c.net.quiesce()
        writes = []
        orig_send = c.net.send

        def counting_send(src, dest, msg):
            if isinstance(msg, M.Write):
                writes.append((src, dest))
            orig_send(src, dest, msg)

        c.net.send = counting_send
        v, t = await c.client.fetch_set_tagged("k")
        assert v == [1]
        assert writes == []  # all replicas agreed: no write-back round

        # a lagging replica (stale tag) forces the repair write-back
        lagger = c.replicas["replica-3"]
        lagger.repository["k"] = (M.ABDTag(0, lagger.name), None)
        lagger.repo_version += 1
        for _ in range(10):  # until the lagger lands in the read quorum
            writes.clear()
            v, t2 = await c.client.fetch_set_tagged("k")
            assert v == [1] and t2 == t
            if writes:
                break
        else:
            raise AssertionError("divergent replica never triggered write-back")
        await c.net.quiesce()
        assert lagger.repository["k"][1] == [1]  # repaired

    run(go())


def test_defer_to_exclusion_picks_a_different_coordinator():
    """The audit's corroborating re-read must not land on the coordinator
    it is checking: defer_to(exclude) avoids it whenever another trusted
    node exists, and only falls back when no alternative remains."""
    from dds_tpu.utils.trust import TrustedNodesList

    t = TrustedNodesList(["a", "b", "c"])
    assert all(t.defer_to(exclude=("a",)) != "a" for _ in range(50))
    t2 = TrustedNodesList(["a"])
    assert t2.defer_to(exclude=("a",)) == "a"  # fallback, not a crash


# --------------------------------------------------------------- proxy level

def _count_fetches(server):
    """Wrap the proxy's quorum reads so tests can count the keys read
    through full ABD quorums (`n`; single reads and the keys of batched
    ones alike) and the batched rounds they took (`rounds`)."""
    counter = {"n": 0, "rounds": 0}
    orig = server.abd.fetch_set_attributed
    orig_batch = server.abd.fetch_sets_attributed

    async def counted(key, exclude=(), deadline=None):
        counter["n"] += 1
        return await orig(key, exclude, deadline=deadline)

    async def counted_batch(keys, exclude=(), deadline=None):
        counter["n"] += len(keys)
        counter["rounds"] += 1
        return await orig_batch(keys, exclude, deadline=deadline)

    server.abd.fetch_set_attributed = counted
    server.abd.fetch_sets_attributed = counted_batch
    return counter


def test_aggregate_cache_serves_warm_and_sees_external_writes():
    async def go():
        async with rest_stack() as (server, replicas, _):
            server.cfg.aggregate_cache_audit = 0  # counting pure cache hits
            pk = PROVIDER.keys.psse.public
            vals = [11, 22, 33]
            keys = []
            for v in vals:
                row = PROVIDER.encrypt_row([v], 1, ["PSSE"])
                _, key = await call(server, "POST", "/PutSet", {"contents": row})
                keys.append(key.decode())
            counter = _count_fetches(server)
            target = f"/SumAll?position=0&nsqr={pk.nsquare}"

            # cold-ish: PutSet already cached each row, so zero full fetches
            _, data = await call(server, "GET", target)
            assert PROVIDER.keys.psse.decrypt(int(json.loads(data)["result"])) == sum(vals)
            assert counter["n"] == 0

            # external writer (another proxy's quorum client) bumps one key
            other = AbdClient(
                "proxy-ext", server.abd.net, list(replicas),
                AbdClientConfig(request_timeout=2.0),
            )
            new_row = PROVIDER.encrypt_row([100], 1, ["PSSE"])
            await other.write_set(keys[0], new_row)

            # tag validation must spot exactly that one stale key
            _, data = await call(server, "GET", target)
            got = PROVIDER.keys.psse.decrypt(int(json.loads(data)["result"]))
            assert got == 100 + 22 + 33
            assert counter["n"] == 1

            # steady state again: all fresh, no fetches
            _, data = await call(server, "GET", target)
            assert counter["n"] == 1

    asyncio.run(go())


def test_audit_costs_exactly_sample_size_fetches():
    """The audit's own cost is pinned: a warm aggregate performs exactly
    min(aggregate_cache_audit, cached-keys) full quorum reads — no more."""

    async def go():
        async with rest_stack() as (server, _, _):
            pk = PROVIDER.keys.psse.public
            vals = [1, 2, 3]
            for v in vals:
                row = PROVIDER.encrypt_row([v], 1, ["PSSE"])
                await call(server, "POST", "/PutSet", {"contents": row})
            counter = _count_fetches(server)
            target = f"/SumAll?position=0&nsqr={pk.nsquare}"
            assert server.cfg.aggregate_cache_audit == 2  # default under test
            for i in (1, 2):
                _, data = await call(server, "GET", target)
                assert (
                    PROVIDER.keys.psse.decrypt(int(json.loads(data)["result"]))
                    == sum(vals)
                )
                assert counter["n"] == 2 * i

    asyncio.run(go())


def test_audit_detects_forged_cache_entry_and_flushes():
    """A forged cached value at the TRUE tag (what a Byzantine coordinator
    holding the proxy MAC secret could plant) is caught by the audit: the
    re-read mismatches at the SAME tag, the cache is flushed, and the
    aggregate is computed from quorum reads only."""

    async def go():
        async with rest_stack() as (server, _, _):
            pk = PROVIDER.keys.psse.public
            vals = [11, 22, 33]
            keys = []
            for v in vals:
                row = PROVIDER.encrypt_row([v], 1, ["PSSE"])
                _, key = await call(server, "POST", "/PutSet", {"contents": row})
                keys.append(key.decode())
            # audit the whole cache so the poisoned key is sampled for sure
            server.cfg.aggregate_cache_audit = len(keys)
            tag, _ = server._cache[keys[0]]
            forged_row = PROVIDER.encrypt_row([999], 1, ["PSSE"])
            server._cache[keys[0]] = (tag, forged_row)

            target = f"/SumAll?position=0&nsqr={pk.nsquare}"
            _, data = await call(server, "GET", target)
            got = PROVIDER.keys.psse.decrypt(int(json.loads(data)["result"]))
            assert got == sum(vals)  # forgery did not reach the result
            # flush: every pre-flush entry (incl. audit refills) was dropped
            assert server._cache == {}

    asyncio.run(go())


def test_audit_benign_concurrent_write_refreshes_without_flush():
    """A write landing between the tag-validation round and the audit
    re-read mismatches at a strictly NEWER tag — the audit must refresh
    that entry and serve the new value, not flush the whole cache."""

    async def go():
        async with rest_stack() as (server, replicas, _):
            pk = PROVIDER.keys.psse.public
            vals = [11, 22, 33]
            keys = []
            for v in vals:
                row = PROVIDER.encrypt_row([v], 1, ["PSSE"])
                _, key = await call(server, "POST", "/PutSet", {"contents": row})
                keys.append(key.decode())
            server.cfg.aggregate_cache_audit = len(keys)

            # freeze the validation round at the pre-write tags, simulating
            # the race where read_tags completes just before the write lands
            stale_tags = {k: server._cache[k][0] for k in keys}

            async def frozen_read_tags(ks, **_kw):
                return [stale_tags[k] for k in ks]

            server.abd.read_tags = frozen_read_tags
            other = AbdClient(
                "proxy-ext3", server.abd.net, list(replicas),
                AbdClientConfig(request_timeout=2.0),
            )
            await other.write_set(keys[0], PROVIDER.encrypt_row([100], 1, ["PSSE"]))

            target = f"/SumAll?position=0&nsqr={pk.nsquare}"
            _, data = await call(server, "GET", target)
            got = PROVIDER.keys.psse.decrypt(int(json.loads(data)["result"]))
            assert got == 100 + 22 + 33  # the audit's newer value is served
            # no flush: all keys still cached, bumped key at its new tag
            assert set(server._cache) == set(keys)
            assert server._cache[keys[0]][0] > stale_tags[keys[0]]

    asyncio.run(go())


async def _stored_rows(server, vals):
    keys = []
    for v in vals:
        row = PROVIDER.encrypt_row([v], 1, ["PSSE"])
        _, key = await call(server, "POST", "/PutSet", {"contents": row})
        keys.append(key.decode())
    return keys


async def _sum(server):
    pk = PROVIDER.keys.psse.public
    _, data = await call(server, "GET",
                         f"/SumAll?position=0&nsqr={pk.nsquare}")
    return PROVIDER.keys.psse.decrypt(int(json.loads(data)["result"]))


@pytest.mark.parametrize("stale,audit", [(0, 2), (1, 2), (3, 2), (2, 0),
                                         (0, 0)])
def test_an_aggregates_rereads_are_one_batched_round(stale, audit):
    """The stale keys and the audit's sample go to ONE coordinator as one
    `IReadBatch`: `stale + audit` keys through full quorums, one round,
    no single read; with nothing to re-read, no round at all."""

    async def go():
        async with rest_stack() as (server, replicas, _):
            server.cfg.aggregate_cache_audit = audit
            vals = [11, 22, 33, 44, 55, 66]
            keys = await _stored_rows(server, vals)
            assert await _sum(server) == sum(vals)
            other = AbdClient(
                "proxy-ext", server.abd.net, list(replicas),
                AbdClientConfig(request_timeout=2.0),
            )
            for i in range(stale):
                vals[i] += 100
                await other.write_set(
                    keys[i], PROVIDER.encrypt_row([vals[i]], 1, ["PSSE"]))
            counter = _count_fetches(server)
            singles = []
            orig = server.abd.fetch_set_attributed

            async def single(key, exclude=(), deadline=None):
                singles.append(key)
                return await orig(key, exclude, deadline=deadline)

            server.abd.fetch_set_attributed = single
            rounds = metrics.value("dds_read_batch_rounds_total") or 0.0
            assert await _sum(server) == sum(vals)
            want = stale + audit
            assert counter == {"n": want, "rounds": 1 if want else 0}
            assert singles == []
            assert (metrics.value("dds_read_batch_rounds_total") or 0.0
                    ) - rounds == (1 if want else 0)

    asyncio.run(go())


@pytest.mark.parametrize("cap,n_keys", [(1, 3), (2, 5), (4, 4), (4, 9)])
def test_the_flush_after_a_forged_audit_rereads_in_capped_batches(
        cap, n_keys, monkeypatch):
    """A planted value at the true tag still flushes the cache, and the
    flush re-reads every other key in batches of at most `REREAD_BATCH`,
    gathered: ceil(remaining / cap) rounds more, the answer from quorum
    reads only."""
    import dds_tpu.http.server as srv

    async def go():
        async with rest_stack() as (server, _, _):
            monkeypatch.setattr(srv, "REREAD_BATCH", cap)
            vals = list(range(10, 10 + n_keys))
            keys = await _stored_rows(server, vals)
            server.cfg.aggregate_cache_audit = 1
            # every entry planted, so whichever key the audit draws fails
            for k in keys:
                tag, _ = server._cache[k]
                server._cache[k] = (
                    tag, PROVIDER.encrypt_row([999], 1, ["PSSE"]))
            server._table = None
            counter = _count_fetches(server)
            sizes = []
            inner = server.abd.fetch_sets_attributed

            async def sized(ks, exclude=(), deadline=None):
                sizes.append(len(ks))
                return await inner(ks, exclude, deadline=deadline)

            server.abd.fetch_sets_attributed = sized
            assert await _sum(server) == sum(vals)
            rest = n_keys - 1
            assert counter["n"] == n_keys
            assert sorted(sizes) == sorted(
                [1] + [cap] * (rest // cap) + ([rest % cap] if rest % cap
                                               else []))
            assert max(sizes) <= cap
            # flushed: the cache holds the reads made after the flush and
            # nothing from before it (the audited key's refill went too)
            assert len(server._cache) == rest

    asyncio.run(go())


def test_the_audits_corroboration_is_a_single_read_through_another_coordinator():
    """A newer (value, tag) from the audited batch is corroborated by ONE
    single read that excludes the coordinator the batch went through."""

    async def go():
        async with rest_stack() as (server, replicas, _):
            vals = [11, 22, 33]
            keys = await _stored_rows(server, vals)
            server.cfg.aggregate_cache_audit = len(keys)
            stale_tags = {k: server._cache[k][0] for k in keys}

            async def frozen_read_tags(ks, **_kw):
                return [stale_tags[k] for k in ks]

            server.abd.read_tags = frozen_read_tags
            other = AbdClient(
                "proxy-ext4", server.abd.net, list(replicas),
                AbdClientConfig(request_timeout=2.0),
            )
            await other.write_set(
                keys[0], PROVIDER.encrypt_row([100], 1, ["PSSE"]))
            batch_coords, singles = [], []
            inner = server.abd.fetch_sets_attributed
            orig = server.abd.fetch_set_attributed

            async def batch(ks, exclude=(), deadline=None):
                out = await inner(ks, exclude, deadline=deadline)
                batch_coords.append(out[0][2])
                return out

            async def single(key, exclude=(), deadline=None):
                out = await orig(key, exclude, deadline=deadline)
                singles.append((key, tuple(exclude), out[2]))
                return out

            server.abd.fetch_sets_attributed = batch
            server.abd.fetch_set_attributed = single
            assert await _sum(server) == 100 + 22 + 33
            assert len(batch_coords) == 1
            assert [(k, ex) for k, ex, _ in singles] == [
                (keys[0], (batch_coords[0],))]
            assert singles[0][2] != batch_coords[0]
            assert set(server._cache) == set(keys)      # no flush

    asyncio.run(go())


def test_under_a_read_lease_rereads_stay_single_reads():
    """A lease read is one hop to the holder already: `_reread` batches
    nothing where `lease_enabled` is set."""

    async def go():
        async with rest_stack() as (server, _, _):
            vals = [1, 2, 3]
            await _stored_rows(server, vals)
            server.abd.cfg.lease_enabled = True
            counter = _count_fetches(server)
            assert await _sum(server) == sum(vals)
            assert counter == {"n": 2, "rounds": 0}

    asyncio.run(go())


@pytest.mark.parametrize("name,steady,writer", [
    ("quorum.reread_rounds_per_agg", 1.0, 1.0),
    ("quorum.reread_written_back_share", 0.0, 25.0),
])
def test_the_yardsticks_reread_metrics_read_the_batch_counters(
        name, steady, writer):
    """`yardstick/layers/<name>.json` is data for a reducer the yardstick
    has, listed for all six cells: one round an aggregate; no key written
    back while the store stands still, and the share of keys the
    coordinator wrote back (one of four here) once replicas disagree."""
    import importlib
    import os
    from types import SimpleNamespace

    from yardstick.run import Window

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "yardstick", "layers", f"{name}.json")) as f:
        spec = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert {k: entry[k] for k in ("unit", "better", "moves", "layer")} == {
        k: spec[k] for k in ("unit", "better", "moves", "layer")}
    assert (spec["moves"], spec["layer"]) == ("agg_p50_ms", "quorum round")
    reduce = importlib.import_module(
        f"yardstick.reducers.{spec['reducer']}").reduce

    async def go():
        async with rest_stack(n=4, quorum=3) as (server, replicas, _):
            server.abd.cfg.quorum_size = 3
            vals = [1, 2, 3, 4]
            keys = await _stored_rows(server, vals)
            await server.abd.net.quiesce()
            assert await _sum(server) == sum(vals)

            async def window(n_aggs):
                w = Window({}, "test", {})
                w.open({name: spec})
                try:
                    for _ in range(n_aggs):
                        assert await _sum(server) == sum(vals)
                        w.ops.append(SimpleNamespace(kind="aggregate",
                                                     status=200))
                    return reduce(w, **spec["args"])
                finally:
                    w.close()

            assert await window(3) == pytest.approx(steady)
            # two of four replicas trail on one key: every quorum of three
            # disagrees on it, and the audit reads all four keys
            server.cfg.aggregate_cache_audit = len(keys)
            for r in list(replicas.values())[:2]:
                r.repository[keys[0]] = (M.ABDTag(0, r.name), None)
            assert await window(1) == pytest.approx(writer)

    asyncio.run(go())


def test_aggregate_cache_disabled_refetches_everything():
    async def go():
        async with rest_stack() as (server, _, _):
            server.cfg.aggregate_cache = False
            pk = PROVIDER.keys.psse.public
            vals = [5, 6]
            for v in vals:
                row = PROVIDER.encrypt_row([v], 1, ["PSSE"])
                await call(server, "POST", "/PutSet", {"contents": row})
            counter = _count_fetches(server)
            target = f"/SumAll?position=0&nsqr={pk.nsquare}"
            for i in (1, 2):
                _, data = await call(server, "GET", target)
                assert (
                    PROVIDER.keys.psse.decrypt(int(json.loads(data)["result"]))
                    == sum(vals)
                )
                assert counter["n"] == len(vals) * i  # reference behavior

    asyncio.run(go())


def test_cached_aggregate_reads_are_atomic():
    """The tag-validated cache path must preserve the atomic-register
    properties under concurrent writers: no reads from the future, no
    new/old inversion (same checker as tests/test_linearizability.py)."""

    import random
    import time

    from dds_tpu.http.server import DDSRestServer, ProxyConfig
    from dds_tpu.utils.retry import retry
    from tests.test_linearizability import (
        KEY, Recorder, _writer, check_atomic_register,
    )

    async def go():
        c = Cluster()
        rng = random.Random(11)
        rec = Recorder()
        server = DDSRestServer(
            AbdClient(
                "proxy-lin", c.net, c.active, AbdClientConfig(request_timeout=1.0)
            ),
            ProxyConfig(),
        )
        server.stored_keys.add(KEY)
        t0 = time.monotonic()
        await c.client.write_set(KEY, ["init"])
        rec.record("write", "init", t0, time.monotonic())

        async def cached_reader(n):
            for _ in range(n):
                t0 = time.monotonic()
                pairs = await retry(server._fetch_stored, 0.01, 5)
                v = pairs[0][1][0] if pairs else None
                rec.record("read", v, t0, time.monotonic())
                await asyncio.sleep(rng.uniform(0, 0.002))

        await asyncio.gather(
            _writer(c, rec, 0, 25, random.Random(1)),
            _writer(c, rec, 1, 25, random.Random(2)),
            cached_reader(60),
            cached_reader(60),
        )
        check_atomic_register(rec.ops)
        reads = [o for o in rec.ops if o["kind"] == "read"]
        assert any(o["value"] is not None for o in reads)

    run(go())


def test_search_routes_use_validated_cache():
    """Order/Search routes share _fetch_stored: results stay correct when
    served from the validated cache after an external write."""

    async def go():
        async with rest_stack() as (server, replicas, _):
            rows = {v: PROVIDER.encrypt_row([v], 1, ["OPE"]) for v in (1, 2, 3)}
            keys = {}
            for v, row in rows.items():
                _, key = await call(server, "POST", "/PutSet", {"contents": row})
                keys[v] = key.decode()
            _, data = await call(server, "GET", "/OrderSL?position=0")
            assert json.loads(data)["keyset"] == [keys[1], keys[2], keys[3]]

            other = AbdClient(
                "proxy-ext2", server.abd.net, list(replicas),
                AbdClientConfig(request_timeout=2.0),
            )
            await other.write_set(keys[1], PROVIDER.encrypt_row([9], 1, ["OPE"]))
            _, data = await call(server, "GET", "/OrderSL?position=0")
            assert json.loads(data)["keyset"] == [keys[2], keys[3], keys[1]]

    asyncio.run(go())


def test_codec_roundtrip_fuzz():
    """Randomized wire-codec roundtrips: every message type with random
    field content (incl. protocol-marker-shaped client data inside stored
    sets) survives dumps/loads exactly."""
    import random

    rng = random.Random(99)

    def rand_value():
        pool = [
            rng.getrandbits(64),
            str(rng.getrandbits(128)),
            None,
            True,
            {"__msg__": "nope"},
            {"__tag__": [1, "x"]},
            {"__b64__": "AA=="},
            [rng.getrandbits(16), "s", None],
        ]
        return rng.choice(pool)

    def rand_set():
        return [rand_value() for _ in range(rng.randrange(0, 5))]

    def rand_tag():
        return M.ABDTag(rng.getrandbits(32), f"replica-{rng.randrange(9)}")

    for _ in range(200):
        sig = bytes(rng.getrandbits(8) for _ in range(rng.randrange(0, 33)))
        nonce = rng.getrandbits(63)
        key = str(rng.getrandbits(256))
        msgs = [
            M.Envelope(M.IWrite(key, rand_set()), nonce, sig),
            M.Envelope(M.IRead(key), nonce, sig),
            M.Envelope(M.IReadReply(key, rand_set(), tag=rand_tag()), nonce, sig),
            M.TagReply(rand_tag(), key, rand_set(), sig, nonce),
            M.Write(rand_tag(), key, rand_set(), sig, nonce),
            M.ReadReply(rand_tag(), key, rand_set(), sig, nonce),
            M.ReadTagBatch(tuple(str(rng.getrandbits(64)) for _ in range(3)),
                           nonce, sig, bytes(32) if rng.random() < 0.5 else None),
            M.TagBatchReply(tuple(rand_tag() for _ in range(3)), key, sig,
                            nonce, unchanged=rng.random() < 0.5,
                            fingerprint=bytes(32)),
            M.Suspect(f"host:1/{key[:8]}", nonce),
            M.State({key: {"tag": [1, "r"], "value": rand_set()}}, [nonce]),
            M.Sleep({key: {"tag": [2, "r"], "value": None}}, [nonce, nonce + 1]),
            M.ActiveReplicas([f"h:{i}/r-{i}" for i in range(3)]),
            M.Redeploy(f"h:1/{key[:6]}"),
            M.Redeployed(f"h:1/{key[:6]}"),
        ]
        m = msgs[rng.randrange(len(msgs))]
        assert M.loads(M.dumps(m)) == m


def test_audit_persistence_bound_monte_carlo():
    """Quantify the audit knob (r4 verdict #8): a planted forged cache
    entry survives until an aggregate round (a) samples it into the audit
    AND (b) the audit's random coordinator is honest. Detection is
    geometric with p = (audit/K) * (n-f)/n, so expected persistence is
    K/audit * n/(n-f) rounds. Monte Carlo at the documented operating
    point (K=8192, audit=2, n=4, f=1 -> ~5461) must match within 5%."""
    import numpy as np

    K, AUDIT, N, F = 8192, 2, 4, 1
    rng = np.random.default_rng(42)
    trials = 20_000
    # per round, two independent events: the forged key lands in the audit
    # sample (P = AUDIT/K exactly, for a uniform sample w/o replacement)
    # and the audit read's random coordinator is honest (P = (N-F)/N)
    remaining = np.arange(trials)
    rounds = np.zeros(trials, np.int64)
    block = 4096
    while remaining.size:
        sampled = rng.random((remaining.size, block)) < AUDIT / K
        honest = rng.integers(0, N, (remaining.size, block)) >= F
        hit = sampled & honest
        first = hit.argmax(axis=1)
        found = hit.any(axis=1)
        rounds[remaining[found]] += first[found] + 1
        rounds[remaining[~found]] += block
        remaining = remaining[~found]
    mean = rounds.mean()
    expect = K / AUDIT * N / (N - F)   # 5461.33
    assert abs(mean - expect) / expect < 0.05, (mean, expect)
    # scaling sanity: audit=8 cuts expected persistence 4x
    p2 = (AUDIT / K) * (N - F) / N
    p8 = (8 / K) * (N - F) / N
    assert abs((1 / p8) / (1 / p2) - 0.25) < 1e-9
