"""`OperandTable.stale` decides at the positions that moved, and finds what
the pass over all K rows finds.

The tag round's reply names the positions the quorum's max moved
(`MergedTags.moved`, written by the real `AbdClient._merge_votes`), the
table logs the positions each version moved (`moved_since`), and `stale`
compares there alone. Held here, under random tables, applies, votes and
rounds, to the K-row comparison the table made before it knew either,
kept below as the plain reference: the same positions in the same order,
whichever path ran, and the path the test expects from what it did to the
table, said by the return value and by
`dds_operand_table_validate_total{path}`.
"""

import random

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core.quorum_client import AbdClient, MergedTags
from dds_tpu.http import operand_table
from dds_tpu.http.operand_table import OperandTable
from dds_tpu.obs.metrics import metrics

PATHS = ("positions", "full")


def plain_stale(entries, sent, reply):
    """The K-row pass: every row of the round whose entry does not hold
    the reply's tag, then every row the round did not cover. None when
    every vote said "unchanged" (the reply is the list sent)."""
    k = len(entries)
    if sent is None:
        return list(range(k))
    _, at, _, _, _, tags = sent
    rows = list(range(k)) if at is None else list(at)
    out = []
    if reply is not tags:
        assert len(reply) == len(rows)
        for j, i in enumerate(rows):
            if entries[i] is None or entries[i][0] != reply[j]:
                out.append(i)
    covered = set(rows)
    return out + [i for i in range(k) if i not in covered]


def counted():
    return {p: metrics.value("dds_operand_table_validate_total", path=p) or 0
            for p in PATHS}


class Tracked:
    """A table, and how far back its log must reach by what the test did
    to it: `reach` is the oldest version a round may have been made at
    and still be answered from positions."""

    def __init__(self, table):
        self.table = table
        self.reach = 0

    def apply(self, updates):
        before = self.table.version
        moved = self.table.apply(updates)
        assert self.table.version == before + (1 if moved else 0)
        if moved > operand_table.MOVED_ROWS:
            self.reach = self.table.version
        elif moved:
            self.reach = max(
                self.reach, self.table.version - operand_table.MOVED_VERSIONS)
        return moved


class Round:
    def __init__(self, tracked, fingerprint):
        self.on = tracked
        self.sent = tracked.table.round_args()
        self.version = tracked.table.version
        self.fingerprint = fingerprint
        self.touched = []       # table positions applied while it was open


class Rig:
    def __init__(self, seed, k, untagged=False):
        self.rng = rng = random.Random(seed)
        self.k = k
        self.cache = {}
        keys = [f"k{i:05d}" for i in range(k)]
        for key in keys:
            if not (untagged and rng.random() < 0.15):
                self.cache[key] = (M.ABDTag(rng.randrange(1, 9), "c0"), ["v"])
        self.untagged = untagged
        self.on = Tracked(OperandTable(keys, self.cache, 1))
        self.open: list[Round] = []
        self.seen = set()

    # -- what happens to a table ------------------------------------------

    def entry_after(self, old, fresh=False):
        """An entry a completed operation could leave at a row; `fresh`:
        one that is not the entry there."""
        rng = self.rng
        if fresh:
            return (M.ABDTag(old[0].seq + 1, "f"), ["f"])
        if self.untagged and rng.random() < 0.2:
            return rng.choice([None, (None, ["u"])])
        tag = old[0] if old is not None and old[0] is not None else None
        if tag is None:
            return (M.ABDTag(rng.randrange(1, 9), "c1"), ["n"])
        roll = rng.random()
        if roll < 0.15:
            return old                           # the very entry: no move
        if roll < 0.3:
            return (M.ABDTag(tag.seq, tag.id), ["same"])    # equal, distinct
        return (M.ABDTag(tag.seq + rng.randrange(1, 3), rng.choice("ab")),
                ["w"])

    def apply_some(self, tracked=None, rows=None, fresh=False):
        tracked = tracked or self.on
        table = tracked.table
        n = len(table.entries)
        rows = rows if rows is not None else self.rng.sample(
            range(n), self.rng.randrange(0, min(n, 6)))
        tracked.apply([(i, self.entry_after(table.entries[i], fresh))
                       for i in rows])
        for rnd in self.open:
            if rnd.on is tracked:
                rnd.touched.extend(rows)

    def begin(self, fingerprint=True):
        rnd = Round(self.on, fingerprint)
        self.open.append(rnd)
        return rnd

    def grow(self, added):
        keys = [f"g{self.rng.randrange(10 ** 6):06d}" for _ in range(added)]
        for key in keys:
            self.cache[key] = (M.ABDTag(1, "g"), ["g"])
        grown = OperandTable.grown(self.on.table, keys, self.cache, 2)
        assert grown is not None
        self.on = Tracked(grown)

    # -- a round's end -----------------------------------------------------

    def votes(self, rnd):
        """Three votes over the round's list: a few positions each, drawn
        from anywhere and from the rows written while it was open, holding
        a newer tag, an older one, the list's own by another object, or
        the very tag the table holds now."""
        rng = self.rng
        _, at, _, _, _, tags = rnd.sent
        rows = list(range(len(tags))) if at is None else at
        where = {i: j for j, i in enumerate(rows)}
        touched = [where[i] for i in rnd.touched if i in where]
        out = []
        for _ in range(3):
            vote = {}
            for _ in range(rng.choice([0, 0, 1, 2, 4])):
                j = (rng.choice(touched) if touched and rng.random() < 0.5
                     else rng.randrange(len(tags)))
                ref, now = tags[j], rnd.on.table.entries[rows[j]]
                roll = rng.random()
                if roll < 0.4 and now is not None and now[0] is not None:
                    tag = rng.choice([now[0], M.ABDTag(now[0].seq, now[0].id)])
                elif roll < 0.7:
                    tag = M.ABDTag(ref.seq + rng.randrange(1, 4), "q")
                elif roll < 0.85:
                    tag = M.ABDTag(ref.seq, ref.id)
                else:
                    tag = M.ABDTag(max(0, ref.seq - 1), ref.id)
                vote[j] = tag
            out.append(vote)
        return out

    def end(self, rnd, failed=False):
        self.open.remove(rnd)
        table = rnd.on.table
        sent, reply = rnd.sent, None
        if failed or sent is None:
            sent, want_path = None, "full"
        else:
            tags = sent[5]
            # a round with a fingerprint votes against the caller's list; one
            # without against the first full reply, a list of its own
            ref = tags if rnd.fingerprint else list(tags)
            reply = AbdClient._merge_votes(ref, self.votes(rnd), tags)
            if reply is tags and sent[1] is None:
                want_path = "unchanged"
            elif (sent[1] is None and rnd.fingerprint
                  and rnd.version >= rnd.on.reach):
                want_path = "positions"
            else:
                want_path = "full"
            if reply is not tags:
                assert isinstance(reply, MergedTags)
                assert (reply.moved is not None) == rnd.fingerprint
        want = plain_stale(list(table.entries), sent, reply)
        before = counted()
        got, path = table.stale(sent, reply)
        assert got == want
        assert path == want_path
        after = counted()
        assert {p: after[p] - before[p] for p in PATHS} == {
            p: int(p == path) for p in PATHS}
        self.seen.add(path)
        return got


def test_merge_votes_names_the_positions_it_moved():
    ref = [M.ABDTag(3, "a") for _ in range(8)]
    votes = [{1: M.ABDTag(4, "a"), 2: M.ABDTag(2, "a")},
             {1: M.ABDTag(5, "b"), 6: M.ABDTag(3, "a")}, {}]
    got = AbdClient._merge_votes(ref, votes, ref)
    assert got.moved == [1] and got[1] == M.ABDTag(5, "b")
    assert all(got[i] is ref[i] for i in range(8) if i != 1)
    # nothing newer: the caller's own list, as ever
    assert AbdClient._merge_votes(ref, [{2: M.ABDTag(2, "a")}, {}, {}],
                                  ref) is ref
    # held against another list than the caller's: no positions to name
    other = AbdClient._merge_votes(list(ref), votes, None)
    assert other == got and other.moved is None


@pytest.mark.parametrize("seed", range(6))
def test_rounds_over_a_tagged_table_are_decided_at_positions(seed):
    rig = Rig(seed, k=200)
    for _ in range(40):
        rig.apply_some()
        rnd = rig.begin()
        rig.apply_some()
        rig.apply_some()
        rig.end(rnd)
        rig.apply_some()
    assert rig.seen <= {"positions", "unchanged"} and "positions" in rig.seen


@pytest.mark.parametrize("seed", range(6))
def test_entries_without_a_tag_take_the_full_pass(seed):
    rig = Rig(seed, k=120, untagged=True)
    for _ in range(40):
        rig.apply_some()
        rnd = rig.begin()
        rig.apply_some()
        got = rig.end(rnd)
        if rnd.sent is not None and rnd.sent[1] is not None:
            assert set(range(120)) - set(rnd.sent[1]) <= set(got)
    assert "full" in rig.seen


@pytest.mark.parametrize("seed", range(4))
def test_a_table_that_gains_its_tags_moves_to_positions(seed):
    rig = Rig(seed, k=64, untagged=True)
    rig.untagged = False
    table = rig.on.table
    rnd = rig.begin()
    rig.end(rnd)
    rig.apply_some(rows=[i for i, e in enumerate(table.entries)
                         if e is None or e[0] is None])
    assert table.uncached == 0
    for _ in range(10):
        rnd = rig.begin()
        rig.apply_some()
        rig.end(rnd)
    assert "positions" in rig.seen


@pytest.mark.parametrize("seed", range(4))
def test_a_round_without_a_fingerprint_or_a_failed_one_takes_the_full_pass(
        seed):
    rig = Rig(seed, k=150)
    for n in range(30):
        rnd = rig.begin(fingerprint=n % 3 != 0)
        rig.apply_some()
        got = rig.end(rnd, failed=n % 5 == 4)
        if n % 5 == 4:
            assert got == list(range(150))
    assert rig.seen >= {"positions", "full"}


@pytest.mark.parametrize("seed", range(6))
def test_two_rounds_open_at_different_versions_on_one_table(seed):
    rig = Rig(seed, k=200)
    for n in range(30):
        first = rig.begin()
        rig.apply_some()
        second = rig.begin()
        assert second.version >= first.version
        rig.apply_some()
        rig.apply_some()
        for rnd in ((first, second) if (n + seed) % 2 else (second, first)):
            rig.end(rnd)
            rig.apply_some()
    assert rig.seen <= {"positions", "unchanged"}


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("limit", ["versions", "rows"])
def test_a_log_trimmed_past_the_round_takes_the_full_pass(limit, seed,
                                                           monkeypatch):
    if limit == "versions":
        monkeypatch.setattr(operand_table, "MOVED_VERSIONS", 4)
    else:
        monkeypatch.setattr(operand_table, "MOVED_ROWS", 8)
    rig = Rig(seed, k=100)
    for n in range(40):
        old = rig.begin()
        if limit == "versions":
            for _ in range(rig.rng.randrange(0, 8)):
                rig.apply_some()
        else:
            rows = rig.rng.sample(range(100), rig.rng.choice([3, 8, 9, 30]))
            rig.apply_some(rows=rows)
        young = rig.begin()
        rig.apply_some()
        # the log may reach the younger round where it has lost the older
        rig.end(old)
        rig.end(young)
    assert rig.seen >= {"positions", "full"}


def test_the_limits_are_the_modules_own():
    """Trimmed by the limits as they stand, not by the test's small ones."""
    rig = Rig(0, k=operand_table.MOVED_ROWS + 200)
    rnd = rig.begin()
    for _ in range(operand_table.MOVED_VERSIONS):
        rig.apply_some(rows=[rig.rng.randrange(rig.k)])
    rig.end(rnd)
    assert rig.seen == {"positions"}
    rnd = rig.begin()
    for _ in range(operand_table.MOVED_VERSIONS + 1):
        rig.apply_some(rows=[rig.rng.randrange(rig.k)], fresh=True)
    rig.end(rnd)
    assert rig.seen == {"positions", "full"}
    rnd, rig.seen = rig.begin(), set()
    rig.apply_some(rows=list(range(operand_table.MOVED_ROWS)), fresh=True)
    rig.end(rnd)
    assert rig.seen == {"positions"}
    rnd = rig.begin()
    rig.apply_some(rows=list(range(operand_table.MOVED_ROWS + 1)), fresh=True)
    rig.end(rnd)
    assert rig.seen == {"positions", "full"}
    assert len(rig.on.table._moved) == 0


@pytest.mark.parametrize("seed", range(4))
def test_a_table_replaced_by_grown_mid_round(seed):
    rig = Rig(seed, k=160)
    for _ in range(12):
        rig.apply_some()
        rnd = rig.begin()
        rig.apply_some()
        old = rig.on
        rig.grow(added=rig.rng.randrange(1, 6))
        assert rig.on.table.version == 0 and not rig.on.table._moved
        # the new table has its own rounds and its own log ...
        young = rig.begin()
        rig.apply_some()
        # ... and the round that began on the old one finishes there,
        # where the re-read rows are still applied
        rig.apply_some(tracked=old)
        rig.end(rnd)
        rig.end(young)
    assert rig.seen <= {"positions", "unchanged"} and "positions" in rig.seen
