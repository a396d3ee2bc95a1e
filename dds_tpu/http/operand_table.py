"""The aggregate's operand table: what `SumAll` / `MultAll` fold, kept
across aggregates and patched by the rows that moved.

One table per stored key set (`OperandTable`, built by
`http/server._sync_table` when `_stored_version` moved or the cache was
flushed). By position in the sorted key list it holds the key, the
`(tag, value)` entry the proxy's cache held when the position was last
patched (the very tuple, no copy), and one `OperandColumn` per aggregated
position `pos`: the parsed python ints of `value[pos]`, as an `Operands`
list nobody mutates once it is handed to a fold, and the `RowTrack` by
which a resident pool finds the rows of the positions that changed and
of no others (`resident/pool.rows_for`).

An aggregate moves it by `apply([(position, entry)])` alone: O(changed
rows) of list stores, `int()` parses and one copy of K pointers per
patched column. A key set that only grew is the old table plus its new
keys (`OperandTable.grown`): copies of the old lists with the new rows
spliced in at their sorted positions, each kept column carried with only
the new rows parsed, and its pool rows carried with only the new
positions to look up. What stays O(K) per changed version runs in C over
whole lists: the copy of the tag vector and the join and hash of its fields
for the replicas' tag round (`round_args`). In Python: the `[(key,
value)]` list of the routes that still read pairs (`pairs`), built when
one of them asks. Which rows the tag round left unconfirmed (`stale`) is
decided at the positions that moved: the quorum's, which the round's
reply names, and the table's own since the round was made, which it logs
by version (`moved_since`). One pass of K tag compares is left for the
rounds in which one of the two is not known.
"""

from __future__ import annotations

from bisect import bisect_left, bisect_right
from collections import deque
from itertools import chain, islice

import numpy as np

from dds_tpu.obs.metrics import metrics
from dds_tpu.resident.pool import Operands, RowTrack
from dds_tpu.utils import sigs

# columns kept per table: one per distinct `position` clients aggregate
# over; past it the oldest goes (a request for it again parses it again)
MAX_COLUMNS = 8
# versions back that a table remembers which positions each moved, for the
# tag rounds in flight over it (`moved_since`; an aggregate bumps the
# version up to four times), and the most positions one version may move
# and be remembered: past either, `stale` compares all K tags
MOVED_VERSIONS = 64
MOVED_ROWS = 1024


def _spliced(old: list, at: list[int], items: list) -> list:
    """A copy of `old` with `items[t]` put before `old[at[t]]` (`at`
    ascending): slices in C and one append an item, `old` untouched."""
    out, prev = [], 0
    for b, item in zip(at, items):
        out += old[prev:b]
        out.append(item)
        prev = b
    out += old[prev:]
    return out


class OperandColumn:
    """`value[pos]` of every row that has one, parsed, in key order."""

    __slots__ = ("pos", "where", "operands", "track", "shown")

    def __init__(self, pos: int, entries: list):
        self.pos = pos
        ops: list[int] = []
        where: list[int] = []   # table position -> index in `ops`, -1: no operand
        for e in entries:
            v = e[1] if e is not None else None
            if v is not None and pos < len(v):
                where.append(len(ops))
                ops.append(int(v[pos]))
            else:
                where.append(-1)
        # every row has the column (the usual store): positions are indices
        self.where = where if len(ops) < len(entries) else None
        self.track = RowTrack()
        self.operands = Operands(ops, self.track)
        # the version last handed out; None: none of this column yet (it
        # was carried from the table before, `grown`)
        self.shown: Operands | None = None

    def grown(self, at: list[int], added: list) -> OperandColumn | None:
        """This column with the entries `added` put before table positions
        `at` (ascending), as a new column: only their operands are parsed,
        and this one is left as it is. None when an added row has no
        operand here or holds what `int()` refuses, as `patch` says False.

        The new column's `RowTrack` hands every pool that resolved this
        one its index array with room made at the inserted positions, and
        a log of this one's positions the slowest of those pools has yet
        to look up, moved to where they are now, then the inserted ones:
        `ResidentPool.rows_for` looks those up and no others."""
        pos, parsed = self.pos, []
        for e in added:
            v = e[1] if e is not None else None
            if v is None or pos >= len(v):
                return None
            try:
                parsed.append(int(v[pos]))
            except (TypeError, ValueError):
                return None
        old, where = self.operands, self.where
        if where is None:
            at_ops = at
        else:
            where, n = _spliced(where, at, [0] * len(at)), 0
            for i, w in enumerate(where):
                if w >= 0:
                    where[i], n = n, n + 1
            # each inserted operand's index, as among the old operands
            at_ops = [where[b + t] - t for t, b in enumerate(at)]
        col = object.__new__(OperandColumn)
        col.pos, col.where, col.shown = pos, where, None
        track = col.track = RowTrack()
        known = list(old.track.rows.values())
        if known:
            base = min(r[2] for r in known)
            track.log = [j + bisect_right(at_ops, j)
                         for j in old.track.log[base:old.version]]
            for pool, epoch, version, idx in known:
                track.rows[id(pool)] = (
                    pool, epoch, version - base, np.insert(idx, at_ops, 0))
        track.log.extend(b + t for t, b in enumerate(at_ops))
        col.operands = Operands(_spliced(old, at_ops, parsed), track)
        return col

    def patch(self, updates: list) -> bool:
        """Take `[(table position, entry)]` in. False when a row gained or
        lost its operand, or holds what `int()` refuses: this column cannot
        describe that, and its owner drops it (the request that wants it
        next parses it whole, and is the one to raise)."""
        pos, where = self.pos, self.where
        ops, changed = None, []
        for i, e in updates:
            v = e[1] if e is not None else None
            j = i if where is None else where[i]
            if (v is not None and pos < len(v)) != (j >= 0):
                return False
            if j < 0:
                continue
            try:
                new = int(v[pos])
            except (TypeError, ValueError):
                return False
            if new != (self.operands if ops is None else ops)[j]:
                if ops is None:
                    ops = Operands(self.operands, self.track)
                ops[j] = new
                changed.append(j)
        if ops is not None:
            if len(self.track.log) > max(4096, 2 * len(ops)):
                # the log outgrew the column: a look-up of all of it costs
                # less than the log, so start a new one
                self.track = ops.track = RowTrack()
            self.track.log.extend(changed)
            ops.version = len(self.track.log)
            self.operands = ops
        return True


def _tags_fields(entries: list) -> tuple[list, list]:
    """Each entry's tag, and its field of the fingerprint."""
    tags = [e[0] if e is not None else None for e in entries]
    return tags, [sigs.tag_field(t) if t is not None else None for t in tags]


class OperandTable:
    def __init__(self, keys: list[str], cache: dict, stored_version: int,
                 _lists: tuple | None = None):
        self.keys = keys                      # sorted, never changed
        self.stored_version = stored_version
        self.index = dict(zip(keys, range(len(keys))))
        # entries[i]: the cache's (tag, value) tuple of keys[i]; None while
        # the key was never read; (None, value) for a read the cache would
        # not keep (cache off, no tag): served this round, stale the next
        # tags, fields: each entry's tag and its field of the fingerprint,
        # patched with the entry: the tag round's vector is then a copy and
        # a join
        if _lists is None:    # `grown` brings its own
            entries = [cache.get(k) for k in keys]
            _lists = (entries, *_tags_fields(entries))
        self.entries, self.tags, self.fields = _lists
        self.uncached = self.entries.count(None)
        self.version = 0                      # bumps when an entry moves
        self.settled = -1     # the version an aggregate last patched up to
        # the positions each of the last versions moved, newest last
        self._moved: deque[list[int]] = deque(maxlen=MOVED_VERSIONS)
        self.columns: dict[int, OperandColumn] = {}
        self._digest: str | None = None
        self._round: tuple | None = None
        self._pairs: tuple | None = None

    @classmethod
    def grown(cls, old: OperandTable, added, cache: dict,
              stored_version: int) -> OperandTable | None:
        """The table of `old`'s keys and the keys `added` (none of them
        `old`'s), equal to one built anew from a cache whose entries of
        `old`'s keys are the ones `old` holds: copies of its lists with the
        added keys' entries spliced in at their sorted positions, a new
        index, and each column carried by `OperandColumn.grown` (one that
        cannot take an added row is left out: the request that wants it
        parses it whole). O(K) in C and O(added) in Python. `old`, its
        lists and its columns are left as they are: an aggregate that
        began on it finishes on it. None when `old` holds an entry without
        a tag, which a table built anew would not hold."""
        if old.uncached and old.uncached != old.entries.count(None):
            return None
        keys = sorted(added)
        at = [bisect_left(old.keys, k) for k in keys]
        entries = [cache.get(k) for k in keys]
        tags, fields = _tags_fields(entries)
        table = cls(_spliced(old.keys, at, keys), cache, stored_version, (
            _spliced(old.entries, at, entries), _spliced(old.tags, at, tags),
            _spliced(old.fields, at, fields)))
        for pos, col in old.columns.items():
            if (col := col.grown(at, entries)) is not None:
                table.columns[pos] = col
        return table

    def apply(self, updates: list) -> int:
        """Replace entries by `[(position, entry)]`, skipping those already
        there; returns how many moved. Each column parses the moved rows'
        operands, and those alone."""
        entries = self.entries
        moved = [(i, e) for i, e in updates if entries[i] is not e]
        if not moved:
            return 0
        for i, e in moved:
            old = entries[i]
            self.uncached += ((e is None or e[0] is None)
                              - (old is None or old[0] is None))
            entries[i] = e
            tag = self.tags[i] = e[0] if e is not None else None
            self.fields[i] = sigs.tag_field(tag) if tag is not None else None
        self.version += 1
        if len(moved) > MOVED_ROWS:
            self._moved.clear()     # the log starts anew after this version
        else:
            self._moved.append([i for i, _ in moved])
        for pos in [p for p, c in self.columns.items() if not c.patch(moved)]:
            del self.columns[pos]
        if self.columns:
            metrics.inc(
                "dds_operand_table_rows_total",
                len(moved) * len(self.columns), outcome="patched",
                help="rows whose operand was parsed into a kept column",
            )
        return len(moved)

    def round_args(self) -> tuple:
        """(version, positions, keys, digest, fingerprint, tags) of the
        entries that hold a tag, for `AbdClient.read_tags`; positions is
        None when every key does; None when no key does (no round to
        make). One copy, one join and one hash per version of the table;
        the keys digest once per table."""
        r = self._round
        if r is not None and r[0] == self.version:
            return r
        if self.uncached == len(self.keys):
            return None
        if self.uncached:
            at = [i for i, e in enumerate(self.entries)
                  if e is not None and e[0] is not None]
            keys = [self.keys[i] for i in at]
            digest = sigs.key_from_set(keys)
            tags = [self.tags[i] for i in at]
            fp = sigs.tags_fingerprint(tags)
        else:
            if self._digest is None:
                self._digest = sigs.key_from_set(self.keys)
            at, keys, digest = None, self.keys, self._digest
            tags = self.tags[:]
            fp = sigs.fields_fingerprint(self.fields)
        self._round = (self.version, at, keys, digest, fp, tags)
        return self._round

    def moved_since(self, version: int) -> list[int] | None:
        """The positions `apply` moved since the table was at `version`
        (one may come twice); None when the log no longer reaches back
        that far."""
        log = self._moved
        n = self.version - version
        if n > len(log):
            return None
        return list(chain.from_iterable(islice(log, len(log) - n, None)))

    def stale(self, sent: tuple | None, reply) -> tuple[list[int], str]:
        """(the positions whose entry the tag round did not confirm, the
        path that found them): those that held no tag when the round `sent`
        was made, and those whose quorum-max tag in `reply` (aligned with
        `sent`) is not the tag the entry holds now, so that a write
        completed after the round is re-read.

        `unchanged`: none, at no cost, when every vote said "unchanged"
        (`reply` is the list sent): entries that moved since come from
        completed operations and are newer than the round. `positions`,
        O(positions moved): the reply names where it differs from the list
        sent (`MergedTags.moved`) and the log reaches back to the version
        the round was made at (`moved_since`). At every other position the
        entry is the one the round was made from and the reply holds that
        entry's own tag, so the comparison is made at those positions
        alone and finds what the pass over K would, in its order. `full`,
        that pass, one comparison a row: no round or a failed one (`sent`
        is None: everything is re-read), a round over some of the rows
        (entries without a tag), a reply that names no positions (a round
        without a fingerprint, one merged over shard groups), a log
        trimmed past the round's version.
        `dds_operand_table_validate_total{path}` counts the two that look
        at entries."""
        entries = self.entries
        if sent is None:     # no round, or it failed: everything is re-read
            out, path = list(range(len(entries))), "full"
        else:
            version, at, _, _, _, tags = sent
            if at is None and reply is tags:
                return [], "unchanged"
            voted = getattr(reply, "moved", None) if at is None else None
            since = self.moved_since(version) if voted is not None else None
            if since is not None:
                rows, path = sorted({*voted, *since}), "positions"
                held = zip(rows, map(reply.__getitem__, rows))
            else:
                path = "full"
                held = zip(range(len(entries)) if at is None else at, reply)
            out = [] if reply is tags else [
                i for i, t in held
                if (e := entries[i]) is None or e[0] is not t and e[0] != t
            ]
            if at is not None:
                had = set(at)
                out.extend(i for i in range(len(entries)) if i not in had)
        metrics.inc(
            "dds_operand_table_validate_total", path=path,
            help="tag rounds held against the table, by how the "
                 "unconfirmed rows were found",
        )
        return out, path

    def pairs(self) -> list[tuple[str, list]]:
        """`[(key, value)]` of the rows that hold a value, in key order,
        for the routes that read whole rows; the same list object until an
        entry moves (the identity the per-tenant and per-shard memos
        match on)."""
        p = self._pairs
        if p is None or p[0] != self.version:
            p = self._pairs = (self.version, [
                (k, e[1]) for k, e in zip(self.keys, self.entries)
                if e is not None and e[1] is not None
            ])
        return p[1]

    def column(self, pos: int) -> tuple[Operands, str]:
        """(operands at `pos`, outcome): `reused` when it is the list
        handed out last time, `patched` when rows were parsed into it
        since, `grown` when it was carried from the table before this one
        with the added rows parsed in, `rebuilt` when every row had to be
        parsed (a first request for `pos` on a table built anew). `int()`
        raises here, for the request that asked, as it always did."""
        col = self.columns.get(pos)
        if col is None:
            col = OperandColumn(pos, self.entries)
            while len(self.columns) >= MAX_COLUMNS:
                del self.columns[next(iter(self.columns))]
            self.columns[pos] = col
            outcome = "rebuilt"
        elif col.shown is None:
            outcome = "grown"
        else:
            outcome = "reused" if col.shown is col.operands else "patched"
        col.shown = col.operands
        return col.operands, outcome
