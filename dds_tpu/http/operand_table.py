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
patched column. What stays O(K) per changed version runs in C over whole
lists: the copy of the tag vector and the join and hash of its fields for
the replicas' tag round (`round_args`). In Python: one pass of tag
compares when the quorum saw a tag move (`stale`), and the `[(key,
value)]` list of the routes that still read pairs (`pairs`), built when
one of them asks.
"""

from __future__ import annotations

from dds_tpu.obs.metrics import metrics
from dds_tpu.resident.pool import Operands, RowTrack
from dds_tpu.utils import sigs

# columns kept per table: one per distinct `position` clients aggregate
# over; past it the oldest goes (a request for it again parses it again)
MAX_COLUMNS = 8


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
        self.shown: Operands | None = None   # the version last handed out

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


class OperandTable:
    def __init__(self, keys: list[str], cache: dict, stored_version: int):
        self.keys = keys                      # sorted, never changed
        self.stored_version = stored_version
        self.index = {k: i for i, k in enumerate(keys)}
        # entries[i]: the cache's (tag, value) tuple of keys[i]; None while
        # the key was never read; (None, value) for a read the cache would
        # not keep (cache off, no tag): served this round, stale the next
        self.entries: list = [cache.get(k) for k in keys]
        self.uncached = sum(1 for e in self.entries if e is None)
        # each entry's tag and its field of the fingerprint, patched with
        # the entry: the tag round's vector is then a copy and a join
        self.tags: list = [e[0] if e is not None else None
                           for e in self.entries]
        self.fields: list = [sigs.tag_field(t) if t is not None else None
                             for t in self.tags]
        self.version = 0                      # bumps when an entry moves
        self.settled = -1     # the version an aggregate last patched up to
        self.columns: dict[int, OperandColumn] = {}
        self._digest: str | None = None
        self._round: tuple | None = None
        self._pairs: tuple | None = None

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

    def stale(self, sent: tuple | None, reply) -> list[int]:
        """Positions whose entry the tag round did not confirm: those that
        held no tag when the round `sent` was made, and those whose
        quorum-max tag in `reply` (aligned with `sent`) is not the tag the
        entry holds now, so that a write completed after the round is
        re-read. One pass of comparisons; none when every vote said
        "unchanged" (`reply` is the list sent): entries that moved since
        come from completed operations and are newer than the round."""
        entries = self.entries
        if sent is None:     # no round, or it failed: everything is re-read
            return list(range(len(entries)))
        _, at, _, _, _, tags = sent
        out = [] if reply is tags else [
            i for i, t in zip(range(len(entries)) if at is None else at, reply)
            if (e := entries[i]) is None or e[0] is not t and e[0] != t
        ]
        if at is not None:
            had = set(at)
            out.extend(i for i in range(len(entries)) if i not in had)
        return out

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
        since, `rebuilt` when every row had to be parsed (a first request
        for `pos` on this table). `int()` raises here, for the request
        that asked, as it always did."""
        col = self.columns.get(pos)
        if col is None:
            col = OperandColumn(pos, self.entries)
            while len(self.columns) >= MAX_COLUMNS:
                del self.columns[next(iter(self.columns))]
            self.columns[pos] = col
            outcome = "rebuilt"
        else:
            outcome = "reused" if col.shown is col.operands else "patched"
        col.shown = col.operands
        return col.operands, outcome
