"""Lodestone resident pools: per-group device-pinned ciphertext limb pools.

A `ResidentPool` is the content-addressed `(rows, L)` uint32 limb buffer
one shard group keeps in device memory for one modulus: the unsharded
backend keeps one a modulus (`TpuBackend.store_for`), the Constellation
one a group. Each distinct ciphertext *value* is ingested once
(int -> 16-bit limbs -> device row); every subsequent aggregate gathers
resident rows on-device instead of re-marshaling host ints per fold —
the memory-residency move the HE-accelerator literature scales by (BTS,
arxiv 2112.15479; HEAAN-demystified, arxiv 2003.04510).

Content addressing (ciphertext int -> row) is what keeps the
dependability story intact: the proxy still performs full ABD quorum
reads per aggregate — the pool only memoizes the transfer/limb-conversion
of bytes the device has already seen, so a stale row cannot exist by
construction; the quorum read decides WHICH ciphertexts fold.

Capacity grows by doubling up to `max_rows`. Past that the behavior
depends on whether a tier sink is wired (`spill`, set by Stratum —
dds_tpu/storage): with one, the pool EVICTS its coldest rows to the
warm tier (coldest-first order from `evict_rank`, the directory's
decayed popularity) and keeps serving the fused fast path for the rows
that stay — the fast path degrades gradually instead of cliff-dropping.
Without a sink the legacy RESET remains (entries re-ingest on demand,
`epoch` bumps, every kept row-index array is void) — simple, and an
aggregate after a reset pays exactly the one-time ingest cost again,
never wrong results; the reset now also files a `resident_reset` flight
incident and stamps `last_reset_ts` so /health surfaces the silent
fast-path loss instead of burying it in a log line.

Placement: `sharding` optionally pins the buffer device-side (a
`NamedSharding` built by `parallel/mesh.group_sharding` maps group i to
its slice of the mesh); None — the single-device fallback — is today's
default-placed buffer.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from dds_tpu.obs import context as obs_context
from dds_tpu.obs import kprof
from dds_tpu.obs.metrics import metrics
from dds_tpu.ops import bignum as bn
from dds_tpu.ops.montgomery import ModCtx
from dds_tpu.utils.trace import tracer

log = logging.getLogger("dds.resident")


class RowTrack:
    """Where one operand column's rows are in the pools that folded it.

    The column's owner appends to `log` every position it changes (append
    only, so a version of the column is a length of `log`); `rows` keeps,
    per pool, the index array one version was last resolved to. A pool
    resolves another version by looking up the positions logged between
    the two and no others. Appends and slices are atomic under the
    interpreter lock; a stored triple is always right for its version."""

    __slots__ = ("log", "rows")

    def __init__(self):
        self.log: list[int] = []
        self.rows: dict[int, tuple] = {}  # id(pool) -> (pool, epoch, version, idx)


class Operands(list):
    """One version of an operand column as a fold receives it: a list
    nobody mutates once it is handed out, with the `track` of its column
    and its `version` there. A list made alone gets a track of its own, so
    folding the same object again finds its rows without a look-up."""

    __slots__ = ("track", "version")

    def __init__(self, cs=(), track: RowTrack | None = None):
        super().__init__(cs)
        self.track = track if track is not None else RowTrack()
        self.version = len(self.track.log)


@dataclass
class ResidentPool:
    """Resident (rows, L) uint32 limb buffer for one (group, modulus).

    `reduce` is the device-level fold callable ((K, L) array -> (1, L));
    backends inject theirs (TpuBackend.reduce_mul_device) so kernel
    dispatch lives in exactly one place. Default: the jnp reference path.
    `kernel` is the family (ops/kernel) that `reduce` runs; it only names
    the product in the `kernel.fold` span.
    `gid` labels this pool's metric series (`shard=` label); empty = the
    unsharded single store.
    """

    modulus: int
    reduce: object = None
    kernel: str = "jnp"
    initial_rows: int = 256
    max_rows: int = 1 << 20  # ~1 GiB of HBM at L=256
    gid: str = ""
    sharding: object = None  # jax Sharding pinning the buffer (None = default)
    # Stratum tier sink (dds_tpu/storage): `spill` receives the evicted
    # [(cipher, (L,) uint32 host row)] batch when capacity overflows;
    # `evict_rank` orders candidate ciphers coldest-first (the tier
    # directory's decayed popularity). Both None = legacy reset behavior.
    spill: object = None
    evict_rank: object = None
    _ctx: ModCtx = field(init=False, repr=False)
    _buf: object = field(init=False, repr=False)   # jnp (cap, L) uint32
    _index: dict[int, int] = field(init=False, repr=False)
    _count: int = field(init=False, default=0, repr=False)

    def __post_init__(self):
        kprof.watch_xla_compiles()   # the pool's own small programs too
        self._ctx = ModCtx.make(self.modulus)
        if self.reduce is None:
            self.reduce = self._ctx.reduce_mul
        # what every fold of this pool runs, fixed when its program is
        # traced: read once, so a span dump names the product per fold
        from dds_tpu.ops.kernel import product_name

        self._fold_meta = {
            "limbs": self._ctx.L,
            "product": product_name(self.kernel, self._ctx.L),
        }
        self._buf = self._place_zeros(self.initial_rows)
        self._index = {}
        # bumps whenever rows move (reset, eviction): index arrays kept by
        # callers (`RowTrack.rows`) are good for the epoch they name only
        self._epoch = 0
        self._resets = 0
        self._last_reset_ts: float | None = None
        # rows evicted under the lock, delivered to `spill` after release
        # (the sink may write to disk; holding the pool lock across an
        # fsync would serialize concurrent folds on storage latency)
        self._spill_out: list[list] = []
        # cumulative operand accounting (resident / ingested / direct):
        # feeds the plane's dds_resident_hit_ratio gauge without a metrics
        # round-trip
        self._served = [0, 0, 0]
        # folds may run on proxy worker threads; ingest (index+buffer
        # mutation) must be serialized. Reads gather from an immutable
        # buffer snapshot, so only `ensure` needs the lock.
        self._lock = threading.Lock()

    # ------------------------------------------------------------ placement

    def _place(self, arr):
        import jax
        import jax.numpy as jnp

        if self.sharding is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, self.sharding)

    def _place_zeros(self, rows: int):
        import jax.numpy as jnp

        return self._place(jnp.zeros((rows, self._ctx.L), jnp.uint32))

    # -------------------------------------------------------------- surface

    @property
    def resident(self) -> int:
        return self._count

    @property
    def capacity(self) -> int:
        return int(self._buf.shape[0])

    @property
    def epoch(self) -> int:
        return self._epoch

    @property
    def resets(self) -> int:
        return self._resets

    def nbytes(self) -> int:
        """Device bytes this pool's buffer occupies (rows x L x 4)."""
        return self.capacity * self._ctx.L * 4

    def hit_ratio(self) -> float | None:
        """Fraction of fold operands served from resident rows (None
        until the pool has served any)."""
        total = sum(self._served)
        return (self._served[0] / total) if total else None

    def stats(self) -> dict:
        return {
            "rows": self._count,
            "capacity": self.capacity,
            "bytes": self.nbytes(),
            "epoch": self._epoch,
            "resets": self._resets,
            "last_reset_age_s": (
                round(time.time() - self._last_reset_ts, 1)
                if self._last_reset_ts is not None else None
            ),
            "hit_ratio": (
                round(self.hit_ratio(), 4)
                if self.hit_ratio() is not None else None
            ),
        }

    # --------------------------------------------------------------- ingest

    def _grow(self, need: int, protect=()) -> None:
        import jax.numpy as jnp

        cap = self.capacity
        while cap < need:
            cap *= 2
        if cap > self.max_rows:
            if self.spill is not None:
                # Stratum eviction-to-warm: demote the coldest resident
                # rows instead of resetting — the counter stays frozen
                # and the fused fast path degrades gradually
                self._evict(need, protect)
                return
            log.warning(
                "resident pool %s over max_rows (%d > %d): resetting",
                self.gid or "-", need, self.max_rows,
            )
            self._index.clear()
            self._count = 0
            self._epoch += 1  # row indices changed: kept index arrays are void
            self._resets += 1
            self._last_reset_ts = time.time()
            metrics.inc(
                "dds_resident_resets_total", shard=self.gid or "-",
                help="resident-pool capacity resets (entries re-ingest "
                     "on demand)",
            )
            self._file_reset_incident(need)
            cap = max(self.initial_rows, min(cap, self.max_rows))
            self._buf = self._place_zeros(cap)
            return
        pad = jnp.zeros((cap - self.capacity, self._ctx.L), jnp.uint32)
        self._buf = self._place(jnp.concatenate([self._buf, pad], axis=0))

    def _file_reset_incident(self, need: int) -> None:
        """A capacity reset silently drops the fused fast path until the
        working set re-ingests — incident-worthy, not just a log line.
        Loop-aware like Chronoscope's exemplar capture: pool calls run on
        worker threads (sync write is fine) but belt-and-braces for any
        on-loop caller the blocking write dispatches supervised."""
        import asyncio

        from dds_tpu.obs.flight import flight

        if not getattr(flight, "enabled", False):
            return
        info = {
            "shard": self.gid or "-", "need": need,
            "max_rows": self.max_rows, "resets": self._resets,
        }
        try:
            asyncio.get_running_loop()
        except RuntimeError:
            try:
                flight.record("resident_reset", **info)
            except Exception:  # noqa: BLE001 — telemetry never breaks ingest
                log.exception("resident_reset incident write failed")
            return
        from dds_tpu.utils.tasks import supervised_task

        supervised_task(
            flight.record_async("resident_reset", **info),
            name="resident.reset_incident",
        )

    def _evict(self, need: int, protect=()) -> None:
        """Demote the coldest rows to the tier sink so `need` total rows
        fit under `max_rows` (caller holds `_lock`). `protect` (the
        operand set being ensured) is never evicted — evicting it would
        re-inflate `missing` and loop; |distinct protect| <= max_rows is
        guaranteed to fit because every non-protected row is evictable.
        The spilled batch is queued and delivered OUTSIDE the lock."""
        import jax.numpy as jnp

        protect = set(protect)
        if len(protect) > self.max_rows:
            return  # aggregate wider than the pool: ensure() answers None
        incoming = need - self._count
        if incoming > self.max_rows:
            return
        evictable = [c for c in self._index if c not in protect]
        # at least a quarter per wave: hysteresis against per-row thrash
        evict_n = max(need - self.max_rows, (self._count + 3) // 4)
        evict_n = min(evict_n, len(evictable))
        if evict_n <= 0:
            return
        if self.evict_rank is not None:
            try:
                ranked = [c for c in self.evict_rank(evictable)
                          if c in self._index and c not in protect]
            except Exception:  # noqa: BLE001 — a sink bug must not lose rows
                log.exception("evict_rank failed; falling back to FIFO")
                ranked = evictable
        else:
            ranked = evictable
        victims = list(dict.fromkeys(ranked))[:evict_n]
        if len(victims) < evict_n:
            seen = set(victims)
            for c in evictable:
                if c not in seen:
                    victims.append(c)
                    if len(victims) >= evict_n:
                        break
        vset = set(victims)
        host = np.asarray(self._buf[: self._count])  # one D2H copy
        spilled = [(c, host[self._index[c]].copy()) for c in victims]
        survivors = [c for c in self._index if c not in vset]
        cap = self.capacity
        while cap < len(survivors) + incoming and cap < self.max_rows:
            cap *= 2
        cap = min(max(cap, self.initial_rows), self.max_rows)
        newbuf = np.zeros((cap, self._ctx.L), np.uint32)
        if survivors:
            newbuf[: len(survivors)] = host[
                [self._index[c] for c in survivors]
            ]
        self._buf = self._place(jnp.asarray(newbuf))
        self._index = {c: i for i, c in enumerate(survivors)}
        self._count = len(survivors)
        self._epoch += 1  # row indices changed: kept index arrays are void
        self._spill_out.append(spilled)
        metrics.inc(
            "dds_resident_evictions_total", len(victims),
            shard=self.gid or "-",
            help="rows evicted from resident pools to the warm tier "
                 "(Stratum; replaces capacity resets)",
        )
        log.info(
            "resident pool %s evicted %d cold rows to warm tier "
            "(%d stay resident)",
            self.gid or "-", len(victims), len(survivors),
        )

    def _flush_spill(self) -> None:
        """Deliver queued evictions to the tier sink outside `_lock`."""
        sink = self.spill
        while True:
            with self._lock:
                if not self._spill_out:
                    return
                batch = self._spill_out.pop(0)
            if sink is None:
                continue
            try:
                sink(batch)
            except Exception:  # noqa: BLE001 — sink bugs must not break folds
                log.exception("tier spill sink failed (%d rows dropped "
                              "back to lazy re-ingest)", len(batch))

    def membership(self, cs: list[int]) -> list[bool]:
        """Per-operand hot-tier residency, one lock round — the Stratum
        planner's split primitive."""
        with self._lock:
            return [c in self._index for c in cs]

    def ensure(self, cs: list[int], pre: dict | None = None,
               path: str = "fold") -> np.ndarray | None:
        """Ingest any unseen ciphertexts; return row indices for all of cs.
        Caller must hold `_lock`. `pre` optionally maps ciphertext -> already
        limb-converted row (fold() precomputes these OUTSIDE the lock so the
        CPU-heavy conversion never serializes concurrent folds). The
        placement is one `ingest.h2d` span (`path` says who asked).

        Returns None when the distinct operands cannot fit even after a
        reset (aggregate wider than max_rows) — callers fall back to a
        direct, non-resident fold."""
        import jax
        import jax.numpy as jnp

        missing = sorted({c for c in cs if c not in self._index})
        if missing:
            if self._count + len(missing) > self.capacity:
                self._grow(self._count + len(missing), protect=cs)
                missing = sorted({c for c in cs if c not in self._index})
            if self._count + len(missing) > self.capacity:
                return None  # wider than max_rows even when empty
            if pre is not None and all(c in pre for c in missing):
                rows = np.stack([pre[c] for c in missing])
            else:
                rows = bn.ints_to_batch(
                    [c % self.modulus for c in missing], self._ctx.L
                )
            start = self._count
            moved = len(missing) * self._ctx.L * 4
            with tracer.span("ingest.h2d", path=path, rows=len(missing),
                             bytes=moved, shard=self.gid or "-"):
                self._buf = self._place(jax.lax.dynamic_update_slice(
                    self._buf, jnp.asarray(rows), (start, 0)
                ))
            metrics.inc(
                "dds_ingest_h2d_bytes_total", moved, shard=self.gid or "-",
                help="bytes placed into device-resident pools (rows*L*4)",
            )
            for i, c in enumerate(missing):
                self._index[c] = start + i
            self._count += len(missing)
        return np.asarray([self._index[c] for c in cs], dtype=np.int32)

    def ingest(self, cs: list[int]) -> int:
        """Ingest ciphertexts eagerly (the write-path entry point): limb
        conversion happens outside the lock, placement under it. Returns
        how many new rows landed; operands wider than the pool are simply
        skipped (they would only ever direct-fold anyway)."""
        distinct = list(dict.fromkeys(cs))
        missing = [c for c in distinct if c not in self._index]
        if not missing:
            return 0
        converted = bn.ints_to_batch(
            [c % self.modulus for c in missing], self._ctx.L
        )
        pre = {c: converted[i] for i, c in enumerate(missing)}
        with self._lock:
            missing_now = [c for c in missing if c not in self._index]
            self.ensure(missing, pre, path="write")
            # count placements, not the buffer delta: an eviction wave in
            # the same ensure() can shrink _count while rows still land
            grew = sum(1 for c in missing_now if c in self._index)
        self._flush_spill()
        if grew:
            metrics.inc(
                "dds_resident_ingest_total", grew, shard=self.gid or "-",
                path="write",
                help="rows ingested into resident pools by path",
            )
        return grew

    # ----------------------------------------------------------------- read

    def _account(self, n_resident: int, n_ingested: int, n_direct: int) -> None:
        self._served[0] += n_resident
        self._served[1] += n_ingested
        self._served[2] += n_direct
        # the pre-Lodestone series, kept for dashboards that scrape it;
        # the direct-fallback path is now honestly its own outcome instead
        # of being misreported as resident
        help_ = "fold operands served from device-resident rows vs ingested"
        if n_resident:
            metrics.inc("dds_cipher_store_total", n_resident,
                        outcome="resident", help=help_)
        if n_ingested:
            metrics.inc("dds_cipher_store_total", n_ingested,
                        outcome="ingested", help=help_)
            metrics.inc(
                "dds_resident_ingest_total", n_ingested,
                shard=self.gid or "-", path="fold",
                help="rows ingested into resident pools by path",
            )
        if n_direct:
            metrics.inc("dds_cipher_store_total", n_direct,
                        outcome="direct", help=help_)

    def _rows(self, cs: list[int], k: int, epoch: int | None = None):
        """(buffer snapshot, row index per element of `cs`, epoch),
        ingesting what is unseen; `k` is the width of the column `cs`
        belongs to (all of it, or the changed part). None when the distinct
        operands cannot fit even after a reset or, with `epoch` given,
        when the pool's rows are or become another epoch's.

        Spans, one each per call and never per row: `residency.lookup`
        over each of the two locked stretches (`stretch`; the wait for
        the lock included, and named in `lock_wait_ms`; `looked_up` is
        len(cs), `memo` says nothing had to be looked up),
        `residency.convert` over the limb conversion between them,
        `ingest.h2d` (from `ensure`) inside the second."""
        with tracer.span("residency.lookup", k=k, stretch=1,
                         looked_up=len(cs), memo=not cs) as lm:
            t_ask = time.perf_counter()
            with self._lock:
                lm["lock_wait_ms"] = (time.perf_counter() - t_ask) * 1e3
                if epoch is not None and epoch != self._epoch:
                    lm["missing"] = 0
                    return None
                missing = sorted({c for c in cs if c not in self._index})
                lm["missing"] = len(missing)
                if not missing:
                    rows = np.asarray(
                        [self._index[c] for c in cs], dtype=np.int32
                    )
                    self._account(k, 0, 0)
                    # immutable jax array: safe outside the lock
                    return self._buf, rows, self._epoch
        # limb-convert the unseen operands OUTSIDE the lock (the
        # CPU-heavy part); placement/index update stays serialized.
        # Entries are only ever added, so `missing` can only shrink in
        # between; ensure() recomputes it under the lock (and converts
        # inline in the rare capacity-reset case where `pre` is short).
        with tracer.span("residency.convert", rows=len(missing)):
            converted = bn.ints_to_batch(
                [c % self.modulus for c in missing], self._ctx.L
            )
            pre = {c: converted[i] for i, c in enumerate(missing)}
        with tracer.span("residency.lookup", k=k, stretch=2,
                         looked_up=len(cs), memo=False,
                         missing=len(missing)) as lm:
            t_ask = time.perf_counter()
            with self._lock:
                lm["lock_wait_ms"] = (time.perf_counter() - t_ask) * 1e3
                rows = None
                if epoch in (None, self._epoch):
                    rows = self.ensure(cs, pre)
                if epoch not in (None, self._epoch):
                    # rows moved under the caller's index array (`ensure`
                    # itself may have reset the pool): what it placed
                    # stays, and the whole column is resolved again
                    if rows is not None:
                        self._account(0, len(missing), 0)
                    out = None
                elif rows is None:
                    if epoch is None:   # else the caller resolves it whole
                        self._account(0, 0, k)
                    out = None
                else:
                    self._account(k - len(missing), len(missing), 0)
                    out = (self._buf, rows, self._epoch)
        # deliver any eviction wave to the tier sink outside the lock
        self._flush_spill()
        return out

    def patch_rows(self, idx: np.ndarray, epoch: int, positions: list[int],
                   ciphers: list[int]):
        """(buffer snapshot, row indices) of a column whose rows were `idx`
        at `epoch` and whose `positions` now hold `ciphers`: those
        ciphertexts alone are looked up and, where unseen, converted and
        placed, so the cost is O(len(positions)) under the lock whatever
        the column's width. `idx` is left as it is. None when the pool's
        rows have moved since `epoch` (reset, eviction): `rows_for` then
        resolves the whole column."""
        got = self._rows(ciphers, len(idx), epoch)
        if got is None:
            return None
        if positions:
            idx = idx.copy()
            idx[positions] = got[1]
        return got[0], idx

    def rows_for(self, cs: list[int]):
        """(buffer snapshot, row indices) for `cs`, ingesting any unseen
        operands first: the gather half of `fold`, shared with the
        plane's fused multi-group dispatch and Prism's resident MatVec
        gather. Returns None when the distinct operands cannot fit even
        after a reset (callers fall back to direct marshaling). Accounts
        resident/ingested operands as a side effect.

        O(K) big-int dictionary probes under the lock for a plain list and
        for an `Operands` this pool has not resolved at its current epoch.
        An `Operands` whose track holds this pool's rows for some version
        costs O(positions logged between that version and this one)
        through `patch_rows`: nothing for the same object again, the rows
        that changed for a column patched since."""
        track = getattr(cs, "track", None)
        known = track.rows.get(id(self)) if track is not None else None
        if known is not None and known[0] is self:
            _, epoch, version, idx = known
            lo, hi = sorted((version, cs.version))
            positions = list(dict.fromkeys(track.log[lo:hi]))
            got = self.patch_rows(idx, epoch, positions,
                                  [cs[p] for p in positions])
            if got is not None:
                if cs.version >= version:
                    track.rows[id(self)] = (self, epoch, cs.version, got[1])
                return got
        got = self._rows(cs, len(cs))
        if got is None:
            return None
        buf, idx, epoch = got
        if track is not None:
            track.rows[id(self)] = (self, epoch, cs.version, idx)
        return buf, idx

    def fold(self, cs: list[int]) -> int:
        """prod(cs) mod modulus, gathering resident rows on-device."""
        import jax.numpy as jnp

        if not cs:
            return 1 % self.modulus
        got = self.rows_for(cs)
        if got is None:  # aggregate wider than the pool: direct fold
            rows = jnp.asarray(
                bn.ints_to_batch([c % self.modulus for c in cs], self._ctx.L)
            )
            resident = False
        else:
            buf, idx = got
            with tracer.span("dispatch.gather", k=len(cs)):
                rows = jnp.take(buf, jnp.asarray(idx), axis=0)
            resident = True
        with tracer.span("kernel.fold", k=len(cs), resident=resident,
                         **self._fold_meta):
            # dispatch (trace/compile) timed apart from the wait in
            # block_until_ready (obs/kprof) — the split the flat span hid
            out = kprof.profiled(
                "store.reduce", lambda: self.reduce(rows), k=len(cs),
            )
            with tracer.span("dispatch.d2h"):
                return bn.limbs_to_int(np.asarray(out)[0])
