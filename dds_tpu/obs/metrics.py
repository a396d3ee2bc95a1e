"""MetricsRegistry: counters, gauges, fixed-bucket histograms; Prometheus text.

The numeric half of Telescope (the span ring in `utils/trace` is the
temporal half): subsystems increment named series with bounded label sets
(route, method, coordinator, cache, outcome, ...) and `GET /metrics`
serves the whole registry in Prometheus text exposition format 0.0.4 —
stdlib only, no client library.

Design notes:
- one process-wide registry (`metrics`); a `Registry()` can be built for
  tests.
- histograms are FIXED-bucket (chosen at first observe): cumulative
  `_bucket{le=...}` counts plus `_sum`/`_count`, the standard shape
  Prometheus quantile queries expect. No dynamic buckets — re-bucketing
  mid-flight would corrupt rate() queries.
- every mutation takes one short lock; the hot-path cost is a dict lookup
  and a float add, matching the tracer's "one deque append" budget.
- counters kept in other processes can be taken in (`counters` there,
  `absorb` here): a launcher whose replicas run in processes of their own
  (`transport.replica_processes`) reads, in its own registry, the sum of
  what they counted about the protocol (`PROTOCOL_FAMILIES`). Families
  that describe one process (the event loop's ledger, the collector's
  pauses, the wire's frames) are never shipped.
- label cardinality is BOUNDED per family (`max_series`, default 1024):
  once a family holds that many distinct label sets, new label sets fold
  into a single `overflow` series (every label value replaced by
  "overflow") and `dds_metrics_label_overflow_total{family=...}` counts
  the fold. Per-tenant gauges can therefore never blow up `/metrics` —
  a wire-supplied label (tenant id, route) is a cardinality attack
  surface, and the registry is the last line of defense.
"""

from __future__ import annotations

import bisect
import threading
from dataclasses import dataclass, field

__all__ = [
    "Registry", "metrics",
    "LATENCY_BUCKETS", "SIZE_BUCKETS",
    "OVERFLOW_LABEL", "OVERFLOW_COUNTER", "PROTOCOL_FAMILIES",
]

# what replicas, their supervisor and their anti-entropy agents count about
# the PROTOCOL: the same whichever process the replica runs in, so a
# launcher sums them over its replica processes. Everything else a replica
# process counts is about that process and stays there.
PROTOCOL_FAMILIES = (
    "dds_read_batch_keys_total",
    "dds_replica_tag_vector_total", "dds_replica_tag_vector_keys_total",
    "dds_replica_keyset_total", "dds_replica_rejected_total",
    "dds_suspect_votes_total", "dds_suspicion_quorums_total",
    "dds_recovery_rotations_total", "dds_recovery_unverified_total",
    "dds_recovery_seeded_entries_total", "dds_recovery_rejected_entries_total",
    "dds_antientropy_rounds_total", "dds_antientropy_timeouts_total",
    "dds_antientropy_digest_mismatches_total",
    "dds_antientropy_rejected_repairs_total",
    "dds_antientropy_repaired_keys_total",
)

# seconds: 1ms .. 10s, the REST/quorum latency range under chaos schedules
LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)
# element counts: fold widths / batch sizes
SIZE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096, 16384)


def _label_key(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


def _escape(v: str) -> str:
    # label VALUE escaping per the text-format spec: backslash first (or
    # the escapes we add would themselves be re-escaped), then quote and
    # newline — a raw newline would split the sample line mid-series
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _escape_help(v: str) -> str:
    # HELP text escaping per the spec: only backslash and newline (quotes
    # are legal in help text, unlike in label values)
    return v.replace("\\", "\\\\").replace("\n", "\\n")


def _fmt(value: float) -> str:
    # integers render without a trailing .0 — smaller payloads, and exact
    # counter values survive a text round-trip
    if float(value).is_integer():
        return str(int(value))
    return repr(float(value))


@dataclass
class _Family:
    kind: str                      # counter | gauge | histogram
    help: str = ""
    buckets: tuple = ()
    # label-key -> float (counter/gauge) or [bucket_counts, sum, count]
    samples: dict = field(default_factory=dict)


OVERFLOW_LABEL = "overflow"
OVERFLOW_COUNTER = "dds_metrics_label_overflow_total"


class Registry:
    def __init__(self, max_series: int = 1024):
        self._lock = threading.Lock()
        self._families: dict[str, _Family] = {}
        self.max_series = int(max_series)
        # (source, family, label key) -> the value last absorbed from there
        self._absorbed: dict[tuple, float] = {}

    # -------------------------------------------------------------- writes

    def _family(self, name: str, kind: str, help: str, buckets: tuple = ()):
        fam = self._families.get(name)
        if fam is None:
            fam = self._families[name] = _Family(kind, help, buckets)
        elif fam.kind != kind:
            raise ValueError(
                f"metric {name!r} already registered as {fam.kind}, not {kind}"
            )
        elif not fam.help and help:
            # backfill: the first touch may come from a call site that
            # passes no help (scrape-time gauges are set from several
            # places) — a later documented touch must still yield # HELP
            fam.help = help
        return fam

    def _admit(self, fam: _Family, name: str, key: tuple) -> tuple:
        """Cardinality guard (caller holds the lock): an already-known
        label set, any label set while the family is under `max_series`,
        and the overflow counter itself pass through; a NEW label set at
        the cap folds into the family's single `overflow` series and is
        counted in `dds_metrics_label_overflow_total{family=...}`."""
        if (
            not key
            or key in fam.samples
            or len(fam.samples) < self.max_series
            or name == OVERFLOW_COUNTER
        ):
            return key
        oc = self._family(
            OVERFLOW_COUNTER, "counter",
            "label sets folded into the overflow series by the per-family "
            "cardinality cap",
        )
        okey = _label_key({"family": name})
        oc.samples[okey] = oc.samples.get(okey, 0.0) + 1
        return tuple((k, OVERFLOW_LABEL) for k, _ in key)

    def inc(self, name: str, n: float = 1.0, help: str = "", **labels) -> None:
        """Add `n` to a counter series (created on first touch)."""
        key = _label_key(labels)
        with self._lock:
            fam = self._family(name, "counter", help)
            key = self._admit(fam, name, key)
            fam.samples[key] = fam.samples.get(key, 0.0) + n

    def set(self, name: str, value: float, help: str = "", **labels) -> None:
        """Set a gauge series to `value`."""
        key = _label_key(labels)
        with self._lock:
            fam = self._family(name, "gauge", help)
            key = self._admit(fam, name, key)
            fam.samples[key] = float(value)

    def observe(self, name: str, value: float, buckets: tuple = LATENCY_BUCKETS,
                help: str = "", **labels) -> None:
        """Record one observation into a fixed-bucket histogram series."""
        key = _label_key(labels)
        with self._lock:
            fam = self._family(name, "histogram", help, tuple(buckets))
            key = self._admit(fam, name, key)
            s = fam.samples.get(key)
            if s is None:
                s = fam.samples[key] = [[0] * len(fam.buckets), 0.0, 0]
            i = bisect.bisect_left(fam.buckets, value)
            if i < len(fam.buckets):
                s[0][i] += 1
            s[1] += value
            s[2] += 1

    def absorb(self, source: str, samples: list) -> None:
        """Take in another process's counters: `samples` as its `counters()`
        gave them, cumulative since that process started. Each series here
        grows by what it grew there since the last call for `source`, so
        the series reads the sum over sources (and over this process's own
        increments). A value below the last one is a process that started
        again: it counts from nothing."""
        for name, help, labels, value in samples:
            at = (source, name, _label_key(labels))
            last = self._absorbed.get(at, 0.0)
            self._absorbed[at] = value
            self.inc(name, value - last if value >= last else value,
                     help=help, **labels)

    # --------------------------------------------------------------- reads

    def counters(self, names) -> list:
        """Every series of the counter families `names`, as `[name, help,
        labels, value]`: what `absorb` takes, in a form JSON carries."""
        with self._lock:
            return [
                [name, fam.help, dict(key), value]
                for name in names
                if (fam := self._families.get(name)) is not None
                and fam.kind == "counter"
                for key, value in fam.samples.items()
            ]

    def value(self, name: str, **labels) -> float | None:
        """Current counter/gauge value of one series (tests/introspection)."""
        with self._lock:
            fam = self._families.get(name)
            if fam is None or fam.kind == "histogram":
                return None
            return fam.samples.get(_label_key(labels))

    def histogram_stats(self, name: str, **labels) -> dict | None:
        """{count, sum} of one histogram series."""
        with self._lock:
            fam = self._families.get(name)
            if fam is None or fam.kind != "histogram":
                return None
            s = fam.samples.get(_label_key(labels))
            if s is None:
                return None
            return {"count": s[2], "sum": s[1]}

    def overflow_total(self) -> float:
        """Total label sets folded into `overflow` series across every
        family — the registry's dropped-series count. Exported at scrape
        time as the `dds_metrics_dropped_series` gauge so dashboards can
        alarm on cardinality overflow without parsing the per-family
        counter."""
        with self._lock:
            fam = self._families.get(OVERFLOW_COUNTER)
            if fam is None:
                return 0.0
            return float(sum(fam.samples.values()))

    def clear_family(self, name: str) -> None:
        """Drop every series of one family (help/kind registration stays).
        For scrape-time re-exported info gauges whose LABEL VALUES rotate
        (Heliograph's exemplar trace ids): the exporter clears and re-sets
        the current series each sample, so rotation can never accrete
        stale series toward the cardinality cap."""
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                fam.samples.clear()

    def reset(self) -> None:
        with self._lock:
            self._families.clear()
            self._absorbed.clear()

    # ---------------------------------------------------------- exposition

    def render(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        out: list[str] = []
        with self._lock:
            for name in sorted(self._families):
                fam = self._families[name]
                if fam.help:
                    out.append(f"# HELP {name} {_escape_help(fam.help)}")
                out.append(f"# TYPE {name} {fam.kind}")
                for key in sorted(fam.samples):
                    labels = dict(key)
                    if fam.kind == "histogram":
                        counts, total, count = fam.samples[key]
                        cum = 0
                        for le, c in zip(fam.buckets, counts):
                            cum += c
                            out.append(
                                f"{name}_bucket{{{self._labels(labels, le=_fmt(le))}}} {cum}"
                            )
                        out.append(
                            f'{name}_bucket{{{self._labels(labels, le="+Inf")}}} {count}'
                        )
                        suffix = self._labels(labels)
                        brace = f"{{{suffix}}}" if suffix else ""
                        out.append(f"{name}_sum{brace} {_fmt(total)}")
                        out.append(f"{name}_count{brace} {count}")
                    else:
                        suffix = self._labels(labels)
                        brace = f"{{{suffix}}}" if suffix else ""
                        out.append(f"{name}{brace} {_fmt(fam.samples[key])}")
        return "\n".join(out) + "\n"

    @staticmethod
    def _labels(labels: dict, **extra) -> str:
        items = {**labels, **extra}
        return ",".join(f'{k}="{_escape(str(v))}"' for k, v in items.items())


# process-wide default registry (subsystems import this)
metrics = Registry()
