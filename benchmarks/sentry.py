"""Perf-regression sentry CLI: gate CI on per-kernel timing baselines.

Modes (all emit one JSON line to stdout):

    python benchmarks/sentry.py --check [--baseline PATH]
        Parse + validate the stored baseline file only (no kernels run;
        no jax import) — the CPU-only smoke CI runs so a corrupted
        baseline is caught before it silently disables gating.
        Also parses any `shard scaling` (benchmarks/shard_scaling.py),
        `analytics matvec` (benchmarks/analytics_matvec.py),
        `overload goodput` (benchmarks/overload_goodput.py),
        `multihost load` (benchmarks/multihost_load.py),
        `resident fold` (benchmarks/resident_fold.py),
        `tiered fold` (benchmarks/tiered_fold.py),
        `fleet obs` (benchmarks/fleet_obs_overhead.py),
        `pipe profile` (benchmarks/pipe_profile.py),
        `decrypt throughput` (benchmarks/decrypt_throughput.py),
        `search latency` (benchmarks/search_latency.py),
        `autoscale goodput` (benchmarks/autoscale_goodput.py) and
        `tenant isolation` (benchmarks/tenant_isolation.py) records
        in benchmarks/results.json / results_quick.json so a malformed
        scaling, analytics, overload, multihost, fleet-obs, pipe,
        resident, decrypt, search, autoscale or tenant record is
        caught by the same smoke.
        Exit 0 on valid (or absent) files, 2 on a malformed one.

    python benchmarks/sentry.py --record [--baseline PATH] [--repeats N]
        Run the probe workload and (over)write its stats as the new
        baseline. Exit 0.

    python benchmarks/sentry.py [--baseline PATH] [--fresh STATS.json]
                                [--threshold 0.2] [--repeats N]
        Compare a fresh measurement — the probe workload, or a stats
        JSON captured elsewhere (`--fresh`) — against the stored
        baseline. Exit 1 when any kernel phase regressed by more than
        `--threshold` (default 20%), 2 on a malformed baseline/stats
        file, 0 when clean (including "nothing to compare": an empty
        baseline can never fail the gate, it just reports coverage 0).

The probe workload drives `ops.foldmany` (the weighted fold behind the
analytics routes) at two fixed shapes; it runs on whatever jax backend is
available, so the same invocation gates CPU CI and TPU perf runs — each
environment keeps its OWN baseline file (a CPU p50 is meaningless
against a TPU one, which is why the kernel key includes shape but the
FILE is per-environment).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dds_tpu.obs import sentry  # noqa: E402 — stdlib-only import


def probe(repeats: int = 5) -> dict:
    """Deterministic probe workload: a handful of weighted-fold dispatches
    at two shapes, collected from a fresh tracer ring."""
    from dds_tpu.ops.foldmany import fold_weighted
    from dds_tpu.utils.trace import tracer

    # a fixed odd modulus (Mersenne 127) keeps ModCtx shapes stable; the
    # UNMEASURED warmup pass eats the trace+compile cost so the recorded
    # dispatch stats are steady-state — a cold compile is ~4x a warm
    # dispatch and would gate on cache temperature, not kernel speed
    n = (1 << 127) - 1
    small = ([3, 5, 7], [[1, 1, 1], [1, 0, 1]])
    wide = ([3, 5, 7, 11, 13, 17, 19, 23], [[1] * 8] * 4)
    fold_weighted(*small, n)
    fold_weighted(*wide, n)
    tracer.reset()
    for _ in range(max(1, repeats)):
        fold_weighted(*small, n)
        fold_weighted(*wide, n)
    return sentry.collect()


def _iter_result_rows(root: str):
    """(file name, record) for every row in the suite result files.
    Unreadable/mis-shaped files raise ValueError — the shared malformed
    contract the per-family checkers map to exit 2."""
    for name in ("results.json", "results_quick.json"):
        path = os.path.join(root, "benchmarks", name)
        if not os.path.exists(path):
            continue
        with open(path) as f:
            try:
                rows = json.load(f)
            except json.JSONDecodeError as e:
                raise ValueError(f"unreadable results file {name}: {e}") from e
        if not isinstance(rows, list):
            raise ValueError(f"malformed results file {name}: expected a list")
        for row in rows:
            yield name, row


def _check_shard_records(root: str = REPO) -> dict:
    """Validate `shard scaling` rows (benchmarks/shard_scaling.py) in the
    suite result files: each must carry a positive ops/s value and a
    detail block naming its shard count and per-shard key split. Returns
    {"rows": n} or raises ValueError on a malformed record — the same
    contract load_baseline has, mapped to exit 2 by --check."""
    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("shard scaling")):
            continue
        detail = row.get("detail")
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("shards"), int)
            and detail["shards"] >= 1
            and isinstance(detail.get("per_shard_keys"), dict)
        )
        if not ok:
            raise ValueError(
                f"malformed shard-scaling record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_analytics_records(root: str = REPO) -> dict:
    """Validate `analytics matvec` rows (benchmarks/analytics_matvec.py):
    positive rows/s value, a detail block naming the matrix shape, and
    positive server/client timings (the comparison the record exists
    for). Same malformed contract as the shard-scaling rows: exit 2."""
    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("analytics matvec")):
            continue
        detail = row.get("detail")
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("rows"), int) and detail["rows"] >= 1
            and isinstance(detail.get("cols"), int) and detail["cols"] >= 1
            and isinstance(detail.get("server_ms"), (int, float))
            and detail["server_ms"] > 0
            and isinstance(detail.get("client_ms"), (int, float))
            and detail["client_ms"] > 0
        )
        if not ok:
            raise ValueError(
                f"malformed analytics record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_overload_records(root: str = REPO) -> dict:
    """Validate `overload goodput` rows (benchmarks/overload_goodput.py):
    positive goodput value, a detail block naming the baseline goodput
    (the comparison the record exists for) and the shed census — count
    plus a non-negative shed-latency p95. Same malformed contract as the
    shard/analytics rows: exit 2."""
    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("overload goodput")):
            continue
        detail = row.get("detail")
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("baseline_goodput"), (int, float))
            and detail["baseline_goodput"] >= 0
            and isinstance(detail.get("shed_requests"), int)
            and detail["shed_requests"] >= 0
            and isinstance(detail.get("shed_p95_ms"), (int, float))
            and detail["shed_p95_ms"] >= 0
            and isinstance(detail.get("aggregate_rate"), (int, float))
            and detail["aggregate_rate"] > 0
        )
        if not ok:
            raise ValueError(
                f"malformed overload-goodput record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_resident_records(root: str = REPO) -> dict:
    """Validate `resident fold` rows (benchmarks/resident_fold.py):
    positive folds/s value and a detail block naming the shard count,
    total rows, and positive warm/cold timings (the warm-vs-marshaling
    comparison the record exists for). Same malformed contract as the
    other row families: exit 2."""
    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("resident fold")):
            continue
        detail = row.get("detail")
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("shards"), int)
            and detail["shards"] >= 1
            and isinstance(detail.get("rows"), int) and detail["rows"] >= 1
            and isinstance(detail.get("warm_ms"), (int, float))
            and detail["warm_ms"] > 0
            and isinstance(detail.get("cold_ms"), (int, float))
            and detail["cold_ms"] > 0
        )
        if not ok:
            raise ValueError(
                f"malformed resident-fold record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_search_records(root: str = REPO) -> dict:
    """Validate `search latency` rows (benchmarks/search_latency.py):
    positive queries/s value and a detail block naming the op, the store
    size, the hit count, and positive indexed/legacy timings (the
    indexed-vs-scan comparison the record exists for). Same malformed
    contract as the other row families: exit 2."""
    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("search latency")):
            continue
        detail = row.get("detail")
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("op"), str) and detail["op"]
            and isinstance(detail.get("rows"), int) and detail["rows"] >= 1
            and isinstance(detail.get("hits"), int) and detail["hits"] >= 0
            and isinstance(detail.get("indexed_ms"), (int, float))
            and detail["indexed_ms"] > 0
            and isinstance(detail.get("legacy_ms"), (int, float))
            and detail["legacy_ms"] > 0
        )
        if not ok:
            raise ValueError(
                f"malformed search-latency record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_tiered_records(root: str = REPO) -> dict:
    """Validate `tiered fold` rows (benchmarks/tiered_fold.py): positive
    folds/s value and a detail block naming the pool capacity, a
    population that genuinely exceeds it, a FROZEN reset counter (the
    whole point of eviction-to-warm), and positive ceiling/tiered
    timings (the vs-no-tiering comparison the record exists for). Same
    malformed contract as the other row families: exit 2."""
    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("tiered fold")):
            continue
        detail = row.get("detail")
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("max_rows"), int)
            and detail["max_rows"] >= 1
            and isinstance(detail.get("population"), int)
            and detail["population"] > detail["max_rows"]
            and detail.get("resets") == 0
            and isinstance(detail.get("ceiling_ms"), (int, float))
            and detail["ceiling_ms"] > 0
            and isinstance(detail.get("tiered_ms"), (int, float))
            and detail["tiered_ms"] > 0
        )
        if not ok:
            raise ValueError(
                f"malformed tiered-fold record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_multihost_records(root: str = REPO) -> dict:
    """Validate `multihost load` rows (benchmarks/multihost_load.py):
    positive good-req/s value, a detail block naming the swept rates, the
    OS-process count (>= 2, or it measured nothing multi-process), the
    open-loop flag, and ordered non-negative p50<=p95<=p99 latencies
    measured from scheduled arrivals. Same malformed contract as the
    other row families: exit 2."""
    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("multihost load")):
            continue
        detail = row.get("detail")
        pcts = []
        if isinstance(detail, dict):
            pcts = [detail.get(k) for k in ("p50_ms", "p95_ms", "p99_ms")]
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("rates"), list)
            and len(detail["rates"]) >= 1
            and all(isinstance(r, (int, float)) and r > 0
                    for r in detail["rates"])
            and isinstance(detail.get("processes"), int)
            and detail["processes"] >= 2
            and detail.get("open_loop") is True
            and all(isinstance(p, (int, float)) and p >= 0 for p in pcts)
            and pcts[0] <= pcts[1] <= pcts[2]
        )
        if not ok:
            raise ValueError(
                f"malformed multihost-load record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_fleet_obs_records(root: str = REPO) -> dict:
    """Validate `fleet obs` rows (benchmarks/fleet_obs_overhead.py):
    positive good-req/s value and a detail block carrying the shipper-
    on/off goodput pair, the overhead percentage (any sign — noise can
    make the shipper run faster), an OS-process count >= 2, the open-loop
    flag, and the collector's proof-of-life census: sources >= 1 (the
    groups actually shipped), non-negative stitched/dropped counts (drops
    ACCOUNTED is the contract, zero drops is not). Same malformed
    contract as the other row families: exit 2."""
    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("fleet obs")):
            continue
        detail = row.get("detail")
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("on_good"), int)
            and detail["on_good"] >= 1
            and isinstance(detail.get("off_good"), int)
            and detail["off_good"] >= 1
            and isinstance(detail.get("overhead_pct"), (int, float))
            and isinstance(detail.get("processes"), int)
            and detail["processes"] >= 2
            and detail.get("open_loop") is True
            and isinstance(detail.get("sources"), int)
            and detail["sources"] >= 1
            and isinstance(detail.get("stitched"), int)
            and detail["stitched"] >= 0
            and isinstance(detail.get("dropped"), int)
            and detail["dropped"] >= 0
        )
        if not ok:
            raise ValueError(
                f"malformed fleet-obs record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_decrypt_records(root: str = REPO) -> dict:
    """Validate `decrypt throughput` rows (benchmarks/decrypt_throughput
    .py): positive ops/s value and a detail block naming the key size,
    batch width, positive per-op / batched-host / Sanctum-device rates,
    and verified=True — the decrypt-verified-before-timed contract the
    record exists for. Same malformed contract as the other row
    families: exit 2."""
    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("decrypt throughput")):
            continue
        detail = row.get("detail")
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("bits"), int) and detail["bits"] >= 256
            and isinstance(detail.get("batch"), int) and detail["batch"] >= 1
            and isinstance(detail.get("per_op_ops"), (int, float))
            and detail["per_op_ops"] > 0
            and isinstance(detail.get("batched_host_ops"), (int, float))
            and detail["batched_host_ops"] > 0
            and isinstance(detail.get("sanctum_device_ops"), (int, float))
            and detail["sanctum_device_ops"] > 0
            and detail.get("verified") is True
        )
        if not ok:
            raise ValueError(
                f"malformed decrypt-throughput record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_autoscale_records(root: str = REPO) -> dict:
    """Validate `autoscale goodput` rows (benchmarks/autoscale_goodput
    .py): positive good-per-group-second value and a detail block
    carrying the static-baseline score (the comparison the record exists
    for), non-negative split/merge/migrated-bytes counts (the controller
    actions the score was bought with), and the open-loop flag. Same
    malformed contract as the other row families: exit 2."""
    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("autoscale goodput")):
            continue
        detail = row.get("detail")
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("static_score"), (int, float))
            and detail["static_score"] >= 0
            and isinstance(detail.get("splits"), int)
            and detail["splits"] >= 0
            and isinstance(detail.get("merges"), int)
            and detail["merges"] >= 0
            and isinstance(detail.get("moved_bytes"), int)
            and detail["moved_bytes"] >= 0
            and detail.get("open_loop") is True
        )
        if not ok:
            raise ValueError(
                f"malformed autoscale-goodput record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_geo_records(root: str = REPO) -> dict:
    """Validate `geo latency` rows (benchmarks/geo_latency.py): positive
    read-local-vs-quorum speedup and a detail block proving where the
    speedup came from — both p95s, a leased-read count, the mid-run
    revocation flag (the degradation path the record exists to cover),
    ZERO stale reads (a leased read that trailed an acked write would
    make the latency win meaningless), and a named WAN preset so the
    schedule is reproducible. Same malformed contract: exit 2."""
    presets = {"wan-100", "wan-200", "wan-300"}
    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("geo latency")):
            continue
        detail = row.get("detail")
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("local_p95_ms"), (int, float))
            and detail["local_p95_ms"] > 0
            and isinstance(detail.get("quorum_p95_ms"), (int, float))
            and detail["quorum_p95_ms"] > 0
            and isinstance(detail.get("reads"), int) and detail["reads"] > 0
            and isinstance(detail.get("leased_reads"), int)
            and detail["leased_reads"] > 0
            and isinstance(detail.get("fallbacks"), int)
            and detail["fallbacks"] >= 0
            and detail.get("revoked_mid_run") is True
            and detail.get("stale_reads") == 0
            and detail.get("wan_preset") in presets
        )
        if not ok:
            raise ValueError(
                f"malformed geo-latency record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_pipe_records(root: str = REPO) -> dict:
    """Validate `pipe profile` rows (benchmarks/pipe_profile.py): positive
    p95 wall-time value, a detail block naming the profiled route, a
    coverage fraction in [0, 1], a top stage drawn from the Chronoscope
    taxonomy, a non-empty stages dict of non-negative per-stage p95s, the
    fleet rollup's top stage alongside the agreement flag (the
    local-vs-fleet cross-check the record exists for), an OS-process
    count >= 2, the open-loop flag, and a numeric profiling-overhead
    percentage (any sign — noise can make the profiled run faster). Same
    malformed contract as the other row families: exit 2."""
    from dds_tpu.obs.chronoscope import STAGES

    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("pipe profile")):
            continue
        detail = row.get("detail")
        stages = detail.get("stages") if isinstance(detail, dict) else None
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("route"), str) and detail["route"]
            and isinstance(detail.get("wall_p95_ms"), (int, float))
            and detail["wall_p95_ms"] > 0
            and isinstance(detail.get("coverage"), (int, float))
            and 0.0 <= detail["coverage"] <= 1.0
            and detail.get("top_stage") in STAGES
            and isinstance(stages, dict) and stages
            and all(isinstance(v, (int, float)) and v >= 0
                    for v in stages.values())
            and isinstance(detail.get("fleet_top_stage"), str)
            and isinstance(detail.get("agree"), bool)
            and isinstance(detail.get("processes"), int)
            and detail["processes"] >= 2
            and detail.get("open_loop") is True
            and isinstance(detail.get("overhead_pct"), (int, float))
        )
        if not ok:
            raise ValueError(
                f"malformed pipe-profile record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_tenant_records(root: str = REPO) -> dict:
    """Validate `tenant isolation` rows (benchmarks/tenant_isolation.py):
    positive victim-p95 value and a detail block carrying both variants'
    p95s (the blast-radius comparison the record exists for), a numeric
    degradation percentage (any sign — best-of runs can come out
    faster), the flooder's shed census (non-negative 429 count bounded
    by its request count, which must be positive or the run flooded
    nothing), at least two tenants, and the open-loop flag. Same
    malformed contract as the other row families: exit 2."""
    found = 0
    for name, row in _iter_result_rows(root):
        if not (isinstance(row, dict)
                and str(row.get("metric", "")).startswith("tenant isolation")):
            continue
        detail = row.get("detail")
        ok = (
            isinstance(row.get("value"), (int, float)) and row["value"] > 0
            and isinstance(detail, dict)
            and isinstance(detail.get("victim_p95_base_ms"), (int, float))
            and detail["victim_p95_base_ms"] > 0
            and isinstance(detail.get("victim_p95_flood_ms"), (int, float))
            and detail["victim_p95_flood_ms"] > 0
            and isinstance(detail.get("degradation_pct"), (int, float))
            and isinstance(detail.get("flooder_requests"), int)
            and detail["flooder_requests"] > 0
            and isinstance(detail.get("flooder_429"), int)
            and 0 <= detail["flooder_429"] <= detail["flooder_requests"]
            and isinstance(detail.get("tenants"), int)
            and detail["tenants"] >= 2
            and detail.get("open_loop") is True
        )
        if not ok:
            raise ValueError(
                f"malformed tenant-isolation record in {name}: "
                f"{row.get('metric')!r}"
            )
        found += 1
    return {"rows": found}


def _check_canary_records(root: str = REPO) -> dict:
    """Validate the Heliograph rows (benchmarks/canary_overhead.py).

    `canary overhead`: positive goodput value, the open-loop flag, the
    default cadence named, a numeric overhead percentage (any sign —
    single-run noise can make the probed run faster), a positive
    baseline goodput, and a non-empty cadence sweep whose every point
    carries goodput, probe census, and its own overhead number.

    `canary drill`: the detection bound the tentpole claims — the
    seeded valid-HMAC corruption caught by decrypt-and-verify within 3
    probe periods, on >= 1 mutated replica, with the passive surface
    green, a Watchtower incident whose trace id matches the ledger
    exemplar, and that exemplar resolvable via `GET /canary`. Same
    malformed contract as the other row families: exit 2."""
    found = 0
    for name, row in _iter_result_rows(root):
        metric = str(row.get("metric", "")) if isinstance(row, dict) else ""
        if metric.startswith("canary overhead"):
            detail = row.get("detail")
            cadences = (detail.get("cadences")
                        if isinstance(detail, dict) else None)
            ok = (
                isinstance(row.get("value"), (int, float)) and row["value"] > 0
                and isinstance(detail, dict)
                and detail.get("open_loop") is True
                and isinstance(detail.get("default_cadence_s"), (int, float))
                and detail["default_cadence_s"] > 0
                and isinstance(detail.get("overhead_pct"), (int, float))
                and isinstance(detail.get("baseline_goodput_rps"),
                               (int, float))
                and detail["baseline_goodput_rps"] > 0
                and isinstance(cadences, dict) and cadences
                and all(
                    isinstance(pt, dict)
                    and isinstance(pt.get("goodput_rps"), (int, float))
                    and isinstance(pt.get("probes"), int) and pt["probes"] >= 0
                    and isinstance(pt.get("probes_ok"), int)
                    and 0 <= pt["probes_ok"] <= pt["probes"]
                    and isinstance(pt.get("overhead_pct"), (int, float))
                    for pt in cadences.values()
                )
                and str(detail["default_cadence_s"]) in cadences
            )
        elif metric.startswith("canary drill"):
            detail = row.get("detail")
            ok = (
                isinstance(row.get("value"), (int, float))
                and 1 <= row["value"] <= 3
                and isinstance(detail, dict)
                and isinstance(detail.get("detected_within_periods"), int)
                and detail["detected_within_periods"] == row["value"]
                and isinstance(detail.get("replicas_mutated"), int)
                and detail["replicas_mutated"] >= 1
                and detail.get("passive_green") is True
                and detail.get("verdict") == "wrong_answer"
                and isinstance(detail.get("trace_id"), str)
                and detail["trace_id"]
                and isinstance(detail.get("watchtower_incidents"), int)
                and detail["watchtower_incidents"] >= 1
                and detail.get("incident_trace_match") is True
                and detail.get("exemplar_resolved") is True
            )
        else:
            continue
        if not ok:
            raise ValueError(
                f"malformed canary record in {name}: {metric!r}"
            )
        found += 1
    return {"rows": found}


def _load_fresh(path: str) -> dict:
    """A stats JSON: either the baseline schema or a bare kernels dict."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict) and isinstance(data.get("kernels"), dict):
        return data["kernels"]
    if isinstance(data, dict):
        return data
    raise ValueError(f"malformed fresh stats {path!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default: DDS_KERNEL_BASELINE or "
                         "benchmarks/kernel_baseline.json)")
    ap.add_argument("--check", action="store_true",
                    help="validate the baseline file and exit")
    ap.add_argument("--record", action="store_true",
                    help="run the probe and store its stats as the baseline")
    ap.add_argument("--fresh", default=None,
                    help="compare this stats JSON instead of running the probe")
    ap.add_argument("--threshold", type=float, default=0.20,
                    help="regression gate as a fraction (default 0.20)")
    ap.add_argument("--floor-ms", type=float, default=0.05,
                    help="ignore deltas below this many ms (timer noise)")
    ap.add_argument("--repeats", type=int, default=5,
                    help="probe workload repetitions")
    args = ap.parse_args(argv)

    path = str(sentry.baseline_path(args.baseline))
    try:
        baseline = sentry.load_baseline(args.baseline)
    except ValueError as e:
        print(json.dumps({"ok": False, "baseline": path, "error": str(e)}))
        return 2

    if args.check:
        try:
            shard = _check_shard_records()
            analytics = _check_analytics_records()
            overload = _check_overload_records()
            multihost = _check_multihost_records()
            fleet_obs = _check_fleet_obs_records()
            pipe = _check_pipe_records()
            resident = _check_resident_records()
            tiered = _check_tiered_records()
            decrypt = _check_decrypt_records()
            search = _check_search_records()
            autoscale = _check_autoscale_records()
            geo = _check_geo_records()
            tenant = _check_tenant_records()
            canary = _check_canary_records()
        except ValueError as e:
            print(json.dumps({"ok": False, "baseline": path,
                              "error": str(e)}))
            return 2
        print(json.dumps({
            "ok": True, "mode": "check", "baseline": path,
            "kernels": len(baseline), "exists": bool(baseline),
            "shard_scaling_rows": shard["rows"],
            "analytics_rows": analytics["rows"],
            "overload_rows": overload["rows"],
            "multihost_rows": multihost["rows"],
            "fleet_obs_rows": fleet_obs["rows"],
            "pipe_rows": pipe["rows"],
            "resident_rows": resident["rows"],
            "tiered_rows": tiered["rows"],
            "decrypt_rows": decrypt["rows"],
            "search_rows": search["rows"],
            "autoscale_rows": autoscale["rows"],
            "geo_rows": geo["rows"],
            "tenant_rows": tenant["rows"],
            "canary_rows": canary["rows"],
        }))
        return 0

    if args.record:
        stats = probe(args.repeats)
        sentry.save_baseline(stats, args.baseline, overwrite=True)
        print(json.dumps({
            "ok": True, "mode": "record", "baseline": path,
            "kernels": sorted(stats),
        }))
        return 0

    try:
        fresh = _load_fresh(args.fresh) if args.fresh else probe(args.repeats)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(json.dumps({"ok": False, "baseline": path, "error": str(e)}))
        return 2

    findings = sentry.compare(
        baseline, fresh, threshold=args.threshold, floor_ms=args.floor_ms
    )
    compared = sorted(set(baseline) & set(fresh))
    print(json.dumps({
        "ok": not findings,
        "mode": "compare",
        "baseline": path,
        "threshold": args.threshold,
        "compared": compared,
        "uncovered": sorted(set(fresh) - set(baseline)),
        "regressions": findings,
    }))
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
