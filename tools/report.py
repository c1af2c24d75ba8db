#!/usr/bin/env python
"""Render a telemetry JSONL run into a human report; gate regressions.

The consumer half of fast_tffm_tpu/telemetry.py: training/predict/serving
write enveloped records (one run_id, ``kind`` ∈ telemetry.SCHEMAS) to
``metrics_path``; this tool turns that stream back into answers —
*how fast was it, was the input or the device the bottleneck, did it
recompile / stall / diverge, and is it worse than the last run?*

    python tools/report.py RUN.jsonl                    # markdown → stdout
    python tools/report.py RUN.jsonl --out REPORT.md
    python tools/report.py RUN.jsonl --compare BASE.jsonl [--threshold 0.15]

``--compare`` prints per-metric deltas and exits **1** when RUN's median
throughput is degraded more than ``--threshold`` (fraction) vs BASE — a
bench gate: wire two instrumented runs into CI and a slowdown fails the
build.  ``--strict`` additionally fails on NEW steady-state recompiles,
stalls, or anomalies.  Exit 2 = unusable input.

Stdlib-only on purpose: the report must render on a machine that can't
even import jax (e.g. triaging a stall dump from a wedged TPU host).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

_BLOCKS = "▁▂▃▄▅▆▇█"


def spark(vals) -> str:
    """Unicode sparkline (empty-safe)."""
    vals = [v for v in vals if v is not None]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _BLOCKS[0] * len(vals)
    return "".join(
        _BLOCKS[int((v - lo) / (hi - lo) * (len(_BLOCKS) - 1))] for v in vals
    )


def _fmt(v, nd=1) -> str:
    if v is None:
        return "–"
    if isinstance(v, float):
        if abs(v) < 10:  # losses/AUCs need the decimals, rates don't
            nd = max(nd, 4)
        return f"{v:,.{nd}f}"
    return f"{v:,}"


def _fmt_bucket_occupancy(sv: dict) -> str:
    """``64: 0.81 (5917/7296 rows)`` per bucket, smallest bucket first.

    Occupancy per bucket, not just the blended mean: the blend hides a
    single oversized bucket absorbing every coalesced flush (the 0.286
    pathology) behind healthy-looking small-bucket numbers."""
    occ = sv.get("bucket_occupancy") or {}
    rows = sv.get("bucket_rows") or {}
    padded = sv.get("bucket_padded_rows") or {}
    parts = []
    for k in sorted(occ, key=lambda x: int(x)):
        r = rows.get(k)
        p = padded.get(k)
        total = (r + p) if isinstance(r, int) and isinstance(p, int) else None
        detail = f" ({r}/{total} rows)" if total is not None else ""
        parts.append(f"{k}: {occ[k]}{detail}")
    return ", ".join(parts) or "–"


def _fmt_bytes(v) -> str:
    if v is None:
        return "–"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(v) < 1024 or unit == "TiB":
            return f"{v:.1f} {unit}" if unit != "B" else f"{v} B"
        v /= 1024
    return f"{v:.1f} TiB"


def load_run(path: str) -> list[dict]:
    """All parseable JSONL records; raises ValueError when nothing is."""
    records, bad = [], 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                bad += 1
                continue
            if isinstance(rec, dict):
                records.append(rec)
    if not records:
        raise ValueError(f"{path}: no parseable JSONL records")
    if bad:
        print(f"note: {path}: skipped {bad} malformed line(s)", file=sys.stderr)
    return records


def _by_kind(records):
    out = {}
    for r in records:
        out.setdefault(r.get("kind", "step"), []).append(r)
    return out


def summarize(records: list[dict]) -> dict:
    """Flatten one run's records into the metrics the report (and the
    compare gate) speaks: throughput stats, loss endpoints, input-path
    shares, event counts, memory peaks.

    MetricsLogger appends, so successive runs with one config share one
    file; pooling them would fake convergence (loss_first from run 1,
    loss_final from run 2) and corrupt the compare gate both ways — only
    the LAST run_id is summarized, with a stderr note."""
    distinct = list(dict.fromkeys(r.get("run_id") for r in records if r.get("run_id")))
    if len(distinct) > 1:
        last = distinct[-1]
        print(
            f"note: {len(distinct)} runs appended in this file; "
            f"reporting only the last (run_id {last})",
            file=sys.stderr,
        )
        records = [r for r in records if r.get("run_id") == last]
    kinds = _by_kind(records)
    s: dict = {
        "run_ids": distinct[-1:],
        "runs_in_file": len(distinct),
        "kinds": {k: len(v) for k, v in sorted(kinds.items())},
    }
    ts = [r["t"] for r in records if isinstance(r.get("t"), (int, float))]
    s["duration_s"] = round(max(ts) - min(ts), 3) if ts else None

    train = kinds.get("train", [])
    rates = [
        r["examples_per_sec"]
        for r in train
        if isinstance(r.get("examples_per_sec"), (int, float))
    ]
    losses = [r["loss"] for r in train if isinstance(r.get("loss"), (int, float))]
    s["train_windows"] = len(train)
    s["steps"] = max((r.get("step", 0) for r in records), default=0)
    s["throughput_timeline"] = rates
    s["throughput_median"] = round(statistics.median(rates), 1) if rates else None
    s["throughput_final"] = rates[-1] if rates else None
    s["loss_timeline"] = losses
    s["loss_first"] = losses[0] if losses else None
    s["loss_final"] = losses[-1] if losses else None

    inputs = kinds.get("input", [])

    def _wsum(key):
        tot = n = 0.0
        for r in inputs:
            v, items = r.get(key), r.get("input_items", 0)
            if isinstance(v, (int, float)) and items:
                tot += v * items
                n += items
        return (tot, n)

    parse_tot, parse_items = _wsum("parse_ms")
    h2d_tot, h2d_items = _wsum("h2d_ms")
    s["parse_ms_mean"] = round(parse_tot / parse_items, 3) if parse_items else None
    s["h2d_ms_mean"] = round(h2d_tot / h2d_items, 3) if h2d_items else None
    wires = [
        r["wire_bytes_per_step"]
        for r in inputs
        if isinstance(r.get("wire_bytes_per_step"), (int, float))
    ]
    s["wire_bytes_per_step"] = int(statistics.median(wires)) if wires else None
    depths = [
        r["prefetch_queue_depth"]
        for r in inputs
        if isinstance(r.get("prefetch_queue_depth"), (int, float))
    ]
    s["prefetch_queue_depth_mean"] = (
        round(sum(depths) / len(depths), 2) if depths else None
    )
    # Input-vs-compute split: host input work (parse + pack/H2D) as a
    # share of the run's wall clock.  >1 window is overlap (prefetch
    # thread) — still an honest "the host was this busy feeding" number.
    if s["duration_s"]:
        busy_ms = parse_tot + h2d_tot
        s["input_time_share"] = round(busy_ms / 1e3 / s["duration_s"], 3)
    else:
        s["input_time_share"] = None

    compiles = kinds.get("compile", [])
    s["warmup_compiles"] = sum(
        r.get("compiles", 0) for r in compiles if r.get("warmup")
    )
    s["steady_compiles"] = sum(
        r.get("compiles", 0) for r in compiles if not r.get("warmup")
    )
    s["steady_compile_steps"] = [r.get("step") for r in compiles if not r.get("warmup")]

    s["stalls"] = len(kinds.get("stall", []))
    s["stall_events"] = [
        {
            "step": r.get("step"),
            "since_last_step_s": r.get("since_last_step_s"),
            "classification": r.get("classification"),
            "prefetch_queue_depth": r.get("prefetch_queue_depth"),
        }
        for r in kinds.get("stall", [])
    ]
    # The host clock's freezes (the summary's totals; kind=freeze events, at
    # most 20 a run): counted apart from stalls,
    # which --strict gates on — a freeze is the machine's or the
    # interpreter's, a stall the program's.
    summaries = kinds.get("summary", [])
    s["freezes"] = summaries[-1].get("freezes") if summaries else None
    s["freeze_ms"] = summaries[-1].get("freeze_ms") if summaries else None
    s["freeze_events"] = [
        {
            "t": r.get("t"),
            "late_ms": r.get("late_ms"),
            "freeze_ms": r.get("freeze_ms"),
            "classification": r.get("classification"),
            "gc_ms": r.get("gc_ms"),
            "cpu_ms": r.get("cpu_ms"),
        }
        for r in kinds.get("freeze", [])
    ]
    s["anomalies"] = len(kinds.get("anomaly", []))
    s["anomaly_events"] = [
        {
            "step": r.get("step"),
            "event": r.get("event"),
            "loss": r.get("loss"),
            "first_nonfinite": r.get("first_nonfinite"),
        }
        for r in kinds.get("anomaly", [])
    ]
    # Resilience layer: fault / restart records (resilience.py) plus
    # rollback anomalies (on_nan = rollback recovery decisions).
    s["rollbacks"] = sum(
        1 for r in kinds.get("anomaly", []) if r.get("event") == "rollback"
    )
    faults = kinds.get("fault", [])
    s["faults"] = len(faults)
    s["fault_events"] = [
        {
            "step": r.get("step"),
            "event": r.get("event"),
            "exit_code": r.get("exit_code"),
            "signal": r.get("signal"),
            "what": r.get("what"),
        }
        for r in faults[:50]  # bounded: a retry storm must not bloat the report
    ]
    restarts = kinds.get("restart", [])
    s["restarts"] = len(restarts)
    s["restart_events"] = [
        {
            "attempt": r.get("attempt"),
            "exit_code": r.get("exit_code"),
            "backoff_s": r.get("backoff_s"),
            "mttr_s": r.get("mttr_s"),
        }
        for r in restarts
    ]
    mttrs = [
        r["mttr_s"] for r in restarts if isinstance(r.get("mttr_s"), (int, float))
    ]
    s["mttr_s_median"] = round(statistics.median(mttrs), 3) if mttrs else None
    s["mttr_s_max"] = round(max(mttrs), 3) if mttrs else None

    ckpts = kinds.get("ckpt", [])
    s["ckpt_saves"] = len(ckpts)
    s["ckpt_modes"] = {}
    for r in ckpts:
        mode = r.get("mode") or "?"
        s["ckpt_modes"][mode] = s["ckpt_modes"].get(mode, 0) + 1
    stall_ms = sum(
        r["train_stall_ms"]
        for r in ckpts
        if isinstance(r.get("train_stall_ms"), (int, float))
    )
    s["ckpt_stall_ms_total"] = round(stall_ms, 1) if ckpts else None
    s["ckpt_bytes_total"] = (
        sum(r.get("bytes") or 0 for r in ckpts) if ckpts else None
    )
    s["ckpt_rows_written"] = (
        sum(max(0, r.get("rows_written") or 0) for r in ckpts) if ckpts else None
    )
    # Checkpoint stall as a share of wall clock — the companion number to
    # input_time_share: together they say where the loop's non-compute
    # time went (feeding the chip vs saving the model).
    s["ckpt_stall_share"] = (
        round(stall_ms / 1e3 / s["duration_s"], 4)
        if ckpts and s["duration_s"]
        else (0.0 if s["duration_s"] else None)
    )

    # Per-host breakdown (multi-process pods): every record carries the
    # emitting host's process_index in the envelope; merging the per-host
    # JSONL files (report.py RUN.jsonl RUN.p1.jsonl ...) for one run_id
    # yields per-host throughput / stall / MTTR columns.  Host-LEVEL
    # faults (a peer's heartbeat lost, straggler kills, host crashes) are
    # counted separately — --compare --strict gates on them.
    procs = sorted(
        {
            r.get("process_index", 0)
            for r in records
            if isinstance(r.get("process_index"), int)
        }
    )
    s["hosts"] = {}
    host_faults = 0
    for r in kinds.get("stall", []):
        if str(r.get("classification", "")).startswith("host-"):
            host_faults += 1
    for r in faults:
        if r.get("event") in ("crash", "straggler_kill") and r.get("process") is not None:
            host_faults += 1
    s["host_faults"] = host_faults
    if len(procs) > 1:
        for p in procs:
            sub = [r for r in records if r.get("process_index", 0) == p]
            sk = _by_kind(sub)
            p_rates = [
                r["examples_per_sec"]
                for r in sk.get("train", [])
                if isinstance(r.get("examples_per_sec"), (int, float))
            ]
            p_mttrs = [
                r["mttr_s"]
                for r in sk.get("restart", [])
                if isinstance(r.get("mttr_s"), (int, float))
            ]
            s["hosts"][p] = {
                "records": len(sub),
                "throughput_median": (
                    round(statistics.median(p_rates), 1) if p_rates else None
                ),
                "steady_compiles": sum(
                    r.get("compiles", 0)
                    for r in sk.get("compile", [])
                    if not r.get("warmup")
                ),
                "stalls": len(sk.get("stall", [])),
                "faults": len(sk.get("fault", [])),
                "restarts": len(sk.get("restart", [])),
                "mttr_s_median": (
                    round(statistics.median(p_mttrs), 3) if p_mttrs else None
                ),
            }

    # Deep observability (ISSUE 9).  profile: one record per measured
    # compiled program (XLA cost analysis) — the MEASURED column DESIGN
    # §8.5's "re-measure only with evidence" reads next to the modeled
    # HBM floor; datastats: sampled id-traffic statistics; freshness:
    # publish→applied / publish→first-scored SLO samples.
    s["profiled_programs"] = {}
    for r in kinds.get("profile", []):
        if r.get("program") and r.get("program") != "trace":
            s["profiled_programs"][r["program"]] = {
                "bytes_accessed": r.get("bytes_accessed"),
                "flops": r.get("flops"),
                "examples": r.get("examples"),
                "bytes_per_example": r.get("bytes_per_example"),
                "modeled_hbm_bytes": r.get("modeled_hbm_bytes"),
            }
    s["trace_events"] = [
        {"step": r.get("step"), "event": r.get("event"), "trace_dir": r.get("trace_dir")}
        for r in kinds.get("profile", [])
        if r.get("program") == "trace"
    ]
    t = s["profiled_programs"].get("train_step") or {}
    s["measured_bytes_per_example"] = t.get("bytes_per_example")

    ds = kinds.get("datastats", [])
    s["datastats_samples"] = len(ds)
    dedups = [r["dedup_ratio"] for r in ds if isinstance(r.get("dedup_ratio"), (int, float))]
    s["dedup_ratio_mean"] = round(sum(dedups) / len(dedups), 4) if dedups else None
    s["datastats_last"] = (
        {
            k: ds[-1].get(k)
            for k in (
                "ids", "unique", "dedup_ratio", "rows_seen", "rows_seen_frac",
                "hh_k", "hh_topk_mass", "gather_bytes", "dedup_gather_bytes",
                "projected_gather_savings_frac",
            )
        }
        if ds
        else None
    )

    def _pctl(vals, q):
        # Nearest-rank over the (small, per-run) record lists; stdlib-only
        # like everything in this tool.
        if not vals:
            return None
        vals = sorted(vals)
        return round(vals[min(len(vals) - 1, int(q * len(vals)))], 3)

    fresh = kinds.get("freshness", [])
    applied = [
        r["publish_to_applied_ms"]
        for r in fresh
        if isinstance(r.get("publish_to_applied_ms"), (int, float))
    ]
    scored = [
        r["publish_to_first_scored_ms"]
        for r in fresh
        if isinstance(r.get("publish_to_first_scored_ms"), (int, float))
    ]
    s["freshness_samples"] = len(fresh)
    s["freshness_applied_p50_ms"] = _pctl(applied, 0.50)
    s["freshness_applied_p99_ms"] = _pctl(applied, 0.99)
    s["freshness_scored_p50_ms"] = _pctl(scored, 0.50)
    s["freshness_scored_p99_ms"] = _pctl(scored, 0.99)
    # The gate metric: end-to-end (first-scored) p99 where measured, the
    # applied p99 otherwise (router-only streams see staging, not
    # scoring).  `is None`, not truthiness: a clamped-to-0 scored p99
    # (publisher clock ahead) is still the scored metric, and silently
    # swapping to applied would gate two different metrics against each
    # other in --compare.
    s["freshness_p99_ms"] = (
        s["freshness_scored_p99_ms"]
        if s["freshness_scored_p99_ms"] is not None
        else s["freshness_applied_p99_ms"]
    )

    mems = kinds.get("mem", [])
    s["host_rss_peak_bytes"] = max(
        (r["host_rss_peak_bytes"] for r in mems if r.get("host_rss_peak_bytes")),
        default=None,
    )
    s["device_peak_bytes"] = max(
        (r["device_peak_bytes"] for r in mems if r.get("device_peak_bytes")),
        default=None,
    )

    vals = kinds.get("validation", [])
    s["validation_aucs"] = [
        r["validation_auc"] for r in vals if r.get("validation_auc") is not None
    ]

    # Online-learning loop (ISSUE 11).  quality: the rolling backtest's
    # per-hour held-out AUC for the online trainer vs its batch-retrain
    # reference (tools/backtest.py); the worst-hour gap (batch − online)
    # is the single-run regression signal --strict gates on.  soak: the
    # sustained-soak harness's sentinel ticks (tools/soak.py) — any
    # ok=false tick is a soak failure.
    qual = kinds.get("quality", [])
    s["quality_hours"] = len(qual)
    s["quality_auc_by_hour"] = [
        {
            "hour": r.get("hour"),
            "online": r.get("auc_online"),
            "batch": r.get("auc_batch"),
        }
        for r in qual
    ]
    q_on = [r["auc_online"] for r in qual if isinstance(r.get("auc_online"), (int, float))]
    q_ba = [r["auc_batch"] for r in qual if isinstance(r.get("auc_batch"), (int, float))]
    s["quality_auc_online_mean"] = (
        round(sum(q_on) / len(q_on), 5) if q_on else None
    )
    s["quality_auc_batch_mean"] = round(sum(q_ba) / len(q_ba), 5) if q_ba else None
    gaps = [
        r["auc_batch"] - r["auc_online"]
        for r in qual
        if isinstance(r.get("auc_online"), (int, float))
        and isinstance(r.get("auc_batch"), (int, float))
    ]
    s["quality_auc_gap_max"] = round(max(gaps), 5) if gaps else None
    soak = kinds.get("soak", [])
    s["soak_ticks"] = len(soak)
    s["soak_failures"] = sum(1 for r in soak if r.get("ok") is False)
    s["soak_failed_phases"] = sorted(
        {str(r.get("phase")) for r in soak if r.get("ok") is False}
    )
    serving = kinds.get("serving", [])
    s["serving_last"] = serving[-1] if serving else None

    # Replicated serving tier (ISSUE 8): per-replica kind=serving records
    # carry a `replica` envelope key (each replica worker writes its own
    # JSONL sibling — pass them all: report.py RUN.jsonl RUN.jsonl.r0 ...).
    # Replica-level faults/restarts come from the router's records.
    by_replica: dict = {}
    for r in serving:
        if isinstance(r.get("replica"), int):
            by_replica[r["replica"]] = r  # last snapshot wins per replica
    s["serving_replicas"] = by_replica
    s["deadline_drops"] = (
        sum(r.get("deadline_drops") or 0 for r in by_replica.values())
        if by_replica
        else (s["serving_last"] or {}).get("deadline_drops")
    )
    sheds: dict[str, int] = {}
    class_p99: dict[str, float] = {}
    snaps = list(by_replica.values()) or ([s["serving_last"]] if s["serving_last"] else [])
    for r in snaps:
        for k, v in (r.get("sheds_by_class") or {}).items():
            sheds[k] = sheds.get(k, 0) + v
        for k, h in (r.get("class_total_ms") or {}).items():
            p99 = h.get("p99")
            if isinstance(p99, (int, float)):
                # Max across replicas: the SLO is only as good as the
                # worst replica a client can land on.
                class_p99[k] = max(class_p99.get(k, 0.0), p99)
    s["sheds_by_class"] = sheds
    s["class_p99_ms"] = class_p99
    # Per-bucket padding waste, summed across replicas (the occupancy
    # fix's observability: bucket chosen AFTER the coalescing flush).
    b_rows: dict[str, int] = {}
    b_padded: dict[str, int] = {}
    for r in snaps:
        for k, v in (r.get("bucket_rows") or {}).items():
            b_rows[k] = b_rows.get(k, 0) + (v or 0)
        for k, v in (r.get("bucket_padded_rows") or {}).items():
            b_padded[k] = b_padded.get(k, 0) + (v or 0)
    s["bucket_rows"] = b_rows
    s["bucket_padded_rows"] = b_padded
    s["bucket_occupancy"] = {
        k: round(r / (r + b_padded.get(k, 0)), 4)
        for k, r in sorted(b_rows.items(), key=lambda kv: int(kv[0]))
        if r + b_padded.get(k, 0) > 0
    }
    s["replica_faults"] = sum(
        1 for r in faults if isinstance(r.get("replica"), int)
    )
    s["replica_fault_events"] = [
        {
            "replica": r.get("replica"),
            "event": r.get("event"),
            "exit_code": r.get("exit_code"),
        }
        for r in faults
        if isinstance(r.get("replica"), int)
    ][:50]
    rep_restarts = [
        r for r in restarts if isinstance(r.get("replica"), int)
    ]
    s["replica_restarts"] = len(rep_restarts)
    rep_mttrs = [
        r["mttr_s"] for r in rep_restarts if isinstance(r.get("mttr_s"), (int, float))
    ]
    s["replica_mttr_s_median"] = (
        round(statistics.median(rep_mttrs), 3) if rep_mttrs else None
    )
    s["replica_mttr_s_max"] = round(max(rep_mttrs), 3) if rep_mttrs else None
    # Tiered parameter store (ISSUE 12; paramstore/): per-log-window
    # residency records.  The hit rate and miss bytes are the two numbers
    # --compare --strict gates on: a hot set gone stale (hit rate down)
    # or a staging path gone fat (miss bytes up) are regressions even
    # when raw throughput holds.
    tier = kinds.get("tiering", [])
    s["tiering_windows"] = len(tier)
    hits = [r["hit_rate"] for r in tier if isinstance(r.get("hit_rate"), (int, float))]
    s["tier_hit_rate_mean"] = round(sum(hits) / len(hits), 4) if hits else None
    mbytes = [
        r["miss_bytes_per_step"]
        for r in tier
        if isinstance(r.get("miss_bytes_per_step"), (int, float))
    ]
    s["tier_miss_bytes_per_step"] = (
        int(statistics.median(mbytes)) if mbytes else None
    )
    s["tier_miss_rows"] = sum(r.get("miss_rows") or 0 for r in tier) if tier else None
    s["tier_writeback_rows"] = (
        sum(r.get("writeback_rows") or 0 for r in tier) if tier else None
    )
    # writeback_ms is a mean a writeback, and a step makes one.
    wb_ms = sum(
        (r.get("writeback_ms") or 0) * (r.get("steps") or 1)
        + (r.get("apply_ms") or 0)
        for r in tier
    )
    s["tier_writeback_ms_total"] = round(wb_ms, 1) if tier else None
    # Writeback stall share: staging D2H + store applies as a fraction of
    # wall clock — the tiered sibling of ckpt_stall_share.
    s["tier_writeback_share"] = (
        round(wb_ms / 1e3 / s["duration_s"], 4)
        if tier and s["duration_s"]
        else None
    )
    s["tier_restages"] = sum(r.get("restages") or 0 for r in tier) if tier else None
    s["tier_pending_rows_max"] = (
        max((r.get("pending_rows") or 0) for r in tier) if tier else None
    )
    s["tier_hot_rows"] = tier[-1].get("hot_rows") if tier else None
    predict = kinds.get("predict", [])
    s["predict_last"] = predict[-1] if predict else None
    summary = kinds.get("summary", [])
    s["summary_record"] = summary[-1] if summary else None
    return s


def render(s: dict, title: str = "run") -> str:
    """One markdown report per run.  Sections appear only when the run
    actually produced that kind — a predict run isn't padded with empty
    train tables."""
    L = [f"# Telemetry report — {title}", ""]
    L.append(f"- run_id: `{', '.join(s['run_ids']) or '?'}`")
    L.append(f"- duration: {_fmt(s['duration_s'], 1)} s, max step {s['steps']}")
    L.append(
        "- records: "
        + ", ".join(f"{k}={n}" for k, n in s["kinds"].items())
    )
    L.append("")
    if s["throughput_timeline"]:
        L += ["## Throughput", ""]
        L.append(f"`{spark(s['throughput_timeline'])}` examples/sec per log window")
        L.append(
            f"- median {_fmt(s['throughput_median'])}, "
            f"final {_fmt(s['throughput_final'])}, "
            f"min {_fmt(min(s['throughput_timeline']))}, "
            f"max {_fmt(max(s['throughput_timeline']))}"
        )
        L.append("")
    if s["loss_timeline"]:
        L += ["## Loss", ""]
        L.append(f"`{spark(s['loss_timeline'])}`")
        L.append(f"- first {s['loss_first']} → final {s['loss_final']}")
        if s["validation_aucs"]:
            L.append(
                "- validation AUC per epoch: "
                + ", ".join(f"{a:.5f}" for a in s["validation_aucs"])
            )
        L.append("")
    if any(
        s[k] is not None
        for k in ("parse_ms_mean", "h2d_ms_mean", "input_time_share")
    ):
        L += ["## Input path", ""]
        L.append(f"- parse {_fmt(s['parse_ms_mean'], 3)} ms/item, "
                 f"pack+H2D {_fmt(s['h2d_ms_mean'], 3)} ms/item")
        L.append(f"- wire bytes/step: {_fmt(s['wire_bytes_per_step'], 0)}")
        L.append(
            f"- prefetch queue depth mean: {_fmt(s['prefetch_queue_depth_mean'], 2)} "
            "(≈0 = producer-bound, at cap = consumer-bound)"
        )
        if s["input_time_share"] is not None:
            L.append(
                f"- host input time ≈ {100 * s['input_time_share']:.1f}% of wall "
                "clock (overlapped via prefetch)"
            )
        if s.get("ckpt_stall_share") is not None:
            L.append(
                f"- checkpoint stall ≈ {100 * s['ckpt_stall_share']:.1f}% of wall "
                "clock (train-loop time blocked on saves)"
            )
        L.append("")
    if s.get("ckpt_saves"):
        L += ["## Checkpointing", ""]
        modes = ", ".join(f"{m}={n}" for m, n in sorted(s["ckpt_modes"].items()))
        L.append(
            f"- {s['ckpt_saves']} save(s) ({modes}), "
            f"{_fmt_bytes(s['ckpt_bytes_total'])} written, "
            f"{_fmt(s['ckpt_rows_written'], 0)} rows"
        )
        L.append(
            f"- train-loop stall {_fmt(s['ckpt_stall_ms_total'])} ms total"
            + (
                f" ({100 * s['ckpt_stall_share']:.1f}% of wall clock)"
                if s.get("ckpt_stall_share") is not None
                else ""
            )
        )
        L.append("")
    if s.get("tiering_windows"):
        L += ["## Parameter store (tiered)", ""]
        L.append(
            f"- hot tier {_fmt(s['tier_hot_rows'], 0)} rows, "
            f"hit rate {_fmt(100 * (s['tier_hit_rate_mean'] or 0), 2)}% of "
            "gather slots"
        )
        L.append(
            f"- miss bytes/step {_fmt_bytes(s['tier_miss_bytes_per_step'])} "
            f"({_fmt(s['tier_miss_rows'], 0)} staged rows total)"
        )
        L.append(
            f"- writeback: {_fmt(s['tier_writeback_rows'], 0)} rows, "
            f"{_fmt(s['tier_writeback_ms_total'])} ms stall"
            + (
                f" ({100 * s['tier_writeback_share']:.1f}% of wall clock)"
                if s.get("tier_writeback_share") is not None
                else ""
            )
        )
        L.append(
            f"- coherency restages: {s['tier_restages']}, pending peak "
            f"{_fmt(s['tier_pending_rows_max'], 0)} rows"
        )
        L.append("")
    L += ["## Events", ""]
    L.append(
        f"- compiles: {s['warmup_compiles']} warmup, "
        f"**{s['steady_compiles']} steady-state**"
        + (
            f" (at steps {s['steady_compile_steps']})"
            if s["steady_compiles"]
            else ""
        )
    )
    L.append(f"- stalls: {s['stalls']}")
    for e in s["stall_events"]:
        L.append(
            f"  - step {e['step']}: {e['classification']}, "
            f"{e['since_last_step_s']}s without a step, "
            f"queue depth {e['prefetch_queue_depth']}"
        )
    L.append(
        f"- freezes: {_fmt(s['freezes'], 0)} ({_fmt(s['freeze_ms'])} ms in which "
        "no Python thread ran; the first 20 below)"
    )
    for e in s["freeze_events"]:
        L.append(
            f"  - t={e['t']}s: {e['classification']}, clock {e['late_ms']} ms late, "
            f"{e['freeze_ms']} ms frozen (gc {e['gc_ms']} ms, cpu {e['cpu_ms']} ms)"
        )
    L.append(f"- anomalies: {s['anomalies']}")
    for e in s["anomaly_events"]:
        L.append(
            f"  - step {e['step']}: {e['event']} loss={e['loss']}"
            + (
                f" first_nonfinite={e['first_nonfinite']}"
                if e.get("first_nonfinite")
                else ""
            )
        )
    L.append("")
    if s.get("faults") or s.get("restarts") or s.get("rollbacks"):
        L += ["## Resilience", ""]
        L.append(
            f"- faults: {s['faults']}, restarts: {s['restarts']}, "
            f"rollbacks: {s['rollbacks']}"
        )
        for e in s["fault_events"]:
            detail = ", ".join(
                f"{k}={v}"
                for k, v in e.items()
                if k not in ("step", "event") and v is not None
            )
            L.append(
                f"  - step {e['step']}: fault {e['event']}"
                + (f" ({detail})" if detail else "")
            )
        for e in s["restart_events"]:
            L.append(
                f"  - restart #{e['attempt']}: child rc {e['exit_code']}, "
                f"backoff {e['backoff_s']}s, MTTR {e['mttr_s']}s"
            )
        if s.get("mttr_s_median") is not None:
            L.append(
                f"- MTTR (crash → first new progress): median "
                f"{s['mttr_s_median']}s, max {s['mttr_s_max']}s"
            )
        L.append("")
    if s.get("hosts"):
        L += ["## Hosts (per-process breakdown)", ""]
        L.append(
            "| host | records | ex/s median | steady compiles | stalls | "
            "faults | restarts | MTTR median |"
        )
        L.append("|---:|---:|---:|---:|---:|---:|---:|---:|")
        for p, h in sorted(s["hosts"].items()):
            L.append(
                f"| {p} | {h['records']} | {_fmt(h['throughput_median'])} | "
                f"{h['steady_compiles']} | {h['stalls']} | {h['faults']} | "
                f"{h['restarts']} | {_fmt(h['mttr_s_median'], 3)} |"
            )
        if s.get("host_faults"):
            L.append(f"- host-level faults: {s['host_faults']}")
        L.append("")
    if s.get("profiled_programs") or s.get("trace_events"):
        L += ["## Profiling (measured vs modeled)", ""]
        if s["profiled_programs"]:
            L.append(
                "| program | measured bytes/dispatch | modeled HBM floor | "
                "× floor | bytes/example | MFLOPs |"
            )
            L.append("|---|---:|---:|---:|---:|---:|")
            for name, p in sorted(s["profiled_programs"].items()):
                meas, mod = p.get("bytes_accessed"), p.get("modeled_hbm_bytes")
                ratio = (
                    f"{meas / mod:.2f}"
                    if isinstance(meas, (int, float))
                    and isinstance(mod, (int, float))
                    and mod > 0
                    else "–"
                )
                fl = p.get("flops")
                L.append(
                    f"| {name} | {_fmt_bytes(meas)} | {_fmt_bytes(mod)} | "
                    f"{ratio} | {_fmt(p.get('bytes_per_example'), 1)} | "
                    f"{_fmt(round(fl / 1e6, 2) if isinstance(fl, (int, float)) else None)} |"
                )
            L.append(
                "- measured = XLA cost analysis (bytes accessed) of the "
                "compiled program; modeled = the driver's irreducible-HBM "
                "floor for the same dispatch (DESIGN §8.5: re-measure only "
                "with evidence — this is the evidence column)"
            )
        for e in s.get("trace_events", []):
            L.append(
                f"- trace {e['event']} at step {e['step']} → `{e['trace_dir']}`"
            )
        L.append("")
    if s.get("datastats_samples"):
        d = s["datastats_last"] or {}
        L += ["## Id-traffic statistics", ""]
        L.append(
            f"- {s['datastats_samples']} sampled windows; dedup ratio "
            f"(unique/slots) mean {_fmt(s['dedup_ratio_mean'], 4)}, "
            f"last {_fmt(d.get('dedup_ratio'), 4)}"
        )
        L.append(
            f"- last window: {_fmt(d.get('ids'))} id slots, "
            f"{_fmt(d.get('unique'))} unique; rows seen (cumulative) "
            f"{_fmt(d.get('rows_seen'))} ({_fmt(d.get('rows_seen_frac'), 4)} of vocab)"
        )
        if d.get("hh_topk_mass") is not None:
            L.append(
                f"- heavy hitters: top-{d.get('hh_k')} sketch buckets carry "
                f"{100 * d['hh_topk_mass']:.1f}% of gather traffic (upper "
                "bound — collisions overstate)"
            )
        if d.get("projected_gather_savings_frac") is not None:
            L.append(
                f"- projected dedup-before-gather saving: "
                f"{100 * d['projected_gather_savings_frac']:.1f}% of gather bytes "
                f"({_fmt_bytes(d.get('gather_bytes'))} → "
                f"{_fmt_bytes(d.get('dedup_gather_bytes'))} per dispatch)"
            )
        L.append("")
    if s.get("freshness_samples"):
        L += ["## Freshness (publish → serving)", ""]
        L.append(
            f"- {s['freshness_samples']} reload(s): publish→applied p50/p99 "
            f"{_fmt(s['freshness_applied_p50_ms'])}/"
            f"{_fmt(s['freshness_applied_p99_ms'])} ms"
        )
        if s.get("freshness_scored_p50_ms") is not None:
            L.append(
                f"- publish→first-scored-with-new-rows p50/p99 "
                f"{_fmt(s['freshness_scored_p50_ms'])}/"
                f"{_fmt(s['freshness_scored_p99_ms'])} ms"
            )
        L.append("")
    if s.get("quality_hours"):
        L += ["## Online quality (rolling backtest)", ""]
        L.append("| hour | online AUC | batch-retrain AUC | gap |")
        L.append("|---:|---:|---:|---:|")
        for row in s["quality_auc_by_hour"]:
            gap = (
                round(row["batch"] - row["online"], 5)
                if isinstance(row.get("online"), (int, float))
                and isinstance(row.get("batch"), (int, float))
                else None
            )
            L.append(
                f"| {row['hour']} | {row['online']} | {row['batch']} | {gap} |"
            )
        L.append(
            f"- mean online {s['quality_auc_online_mean']} vs batch "
            f"{s['quality_auc_batch_mean']}; worst-hour gap "
            f"{s['quality_auc_gap_max']}"
        )
        L.append("")
    if s.get("soak_ticks"):
        L += ["## Soak sentinels", ""]
        L.append(
            f"- {s['soak_ticks']} sentinel tick(s), "
            f"{s['soak_failures']} failed"
            + (
                f" (phases: {', '.join(s['soak_failed_phases'])})"
                if s.get("soak_failed_phases")
                else ""
            )
        )
        L.append("")
    L += ["## Memory", ""]
    L.append(f"- host RSS peak: {_fmt_bytes(s['host_rss_peak_bytes'])}")
    L.append(f"- device live-buffer peak: {_fmt_bytes(s['device_peak_bytes'])}")
    L.append("")
    if s["predict_last"]:
        p = s["predict_last"]
        L += [
            "## Predict",
            "",
            f"- {_fmt(p.get('examples'))} examples at "
            f"{_fmt(p.get('examples_per_sec'))} examples/sec",
            "",
        ]
    if s["serving_last"]:
        sv = s["serving_last"]
        L += ["## Serving (last snapshot)", ""]
        L.append(
            f"- requests {_fmt(sv.get('requests'))}, rejected "
            f"{_fmt(sv.get('rejected'))}, flushes {_fmt(sv.get('flushes'))}, "
            f"occupancy {sv.get('batch_occupancy')}"
        )
        for stage in ("queue_ms", "compute_ms", "total_ms"):
            h = sv.get(stage) or {}
            L.append(
                f"- {stage}: p50 {h.get('p50')}, p95 {h.get('p95')}, "
                f"p99 {h.get('p99')}, max {h.get('max')}"
            )
        if sv.get("bucket_occupancy"):
            L.append("- per-bucket occupancy: " + _fmt_bucket_occupancy(sv))
        L.append("")
    if s.get("serving_replicas") or s.get("replica_faults"):
        L += ["## Serving resilience (replicated tier)", ""]
        if s.get("serving_replicas"):
            L.append(
                "| replica | requests | rows scored | ex/s | deadline_drops "
                "| sheds | p99 ms |"
            )
            L.append("|---:|---:|---:|---:|---:|---:|---:|")
            for rep, sv in sorted(s["serving_replicas"].items()):
                rows = sv.get("rows")
                qps = (
                    round(rows / s["duration_s"], 1)
                    if isinstance(rows, (int, float)) and s["duration_s"]
                    else None
                )
                shed_n = sum((sv.get("sheds_by_class") or {}).values())
                L.append(
                    f"| {rep} | {_fmt(sv.get('requests'))} | {_fmt(rows)} | "
                    f"{_fmt(qps)} | {_fmt(sv.get('deadline_drops'))} | "
                    f"{_fmt(shed_n)} | "
                    f"{(sv.get('total_ms') or {}).get('p99')} |"
                )
        if s.get("sheds_by_class"):
            L.append(
                "- sheds by class: "
                + ", ".join(f"{k}={v}" for k, v in sorted(s["sheds_by_class"].items()))
            )
        if s.get("class_p99_ms"):
            L.append(
                "- per-class p99 (worst replica): "
                + ", ".join(
                    f"{k}={v}ms" for k, v in sorted(s["class_p99_ms"].items())
                )
            )
        if s.get("bucket_occupancy"):
            L.append(
                "- per-bucket occupancy (all replicas): "
                + _fmt_bucket_occupancy(
                    {
                        "bucket_rows": s.get("bucket_rows"),
                        "bucket_padded_rows": s.get("bucket_padded_rows"),
                        "bucket_occupancy": s["bucket_occupancy"],
                    }
                )
            )
        L.append(
            f"- replica faults: {s.get('replica_faults', 0)}, restarts: "
            f"{s.get('replica_restarts', 0)}"
        )
        for e in s.get("replica_fault_events", []):
            L.append(
                f"  - replica {e['replica']}: {e['event']}"
                + (f" (rc={e['exit_code']})" if e.get("exit_code") is not None else "")
            )
        if s.get("replica_mttr_s_median") is not None:
            L.append(
                f"- replica MTTR (death detected → healthy again): median "
                f"{s['replica_mttr_s_median']}s, max {s['replica_mttr_s_max']}s"
            )
        L.append("")
    return "\n".join(L)


# -- compare (the bench gate) --------------------------------------------

# (metric key, human label, higher_is_better)
_GATE_METRICS = [
    ("throughput_median", "median examples/sec", True),
    ("throughput_final", "final examples/sec", True),
    ("loss_final", "final loss", False),
    ("steady_compiles", "steady-state compiles", False),
    ("stalls", "stalls", False),
    ("anomalies", "anomalies", False),
    ("faults", "faults", False),
    ("restarts", "restarts", False),
    ("rollbacks", "rollbacks", False),
    ("deadline_drops", "serving deadline drops", False),
    ("replica_faults", "serving replica faults", False),
    ("replica_restarts", "serving replica restarts", False),
    ("host_rss_peak_bytes", "host RSS peak", False),
    ("device_peak_bytes", "device mem peak", False),
    ("ckpt_stall_share", "ckpt stall share", False),
    ("measured_bytes_per_example", "measured HBM bytes/example", False),
    ("dedup_ratio_mean", "id dedup ratio (unique/slots)", False),
    ("freshness_p99_ms", "freshness p99 (ms)", False),
    ("quality_auc_online_mean", "backtest online AUC (mean)", True),
    ("quality_auc_gap_max", "backtest worst-hour AUC gap", False),
    ("soak_failures", "failed soak sentinel ticks", False),
    ("tier_hit_rate_mean", "paramstore hot-tier hit rate", True),
    ("tier_miss_bytes_per_step", "paramstore miss bytes/step", False),
    ("tier_restages", "paramstore coherency restages", False),
]


def compare(run: dict, base: dict, threshold: float, strict: bool = False):
    """Per-metric deltas (run vs base) + the gate verdict.

    Returns (markdown, regressions: list[str]).  The hard gate is median
    throughput degraded by more than ``threshold`` (fraction); ``strict``
    adds NEW steady compiles / stalls / anomalies to the gate.
    """
    L = ["# Telemetry compare — run vs base", ""]
    L.append("| metric | base | run | delta |")
    L.append("|---|---:|---:|---:|")
    regressions = []
    for key, label, _hib in _GATE_METRICS:
        a, b = run.get(key), base.get(key)
        if a is None and b is None:
            continue
        if isinstance(a, (int, float)) and isinstance(b, (int, float)) and b:
            delta = f"{(a - b) / abs(b) * 100:+.1f}%"
        elif isinstance(a, (int, float)) and isinstance(b, (int, float)):
            delta = f"{a - b:+g}"
        else:
            delta = "–"
        L.append(f"| {label} | {_fmt(b)} | {_fmt(a)} | {delta} |")
    L.append("")
    a, b = run.get("throughput_median"), base.get("throughput_median")
    if isinstance(a, (int, float)) and isinstance(b, (int, float)) and b > 0:
        drop = (b - a) / b
        if drop > threshold:
            regressions.append(
                f"median throughput degraded {drop * 100:.1f}% "
                f"(> {threshold * 100:.0f}% threshold): {b} -> {a}"
            )
    elif a is None and isinstance(b, (int, float)) and b > 0:
        # A gate that passes when the candidate produced NO throughput
        # records would wave through the worst regression of all: a run
        # that crashed or wedged before its first log window.
        regressions.append(
            "run has no train throughput records (base has "
            f"{b}) — crashed/stalled before the first log window?"
        )
    if strict:
        for key, label in (
            ("steady_compiles", "steady-state compiles"),
            ("stalls", "stalls"),
            ("anomalies", "anomalies"),
            ("faults", "faults"),
            ("restarts", "restarts"),
            ("rollbacks", "rollbacks"),
            ("host_faults", "host-level faults"),
            ("replica_faults", "serving replica faults"),
        ):
            if (run.get(key) or 0) > (base.get(key) or 0):
                regressions.append(
                    f"new {label}: {base.get(key) or 0} -> {run.get(key) or 0}"
                )
        # Per-class serving p99 SLO gate: a class whose worst-replica p99
        # degraded past the threshold regresses even if the aggregate
        # (dominated by the bulk class) still looks fine — priority
        # classes are exactly the ones a mean would hide.
        for k, bp in (base.get("class_p99_ms") or {}).items():
            rp = (run.get("class_p99_ms") or {}).get(k)
            if (
                isinstance(rp, (int, float))
                and isinstance(bp, (int, float))
                and bp > 0
                and rp > bp * (1 + threshold)
            ):
                regressions.append(
                    f"serving class {k!r} p99 regressed "
                    f"{(rp - bp) / bp * 100:.1f}% (> {threshold * 100:.0f}%): "
                    f"{bp}ms -> {rp}ms"
                )
        # The ISSUE-9 SLO gates: a freshness p99 regression (the model is
        # measurably staler at the replicas) and a measured-bytes-per-
        # example regression (the compiled step moves more HBM per row
        # than the base did — the evidence ledger as an enforced budget).
        # Freshness gates FLAVOR-MATCHED: scored-vs-scored when both runs
        # measured end-to-end, else applied-vs-applied — a run that only
        # saw staging must never be gated against one that saw scoring
        # (applied <= scored by construction, so a mixed pair would mask
        # a real regression or invent a spurious one).
        if (
            run.get("freshness_scored_p99_ms") is not None
            and base.get("freshness_scored_p99_ms") is not None
        ):
            fresh_gate = (
                "freshness_scored_p99_ms", "freshness p99 (publish→first-scored)",
            )
        else:
            fresh_gate = (
                "freshness_applied_p99_ms", "freshness p99 (publish→applied)",
            )
        for key, label, floor in (
            (*fresh_gate, 1.0),
            ("measured_bytes_per_example", "measured HBM bytes/example", 0.0),
        ):
            rv, bv = run.get(key), base.get(key)
            if (
                isinstance(rv, (int, float))
                and isinstance(bv, (int, float))
                and bv > floor
                and rv > bv * (1 + threshold)
            ):
                regressions.append(
                    f"{label} regressed {(rv - bv) / bv * 100:.1f}% "
                    f"(> {threshold * 100:.0f}%): {bv} -> {rv}"
                )
        # Online-quality gates (ISSUE 11).  Against a BASE with backtest
        # records: the online trainer's mean held-out AUC must not drop
        # more than the threshold fraction.  Within the RUN alone: the
        # worst-hour gap to its OWN batch-retrain reference must stay
        # under the threshold (read as absolute AUC points here — AUC is
        # already a [0.5, 1] fraction), and any failed soak sentinel tick
        # is a regression outright.
        rq, bq = run.get("quality_auc_online_mean"), base.get("quality_auc_online_mean")
        if (
            isinstance(rq, (int, float))
            and isinstance(bq, (int, float))
            and bq > 0
            and rq < bq * (1 - threshold)
        ):
            regressions.append(
                f"online backtest AUC regressed {(bq - rq) / bq * 100:.1f}% "
                f"(> {threshold * 100:.0f}%): {bq} -> {rq}"
            )
        gap = run.get("quality_auc_gap_max")
        if isinstance(gap, (int, float)) and gap > threshold:
            regressions.append(
                f"online trainer trails its batch-retrain reference by "
                f"{gap:.4f} AUC at the worst hour (> {threshold:.2f})"
            )
        if (run.get("soak_failures") or 0) > 0:
            regressions.append(
                f"{run['soak_failures']} soak sentinel tick(s) failed "
                f"(phases: {', '.join(run.get('soak_failed_phases') or [])})"
            )
        # Tiered-parameter-store gates (ISSUE 12): a hot-tier HIT-RATE
        # drop past the threshold (the residency decision got worse — the
        # staging path absorbs gathers the hot tier should) and a
        # MISS-BYTES-per-step increase past it (the wire/staging traffic
        # the hit rate is supposed to bound).  Both only when both runs
        # are tiered.
        rh, bh = run.get("tier_hit_rate_mean"), base.get("tier_hit_rate_mean")
        if (
            isinstance(rh, (int, float))
            and isinstance(bh, (int, float))
            and bh > 0
            and rh < bh * (1 - threshold)
        ):
            regressions.append(
                f"paramstore hit rate regressed {(bh - rh) / bh * 100:.1f}% "
                f"(> {threshold * 100:.0f}%): {bh} -> {rh}"
            )
        rm, bm = (
            run.get("tier_miss_bytes_per_step"),
            base.get("tier_miss_bytes_per_step"),
        )
        if (
            isinstance(rm, (int, float))
            and isinstance(bm, (int, float))
            and bm > 0
            and rm > bm * (1 + threshold)
        ):
            regressions.append(
                f"paramstore miss bytes/step regressed "
                f"{(rm - bm) / bm * 100:.1f}% (> {threshold * 100:.0f}%): "
                f"{bm} -> {rm}"
            )
        # Checkpoint stall share regression: the run spends a meaningfully
        # larger fraction of wall clock blocked on saves than the base did.
        # The 1% absolute floor keeps end-of-run sync saves (every run has
        # one) from flagging noise on short runs.
        rs = run.get("ckpt_stall_share") or 0.0
        bs = base.get("ckpt_stall_share") or 0.0
        if rs > 0.01 and rs > bs * (1 + threshold) + 0.002:
            regressions.append(
                f"ckpt stall share regressed: {bs:.3f} -> {rs:.3f} of wall clock"
            )
    if regressions:
        L.append("**REGRESSED:**")
        L += [f"- {r}" for r in regressions]
    else:
        L.append(f"OK — no regression beyond the {threshold * 100:.0f}% threshold.")
    L.append("")
    return "\n".join(L), regressions


# -- serving bench (loadgen artifacts) ------------------------------------


def load_bench_serve(path: str) -> dict:
    """A ``tools/loadgen.py --out`` artifact (BENCH_SERVE_rNN.json);
    raises ValueError on anything else."""
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict) or data.get("bench") != "BENCH_SERVE":
        raise ValueError(
            f"{path}: not a BENCH_SERVE artifact (tools/loadgen.py --out)"
        )
    return data


def render_bench_serve(b: dict, base: dict | None = None) -> str:
    """The "Serving bench" section: offered vs scored QPS, per-class
    client latency, and typed shed counts from a loadgen artifact (with
    ``base``, side by side against the previous round's)."""
    L = ["## Serving bench (loadgen)", ""]
    rows = [
        ("offered QPS", "qps_target"),
        ("scored QPS", "qps_achieved"),
        ("requests sent", "requests_sent"),
        ("requests scored", "requests_scored"),
        ("unanswered", "unanswered"),
        ("wire", "wire"),
        ("sender processes", "processes"),
        ("connections", "connections"),
        ("client failovers", "client_failovers"),
        ("deadline (ms)", "deadline_ms"),
    ]
    if base is None:
        L += ["| metric | run |", "|---|---:|"]
        for label, key in rows:
            L.append(f"| {label} | {_fmt(b.get(key)) if not isinstance(b.get(key), str) else b[key]} |")
    else:
        L += ["| metric | base | run |", "|---|---:|---:|"]
        for label, key in rows:
            bv, rv = base.get(key), b.get(key)
            bs = bv if isinstance(bv, str) else _fmt(bv)
            rs = rv if isinstance(rv, str) else _fmt(rv)
            L.append(f"| {label} | {bs} | {rs} |")
    for klass, h in sorted((b.get("client_ms_by_class") or {}).items()):
        bh = ((base or {}).get("client_ms_by_class") or {}).get(klass) or {}
        vs = f" (base p99 {bh.get('p99')})" if bh else ""
        L.append(
            f"- class {klass!r}: client p50 {h.get('p50')}ms, "
            f"p99 {h.get('p99')}ms over {_fmt(h.get('count'))} scored{vs}"
        )
    codes = b.get("shed_codes") or {}
    if codes:
        L.append(
            "- typed sheds: "
            + ", ".join(f"{k}={v}" for k, v in sorted(codes.items()))
        )
    L.append("")
    return "\n".join(L)


def compare_bench_serve(run_b: dict, base_b: dict, threshold: float) -> list[str]:
    """Strict-gate regressions between two BENCH_SERVE artifacts: scored
    QPS down past the threshold, any per-class CLIENT p99 up past it,
    and unanswered requests where the base had none.  Client-side
    latency, not engine-side: the queueing a saturated data plane hides
    from engine histograms is exactly what the client clock sees."""
    regressions = []
    rq, bq = run_b.get("qps_achieved"), base_b.get("qps_achieved")
    if (
        isinstance(rq, (int, float))
        and isinstance(bq, (int, float))
        and bq > 0
        and rq < bq * (1 - threshold)
    ):
        regressions.append(
            f"serving scored QPS regressed {(bq - rq) / bq * 100:.1f}% "
            f"(> {threshold * 100:.0f}%): {bq} -> {rq}"
        )
    elif rq is None and isinstance(bq, (int, float)) and bq > 0:
        regressions.append(
            f"run bench has no qps_achieved (base scored {bq}) — "
            "loadgen died before writing results?"
        )
    for klass, bh in sorted((base_b.get("client_ms_by_class") or {}).items()):
        bp = (bh or {}).get("p99")
        rp = ((run_b.get("client_ms_by_class") or {}).get(klass) or {}).get("p99")
        if (
            isinstance(rp, (int, float))
            and isinstance(bp, (int, float))
            and bp > 0
            and rp > bp * (1 + threshold)
        ):
            regressions.append(
                f"serving bench class {klass!r} client p99 regressed "
                f"{(rp - bp) / bp * 100:.1f}% (> {threshold * 100:.0f}%): "
                f"{bp}ms -> {rp}ms"
            )
    if (run_b.get("unanswered") or 0) > (base_b.get("unanswered") or 0):
        regressions.append(
            f"serving bench unanswered requests: "
            f"{base_b.get('unanswered') or 0} -> {run_b.get('unanswered') or 0} "
            "(every admitted request must resolve to a score or a typed shed)"
        )
    return regressions


# -- static analysis ------------------------------------------------------


def load_analysis(path: str) -> dict:
    """Output of ``tools/analysis/run.py --json``; raises ValueError on
    anything else."""
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict) or "counts" not in data or "baseline" not in data:
        raise ValueError(f"{path}: not an analysis JSON (run.py --json output)")
    return data


def render_analysis(a: dict, base_a: dict | None = None) -> str:
    """The "Analysis" section: findings by rule/severity + baseline debt,
    per rule.  Debt = findings the committed baseline excuses; the
    compare gate fails --strict when it grows.  With ``base_a`` (the
    --analysis-base payload) each rule row also shows its debt DELTA, so
    "who re-pinned instead of fixing" is visible per checker, not just in
    the total."""
    counts = a.get("counts", {})
    base = a.get("baseline", {})
    new = a.get("new", [])
    debt_by_rule = (base.get("debt_by_rule") or {})
    base_debt_by_rule = (
        ((base_a.get("baseline") or {}).get("debt_by_rule") or {})
        if base_a is not None
        else None
    )
    L = ["## Analysis (static invariant checkers)", ""]
    if base_debt_by_rule is None:
        L.append("| rule | findings | pinned debt |")
        L.append("|---|---:|---:|")
    else:
        L.append("| rule | findings | pinned debt | Δ debt vs base |")
        L.append("|---|---:|---:|---:|")
    rules = sorted(set(counts.get("by_rule") or {}) | set(debt_by_rule)
                   | set(base_debt_by_rule or {}))
    for rule in rules:
        n = (counts.get("by_rule") or {}).get(rule, 0)
        d = debt_by_rule.get(rule, 0)
        if base_debt_by_rule is None:
            L.append(f"| {rule} | {n} | {d} |")
        else:
            delta = d - base_debt_by_rule.get(rule, 0)
            L.append(f"| {rule} | {n} | {d} | {delta:+d} |")
    if not rules:
        L.append("| – | 0 | 0 |" if base_debt_by_rule is None else "| – | 0 | 0 | +0 |")
    if a.get("lock_drift"):
        L.append("")
        L.append(
            f"**LOCKFILE DRIFT: {a['lock_drift']} format-drift finding(s)** — "
            "a persisted/wire registry diverged from formats.lock.json "
            "(removal/reorder is never legal; additions need --write-lock "
            "in the same diff)."
        )
    # Blocking-under-lock hotspots: where the wedge-class debt lives,
    # pinned or not — the worklist for shrinking lock scopes (PR 15).
    blocking = [
        f
        for f in (a.get("findings") or [])
        if f.get("rule") == "blocking-under-lock"
    ]
    if blocking:
        per_path: dict[str, int] = {}
        for f in blocking:
            per_path[f.get("path", "?")] = per_path.get(f.get("path", "?"), 0) + 1
        L.append("")
        L.append("**Blocking-under-lock hotspots** (unbounded waits while a lock is held):")
        ranked = sorted(per_path.items(), key=lambda kv: (-kv[1], kv[0]))
        for path, n in ranked[:8]:
            L.append(f"- {path}: {n} site(s)")
        if len(ranked) > 8:
            L.append(f"- … and {len(ranked) - 8} more file(s)")
    sev = counts.get("by_severity") or {}
    L.append("")
    L.append(
        f"Severity: {sev.get('error', 0)} error(s), "
        f"{sev.get('warning', 0)} warning(s).  Baseline debt: "
        f"{base.get('debt', 0)} pinned finding(s)"
        + (f", {base.get('stale', 0)} stale pin(s) to prune" if base.get("stale") else "")
        + (
            f", {base.get('unjustified', 0)} pin(s) MISSING a justification"
            if base.get("unjustified")
            else ""
        )
        + "."
    )
    if new:
        L.append("")
        L.append(f"**{len(new)} NEW finding(s) (not in the baseline):**")
        for f in new[:20]:
            L.append(
                f"- `{f.get('rule')}` {f.get('path')}:{f.get('line')} — "
                f"{f.get('message')}"
            )
        if len(new) > 20:
            L.append(f"- … and {len(new) - 20} more")
    L.append("")
    return "\n".join(L)


def compare_analysis(run_a: dict, base_a: dict) -> list[str]:
    """Strict-gate regressions: baseline-debt growth (total and per
    rule), new findings, and PERSISTED-FORMAT LOCKFILE DRIFT.  (run.py
    --strict already fails on new findings in CI; this gate catches the
    debt creeping up between two otherwise-green runs — i.e. someone
    re-baselining instead of fixing — and drift someone pinned into the
    baseline to sneak past run.py.)"""
    regressions = []
    rd = (run_a.get("baseline") or {}).get("debt", 0) or 0
    bd = (base_a.get("baseline") or {}).get("debt", 0) or 0
    if rd > bd:
        rbr = (run_a.get("baseline") or {}).get("debt_by_rule") or {}
        bbr = (base_a.get("baseline") or {}).get("debt_by_rule") or {}
        grew = [
            f"{r} +{rbr.get(r, 0) - bbr.get(r, 0)}"
            for r in sorted(set(rbr) | set(bbr))
            if rbr.get(r, 0) > bbr.get(r, 0)
        ]
        regressions.append(
            f"analysis baseline debt grew: {bd} -> {rd} pinned finding(s) "
            f"({', '.join(grew) or 'total'}) — fix findings instead of "
            "re-pinning them"
        )
    rn, bn = len(run_a.get("new") or ()), len(base_a.get("new") or ())
    if rn > bn:
        regressions.append(f"new analysis findings: {bn} -> {rn}")
    drift = run_a.get("lock_drift", 0) or 0
    if drift:
        regressions.append(
            f"persisted-format lockfile drift: {drift} format-drift "
            "finding(s) — registries diverged from formats.lock.json "
            "(append-only; removal/reorder is never legal)"
        )
    return regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="report",
        description="Render a fast_tffm_tpu telemetry JSONL run; "
        "--compare gates regressions (exit 1).",
    )
    ap.add_argument(
        "run",
        nargs="+",
        help="telemetry JSONL file(s); pass every per-host file of one "
        "multi-process run (RUN.jsonl RUN.p1.jsonl ...) to merge them "
        "into a single report with per-host columns",
    )
    ap.add_argument(
        "--compare",
        metavar="BASE",
        nargs="+",
        help="baseline telemetry JSONL file(s) to diff against (per-host "
        "files merge like the run's)",
    )
    ap.add_argument(
        "--threshold",
        type=float,
        default=0.15,
        help="max tolerated median-throughput drop vs BASE (fraction, default 0.15)",
    )
    ap.add_argument(
        "--strict",
        action="store_true",
        help="also fail on NEW steady-state compiles / stalls / anomalies / "
        "faults / restarts / rollbacks, and on freshness-p99 or "
        "measured-bytes-per-example regressions past --threshold",
    )
    ap.add_argument("--out", metavar="PATH", help="write the report here instead of stdout")
    ap.add_argument(
        "--analysis",
        metavar="JSON",
        help="static-analysis results (tools/analysis/run.py --json): "
        "render an Analysis section; with --compare --strict, gate on "
        "baseline-debt growth vs --analysis-base",
    )
    ap.add_argument(
        "--analysis-base",
        metavar="JSON",
        help="baseline run's analysis JSON for the debt-growth gate",
    )
    ap.add_argument(
        "--bench-serve",
        metavar="JSON",
        help="serving bench artifact (tools/loadgen.py --out, "
        "BENCH_SERVE_rNN.json): render a Serving bench section; with "
        "--strict and --bench-serve-base, gate on scored-QPS and "
        "per-class client-p99 regressions past --threshold",
    )
    ap.add_argument(
        "--bench-serve-base",
        metavar="JSON",
        help="baseline round's serving bench artifact for the QPS/p99 gate",
    )
    args = ap.parse_args(argv)

    def _load_many(paths):
        records = []
        for p in paths:
            records.extend(load_run(p))
        return records

    try:
        run = summarize(_load_many(args.run))
    except (OSError, ValueError) as e:
        print(f"report: {e}", file=sys.stderr)
        return 2
    title = ", ".join(os.path.basename(p) for p in args.run)
    text = render(run, title=title)
    rc = 0
    if args.analysis_base and not args.analysis:
        # A dropped --analysis must not silently skip the debt gate and
        # exit 0 — half a flag pair is a usage error, not a pass.
        print(
            "report: --analysis-base requires --analysis (the run's own "
            "analysis JSON) — debt gate would be silently skipped",
            file=sys.stderr,
        )
        return 2
    if args.bench_serve_base and not args.bench_serve:
        print(
            "report: --bench-serve-base requires --bench-serve (the run's "
            "own bench artifact) — QPS/p99 gate would be silently skipped",
            file=sys.stderr,
        )
        return 2
    bench_run = bench_base = None
    if args.bench_serve:
        try:
            bench_run = load_bench_serve(args.bench_serve)
            if args.bench_serve_base:
                bench_base = load_bench_serve(args.bench_serve_base)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"report: {e}", file=sys.stderr)
            return 2
        text = text + "\n" + render_bench_serve(bench_run, bench_base)
    run_analysis = base_analysis = None
    if args.analysis:
        try:
            run_analysis = load_analysis(args.analysis)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"report: {e}", file=sys.stderr)
            return 2
        if args.analysis_base:
            try:
                base_analysis = load_analysis(args.analysis_base)
            except (OSError, ValueError, json.JSONDecodeError) as e:
                print(f"report: {e}", file=sys.stderr)
                return 2
        text = text + "\n" + render_analysis(run_analysis, base_analysis)
    if args.compare:
        try:
            base = summarize(_load_many(args.compare))
        except (OSError, ValueError) as e:
            print(f"report: {e}", file=sys.stderr)
            return 2
        cmp_text, regressions = compare(
            run, base, threshold=args.threshold, strict=args.strict
        )
        if args.strict and run_analysis is not None:
            if not args.analysis_base:
                print(
                    "report: note: --analysis given without "
                    "--analysis-base — debt-growth gate skipped",
                    file=sys.stderr,
                )
            else:
                extra = compare_analysis(run_analysis, base_analysis)
                if extra:
                    cmp_text += "**ANALYSIS REGRESSED:**\n" + "\n".join(
                        f"- {r}" for r in extra
                    ) + "\n"
                    regressions.extend(extra)
        text = text + "\n" + cmp_text
        if regressions:
            rc = 1
    # The serving-bench gate rides on --strict alone (no --compare
    # needed): CI keeps only the BENCH_SERVE artifacts between rounds,
    # not the raw telemetry JSONLs.
    if args.strict and bench_run is not None:
        if bench_base is None:
            print(
                "report: note: --bench-serve given without "
                "--bench-serve-base — serving bench gate skipped",
                file=sys.stderr,
            )
        else:
            extra = compare_bench_serve(bench_run, bench_base, args.threshold)
            if extra:
                text += (
                    "\n**SERVING BENCH REGRESSED:**\n"
                    + "\n".join(f"- {r}" for r in extra)
                    + "\n"
                )
                rc = 1
    if args.out:
        # tmp + os.replace, inline (this tool stays stdlib-only): a
        # regenerated report must never be readable half-written.
        tmp = f"{args.out}.{os.getpid():x}.tmp"
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, args.out)
        print(f"report -> {args.out}", file=sys.stderr)
    else:
        print(text)
    return rc


if __name__ == "__main__":
    sys.exit(main())
