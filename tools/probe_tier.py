#!/usr/bin/env python
"""Beyond-HBM tiered-store probe (ISSUE 12): train a vocab far past the
device wall (default 2^30 rows — 4x the 2^28 single-chip ceiling DESIGN
§8.6 measured) on ONE chip through the [ParamStore] tiered path, and pin
the residency economics against the PR-9 evidence:

  * **hit rate vs coverage curve** — the measured hot-tier hit rate
    (kind=tiering telemetry) next to the EXACT coverage a top-K cache
    should absorb on this workload (host bincount over every gather
    slot — the same curve PROBE_IDSTATS_r09 committed at the 2^22 scale
    shape, where top-4096 absorbed 59%).  The acceptance bar: measured
    within a few points of predicted (the sample-policy hot set is drawn
    from a prefix, the curve from the whole stream).
  * **gather savings** — the CostLedger's measured bytes/example for the
    compiled tiered step next to the resident path's modeled floor, plus
    the wire/staging bytes the dedup + hit path actually shipped.

The workload is bench.py's Zipf(1.1) scale shape (NNZ=39, synthesized
FMB via ensure_scale_fmb).  Also reachable as `python bench.py --tier`.

Usage:
  python tools/probe_tier.py [--vocab 1073741824] [--batch 4096]
      [--steps 12] [--hot 4096] [--out PROBE_TIER_r12.json]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from fast_tffm_tpu.telemetry import (
    arm_hang_exit,
    artifact_stamp,
    new_run_id,
    write_json_artifact,
)

_watchdog = arm_hang_exit(seconds=3000, what="probe_tier.py")

import numpy as np  # noqa: E402


def _read_jsonl(path: str) -> list[dict]:
    if not os.path.exists(path):
        return []
    return [json.loads(l) for l in open(path) if l.strip()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--vocab", type=int, default=1 << 30)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--hot", type=int, default=4096)
    ap.add_argument("--factor-num", type=int, default=8)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--delta-every", type=int, default=4)
    ap.add_argument("--out", default=os.path.join(REPO, "PROBE_TIER_r12.json"))
    args = ap.parse_args(argv)

    import bench  # repo-root module: the scale workload's one source of truth
    from fast_tffm_tpu.config import Config
    from fast_tffm_tpu.data.binary import open_fmb
    from fast_tffm_tpu.training import train

    rows = args.batch * args.steps
    t0 = time.time()
    fmb = bench.ensure_scale_fmb(args.vocab, rows=rows, seed=args.seed)

    # Exact coverage curve over EVERY gather slot of the workload (the
    # prediction the measured hit rate is pinned against).
    ids = np.asarray(open_fmb(fmb).ids[:rows]).reshape(-1)
    uniq, cnt = np.unique(ids, return_counts=True)
    order = np.argsort(-cnt, kind="stable")
    csum = np.cumsum(cnt[order])
    total_slots = ids.size

    def coverage(k: int) -> float:
        k = min(k, csum.size)
        return float(csum[k - 1] / total_slots) if k else 0.0

    curve = {str(k): round(coverage(k), 4) for k in (256, 4096, 65536)}
    predicted = coverage(args.hot)

    work = tempfile.mkdtemp(prefix="probe_tier_")
    run_id = new_run_id()
    metrics = os.path.join(work, "metrics.jsonl")
    cfg = Config()
    cfg.model = "fm"
    cfg.factor_num = args.factor_num
    cfg.vocabulary_size = args.vocab
    cfg.hash_feature_id = True  # ensure_scale_fmb writes pre-hashed ids
    cfg.train_files = (fmb,)
    cfg.max_nnz = bench.NNZ
    cfg.epoch_num = 1
    cfg.batch_size = args.batch
    cfg.learning_rate = 0.05
    cfg.log_every = max(1, args.steps // 4)
    cfg.model_file = os.path.join(work, "model.ckpt")
    cfg.metrics_path = metrics
    cfg.telemetry_run_id = run_id
    cfg.save_every_epochs = 1
    cfg.delta_every_steps = args.delta_every
    # Row-granular accumulator: the cold store's accumulator file packs
    # 1024 rows per page instead of one row per ~9-lane stripe — at
    # 2^30 sparse-file scale that halves the probe's dirty-page bill.
    cfg.adagrad_accumulator = "row"
    cfg.paramstore = True
    cfg.paramstore_hot_rows = args.hot
    cfg.paramstore_dir = os.path.join(work, "store")
    cfg.paramstore_residency = "sample"
    cfg.paramstore_sample_batches = min(8, args.steps)
    cfg.validate()

    logs: list[str] = []
    train(cfg, log=lambda *a: logs.append(" ".join(map(str, a))))
    wall = time.time() - t0

    recs = _read_jsonl(metrics)
    tier = [r for r in recs if r.get("kind") == "tiering"]
    if not tier:
        print("no kind=tiering records emitted — probe failed", file=sys.stderr)
        return 1
    hits = [r["hit_rate"] for r in tier]
    # Weighted by miss exposure windows — simple mean is fine at this
    # probe's uniform window sizes.
    hit_rate = round(sum(hits) / len(hits), 4)
    dedups = [r["dedup_ratio"] for r in tier if r.get("dedup_ratio") is not None]
    miss_bytes = int(np.median([r["miss_bytes_per_step"] for r in tier]))
    wire_bytes = int(np.median([r["wire_bytes_per_step"] for r in tier]))
    steady = sum(
        r.get("compiles", 0)
        for r in recs
        if r.get("kind") == "compile" and not r.get("warmup")
    )
    prof = [
        r
        for r in recs
        if r.get("kind") == "profile" and r.get("program") == "train_step"
    ]
    measured = (
        {
            k: prof[-1].get(k)
            for k in (
                "bytes_accessed", "flops", "examples", "bytes_per_example",
                "modeled_hbm_bytes",
            )
        }
        if prof
        else None
    )

    # The PR-9 committed curve (2^22 scale shape) as the cross-scale
    # reference the ISSUE names.
    pr9 = None
    pr9_path = os.path.join(REPO, "PROBE_IDSTATS_r09.json")
    if os.path.exists(pr9_path):
        with open(pr9_path) as f:
            pr9 = json.load(f).get("hot_id_cache_coverage_exact")

    # The resident path at this vocab would need ~vocab*(D+A)*4 bytes of
    # device memory — report the wall it walked past.
    d = args.factor_num + 1
    resident_bytes = args.vocab * (d + 1) * 4

    out = {
        "probe": "PROBE_TIER",
        **artifact_stamp(run_id),
        "workload": {
            "vocab": args.vocab,
            "batch": args.batch,
            "steps": args.steps,
            "nnz": bench.NNZ,
            "row_dim": d,
            "rows": rows,
            "distribution": "zipf_1.1",
            "wall_s": round(wall, 1),
        },
        "hot_rows": args.hot,
        "hit_rate_measured": hit_rate,
        "hit_rate_predicted_exact": round(predicted, 4),
        "hit_rate_gap": round(abs(hit_rate - predicted), 4),
        "coverage_curve_exact": curve,
        "pr9_coverage_curve_2e22": pr9,
        "dedup_ratio_mean": round(sum(dedups) / len(dedups), 4) if dedups else None,
        "miss_bytes_per_step": miss_bytes,
        "wire_bytes_per_step": wire_bytes,
        "resident_state_bytes_this_vocab": resident_bytes,
        "device_tier_rows": args.hot,
        "measured_train_step": measured,
        "steady_state_recompiles": steady,
        "note": (
            "hit_rate_measured = hot-tier share of gather slots over the "
            "run (kind=tiering); hit_rate_predicted_exact = exact top-"
            f"{args.hot} coverage of this workload's slot distribution "
            "(the PR-9 curve recomputed at this scale) — the sample-"
            "policy hot set is drawn from a stream prefix, so a few "
            "points of gap is the expected sampling error.  "
            "resident_state_bytes_this_vocab is what a non-tiered run "
            "would need on device (vs the ~11.5 GB single-chip wall)."
        ),
    }
    write_json_artifact(args.out, out)
    shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(out, indent=1, sort_keys=True))
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    rc = main()
    _watchdog.cancel()
    sys.exit(rc)
