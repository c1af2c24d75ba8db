#!/usr/bin/env python3
"""Readings on the chip, at a cell's own size, that ``chip_readings.py`` cannot
take because they plant something in the harness itself (the benchmark's own
runs never run this):

    python3 benchmark/tests/chip_field_readings.py --workload W --what zero_fields --seeds 1,2
    ... --what zero_fields   the FMB file written with every field id zero
    ... --what order2        the order-2 score in the field-aware reference's place
    ... --what unprobed      the program as it is, the configuration's float32 probe switched off
                             (what a parent commit that contracts in one bfloat16 pass reads)
    ... --what trace --out chiprun_out/dir   one traced run: the .xplane.pb kept under it, the head of
                             its scoped ops (tests/recorded_scopes.json) and the table by scope

Each drives the whole harness (``train.run``) with a window of ``--seconds``.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", choices=("zero_fields", "order2", "unprobed", "trace"), required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default="chiprun_out/field_readings")
    a = ap.parse_args(argv)
    os.chdir(os.path.dirname(HERE))

    from harness import cells, common, gen, scopes, train
    from harness.models import ffm, ffm_f32, fm2

    if a.what == "unprobed":
        ffm_f32._PROBE_LIMIT = float("inf")
    cell = cells.load_cell(a.workload)
    if a.what == "zero_fields":
        real = gen.write_fmb
        gen.write_fmb = lambda *args: real(*args[:5])
    elif a.what == "order2":
        ffm.Model.score = fm2.Model.score
    elif a.what == "trace":
        remove = common.remove_tree
        kept = os.path.join(a.out, "plugins", "profile", "kept")  # where ``scopes.read_ops`` looks

        def keep_then_remove(work):
            for path in glob.glob(os.path.join(work, "trace", "plugins", "profile", "*", "*.xplane.pb")):
                os.makedirs(kept, exist_ok=True)
                shutil.copy(path, os.path.join(kept, "trace.xplane.pb"))
            remove(work)

        common.remove_tree = keep_then_remove
    for seed in [int(s) for s in a.seeds.split(",")]:
        t0 = time.time()
        r = train.run(cell, seed, a.seconds, a.what == "trace", t0)
        line = {"seed": seed, "what": a.what, "correct": r["correct"], "attempted": r["attempted"],
                "compared": {k: v["value"] for k, v in r["compared"].items()},
                "metrics": {k: v["value"] for k, v in r["metrics"].items()}, "device": r["device"],
                "took_s": round(time.time() - t0, 1)}
        print("READING " + json.dumps(line), flush=True)
        if a.what == "trace":
            ops = scopes.read_ops(a.out)
            scopes.dump_ops(ops, os.path.join(a.out, "recorded_scopes.json"))
            steps = max(1, r["attempted"])
            table = [[sc, 1e3 * s / steps, sorted(((o, 1e3 * t / steps) for o, t in by.items()), key=lambda kv: -kv[1])[:6]]
                     for sc, s, by in scopes.by_scope(ops)]
            with open(os.path.join(a.out, "by_scope.json"), "w") as f:
                json.dump(table, f, indent=1)
            for sc, ms, by in table[:16]:
                print(f"SCOPE {ms:9.3f} ms  {sc}  {by[:3]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
