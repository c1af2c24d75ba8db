#!/usr/bin/env python3
"""Readings a limit is set from, taken on the chip at a cell's own size, many
seeds in one process (the benchmark's own runs never run this):

    python3 benchmark/tests/chip_readings.py --workload W --what program --seeds 1,2,3
    ... --what control     the reference in bfloat16 put in the program's place
    ... --what half_batch  the reference with half of each batch left out, the mean over the rest
    ... --what no_exchange the reference of a row-sharded cell with the gradients' exchange between shards left out
    ... --what dense_frozen the reference of a model with dense leaves, those leaves never updated
    ... --what trace --out chiprun_out/recorded_trace.json   one short traced run, its events kept
    ... --what sweep --rates 50000,100000 --seconds 5        serve mixes: one server, a window a rate

``program`` drives the whole harness with a one-second window.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", choices=("program", "control", "half_batch", "no_exchange", "dense_frozen", "trace", "sweep"), required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--set", action="append", default=[], metavar="Section.key=value | traffic.key=value",
                    help="a one-off change to the cell as loaded, for a reading such as the rate at another log_every")
    ap.add_argument("--rates", default="", help="sweep: rows a second, comma-separated")
    a = ap.parse_args(argv)
    os.chdir(os.path.dirname(HERE))

    from harness import cells

    cell = cells.load_cell(a.workload)
    for item in a.set:
        where, value = item.split("=", 1)
        section, key = where.split(".", 1)
        if section == "traffic":
            cell["traffic"][key] = json.loads(value)
        else:
            cell["ini"].setdefault(section, {})[key] = value
    mod = cells.window_module(cell["kind"])
    for seed in [int(s) for s in a.seeds.split(",")]:
        t0 = time.time()
        if a.what == "sweep":
            mod.sweep(cell, seed, [int(r) for r in a.rates.split(",")], a.seconds, t0)
            continue
        if a.what in ("program", "trace"):
            kw = {"keep_events": a.out} if a.what == "trace" else {}
            r = mod.run(cell, seed, a.seconds, a.what == "trace", t0, **kw)
            line = {"seed": seed, "correct": r["correct"], "compared": {k: v["value"] for k, v in r["compared"].items()},
                    "metrics": {k: v["value"] for k, v in r["metrics"].items()}, "peak": r["device"]["memory_peak_bytes"]}
        else:
            line = {"seed": seed, "what": a.what, "compared": mod.planted(cell, seed, a.what)}
        line["took_s"] = round(time.time() - t0, 1)
        print("READING " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
