#!/usr/bin/env python3
"""Readings on the chip, at the order-3 cell's own size, that ``chip_readings.py``
cannot take because ``train.planted`` does not plant them (the benchmark's own
runs never run this):

    python3 benchmark/tests/chip_order3_readings.py --workload fm3_k30_kdd12.train_fmb_order3 --seeds 1,2

The one reading (``no_a3``): the reference with the third-order term left out (the order-2 score:
the bias term and A^2) put in the program's place, against the sound reference.
"""

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def no_a3(cell, seed):
    """``train.planted``'s recipe with a fault of this cell's own: the same
    three batches followed by the reference at ``order = 2``."""
    import numpy as np

    from harness import gen, train

    ini = cell["ini"]
    batch, nnz = int(ini["Train"]["batch_size"]), int(ini["Train"]["max_nnz"])
    h, model = train._hyper(ini), cell["model"]
    labels, ids, vals = gen.rows_from_seed(seed, train.CHECK_STEPS * batch, nnz, h["vocab"], cell["traffic"].get("zipf_alpha", 2.5))
    shape = (train.CHECK_STEPS, batch, nnz)
    first, vals, labels = ids.reshape(shape), vals.reshape(shape), labels.reshape(train.CHECK_STEPS, batch)
    fields = gen.column_fields(first)
    u, u1 = np.unique(first), np.unique(first[0])
    lacking = copy.copy(model)
    lacking.order = 2
    ref = train.followed(h, model, first, vals, fields, labels, u, u1)
    bad = train.followed(h, lacking, first, vals, fields, labels, u, u1)
    return train.compare(bad, ref, h["lr"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args(argv)
    os.chdir(os.path.dirname(HERE))

    from harness import cells, common

    cell = cells.load_cell(a.workload)
    for seed in [int(s) for s in a.seeds.split(",")]:
        t0 = time.time()
        numbers = no_a3(cell, seed)
        line = {"seed": seed, "what": "no_a3", "compared": numbers, "correct": common.decide(numbers, cell["traffic"]["limits"])[0]}
        line["took_s"] = round(time.time() - t0, 1)
        print("READING " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
