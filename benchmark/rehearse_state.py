#!/usr/bin/env python3
"""Rehearsal of a ``dist_train`` cell's INITIAL STATE without the chip: compile
the construction the program itself uses (``parallel/train_step``'s jitted
table-and-accumulator draw behind ``init_sharded_state``, each shard born on
its own device since PR 35) at the cell's real shapes for a described
``v5e:2x2``, and print ``memory_analysis()`` per device.  Nothing runs.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_state.py --workload <config>.<mix>

``rehearse.py`` (older, left as it is) compiles a one-device draw of its own
beside the sharded step: 24 + 1 GiB at ``fm16_criteo_row4``, what the program
asked of ONE chip before PR 35.  A program that lacks the construction (a
parent of PR 35) exits 1 with a sentence.
"""

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    a = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from fast_tffm_tpu.config import build_model, load_config
    from fast_tffm_tpu.parallel import train_step
    from harness import cells, common
    from rehearse import _report

    build = getattr(train_step, "_sharded_table_init", None)
    if build is None:
        raise SystemExit("this program draws its initial state on one device (no parallel/train_step._sharded_table_init): see rehearse.py")
    jax.config.update("jax_enable_compilation_cache", False)
    cell = cells.load_cell(a.workload)
    if cell["kind"] != "dist_train":
        raise SystemExit(f"{a.workload}: a {cell['kind']} mix has no sharded state")
    work = cells.fresh_workdir(cell["name"] + ".rehearse_state")
    cfg = load_config(cells.write_ini(os.path.join(work, "cell.cfg"), cell["ini"]))
    common.remove_tree(work)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices).reshape(cfg.data_parallel or 1, cfg.row_parallel), ("data", "row"))
    padded = train_step._pad_model_vocab(build_model(cfg), mesh)
    key = jax.eval_shape(lambda: jax.random.split(jax.random.key(0))[0])
    compiled = build(padded, mesh, cfg.init_accumulator_value, cfg.adagrad_accumulator).lower(key).compile()
    _report(f"{a.workload} initial table and accumulator drawn shard by shard on {dict(mesh.shape)}, table ({padded.vocabulary_size}, {padded.row_dim})", compiled)
    return 0


if __name__ == "__main__":
    sys.exit(main())
