#!/usr/bin/env python3
"""Rehearsal without the chip: compile a cell's step at its real shapes for a
described ``v5e:2x2`` and print ``memory_analysis()`` per device.  Nothing runs
and no time is measured.  Run it before a cell's first call to the chip:

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <config>.<mix>

For a ``dist_train`` mix it also compiles the initial state as the program
builds it, on ONE device (``init_sharded_state`` draws the whole table there
before it shards it), which is what decides how many rows a four-chip cell can
hold.
"""

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def _report(name, compiled):
    m = compiled.memory_analysis()
    gib = lambda b: f"{b / 2**30:.3f} GiB"
    print(
        f"{name}: arguments {gib(m.argument_size_in_bytes)} outputs {gib(m.output_size_in_bytes)} "
        f"aliased {gib(m.alias_size_in_bytes)} temporaries {gib(m.temp_size_in_bytes)} "
        f"-> live at peak about {gib(m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes)} per device",
        flush=True,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

    from fast_tffm_tpu.config import build_model, load_config
    from fast_tffm_tpu.models.base import Batch
    from fast_tffm_tpu.trainer import init_state, make_train_step
    from harness import cells

    jax.config.update("jax_enable_compilation_cache", False)
    cell = cells.load_cell(a.workload)
    work = cells.fresh_workdir(cell["name"] + ".rehearse")
    cfg = load_config(cells.write_ini(os.path.join(work, "cell.cfg"), cell["ini"]))
    model = build_model(cfg)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    b, n = cfg.batch_size, cfg.max_nnz
    init = lambda: init_state(model, jax.random.key(0), cfg.init_accumulator_value, cfg.adagrad_accumulator)
    state = jax.eval_shape(init)
    batch = Batch(
        labels=jax.ShapeDtypeStruct((b,), jnp.float32), ids=jax.ShapeDtypeStruct((b, n), jnp.int32),
        vals=jax.ShapeDtypeStruct((b, n), jnp.float32),
        fields=jax.ShapeDtypeStruct((b, n if cell["model"].reads_fields else 0), jnp.int32),
        weights=jax.ShapeDtypeStruct((b,), jnp.float32),
    )
    place = lambda tree, sh: jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, sh
    )
    one = SingleDeviceSharding(topo.devices[0])
    if cell["kind"] == "train":
        step = make_train_step(model, cfg.learning_rate)
        args = place((state, batch), jax.tree.map(lambda _: one, (state, batch)))
        _report(f"{a.workload} train step, table {state.table.shape}", step.lower(*args).compile())
    elif cell["kind"] == "dist_train":
        from fast_tffm_tpu.parallel import make_sharded_train_step
        from fast_tffm_tpu.parallel.train_step import _batch_specs, _pad_model_vocab, _state_specs
        import numpy as np

        mesh = Mesh(np.array(topo.devices).reshape(cfg.data_parallel or 1, cfg.row_parallel), ("data", "row"))
        _report(
            f"{a.workload} initial state drawn on one device, table {state.table.shape}",
            jax.jit(init, out_shardings=jax.tree.map(lambda _: one, state)).lower().compile(),
        )
        padded = _pad_model_vocab(model, mesh)
        state = jax.eval_shape(lambda: init_state(padded, jax.random.key(0), cfg.init_accumulator_value, cfg.adagrad_accumulator))
        from jax.sharding import PartitionSpec as P

        ns = lambda p: NamedSharding(mesh, p)
        specs = _state_specs()
        st_sh = state._replace(
            table=ns(specs.table), table_opt=type(state.table_opt)(ns(specs.table_opt.accum)),
            dense={}, dense_opt=type(state.dense_opt)({}), step=ns(P()),
        )
        step = make_sharded_train_step(
            model, cfg.learning_rate, mesh, lookup=cfg.lookup, capacity_factor=cfg.lookup_capacity_factor,
            overflow_mode=cfg.lookup_overflow, table_layout=cfg.table_layout, accumulator=cfg.adagrad_accumulator,
        )
        bspec = _batch_specs()
        b_sh = Batch(**{f: ns(getattr(bspec, f)) for f in ("labels", "ids", "vals", "fields", "weights")})
        args = (place(state, st_sh), place(batch, b_sh))
        _report(f"{a.workload} sharded step on {dict(mesh.shape)}, table {state.table.shape}", step.lower(*args).compile())
    else:
        print(f"{a.workload}: a {cell['kind']} mix compiles no training step; see the serving buckets' compile in PERF.md")
    from harness import common

    common.remove_tree(work)
    return 0


if __name__ == "__main__":
    sys.exit(main())
