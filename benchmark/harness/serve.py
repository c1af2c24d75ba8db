"""The serve window: the program's socket tier (front end -> router -> one
replica -> engine -> bucket ladder -> score -> reply, over the FMD1 wire)
under an open loop from ``loadgen.py``, a process of its own.

``Frontend`` and ``Router`` run here as ``fast_tffm.py serve --port 0`` builds
them, with a ``launcher`` that runs ``serving.replica.run_replica`` on a thread
(the duck type ``ReplicaProcess`` describes): only the process that holds the
chip can trace it and read its memory, so both ``--trace`` modes use this one
arrangement.  The model file is a checkpoint of a state made on the device
from the seed, written by the program's own writer; a model's dense leaves
(``models/__init__.py``) are saved in it as the model draws them, and the pool
is scored under the same leaves.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from . import cells, common, gen, loadgen, peaks, readers, reference
from .models import dense_leaves

_POOL_BLOCK = 1 << 16


class _ThreadReplica:
    """One replica on a thread of this process; the handle the router asks
    for (port, pid, platform, chip, alive, returncode, kill, wait)."""

    def __init__(self, cfg, index: int, log):
        self.port = self.platform = self.chip = self.returncode = self.error = None
        self.pid = os.getpid()
        self._ready = threading.Event()
        self._thread = threading.Thread(target=self._main, args=(cfg, index, log), name=f"replica-{index}", daemon=True)
        self._thread.start()
        if not self._ready.wait(600) or self.port is None:
            raise RuntimeError(f"replica {index} did not come up: {self.error!r}")

    def _main(self, cfg, index, log):
        from fast_tffm_tpu.serving.replica import run_replica

        try:
            self.returncode = run_replica(cfg, replica=index, port=0, log=log, ready_out=self)
        except BaseException as e:  # noqa: BLE001 - reported through the handle
            self.error, self.returncode = e, 1
        finally:
            self._ready.set()

    def write(self, text: str) -> None:  # the READY line lands here
        fields = dict(kv.split("=", 1) for kv in text.split() if "=" in kv)
        if "port" in fields:
            self.port, self.platform, self.chip = int(fields["port"]), fields.get("platform"), fields.get("chip")
            self._ready.set()

    def flush(self) -> None:
        pass

    def alive(self) -> bool:
        return self._thread.is_alive()

    def kill(self) -> None:
        pass

    def wait(self, timeout=None) -> None:
        self._thread.join(timeout)


def seed_table(seed: int, vocab: int, row_dim: int):
    """The served weights, on the device in one jitted call from the seed."""
    import jax

    key = jax.random.fold_in(jax.random.key(int(seed) >> 31), int(seed) & 0x7FFFFFFF)
    # The key is an argument, not a constant of the program: one program, cached once, for every seed.
    return jax.jit(lambda k: jax.random.uniform(k, (vocab, row_dim), minval=-0.08, maxval=0.08))(key)


def served_model(cell):
    """The cell's model, where this window can serve it: the load generator's
    FMD1 frames carry no field ids."""
    model = cell["model"]
    if model.reads_fields:
        raise SystemExit(
            f"{cell['name']}: the configuration's model reads field ids and the serve window's FMD1 frames "
            "(harness/loadgen.py) carry none; it would be scored with every feature in field 0"
        )
    return model


def write_model_file(cfg, seed: int, row_dim: int, dense=None) -> None:
    """The served state: the table from the seed, the dense leaves ``dense``
    (none: ``{}``) as the model draws them, every accumulator at its start."""
    import jax.numpy as jnp

    from fast_tffm_tpu.checkpoint import save_checkpoint
    from fast_tffm_tpu.optim import AdagradState
    from fast_tffm_tpu.trainer import TrainState

    table = seed_table(seed, cfg.vocabulary_size, row_dim)
    cols = table.shape[1] if cfg.adagrad_accumulator == "element" else 1
    accum = np.broadcast_to(np.float32(cfg.init_accumulator_value), (table.shape[0], cols))
    dense = dict(dense or {})
    dense_accum = {k: jnp.full_like(v, cfg.init_accumulator_value) for k, v in dense.items()}
    state = TrainState(table, AdagradState(accum), dense, AdagradState(dense_accum), jnp.zeros((), jnp.int32))
    save_checkpoint(cfg.model_file, state, "npz")


def pool_reference(seed, spec, model, dtype=None):
    """The reference's score of every row of the frame pool, [pool, rows]."""
    import jax.numpy as jnp

    _, ids, vals = loadgen.pool_rows(seed, spec)
    fields = gen.column_fields(ids)
    table = seed_table(seed, spec["vocab"], model.row_dim)
    dense = dense_leaves(model)
    out = [
        np.asarray(reference.score_rows(model.score, table, *(a[i : i + _POOL_BLOCK] for a in (ids, vals, fields)), dtype or jnp.float32, dense))
        for i in range(0, ids.shape[0], _POOL_BLOCK)
    ]
    return np.concatenate(out).reshape(spec["pool_frames"], spec["frame_rows"])


def _spec(cell, seed, seconds, port, out):
    tr, ini = cell["traffic"], cell["ini"]
    return {
        "port": port, "seed": int(seed), "seconds": float(seconds), "out": out,
        "vocab": int(ini["General"]["vocabulary_size"]), "nnz": int(ini["Train"]["max_nnz"]),
        **{k: tr[k] for k in ("rate_rows_per_s", "frame_rows", "connections", "processes", "pool_frames", "warm_seconds", "drain_seconds")},
        "zipf_alpha": tr.get("zipf_alpha", 2.5),
    }


def planted(cell, seed, what):
    """The control at the cell's own size: the reference in bfloat16 put in
    the program's place, against the float32 reference, over the whole pool."""
    import jax.numpy as jnp

    if what != "control":
        raise ValueError(what)
    spec, model = _spec(cell, seed, 1.0, 0, ""), served_model(cell)
    ref = pool_reference(seed, spec, model)
    low = pool_reference(seed, spec, model, jnp.bfloat16)
    return {"score_gap": float(np.max(np.abs(low - ref))), "unanswered_rows": 0.0}


@contextlib.contextmanager
def serving(cfg):
    """Router and front end as ``run_frontend`` builds them, one thread
    replica; closed (and the replica's state dropped) on exit."""
    from fast_tffm_tpu.serving.frontend import Frontend
    from fast_tffm_tpu.serving.router import Router

    log = lambda *a: print(*a, file=sys.stderr)
    router = Router(cfg, launcher=lambda i: _ThreadReplica(cfg, i, log), log=log)
    fe = None
    try:
        fe = Frontend(router, port=0, default_deadline_ms=cfg.serve_deadline_ms, wire=cfg.serve_wire, affinity=cfg.serve_affinity)
        yield router, fe
    finally:
        if fe is not None:
            fe.close()
        router.close()


def probe(router) -> None:
    """One libsvm line scored through the router's own text path: the tier
    answers before load is offered.  It is also what keeps the replica up:
    the router's data socket to a replica keeps ``create_connection``'s 30 s
    timeout, the FMD1 frames bypass that socket, and after 30 s without a
    routed answer the router declares the replica lost and restarts it
    (PERF.md section 7, first fault).  A probe before GO and one at the close
    leave the window and the drain 30 s each."""
    router.submit("0 1:1").result(timeout=30)


def drive(router, spec, trace_dir, phase):
    """One warm-up and window from the load generator's processes.  Returns
    the engine's counters at GO, OPEN and CLOSE with the wall time of OPEN,
    and the generator's results merged over its shards."""
    import jax

    spec_path = spec["out"] + ".json"
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)), "loadgen.py")
    children = [
        subprocess.Popen([sys.executable, script, spec_path, str(p)], stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        for p in range(spec["processes"])
    ]
    engine = lambda: router.stats()["engines"]["0"]["engine"]
    marks = {}

    def sleep_until(t):
        time.sleep(max(0.0, t - time.time()))

    try:
        for c in children:
            if c.stdout.readline().strip() != "READY":
                raise SystemExit("a load generator process did not come up")
        phase("load generator ready")
        if trace_dir:
            common.start_trace(trace_dir)
        probe(router)
        marks["go"] = engine()
        t0 = time.time() + 0.3
        for c in children:
            c.stdin.write(f"GO {t0!r}\n")
            c.stdin.flush()
        marks["wall_open"] = t0 + spec["warm_seconds"]
        sleep_until(marks["wall_open"])
        marks["open"] = engine()
        sleep_until(marks["wall_open"] + spec["seconds"])
        marks["close"] = engine()
        probe(router)
        if trace_dir:
            jax.profiler.stop_trace()
        for c in children:
            if c.stdout.readline().strip() != "DONE" or c.wait(timeout=30) != 0:
                raise SystemExit(f"a load generator process failed (exit {c.poll()})")
    finally:
        for c in children:
            if c.poll() is None:
                c.kill()
                c.wait()
    phase("window drained")
    due, which, n_warm = loadgen.schedule(spec["seed"], spec["rate_rows_per_s"] / spec["frame_rows"], spec["warm_seconds"], spec["seconds"], spec["pool_frames"])
    res = {"due": t0 + due, "which": which, "n_warm": n_warm, "t_open": marks["wall_open"], "t_close": marks["wall_open"] + spec["seconds"],
           "sent": np.full(due.size, np.nan), "done": np.full(due.size, np.nan), "t_drained": 0.0,
           "status": np.zeros((due.size, spec["frame_rows"]), np.uint8), "score": np.zeros((due.size, spec["frame_rows"]), np.float32)}
    for p in range(spec["processes"]):
        part = np.load(f"{spec['out']}.{p}.npz")
        for k in ("sent", "done", "status", "score"):
            res[k][part["frame"]] = part[k]
        res["t_drained"] = max(res["t_drained"], float(part["t_drained"]))
    return marks, res


def population(res, seconds):
    """End-to-end numbers over ALL frames due in the window, each timed from
    when it was due to its last reply row; an unanswered frame is timed to the
    end of the drain.  A late answer is answered; refused, errored and
    unanswered frames are failed."""
    w = slice(int(res["n_warm"]), None)
    due, done, sent, status = res["due"][w], res["done"][w], res["sent"][w], res["status"][w]
    lat = np.where(np.isnan(done), float(res["t_drained"]) - due, done - due) * 1e3
    ok_rows = (status == 1).sum(axis=1)
    in_window = ~np.isnan(done) & (done <= float(res["t_close"]))
    sec = np.minimum((due - float(res["t_open"])).astype(int), int(np.ceil(seconds)) - 1)
    by_1s = [np.percentile(lat[sec == s], 99) for s in range(int(np.ceil(seconds))) if (sec == s).sum() >= 20]
    return {
        "attempted": int(due.size),
        "failed": int((ok_rows < status.shape[1]).sum()),
        "unanswered_rows": float((status == 0).sum()),
        "serve_p50_ms": float(np.percentile(lat, 50)),
        "latency_ms_p95": float(np.percentile(lat, 95)),
        "serve_rows_per_s": float(ok_rows[in_window].sum() / seconds),
        "latency_ms_p99": float(np.percentile(lat, 99)),
        "latency_ms_p99_by_1s": float(np.median(by_1s)) if by_1s else None,
        "late_ms_p99": float(np.percentile((sent - due) * 1e3, 99)),
        "first_quarter_p50_ms": float(np.percentile(lat[: lat.size // 4], 50)),
        "last_quarter_p50_ms": float(np.percentile(lat[-(lat.size // 4) :], 50)),
    }


def _model_and_config(cell, model, seed, name, workroot, phase):
    """An emptied work directory with the cell's INI file and the model file
    made from the seed; (directory, loaded Config)."""
    work, cfg = common.configured(cell, name, workroot)
    write_model_file(cfg, seed, model.row_dim, dense_leaves(model))
    phase("model file written")
    # The model file's dirty pages go to disk now, not under the window, and
    # what this process has built so far is kept out of later collections:
    # the replica runs on a thread here, and a pause of ours would be its stall.
    os.sync()
    phase("model file on disk")
    gc.collect()
    gc.freeze()
    return work, cfg


def sweep(cell, seed, rates, seconds, t_start):
    """The knee, once: one server, one short window at each rate.  Prints a
    line a rate; run by ``tests/chip_readings.py --what sweep``."""
    model = served_model(cell)
    common.device_info(cell["chips"])
    phase = common.phases(t_start)
    work, cfg = _model_and_config(cell, model, seed, cell["name"] + ".sweep", cells.CHECKOUT, phase)
    with serving(cfg) as (router, fe):
        for rate in rates:
            spec = _spec(cell, seed, seconds, fe.port, os.path.join(work, f"loadgen_{rate}"))
            spec["rate_rows_per_s"] = rate
            try:
                marks, res = drive(router, spec, None, phase)
            except (SystemExit, Exception) as e:  # noqa: BLE001 - past its knee the tier refuses the probe or loses the replica
                print("SWEEP " + json.dumps({"rate_rows_per_s": rate, "broke": repr(e)}), flush=True)
                break
            pop = population(res, seconds)
            rows, padded = (marks["close"][k] - marks["open"][k] for k in ("rows", "padded_rows"))
            pop.update(rate_rows_per_s=rate, occupancy=rows / max(1, rows + padded),
                       queue_ms_p50=marks["close"]["queue_ms"].get("p50"), compute_ms_p50=marks["close"]["compute_ms"].get("p50"),
                       rejected=marks["close"]["rejected"] - marks["open"]["rejected"])
            print("SWEEP " + json.dumps(pop), flush=True)
            if pop["failed"]:
                break  # past the knee the router restarts the replica: nothing further can be read
    common.remove_tree(work)


def run(cell, seed, seconds, do_trace, t_start, require_chip=True, workroot=cells.CHECKOUT, keep_events=None):
    model = served_model(cell)
    device = common.device_info(cell["chips"], require_chip)
    phase = common.phases(t_start)
    tr = cell["traffic"]
    runtime_start_s = phase("device found")
    work, cfg = _model_and_config(cell, model, seed, cell["name"], workroot, phase)

    trace_dir = os.path.join(work, "trace")
    with serving(cfg) as (router, fe):
        phase("server up")
        spec = _spec(cell, seed, seconds, fe.port, os.path.join(work, "loadgen"))
        marks, res = drive(router, spec, trace_dir if do_trace else None, phase)
        peak = common.memory_peak_bytes()
    gc.collect()

    pop = population(res, seconds)
    # Every answer due in the window against the reference's score of its row.
    ref = pool_reference(seed, spec, model)
    w = slice(int(res["n_warm"]), None)
    answered = res["status"][w] == 1
    gaps = np.abs(res["score"][w] - ref[res["which"][w]])[answered]
    numbers = {
        "score_gap": float(gaps.max()) if gaps.size else float("inf"),
        "unanswered_rows": pop["unanswered_rows"],
    }
    correct, compared = common.decide(numbers, tr["limits"])
    phase("reference scored and compared")

    result = {
        "correct": correct, "attempted": pop["attempted"], "failed": pop["failed"], "metrics": {},
        "device": dict(device, memory_peak_bytes=peak),
    }
    if do_trace:
        red = common.reduce_trace(result, trace_dir, keep_events)
        d = lambda a, b, k: marks[b][k] - marks[a][k]
        rows, padded = d("open", "close", "rows"), d("open", "close", "padded_rows")
        values = {
            "runtime_start_s": runtime_start_s,
            "queue_ms_p50": marks["close"]["queue_ms"].get("p50"),
            "compute_ms_p50": marks["close"]["compute_ms"].get("p50"),
            "batch_occupancy": rows / (rows + padded) if rows + padded else None,
            **{k: pop[k] for k in ("latency_ms_p95", "latency_ms_p99", "latency_ms_p99_by_1s", "late_ms_p99")},
        }
        if red and red["busy_s"]:
            least, _ = peaks.least_seconds(0.0, model.score_bytes(d("go", "close", "rows"), cfg.max_nnz), device["kind"])
            values["score_mfu"] = 100.0 * least / red["busy_s"]
        ctx = {"records": readers.read_jsonl(cfg.metrics_path + ".r0"), "steps": "warmup_flag", "values": values, "trace": red, "trace_dir": trace_dir, "model": model}
        result["metrics"] = readers.read_all(cells.load_metrics(cell["kind"], cell["bench_dir"], cell["name"]), ctx)
    else:
        result["metrics"] = {
            "serve_p50_ms": common.metric(pop["serve_p50_ms"], "ms"),
            "serve_rows_per_s": common.metric(pop["serve_rows_per_s"], "rows/s"),
            "setup_s": common.metric(marks["wall_open"] - t_start, "s"),
        }
    result["compared"] = compared
    common.remove_tree(work)
    return result
