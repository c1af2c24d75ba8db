"""Discovery: a cell, its configuration, its traffic and its per-layer
metrics are found by name from data files; adding one edits no file here."""

from __future__ import annotations

import configparser
import importlib
import json
import os
import shutil

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


# Which window (a module of this package) drives a traffic mix's ``kind``.
WINDOW_OF_KIND = {"train": "train", "dist_train": "train", "serve": "serve"}


def window_module(kind: str):
    return importlib.import_module(f"harness.{WINDOW_OF_KIND[kind]}")


def harness_model(config: dict, ini: dict):
    """The ``Model`` of the module ``models/<name>.py`` that the configuration's
    file names under ``harness_model``, built from the cell's INI.  A file
    that names none, or one that is not there, is an error, never a default."""
    name = config.get("harness_model")
    if not name:
        raise SystemExit(f"configuration {config.get('name')!r} names no harness_model (a module of harness/models/)")
    try:
        module = importlib.import_module(f"harness.models.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"harness.models.{name}":
            raise
        raise SystemExit(f"configuration {config.get('name')!r} names the harness_model {name!r}: there is no harness/models/{name}.py")
    return module.Model(ini)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench_dir: str = BENCH_DIR) -> dict:
    """``<config>.<mix>`` -> {name, config, traffic, kind, chips, ini, model}.

    The config name may itself hold dots; the mix is what follows the last
    one whose two halves both name a file."""
    for i in [j for j, c in enumerate(workload) if c == "."][::-1]:
        cfg_path = os.path.join(bench_dir, "configs", workload[:i] + ".json")
        mix_path = os.path.join(bench_dir, "traffic", workload[i + 1 :] + ".json")
        if os.path.isfile(cfg_path) and os.path.isfile(mix_path):
            config, traffic = _load(cfg_path), _load(mix_path)
            ini = {s: dict(kv) for s, kv in config.get("ini", {}).items()}
            for s, kv in traffic.get("ini", {}).items():
                ini.setdefault(s, {}).update(kv)
            return {
                "name": workload,
                "config": config,
                "traffic": traffic,
                "kind": traffic["kind"],
                "chips": int(config.get("chips", 1)),
                "ini": ini,
                "model": harness_model(config, ini),
                "bench_dir": bench_dir,
            }
    raise SystemExit(f"unknown workload {workload!r}: no configs/<config>.json + traffic/<mix>.json")


def load_metrics(kind: str, bench_dir: str = BENCH_DIR, workload: str | None = None) -> list[dict]:
    """Every metric file whose ``kinds`` holds this cell's traffic kind.  A
    file may name its cells (``"workloads": [...]``, where only some cells of
    the kind have what its reader reads): it is left out of a ``workload`` it
    does not name."""
    out = []
    mdir = os.path.join(bench_dir, "metrics")
    for fn in sorted(os.listdir(mdir)):
        if fn.endswith(".json"):
            m = _load(os.path.join(mdir, fn))
            m.setdefault("name", fn[: -len(".json")])
            named = m.get("workloads")
            if kind in m["kinds"] and (workload is None or named is None or workload in named):
                out.append(m)
    return out


def fresh_workdir(workload: str, root: str = CHECKOUT) -> str:
    """``<checkout>/.bench_work/<workload>``, emptied: generated input, the
    INI file and the telemetry of one run.  Gitignored; removed at exit."""
    path = os.path.join(root, ".bench_work", workload)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def write_ini(path: str, sections: dict) -> str:
    ini = configparser.ConfigParser()
    ini.optionxform = str
    for s, kv in sections.items():
        ini[s] = {k: str(v) for k, v in kv.items()}
    with open(path, "w") as f:
        ini.write(f)
    return path
