"""A configuration, a mix, a metric and a reader dropped into a directory are
found by name, with no edit to any file that is there; BENCHMARK.json agrees
with the metric files."""

import json
import os

import pytest

from harness import cells, readers

BENCH = cells.BENCH_DIR


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    for d in ("configs", "traffic", "metrics"):
        (tmp_path / d).mkdir()
    json.dump({"chips": 1, "harness_model": "fm2", "ini": {"General": {"factor_num": 4, "vocabulary_size": 64}, "Train": {"batch_size": 8}}},
              open(tmp_path / "configs" / "fm4.v2_new.json", "w"))
    json.dump({"kind": "train", "ini": {"Train": {"log_every": 2, "batch_size": 16}}},
              open(tmp_path / "traffic" / "burst.json", "w"))
    json.dump({"layer": "input (data)", "unit": "ms", "better": "lower", "source": "program_span",
               "moves": "train_examples_per_s_per_chip", "kinds": ["train"],
               "reader": "telemetry_field", "kind": "input", "field": "parse_ms", "reduce": "median"},
              open(tmp_path / "metrics" / "input.parse_ms_median.json", "w"))
    cell = cells.load_cell("fm4.v2_new.burst", str(tmp_path))
    assert cell["kind"] == "train" and cell["chips"] == 1 and cell["model"].row_dim == 5
    assert cell["ini"]["Train"] == {"batch_size": 16, "log_every": 2}  # the mix overrides
    metrics = cells.load_metrics("train", str(tmp_path))
    assert [m["name"] for m in metrics] == ["input.parse_ms_median"]
    ctx = {"records": [{"kind": "input", "step": s, "parse_ms": v} for s, v in ((4, 9.0), (8, 1.0), (12, 2.0), (16, 3.0))],
           "steps": (4, 16)}
    assert readers.read_all(metrics, ctx) == {"input.parse_ms_median": {"value": 2.0, "unit": "ms"}}
    assert cells.load_metrics("serve", str(tmp_path)) == []
    with pytest.raises(SystemExit):
        cells.load_cell("fm4.v2_new.nothing", str(tmp_path))


def test_a_new_reader_module_is_found_by_the_name_its_metric_file_gives(tmp_path, monkeypatch):
    import harness

    # A later PR's new file ``harness/scope_reader.py``, here beside the package.
    (tmp_path / "scope_reader.py").write_text(
        "def scope_ms(m, ctx):\n"
        "    by_scope = ctx.get('scopes') or {}\n"
        "    return 1e3 * by_scope[m['scope']] / ctx['n_steps'] if m['scope'] in by_scope else None\n"
    )
    monkeypatch.setattr(harness, "__path__", list(harness.__path__) + [str(tmp_path)])
    (tmp_path / "metrics").mkdir()
    for scope in ("fm.tail", "fm.absent"):
        json.dump({"layer": "step (trainer, parallel/train_step)", "unit": "ms", "better": "lower", "source": "device_trace",
                   "moves": "train_examples_per_s_per_chip", "kinds": ["train"], "reader": "scope_reader:scope_ms", "scope": scope},
                  open(tmp_path / "metrics" / f"step.{scope}_ms.json", "w"))
    out = readers.read_all(cells.load_metrics("train", str(tmp_path)), {"scopes": {"fm.tail": 1.5}, "n_steps": 3})
    assert out == {"step.fm.tail_ms": {"value": 500.0, "unit": "ms"}}  # nothing to read: left out
    with pytest.raises(SystemExit, match="no reader 'no_such_reader'"):
        readers.reader("no_such_reader")


def test_a_reader_with_nothing_to_read_leaves_the_metric_out():
    metrics = cells.load_metrics("dist_train")
    out = readers.read_all(metrics, {"records": [], "steps": (0, 0), "trace": None})
    assert "step.mfu" not in out and "collective.ms_per_step" not in out and "input.parse_ms" not in out


def test_benchmark_json_agrees_with_the_files():
    b = json.load(open(os.path.join(cells.CHECKOUT, "BENCHMARK.json")))
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in b["end_to_end"]}
    for c in b["configs"]:
        f = json.load(open(os.path.join(cells.CHECKOUT, c["file"])))
        assert f["name"] == c["name"] and f["source"] == c["source"] and f["reduced"] == c["reduced"]
    kinds = {}
    for w in b["workloads"]:
        cell = cells.load_cell(w["name"])
        assert w["name"] == f"{w['config']}.{w['traffic']}" and cell["chips"] == w["chips"]
        kinds[w["name"]] = cell["kind"]
    files = {m["name"]: m for k in set(kinds.values()) for m in cells.load_metrics(k)}
    listed = {m["name"]: m for m in b["per_layer"]}
    assert set(listed) == set(files)
    reported = {w: {m["name"] for m in cells.load_metrics(k, workload=w)} for w, k in kinds.items()}
    for name, m in listed.items():
        f = files[name]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            f["unit"], f["better"], f["source"], f["layer"], f["moves"])
        assert m["moves"] in e2e and readers.reader(f["reader"])
        # Every metric lists its cells, so that a PR that adds a cell only appends: those its file
        # names where it names any (each of a kind it reads), else every cell of its kinds.
        of_kind = sorted(w for w, k in kinds.items() if k in f["kinds"])
        assert set(f.get("workloads", of_kind)) <= set(of_kind)
        assert sorted(m["workloads"]) == sorted(f.get("workloads", of_kind))
        assert [w for w in kinds if name in reported[w]] == [w for w in kinds if w in m["workloads"]]
