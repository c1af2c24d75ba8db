#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call, at
the full width of BASELINE #1 (2nd-order FM, k=8, 39 features a row, batch
16384, 2^20 rows; every other key as configs/baseline1_fm_criteo_sample.cfg
has it).  Each phase is ONE child process, run in sequence, so the chip is
free when the next one starts; this parent never imports jax:

  device        what jax finds (platform, device_kind, count)
  data          tools/gen_synthetic.py, from a seed
  train         python fast_tffm.py train    — >= 5 steps, every logged loss
                finite, a validation pass, a checkpoint
  predict       python fast_tffm.py predict  — one score in (0, 1) per line
  serve         python fast_tffm.py serve --port 0 — SERVE_READY, 64 rows as
                binary FMD1 frames through fast_tffm_tpu.serving.client,
                scores equal predict's, SIGTERM -> exit 0
  kernels       the two Pallas entry points against the XLA paths they
                replace (compiled and matched)
  dist_*        on a host with four chips: dist_train / dist_predict on
                BASELINE #2, table shards, both lookups, loss parity

A phase that fails fails the script: non-zero exit, the phase and the
reason on the last line, no result line.  On success the last line of
stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``python chip_smoke.py`` always expects a TPU and has no flag that relaxes
it; tests/test_chip_smoke.py rehearses the same phase functions at toy
size with the expected platform passed in.  Everything written goes under
smoke_out/ (gitignored).
"""

from __future__ import annotations

import configparser
import dataclasses
import json
import math
import os
import re
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "smoke_out")
# The whole run, compilation included, must end inside 1200 s: every
# child's timeout is capped by what is left of this.
DEADLINE_S = 1150.0

# Serving scores against predict's %.6f score file: one format ULP plus a
# few float32 ULPs between predict's batch-shaped program and the serving
# bucket's — the tolerance tests/test_serving.py already uses.
SERVE_ATOL = 2e-6
# ANOVA kernel against the XLA scan path: both evaluate the same order-3
# polynomial in float32 with different summation orders; measured on a
# TPU v5 lite at B=16384 N=11 k=8: value identical, gradient 2.7e-7 of its
# scale.  Relative to the largest magnitude, not elementwise.
KERNEL_RTOL = 1e-5
# The rows sweep against the XLA row operations on the same batch: where no
# id repeats table and accumulator came out bit-equal at fm8_criteo's size on
# a TPU v5 lite (PERF.md §6, PR 30 and 32); where ids repeat the sweep adds a
# row's occurrences in another order than the rows' segment sum, a few ULP
# of the sum; on the CPU the interpreted kernel is another fusion besides, a
# few ULP off.  Steps here are about 1e-3, accumulators 0.1 (one ULP 7.5e-9).
ROWS_SWEEP_ATOL = 1e-7
# Sharded first-step loss against the one-chip step on the same batch:
# the bound __graft_entry__.py asserts on the CPU mesh.
DIST_LOSS_ATOL = 1e-5


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one rehearsal runs at.  ``FULL`` is the only thing
    ``python chip_smoke.py`` ever uses; the toy in tests/ overrides widths
    through ``overrides`` ((section, key) -> value, applied on top of the
    paths/epoch_num/model_file the derived config always replaces)."""

    config: str = "configs/baseline1_fm_criteo_sample.cfg"
    train_batches: int = 6  # x epoch_num = 24 steps: one log_every=20 line
    epoch_num: int = 4
    valid_rows: int = 20_000  # not a batch multiple: the padded tail scores
    serve_rows: int = 64
    overrides: tuple = ()
    anova: tuple = (16384, 11, 8)  # BASELINE #5: B, N, k at order 3
    dist_config: str = "configs/baseline2_fm_sharded.cfg"
    dist_batches: int = 5
    dist_overrides: tuple = ()
    timeouts: tuple = (
        ("device", 120), ("data", 180), ("train", 480), ("predict", 240),
        ("serve", 300), ("kernels", 300), ("dist_train", 480),
        ("dist_predict", 300), ("dist_check", 480),
    )


FULL = Sizes()


class SmokeFailure(Exception):
    def __init__(self, phase: str, reason: str):
        super().__init__(f"{phase}: {reason}")
        self.phase, self.reason = phase, reason


# --------------------------------------------------------------------------
# config + records
# --------------------------------------------------------------------------


def derive_config(src: str, dst: str, replace: dict) -> None:
    """Write ``dst`` = ``src`` with the ``(section, key) -> value`` entries
    of ``replace`` substituted (appended to their section when the source
    does not set them) and every other line verbatim."""
    pending = dict(replace)
    out: list[str] = []
    section = None

    def flush(sec):
        for (s, k) in [sk for sk in pending if sk[0] == sec]:
            out.append(f"{k} = {pending.pop((s, k))}\n")

    with open(src) as f:
        for line in f:
            head = re.match(r"\s*\[(\w+)\]", line)
            if head:
                flush(section)
                section = head.group(1)
            key = re.match(r"\s*([A-Za-z_0-9]+)\s*=", line)
            if key and (section, key.group(1)) in pending:
                out.append(f"{key.group(1)} = {pending.pop((section, key.group(1)))}\n")
                continue
            out.append(line)
    flush(section)
    for sec in sorted({s for s, _ in pending}):
        out.append(f"\n[{sec}]\n")
        flush(sec)
    with open(dst, "w") as f:
        f.writelines(out)


def read_cfg(path: str) -> configparser.ConfigParser:
    cp = configparser.ConfigParser(inline_comment_prefixes=(";", "#"))
    cp.read(path)
    return cp


def read_records(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def summarize(records: list[dict], phase: str) -> dict:
    """platform/device_kind/device_count + compile and cache-hit totals off
    one run's telemetry (kind=summary is the run's last record)."""
    summaries = [r for r in records if r.get("kind") == "summary"]
    if not summaries:
        raise SmokeFailure(phase, "no kind=summary record (the run did not close)")
    s = summaries[-1]
    return {
        "platform": s.get("platform"),
        "device_kind": s.get("device_kind"),
        "device_count": s.get("device_count"),
        "compiles": s.get("total_compiles"),
        "cache_hits": sum(
            int(r.get("cache_hits") or 0) for r in records if r.get("kind") == "compile"
        ),
    }


_DEVICE_LINE = re.compile(
    r"device platform=(\S+) device_kind=(\"[^\"]*\"|null) device_count=(\S+) "
    r"pallas=(\S+) parser=(\S+)"
)


def device_line(log_text: str, phase: str) -> dict:
    """The one line every device-holding entry point logs at start."""
    m = _DEVICE_LINE.search(log_text)
    if not m:
        raise SmokeFailure(phase, "no 'device platform=...' line in the child's log")
    return {"platform": m.group(1), "pallas": m.group(4), "parser": m.group(5)}


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------


class Smoke:
    """One smoke run: the child-process plumbing plus one method a phase.
    ``expect_platform`` is what every child must report."""

    def __init__(self, out_dir: str, expect_platform: str, sizes: Sizes = FULL,
                 deadline_s: float = DEADLINE_S, echo=print):
        self.out = out_dir
        self.expect = expect_platform
        self.sizes = sizes
        self.echo = echo
        self._t_end = time.monotonic() + deadline_s
        self._timeouts = dict(sizes.timeouts)
        self._live: list[subprocess.Popen] = []
        os.makedirs(out_dir, exist_ok=True)

    # -- children ----------------------------------------------------------

    def _timeout(self, phase: str) -> float:
        left = self._t_end - time.monotonic()
        if left <= 0:
            raise SmokeFailure(phase, "the run's overall deadline is spent")
        return min(float(self._timeouts[phase]), left)

    def spawn(self, phase: str, argv: list[str]) -> tuple[subprocess.Popen, str]:
        """Start one child in its own process group, stdout+stderr to
        ``<out>/<phase>.log``.  The environment is inherited untouched."""
        log_path = os.path.join(self.out, f"{phase}.log")
        with open(log_path, "w") as logf:
            proc = subprocess.Popen(
                argv, stdout=logf, stderr=subprocess.STDOUT, cwd=ROOT,
                start_new_session=True,
            )
        self._live.append(proc)
        return proc, log_path

    def reap(self, proc: subprocess.Popen) -> None:
        """Kill whatever is left of a child's process group."""
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        if proc in self._live:
            self._live.remove(proc)

    def close(self) -> None:
        for proc in list(self._live):
            self.reap(proc)

    def run_child(self, phase: str, argv: list[str]) -> str:
        """Run one child to its end; returns its log text.  A non-zero
        exit or a timeout is the phase's failure."""
        timeout = self._timeout(phase)
        proc, log_path = self.spawn(phase, argv)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired as e:
            self.reap(proc)
            raise SmokeFailure(
                phase, f"child timed out after {timeout:.0f}s ({log_path})"
            ) from e
        self.reap(proc)  # a finished child leaves no process behind
        with open(log_path, errors="replace") as f:
            text = f.read()
        if rc != 0:
            tail = [l for l in text.strip().splitlines() if l.strip()][-1:] or ["no output"]
            raise SmokeFailure(phase, f"child exited {rc}: {tail[0][:300]} ({log_path})")
        return text

    def tagged_json(self, phase: str, text: str, tag: str) -> dict:
        for line in reversed(text.splitlines()):
            if line.startswith(tag + " "):
                return json.loads(line[len(tag) + 1:])
        raise SmokeFailure(phase, f"child printed no {tag} line")

    def check_platform(self, phase: str, platform) -> None:
        if platform != self.expect:
            raise SmokeFailure(
                phase, f"child reports platform {platform!r}, expected {self.expect!r}"
            )

    def report(self, phase: str, wall_s: float, info: dict, **extra) -> None:
        fields = {
            "platform": info.get("platform"),
            "device_kind": json.dumps(info.get("device_kind")),
            "devices": info.get("device_count"),
            "wall": f"{wall_s:.1f}s",
            "compiles": info.get("compiles"),
            "cache_hits": info.get("cache_hits"),
            **extra,
        }
        self.echo(
            f"chip_smoke: {phase} ok "
            + " ".join(f"{k}={v}" for k, v in fields.items() if v is not None)
        )

    # -- phases ------------------------------------------------------------

    def phase_device(self) -> dict:
        t0 = time.monotonic()
        text = self.run_child(
            "device",
            [sys.executable, "-c",
             "import json, jax; d = jax.devices(); print('SMOKE_DEVICE ' + "
             "json.dumps({'platform': d[0].platform, 'kind': d[0].device_kind, "
             "'count': len(d)}))"],
        )
        dev = self.tagged_json("device", text, "SMOKE_DEVICE")
        self.check_platform("device", dev["platform"])
        self.report(
            "device", time.monotonic() - t0,
            {"platform": dev["platform"], "device_kind": dev["kind"],
             "device_count": dev["count"]},
        )
        return dev

    def _prepare(self, tag: str, src_cfg: str, train_batches: int, valid_rows: int,
                 epoch_num: int, overrides: tuple, seed: int) -> dict:
        """Derived config + generated data for one model: the committed
        config with only the file paths, epoch_num and model_file replaced
        (plus a toy's ``overrides``)."""
        d = self.out
        paths = {
            "cfg": os.path.join(d, f"{tag}.cfg"),
            "train": os.path.join(d, f"{tag}.train.libsvm"),
            "valid": os.path.join(d, f"{tag}.valid.libsvm"),
            "model": os.path.join(d, f"{tag}.ckpt"),
            "scores": os.path.join(d, f"{tag}.scores.txt"),
        }
        replace = {
            ("General", "model_file"): paths["model"],
            ("Train", "train_files"): paths["train"],
            ("Train", "validation_files"): paths["valid"],
            ("Train", "epoch_num"): str(epoch_num),
            ("Predict", "predict_files"): paths["valid"],
            ("Predict", "score_path"): paths["scores"],
        }
        replace.update(dict(overrides))
        derive_config(os.path.join(ROOT, src_cfg), paths["cfg"], replace)
        cp = read_cfg(paths["cfg"])
        vocab = cp.getint("General", "vocabulary_size")
        nnz = cp.getint("Train", "max_nnz")
        batch = cp.getint("Train", "batch_size")
        for name, rows, s in (
            ("train", train_batches * batch, seed), ("valid", valid_rows, seed + 1),
        ):
            self.run_child(
                "data",
                [sys.executable, os.path.join(ROOT, "tools", "gen_synthetic.py"),
                 "--rows", str(rows), "--fields", str(nnz), "--vocab", str(vocab),
                 "--out", paths[name], "--seed", str(s)],
            )
        paths.update(vocab=vocab, max_nnz=nnz, batch=batch)
        return paths

    def phase_data(self) -> dict:
        t0 = time.monotonic()
        z = self.sizes
        p = self._prepare("b1", z.config, z.train_batches, z.valid_rows,
                          z.epoch_num, z.overrides, seed=1)
        self.echo(
            f"chip_smoke: data ok train_rows={z.train_batches * p['batch']} "
            f"valid_rows={z.valid_rows} wall={time.monotonic() - t0:.1f}s"
        )
        return p

    def check_train(self, phase: str, records: list[dict], log_text: str,
                    min_steps: int, model_file: str) -> dict:
        info = summarize(records, phase)
        self.check_platform(phase, info["platform"])
        dev = device_line(log_text, phase)
        self.check_platform(phase, dev["platform"])
        if self.expect == "tpu" and dev["pallas"] != "compiled":
            raise SmokeFailure(phase, "Pallas kernels on this path run interpreted")
        losses = [r["loss"] for r in records if r.get("kind") == "train"]
        if not losses:
            raise SmokeFailure(phase, "no loss was logged (no kind=train record)")
        if not all(isinstance(x, (int, float)) and math.isfinite(x) for x in losses):
            raise SmokeFailure(phase, f"a logged loss is not finite: {losses}")
        steps = max(int(r.get("step") or 0) for r in records)
        if steps < min_steps:
            raise SmokeFailure(phase, f"only {steps} optimizer steps, need >= {min_steps}")
        aucs = [r["validation_auc"] for r in records if r.get("kind") == "validation"]
        if not aucs or not all(math.isfinite(a) for a in aucs):
            raise SmokeFailure(phase, f"no finite validation pass: {aucs}")
        if not os.path.exists(model_file):
            raise SmokeFailure(phase, f"no checkpoint at {model_file}")
        info.update(parser=dev["parser"], steps=steps, loss=losses[-1])
        return info

    def phase_train(self, p: dict) -> dict:
        t0 = time.monotonic()
        metrics = os.path.join(self.out, "train.jsonl")
        _unlink(metrics)
        text = self.run_child(
            "train",
            [sys.executable, os.path.join(ROOT, "fast_tffm.py"), "train", p["cfg"],
             "--metrics-path", metrics],
        )
        info = self.check_train("train", read_records(metrics), text,
                                min_steps=5, model_file=p["model"])
        self.report("train", time.monotonic() - t0, info, parser=info["parser"],
                    steps=info["steps"], loss=info["loss"])
        return info

    def check_scores(self, phase: str, score_path: str, input_path: str) -> list[float]:
        from fast_tffm_tpu.data.native import count_lines

        want = count_lines([input_path])
        with open(score_path) as f:
            scores = [float(line) for line in f if line.strip()]
        if len(scores) != want:
            raise SmokeFailure(
                phase, f"{len(scores)} score lines for {want} input lines"
            )
        bad = [s for s in scores if not 0.0 < s < 1.0]
        if bad:
            raise SmokeFailure(phase, f"{len(bad)} scores outside (0, 1), e.g. {bad[0]}")
        return scores

    def phase_predict(self, p: dict, verb: str = "predict") -> list[float]:
        t0 = time.monotonic()
        metrics = os.path.join(self.out, f"{verb}.jsonl")
        _unlink(metrics)
        _unlink(p["scores"])
        text = self.run_child(
            verb,
            [sys.executable, os.path.join(ROOT, "fast_tffm.py"), verb, p["cfg"],
             "--metrics-path", metrics],
        )
        info = summarize(read_records(metrics), verb)
        self.check_platform(verb, info["platform"])
        dev = device_line(text, verb)
        scores = self.check_scores(verb, p["scores"], p["valid"])
        self.report(verb, time.monotonic() - t0, info, parser=dev["parser"],
                    scores=len(scores))
        return scores

    def _wait_serve_ready(self, proc, log_path: str, t_end: float) -> dict:
        """The SERVE_READY line's key=value fields, read off the child's
        log (its own group and log file are why this is not
        client.spawn_serve): fails on exit or silence past ``t_end``."""
        from fast_tffm_tpu.serving.protocol import SERVE_READY_PREFIX

        while True:
            with open(log_path, errors="replace") as f:
                for line in f:
                    if line.startswith(SERVE_READY_PREFIX):
                        return dict(
                            kv.split("=", 1)
                            for kv in line[len(SERVE_READY_PREFIX):].split()
                        )
            if proc.poll() is not None:
                raise SmokeFailure(
                    "serve", f"exited {proc.returncode} before SERVE_READY ({log_path})"
                )
            if time.monotonic() > t_end:
                raise SmokeFailure("serve", f"no SERVE_READY in time ({log_path})")
            time.sleep(0.2)

    def phase_serve(self, p: dict, predict_scores: list[float]) -> dict:
        import numpy as np

        from fast_tffm_tpu.data.native import best_parser, parser_name
        from fast_tffm_tpu.serving.client import FrameConnection

        t0 = time.monotonic()
        timeout = self._timeout("serve")
        metrics = os.path.join(self.out, "serve.jsonl")
        _unlink(metrics)
        _unlink(metrics + ".r0")
        proc, log_path = self.spawn(
            "serve",
            [sys.executable, os.path.join(ROOT, "fast_tffm.py"), "serve", p["cfg"],
             "--port", "0", "--metrics-path", metrics],
        )
        conn = None
        try:
            ready = self._wait_serve_ready(proc, log_path, t0 + timeout)
            t_ready = time.monotonic() - t0
            self.check_platform("serve", ready.get("platform"))
            n = self.sizes.serve_rows
            with open(p["valid"]) as f:
                lines = [line.strip() for line in f if line.strip()][:n]
            conn = FrameConnection(int(ready["port"]), timeout=60.0)
            pb = best_parser()(
                lines, vocabulary_size=p["vocab"], hash_feature_id_flag=False,
                max_nnz=conn.max_nnz,
            )
            req_ids = np.arange(1, n + 1, dtype=np.uint32)
            for a in range(0, n, conn.max_frame_rows):
                b = min(n, a + conn.max_frame_rows)
                conn.send_batch(
                    req_ids[a:b], pb.ids[a:b], pb.vals[a:b],
                    fields=pb.fields[a:b] if conn.uses_fields else None,
                )
            missing = conn.wait_answered(req_ids, timeout=60.0)
            if missing:
                raise SmokeFailure("serve", f"{len(missing)} of {n} rows never answered")
            with conn.lock:
                results = [conn.results[int(r)] for r in req_ids]
            bad = [st for st, _ in results if st != "ok"]
            if bad:
                raise SmokeFailure("serve", f"{len(bad)} rows not scored, e.g. {bad[0]!r}")
            got = np.asarray([sc for _, sc in results], np.float64)
            want = np.asarray(predict_scores[:n], np.float64)
            worst = float(np.max(np.abs(got - want)))
            if not worst <= SERVE_ATOL:
                raise SmokeFailure(
                    "serve", f"scores differ from predict's by {worst:.3g} > {SERVE_ATOL}"
                )
            conn.close()
            conn = None
            proc.send_signal(signal.SIGTERM)
            try:
                rc = proc.wait(timeout=60)
            except subprocess.TimeoutExpired as e:
                raise SmokeFailure("serve", "did not exit within 60s of SIGTERM") from e
            if rc != 0:
                raise SmokeFailure("serve", f"exited {rc} on SIGTERM, expected 0")
        finally:
            if conn is not None:
                conn.close()
            self.reap(proc)
        info = summarize(read_records(metrics + ".r0"), "serve")
        self.check_platform("serve", info["platform"])
        self.report("serve", time.monotonic() - t0, info, parser=parser_name(),
                    rows=n, ready=f"{t_ready:.1f}s", max_abs_diff=f"{worst:.2g}")
        return info

    def phase_kernels(self) -> dict:
        t0 = time.monotonic()
        b, n, k = self.sizes.anova
        text = self.run_child(
            "kernels",
            [sys.executable, "-c",
             f"import chip_smoke; chip_smoke.kernels_child({b}, {n}, {k})"],
        )
        res = self.tagged_json("kernels", text, "SMOKE_KERNELS")
        self.check_platform("kernels", res["platform"])
        on_chip = self.expect == "tpu"
        a = res["anova"]
        if not (a["value_rel"] <= KERNEL_RTOL and a["grad_rel"] <= KERNEL_RTOL):
            raise SmokeFailure("kernels", f"anova_inter disagrees with the XLA path: {a}")
        if on_chip and not a["compiled"]:
            raise SmokeFailure("kernels", "anova_inter ran interpreted on the chip")
        rows = res["rows_tail"]
        if rows.get("refused"):
            raise SmokeFailure("kernels", f"the rows sweep did not run: {rows['refused']}")
        if on_chip and not rows["compiled"]:
            raise SmokeFailure("kernels", "the rows sweep ran interpreted on the chip")
        if not max(rows["max_abs_diff"], rows["max_abs_diff_repeats"]) <= ROWS_SWEEP_ATOL:
            raise SmokeFailure("kernels", f"the rows sweep disagrees with the XLA row operations: {rows}")
        self.echo(
            f"chip_smoke: kernels rows_tail: {'compiled' if rows['compiled'] else 'interpreted'}"
            f"+matched (max_abs_diff={rows['max_abs_diff']:.2g} on a batch without repeats, "
            f"{rows['max_abs_diff_repeats']:.2g} with {rows['repeats']} repeated ids of 512, "
            f"atol={ROWS_SWEEP_ATOL}); what the rows layout takes where optim.rows_tail_form says so"
        )
        self.echo(
            f"chip_smoke: kernels ok platform={res['platform']} "
            f"anova_inter={'compiled' if a['compiled'] else 'interpreted'}+matched "
            f"(value_rel={a['value_rel']:.2g} grad_rel={a['grad_rel']:.2g} "
            f"rtol={KERNEL_RTOL}) "
            f"wall={time.monotonic() - t0:.1f}s"
        )
        return res

    def phase_dist(self) -> dict:
        """Four chips: BASELINE #2 through dist_train / dist_predict, then
        shard placement, both lookups and loss parity on the row mesh."""
        z = self.sizes
        t0 = time.monotonic()
        p = self._prepare("b2", z.dist_config, z.dist_batches, z.valid_rows,
                          1, z.dist_overrides, seed=3)
        metrics = os.path.join(self.out, "dist_train.jsonl")
        _unlink(metrics)
        text = self.run_child(
            "dist_train",
            [sys.executable, os.path.join(ROOT, "fast_tffm.py"), "dist_train",
             p["cfg"], "--metrics-path", metrics],
        )
        records = read_records(metrics)
        info = summarize(records, "dist_train")
        self.check_platform("dist_train", info["platform"])
        steps = max(int(r.get("step") or 0) for r in records)
        if steps < z.dist_batches:
            raise SmokeFailure("dist_train", f"only {steps} steps, need {z.dist_batches}")
        if not os.path.exists(p["model"]):
            raise SmokeFailure("dist_train", f"no checkpoint at {p['model']}")
        mesh = re.search(r"^mesh: (.*)$", text, re.M)
        self.report("dist_train", time.monotonic() - t0, info, steps=steps,
                    mesh=json.dumps(mesh.group(1) if mesh else None))
        self.phase_predict(p, verb="dist_predict")
        t1 = time.monotonic()
        cp = read_cfg(p["cfg"])
        text = self.run_child(
            "dist_check",
            [sys.executable, "-c",
             "import chip_smoke; chip_smoke.dist_check_child("
             f"{p['vocab']}, {cp.getint('General', 'factor_num')}, "
             f"{p['batch']}, {p['max_nnz']})"],
        )
        res = self.tagged_json("dist_check", text, "SMOKE_DIST")
        self.check_platform("dist_check", res["platform"])
        if res["shard_devices"] != res["devices"] or not res["equal_bytes"]:
            raise SmokeFailure("dist_check", f"table shards are not one per device: {res}")
        if res.get("alltoall_fell_back"):
            raise SmokeFailure(
                "dist_check", "the alltoall step overflowed its capacity and "
                "fell back to allgather: the alltoall route did not run"
            )
        for name in ("allgather", "alltoall"):
            diff = abs(res[name] - res["local"])
            if not diff <= DIST_LOSS_ATOL:
                raise SmokeFailure(
                    "dist_check", f"lookup = {name} first-step loss {res[name]} vs "
                    f"one-chip {res['local']}: |diff| {diff:.3g} > {DIST_LOSS_ATOL}"
                )
        self.echo(
            f"chip_smoke: dist_check ok platform={res['platform']} "
            f"devices={res['devices']} shards={res['shard_devices']}x"
            f"{res['shard_bytes']}B loss local={res['local']:.7f} "
            f"allgather={res['allgather']:.7f} alltoall={res['alltoall']:.7f} "
            f"wall={time.monotonic() - t1:.1f}s"
        )
        return res


def _unlink(path: str) -> None:
    try:
        os.remove(path)
    except FileNotFoundError:
        pass


def run(out_dir: str, expect_platform: str, sizes: Sizes = FULL, echo=print) -> dict:
    """Every phase, in order; returns the device triple.  Raises
    SmokeFailure at the first phase that fails."""
    smoke = Smoke(out_dir, expect_platform, sizes, echo=echo)
    try:
        device = smoke.phase_device()
        paths = smoke.phase_data()
        smoke.phase_train(paths)
        scores = smoke.phase_predict(paths)
        smoke.phase_serve(paths, scores)
        smoke.phase_kernels()
        if device["count"] >= 4:
            smoke.phase_dist()
        else:
            echo(f"chip_smoke: dist skipped: {device['count']} chip")
    finally:
        smoke.close()
    if "jax" in sys.modules:
        # One process per chip: had this parent loaded jax, a backend
        # could have come up here and taken the chip from the children.
        raise SmokeFailure("parent", "the smoke's own process imported jax")
    return device


# --------------------------------------------------------------------------
# children that need jax (run as `python -c "import chip_smoke; ..."`)
# --------------------------------------------------------------------------


def kernels_child(b: int, n: int, k: int) -> None:
    """The two Pallas entry points on whatever backend this child gets."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fast_tffm_tpu.ops.fm import fm_score
    from fast_tffm_tpu.ops.pallas_tail import rows_tail_adagrad_update
    from fast_tffm_tpu.telemetry import enable_compilation_cache

    enable_compilation_cache()
    rng = np.random.default_rng(0)
    out = {"platform": jax.devices()[0].platform}

    # anova_inter forward + backward at BASELINE #5's width against the
    # XLA scan path fm_score(use_pallas=False).
    rows = jnp.asarray(rng.standard_normal((b, n, k + 1)) * 0.1, jnp.float32)
    vals = jnp.asarray(rng.uniform(0.5, 1.5, (b, n)), jnp.float32)

    def vg(use_pallas):
        return jax.jit(
            jax.value_and_grad(
                lambda r: jnp.sum(fm_score(r, vals, 3, use_pallas=use_pallas) ** 2)
            )
        )

    ref_v, ref_g = vg(False)(rows)
    ker = vg(True)
    hlo = ker.lower(rows).as_text()
    ker_v, ker_g = ker(rows)
    out["anova"] = {
        "compiled": "tpu_custom_call" in hlo,
        "value_rel": float(abs(ker_v - ref_v) / abs(ref_v)),
        "grad_rel": float(jnp.max(jnp.abs(ker_g - ref_g)) / jnp.max(jnp.abs(ref_g))),
    }

    # The rows sweep (PR 30) at BASELINE #1's row width (D = 9 lanes; few
    # rows): it compiles on a TPU and must match the XLA row operations.  On
    # the CPU test mesh it interprets.  Two batches: one in which no id
    # repeats (the two forms then add nothing in different orders: bit-equal
    # on the chip) and one with the benchmark generator's heavy tail (PR 32:
    # the sweep sums a row's occurrences in its own contraction, the rows by
    # a segment sum, so the float32 sums may differ in the last digits).
    v, m = 4096, 512
    batches = {
        "max_abs_diff": rng.permutation(v)[:m],
        "max_abs_diff_repeats": (v * rng.random(m) ** 2.5).astype(np.int64),
    }

    def attempt(program, *args):
        try:
            jax.block_until_ready(program(*args))
        except Exception as e:  # the compiler's refusal IS the result
            text = str(e).strip()
            return {"refused": f"{type(e).__name__}: " + " ".join(text.split())[:400]}
        return {"refused": None}

    g9 = jnp.asarray(rng.standard_normal((m, 9)) * 1e-2, jnp.float32)
    t9, a9 = jnp.zeros((v, 9), jnp.float32), jnp.full((v, 9), 0.1, jnp.float32)
    from fast_tffm_tpu.optim import AdagradState, sparse_adagrad_update

    tail = {  # the same update in its two forms
        "sweep": jax.jit(lambda t, a, ids: rows_tail_adagrad_update(t, a, ids, g9, 0.05)),
        "rows": jax.jit(
            lambda t, a, ids: sparse_adagrad_update(t, AdagradState(a), ids, g9, 0.05, form="rows")
        ),
    }
    first = jnp.asarray(batches["max_abs_diff"], jnp.int32)
    out["rows_tail"] = attempt(tail["sweep"], t9, a9, first)
    if not out["rows_tail"]["refused"]:
        out["rows_tail"]["compiled"] = "tpu_custom_call" in tail["sweep"].lower(t9, a9, first).as_text()
        for key, ids in batches.items():
            ids = jnp.asarray(ids, jnp.int32)
            want_t, want_s = tail["rows"](t9, a9, ids)
            got_t, got_a = tail["sweep"](t9, a9, ids)
            out["rows_tail"][key] = float(
                jnp.maximum(jnp.max(jnp.abs(got_t - want_t)), jnp.max(jnp.abs(got_a - want_s.accum)))
            )
        out["rows_tail"]["repeats"] = m - int(np.unique(batches["max_abs_diff_repeats"]).size)
    print("SMOKE_KERNELS " + json.dumps(out), flush=True)


def dist_check_child(vocab: int, k: int, batch_size: int, nnz: int) -> None:
    """Row mesh over every device: table shard placement, one step through
    each lookup, and the one-chip step's loss on the same batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fast_tffm_tpu.models import Batch, FMModel
    from fast_tffm_tpu.parallel import (
        init_sharded_state,
        make_mesh,
        make_sharded_train_step,
    )
    from fast_tffm_tpu.telemetry import enable_compilation_cache
    from fast_tffm_tpu.trainer import init_state, make_train_step

    enable_compilation_cache()
    devices = jax.devices()
    n_dev = len(devices)
    model = FMModel(vocabulary_size=vocab, factor_num=k, order=2)
    rng = np.random.default_rng(5)
    # One id per field, heavy-tailed inside the field's own slice of the
    # vocabulary (tools/gen_synthetic.py's shape): skewed within a shard,
    # balanced across shards, so the alltoall route runs inside its
    # capacity instead of falling back to allgather.
    span = vocab // nnz
    ids = np.arange(nnz) * span + (span * rng.random((batch_size, nnz)) ** 2.5).astype(np.int64)
    batch = Batch(
        labels=jnp.asarray(rng.integers(0, 2, (batch_size,)), jnp.float32),
        ids=jnp.asarray(ids, jnp.int32),
        vals=jnp.asarray(rng.uniform(0.1, 1.0, (batch_size, nnz)), jnp.float32),
        fields=jnp.zeros((batch_size, nnz), jnp.int32),
        weights=jnp.ones((batch_size,), jnp.float32),
    )
    out = {"platform": devices[0].platform, "devices": n_dev}
    _, loss = make_train_step(model, 0.05)(init_state(model, jax.random.key(0)), batch)
    out["local"] = float(loss)
    mesh = make_mesh(1, n_dev)
    for lookup in ("allgather", "alltoall"):
        state = init_sharded_state(model, mesh, jax.random.key(0))
        if lookup == "allgather":
            shards = state.table.addressable_shards
            out["shard_devices"] = len({s.device for s in shards})
            out["shard_bytes"] = int(shards[0].data.nbytes)
            out["equal_bytes"] = len({int(s.data.nbytes) for s in shards}) == 1
        # overflow_mode as [Distributed] lookup_overflow defaults it.
        res = make_sharded_train_step(
            model, 0.05, mesh, lookup=lookup, overflow_mode="fallback"
        )(state, batch)
        out[lookup] = float(res[1])
        if len(res) > 2:
            out["alltoall_fell_back"] = bool(int(res[2]))
        del state, res
    print("SMOKE_DIST " + json.dumps(out), flush=True)


def main() -> int:
    try:
        device = run(OUT_DIR, "tpu")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED {e.phase}: {e.reason}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
