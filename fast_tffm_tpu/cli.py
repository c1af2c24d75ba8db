"""CLI dispatcher — the reference's fast_tffm.py entry surface.

`renyi533/fast_tffm` :: fast_tffm.py: positional mode + cfg path
(`python fast_tffm.py {train,predict,dist_train,dist_predict} <cfg>
[job_name task_index]`).  The job_name/task_index pair is accepted for CLI
compatibility but ignored with a notice: under single-program SPMD there is
no per-task launch — one process drives the whole mesh.
"""

from __future__ import annotations

import argparse
import sys

from fast_tffm_tpu.config import load_config

MODES = ("train", "predict", "dist_train", "dist_predict", "convert", "serve")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="fast_tffm",
        description="TPU-native factorization machine trainer (fast_tffm capabilities)",
    )
    ap.add_argument("mode", choices=MODES)
    ap.add_argument("config", help="INI config file (see sample.cfg)")
    ap.add_argument("legacy", nargs="*", help="ignored job_name/task_index (TF-1.x compat)")
    ap.add_argument("--resume", action="store_true", help="resume training from model_file")
    ap.add_argument(
        "--metrics-path",
        default=None,
        metavar="PATH",
        help="telemetry JSONL sink; overrides [Train] metrics_path so a run "
        "can be instrumented (tools/report.py) without editing the config",
    )
    ap.add_argument(
        "--run-id",
        default=None,
        metavar="ID",
        help="telemetry run id stamped on every record; overrides "
        "[Telemetry] run_id (default: auto-generated per run)",
    )
    ap.add_argument(
        "--profile-steps",
        default=None,
        metavar="A:B",
        help="capture a jax.profiler trace over steps [A, B) (rounded to "
        "dispatch boundaries under step fusion) into <model_file>.profile "
        "(trace_dir overrides); overrides [Telemetry] profile_steps",
    )
    ap.add_argument(
        "--supervised",
        action="store_true",
        help="train/dist_train only: run the trainer as a SUPERVISED child "
        "process — a crash relaunches it with bounded retries and "
        "exponential backoff ([Resilience] restart_* keys), resuming from "
        "the latest full+delta checkpoint chain; kind=fault/restart "
        "telemetry (incl. MTTR) goes to metrics_path",
    )
    ap.add_argument(
        "--max-restarts",
        type=int,
        default=None,
        metavar="N",
        help="override [Resilience] restart_max for --supervised",
    )
    ap.add_argument(
        "--fault-plan",
        default=None,
        metavar="SPEC",
        help="arm a deterministic fault plan (chaos testing): "
        "'kill@120,io_error@45,nan@200:210,torn_delta@1' or "
        "'random:kill=2,io_error=3' drawn from --fault-seed; under "
        "--supervised the plan applies to the FIRST launch only (restarts "
        "run clean)",
    )
    ap.add_argument(
        "--fault-seed", type=int, default=0, metavar="N",
        help="seed for random: fault plans (same seed = same schedule)",
    )
    ap.add_argument(
        "--fault-horizon", type=int, default=1000, metavar="STEPS",
        help="step horizon random: fault plans draw positions from",
    )
    ap.add_argument(
        "--port", type=int, default=None, metavar="P",
        help="serve only: run the SOCKET front end (serving/frontend.py) on "
        "this TCP port instead of the stdin/stdout pipe — replicated "
        "engines, health-checked failover, typed wire errors ([Serving] "
        "replicas/classes/deadline_ms).  0 = ephemeral (announced as "
        "SERVE_READY on stdout).  [Serving] port > 0 implies this mode",
    )
    ap.add_argument(
        "--fault-process", type=int, default=0, metavar="P",
        help="pod-supervised dist_train only: arm --fault-plan on host P "
        "(default 0, the checkpoint writer; -1 = every host — e.g. nan "
        "faults, which each host must observe) — the writer-kill vs "
        "survivor-kill axis of the pod chaos matrix",
    )
    args = ap.parse_args(argv)

    cfg = load_config(args.config)
    if args.metrics_path is not None:
        cfg.metrics_path = args.metrics_path
    if args.run_id is not None:
        cfg.telemetry_run_id = args.run_id
    if args.profile_steps is not None:
        from fast_tffm_tpu.profiling import parse_profile_steps

        parse_profile_steps(args.profile_steps)  # fail fast on a bad spec
        cfg.telemetry_profile_steps = args.profile_steps
    # Before any driver import compiles a program: repeated runs (and
    # serving cold starts) read their XLA programs back from the on-disk
    # cache instead of recompiling — the compile sentinel reports the hits
    # distinctly (kind=compile cache_hits).  serving/replica.main makes the
    # same call, so the workers land on the same directory.  (Configures
    # jax; initialises no backend — the supervisor and the socket front
    # end stay off the device.)
    from fast_tffm_tpu.telemetry import enable_compilation_cache

    enable_compilation_cache(cfg.telemetry_compilation_cache_dir)
    if args.legacy:
        print(
            f"note: ignoring legacy cluster args {args.legacy!r} — the SPMD mesh "
            "replaces ps/worker tasks (one launch drives all devices)",
            file=sys.stderr,
        )

    if args.supervised:
        if args.mode not in ("train", "dist_train"):
            ap.error("--supervised applies to train / dist_train only")
        # The supervisor process stays device-free: it re-execs THIS CLI
        # as a child (without --supervised), watches it, and relaunches
        # on crash with --resume so the child restores the latest
        # full+delta chain at the exact saved input position.
        import os

        from fast_tffm_tpu.resilience import Supervisor

        # ONE run id for the whole supervised run: the supervisor's
        # fault/restart records and every child's train/ckpt/input
        # records must share it, or tools/report.py (which summarizes
        # one run_id per file) would drop the crash history and the
        # Resilience section from a supervised run's report.
        if not cfg.telemetry_run_id:
            from fast_tffm_tpu.telemetry import new_run_id

            cfg.telemetry_run_id = new_run_id()
        base = [sys.executable, "-m", "fast_tffm_tpu.cli", args.mode, args.config]
        if args.metrics_path is not None:
            base += ["--metrics-path", args.metrics_path]
        base += ["--run-id", cfg.telemetry_run_id]
        # The child resolves the package the same way THIS process did —
        # works for pip installs and straight-from-checkout runs alike.
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        child_env = dict(os.environ)
        child_env["PYTHONPATH"] = (
            pkg_root + os.pathsep + child_env["PYTHONPATH"]
            if child_env.get("PYTHONPATH")
            else pkg_root
        )

        if args.mode == "dist_train" and cfg.num_processes > 1:
            # POD supervision: one supervisor process owns N local trainer
            # children (one per pod host), the shared generation file, and
            # the single-host-relaunch recovery protocol (distributed.py).
            # A chaos plan arms on --fault-process's first launch only.
            runtime_dir = cfg.runtime_dir or (cfg.model_file + ".dist")

            def build_pod_cmd(attempt: int, resume_flag: bool, proc: int) -> list[str]:
                cmd = list(base)
                if resume_flag:
                    cmd += ["--resume"]
                if args.fault_plan and attempt == 0 and (
                    proc == args.fault_process or args.fault_process < 0
                ):
                    cmd += [
                        "--fault-plan", args.fault_plan,
                        "--fault-seed", str(args.fault_seed),
                        "--fault-horizon", str(args.fault_horizon),
                    ]
                return cmd

            sup = Supervisor(
                build_pod_cmd,
                model_file=cfg.model_file,
                max_restarts=(
                    args.max_restarts if args.max_restarts is not None else cfg.restart_max
                ),
                backoff_s=cfg.restart_backoff_s,
                backoff_max_s=cfg.restart_backoff_max_s,
                metrics_path=cfg.metrics_path or None,
                run_id=cfg.telemetry_run_id,
                log=lambda *a: print(*a, file=sys.stderr),
                child_log=print,
                env=child_env,
                processes=cfg.num_processes,
                runtime_dir=runtime_dir,
                straggler_timeout_s=cfg.host_stall_timeout_s,
            )
            return sup.run(resume=args.resume)

        def build_cmd(attempt: int, resume_flag: bool) -> list[str]:
            cmd = list(base)
            if resume_flag:
                cmd += ["--resume"]
            if args.fault_plan and attempt == 0:
                # Chaos plans arm the FIRST launch only: a kill fault that
                # re-armed on every relaunch would crash-loop forever.
                cmd += [
                    "--fault-plan", args.fault_plan,
                    "--fault-seed", str(args.fault_seed),
                    "--fault-horizon", str(args.fault_horizon),
                ]
            return cmd

        sup = Supervisor(
            build_cmd,
            model_file=cfg.model_file,
            max_restarts=(
                args.max_restarts if args.max_restarts is not None else cfg.restart_max
            ),
            backoff_s=cfg.restart_backoff_s,
            backoff_max_s=cfg.restart_backoff_max_s,
            metrics_path=cfg.metrics_path or None,
            run_id=cfg.telemetry_run_id,
            log=lambda *a: print(*a, file=sys.stderr),
            child_log=print,
            env=child_env,
        )
        return sup.run(resume=args.resume)

    step_hook = None
    if args.fault_plan:
        from fast_tffm_tpu.resilience import FaultPlan, install_faults

        inj = install_faults(
            FaultPlan.parse(
                args.fault_plan, seed=args.fault_seed, horizon=args.fault_horizon
            )
        )
        print(f"fault plan armed: {inj.plan.to_json()}", file=sys.stderr)
        step_hook = inj.step_hook

    if args.mode == "train":
        from fast_tffm_tpu.training import train

        train(cfg, resume=args.resume, step_hook=step_hook)
    elif args.mode == "dist_train":
        from fast_tffm_tpu.training import dist_train

        try:
            dist_train(cfg, resume=args.resume, step_hook=step_hook)
        except Exception as e:
            from fast_tffm_tpu.resilience import NonFiniteLossError

            if isinstance(e, NonFiniteLossError):
                raise  # a shared, deterministic decision — never peer loss
            import os
            import time as _time

            from fast_tffm_tpu.distributed import (
                ENV_GENERATION,
                ENV_RUNTIME_DIR,
                PEER_LOST_EXIT,
                read_generation,
            )

            gen_env = os.environ.get(ENV_GENERATION)
            rdir = os.environ.get(ENV_RUNTIME_DIR)
            if gen_env is None or not rdir:
                raise
            # Pod child: an escaping error here is USUALLY collateral of a
            # peer dying (gloo/coordination errors surface as generic
            # runtime errors).  Dying now would turn one host's crash into
            # N relaunches, so PARK: the supervisor's generation bump
            # re-execs this process via the watcher thread mid-sleep.  If
            # no bump arrives, the failure was ours alone — re-raise it.
            print(
                f"dist_train failed ({e!r}); parking for a pod generation "
                "bump (peer crash?) before giving up",
                file=sys.stderr,
            )
            deadline = _time.monotonic() + min(30.0, cfg.barrier_timeout_s)
            while _time.monotonic() < deadline:
                _time.sleep(0.25)
            info = read_generation(rdir)
            if info is not None and int(info.get("generation", -1)) > int(gen_env):
                # Bump landed but the watcher lost the exec race — die
                # with the collateral code; the supervisor relaunches us
                # into the current generation.
                return PEER_LOST_EXIT
            raise
    elif args.mode == "predict":
        from fast_tffm_tpu.prediction import predict

        predict(cfg)
    elif args.mode == "serve":
        if args.port is not None or cfg.serve_port > 0:
            # Socket mode: TCP front end -> router -> serve_replicas
            # engine worker processes (per-replica jit caches), with
            # health-checked failover, deadline/class admission, and the
            # router-owned checkpoint-reload fan-out.
            from fast_tffm_tpu.serving.frontend import run_frontend

            return run_frontend(
                cfg,
                args.config,
                port=args.port,
                log=lambda *a: print(*a, file=sys.stderr),
            )
        # Pipe mode: libsvm lines on stdin -> one score per line on
        # stdout, micro-batched through the bucket-compiled engine
        # ([Serving] config).  Logs/metrics go to stderr/metrics_path so
        # the score stream stays clean for piping.
        from fast_tffm_tpu.serving import serve_lines

        return serve_lines(cfg, log=lambda *a: print(*a, file=sys.stderr))
    elif args.mode == "convert":
        # Pre-pack every configured data file into its FMB binary cache
        # (what `binary_cache = true` would do lazily at first stream) —
        # handy before a pod run so training starts at memmap speed.
        # Per FILE, not one ensure_fmb_cache call: the all-or-nothing text
        # fallback is a per-STREAM rule, but these files feed independent
        # streams — an unwritable predict mount must not abort packing the
        # train files.  No upfront width scan either: a fresh-cache rerun
        # stays nearly free, and write_fmb defaults to each file's widest
        # row (compatible with any training-time max_nnz >= it).
        from fast_tffm_tpu.data.binary import ensure_fmb_cache, is_fmb

        files = tuple(
            dict.fromkeys((*cfg.train_files, *cfg.validation_files, *cfg.predict_files))
        )
        if not files:
            print("no data files configured", file=sys.stderr)
            return 1
        if not cfg.binary_cache:
            print(
                "note: this config has binary_cache = false — set it to true "
                "(or put the .fmb paths in the file lists) so train/predict "
                "actually stream the packed caches",
                file=sys.stderr,
            )
        failures = 0
        for src in files:
            try:
                (dst,) = ensure_fmb_cache(
                    [src],
                    vocabulary_size=cfg.vocabulary_size,
                    hash_feature_id=cfg.hash_feature_id,
                    max_nnz=cfg.max_nnz or None,
                    log=print,
                )
            except (OSError, ValueError, RuntimeError) as e:
                # ValueError: malformed libsvm / id out of range;
                # RuntimeError: file changed mid-convert.  One bad FILE must
                # not abort packing the rest any more than a bad mount does.
                print(f"{src}: FAILED ({e})", file=sys.stderr)
                failures += 1
                continue
            if dst == src and not is_fmb(src):
                # The unwritable-location fallback hands back the text path.
                print(f"{src}: FAILED (cache location unwritable)", file=sys.stderr)
                failures += 1
            elif src == dst:
                print(f"{src} (already FMB)")
            else:
                print(f"{src} -> {dst}")
        if failures:
            print(f"{failures} of {len(files)} files not converted", file=sys.stderr)
            return 1
    else:
        from fast_tffm_tpu.prediction import dist_predict

        dist_predict(cfg)
    return 0


if __name__ == "__main__":
    sys.exit(main())
