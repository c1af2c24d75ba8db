"""fast_tffm_tpu: a TPU-native factorization-machine training framework.

Built from scratch on JAX/XLA/Pallas with the capabilities of
`renyi533/fast_tffm` (TF-1.x + custom C++ ops): train/predict entrypoints
driven by an INI config, libsvm input with optional feature-id hashing,
fused arbitrary-order FM scoring kernels with hand-written backward passes,
sparse Adagrad with L2 regularization, and row-sharded embedding tables
across a TPU device mesh (the reference's `vocabulary_block_num`
parameter-server sharding, redone as `jax.sharding` + collectives).
"""

__version__ = "0.1.0"

import importlib

# PEP 562 lazy exports.  Two reasons this is a name table and not a block
# of eager imports:
#
#   * the package exports pull in jax (models, drivers) — but the
#     telemetry module's hang-exit watchdog must be armable BEFORE
#     ``import jax`` (a batch tool must not hang, and backend init is a
#     place a process can block), and jax-free parents (chip_smoke.py,
#     the serving router, the supervisor) import this package while
#     their children hold the chip — so ``import fast_tffm_tpu`` and
#     ``import fast_tffm_tpu.telemetry`` have to stay jax-free;
#   * CLI startup (`--help`, config errors) stops paying backend-init
#     latency on paths that never touch a device.
#
# Driver modules are named training/prediction — NOT train/predict — so
# the package-level FUNCTIONS (the reference's entrypoint vocabulary)
# never collide with a submodule attribute: `from fast_tffm_tpu import
# train` is always the function, and `fast_tffm_tpu.training.scan_max_nnz`
# -style module access keeps working.  Heavy optional deps (orbax) stay
# lazy inside the driver modules.
_EXPORTS = {
    "Config": "fast_tffm_tpu.config",
    "build_model": "fast_tffm_tpu.config",
    "load_config": "fast_tffm_tpu.config",
    "open_fmb": "fast_tffm_tpu.data.binary",
    "write_fmb": "fast_tffm_tpu.data.binary",
    "StreamingAUC": "fast_tffm_tpu.metrics",
    "auc": "fast_tffm_tpu.metrics",
    "AsyncCheckpointer": "fast_tffm_tpu.checkpoint_async",
    "save_checkpoint": "fast_tffm_tpu.checkpoint",
    "restore_checkpoint": "fast_tffm_tpu.checkpoint",
    "Batch": "fast_tffm_tpu.models",
    "DeepFMModel": "fast_tffm_tpu.models",
    "FFMModel": "fast_tffm_tpu.models",
    "FMModel": "fast_tffm_tpu.models",
    "fm_score": "fast_tffm_tpu.ops.fm",
    "predict": "fast_tffm_tpu.prediction",
    "dist_predict": "fast_tffm_tpu.prediction",
    "ServingEngine": "fast_tffm_tpu.serving",
    "serve_lines": "fast_tffm_tpu.serving",
    "RunMonitor": "fast_tffm_tpu.telemetry",
    "train": "fast_tffm_tpu.training",
    "dist_train": "fast_tffm_tpu.training",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is not None:
        value = getattr(importlib.import_module(mod), name)
    else:
        # The eager imports used to bind submodules as package attributes
        # (`fast_tffm_tpu.training.scan_max_nnz`-style access, documented
        # above) — keep that working lazily too.
        try:
            value = importlib.import_module(f"{__name__}.{name}")
        except ModuleNotFoundError as e:
            if e.name != f"{__name__}.{name}":
                raise  # the submodule EXISTS but one of its deps is missing
            raise AttributeError(
                f"module {__name__!r} has no attribute {name!r}"
            ) from None
    globals()[name] = value  # cache: resolve each name once
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
