"""Test environment: force an 8-device virtual CPU mesh before jax imports.

Multi-device tests (sharded suff-stats psum parity etc.) run on the CPU
backend with 8 virtual devices, the same recipe README "Development"
gives for running anything by hand off the chip: JAX_PLATFORMS=cpu,
eight virtual devices, Pallas kernels interpreted.
"""

import os
import tempfile

# Hermetic measured-plan cache (oni_ml_tpu/plans): the suite must
# neither read a developer's ~/.cache plan state nor write test
# measurements into it — a CPU-measured calibration leaking into the
# user cache would "tune" real runs from test synthetics.  Guarded (not
# setdefault) so an operator pinning the path doesn't still pay an
# eagerly-created throwaway tempdir.
if "ONI_ML_TPU_PLAN_CACHE" not in os.environ:
    os.environ["ONI_ML_TPU_PLAN_CACHE"] = os.path.join(
        tempfile.mkdtemp(prefix="oni_plans_test_"), "plans.jsonl"
    )
# The compilation cache follows the program's own rule
# (plans/warmup.cache_dir): JAX_COMPILATION_CACHE_DIR when the caller
# set it, else a FIXED directory inside the checkout — the directory is
# part of the cache key, so a fresh mkdtemp per session recompiled
# every program of the suite every time.  A subdirectory keeps CPU
# test executables apart from what a run on the chip caches.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache", "tests-cpu",
    )

# bench.main()'s lint preflight re-lints the whole repo (~2s per call,
# and in-process bench tests call main() repeatedly).  The suite
# already enforces that exact gate ONCE via test_analysis's live-repo
# self-run, so bench tests skip it — both faster and decoupled (a lint
# finding fails the one test that owns the gate, not every bench
# test).  Guarded so the preflight test can force it back on.
if "BENCH_LINT" not in os.environ:
    os.environ["BENCH_LINT"] = "0"

# The suite runs on the CPU whatever the machine has: the platform is
# pinned through the environment AND the config API (something may have
# imported jax before this file ran; the config update holds as long as
# no backend has been instantiated yet).
#
# ONI_ML_TPU_TESTS_ON_TPU=1 skips the pin so the `compiled` variants of
# the kernel parity tests reach the chip, Mosaic-compiled at the
# config-1 block shape:
#     ONI_ML_TPU_TESTS_ON_TPU=1 python -m pytest \
#         tests/test_sparse_estep.py tests/test_pallas_estep.py -k compiled
# Only run those that way — the full suite assumes 8 devices.
if os.environ.get("ONI_ML_TPU_TESTS_ON_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    # Keep test numerics deterministic and f32-stable on CPU.
    os.environ.setdefault("JAX_ENABLE_X64", "0")

    import jax

    jax.config.update("jax_platforms", "cpu")
