"""Test harness: force an 8-device CPU platform so every parallelism recipe
is exercised with real XLA collectives and no TPU (SURVEY.md §4 — the
reference has zero tests; this virtual mesh replaces its manual 2-GPU
Kaggle smoke runs). `JAX_PLATFORMS=cpu` is enough to pin the backend; it
is set here too so a bare `pytest` and the subprocesses tests spawn get
it as well."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

# Compile-time trim: tiny test shapes gain nothing from LLVM's expensive
# optimization passes, and XLA:CPU compile time dominates suite wall-clock
# (~40% faster overall). Parsed when the first backend client is created.
_FAST_COMPILE = ("--xla_backend_optimization_level=0 "
                 "--xla_llvm_disable_expensive_passes=true")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                           + _FAST_COMPILE).strip()

from distributed_pytorch_tpu import compat, config  # noqa: E402

compat.request_cpu_devices(8)

# Persistent compile cache: the suite is compile-dominated and most test
# invocations recompile identical tiny-shape programs. One placement rule
# for the whole repo (config.enable_compile_cache).
config.enable_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running tests")
