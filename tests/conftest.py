"""Test env: force CPU jax with 8 virtual devices so mesh/parallelism tests
run without TPUs (SURVEY.md §4: the TPU-world equivalent of Paddle's Gloo
fallback + localhost multi-process simulation).

Tests are hermetic CPU: ``JAX_PLATFORMS=cpu`` is set here (and mirrored
into jax.config) before any backend is initialized — conftest imports
precede test modules. The chip is never reached from the suite; see
``chip_smoke.py`` for that."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Serving page-accounting audit (ISSUE 10): every engine built by the
# suite asserts free + held + deferred + trash == num_pages after each
# drain/preempt/cancel, so a reclamation bug fails the nearest test
# loudly instead of leaking quietly.
os.environ.setdefault("PADDLE_TPU_SERVING_AUDIT", "1")

# Hermetic tuner cache: kernels consult the persistent tuning cache at
# trace time (paddle_tpu/tuner); tests must never read a developer's
# ~/.cache winners nor write theirs back, so the suite gets a private
# per-run cache file (tests that need a specific cache state point the
# global cache elsewhere and restore this one).
if "PADDLE_TPU_TUNER_CACHE" not in os.environ:
    import tempfile
    os.environ["PADDLE_TPU_TUNER_CACHE"] = os.path.join(
        tempfile.mkdtemp(prefix="paddle_tpu_test_tuner_"),
        "tuning_cache.json")

import jax

jax.config.update("jax_platforms", "cpu")

# The suite tests framework semantics (shapes, parity, autograd), not
# XLA's optimizer — and this container has ONE cpu core, so XLA:CPU
# compile time dominates suite wall-time (measured 27% faster with
# optimizations off, all tests green). Set PADDLE_TPU_TEST_FULL_OPT=1
# to run against fully-optimized XLA output instead.
if not os.environ.get("PADDLE_TPU_TEST_FULL_OPT"):
    jax.config.update("jax_disable_most_optimizations", True)

# Persistent compilation cache: many test files compile IDENTICAL tiny
# programs (the same tiny-llama step, the same collective shapes) — the
# HLO-keyed cache dedupes them even within one cold run (~15% suite
# wall; repeat runs ~30%). Honors an externally-set cache dir.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import tempfile
    _user = os.environ.get("USER") or os.environ.get("LOGNAME") \
        or str(os.getuid() if hasattr(os, "getuid") else "anon")
    _cache_dir = os.path.join(
        tempfile.gettempdir(), f"paddle_tpu_test_xla_cache_{_user}")
    jax.config.update("jax_compilation_cache_dir", _cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


import pytest


def reset_fleet_state():
    """Restore single-device fleet state after fleet.init — the ONE
    place that knows the private fields."""
    from paddle_tpu.distributed import fleet
    fleet.fleet._hcg = None
    fleet.fleet._topology = None
    fleet.fleet._is_initialized = False


@pytest.fixture
def reset_fleet():
    yield
    reset_fleet_state()
