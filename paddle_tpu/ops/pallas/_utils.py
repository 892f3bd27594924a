"""Shared Pallas kernel utilities."""

from __future__ import annotations


def interpret_mode():
    """Pallas kernels compile natively on TPU; everywhere else (CPU
    tests/CI) they run in interpret mode so the kernel path is always
    exercised."""
    import jax
    return jax.default_backend() != "tpu"


def no_x64():
    """Trace pallas kernels with x64 promotion OFF: the framework runs
    with jax_enable_x64 globally (explicit 64-bit dtypes must survive),
    but weak python literals inside a kernel then promote to i64/f64,
    which Mosaic cannot legalize (observed: infinite recursion in the
    lowering's dtype promotion). Kernel inputs carry explicit dtypes,
    so disabling x64 for the trace changes nothing semantically."""
    import jax
    return jax.enable_x64(False)
