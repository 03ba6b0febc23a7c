"""Shared fixtures: process-global state must not leak between tests."""

import pytest


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop jit/pjit compilation caches after each test module.  The
    JAX-heavy modules each compile dozens of distinct graphs; letting
    every executable from every module stay live for the whole run has
    crashed the XLA CPU compiler late in a full single-process suite.
    Modules rarely share shapes, so the recompile cost is negligible."""
    yield
    try:
        import jax
    except ImportError:          # pure-DSE tier without jax installed
        return
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _reset_lengths_downgrade_warning():
    """Re-arm kernels.ops's warn-once masked-lengths downgrade flag
    around every test, so one test tripping (or asserting on) the
    warning cannot hide it from — or fail — another."""
    try:
        from repro.kernels import ops
    except ImportError:          # pure-DSE tier without jax installed
        yield
        return
    ops.reset_lengths_downgrade_warning()
    yield
    ops.reset_lengths_downgrade_warning()
