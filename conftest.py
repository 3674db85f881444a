"""Pytest root conftest: force an 8-device virtual CPU mesh (SURVEY §4
"fake-backend note": multi-chip tests run on
xla_force_host_platform_device_count virtual devices).

The backend-reset logic lives in _virtual_devices.force_virtual_cpu, shared
with __graft_entry__.dryrun_multichip.
"""
from _virtual_devices import force_virtual_cpu

force_virtual_cpu(8)

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _jax_persistent_cache_as_found():
    """A test file leaves JAX's own persistent compilation cache as it found
    it.  ``compile.jax_cache_dir()`` (every in-process ``chipbench`` cell
    calls it) turns that cache on for the whole process, and an xdist
    worker runs many files in one process: a later file's ``compile()`` may
    then hand back an executable JAX loaded from disk, and XLA:CPU
    serialises such an executable without its kernels, so what ``mx.compile``
    commits from it fails at its next load's first dispatch ("Function
    ... not found")."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    found = [getattr(jax.config, name) for name in names]
    yield
    if [getattr(jax.config, name) for name in names] != found:
        for name, value in zip(names, found):
            jax.config.update(name, value)
        compilation_cache.reset_cache()
