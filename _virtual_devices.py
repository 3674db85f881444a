"""Force jax onto an n-device virtual CPU platform.

SURVEY §4 "fake-backend note": multi-chip tests/dryruns execute on
``xla_force_host_platform_device_count`` virtual CPU devices.  The env vars
are read when the first backend is created; if jax is already loaded and a
backend is live, its config is flipped and the backend dropped so the next
resolution lands on the virtual CPU platform.

Shared by ``conftest.py`` (pytest) and ``__graft_entry__.py`` (driver
dryrun) so the backend-reset dance lives in ONE place.
"""
import os
import sys


def force_virtual_cpu(n):
    """Make ``jax.devices()`` return ``n`` virtual CPU devices."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d" % n).strip()

    if "jax" not in sys.modules:
        # jax not imported yet: the env vars above are read at first client
        # creation, nothing else to do.
        return
    import jax
    import jax.extend.backend

    jax.config.update("jax_platforms", "cpu")
    jax.extend.backend.clear_backends()
    # must come AFTER clear_backends: the knob refuses to change while a
    # backend is live.  (XLA_FLAGS is parsed once per process at first
    # client creation, so re-setting it here would be too late — the
    # config knob is the only reliable in-process path.)
    jax.config.update("jax_num_cpu_devices", n)
