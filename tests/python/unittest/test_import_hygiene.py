"""Import hygiene: `import mxnet_tpu` must never touch a PJRT backend.

A module-level device computation would make every import pay for (or hang
on) backend start-up, and a forked DataLoader worker or a second process on
a one-chip host must be able to import the package without claiming the
chip.  These tests pin the contract: import stays host-only, and bench.py
fails — non-zero, no headline — when it finds no TPU.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _run(code, env_extra=None, timeout=120):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO)


def test_import_initializes_no_backend():
    # Runs in a fresh interpreter: the parent pytest process has long since
    # initialized its CPU backend, which would mask the regression.
    proc = _run(
        "import mxnet_tpu\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), (\n"
        "    'import mxnet_tpu initialized a PJRT backend')\n"
        "print('CLEAN')\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CLEAN" in proc.stdout


def test_import_succeeds_without_any_platform():
    # JAX_PLATFORMS set to a bogus name: any backend touch at import time
    # would raise.  Import must still succeed because it never asks.
    proc = _run(
        "import mxnet_tpu\nprint('OK', mxnet_tpu.__version__)\n",
        env_extra={"JAX_PLATFORMS": "no_such_platform"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


def _sources_holding(word):
    package = os.path.join(REPO, "mxnet_tpu")
    found, seen = [], 0
    for folder, _, files in os.walk(package):
        if word in folder.lower():
            found.append(folder)
        for name in files:
            if name.endswith((".py", ".cc", ".h", ".md")):
                seen += 1
                with open(os.path.join(folder, name), errors="replace") as f:
                    if word in f.read().lower():
                        found.append(os.path.join(folder, name))
    assert seen > 100
    return found


@pytest.mark.parametrize("where", ["attribute", "feature", "variable",
                                   "instrument", "source"])
def test_no_tuning_subsystem_is_left(where):
    # Block sizes, bucket bytes, bucket tables, K, ring depth and slots are
    # constants or environment defaults in the one module that uses each.
    import mxnet_tpu as mx
    from mxnet_tpu import config, runtime, telemetry

    word = "autotune"
    names = {"attribute": lambda: dir(mx),
             "feature": lambda: runtime.Features(),
             "variable": lambda: config.ENV_VARS,
             "instrument": lambda: telemetry._REGISTRY,
             "source": lambda: _sources_holding(word)}[where]()
    assert [n for n in names if word in n.lower()] == []


def test_bench_fails_without_a_tpu():
    # A run that does not reach the chip is a failure, not a null row: on
    # the CPU backend bench.py exits non-zero before it prints a headline.
    proc = _run(
        "import runpy, sys\n"
        "sys.argv = ['bench.py']\n"
        "runpy.run_path('bench.py', run_name='__main__')\n",
        env_extra={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "resnet50_train_bf16_bs128_imgs_per_sec" not in proc.stdout


def test_runtime_features_lazy_and_complete():
    # Detection must not happen at import; every dict entry point (get,
    # `in`, iteration) must see the fully-detected map on first touch.
    proc = _run(
        "import mxnet_tpu\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "from mxnet_tpu import runtime\n"
        "assert 'XLA' in runtime.features\n"
        "assert runtime.features.get('XLA').enabled\n"
        "assert runtime.features.is_enabled('BF16')\n"
        "assert len(list(runtime.features)) == len(runtime.feature_list())\n"
        "print('LAZYOK')\n",
        env_extra={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "LAZYOK" in proc.stdout
