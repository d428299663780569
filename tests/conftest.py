"""Test-global setup: fake an 8-device CPU mesh before jax initializes.

Mirrors the reference test strategy (tests/conftest.py + LT_DEVICES
parametrization, SURVEY.md §4): algorithms are exercised on CPU with tiny
configs; multi-device paths run on an XLA host-platform mesh instead of a
real pod.
"""

import os
import sys

# repo root on sys.path regardless of entry point: the installed `pytest`
# console script and tests/run_tests.py don't add the cwd, which breaks
# `from scripts...` imports (scripts/ is not an installed package)
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# Tests run on the CPU: JAX_PLATFORMS=cpu with eight virtual devices for
# the mesh tests.  The env var is exported for the subprocesses tests
# spawn; the config update below pins this process even where jax was
# imported before this file ran (it works while no backend is initialized).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _leak_sweep():
    """Suite-wide resource-leak sweep (ISSUE 9 satellite): at session end,
    orphaned ``/dev/shm/sheeprl_*`` segments or still-alive NON-daemon
    threads fail the session — the classes that previously surfaced as a
    PR-6-style exit hang or a PR-3-style /dev/shm orphan long after the
    offending test.  Replaces the ad-hoc per-test orphan checks that only
    ``tests/test_parallel`` carried.  Daemon-thread/registry leftovers
    ride along in the message as warnings, not failures (jax and test
    helpers legitimately keep daemons alive)."""
    yield
    from sheeprl_tpu.analysis.sanitizers import session_leak_report

    report = session_leak_report()
    hard = {k: v for k, v in report.items() if not k.endswith("_warn")}
    if hard:
        pytest.fail(f"resource leaks at session end: {report}", pytrace=False)


@pytest.fixture(autouse=True)
def _no_env_leaks():
    """Guard against tests leaking SHEEPRL_* env vars (reference conftest.py:20-61)."""
    before = {k: v for k, v in os.environ.items() if k.startswith("SHEEPRL_")}
    yield
    after = {k: v for k, v in os.environ.items() if k.startswith("SHEEPRL_")}
    for k in after:
        if k not in before:
            del os.environ[k]
    os.environ.update(before)


@pytest.fixture(autouse=True)
def _restore_cache_key_setting():
    """``MeshRuntime.launch`` makes the compile cache key on metadata
    (``configure_compilation_cache``): a process-wide jax setting, under which
    one program called from two lines has two keys.  One test's launch must
    not decide what another test's cache hits."""
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_include_metadata_in_key", before)


@pytest.fixture(autouse=True)
def _reset_metric_globals():
    """timer/MetricAggregator disabled are CLASS-level flags the CLI sets
    per run; reset them so one test's metric.log_level=0 cannot leak into
    another's assertions."""
    from sheeprl_tpu.utils.metric import MetricAggregator
    from sheeprl_tpu.utils.timer import timer

    before = (timer.disabled, MetricAggregator.disabled)
    yield
    timer.disabled, MetricAggregator.disabled = before
