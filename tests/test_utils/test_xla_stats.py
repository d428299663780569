"""Recompile detector, MFU reporter, and windowed trace capture
(ISSUE 1 tentpole)."""

import glob
import os
import warnings

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.obs import RecompileMonitor, mfu_percent, peak_flops
from sheeprl_tpu.obs.trace import ProfileScheduler, trace_scope


def test_recompile_detector_flags_shape_perturbation_exactly_once():
    @jax.jit
    def f(x):
        return x * 2.0

    # materialize both inputs BEFORE warmup ends: array creation compiles too
    a = jax.block_until_ready(jnp.ones((4,)))
    b = jax.block_until_ready(jnp.ones((5,)))

    mon = RecompileMonitor(name="test").install()
    try:
        f(a)
        f(a)
        compiles_before = mon.compiles
        mon.mark_warmup_complete()
        assert mon.post_warmup_compiles == 0

        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            f(b)  # shape perturbation -> one retrace
        retrace_warns = [w for w in caught if "recompile" in str(w.message).lower()]
        assert mon.post_warmup_compiles == 1
        assert len(retrace_warns) == 1
        assert mon.compiles == compiles_before + 1

        f(b)  # now cached: no new compile, no new warning
        f(a)
        assert mon.post_warmup_compiles == 1
    finally:
        mon.uninstall()


def test_recompile_monitor_uninstall_stops_counting():
    mon = RecompileMonitor(name="test").install()
    mon.uninstall()
    before = mon.compiles

    @jax.jit
    def g(x):
        return x + 1

    jax.block_until_ready(g(jnp.ones((3,))))
    assert mon.compiles == before


def test_warmup_requires_explicit_mark():
    mon = RecompileMonitor(name="test").install()
    try:

        @jax.jit
        def h(x):
            return x - 1

        jax.block_until_ready(h(jnp.ones((2,))))
        assert mon.compiles >= 1
        assert mon.post_warmup_compiles == 0  # nothing flagged before the mark
    finally:
        mon.uninstall()


def test_monitor_counts_the_events_this_jax_emits(tmp_path):
    """Every counter moves on a real compile under jax 0.9.0: a renamed
    monitoring event fails here instead of leaving a counter at 0."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {
        k: getattr(jax.config, k)
        for k in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_include_metadata_in_key",
        )
    }
    mon = RecompileMonitor(name="test", warn=False).install()
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # a cli.run earlier in this process leaves metadata in the cache key (parallel/mesh.py),
        # and the second compile below then misses: the test depended on the order of files
        jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
        cc.reset_cache()

        def make():
            # a new function object each time: the in-memory jit cache misses
            # while the lowered program, and so the disk entry's key, is the same
            def k(x):
                return jnp.tanh(x) * 3.0 + 1.0

            return jax.jit(k)

        x = jnp.ones((7,))
        jax.block_until_ready(make()(x))  # traced, compiled, a cache miss, written
        assert mon.compiles >= 1 and mon.compile_time_s > 0
        assert mon.trace_time_s > 0
        assert mon.cache_misses >= 1 and mon.cache_hits == 0
        jax.block_until_ready(make()(x))  # read back from the disk entry
        assert mon.cache_hits >= 1
    finally:
        mon.uninstall()
        for key, value in saved.items():
            jax.config.update(key, value)
        cc.reset_cache()


def test_mfu_percent_math():
    # 50 TFLOP step in 1 s on a 100 TFLOP/s chip = 50% MFU
    assert mfu_percent(50e12, 1.0, peak=100e12) == pytest.approx(50.0)
    assert mfu_percent(None, 1.0, peak=100e12) is None
    assert mfu_percent(50e12, 0.0, peak=100e12) is None


def test_peak_flops_env_override():
    os.environ["SHEEPRL_PEAK_FLOPS"] = "123e12"
    try:
        assert peak_flops() == pytest.approx(123e12)
    finally:
        del os.environ["SHEEPRL_PEAK_FLOPS"]


def test_peak_flops_unknown_on_cpu():
    # the test platform is CPU (conftest pins it): no published bf16 peak
    assert peak_flops(jax.devices()[0]) is None


def test_profile_scheduler_windowed_capture(tmp_path):
    trace_dir = str(tmp_path / "prof")
    sched = ProfileScheduler(trace_dir, every_n=2, num_iters=1)
    for _ in range(4):
        with trace_scope("test_phase"):
            jax.block_until_ready(jnp.ones((8,)) * 3)
        sched.on_iteration()
    sched.close()
    assert sched.captures >= 1
    traces = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert traces, "windowed capture produced no TensorBoard-readable trace"
