"""Unknowns in obs/xla_stats.py: ``compiled_flops`` / ``peak_flops`` /
``mfu_percent`` answer None for what is genuinely unknown (no flops in the
analysis, a device kind outside the table) and RAISE on a failing analysis
or a malformed override; the RecompileMonitor counts events without a live
jax backend."""

import pytest

from sheeprl_tpu.obs.xla_stats import (
    RecompileMonitor,
    compiled_flops,
    mfu_percent,
    peak_flops,
)


# ----------------------------------------------------------- compiled_flops
class _Compiled:
    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        if isinstance(self._ca, Exception):
            raise self._ca
        return self._ca


def test_flops_from_dict_and_legacy_list_shapes():
    assert compiled_flops(_Compiled({"flops": 123.0})) == 123.0
    # older jax returned a one-element list of dicts
    assert compiled_flops(_Compiled([{"flops": 5.0}])) == 5.0
    assert compiled_flops(_Compiled(({"flops": 7.0},))) == 7.0


def test_missing_cost_analysis_method_raises():
    with pytest.raises(AttributeError):
        compiled_flops(object())


def test_cost_analysis_raising_propagates():
    with pytest.raises(RuntimeError, match="not supported"):
        compiled_flops(_Compiled(RuntimeError("cost analysis not supported")))


def test_cost_analysis_returning_none_or_empty_is_none():
    assert compiled_flops(_Compiled(None)) is None
    assert compiled_flops(_Compiled({})) is None  # no flops key -> 0.0 -> None
    assert compiled_flops(_Compiled([])) is None  # empty legacy list
    assert compiled_flops(_Compiled({"flops": 0.0})) is None  # zero is "unknown"


# --------------------------------------------------------------- peak_flops
class _Device:
    def __init__(self, kind):
        self.device_kind = kind


def test_peak_from_device_kind_table():
    assert peak_flops(_Device("TPU v4")) == 275e12
    assert peak_flops(_Device("TPU v5 lite")) == 197e12
    assert peak_flops(_Device("cpu")) is None  # CPUs have no published peak
    assert peak_flops(_Device("")) is None
    assert peak_flops(_Device("TPU v9 future")) is None  # never another chip's peak


def test_peak_env_override_wins_and_bad_value_raises(monkeypatch):
    monkeypatch.setenv("SHEEPRL_PEAK_FLOPS", "1e12")
    assert peak_flops(_Device("cpu")) == 1e12
    monkeypatch.setenv("SHEEPRL_PEAK_FLOPS", "not-a-number")
    with pytest.raises(ValueError):
        peak_flops(_Device("TPU v4"))


# -------------------------------------------------------------- mfu_percent
def test_mfu_none_when_any_input_unknown():
    assert mfu_percent(None, 0.1, peak=1e12) is None
    assert mfu_percent(1e9, 0.0, peak=1e12) is None
    assert mfu_percent(1e9, 0.1, peak=None, device=_Device("cpu")) is None


def test_mfu_math():
    # 1e12 FLOPs in 10ms on a 200e12 peak chip = 50% MFU
    assert mfu_percent(1e12, 0.01, peak=200e12) == pytest.approx(50.0)


# -------------------------------------------------------- RecompileMonitor
def test_monitor_counts_without_jax_backend():
    mon = RecompileMonitor(name="t", warn=False)
    # feed the listener callbacks directly — no jax.monitoring needed
    mon._on_duration("/jax/core/compile/backend_compile_duration", 1.5)
    mon._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.25)
    mon._on_event("/jax/compilation_cache/cache_hits")
    mon._on_event("/jax/compilation_cache/cache_misses")
    snap = mon.snapshot()
    assert snap["total"] == 1 and snap["compile_time_s"] == 1.5
    assert snap["trace_time_s"] == 0.25
    assert snap["cache_hits"] == 1 and snap["cache_misses"] == 1
    assert snap["post_warmup"] == 0


def test_monitor_flags_post_warmup_recompiles():
    mon = RecompileMonitor(name="t", warn=True)
    mon.mark_warmup_complete()
    with pytest.warns(RuntimeWarning, match="retracing"):
        mon._on_duration("/jax/core/compile/backend_compile_duration", 2.0)
    snap = mon.snapshot()
    assert snap["post_warmup"] == 1
    assert snap["post_warmup_compile_time_s"] == 2.0


def test_monitor_ignores_unrelated_events():
    mon = RecompileMonitor(warn=False)
    mon._on_duration("/jax/some/other_duration", 9.0)
    mon._on_event("/jax/unrelated")
    snap = mon.snapshot()
    assert snap["total"] == 0 and snap["cache_hits"] == 0 and snap["cache_misses"] == 0
