"""bench.py perf-regression gate (ISSUE 6 satellite / ROADMAP item 5):
headline metrics must be compared against the newest committed
BENCH_r*.json in the correct better-direction, with the justified
skip-list exempting known-noisy metrics."""

import importlib.util
import json
import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench_mod", os.path.join(_REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _round_file(tmp_path, metrics_lines):
    tail = "\n".join(json.dumps(m) for m in metrics_lines)
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({"n": 3, "tail": tail}))
    return tmp_path


def test_gate_flags_only_true_regressions(bench, tmp_path):
    _round_file(
        tmp_path,
        [
            {"metric": "ppo_wallclock", "value": 100.0, "unit": "s"},
            {"metric": "dv3_frames", "value": 1000.0, "unit": "frames/s"},
            {"metric": "sac_wallclock", "value": 50.0, "unit": "s"},
        ],
    )
    current = {
        "ppo": {"metric": "ppo_wallclock", "value": 130.0, "unit": "s"},  # 30% slower
        "dv3": {"metric": "dv3_frames", "value": 700.0, "unit": "frames/s"},  # 30% slower
        "sac": {"metric": "sac_wallclock", "value": 55.0, "unit": "s"},  # 10%: within budget
    }
    gate = bench.run_perf_gate(current, repo=str(tmp_path), threshold=0.20)
    failed = {r["metric"] for r in gate["regressions"]}
    assert failed == {"ppo_wallclock", "dv3_frames"}
    assert gate["baseline_round"] == "BENCH_r03.json"
    assert set(gate["checked"]) == {"ppo_wallclock", "dv3_frames", "sac_wallclock"}


def test_gate_improvements_and_new_metrics_pass(bench, tmp_path):
    _round_file(tmp_path, [{"metric": "ppo_wallclock", "value": 100.0, "unit": "s"}])
    current = {
        "ppo": {"metric": "ppo_wallclock", "value": 60.0, "unit": "s"},  # faster
        "new": {"metric": "brand_new_metric", "value": 1.0, "unit": "s"},  # no baseline
    }
    gate = bench.run_perf_gate(current, repo=str(tmp_path))
    assert gate["regressions"] == []


def test_gate_newest_round_wins(bench, tmp_path):
    for n, val in ((2, 100.0), (10, 40.0)):  # r10 > r2 numerically, not lexically
        tail = json.dumps({"metric": "ppo_wallclock", "value": val, "unit": "s"})
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps({"n": n, "tail": tail}))
    name, metrics = bench.load_previous_round(str(tmp_path))
    assert name == "BENCH_r10.json"
    assert metrics["ppo_wallclock"]["value"] == 40.0


def test_gate_skiplist_exempts_noisy_metrics(bench, tmp_path):
    _round_file(tmp_path, [{"metric": "decoupled_over_coupled_speedup", "value": 0.5, "unit": "x"}])
    current = {
        "dec": {"metric": "decoupled_over_coupled_speedup", "value": 0.1, "unit": "x"}
    }
    gate = bench.run_perf_gate(current, repo=str(tmp_path))
    assert gate["regressions"] == []
    assert "decoupled_over_coupled_speedup" in gate["skipped"]


def test_gate_no_baseline_is_a_pass(bench, tmp_path):
    gate = bench.run_perf_gate(
        {"ppo": {"metric": "x", "value": 1.0, "unit": "s"}}, repo=str(tmp_path)
    )
    assert gate["regressions"] == [] and gate["baseline_round"] is None


def test_committed_skiplist_is_well_formed(bench):
    skip = bench.load_gate_skiplist()
    assert skip, "benchmarks/bench_gate_skiplist.json missing or empty"
    for metric, reason in skip.items():
        assert isinstance(reason, str) and len(reason) > 10, f"{metric} needs a justification"


def test_gate_runs_against_committed_rounds(bench):
    """The real repo baseline parses and gates the real metric names."""
    name, metrics = bench.load_previous_round()
    assert name and "ppo_cartpole_benchmark_wallclock" in metrics
    current = {
        "ppo": {
            "metric": "ppo_cartpole_benchmark_wallclock",
            "value": metrics["ppo_cartpole_benchmark_wallclock"]["value"] * 2,
            "unit": "s",
        }
    }
    gate = bench.run_perf_gate(current)
    assert [r["metric"] for r in gate["regressions"]] == ["ppo_cartpole_benchmark_wallclock"]


def test_importing_bench_leaves_the_jax_backends_uninitialized():
    """One process per chip: the bench parent only spawns the section
    children and must never touch a backend itself — a parent that holds
    the chip makes every child fail or hang."""
    import subprocess
    import sys

    probe = (
        "import sys; sys.path.insert(0, {repo!r}); import bench; "
        "xb = sys.modules.get('jax._src.xla_bridge'); "
        "assert not (xb and xb._backends), sorted(xb._backends)"
    ).format(repo=_REPO)
    proc = subprocess.run([sys.executable, "-c", probe], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
