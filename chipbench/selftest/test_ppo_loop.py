"""The cell ``sdar_ep8_loop`` (driver ``ppo_loop``, PR 32): the whole cell at
the tiny size on the CPU; its comparison held to planted faults (a cache
written one block late, losses over half of the minibatch, an update call that
stops after half of its steps); the byte count against a count by hand; the
five ``collect_*`` readers and the collect split on a slice of a chip run's
trace.

The two controls (``benchmarks/ppo_loop_controls.py``), saying which is shown
where: a cache written one block late is planted HERE, at the tiny size, and
read against the sound run; parameters stored in bf16 do not show at the tiny
size (two steps of 1e-5 on 64-wide weights), so that control, and the first
at the published widths, are held by the stored readings of the builder's
chip runs (``testdata/sdar_ep8_loop_readings.json``)."""

import contextlib
import gzip
import importlib
import io
import json
import os
import sys

import pytest

from chipbench import bytes_collect, collect_scopes, flops_sdar
from chipbench.drivers import ppo_loop

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NEW_READERS = ("collect_device_ms", "collect_wait_pct", "collect_pass_us", "collect_read_roofline_pct",
               "collect_outside_model_ms")
PUBLISHED = flops_sdar.SdarShapes(
    hidden=2048, q_heads=32, kv_heads=4, head_dim=128, router_width=128, top_k=8, experts_held=16, expert_width=768,
    layers=4, vocab=18992, prompt=512, response=1024, block=4, steps=4, episodes=3)


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


# ------------------------------------------------------------------ the cell, end to end
def _bench_run():
    sys.path.insert(0, HERE)
    return importlib.import_module("run")  # chipbench/run.py


def _run_tiny(argv, fault=None):
    """``chipbench/run.py --tiny`` in this process, under ``fault`` (a context
    manager) where one is planted: (exit code, the lines it printed as dicts)."""
    bench_run = _bench_run()
    out = io.StringIO()
    with contextlib.redirect_stdout(out), (fault or contextlib.nullcontext()):
        rc = bench_run.main(["--workload", "sdar_ep8_loop", "--tiny", "--seconds", "2"] + argv)
    lines = [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")]
    return rc, lines


@pytest.fixture(scope="module")
def tiny_run():
    return _run_tiny(["--seed", "2147483659"])


def _line(lines, key):
    return next(line[key] for line in lines if key in line)


def test_tiny_cell_end_to_end(tiny_run):
    rc, lines = tiny_run
    assert rc == 0 and not any("incorrect" in line for line in lines), [line for line in lines if "incorrect" in line]
    result = lines[-1]
    assert result["metrics"]["env_frames_per_s"]["value"] > 0 and result["metrics"]["setup_s"]["value"] > 0
    window = _line(lines, "window")
    assert window["iterations"] >= 2 and window["update_calls"] == window["iterations"]
    assert window["policy_steps"] == window["iterations"] * 4 * 16 == result["attempted"]
    readings = _line(lines, "compare_with_reference")["readings"]
    assert readings["cells"] == 2 * 16 and not ppo_loop.judge(readings, ppo_loop.limits_for("sdar_moe"))
    # float32 on both sides at the tiny size: the cache-carrying passes ARE the full pass, and the update the reference's
    assert readings["recorded_logp_max_abs"] < 1e-5 and readings["recorded_value_max_abs"] < 1e-5
    assert readings["logp_max_abs"] < 1e-5 and readings["recorded_vs_update_logp_max_abs"] < 1e-5
    assert readings["grad_norm_rel"] < 1e-3 and readings["moved_leaf_worst_rel"] < 1e-2 and readings["loss_worst"] < 1e-2
    assert readings["steps_missing"] == 0 and len(readings["steps_change"]) == 2 and 0.0 < readings["returned_shortfall"] < 0.5
    jaxenv = _line(lines, "telemetry_last")["jaxenv"]
    assert jaxenv["params_age"] == 0 and jaxenv["passes"] == jaxenv["rollouts"] * (1 + 4 * 5)
    # the byte count rests on the experts a pass's 16 rows were COUNTED to reach
    needs = _line(lines, "collect_needs")
    assert 0 < needs["experts_reached_per_pass_and_layer"] <= needs["experts_held"]
    assert needs["counted_per_rollout"] == {"passes": 1 + 4 * 5, "positions": 4 * (8 + 4 * 5 * 4)}


def _controls():
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    return importlib.import_module("ppo_loop_controls")


def test_a_cache_written_one_block_late_fails(tiny_run):
    sound = _line(tiny_run[1], "compare_with_reference")["readings"]
    _, lines = _run_tiny(["--seed", "2147483659"], _controls().cache_written_one_block_late())
    shifted = _line(lines, "compare_with_reference")["readings"]
    assert "recorded_logp_mean_abs" in _line(lines, "incorrect")  # (a --tiny run never says "correct": this line says why not)
    for key in ("recorded_logp_mean_abs", "recorded_logp_max_abs", "recorded_value_mean_abs", "recorded_value_max_abs",
                "recorded_vs_update_logp_max_abs"):
        assert shifted[key] > 1e-3 > 100 * sound[key], (key, shifted[key], sound[key])
    # the update is untouched by the fault: its own forward pass still is the reference's
    assert shifted["logp_max_abs"] < 1e-5 and shifted["returned_shortfall"] == pytest.approx(sound["returned_shortfall"], abs=0.05)


@contextlib.contextmanager
def _losses_over_half_of_the_minibatch():
    """The update's three losses take the first half of the minibatch's
    episodes only (``chipbench/selftest/test_sdar.py`` plants the same in the
    update alone): collection and the forward pass stay sound."""
    import sheeprl_tpu.algos.ppo.ppo as ppo

    whole = {name: getattr(ppo, name) for name in ("policy_loss", "value_loss", "entropy_loss")}
    for name, f in whole.items():
        setattr(ppo, name, lambda *a, _f=f: _f(*(x[: x.shape[0] // 2] if getattr(x, "ndim", 0) == 2 else x for x in a)))
    try:
        yield
    finally:
        for name, f in whole.items():
            setattr(ppo, name, f)


def test_half_of_the_batch_is_not_correct():
    _, lines = _run_tiny(["--seed", "2147483659"], _losses_over_half_of_the_minibatch())
    readings = _line(lines, "compare_with_reference")["readings"]
    over = set(ppo_loop.judge(readings, ppo_loop.limits_for("sdar_moe")))
    assert "moved_leaf_worst_rel" in _line(lines, "incorrect") and {"grad_leaf_worst_rel", "moved_leaf_worst_rel"} <= over, readings
    # what collection recorded and what the forward pass produced are the whole batch's
    assert readings["recorded_logp_max_abs"] < 1e-5 and readings["logp_max_abs"] < 1e-5


@contextlib.contextmanager
def _an_update_that_stops_after_half_of_its_steps():
    """``ppo.main`` builds its update over the first half of the rollout's
    episodes: half of the minibatch steps run, each of them sound."""
    import jax

    import sheeprl_tpu.algos.ppo.ppo as ppo

    make = ppo.make_episode_update_fn

    def halved(runtime, policy, tx, cfg):
        fn = make(runtime, policy, tx, cfg)

        def update(params, opt_state, data, *rest):
            return fn(params, opt_state, jax.tree_util.tree_map(lambda x: x[:, : x.shape[1] // 2], data), *rest)

        update.health = fn.health
        return update

    ppo.make_episode_update_fn = halved
    try:
        yield
    finally:
        ppo.make_episode_update_fn = make


def test_half_of_the_steps_is_not_correct():
    """Each step that ran is the reference's, and the state the call returned is
    the sum of them: only the count of steps tells."""
    _, lines = _run_tiny(["--seed", "2147483659"], _an_update_that_stops_after_half_of_its_steps())
    readings = _line(lines, "compare_with_reference")["readings"]
    assert "steps_missing" in _line(lines, "incorrect")
    assert set(ppo_loop.judge(readings, ppo_loop.limits_for("sdar_moe"))) == {"steps_missing"}, readings
    assert readings["steps_missing"] == 1 and len(readings["steps_change"]) == 1


def test_the_causal_twin_needs_only_data(monkeypatch):
    """The same driver over ``joyai_flash_ep`` with a traffic mix that names ``mla_moe``: two data files."""
    from chipbench import harness

    traffic = {**_load("traffic", "loop_12env_p512_r1024_mb3.json"), "policy": "mla_moe"}
    traffic["tiny_overrides"] = ["env.num_envs=4", "env.wrapper.prompt_len=8", "env.wrapper.response_len=16",
                                 "algo.per_rank_batch_size=1", "metric.log_every=64", "fabric.precision=32-true"]
    traffic["tiny"] = {**traffic["tiny"], "minibatch_episodes": 1}
    workload = {"config": "joyai_flash_ep", "traffic": "twin", "chips": 1, "why": "", "layer_metrics": ["window_compiles"]}
    load = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda *parts: {("workloads", "twin.json"): workload, ("traffic", "twin.json"): traffic}
                        .get(parts) or load(*parts))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert _bench_run().main(["--workload", "twin", "--tiny", "--seconds", "2", "--seed", "5"]) == 0
    lines = [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")]
    assert not any("incorrect" in line for line in lines), [line for line in lines if "incorrect" in line]
    readings = _line(lines, "compare_with_reference")["readings"]
    assert readings["cells"] == 16 and not ppo_loop.judge(readings, ppo_loop.limits_for("mla_moe"))
    assert readings["recorded_logp_max_abs"] < 1e-5 and readings["logp_max_abs"] < 1e-5 and readings["steps_missing"] == 0
    needs = _line(lines, "collect_needs")
    assert needs["counted_per_rollout"] == {"passes": 1 + 16, "positions": 4 * (8 + 16)}
    assert 0 < needs["experts_reached_per_pass_and_layer"] <= needs["experts_held"]


# ------------------------------------------------------------- the comparison's judge
def test_judge_on_the_chip_runs_readings():
    """``sound`` / ``bf16_true`` / ``cache_shift``: the comparison as it stands (the first minibatch's three
    episodes, the update held too); ``*_one_episode``: the earlier runs of this PR, one episode's recorded
    cells (their keys renamed to the ``recorded_*`` ones, nothing else)."""
    stored, limits = _load("testdata", "sdar_ep8_loop_readings.json"), ppo_loop.limits_for("sdar_moe")
    sound = stored["sound"] + stored["sound_one_episode"]
    assert len(stored["sound"]) >= 3 and len(stored["sound_one_episode"]) == 10
    assert all(not ppo_loop.judge(r, limits) for r in sound)
    assert all(set(limits) <= set(r) for r in stored["sound"] + [stored["bf16_true"], stored["cache_shift"]])
    assert all(set(ppo_loop.judge(r, limits)) == {"returned_shortfall"} for r in (stored["bf16_true"], stored["bf16_true_one_episode"]))
    late = [stored["cache_shift"]] + stored["cache_shift_one_episode"]
    assert all(set(ppo_loop.judge(r, limits)) == {"recorded_logp_mean_abs"} for r in late)
    # each deciding limit stands between the sound runs' largest reading and the control's, with room on both sides
    sound_logp = max(r["recorded_logp_mean_abs"] for r in sound)
    assert 1.25 * sound_logp < limits["recorded_logp_mean_abs"] < min(r["recorded_logp_mean_abs"] for r in late) / 1.25
    assert 2 * max(r["returned_shortfall"] for r in sound) < 0.5 < stored["bf16_true"]["returned_shortfall"] / 1.5
    # the limits on the update are sdar_train's: the loop's sound runs leave each of them room
    for key in ("loss_worst", "grad_norm_rel", "grad_leaf_worst_rel", "moved_leaf_worst_rel", "logp_mean_abs", "value_mean_abs"):
        assert 1.5 * max(r[key] for r in stored["sound"]) < limits[key], key


@pytest.mark.parametrize("fault, over", [
    ({"returned_shortfall": 1.0, "returned_excess": -1.0}, {"returned_shortfall"}),  # a state returned unchanged
    ({"returned_shortfall": 0.75, "returned_excess": -0.75}, {"returned_shortfall"}),  # one step of four kept
    ({"recorded_logp_max_abs": 0.5}, {"recorded_logp_max_abs"}), ({"recorded_value_mean_abs": 0.05}, {"recorded_value_mean_abs"}),
    ({"handed_share": 0.2}, {"handed_share"}), ({"recorded_logp_mean_abs": float("nan")}, {"recorded_logp_mean_abs"}),
    ({"steps_missing": 2}, {"steps_missing"}),  # an update call that ran two of its four minibatch steps
    ({"grad_norm_rel": 0.5, "moved_leaf_worst_rel": 0.9}, {"grad_norm_rel", "moved_leaf_worst_rel"}),  # a step that is not the reference's
])
def test_judge_names_the_limit_that_failed(fault, over):
    limits = ppo_loop.limits_for("sdar_moe")
    sound = {"handed_share": 0.045, "recorded_logp_mean_abs": 4e-3, "recorded_logp_max_abs": 2e-2, "recorded_value_mean_abs": 8e-3,
             "recorded_value_max_abs": 2e-2, "returned_shortfall": 0.15, "returned_excess": -0.15, "steps_missing": 0,
             "grad_norm_rel": 0.06, "moved_leaf_worst_rel": 0.2, "loss_worst": 0.2, "cells": 3072}
    assert not ppo_loop.judge(sound, limits)
    assert set(ppo_loop.judge({**sound, **fault}, limits)) == over


# ------------------------------------------------------------------ bytes, by hand
def test_published_rollout_bytes_by_hand():
    got = bytes_collect.sdar_rollout_bytes(PUBLISHED, envs=12, width=2)
    # a layer: q and o 2048 x 4096 each, k and v 2048 x 512 each, two head norms of 128; the router 2048 x 128;
    # 16 experts of 3 x 2048 x 768; two norms of 2048
    layer = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 2048 * 128 + 16 * 3 * 2048 * 768 + 2 * 2048
    assert layer == 94_638_336 and got["layers"] == 2 * 4 * layer == 757_106_688  # 378.6 M parameters in bf16
    assert got["head"] == 2 * (2048 * 18993 + 2048) == 77_799_424  # 38.9 M
    # keys and values of 4 layers, 12 envs, 4 heads of 128, at the mean of 512, 516, ... 1,532 clean positions
    assert got["cache"] == 2 * 4 * 2 * 12 * 1022 * 512 == 100_466_688
    assert got["passes"] == 256 * 5 == 1280 and got["scored_passes"] == 1024
    assert got["rollout"] == 1280 * (757_106_688 + 100_466_688) + 1024 * 77_799_424 == 1_177_360_531_456
    # 1.177 TB a rollout: 1.44 s at the chip's 819 GB/s
    assert got["rollout"] / 819e9 == pytest.approx(1.4376, abs=1e-3)
    assert got["positions"] == 12 * (512 + 1280 * 4)
    # that is every held expert read in every pass: an upper bound.  With the experts a pass reaches counted,
    # a layer reads that many experts' weights and everything else as before
    counted = bytes_collect.sdar_rollout_bytes(PUBLISHED, envs=12, width=2, reached=13.5)
    assert got["layers"] - counted["layers"] == 2 * 4 * 2.5 * 3 * 2048 * 768
    assert {k: counted[k] for k in ("head", "cache", "passes")} == {k: got[k] for k in ("head", "cache", "passes")}


def test_experts_reached_by_hand():
    """Two envs, prompt 4, one block of 4 in 2 steps, one layer, top-2 of 8 experts of which 2..5 are held:
    16 packed positions an episode (4 prompt, 4 clean = the pass that commits, 2 copies of 4 = the denoising passes)."""
    import numpy as np

    shapes = flops_sdar.SdarShapes(hidden=8, q_heads=1, kv_heads=1, head_dim=8, router_width=8, top_k=2, experts_held=4,
                                   expert_width=8, layers=1, vocab=16, prompt=4, response=4, block=4, steps=2, episodes=1)
    assert bytes_collect.sdar_pass_of_position(shapes).tolist() == [-1] * 4 + [2] * 4 + [0] * 4 + [1] * 4
    top_i = np.zeros((1, 2, 16, 2), np.int64)  # expert 0 everywhere: not held
    top_i[0, :, :4] = 3  # the prompt's rows reach a held expert, in the prefill: not a cached pass
    top_i[0, 0, 4:8] = [[2, 3], [2, 0], [0, 1], [7, 6]]  # the commit pass, env 0: experts 2 and 3
    top_i[0, 1, 4:8] = [[3, 5], [0, 0], [0, 0], [0, 0]]  # env 1 adds expert 5: three distinct
    top_i[0, 1, 8:12] = [[4, 0], [4, 0], [4, 0], [4, 4]]  # denoising pass 0: expert 4 alone; pass 1 reaches none
    assert bytes_collect.experts_reached("sdar_moe", shapes, top_i, offset=2) == pytest.approx((3 + 1 + 0) / 3)
    with pytest.raises(ValueError):
        bytes_collect.experts_reached("sdar_moe", shapes, top_i[:, :, :12], offset=2)


def test_bytes_follow_the_compute_width_not_the_storage():
    config, traffic = _load("configs", "sdar_30b_a3b_ep8.json"), _load("traffic", "loop_12env_p512_r1024_mb3.json")
    mixed = bytes_collect.rollout_bytes("sdar_moe", config, traffic, False, 12)
    stored_low = bytes_collect.rollout_bytes("sdar_moe", {**config, "precision": "bf16-true"}, traffic, False, 12)
    assert mixed == stored_low == bytes_collect.sdar_rollout_bytes(PUBLISHED, 12, 2)
    assert bytes_collect.rollout_bytes("sdar_moe", {**config, "precision": "32-true"}, traffic, False, 12)["rollout"] == 2 * mixed["rollout"]


def test_causal_rollout_bytes_by_hand():
    config, traffic = _load("configs", "joyai_flash_ep.json"), _load("traffic", "rollout_p1024_r7168_mb1.json")
    got = bytes_collect.rollout_bytes("mla_moe", config, traffic, False, 4, reached=2.0)
    attention = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048 + 1536 + 512)
    # a pass whose 4 rows reach 2 of the 16 held experts (what even routing would send them: 32 x 16 / 256): those and the shared one
    routed = 2048 * 256 + 256 + (2 + 1) * 3 * 2048 * 768
    layers = 5 * (attention + 2 * 2048) + 3 * 2048 * 7168 + 4 * routed
    assert got["layers"] == 2 * layers and got["passes"] == got["scored_passes"] == 7168
    assert got["cache"] == 2 * 5 * 4 * (1024 + 7167 / 2) * 576
    assert got["head"] == 2 * (2048 * 16161 + 2048)
    # 0.64 GB a scored token at 819 GB/s is 0.78 ms; the chip took 1.35 ms for decode and score (PERF.md section 6)
    assert (got["layers"] + got["cache"] + got["head"]) / 819e9 == pytest.approx(0.785e-3, rel=0.02)


# ------------------------------------------------- the readers on a slice of a chip run's trace
@pytest.fixture(scope="module")
def slice_evidence():
    """``testdata/sdar_ep8_loop_collect_slice.json.gz``: the ops of one rollout's first and last
    milliseconds with their scope paths (my chip run, PR 32), its ``XLA Modules`` events and the program's
    ``Time/*`` spans; the expected numbers beside it were read off it by hand."""
    with gzip.open(os.path.join(HERE, "testdata", "sdar_ep8_loop_collect_slice.json.gz"), "rt") as f:
        return json.load(f)


def test_collect_split_on_the_slice(slice_evidence):
    expected = _load("testdata", "sdar_ep8_loop_collect_slice.expected.json")
    devices = {slice_evidence["device"]: {"ops": slice_evidence["ops"], "modules": slice_evidence["modules"]}}
    got = collect_scopes.by_scope(devices, tuple(slice_evidence["window"]), "^jit_collect_rollout")
    assert got["count"] == expected["rollouts"]
    for scope, ms in expected["ms_by_scope"].items():
        assert 1e3 * got["self_s"].get(scope, 0.0) == pytest.approx(ms, rel=1e-6), scope
    assert set(got["self_s"]) == set(expected["ms_by_scope"])
    # what found its owner by the fill rule, by owner, and how little of it lies between two phases
    assert set(got["filled_s"]) == set(expected["filled_ms_by_scope"])
    for scope, ms in expected["filled_ms_by_scope"].items():
        assert 1e3 * got["filled_s"][scope] == pytest.approx(ms, rel=1e-6), scope
    assert 1e3 * got["boundary_s"] == pytest.approx(expected["boundary_ms"], rel=1e-6)
    in_scan = sum(1e3 * got["self_s"][k] for k in collect_scopes.TOKENS if k in got["self_s"] and k != "collect_prefill")
    assert got["boundary_s"] * 1e3 < 1e-3 * in_scan  # under 0.1 % of the passes the slice holds; BOUNDARY_MAX is 1 %
    # sampling's and the env step's time is mostly filled in (copies and slices of the token arrays), all of it bracketed
    assert 0.5 < sum(got["filled_s"][k] for k in collect_scopes.OUTSIDE_MODEL) / sum(got["self_s"][k] for k in collect_scopes.OUTSIDE_MODEL) < 0.8
    assert collect_scopes.by_scope(devices, tuple(slice_evidence["window"]), "^jit__rollout_fn") is None


def test_a_traced_run_says_how_far_the_fill_rule_goes(slice_evidence, monkeypatch):
    """The line ``collect_scopes`` of a traced run: of each owner's time the share the fill rule placed, of the
    rollout the share between two phases; over ``BOUNDARY_MAX`` of it the split is not reported."""
    from chipbench import harness, scope_reduce, span_reduce, trace_reduce

    devices = {slice_evidence["device"]: {"ops": slice_evidence["ops"], "modules": slice_evidence["modules"]}}
    monkeypatch.setattr(span_reduce, "window_table", lambda: {"window": tuple(slice_evidence["window"])})
    monkeypatch.setattr(trace_reduce, "newest_xplane", lambda path: path)
    monkeypatch.setattr(scope_reduce, "load_scoped", lambda path: devices)
    noted = {}
    monkeypatch.setattr(harness, "note", lambda **kw: noted.update(kw))
    collect_scopes._this_run.cache_clear()
    evidence = {"trace": {}, "programs": {"collect": "^jit_collect_rollout"}}
    try:
        assert collect_scopes.seconds_per_rollout(evidence, collect_scopes.OUTSIDE_MODEL) == pytest.approx(0.389e-3, rel=1e-2)
        line = noted["collect_scopes"]
        assert 60 < line["filled_pct_by_scope"]["collect_env"] < 70 and line["filled_pct_by_scope"]["collect_score"] < 0.1
        assert 0 < line["boundary_pct"] < 1e-3 and line["boundary_max_pct"] == 1.0
        monkeypatch.setattr(collect_scopes, "BOUNDARY_MAX", 1e-9)
        assert collect_scopes.seconds_per_rollout(evidence, collect_scopes.OUTSIDE_MODEL) is None
    finally:
        collect_scopes._this_run.cache_clear()


def test_readers_on_the_slice(slice_evidence, monkeypatch):
    expected = _load("testdata", "sdar_ep8_loop_collect_slice.expected.json")
    devices = {slice_evidence["device"]: {"ops": slice_evidence["ops"], "modules": slice_evidence["modules"]}}
    split = collect_scopes.by_scope(devices, tuple(slice_evidence["window"]), "^jit_collect_rollout")
    monkeypatch.setattr(collect_scopes, "_this_run", lambda pattern: split if pattern == "^jit_collect_rollout" else None)
    evidence = {**expected["evidence"], "trace": expected["trace_summary"]}
    for name in NEW_READERS:
        reader = importlib.import_module("chipbench.layer_metrics." + name)
        assert reader.read(evidence) == pytest.approx(expected["readers"][name], rel=1e-6), name
        assert (reader.NAME, reader.LAYER, reader.MOVES) == (name, "L3 collect", "env_frames_per_s")
    # (the slice holds a few of the rollout's 1,280 passes, so its share of ALL the rollout's bytes reads far
    # over 100: the reader's arithmetic is held here, the chip's reading of a whole rollout is in PERF.md)


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_return_nothing_on_a_program_without_the_spans(name, monkeypatch):
    """The parent of PR 32: no ``Time/collect_wait`` in its records, its rollout under another name."""
    monkeypatch.setattr(collect_scopes, "_this_run", lambda pattern: None)
    records = [{"train_step": i, "ts": 10.0 * i, "timers_s": {"Time/env_interaction_time": 0.01, "Time/train_time": 0.02}}
               for i in (1, 2, 3)]
    evidence = {"trace": {"programs": {"jit__rollout_fn": {"count": 2, "seconds": 5.0}}}, "telemetry": records,
                "programs": {"collect": "^jit_collect_rollout", "update": "^jit_(update|guarded)"}, "device_kind": "TPU v5 lite",
                "collect": {"rollout_bytes": bytes_collect.sdar_rollout_bytes(PUBLISHED, 12)}}
    assert importlib.import_module("chipbench.layer_metrics." + name).read(evidence) is None
    assert importlib.import_module("chipbench.layer_metrics." + name).read({}) is None


def test_files_name_each_other():
    workload, bench = _load("workloads", "sdar_ep8_loop.json"), _load("..", "BENCHMARK.json")
    traffic = _load("traffic", workload["traffic"] + ".json")
    assert traffic["driver"] == "ppo_loop" and traffic["policy"] in ppo_loop.KINDS
    assert traffic["policy"] in bytes_collect.KINDS
    cell = next(w for w in bench["workloads"] if w["name"] == "sdar_ep8_loop")
    assert {k: cell[k] for k in ("config", "traffic", "chips", "why")} == {k: workload[k] for k in ("config", "traffic", "chips", "why")}
    listed = [m["name"] for m in bench["per_layer"] if "sdar_ep8_loop" in m.get("workloads", [])]
    assert sorted(listed) == sorted(workload["layer_metrics"]) and len(listed) == 9
    for name in workload["layer_metrics"]:
        reader, entry = importlib.import_module("chipbench.layer_metrics." + name), next(m for m in bench["per_layer"] if m["name"] == name)
        assert (reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == (entry["unit"], entry["layer"], entry["source"], entry["moves"])
    assert "sdar_ep8_loop" in next(m for m in bench["end_to_end"] if m["name"] == "env_frames_per_s")["workloads"]
