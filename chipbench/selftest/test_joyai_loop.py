"""The cell ``joyai_ep_loop`` (driver ``ppo_loop_causal``, PR 34): the whole
cell at the tiny size on the CPU through the driver its traffic mix names; a
causal cache write one token late planted at the tiny size and read through
that driver's limits; the stored chip readings of the sound runs and of the
control through ``judge``; the three readers this PR brings, by hand and on
the stored slice of a chip run (a program from before the span and the
counters: nothing to read, nothing raised); the files held to each other."""

import contextlib
import gzip
import importlib
import io
import json
import os
import sys

import pytest

from chipbench import bytes_collect, collect_scopes, flops_joyai, reach_collect
from chipbench.drivers import ppo_loop, ppo_loop_causal

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "joyai_ep_loop"
NEW_READERS = ("update_wait_pct", "collect_experts_reached", "collect_counted_roofline_pct")


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _run_tiny(seed, fault=None):
    sys.path.insert(0, HERE)
    bench_run = importlib.import_module("run")  # chipbench/run.py
    out = io.StringIO()
    with contextlib.redirect_stdout(out), (fault or contextlib.nullcontext()):
        rc = bench_run.main(["--workload", CELL, "--tiny", "--seconds", "2", "--seed", str(seed)])
    return rc, [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")]


def _line(lines, key):
    return next(line[key] for line in lines if key in line)


def _limits():
    """What ``ppo_loop.run`` judges by while the driver runs."""
    with ppo_loop_causal.in_ppo_loop():
        return ppo_loop.limits_for("mla_moe")


@pytest.fixture(scope="module")
def tiny_run():
    return _run_tiny(11)


def test_tiny_cell_end_to_end(tiny_run):
    rc, lines = tiny_run
    assert rc == 0 and not any("incorrect" in line for line in lines), [line for line in lines if "incorrect" in line]
    assert _line(lines, "driver") == "ppo_loop_causal"
    result = lines[-1]
    assert result["metrics"]["env_frames_per_s"]["value"] > 0 and result["metrics"]["setup_s"]["value"] > 0
    window = _line(lines, "window")
    assert window["iterations"] >= 2 and window["update_calls"] == window["iterations"]
    assert window["policy_steps"] == window["iterations"] * 4 * 16 == result["attempted"]
    compared = _line(lines, "compare_with_reference")
    assert compared["limits"] == _limits()  # the driver's own stood while it ran
    readings = compared["readings"]
    assert readings["cells"] == 16 and not ppo_loop.judge(readings, _limits())
    assert readings["recorded_logp_max_abs"] < 1e-5 and readings["logp_max_abs"] < 1e-5 and readings["steps_missing"] == 0
    # the program's own counters: a prefill and 16 cached passes a rollout, each over 4 rows that choose 2 of 8 experts
    # in the 2 routed layers of the trunk, 4 of them held
    jaxenv = _line(lines, "telemetry_last")["jaxenv"]
    layer_passes = jaxenv["rollouts"] * 16 * 2
    assert jaxenv["passes"] == jaxenv["rollouts"] * 17 and jaxenv["event_fetches"] == jaxenv["rollouts"]
    assert 0 < jaxenv["experts_reached"] <= layer_passes * 4


def test_a_cache_written_one_token_late_fails_through_the_driver(tiny_run):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    controls = importlib.import_module("ppo_loop_controls")
    sound = _line(tiny_run[1], "compare_with_reference")["readings"]
    _, lines = _run_tiny(11, controls.cache_written_one_block_late())
    shifted = _line(lines, "compare_with_reference")["readings"]
    assert "recorded_logp_mean_abs" in _line(lines, "incorrect")  # (a --tiny run never says "correct": this line says why not)
    assert set(ppo_loop.judge(shifted, _limits())) == {"recorded_logp_mean_abs"}
    for key in ("recorded_logp_mean_abs", "recorded_logp_max_abs", "recorded_value_mean_abs", "recorded_vs_update_logp_max_abs"):
        assert shifted[key] > 1e-3 > 100 * sound[key], (key, shifted[key], sound[key])
    assert shifted["logp_max_abs"] < 1e-5  # the update is untouched by the fault


def test_the_limits_stand_in_ppo_loop_only_while_the_driver_runs():
    theirs, kinds = ppo_loop.limits_for("mla_moe"), dict(ppo_loop.KINDS)
    with ppo_loop_causal.in_ppo_loop():
        ours = ppo_loop.limits_for("mla_moe")
        assert ppo_loop.KINDS["mla_moe"] == ("chipbench.drivers.ppo_loop_causal", *kinds["mla_moe"][1:]) and ppo_loop.KINDS["sdar_moe"] == kinds["sdar_moe"]
    assert ppo_loop.limits_for("mla_moe") == theirs and ppo_loop.KINDS == kinds  # and nothing is left behind
    # the update's limits are the train driver's; of what collection recorded the two means are judged, one at this cell's own
    # reading, and the two worst-cell readings are not (no reading above the sound runs' stands behind a limit on them)
    assert set(theirs) - set(ours) == {"recorded_logp_max_abs", "recorded_value_max_abs"} and set(ours) < set(theirs)
    assert {k for k in ours if ours[k] != theirs[k]} == {"recorded_logp_mean_abs"}


def test_a_leaf_at_float32s_resolution_reads_against_the_floor(monkeypatch):
    """``mtp.block.q_norm`` on seed 3420000011: gains at 1.0 of which three registered one step on the reference's
    side and none on the program's.  ``causal_lm_train.compare`` reads that as a leaf that did not move (1.0)."""
    from chipbench.drivers import causal_lm_train

    ulp = 2.0 ** -24  # the spacing below 1.0
    ref = {"w": 3.2e-3, "q_norm": ulp * 3 ** 0.5, "frozen": 0.0}
    monkeypatch.setattr(causal_lm_train, "compare", lambda got, ref: {"moved_leaf_worst_rel": causal_lm_train._worst_leaf(
        got["moved_leaf_norms"], ref["moved_leaf_norms"])[0], "grad_norm_rel": 0.01})

    def worst(**got):
        r = ppo_loop_causal.compare({"moved_leaf_norms": {**ref, **got}}, {"moved_leaf_norms": ref})
        return r["moved_leaf_worst_rel"], r["moved_leaf_worst_at"], r["grad_norm_rel"]

    assert causal_lm_train.compare({"moved_leaf_norms": {**ref, "q_norm": 0.0}}, {"moved_leaf_norms": ref})["moved_leaf_worst_rel"] == 1.0
    assert worst(q_norm=0.0) == (pytest.approx(ulp * 3 ** 0.5 / 1e-6), "q_norm", 0.01) and worst(q_norm=0.0)[0] < 0.3
    assert worst(frozen=ulp)[0] < 0.3  # and the other way round: a step the reference did not register
    # what the reading is for still reads as it did: a state left unchanged, a matrix that moved half as far, a whole
    # norm leaf (1,536 gains a unit each) that did not move
    assert worst(w=0.0)[:2] == (1.0, "w") and worst(w=1.6e-3)[0] == pytest.approx(0.5)
    whole = {**ref, "q_norm": ulp * 1536 ** 0.5}
    assert ppo_loop_causal.compare({"moved_leaf_norms": {**whole, "q_norm": 0.0}}, {"moved_leaf_norms": whole})["moved_leaf_worst_rel"] == 1.0


def test_judge_on_the_chip_runs_readings():
    stored, limits = _load("testdata", "joyai_ep_loop_readings.json"), _limits()
    sound, late, at_resolution = stored["sound"], stored["cache_shift"], stored["sound_at_the_resolution"]
    assert len(sound) >= 5 and len(late) >= 2
    assert all(set(limits) <= set(r) for r in sound + late + stored["bf16_true"])
    # the nearest precision below the configuration's, in this cell: not correct, by one limit of the update's
    assert all(set(ppo_loop.judge(r, limits)) == {"returned_shortfall"} for r in stored["bf16_true"])
    assert 1.5 * max(r["returned_shortfall"] for r in sound) < limits["returned_shortfall"] < min(r["returned_shortfall"] for r in stored["bf16_true"]) / 1.5
    assert all(not ppo_loop.judge(r, limits) for r in sound)
    # the sound seed that causal_lm_train.compare's moved-leaf reading failed, and nothing else of it (the driver's floor)
    assert all(set(ppo_loop.judge(r, limits)) == {"moved_leaf_worst_rel"} and r["moved_leaf_worst_rel"] == 1.0 for r in at_resolution)
    sound = sound + at_resolution
    assert all(set(ppo_loop.judge(r, limits)) == {"recorded_logp_mean_abs"} for r in late)
    # under ppo_loop's own recorded limits, SDAR's readings, every sound run of this cell fails: why the driver is here
    assert all("recorded_logp_mean_abs" in ppo_loop.judge(r, ppo_loop.limits_for("mla_moe")) for r in sound)
    # the deciding limit stands between the sound runs' largest reading and the control's smallest, with room on both sides
    largest, smallest = max(r["recorded_logp_mean_abs"] for r in sound), min(r["recorded_logp_mean_abs"] for r in late)
    assert 1.3 * largest < limits["recorded_logp_mean_abs"] < smallest / 1.3
    for key in ("recorded_logp_max_abs", "recorded_value_max_abs"):  # why these are not judged: the control reads inside the sound runs' range
        assert key not in limits and min(r[key] for r in sound) < min(r[key] for r in late) <= max(r[key] for r in late) < max(r[key] for r in sound)
    assert 2 * max(r["recorded_value_mean_abs"] for r in sound) < limits["recorded_value_mean_abs"]
    # the limits on the update are causal_lm_train's: the loop's sound runs leave each of them room
    for key in ("loss_worst", "grad_norm_rel", "grad_leaf_worst_rel", "logp_mean_abs", "value_mean_abs", "handed_share", "returned_shortfall"):
        assert 1.5 * max(r[key] for r in sound) < limits[key], key
    assert 1.5 * max(r["moved_leaf_worst_rel"] for r in stored["sound"]) < limits["moved_leaf_worst_rel"]


# ------------------------------------------------------------------ the readers, by hand
PUBLISHED = dict(config=_load("configs", "joyai_flash_ep.json"), traffic=_load("traffic", "loop_4env_p1024_r3584_mb1.json"), tiny=False)


def _records(n=4, reached=28_000, **timers):
    """``n`` records of a steady loop, 5.2 s apart, one rollout each: cumulative counters as the program writes them."""
    base = {"Time/env_interaction_time": 0.002, "Time/collect_wait": 4.16, "Time/train_time": 0.02, "Time/publish": 0.001,
            "Time/loss_fetch": 0.056, "Time/log": 0.004, **timers}
    return [{"train_step": 3 + i, "ts": 1000.0 + 5.2 * i, "timers_s": dict(base),
             "jaxenv": {"rollouts": 3 + i, "event_fetches": 3 + i, "passes": (3 + i) * 3585, "positions": (3 + i) * 18432,
                        "experts_reached": (3 + i) * reached}} for i in range(n)]


def _evidence(**timers):
    return {"telemetry": _records(**timers), "cell": PUBLISHED, "device_kind": "TPU v5 lite", "trace": {},
            "programs": {"collect": "^jit_collect_rollout"}, "collect": {"policy": "mla_moe", "envs": 4, "cached_passes": 3584}}


def _reader(name):
    return importlib.import_module("chipbench.layer_metrics." + name)


def test_update_wait_pct_by_hand():
    # 0.935 s of every 5.2 s between two records
    assert _reader("update_wait_pct").read(_evidence(**{"Time/update_wait": 0.935})) == pytest.approx(100 * 0.935 / 5.2)
    assert _reader("collect_wait_pct").read(_evidence(**{"Time/update_wait": 0.935})) == pytest.approx(100 * 4.162 / 5.2)
    assert _reader("update_wait_pct").read(_evidence()) is None  # a program whose wait for the update lies in Time/publish


def test_experts_reached_by_hand():
    # 3 rollouts between the first record and the last; a rollout is 3,584 cached passes through 4 routed layers
    shapes = flops_joyai.MlaShapes.from_config(PUBLISHED["config"], PUBLISHED["traffic"])
    assert reach_collect.LAYER_PASSES["mla_moe"](shapes) == 3584 * 4
    got = reach_collect.counted(_evidence())
    assert got == {"experts_reached": 84_000, "rollouts": 3, "layer_passes": 14_336, "mean_reached": pytest.approx(28_000 / 14_336)}
    assert _reader("collect_experts_reached").read(_evidence()) == pytest.approx(1.953125)
    # every second rollout's events fetched (metric.fetch_every=2): the counts are over those rollouts alone
    sparse = _evidence()
    for i, r in enumerate(sparse["telemetry"]):
        r["jaxenv"].update(rollouts=6 + 2 * i, event_fetches=3 + i, passes=(6 + 2 * i) * 3585)
    assert _reader("collect_experts_reached").read(sparse) == pytest.approx(1.953125)


def test_counted_roofline_by_hand(monkeypatch):
    evidence = _evidence()
    monkeypatch.setattr(collect_scopes, "seconds_per_rollout", lambda ev, tokens: 4.068 if tokens == collect_scopes.MODEL else None)
    needed = bytes_collect.rollout_bytes("mla_moe", PUBLISHED["config"], PUBLISHED["traffic"], False, 4, reached=28_000 / 14_336)
    assert reach_collect.rollout_bytes(evidence) == needed
    # a pass reads 0.60 GB (0.467 of layers, 0.065 of cache, 0.066 of head): 0.73 ms at 819 GB/s of the 1.135 ms it took
    assert needed["rollout"] / 3584 == pytest.approx(0.598e9, rel=5e-3)
    assert _reader("collect_counted_roofline_pct").read(evidence) == pytest.approx(100 * needed["rollout"] / 819e9 / 4.068)
    assert 60 < _reader("collect_counted_roofline_pct").read(evidence) < 70
    # every held expert taken as read would pass 100 % at this time: what the count is for
    assert 100 * bytes_collect.rollout_bytes("mla_moe", PUBLISHED["config"], PUBLISHED["traffic"], False, 4)["rollout"] / 819e9 / 4.068 > 100


@pytest.fixture
def slice_evidence(monkeypatch):
    """The evidence of ``testdata/sdar_ep8_loop_collect_slice`` (PR 32's program on the chip), its collect split in
    place of this run's trace."""
    expected = _load("testdata", "sdar_ep8_loop_collect_slice.expected.json")
    with gzip.open(os.path.join(HERE, "testdata", "sdar_ep8_loop_collect_slice.json.gz"), "rt") as f:
        stored = json.load(f)
    devices = {stored["device"]: {"ops": stored["ops"], "modules": stored["modules"]}}
    split = collect_scopes.by_scope(devices, tuple(stored["window"]), "^jit_collect_rollout")
    monkeypatch.setattr(collect_scopes, "_this_run", lambda pattern: split)
    return {**expected["evidence"], "trace": expected["trace_summary"]}


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_return_nothing_on_the_stored_slice_and_on_nothing(name, slice_evidence):
    """PR 32's program: no ``Time/update_wait``, no counters, a driver that leaves no ``cell``.  With the counters but
    no ``cell``, and with one record, there is nothing to read either."""
    evidence = slice_evidence
    assert _reader("collect_read_roofline_pct").read(evidence) is not None  # (the slice does read, for PR 32's readers)
    assert _reader(name).read(evidence) is None and _reader(name).read({}) is None
    if name != "update_wait_pct":
        assert _reader(name).read({k: v for k, v in _evidence().items() if k != "cell"}) is None
        assert _reader(name).read({**_evidence(), "telemetry": _records(n=1)}) is None


def test_the_block_diffusion_slice_with_the_counter_reads_nothing_yet(slice_evidence):
    """``sdar_ep8_loop``'s collector counts too, but its (cached pass, routed layer) pairs are not here until a
    ``benchmark`` PR points that cell at the counter: with the counter and a ``cell`` the readers still say nothing."""
    cell = dict(config=_load("configs", "sdar_30b_a3b_ep8.json"), traffic=_load("traffic", "loop_12env_p512_r1024_mb3.json"), tiny=False)
    records = [{**r, "jaxenv": {"rollouts": i, "event_fetches": i, "experts_reached": i * 46_000}} for i, r in enumerate(slice_evidence["telemetry"], 1)]
    evidence = {**slice_evidence, "telemetry": records, "cell": cell}
    assert list(reach_collect.LAYER_PASSES) == ["mla_moe"]
    assert _reader("collect_experts_reached").read(evidence) is None and _reader("collect_counted_roofline_pct").read(evidence) is None


def test_files_name_each_other():
    workload, bench = _load("workloads", CELL + ".json"), _load("..", "BENCHMARK.json")
    traffic = _load("traffic", workload["traffic"] + ".json")
    assert traffic["driver"] == "ppo_loop_causal" and traffic["policy"] == "mla_moe"
    assert traffic["policy"] in ppo_loop.KINDS and traffic["policy"] in bytes_collect.KINDS and traffic["policy"] in reach_collect.LAYER_PASSES
    assert ppo_loop_causal.POLICY == traffic["policy"]
    # the traffic is ISSUE 34's: 4 envs, prompt 1,024, response 3,584, minibatches of one episode, one record an iteration
    assert {k: traffic[k] for k in ("episodes", "prompt_len", "response_len", "minibatch_episodes", "warmup_updates")} == {
        "episodes": 4, "prompt_len": 1024, "response_len": 3584, "minibatch_episodes": 1, "warmup_updates": 2}
    assert {"env.num_envs=4", "env.wrapper.prompt_len=1024", "env.wrapper.response_len=3584", "algo.per_rank_batch_size=1",
            "algo.update_epochs=1", "metric.fetch_every=1", "metric.log_every=14336"} <= set(traffic["overrides"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert bench["workloads"][-1] == cell  # appended
    assert {k: cell[k] for k in ("config", "traffic", "chips", "why")} == {k: workload[k] for k in ("config", "traffic", "chips", "why")}
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert sorted(listed) == sorted(workload["layer_metrics"]) and len(listed) == 11
    assert "collect_read_roofline_pct" not in listed  # it rests on the update's routing of the first rollout: the counted share takes its place
    assert "loop_idle_unspanned_pct" not in listed  # PR 24's reader stays with its twin until test_pending_metrics.py may change (PERF.md section 7)
    for name in workload["layer_metrics"]:
        reader, entry = _reader(name), next(m for m in bench["per_layer"] if m["name"] == name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == (
            name, entry["unit"], entry["layer"], entry["source"], entry["moves"])
    new = [m for m in bench["per_layer"] if m["name"] in NEW_READERS]
    assert new == bench["per_layer"][-3:] and all(m["workloads"] == [CELL] for m in new)
    assert next(m for m in bench["end_to_end"] if m["name"] == "env_frames_per_s")["workloads"][-1] == CELL
    for stored in ("joyai_ep_loop_readings.json",):
        assert os.path.exists(os.path.join(HERE, "testdata", stored))
