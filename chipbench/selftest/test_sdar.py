"""The language-model cell's arithmetic and data, held to hand counts:
``flops_sdar`` at the tiny and the published shapes, the seeded trajectories
against their numpy reference, the new readers on a hand-built evidence dict,
and the configuration file against the catalog's numbers."""

import importlib
import json
import os

import numpy as np
import pytest

from chipbench import flops_sdar, rollout_fill

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = flops_sdar.SdarShapes(
    hidden=2048, q_heads=32, kv_heads=4, head_dim=128, router_width=128, top_k=8, experts_held=16, expert_width=768,
    layers=4, vocab=18992, prompt=512, response=1024, block=4, steps=4, episodes=3)
TINY = flops_sdar.SdarShapes(
    hidden=64, q_heads=4, kv_heads=2, head_dim=16, router_width=8, top_k=2, experts_held=4, expert_width=32,
    layers=2, vocab=64, prompt=8, response=16, block=4, steps=4, episodes=2)


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _dense_pairs(s):
    """The mask rule, position by position."""
    n_clean = s.prompt + s.response
    pairs = sum((p // s.block + 1) * s.block for p in range(n_clean))
    for r in range(s.response // s.block):
        pairs += s.steps * s.block * (s.prompt + r * s.block + s.block)
    return pairs


@pytest.mark.parametrize("shapes", [TINY, PUBLISHED], ids=["tiny", "published"])
def test_visible_pairs_by_the_rule(shapes):
    assert flops_sdar.visible_pairs(shapes) == _dense_pairs(shapes)


def test_tiny_pairs_against_the_dense_mask():
    from chipbench.reference import sdar_moe as reference

    mask = reference.dense_mask(reference.packed_layout(TINY.prompt, TINY.response, TINY.block, TINY.steps))
    assert int(mask.sum()) == flops_sdar.visible_pairs(TINY) and mask.shape[0] == TINY.packed_positions == 88


def test_published_step_by_hand():
    s = PUBLISHED
    assert s.packed_positions == 512 + 1024 + 4 * 1024 == 5632 and s.frames == 3072
    positions = 3 * 5632
    assert positions == 16896 and flops_sdar.expected_assignments(s) == 16896  # 16,896 x 8 x 16 / 128: 1,056 an expert
    f = flops_sdar.forward_flops(s)
    # per position and layer: 2 x (2048 x 4096 + 2 x 2048 x 512 + 4096 x 2048) = 37.75 M, router 0.52 M, an expert 9.44 M
    assert f["projections"] == 4 * positions * 2 * 18_874_368
    assert f["router"] == 4 * positions * 2 * 2048 * 128
    assert f["experts"] == 4 * positions * 2 * 3 * 2048 * 768 == 4 * positions * 9_437_184
    # attention: 4 x 32 x 128 a visible pair; 956.2 visible keys a position on average -> 15.67 M a position and layer
    assert flops_sdar.visible_pairs(s) / s.packed_positions == pytest.approx(956.18, abs=0.01)
    assert f["attention"] == 4 * 3 * 4 * 4096 * flops_sdar.visible_pairs(s)
    assert f["attention"] / (4 * positions) == pytest.approx(15.67e6, rel=1e-3)
    assert f["head"] == 3072 * 2 * 2048 * 18993 == pytest.approx(0.239e12, rel=2e-3)
    assert f["total"] == pytest.approx(4.52e12, rel=2e-3)
    assert flops_sdar.step_flops(s)["total"] == pytest.approx(13.57e12, rel=1e-3)
    # the counted assignments take the expectation's place: twice the load, twice the experts' term, nothing else
    skewed = flops_sdar.step_flops(s, 2 * 16896)
    assert skewed["experts"] == 2 * flops_sdar.step_flops(s)["experts"]
    assert skewed["total"] - skewed["experts"] == pytest.approx(flops_sdar.step_flops(s)["total"] - flops_sdar.step_flops(s)["experts"])


def test_tiny_step_by_hand():
    s = TINY
    positions = 2 * 88
    f = flops_sdar.forward_flops(s)
    assert f["projections"] == 2 * positions * 2 * (64 * 64 + 2 * 64 * 32 + 64 * 64)
    assert f["router"] == 2 * positions * 2 * 64 * 8
    assert f["experts"] == 2 * (positions * 2 * 4 / 8) * 2 * 3 * 64 * 32
    assert f["attention"] == 2 * 2 * 4 * 64 * flops_sdar.visible_pairs(s)
    assert f["head"] == 32 * 2 * 64 * 65


def test_shapes_from_the_files():
    config, traffic = _load("configs", "sdar_30b_a3b_ep8.json"), _load("traffic", "rollout_p512_r1024_mb3.json")
    assert flops_sdar.SdarShapes.from_config(config, traffic) == PUBLISHED
    assert flops_sdar.SdarShapes.from_config(config, traffic, tiny=True) == TINY


def test_configuration_file_against_the_catalog():
    """Every number of the catalog row's ``config`` under its key, but the three cut."""
    catalog = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
               "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768, "max_window_layers": 48,
               "mlp_only_layers": [], "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True,
               "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
               "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
               "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
    config = _load("configs", "sdar_30b_a3b_ep8.json")
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in catalog.items():
        if key in config["reduced"]:
            assert config["published"][key] == value and config[key] < value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (4, 16, 18992)
    assert config["vocab_size"] * 8 == 151936 and config["num_experts"] * 8 == config["router_width"] == 128
    assert config["mask_id"] == config["vocab_size"] - 1
    assert "8 chips share each layer" in config["deployment"]
    # the weights' draw is the run's, and the traffic one rollout, as ISSUE 26 names them: no knob of the cell's own
    assert "weights_seed" not in config and "--seed" in config["assumed"]["weights"]
    assert "rollouts" not in _load("traffic", "rollout_p512_r1024_mb3.json")
    # the program's own defaults are the published ones, and the experiment's are this cut
    overrides = dict(o.split("=", 1) for o in config["overrides"])
    assert overrides["algo.sdar.experts_held"] == "16" and overrides["algo.sdar.num_experts"] == "128"


@pytest.mark.parametrize("seed", [0, 7, 2**31 - 1])
def test_rollout_on_the_device_is_the_numpy_reference(seed):
    import jax

    args = (seed, 3, 8, 16, 4, 63)
    got = {k: np.array(v) for k, v in jax.device_get(rollout_fill.fill(*args)).items()}
    assert rollout_fill.check(got, *args) == ""
    assert got["prompt"].shape == (1, 3, 8) and got["actions"].shape == (16, 3, 2) and got["rewards"].shape == (16, 3, 1)
    assert not got["rewards"][:-1].any() and (0 <= got["rewards"][-1]).all() and (got["rewards"][-1] < 1).all()
    assert got["dones"][-1].all() and not got["dones"][:-1].any()
    # another seed, other data; a wrong value is found
    other = rollout_fill.reference(seed + 1, 3, 8, 16, 4, 63)
    assert not np.array_equal(other["prompt"], got["prompt"])
    got["actions"][3, 1, 1] += 1
    assert "actions" in rollout_fill.check(got, *args)


def test_rollout_ids_are_roughly_uniform():
    ref = rollout_fill.reference(5, 12, 512, 1024, 4, 18991)
    ids = np.concatenate([ref["prompt"].ravel(), ref["actions"][..., 1].ravel()])
    assert ids.min() >= 0 and ids.max() <= 18990 and len(np.unique(ids)) > 0.6 * 18991
    order = ref["actions"][..., 0].T.reshape(12, 256, 4)
    assert (np.sort(order, -1) == np.arange(4)).all()
    # each of the 24 orders of a block turns up
    assert len({tuple(o) for o in order.reshape(-1, 4)}) == 24


NEW_READERS = ("moe_device_ms", "moe_dispatch_ms", "blockdiff_attn_device_ms", "moe_load_max_over_mean",
               "moe_experts_roofline_pct", "blockdiff_attn_roofline_pct")


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_where_nothing_is(name):
    reader = importlib.import_module("chipbench.layer_metrics." + name)
    assert reader.read({}) is None  # the parent's program: no scope, no counter, nothing raised
    assert reader.read({"trace": None, "programs": {"update": "^jit_update"}, "steps_per_call": 4}) is None


def test_new_readers_on_hand_built_evidence(monkeypatch):
    from chipbench import sdar_scopes

    split = {"count": 5.0, "seconds": 4.0, "by_op": {},
             "self_s": {"moe_router": 0.1, "moe_dispatch": 0.3, "moe_experts": 1.0, "sdar_attn": 0.8,
                        "blockdiff_attn": 0.4, "unscoped": 0.1}}
    monkeypatch.setattr(sdar_scopes, "_this_run", lambda pattern: split)
    evidence = {"trace": {}, "programs": {"update": "^jit_update"}, "steps_per_call": 4, "device_kind": "TPU v5 lite",
                "moe": {"load_max_over_mean": 1.9, "expert_flops_per_step": 0.5 * 197e12 * (1.0 / 20)},
                "attention_flops_per_step": 0.25 * 197e12 * (0.4 / 20)}
    read = lambda name: importlib.import_module("chipbench.layer_metrics." + name).read(evidence)  # noqa: E731
    assert read("moe_device_ms") == pytest.approx(1e3 * 1.4 / 20)
    assert read("moe_dispatch_ms") == pytest.approx(1e3 * 0.3 / 20)
    assert read("blockdiff_attn_device_ms") == pytest.approx(1e3 * 1.2 / 20)
    assert read("moe_load_max_over_mean") == 1.9
    assert read("moe_experts_roofline_pct") == pytest.approx(50.0)
    assert read("blockdiff_attn_roofline_pct") == pytest.approx(25.0)


def test_scope_owner_by_the_new_tokens():
    from chipbench import scope_reduce, sdar_scopes

    moe = "jit(update)/jit(main)/while/body/transpose(jvp(SdarMoE))/layer_2/moe/moe_experts/ragged_dot_general"
    inner = "jit(update)/jvp(SdarMoE)/layer_0/sdar_attn/attn/blockdiff_attn/splash_mqa_fwd"
    assert scope_reduce.owner(moe) is None  # DreamerV3's tokens are untouched, and nothing of scope_reduce is swapped
    devices = {"/device:TPU:0": {
        "modules": [["jit_update(1)", 0.0, 100.0], ["jit_other(2)", 100.0, 50.0]],
        "ops": [["while.1", 0.0, 95.0, ""],  # the minibatch scan: no path, owns only what nothing nested takes
                ["fusion.1", 0.0, 30.0, "jit(update)/sdar_attn/attn/dot_general"],
                ["splash.2", 30.0, 10.0, inner],  # the innermost scope owns the op
                ["fusion.3", 40.0, 50.0, moe],
                ["copy.4", 50.0, 5.0, ""],  # no path: the op that encloses it in time
                ["copy.5", 95.0, 3.0, ""],
                ["ragged-dot-none.7", 98.0, 2.0, "ragged-dot-none"],  # the compiler's name for a grouped product
                ["fusion.6", 100.0, 50.0, moe]],  # another program's
    }}
    got = sdar_scopes.by_scope(devices, (0.0, 200.0), "^jit_update", sdar_scopes.TOKENS)
    assert got["count"] == 1 and got["seconds"] == pytest.approx(100e-9)
    assert got["self_s"] == {"sdar_attn": pytest.approx(30e-9), "blockdiff_attn": pytest.approx(10e-9),
                             "moe_experts": pytest.approx(52e-9), "unscoped": pytest.approx(8e-9)}
    assert got["by_op"][("moe_experts", "copy.4")] == pytest.approx(5e-9)
    assert sdar_scopes.by_scope(devices, (0.0, 90.0), "^jit_update", sdar_scopes.TOKENS) is None


# ------------------------------------------------ the comparison that decides `correct`
@pytest.fixture(scope="module")
def tiny_cell():
    """The cell's own set-up at the tiny widths (f32, CPU): program, rollout,
    one compared call of the sound update and the reference's reading of it."""
    import jax

    from chipbench import harness
    from chipbench.drivers import sdar_train as driver

    workload = _load("workloads", "sdar_ep8_train.json")
    ctx = harness.Context(
        name="sdar_ep8_train", workload=workload, config=_load("configs", workload["config"] + ".json"),
        traffic=_load("traffic", workload["traffic"] + ".json"), seed=11, seconds=1.0, trace=False, tiny=True,
        t_process_start=0.0, run_dir=os.path.join(harness.OUT, "runs"))
    prog, shapes = driver.build(ctx)
    data, host = driver.make_rollout(ctx, prog, shapes)
    key = jax.random.PRNGKey(ctx.seed)
    got, initial, _ = driver.compared_call(prog, shapes, data, key)
    ref = driver.reference_for(prog, shapes, host, got, initial)
    return {"driver": driver, "ctx": ctx, "prog": prog, "shapes": shapes, "data": data, "host": host, "key": key,
            "got": got, "ref": ref}


def test_sound_update_is_correct(tiny_cell):
    d = tiny_cell["driver"]
    readings = d.compare(tiny_cell["got"], tiny_cell["ref"])
    assert d.judge(readings) == {}, readings
    assert readings["moved_leaf_worst_rel"] < 1e-2 and readings["grad_leaf_worst_rel"] < 1e-4
    assert set(tiny_cell["got"]["moved_leaf_norms"]) == set(tiny_cell["ref"]["moved_leaf_norms"])
    assert len(tiny_cell["got"]["moved_leaf_norms"]) == 4 + 12 * tiny_cell["shapes"].layers  # every leaf is held


def test_a_state_left_unchanged_is_not_correct(tiny_cell):
    """The same program at a learning rate of 0: every output of the step is
    sound, the state does not move."""
    d, c = tiny_cell["driver"], tiny_cell
    got, _, _ = d.compared_call(c["prog"], c["shapes"], c["data"], c["key"], learning_rate=0.0)
    readings = d.compare(got, c["ref"])
    over = d.judge(readings)
    assert set(over) == {"moved_leaf_worst_rel", "returned_shortfall"}, readings
    assert over["moved_leaf_worst_rel"] == 1.0 and over["returned_shortfall"] == 1.0


def test_a_state_not_carried_is_not_correct(tiny_cell):
    """Every step moved the state, the call returned what it was given: only
    the driver's own subtraction can tell."""
    d, c = tiny_cell["driver"], tiny_cell
    over = d.judge(d.compare({**c["got"], "returned_change": 0.0}, c["ref"]))
    assert set(over) == {"returned_shortfall"}
    kept_one = c["got"]["steps_change"][-1]  # of the call's steps only the last reached the returned state
    steps = [kept_one] * 4
    assert "returned_shortfall" in d.judge(d.compare({**c["got"], "returned_change": kept_one, "steps_change": steps}, c["ref"]))


def test_half_of_the_batch_is_not_correct(tiny_cell, monkeypatch):
    """An update whose losses take the first half of the minibatch's episodes
    only: the forward outputs are sound, the losses, gradients and the step are
    not."""
    import sheeprl_tpu.algos.ppo.ppo as ppo
    from chipbench import program_sdar

    d, c = tiny_cell["driver"], tiny_cell
    for name in ("policy_loss", "value_loss", "entropy_loss"):
        whole = getattr(ppo, name)
        monkeypatch.setattr(ppo, name, lambda *a, _f=whole: _f(*(x[: x.shape[0] // 2] if getattr(x, "ndim", 0) == 2 else x
                                                                  for x in a)))
    prog = program_sdar.LmUpdate(c["prog"].cfg)
    got, _, _ = d.compared_call(prog, c["shapes"], c["data"], c["key"])
    assert got["episodes"] == c["got"]["episodes"]
    readings = d.compare(got, c["ref"])
    over = d.judge(readings)
    # (the whole gradient's norm hardly tells at these widths, where the entropy term leads it: the leaves do)
    assert {"grad_leaf_worst_rel", "moved_leaf_worst_rel"} <= set(over), readings
    assert readings["logp_max_abs"] < 1e-5  # what the forward pass produced is the whole batch's


def test_a_wrong_routing_shows_in_the_counts(tiny_cell):
    d, c = tiny_cell["driver"], tiny_cell
    load = c["got"]["load"].copy()
    load[0, 0] += 3
    over = d.judge(d.compare({**c["got"], "load": load}, c["ref"]))
    assert over["count_mismatch"] == 3 and "own_count_mismatch_share" not in over  # 3 of some hundreds: under its limit
