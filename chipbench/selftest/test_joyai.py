"""The causal language-model cell's arithmetic and data, held to hand counts:
``flops_joyai`` at the tiny and the published shapes, the seeded episodes
against their numpy reference, the new readers on a hand-built evidence dict,
the configuration file against the catalog's numbers, and the comparison that
decides ``correct`` against planted faults."""

import importlib
import json
import os

import numpy as np
import pytest

from chipbench import causal_rollout_fill, flops_joyai

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = flops_joyai.MlaShapes(
    hidden=2048, heads=32, q_rank=1536, kv_rank=512, nope=128, rope=64, v_dim=128, dense_width=7168, expert_width=768,
    shared_experts=1, router_width=256, top_k=8, experts_held=16, layers=5, dense_layers=1, mtp_modules=1, vocab=16160,
    prompt=1024, response=7168, episodes=1)
TINY = flops_joyai.MlaShapes(
    hidden=64, heads=4, q_rank=32, kv_rank=16, nope=16, rope=8, v_dim=16, dense_width=96, expert_width=32,
    shared_experts=1, router_width=8, top_k=2, experts_held=4, layers=3, dense_layers=1, mtp_modules=1, vocab=64,
    prompt=8, response=16, episodes=1)


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def test_published_step_by_hand():
    """ISSUE 30's count, term by term, at S = 8,192."""
    s = PUBLISHED
    assert s.positions == 8192 and s.frames == 7168 and s.blocks == 6 and s.routed_blocks == 5
    assert flops_joyai.expected_assignments(s) == 4096  # 8,192 x 8 x 16 / 256: 256 an expert
    f = flops_joyai.forward_flops(s)
    # the five products: 3,145,728 + 9,437,184 + 1,179,648 + 4,194,304 + 8,388,608 weights
    assert f["projections"] == 6 * 8192 * 2 * 26_345_472 == pytest.approx(6 * 0.4317e12, rel=1e-3)
    assert flops_joyai.visible_pairs(s) == 8192 * 8193 // 2 == 33_558_528
    assert f["attention"] == 6 * 33_558_528 * 32 * 2 * (192 + 128) == pytest.approx(6 * 0.6873e12, rel=1e-3)
    assert f["dense_mlp"] == 8192 * 2 * 3 * 2048 * 7168 == pytest.approx(0.7216e12, rel=1e-3)
    assert f["router"] == 5 * 8192 * 2 * 2048 * 256 and f["shared"] == 5 * 8192 * 2 * 3 * 2048 * 768
    assert f["experts"] == 5 * 4096 * 9_437_184 == pytest.approx(5 * 0.03865e12, rel=1e-3)
    assert f["mtp_projection"] == 8192 * 2 * 4096 * 2048 == pytest.approx(0.1374e12, rel=1e-3)
    assert f["head"] == 7168 * 2 * 2048 * (16161 + 16160) == pytest.approx(2 * 0.4744e12, rel=1e-3)
    assert f["total"] == pytest.approx(9.14e12, rel=2e-3)
    assert flops_joyai.step_flops(s)["total"] == pytest.approx(27.4e12, rel=2e-3)
    # the routed products: 0.42 % of the step a routed block, 2.1 % in all five
    assert flops_joyai.step_flops(s)["experts"] / flops_joyai.step_flops(s)["total"] == pytest.approx(5 * 0.0042, abs=5e-4)
    # the counted assignments take the expectation's place: twice the load, twice the experts' term, nothing else
    skewed, even = flops_joyai.step_flops(s, 2 * 4096), flops_joyai.step_flops(s)
    assert skewed["experts"] == 2 * even["experts"]
    assert skewed["total"] - skewed["experts"] == pytest.approx(even["total"] - even["experts"])


def test_tiny_step_by_hand():
    s = TINY
    f = flops_joyai.forward_flops(s)
    per_position = 64 * 32 + 32 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 4 * 16 * 64
    assert f["projections"] == 4 * 24 * 2 * per_position
    assert f["attention"] == 4 * (24 * 25 // 2) * 2 * 4 * (24 + 16)
    assert f["dense_mlp"] == 24 * 2 * 3 * 64 * 96
    assert f["router"] == 3 * 24 * 2 * 64 * 8 and f["shared"] == 3 * 24 * 2 * 3 * 64 * 32
    assert f["experts"] == 3 * (24 * 2 * 4 / 8) * 2 * 3 * 64 * 32
    assert f["mtp_projection"] == 24 * 2 * 128 * 64 and f["head"] == 16 * 2 * 64 * (65 + 64)


def test_pairs_against_the_reference_mask():
    """The dense causal mask the reference applies, counted."""
    n = TINY.positions
    mask = np.arange(n)[None, :] <= np.arange(n)[:, None]
    assert int(mask.sum()) == flops_joyai.visible_pairs(TINY)


def test_shapes_from_the_files():
    config, traffic = _load("configs", "joyai_flash_ep.json"), _load("traffic", "rollout_p1024_r7168_mb1.json")
    assert flops_joyai.MlaShapes.from_config(config, traffic) == PUBLISHED
    assert flops_joyai.MlaShapes.from_config(config, traffic, tiny=True) == TINY


def test_configuration_file_against_the_catalog():
    """Every number of the catalog row's ``config`` under its key, but the three cut."""
    catalog = {"attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
               "hidden_size": 2048, "intermediate_size": 7168, "kv_lora_rank": 512, "max_position_embeddings": 131072,
               "model_type": "joyai_llm_flash", "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
               "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
               "num_experts_per_tok": 8, "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
               "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
               "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000, "routed_scaling_factor": 2.5,
               "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
               "v_head_dim": 128, "vocab_size": 129280}
    config = _load("configs", "joyai_flash_ep.json")
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in catalog.items():
        if key in config["reduced"]:
            assert config["published"][key] == value and config[key] < value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (5, 16, 16160)
    # the guide's floors: the dense layer and at least four of the layers that follow, 8 experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4 and config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 == 129280 and config["n_routed_experts"] * 16 == config["router_width"] == 256
    assert "16 chips share each layer" in config["deployment"] and "sixteen times" in config["deployment"]
    assert "weights_seed" not in config and "--seed" in config["assumed"]["weights"]
    assert {"mtp_input", "mtp_coef", "e_score_correction_bias", "value_head", "environment", "sampler", "optimizer",
            "attention"} <= set(config["assumed"])
    overrides = dict(o.split("=", 1) for o in config["overrides"])
    assert overrides["algo.mla.experts_held"] == "16" and overrides["algo.mla.n_routed_experts"] == "256"
    assert overrides["env.wrapper.block_length"] == "1" and float(overrides["algo.mtp_coef"]) == config["mtp_coef"] == 0.1
    traffic = _load("traffic", "rollout_p1024_r7168_mb1.json")
    assert (traffic["episodes"], traffic["prompt_len"], traffic["response_len"], traffic["minibatch_episodes"]) == (4, 1024, 7168, 1)


@pytest.mark.parametrize("seed", [0, 7, 2**31 - 1])
def test_rollout_on_the_device_is_the_numpy_reference(seed):
    import jax

    args = (seed, 3, 8, 16, 64)
    got = {k: np.array(v) for k, v in jax.device_get(causal_rollout_fill.fill(*args)).items()}
    assert causal_rollout_fill.check(got, *args) == ""
    assert got["prompt"].shape == (1, 3, 8) and got["actions"].shape == (16, 3, 2) and got["rewards"].shape == (16, 3, 1)
    assert not got["actions"][..., 0].any()  # a block of one: the position is always 0
    assert not got["rewards"][:-1].any() and (0 <= got["rewards"][-1]).all() and (got["rewards"][-1] < 1).all()
    assert got["dones"][-1].all() and not got["dones"][:-1].any()
    # another seed, other data; a wrong value is found
    assert not np.array_equal(causal_rollout_fill.reference(seed + 1, 3, 8, 16, 64)["prompt"], got["prompt"])
    got["actions"][3, 1, 1] += 1
    assert "actions" in causal_rollout_fill.check(got, *args)


def test_rollout_ids_are_roughly_uniform_over_the_whole_slice():
    ref = causal_rollout_fill.reference(5, 4, 1024, 7168, 16160)
    ids = np.concatenate([ref["prompt"].ravel(), ref["actions"][..., 1].ravel()])
    assert ids.min() >= 0 and ids.max() <= 16159 and len(np.unique(ids)) > 0.8 * 16160


NEW_READERS = ("mla_attn_device_ms", "mla_kernel_roofline_pct", "mtp_device_ms")


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_where_nothing_is(name):
    reader = importlib.import_module("chipbench.layer_metrics." + name)
    assert reader.read({}) is None  # the parent's program: no scope, no counter, nothing raised
    assert reader.read({"trace": None, "programs": {"update": "^jit_update"}, "steps_per_call": 4}) is None


def test_new_readers_on_hand_built_evidence(monkeypatch):
    from chipbench import joyai_scopes

    split = {"count": 5.0, "seconds": 4.0, "by_op": {},
             "self_s": {"mla_proj": 0.8, "mla_kernel": 0.4, "moe_experts": 0.2, "mtp_module": 0.05, "unscoped": 0.1},
             "under": {"mtp_module": 0.6}}
    monkeypatch.setattr(joyai_scopes, "_this_run", lambda pattern: split)
    evidence = {"trace": {}, "programs": {"update": "^jit_update"}, "steps_per_call": 4, "device_kind": "TPU v5 lite",
                "mla_kernel_flops_per_step": 0.25 * 197e12 * (0.4 / 20)}
    read = lambda name: importlib.import_module("chipbench.layer_metrics." + name).read(evidence)  # noqa: E731
    assert read("mla_attn_device_ms") == pytest.approx(1e3 * 1.2 / 20)
    assert read("mla_kernel_roofline_pct") == pytest.approx(25.0)
    assert read("mtp_device_ms") == pytest.approx(1e3 * 0.6 / 20)  # everything under the module, not its own ops only


def test_scope_owners_and_the_module_total():
    from chipbench import joyai_scopes, sdar_scopes

    trunk = "jit(update)/jit(main)/while/body/transpose(jvp(MlaMoE))/layer_2/mla_proj/attn/mla_kernel/splash_mqa_dkv"
    module = "jit(update)/jvp(MlaMoE)/mtp_module/mtp/block/mla_proj/attn/dot_general"
    own = "jit(update)/jvp(MlaMoE)/mtp_module/mtp/dot_general"
    devices = {"/device:TPU:0": {
        "modules": [["jit_update(1)", 0.0, 100.0]],
        "ops": [["while.1", 0.0, 95.0, ""],
                ["splash.2", 0.0, 30.0, trunk],  # the innermost scope owns the op
                ["fusion.3", 30.0, 20.0, module],  # the module's block: its inner scope in the split, the module in the total
                ["fusion.4", 50.0, 10.0, own],  # the module's own op
                ["ragged-dot-none.5", 60.0, 5.0, "ragged-dot-none"],
                ["copy.6", 95.0, 3.0, ""]],
    }}
    split = sdar_scopes.by_scope(devices, (0.0, 200.0), "^jit_update", joyai_scopes.TOKENS)
    assert split["self_s"] == {"mla_kernel": pytest.approx(30e-9), "mla_proj": pytest.approx(20e-9),
                               "mtp_module": pytest.approx(10e-9), "moe_experts": pytest.approx(5e-9),
                               "unscoped": pytest.approx(33e-9)}
    total = sdar_scopes.by_scope(devices, (0.0, 200.0), "^jit_update", (joyai_scopes.MODULE,))
    assert total["self_s"]["mtp_module"] == pytest.approx(30e-9)


# ------------------------------------------------ the comparison that decides `correct`
@pytest.fixture(scope="module")
def tiny_cell():
    """The cell's own set-up at the tiny widths (f32, CPU): program, rollout,
    one compared call of the sound update and the reference's reading of it."""
    import jax

    from chipbench import harness
    from chipbench.drivers import causal_lm_train as driver

    workload = _load("workloads", "joyai_ep_train.json")
    ctx = harness.Context(
        name="joyai_ep_train", workload=workload, config=_load("configs", workload["config"] + ".json"),
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
    # every leaf is held: 4 of the model, 12 of the dense block, 17 of each routed block (the MTP module's too), 4 of the module
    assert len(tiny_cell["got"]["moved_leaf_norms"]) == 4 + 12 + 17 * 3 + 4
    assert len(tiny_cell["got"]["losses"]) == 4 and tiny_cell["got"]["mtp_top1_match"] == tiny_cell["ref"]["mtp_top1_match"]


def test_a_state_left_unchanged_is_not_correct(tiny_cell):
    """The same program at a learning rate of 0: every output of the step is
    sound, the state does not move."""
    d, c = tiny_cell["driver"], tiny_cell
    got, _, _ = d.compared_call(c["prog"], c["shapes"], c["data"], c["key"], learning_rate=0.0)
    over = d.judge(d.compare(got, c["ref"]))
    assert set(over) == {"moved_leaf_worst_rel", "returned_shortfall"}, over
    assert over["moved_leaf_worst_rel"] == 1.0 and over["returned_shortfall"] == 1.0


def test_a_state_not_carried_is_not_correct(tiny_cell):
    """Every step moved the state, the call returned what it was given: only
    the driver's own subtraction can tell."""
    d, c = tiny_cell["driver"], tiny_cell
    assert set(d.judge(d.compare({**c["got"], "returned_change": 0.0}, c["ref"]))) == {"returned_shortfall"}


def _rebuilt(tiny_cell):
    from chipbench import program_joyai

    return program_joyai.CausalLmUpdate(tiny_cell["prog"].cfg)


def test_the_mtp_loss_left_out_is_not_correct(tiny_cell, monkeypatch):
    """An update whose policy hands back no auxiliary loss: three losses where
    the reference has four, and the MTP module's leaves get no gradient."""
    from sheeprl_tpu.algos.ppo.causal_lm_policy import CausalLmPolicy

    d, c = tiny_cell["driver"], tiny_cell
    whole = CausalLmPolicy.evaluate_episodes

    def without(self, params, prompt, actions):
        logp, entropy, values, aux = whole(self, params, prompt, actions)
        return logp, entropy, values, {k: v for k, v in aux.items() if k != "aux_loss"}

    monkeypatch.setattr(CausalLmPolicy, "evaluate_episodes", without)
    got, _, _ = d.compared_call(_rebuilt(c), c["shapes"], c["data"], c["key"])
    readings = d.compare(got, c["ref"])
    over = d.judge(readings)
    assert {"loss_worst", "grad_leaf_worst_rel", "moved_leaf_worst_rel"} <= set(over), readings
    assert readings["logp_max_abs"] < 1e-5 and "mtp" in readings["grad_leaf_worst_at"]


def test_a_wrong_mtp_coefficient_is_not_correct(tiny_cell):
    """The MTP term at ten times its weight: every loss reads right, the gradient does not."""
    d, c = tiny_cell["driver"], tiny_cell
    prog = _rebuilt(c)
    prog.policy.aux_coef = 1.0
    prog.update_fn = __import__("sheeprl_tpu.algos.ppo.ppo", fromlist=["ppo"]).make_update_fn(
        prog.runtime, prog.policy, prog.tx, prog.cfg, list(prog.cfg.algo.mlp_keys.encoder))
    got, _, _ = d.compared_call(prog, c["shapes"], c["data"], c["key"])
    readings = d.compare(got, c["ref"])
    assert "grad_leaf_worst_rel" in d.judge(readings) and readings["loss_worst"] < 1e-2, readings


def test_half_of_the_batch_is_not_correct(tiny_cell, monkeypatch):
    """An update whose PPO losses take the first half of the episode's steps
    only: the forward outputs are sound, the losses, gradients and the step are
    not."""
    import sheeprl_tpu.algos.ppo.ppo as ppo

    d, c = tiny_cell["driver"], tiny_cell
    for name in ("policy_loss", "value_loss", "entropy_loss"):
        whole = getattr(ppo, name)
        monkeypatch.setattr(ppo, name, lambda *a, _f=whole: _f(*(x[:, : x.shape[1] // 2] if getattr(x, "ndim", 0) == 2 else x
                                                                  for x in a)))
    got, _, _ = d.compared_call(_rebuilt(c), c["shapes"], c["data"], c["key"])
    assert got["episodes"] == c["got"]["episodes"]
    readings = d.compare(got, c["ref"])
    over = d.judge(readings)
    # (Adam's step of a gradient at half its size is nearly the same step: the gradients and the losses tell)
    assert {"grad_leaf_worst_rel", "loss_worst"} <= set(over), readings
    assert readings["logp_max_abs"] < 1e-5  # what the forward pass produced is the whole batch's


def test_a_wrong_routing_shows_in_the_counts(tiny_cell):
    d, c = tiny_cell["driver"], tiny_cell
    load = c["got"]["load"].copy()
    load[0, 0] += 3
    assert d.judge(d.compare({**c["got"], "load": load}, c["ref"]))["count_mismatch"] == 3
