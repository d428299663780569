"""The Qwen3-Next cell's arithmetic and files, held to hand counts:
``flops_qwen3_next`` at the published shapes (the delta rule's work counted
chunk by chunk against a direct count of the same products), the configuration
file against the catalog's numbers and the other files of the cell, the new
readers without a trace and on a hand-built one, and the comparison that
decides ``correct`` against planted faults: readings of a state left unchanged
and the like, and the three faults of the Gated DeltaNet layers
(``benchmarks/gdn_fault_readings.py``) planted in the program at the tiny size."""

import importlib
import importlib.util
import json
import os

import numpy as np
import pytest

from chipbench import flops_qwen3_next as F

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "qwen3next_ep16_train"
PUBLISHED = F.Qwen3NextShapes(
    hidden=2048, heads=16, kv_heads=2, head_dim=256, key_heads=16, value_heads=32, d_k=128, d_v=128, conv_kernel=4,
    interval=4, layers=4, expert_width=512, shared_width=512, router_width=512, top_k=10, experts_held=32, vocab=18992,
    prompt=1024, response=7168, episodes=1)
# the catalog's numbers (model-configs guide, architectures.jsonl, row Qwen3-Next-80B-A3B-Instruct) that the cut keeps
CATALOG = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_size": 2048,
           "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
           "linear_num_value_heads": 32, "linear_value_head_dim": 128, "max_position_embeddings": 262144,
           "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16, "num_experts_per_tok": 10,
           "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_theta": 10000000,
           "shared_expert_intermediate_size": 512, "tie_word_embeddings": False, "use_sliding_window": False}
NEW_READERS = ("gdn_device_ms", "gdn_scan_device_ms", "gdn_scan_roofline_pct", "gated_attn_device_ms")


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def test_published_step_by_hand():
    """The step's count, term by term, at S = 8,192."""
    s = PUBLISHED
    assert s.positions == 8192 and s.frames == 7168 and (s.linear_layers, s.full_layers, s.routed_blocks) == (3, 1, 4)
    assert s.channels == 8192 and F.expected_assignments(s) == 5120  # 8,192 x 10 x 32 / 512: 160 an expert
    f = F.forward_flops(s)
    assert f["gdn_projections"] == 3 * 8192 * (2 * 2048 * (12288 + 64) + 2 * 4096 * 2048 + 2 * 4 * 8192)
    assert f["attn_projections"] == 8192 * (2 * 2048 * 256 * (32 + 4) + 2 * 4096 * 2048)
    assert f["attention"] == 8192 * 8193 // 2 * 2 * 16 * 512
    assert f["router"] == 4 * 8192 * 2 * 2048 * 512 and f["shared"] == 4 * 8192 * (2 * 3 * 2048 * 512 + 2 * 2048)
    assert f["experts"] == 4 * 5120 * 2 * 3 * 2048 * 512 and f["head"] == 7168 * 2 * 2048 * 18993
    assert f["total"] / 8192 == pytest.approx(453.9e6, rel=1e-3)  # MFLOP a position, forward
    assert F.step_flops(s)["total"] == pytest.approx(11.155e12, rel=1e-3)
    # the linear layers hold over half of the work, the routed experts a few per cent
    assert (f["gdn_projections"] + f["gdn_scan"]) / f["total"] == pytest.approx(0.473, abs=5e-3)
    assert f["experts"] / f["total"] == pytest.approx(0.035, abs=2e-3)


def test_the_delta_rules_work_by_direct_count():
    """The chunked form's products counted one by one for one chunk and one key head with its two value
    heads, against ``gdn_forward_flops``; its bytes by hand."""
    s, c = PUBLISHED, F.CHUNK
    lower_strict, lower = c * (c - 1) // 2, c * (c + 1) // 2
    key_head = 2 * 128 * (lower_strict + lower)  # K K^T below the diagonal, Q K^T on and below
    value_head = (c * (c - 1) * 256  # the unit triangular solve with dv + dk right-hand columns
                  + 3 * 2 * c * 128 * 128  # W S, Q S, K^T V'
                  + 2 * 128 * lower)  # (Q K^T * Gamma) V'
    assert F.gdn_forward_flops(s, c) == 16 * key_head + 32 * value_head
    assert F.gdn_forward_flops(s) == 128 * F.gdn_forward_flops(s, c) and F.gdn_forward_flops(s, c + 1) == 2 * F.gdn_forward_flops(s, c)
    assert F.gdn_forward_bytes(s) == 4 * (8192 * (2 * 2048 + 2 * 4096 + 64) + 128 * 32 * 128 * 128)
    step = F.gdn_step(s)
    assert step["flops"] == 4 * 3 * F.gdn_forward_flops(s) and step["bytes"] == 4 * 3 * F.gdn_forward_bytes(s)
    # bytes-bound on a v5e: under the ridge of 240 FLOP a byte
    assert step["flops"] / step["bytes"] < 240
    assert F.roofline_seconds(step["flops"], step["bytes"], 197e12, 819e9) == pytest.approx(step["bytes"] / 819e9)


def test_files_name_each_other():
    workload, bench = _load("workloads", CELL + ".json"), _load("..", "BENCHMARK.json")
    config, traffic = _load("configs", workload["config"] + ".json"), _load("traffic", workload["traffic"] + ".json")
    assert traffic["driver"] == "qwen3_next_train" and workload["chips"] == 1
    assert {k: config[k] for k in CATALOG} == CATALOG  # every number the cut keeps, under its own key
    assert (config["num_hidden_layers"], config["num_experts"], config["router_width"], config["vocab_size"]) == (4, 32, 512, 18992)
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert F.Qwen3NextShapes.from_config(config, traffic) == PUBLISHED
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    conf = next(c for c in bench["configs"] if c["name"] == workload["config"])
    assert conf["reduced"] == config["reduced"] and set(config["reduced"]) == set(config["published"])
    assert {k: cell[k] for k in ("config", "traffic", "chips", "why")} == {k: workload[k] for k in ("config", "traffic", "chips", "why")}
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert sorted(listed) == sorted(workload["layer_metrics"]) and len(listed) == 12
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "train_frames_per_s")["workloads"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_read_nothing_without_a_trace(name):
    reader = importlib.import_module("chipbench.layer_metrics." + name)
    assert reader.read({"programs": {"update": "^jit_update"}, "steps_per_call": 4, "gdn_step": {"flops": 1e12, "bytes": 1e10}}) is None
    assert (reader.NAME, reader.LAYER, reader.SOURCE, reader.MOVES) == (name, "L6 kernels", "device_trace", "train_frames_per_s")


def test_readers_on_hand_built_scopes(monkeypatch):
    """Self seconds by scope, per minibatch step: the three mixer scopes sum, the scan alone, its roofline
    against the larger bound, the kernel alone."""
    from chipbench import qwen3_next_scopes

    got = {"count": 2.0, "seconds": 1.0, "self_s": {"gdn_proj": 0.08, "gdn_scan": 0.16, "gdn_out": 0.04,
                                                     "gated_attn_kernel": 0.024, "moe_experts": 0.5}}
    monkeypatch.setattr(qwen3_next_scopes, "_this_run", lambda pattern: got)
    evidence = {"trace": {}, "programs": {"update": "^jit_update"}, "steps_per_call": 4, "device_kind": "TPU v5 lite",
                "gdn_step": {"flops": 197e12 * 1e-3, "bytes": 819e9 * 4e-3}}
    read = {name: importlib.import_module("chipbench.layer_metrics." + name).read(evidence) for name in NEW_READERS}
    assert read["gdn_device_ms"] == pytest.approx(35.0) and read["gdn_scan_device_ms"] == pytest.approx(20.0)
    assert read["gated_attn_device_ms"] == pytest.approx(3.0)
    assert read["gdn_scan_roofline_pct"] == pytest.approx(100.0 * 4e-3 / 20e-3)  # the bytes' 4 ms over 20 ms


def _sound():
    from chipbench.drivers import qwen3_next_train

    return {k: 0.0 for k in qwen3_next_train.LIMITS} | {"returned_shortfall": 0.2, "returned_excess": -0.2}


@pytest.mark.parametrize("fault,readings", [
    ("state returned unchanged", {"returned_shortfall": 1.0, "returned_excess": -1.0}),
    ("one step of four kept", {"returned_shortfall": 0.75, "returned_excess": -0.75}),
    ("a loss term missing", {"loss_worst": float("inf")}),
    ("choices and counts differ at a hundred layer-positions", {"count_mismatch": 100}),
    ("two of four steps left the state as it was", {"steps_missing": 2}),
    ("an episode taken twice, another never", {"episodes_uncovered": 2}),
])
def test_judge_refuses_planted_readings(fault, readings):
    from chipbench.drivers import qwen3_next_train

    assert qwen3_next_train.judge(_sound()) == {}
    assert set(qwen3_next_train.judge({**_sound(), **readings})) == {k for k in readings if not k.endswith("excess")}, fault


# ------------------------------------------------ the comparison that decides `correct`, at the tiny size
@pytest.fixture(scope="module")
def tiny_cell():
    """The cell's own set-up at the tiny widths (f32, CPU): program, rollout, one compared call of the sound
    update and the reference's reading of it."""
    import jax

    from chipbench import harness
    from chipbench.drivers import qwen3_next_train as driver

    workload = _load("workloads", CELL + ".json")
    ctx = harness.Context(
        name=CELL, workload=workload, config=_load("configs", workload["config"] + ".json"),
        traffic=_load("traffic", workload["traffic"] + ".json"), seed=13, seconds=1.0, trace=False, tiny=True,
        t_process_start=0.0, run_dir=os.path.join(harness.OUT, "runs"))
    prog, shapes = driver.build(ctx)
    data, host = driver.make_rollout(ctx, prog, shapes)
    key = jax.random.PRNGKey(ctx.seed)
    got, initial, _ = driver.compared_call(prog, shapes, data, key)
    ref = driver.reference_for(prog, host, got, initial)
    return {"driver": driver, "prog": prog, "shapes": shapes, "data": data, "host": host, "key": key, "got": got,
            "ref": ref, "initial": initial}


def test_sound_update_is_correct(tiny_cell):
    d = tiny_cell["driver"]
    readings = {**d.compare(tiny_cell["got"], tiny_cell["ref"]), **d.coverage(tiny_cell["got"], 4, 4)}
    assert d.judge(readings) == {}, readings
    assert readings["grad_leaf_worst_rel"] < 1e-3 and readings["logp_max_abs"] < 1e-4
    # every leaf is held: 4 of the model; a linear layer's 7 mixer leaves, a full layer's 6, and 10 more a layer
    assert len(tiny_cell["got"]["moved_leaf_norms"]) == 4 + 3 * (7 + 10) + (6 + 10)


def _faults():
    spec = importlib.util.spec_from_file_location("gdn_fault_readings", os.path.join(HERE, "..", "benchmarks", "gdn_fault_readings.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("fault", ["no_decay", "additive", "no_gate"])
def test_gdn_faults_are_not_correct(tiny_cell, fault):
    """Each of the three faults planted in the program (not in the reference): the judge refuses
    the compared call."""
    from chipbench import program_qwen3_next

    d, c = tiny_cell["driver"], tiny_cell
    with getattr(_faults(), fault)():
        prog = program_qwen3_next.CausalLmUpdate(c["prog"].cfg)
        got, _, _ = d.compared_call(prog, c["shapes"], c["data"], c["key"])
    assert d.judge(d.compare(got, c["ref"])), fault


def test_the_additive_rule_is_the_delta_rule_without_its_read():
    """The planted ``additive`` rule against its own definition: with no write it is the sound rule (the read is
    multiplied by a write of 0), with writes it differs."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.ops.gated_delta_rule import gated_delta_rule

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k = (jax.random.normal(kk, (1, 300, 2, 8)) / 8 for kk in ks[:2])
    v, g = jax.random.normal(ks[2], (1, 300, 4, 8)), -jnp.abs(jax.random.normal(ks[3], (1, 300, 4)))
    rule = _faults().additive_rule
    for beta, same in ((jnp.zeros((1, 300, 4)), True), (jax.nn.sigmoid(jax.random.normal(ks[4], (1, 300, 4))), False)):
        a, b = rule(q, k, v, g, beta)[0], gated_delta_rule(q, k, v, g, beta)[0]
        assert bool(np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)) == same


def _chip_readings():
    return _load("testdata", CELL + "_readings.json")["runs"]


@pytest.mark.parametrize("run", ["sound_2500000003", "sound_3100000037", "sound_3200000041"])
def test_sound_chip_readings_are_within_the_limits(run):
    """The readings the program gave on the chip at the cell's size, held to the driver's limits as they stand."""
    from chipbench.drivers import qwen3_next_train

    assert qwen3_next_train.judge(_chip_readings()[run]) == {}, run


@pytest.mark.parametrize("run,by", [
    ("bf16_true_3400000057", {"returned_shortfall"}),
    ("additive_3600000071", {"handed_share", "own_count_mismatch_share", "logp_mean_abs", "logp_max_abs",
                             "value_mean_abs", "value_max_abs", "loss_worst", "grad_leaf_worst_rel",
                             "moved_leaf_worst_rel", "count_mismatch"}),
    ("no_gate_3600000071", {"handed_share", "own_count_mismatch_share", "logp_mean_abs", "logp_max_abs",
                            "value_mean_abs", "value_max_abs", "loss_worst", "grad_norm_rel", "grad_leaf_worst_rel",
                            "moved_leaf_worst_rel", "count_mismatch"}),
])
def test_control_and_fault_chip_readings_are_not_correct(run, by):
    """The control (the program at bf16-true) and the faults planted at the cell's size, as the chip read them:
    each is not correct under the driver's limits, and by the readings named."""
    from chipbench.drivers import qwen3_next_train

    assert set(qwen3_next_train.judge(_chip_readings()[run])) == by, run
