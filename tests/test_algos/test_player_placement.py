"""Where the player acts and what its weight refresh copies (ISSUE 27): on the
training device it holds the learner's own arrays, an explicit CPU device
computes the same actions, its reset is one program that compiles at
construction, and the telemetry's ``player`` key says where it is and what
the refreshes copied."""

import glob

import gymnasium as gym
import jax
import numpy as np
import pytest

from sheeprl_tpu.algos.dreamer_v3.agent import PlayerDV3, build_agent
from sheeprl_tpu.cli import run
from sheeprl_tpu.config import compose
from sheeprl_tpu.obs import read_records
from sheeprl_tpu.parallel.mesh import MeshRuntime
from sheeprl_tpu.utils import utils

WIDTHS = [
    "algo.dense_units=8", "algo.mlp_layers=1", "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.representation_model.hidden_size=8", "algo.world_model.transition_model.hidden_size=8",
    "algo.world_model.stochastic_size=4", "algo.world_model.discrete_size=4",
    "algo.world_model.reward_model.bins=15", "algo.critic.bins=15", "env.screen_size=16",
]
NUM_ENVS = 2


@pytest.fixture(scope="module")
def parts():
    cfg = compose(overrides=["exp=dreamer_v3", "env=dummy", "algo.mlp_keys.encoder=[state]",
                             "algo.mlp_keys.decoder=[state]", "algo.cnn_keys.encoder=[]", "algo.cnn_keys.decoder=[]"]
                  + WIDTHS)
    space = gym.spaces.Dict({"state": gym.spaces.Box(-np.inf, np.inf, shape=(4,), dtype=np.float32)})
    runtime = MeshRuntime(devices=1, accelerator="cpu", precision="32-true").launch()
    world_model, actor, _, params = build_agent(runtime, (2,), False, cfg, space)
    params = runtime.replicate(params)
    return runtime, world_model, actor, {"world_model": params["world_model"], "actor": params["actor"]}


def _player(parts, device):
    _, world_model, actor, params = parts
    return PlayerDV3(world_model, actor, params, (2,), NUM_ENVS, 4, 8, discrete_size=4, device=device)


def _obs(fill):
    return {"state": np.full((1, NUM_ENVS, 4), fill, np.float32)}


def _same_leaves(a, b):
    return all(x is y for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b), strict=True))


def test_player_on_the_training_device_holds_the_learners_own_leaves(parts):
    params = parts[3]
    player = _player(parts, None)
    assert _same_leaves(player.params, params)
    fresh = jax.tree_util.tree_map(lambda x: x + 1, params)  # what an update hands back: new arrays
    player.params = fresh
    assert _same_leaves(player.params, fresh)


def test_player_on_an_explicit_cpu_device_computes_the_same(parts):
    shared, placed = _player(parts, None), _player(parts, jax.devices("cpu")[0])
    for i in range(3):
        key = jax.random.PRNGKey(i)
        a, b = shared.get_actions(_obs(0.1 * i), key), placed.get_actions(_obs(0.1 * i), key)
        for x, y in zip(a, b, strict=True):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        if i == 1:
            shared.init_states([1])
            placed.init_states([1])
        for name in ("actions", "recurrent_state", "stochastic_state"):
            np.testing.assert_array_equal(np.asarray(getattr(shared, name)), np.asarray(getattr(placed, name)))


def test_reset_is_masked_and_leaves_the_other_envs_alone(parts):
    player = _player(parts, None)
    initial = [np.asarray(getattr(player, n)) for n in ("actions", "recurrent_state", "stochastic_state")]
    player.get_actions(_obs(0.5), jax.random.PRNGKey(0))
    stepped = [np.asarray(getattr(player, n)) for n in ("actions", "recurrent_state", "stochastic_state")]
    assert not np.array_equal(stepped[1], initial[1])
    player.init_states([1])
    for got, was, fresh in zip((player.actions, player.recurrent_state, player.stochastic_state), stepped, initial):
        np.testing.assert_array_equal(np.asarray(got)[:, 0], was[:, 0])
        np.testing.assert_array_equal(np.asarray(got)[:, 1], fresh[:, 1])
    player.init_states()
    for got, fresh in zip((player.actions, player.recurrent_state, player.stochastic_state), initial):
        np.testing.assert_array_equal(np.asarray(got), fresh)


@pytest.mark.parametrize("device", [None, "cpu"])
def test_nothing_compiles_after_construction_but_the_step_once(parts, device):
    """An episode's end in the middle of a run compiles nothing: the reset's
    programs exist when the player does, and the step sees one layout."""
    player = _player(parts, None if device is None else jax.devices("cpu")[0])
    resets = player._reset._cache_size()
    for i, done in enumerate(([], [0], [0, 1], [1])):
        player.get_actions(_obs(float(i)), jax.random.PRNGKey(i))
        if done:
            player.init_states(done)
    player.init_states()
    assert player._reset._cache_size() == resets
    assert player._step._cache_size() == 1


class _OtherBackend:
    platform = "tpu"


def test_refresh_counts_the_bytes_that_cross_backends(parts, monkeypatch):
    params = parts[3]
    nbytes = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params))
    utils.take_refresh_copied_bytes()
    assert utils.place_player_params(params, None) is params  # shared: a rebinding
    assert utils.place_player_params(params, jax.devices("cpu")[0]) is not None  # same backend: no crossing
    assert utils.take_refresh_copied_bytes() == 0
    monkeypatch.setattr(utils, "transfer_tree", lambda tree, device: tree)  # no second backend here to copy to
    utils.place_player_params(params, _OtherBackend())
    utils.place_player_params(params, _OtherBackend())
    assert utils.take_refresh_copied_bytes() == 2 * nbytes > 0
    assert utils.take_refresh_copied_bytes() == 0  # read and reset, once per record


def test_runtime_reports_where_it_placed_the_player(parts):
    runtime = MeshRuntime(devices=1, accelerator="cpu").launch()
    assert runtime.player_telemetry() is None
    params = parts[3]
    assert runtime.player_device(params) is None
    utils.take_refresh_copied_bytes()
    assert runtime.player_telemetry() == {
        "device": "cpu:0",
        "param_bytes": sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)),
        "refresh_copied_bytes": 0,
    }


def test_loop_writes_the_player_key_with_nothing_copied_when_shared(tmp_path):
    run([
        "exp=dreamer_v3", "env=dummy", "env.num_envs=1", "env.sync_env=True", "env.capture_video=False",
        "fabric.accelerator=cpu", "fabric.devices=1", "buffer.memmap=False", "seed=0", "algo.per_rank_batch_size=2",
        "algo.per_rank_sequence_length=2", "algo.horizon=3", "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.mlp_keys.encoder=[state]", "algo.cnn_keys.encoder=[rgb]", "metric.log_level=1", "metric.log_every=4",
        f"metric.logger.root_dir={tmp_path}/logs", "checkpoint.save_last=False", "checkpoint.every=100000",
        "algo.learning_starts=4", "algo.total_steps=16", "algo.replay_ratio=0.5", "algo.run_test=False",
        f"root_dir={tmp_path}/dv3", "run_name=player",
    ] + WIDTHS)
    records = read_records(glob.glob(f"{tmp_path}/dv3/**/telemetry.jsonl", recursive=True)[0])
    assert len(records) == 4 and records[-1]["train_step"] > 0
    for record in records:
        assert record["player"]["device"] == "cpu:0"
        assert record["player"]["param_bytes"] > 0
        assert record["player"]["refresh_copied_bytes"] == 0
