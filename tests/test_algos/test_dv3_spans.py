"""DreamerV3's spans and scopes (ISSUE 24): every ``timer`` of the loop lands
in ``telemetry.jsonl`` and nests as the loop nests them; the lowered update
carries the nine ``jax.named_scope`` tokens; and the scopes are metadata only
— with ``jax.named_scope`` made a no-op, the way the code read before them,
the lowered program is the same text and a seeded step gives the same bits."""

import contextlib
import glob

import jax
import numpy as np
import pytest

from sheeprl_tpu.cli import run
from sheeprl_tpu.config import compose, instantiate
from sheeprl_tpu.obs import read_records

TINY = [
    "exp=dreamer_v3", "env=dummy", "env.num_envs=1", "env.sync_env=True", "env.capture_video=False",
    "fabric.accelerator=cpu", "fabric.devices=1", "buffer.memmap=False", "seed=0",
    "algo.per_rank_batch_size=2", "algo.per_rank_sequence_length=2", "algo.horizon=3", "algo.dense_units=8",
    "algo.mlp_layers=1", "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8", "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8", "algo.world_model.stochastic_size=4",
    "algo.world_model.discrete_size=4", "algo.world_model.reward_model.bins=15", "algo.critic.bins=15",
    "env.screen_size=16", "algo.mlp_keys.encoder=[state]", "algo.cnn_keys.encoder=[rgb]",
]
STEP_CHILDREN = ("Time/player_step", "Time/replay_add", "Time/env_step")
OUTSIDE_STEP = ("Time/feed_dispatch", "Time/train_time", "Time/params_refresh", "Time/loss_fetch", "Time/log")
TIMERS = ("Time/env_interaction_time",) + STEP_CHILDREN + OUTSIDE_STEP
TOKENS = ("wm_encoder", "wm_dynamics", "wm_heads", "wm_optim", "bh_imagine", "bh_actor", "bh_critic", "actor_optim",
          "critic_optim")


@pytest.fixture(scope="module")
def records(tmp_path_factory):
    """Six log intervals of a tiny loop: four random-action steps, then the
    policy, one update every second step."""
    tmp = tmp_path_factory.mktemp("dv3_spans")
    run(TINY + [
        "metric.log_level=1", "metric.log_every=4", f"metric.logger.root_dir={tmp}/logs", "checkpoint.save_last=False",
        "checkpoint.every=100000", "algo.learning_starts=4", "algo.total_steps=24", "algo.replay_ratio=0.5",
        "algo.run_test=False", f"root_dir={tmp}/dv3", "run_name=spans",
    ])
    files = glob.glob(f"{tmp}/dv3/**/telemetry.jsonl", recursive=True)
    assert files, "the run wrote no telemetry.jsonl"
    got = read_records(files[0])
    assert len(got) == 6
    return got


def _sums(records):
    out = {}
    for r in records:
        for k, v in r["timers_s"].items():
            out[k] = out.get(k, 0.0) + v
    return out


@pytest.mark.parametrize("name", TIMERS)
def test_every_loop_timer_reaches_telemetry(records, name):
    assert _sums(records).get(name, 0.0) > 0.0
    assert any(name in r["timer_percentiles_s"] for r in records)


def test_step_children_fit_inside_env_interaction(records):
    # timers_s is rounded to microseconds, once per timer and record
    for r in records[1:]:
        t = r["timers_s"]
        assert t["Time/player_step"] + t["Time/env_step"] <= t["Time/env_interaction_time"] + 2e-6
        assert sum(t[k] for k in STEP_CHILDREN) <= t["Time/env_interaction_time"] + 3e-6


def test_leaves_fit_inside_the_records_wall(records):
    # the first record holds the compiles; shares are taken over the later ones, as the benchmark takes them
    wall = records[-1]["ts"] - records[0]["ts"]
    sums = _sums(records[1:])
    assert 0.0 < sums["Time/env_interaction_time"] + sum(sums[k] for k in OUTSIDE_STEP) <= wall + 1e-3


def test_log_span_survives_the_reset_it_holds(records):
    # Time/log closes after on_log read the sums and after timer.reset(): it shows one record late
    assert records[0]["timers_s"].get("Time/log", 0.0) == 0.0
    assert all(r["timers_s"]["Time/log"] > 0.0 for r in records[1:])


# ---------------------------------------------------------------- the update
def _tiny_update():
    import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.utils.env import make_env

    cfg = compose(config_name="config", overrides=TINY)
    runtime = instantiate(dict(cfg.fabric))
    runtime.launch()
    runtime.seed_everything(cfg.seed)
    cfg.env.frame_stack = -1
    env = make_env(cfg, cfg.seed, 0, None, "train")()
    space, n_actions = env.observation_space, int(env.action_space.n)
    env.close()
    actions_dim = (n_actions,)
    world_model, actor, critic, params = dv3.build_agent(runtime, actions_dim, False, cfg, space)
    params = runtime.replicate(runtime.to_param_dtype(params, exclude=("target_critic",)))
    names = ("world_model", "actor", "critic")
    txs = tuple(
        dv3._make_optimizer(cfg.algo[n].optimizer, cfg.algo[n].clip_gradients, runtime.precision) for n in names
    )
    opt_states = runtime.replicate({n: tx.init(params[n]) for n, tx in zip(names, txs)})
    train_fn = dv3.make_train_fn(runtime, world_model, actor, critic, txs, cfg, False, actions_dim)
    rng = np.random.default_rng(0)
    T, B = 4, 2
    batch = {
        "rgb": rng.integers(0, 256, (T, B) + space["rgb"].shape, dtype=np.uint8),
        "state": rng.normal(size=(T, B) + space["state"].shape).astype(np.float32),
        "actions": np.eye(n_actions, dtype=np.float32)[rng.integers(0, n_actions, (T, B))],
        "rewards": rng.normal(size=(T, B, 1)).astype(np.float32),
        "terminated": np.zeros((T, B, 1), np.float32),
        "truncated": np.zeros((T, B, 1), np.float32),
        "is_first": np.zeros((T, B, 1), np.float32),
    }
    return train_fn, (params, opt_states, runtime.replicate(init_moments()), batch, jax.random.PRNGKey(3))


def _lowered_and_losses():
    """(lowered text with locations, without them, the losses' bits) of one seeded step."""
    train_fn, args = _tiny_update()
    lowered = train_fn._jitted.lower(*args)
    losses = jax.device_get(train_fn(*args)[3])  # donates the state: args are not used again
    return lowered.as_text(debug_info=True), lowered.as_text(), {k: np.float32(v).tobytes() for k, v in losses.items()}


@pytest.fixture(scope="module")
def scoped():
    return _lowered_and_losses()


@pytest.mark.parametrize("token", TOKENS)
def test_lowered_update_names_every_phase(scoped, token):
    text = scoped[0]
    # plain in the optimizers' paths, inside jvp(..) / transpose(jvp(..)) where a loss is differentiated
    assert f"/{token}/" in text or f"({token})" in text


def test_backward_ops_inherit_their_phase(scoped):
    for token in ("wm_encoder", "wm_dynamics", "wm_heads", "bh_actor", "bh_critic"):
        assert f"transpose(jvp({token}))" in scoped[0], token


@contextlib.contextmanager
def _no_scope(name):  # a context manager and a decorator, as jax.named_scope is
    yield


def test_scopes_are_metadata_only(scoped, monkeypatch):
    """The same step traced with ``jax.named_scope`` a no-op — the program as
    it was before the scopes — lowers to the same text once locations are
    left out, and its losses and gradient norms are the same bits."""
    _, plain_scoped, with_scopes = scoped
    monkeypatch.setattr(jax, "named_scope", _no_scope)
    bare_debug, plain_bare, without = _lowered_and_losses()
    assert not any(token in bare_debug for token in TOKENS)  # the switch reached the program
    assert plain_scoped == plain_bare
    assert with_scopes == without and len(without) == 13
