"""DreamerV3's spans and scopes (ISSUE 24), for the three entry points that
run the one loop (ISSUE 28: ``dreamer_v3``, ``p2e_dv3_exploration`` and
``p2e_dv3_finetuning``): every ``timer`` of the loop lands in
``telemetry.jsonl`` and nests as the loop nests them; the lowered updates
carry the nine ``jax.named_scope`` tokens; and the scopes are metadata only
— with ``jax.named_scope`` made a no-op, the way the code read before them,
the lowered program is the same text and a seeded step gives the same bits."""

import contextlib
import glob
import pathlib
import re

import jax
import numpy as np
import pytest

from sheeprl_tpu.cli import run
from sheeprl_tpu.config import compose, instantiate
from sheeprl_tpu.obs import read_records
from sheeprl_tpu.utils.timer import timer

TINY = [
    "env=dummy", "env.num_envs=1", "env.sync_env=True", "env.capture_video=False",
    "fabric.accelerator=cpu", "fabric.devices=1", "buffer.memmap=False", "seed=0",
    "algo.per_rank_batch_size=2", "algo.per_rank_sequence_length=2", "algo.horizon=3", "algo.dense_units=8",
    "algo.mlp_layers=1", "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8", "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8", "algo.world_model.stochastic_size=4",
    "algo.world_model.discrete_size=4", "algo.world_model.reward_model.bins=15", "algo.critic.bins=15",
    "env.screen_size=16", "algo.mlp_keys.encoder=[state]", "algo.cnn_keys.encoder=[rgb]",
]
P2E = ["algo.ensembles.n=2", "algo.ensembles.dense_units=8", "algo.ensembles.mlp_layers=1"]
ENTRY_POINTS = ("dreamer_v3", "p2e_dv3_exploration", "p2e_dv3_finetuning")
STEP_CHILDREN = ("Time/player_step", "Time/replay_add", "Time/env_step")
OUTSIDE_STEP = ("Time/feed_dispatch", "Time/train_time", "Time/params_refresh", "Time/loss_fetch", "Time/log")
TIMERS = ("Time/env_interaction_time",) + STEP_CHILDREN + OUTSIDE_STEP
TOKENS = ("wm_encoder", "wm_dynamics", "wm_heads", "wm_optim", "bh_imagine", "bh_actor", "bh_critic", "actor_optim",
          "critic_optim")


@pytest.fixture(scope="module")
def loop_records(tmp_path_factory):
    """entry point -> the telemetry of six log intervals of a tiny loop: four
    steps before learning starts (random actions, but for the finetuning),
    then the policy, one update every second step.  The finetuning starts from
    the checkpoint the exploration run leaves."""
    tmp = tmp_path_factory.mktemp("dv3_spans")
    done = {}

    def records_of(exp):
        if exp in done:
            return done[exp]
        extra = [] if exp == "dreamer_v3" else list(P2E)
        if exp == "p2e_dv3_finetuning":
            records_of("p2e_dv3_exploration")
            ckpts = sorted(glob.glob(f"{tmp}/p2e_dv3_exploration/**/ckpt_*.ckpt", recursive=True))
            assert ckpts, "the exploration run left no checkpoint"
            extra.append(f"checkpoint.exploration_ckpt_path={ckpts[-1]}")
        timer.reset()  # a run's last Time/log closes after its last reset: keep it out of the next run's first record
        run(TINY + extra + [
            f"exp={exp}", "metric.log_level=1", "metric.log_every=4", f"metric.logger.root_dir={tmp}/logs_{exp}",
            f"checkpoint.save_last={exp == 'p2e_dv3_exploration'}", "checkpoint.every=100000",
            "algo.learning_starts=4", "algo.total_steps=24", "algo.replay_ratio=0.5", "algo.run_test=False",
            f"root_dir={tmp}/{exp}", "run_name=spans",
        ])
        files = glob.glob(f"{tmp}/{exp}/**/telemetry.jsonl", recursive=True)
        assert files, "the run wrote no telemetry.jsonl"
        done[exp] = read_records(files[0])
        assert len(done[exp]) == 6
        return done[exp]

    return records_of


@pytest.fixture(scope="module", params=ENTRY_POINTS)
def records(request, loop_records):
    return loop_records(request.param)


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
def _tiny_setup(overrides):
    from sheeprl_tpu.utils.env import make_env

    cfg = compose(config_name="config", overrides=overrides)
    runtime = instantiate(dict(cfg.fabric))
    runtime.launch()
    runtime.seed_everything(cfg.seed)
    cfg.env.frame_stack = -1
    env = make_env(cfg, cfg.seed, 0, None, "train")()
    space, n_actions = env.observation_space, int(env.action_space.n)
    env.close()
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
    return cfg, runtime, space, (n_actions,), batch


def _tiny_update():
    """DreamerV3's update, built by hand through the names the benchmark binds to."""
    import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments

    cfg, runtime, space, actions_dim, batch = _tiny_setup(["exp=dreamer_v3"] + TINY)
    world_model, actor, critic, params = dv3.build_agent(runtime, actions_dim, False, cfg, space)
    params = runtime.replicate(runtime.to_param_dtype(params, exclude=("target_critic",)))
    names = ("world_model", "actor", "critic")
    txs = tuple(
        dv3._make_optimizer(cfg.algo[n].optimizer, cfg.algo[n].clip_gradients, runtime.precision) for n in names
    )
    opt_states = runtime.replicate({n: tx.init(params[n]) for n, tx in zip(names, txs)})
    train_fn = dv3.make_train_fn(runtime, world_model, actor, critic, txs, cfg, False, actions_dim)
    return train_fn, (params, opt_states, runtime.replicate(init_moments()), batch, jax.random.PRNGKey(3))


def _tiny_exploration_update():
    """Plan2Explore's exploration update, as its learner builds it."""
    from sheeprl_tpu.algos.p2e_dv3.p2e_dv3_exploration import ExplorationLearner

    cfg, runtime, space, actions_dim, batch = _tiny_setup(["exp=p2e_dv3_exploration"] + TINY + P2E)
    lrn = ExplorationLearner(runtime, cfg, None, space, actions_dim, False)
    state = (lrn.params, lrn.opt_states, lrn.moments_task, lrn.moments_exploration)
    return lrn._train_fn, (*state, batch, jax.random.PRNGKey(3))


def _lowered_and_losses():
    """(lowered text with locations, without them, the losses' bits) of one seeded step."""
    train_fn, args = _tiny_update()
    lowered = train_fn._jitted.lower(*args)
    losses = jax.device_get(train_fn(*args)[3])  # donates the state: args are not used again
    return lowered.as_text(debug_info=True), lowered.as_text(), {k: np.float32(v).tobytes() for k, v in losses.items()}


@pytest.fixture(scope="module")
def scoped():
    return _lowered_and_losses()


@pytest.fixture(scope="module")
def exploration_lowered():
    train_fn, args = _tiny_exploration_update()
    return train_fn._jitted.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("token", TOKENS)
@pytest.mark.parametrize("update", ["dreamer_v3", "p2e_dv3_exploration"])
def test_lowered_update_names_every_phase(request, update, token):
    text = request.getfixturevalue("scoped")[0] if update == "dreamer_v3" else request.getfixturevalue("exploration_lowered")
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


# ------------------------------------------------------------------ one loop
ALGOS = pathlib.Path(__file__).parents[2] / "sheeprl_tpu" / "algos"
FAMILY = ("dreamer_v3/dreamer_v3.py", "p2e_dv3/p2e_dv3_exploration.py", "p2e_dv3/p2e_dv3_finetuning.py")


@pytest.mark.parametrize("call", ["envs.step(", "sequence_batches(", "ckpt_mgr.maybe_checkpoint(", "reconstruction_loss("])
def test_the_family_has_one_loop_and_one_world_model_loss(call):
    holders = [f for f in FAMILY if call in (ALGOS / f).read_text()]
    assert holders == ["dreamer_v3/dreamer_v3.py"]


def test_the_loop_does_not_ask_which_algorithm_runs():
    text = (ALGOS / FAMILY[0]).read_text()
    loop = text[text.index("def train_loop("):]
    assert not re.search(r"actor_type|ensembles|critics_cfg|exploration|finetuning|p2e", loop)


def test_p2e_dv3_prioritized_ring_rides_the_checkpoint(tmp_path):
    """(Here and not in test_algos.py, the file that sets the suite's wall time.)  The exploration phase runs
    dreamer_v3's loop: with a prioritized device ring its checkpoint holds the sequence-start priorities, as DV3's does."""
    from sheeprl_tpu.utils.callback import load_checkpoint

    run(TINY + P2E + [
        "exp=p2e_dv3_exploration", "dry_run=True", "env.num_envs=2", "metric.log_level=1", "checkpoint.save_last=True",
        "algo.learning_starts=0", "algo.per_rank_sequence_length=1", "buffer.device_cache=True", "buffer.prioritized=True",
        "algo.run_test=False",
        f"metric.logger.root_dir={tmp_path}/logs", f"root_dir={tmp_path}/p2edv3per",
    ])
    ckpts = sorted(glob.glob(f"{tmp_path}/p2edv3per/**/ckpt_*.ckpt", recursive=True))
    assert ckpts
    assert "replay_priority" in load_checkpoint(ckpts[-1])
