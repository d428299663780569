"""Virtual-mesh scaling table: PPO, SAC, and DreamerV3 jitted-step
wall-clock at 1/2/4/8 mesh devices (BASELINE.md's "PPO FPS 1->16 chips"
stand-in).

All "devices" here are XLA host-platform devices sharing ONE physical
core, so wall-clock cannot improve with mesh size; what the table
validates is the OVERHEAD of the SPMD path: with the global batch fixed
(strong scaling), total FLOPs are constant, so ideal sharding keeps the
normalized step time at ~1.0 at every mesh size — anything above that is
partitioning/collective overhead that would also tax a real pod.  Run on
real multi-chip hardware the same script measures true scaling.

Writes benchmarks/results/scaling_r3.json and prints one JSON line per
(algo, devices) pair.

Usage:  python benchmarks/bench_scaling.py  [--steps N] [--out PATH]
(spawns nothing; force the virtual mesh with
 XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax

# the scaling mesh is made of virtual host CPU devices
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

MESH_SIZES = (1, 2, 4, 8)


def _time_step(step, carry, n_warm=2, n_steps=10):
    """``step(carry) -> carry`` with every donated buffer threaded through
    the carry — reusing a donated input crashes with 'buffer deleted'."""
    for _ in range(n_warm):
        carry = step(carry)
        jax.block_until_ready(carry)
    tic = time.perf_counter()
    for _ in range(n_steps):
        carry = step(carry)
    jax.block_until_ready(carry)
    return (time.perf_counter() - tic) / n_steps


def bench_ppo(devices: int, steps: int):
    """Full PPO update (GAE + epochs x minibatches) on a `devices`-wide
    data-parallel mesh; global rollout fixed at T=128 x 64 envs."""
    import gymnasium as gym

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import build_ppo_optimizer, make_update_fn
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel.mesh import MeshRuntime

    cfg = compose(
        overrides=[
            "exp=ppo",
            "env=dummy",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
            "env.num_envs=64",
            "algo.rollout_steps=128",
            "algo.per_rank_batch_size=256",
            "algo.update_epochs=2",
        ]
    )
    runtime = MeshRuntime(devices=devices, accelerator="cpu").launch()
    runtime.seed_everything(0)
    obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-1, 1, (64,), np.float32)})
    module, params = build_agent(runtime, (4,), False, cfg, obs_space)
    params = runtime.replicate(params)
    tx = build_ppo_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm, runtime.precision)
    opt_state = runtime.replicate(tx.init(params))
    update_fn = make_update_fn(runtime, module, tx, cfg, ["state"])

    T, E = 128, 64
    rng = np.random.default_rng(0)
    data = {
        "state": jnp.asarray(rng.normal(size=(T, E, 64)).astype(np.float32)),
        "values": jnp.asarray(rng.normal(size=(T, E, 1)).astype(np.float32)),
        "rewards": jnp.asarray(rng.normal(size=(T, E, 1)).astype(np.float32)),
        "dones": jnp.zeros((T, E, 1), jnp.float32),
        "logprobs": jnp.asarray(rng.normal(size=(T, E, 1)).astype(np.float32)),
        "actions": jnp.asarray(rng.integers(0, 4, size=(T, E, 1)).astype(np.float32)),
    }
    data = runtime.shard_batch(data, axis=1)  # DP over the env axis
    next_obs = runtime.shard_batch(
        {"state": jnp.asarray(rng.normal(size=(E, 64)).astype(np.float32))}, axis=0
    )

    def step(carry):
        params, opt_state = carry
        params, opt_state, _ = update_fn(
            params, opt_state, data, next_obs, runtime.next_key(),
            jnp.float32(0.2), jnp.float32(0.0), jnp.float32(3e-4),
        )
        return params, opt_state

    dt = _time_step(step, (params, opt_state), n_steps=steps)
    return dt, T * E


def bench_dv3(devices: int, steps: int):
    """Compact DreamerV3 train step (wm + imagination + actor + critic) on
    a `devices`-wide mesh; global batch fixed at B=16 x T=16 pixels."""
    import gymnasium as gym

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _make_optimizer, make_train_fn
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel.mesh import MeshRuntime

    cfg = compose(
        overrides=[
            "exp=dreamer_v3",
            "env=dummy",
            "env.num_envs=1",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            "algo.per_rank_batch_size=16",
            "algo.per_rank_sequence_length=8",
            "algo.horizon=4",
            "algo.world_model.recurrent_model.recurrent_state_size=128",
            "algo.world_model.representation_model.hidden_size=128",
            "algo.world_model.transition_model.hidden_size=128",
            "algo.world_model.encoder.cnn_channels_multiplier=4",
            "algo.dense_units=128",
            "algo.mlp_layers=1",
        ]
    )
    runtime = MeshRuntime(devices=devices, accelerator="cpu").launch()
    runtime.seed_everything(0)
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    world_model, actor, critic, params = build_agent(runtime, (6,), True, cfg, obs_space)
    params = runtime.replicate(params)
    wm_tx = _make_optimizer(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients)
    actor_tx = _make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
    critic_tx = _make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
    opt_states = runtime.replicate(
        {
            "world_model": wm_tx.init(params["world_model"]),
            "actor": actor_tx.init(params["actor"]),
            "critic": critic_tx.init(params["critic"]),
        }
    )
    moments = runtime.replicate(init_moments())
    train_fn = make_train_fn(
        runtime, world_model, actor, critic, (wm_tx, actor_tx, critic_tx), cfg, True, (6,)
    )
    T, B = 8, 16
    rng = np.random.default_rng(0)
    data = {
        "rgb": jnp.asarray(rng.integers(0, 255, size=(T, B, 64, 64, 3), dtype=np.uint8)),
        "actions": jnp.asarray(rng.normal(size=(T, B, 6)).astype(np.float32)),
        "rewards": jnp.asarray(rng.normal(size=(T, B, 1)).astype(np.float32)),
        "terminated": jnp.zeros((T, B, 1), jnp.float32),
        "truncated": jnp.zeros((T, B, 1), jnp.float32),
        "is_first": jnp.zeros((T, B, 1), jnp.float32),
    }
    data = runtime.shard_batch(data, axis=1)

    def step(carry):
        params, opt_states, moments = carry
        params, opt_states, moments, _ = train_fn(
            params, opt_states, moments, data, runtime.next_key()
        )
        return params, opt_states, moments

    dt = _time_step(step, (params, opt_states, moments), n_steps=steps)
    return dt, T * B


def bench_sac(devices: int, steps: int):
    """SAC scan dispatch (G=8 gradient steps per call, twin critics, alpha
    autotune) on a `devices`-wide mesh; global batch fixed at 8 x 512
    vector rows (the GSPMD path: batch-axis sharding, psum'd grads)."""
    import gymnasium as gym

    from sheeprl_tpu.algos.sac.agent import build_agent
    from sheeprl_tpu.algos.sac.sac import _make_optimizer, make_train_fn
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel.mesh import MeshRuntime

    cfg = compose(
        overrides=[
            "exp=sac",
            "env=dummy",
            "env.id=dummy_continuous",
            "algo.mlp_keys.encoder=[state]",
        ]
    )
    runtime = MeshRuntime(devices=devices, accelerator="cpu").launch()
    runtime.seed_everything(0)
    obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-1, 1, (16,), np.float32)})
    act_space = gym.spaces.Box(-1, 1, (4,), np.float32)
    actor, critic, params, target_entropy = build_agent(runtime, cfg, obs_space, act_space)
    params = runtime.replicate(params)
    actor_tx = _make_optimizer(cfg.algo.actor.optimizer)
    critic_tx = _make_optimizer(cfg.algo.critic.optimizer)
    alpha_tx = _make_optimizer(cfg.algo.alpha.optimizer)
    opt_states = runtime.replicate(
        {
            "actor": actor_tx.init(params["actor"]),
            "critic": critic_tx.init(params["critic"]),
            "alpha": alpha_tx.init(params["log_alpha"]),
        }
    )
    train_fn = make_train_fn(
        runtime, actor, critic, (actor_tx, critic_tx, alpha_tx), cfg, target_entropy
    )
    G, B = 8, 512
    rng = np.random.default_rng(0)
    data = {
        "observations": jnp.asarray(rng.normal(size=(G, B, 16)).astype(np.float32)),
        "next_observations": jnp.asarray(rng.normal(size=(G, B, 16)).astype(np.float32)),
        "actions": jnp.asarray(rng.normal(size=(G, B, 4)).astype(np.float32)),
        "rewards": jnp.asarray(rng.normal(size=(G, B, 1)).astype(np.float32)),
        "terminated": jnp.zeros((G, B, 1), jnp.float32),
    }
    data = runtime.shard_batch(data, axis=1)
    ema_flags = jnp.asarray(np.array([True] + [False] * (G - 1)))

    def step(carry):
        params, opt_states = carry
        params, opt_states, _ = train_fn(params, opt_states, data, runtime.next_key(), ema_flags)
        return params, opt_states

    dt = _time_step(step, (params, opt_states), n_steps=steps)
    return dt, G * B


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument(
        "--out",
        default=os.path.join(os.path.dirname(__file__), "results", "scaling_r3.json"),
    )
    args = ap.parse_args()

    if len(jax.devices()) < max(MESH_SIZES):
        raise RuntimeError(
            f"need {max(MESH_SIZES)} host devices; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={max(MESH_SIZES)}"
        )

    results = {"protocol": (
        "strong scaling on XLA host-platform virtual devices (one physical core): "
        "global batch fixed, normalized step time ~1.0 at every mesh size = "
        "zero-overhead sharding; >1.0 = partition/collective overhead"
    ), "algos": {}}
    for name, fn in (("ppo", bench_ppo), ("sac", bench_sac), ("dreamer_v3", bench_dv3)):
        base = None
        rows = []
        for n in MESH_SIZES:
            dt, global_items = fn(n, args.steps)
            base = base or dt
            row = {
                "devices": n,
                "step_ms": round(dt * 1e3, 1),
                "normalized_vs_1dev": round(dt / base, 3),
                "global_items_per_step": global_items,
            }
            rows.append(row)
            print(json.dumps({"algo": name, **row}), flush=True)
        results["algos"][name] = rows

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
