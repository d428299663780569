"""DV1/DV2 train-step micro-benchmark on the current default jax platform.

Companion to ``bench_dv3_step.py`` for the other two Dreamer generations:
builds each algo's full single-jit train step at its default model size on
Atari-shaped pixels (64x64x3, discrete 6 actions, the exp yaml's
per_rank batch/sequence: DV1 50x50, DV2 16x50) and times steady-state
dispatch the way the training CLI runs it (chained async dispatches, one
trailing sync).

Round-4 context: the DV3 scan-path optimizations (RNG hoisting, prior
hoisting, remat policies) were propagated to DV1/DV2 mechanically; this
harness produces the chip numbers for that claim.

Usage: python benchmarks/bench_dreamer_family_step.py \
           [--precision bf16-mixed] [--steps 20] [--algos dreamer_v1,dreamer_v2] \
           [--out benchmarks/results/dreamer_family_step.json]
"""

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def time_algo(name: str, precision: str, steps: int, extra_overrides=(), accelerator="auto"):
    import gymnasium as gym
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel.mesh import MeshRuntime

    agent_mod = importlib.import_module(f"sheeprl_tpu.algos.{name}.agent")
    algo_mod = importlib.import_module(f"sheeprl_tpu.algos.{name}.{name}")

    cfg = compose(
        overrides=[
            f"exp={name}",
            "env=dummy",
            "env.num_envs=1",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            *extra_overrides,
        ]
    )
    # pass --accelerator cpu for host-only smoke runs (a chip belongs to
    # one process: a stray bench fails or blocks whatever trains there)
    runtime = MeshRuntime(devices=1, accelerator=accelerator, precision=precision).launch()
    runtime.seed_everything(0)
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    actions_dim = (6,)
    world_model, actor, critic, params = agent_mod.build_agent(
        runtime, actions_dim, False, cfg, obs_space
    )
    params = runtime.to_param_dtype(params, exclude=("target_critic",))
    mk = algo_mod._make_optimizer
    txs = (
        mk(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients, precision),
        mk(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients, precision),
        mk(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients, precision),
    )
    opt_states = {
        "world_model": txs[0].init(params["world_model"]),
        "actor": txs[1].init(params["actor"]),
        "critic": txs[2].init(params["critic"]),
    }
    train_fn = algo_mod.make_train_fn(
        runtime, world_model, actor, critic, txs, cfg, False, actions_dim
    )

    T = int(cfg.algo.per_rank_sequence_length)
    B = int(cfg.algo.per_rank_batch_size)
    rng = np.random.default_rng(0)
    data = {
        "rgb": jnp.asarray(rng.integers(0, 255, (T, B, 64, 64, 3)).astype(np.float32)),
        "actions": jnp.asarray(np.eye(6, dtype=np.float32)[rng.integers(0, 6, (T, B))]),
        "rewards": jnp.asarray(rng.normal(size=(T, B, 1)).astype(np.float32)),
        "terminated": jnp.zeros((T, B, 1), jnp.float32),
        "is_first": jnp.zeros((T, B, 1), jnp.float32),
    }
    params = runtime.replicate(params)
    opt_states = runtime.replicate(opt_states)
    for _ in range(2):  # compile + cache-stability proof
        params, opt_states, metrics = train_fn(params, opt_states, data, runtime.next_key())
        float(jax.tree_util.tree_leaves(metrics)[0])
    tic = time.perf_counter()
    for _ in range(steps):
        params, opt_states, metrics = train_fn(params, opt_states, data, runtime.next_key())
    float(jax.tree_util.tree_leaves(metrics)[0])
    dt = (time.perf_counter() - tic) / steps
    # the actual compute device, NOT jax.default_backend() (which reports
    # the process default even when the runtime pinned compute elsewhere)
    device = next(iter(jax.tree_util.tree_leaves(params)[0].devices()))
    print(
        f"{name} [{device.platform}]: {dt * 1e3:.1f} ms/step, "
        f"{T * B / dt:,.0f} replayed frames/s (T={T}, B={B})",
        file=sys.stderr,
    )
    return {
        "step_ms": round(dt * 1e3, 2),
        "replayed_frames_per_s": round(T * B / dt, 1),
        "T": T,
        "B": B,
        "platform": device.platform,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--precision", default="bf16-mixed")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--algos", default="dreamer_v1,dreamer_v2")
    ap.add_argument("--out", default="benchmarks/results/dreamer_family_step.json")
    ap.add_argument("--accelerator", default="auto", help="cpu forces host-only (smoke tests)")
    ap.add_argument("overrides", nargs="*", help="extra config overrides (smoke tests)")
    args = ap.parse_args()

    import jax

    results = {
        "precision": args.precision,
        "protocol": (
            "single-jit train step, default exp per_rank shapes on 64x64x3 "
            "pixels + discrete(6); steady state over chained async "
            f"dispatches, {args.steps} steps after 2 warmups"
        ),
    }
    for name in args.algos.split(","):
        results[name] = time_algo(
            name.strip(), args.precision, args.steps, tuple(args.overrides), args.accelerator
        )
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
