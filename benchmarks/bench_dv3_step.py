"""DV3-S train-step micro-benchmark on the current default jax platform.

Builds the full single-jit DreamerV3 train step (world model + imagination +
actor + critic + Moments) at S size on Atari-shaped pixels (64x64x3,
batch 16 x seq 64 — the reference's per_rank settings,
reference configs/algo/dreamer_v3.yaml + exp/dreamer_v3_100k_ms_pacman.yaml)
and times it with the fused Pallas GRU off and on.

Usage: python benchmarks/bench_dv3_step.py [--precision bf16-mixed] [--steps 20]
"""

import argparse
import os as _os

# the reference anchor config (dreamer_v3_100k_ms_pacman) is DISCRETE —
# REINFORCE actor loss, no dynamics backprop through imagination
IS_CONTINUOUS = _os.environ.get("SHEEPRL_BENCH_CONTINUOUS", "0") == "1"
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(fused: bool, precision: str):
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _make_optimizer, make_train_fn
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel.mesh import MeshRuntime

    import gymnasium as gym

    cfg = compose(
        overrides=[
            "exp=dreamer_v3",
            "env=dummy",
            "algo=dreamer_v3_S",
            "env.num_envs=1",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            f"algo.world_model.recurrent_model.fused={fused}",
        ]
    )
    runtime = MeshRuntime(devices=1, accelerator="auto", precision=precision).launch()
    runtime.seed_everything(0)
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    actions_dim = (6,)
    world_model, actor, critic, params = build_agent(runtime, actions_dim, IS_CONTINUOUS, cfg, obs_space)
    # same storage/optimizer policy as the training CLI (dreamer_v3.py main):
    # bf16-true stores params in bfloat16 with f32 master weights in the
    # optimizer and keeps the EMA target critic f32
    params = runtime.to_param_dtype(params, exclude=("target_critic",))
    wm_tx = _make_optimizer(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients, precision)
    actor_tx = _make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients, precision)
    critic_tx = _make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients, precision)
    opt_states = {
        "world_model": wm_tx.init(params["world_model"]),
        "actor": actor_tx.init(params["actor"]),
        "critic": critic_tx.init(params["critic"]),
    }
    moments = init_moments()
    train_fn = make_train_fn(
        runtime, world_model, actor, critic, (wm_tx, actor_tx, critic_tx), cfg, IS_CONTINUOUS, actions_dim
    )

    T, B = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
    rng = np.random.default_rng(0)
    data = {
        "rgb": jnp.asarray(rng.integers(0, 255, (T, B, 64, 64, 3)).astype(np.float32)),
        "actions": jnp.asarray(np.eye(6, dtype=np.float32)[rng.integers(0, 6, (T, B))]),
        "rewards": jnp.asarray(rng.normal(size=(T, B, 1)).astype(np.float32)),
        "terminated": jnp.zeros((T, B, 1), jnp.float32),
        "is_first": jnp.zeros((T, B, 1), jnp.float32),
    }
    return runtime, train_fn, params, opt_states, moments, data, (T, B)


def time_variant(
    fused: bool,
    precision: str,
    steps: int,
    cost_analysis: bool = False,
    sync_every_step: bool = True,
):
    """Returns (seconds_per_step, T, B, extras) for the timed configuration.

    ``sync_every_step=False`` times the loop the way the training CLI runs
    it — chained async dispatches with a single trailing host sync, so
    the per-call dispatch latency overlaps the device step.
    ``extras["flops_per_step"]`` (XLA cost analysis of the compiled step,
    for MFU computation) is populated when ``cost_analysis=True``.
    """
    import jax

    runtime, train_fn, params, opt_states, moments, data, (T, B) = build(fused, precision)
    extras = {}
    # Place ALL carried state on the mesh up front: feeding unsharded arrays
    # into the first call and mesh-sharded outputs into the next changes the
    # input avals and forces a full Python retrace per call — which once
    # masqueraded as a "4.9s f32 train step" (real steady state: ~0.12s).
    params = runtime.replicate(params)
    opt_states = runtime.replicate(opt_states)
    moments = runtime.replicate(moments)
    # compile + warmup (2 calls: the second proves the cache is stable)
    for _ in range(2):
        params, opt_states, moments, metrics = train_fn(
            params, opt_states, moments, data, runtime.next_key()
        )
        float(jax.tree_util.tree_leaves(metrics)[0])
    tic = time.perf_counter()
    for _ in range(steps):
        params, opt_states, moments, metrics = train_fn(
            params, opt_states, moments, data, runtime.next_key()
        )
        if sync_every_step:
            float(jax.tree_util.tree_leaves(metrics)[0])
    if not sync_every_step:
        float(jax.tree_util.tree_leaves(metrics)[0])
    dt = (time.perf_counter() - tic) / steps
    frames = T * B / dt
    if cost_analysis:
        from sheeprl_tpu.obs import compiled_flops

        jitted = getattr(train_fn, "_jitted", None)
        if jitted is not None:
            with jax.set_mesh(runtime.mesh):
                compiled = jitted.lower(
                    params, opt_states, moments, data, runtime.next_key()
                ).compile()
            extras["flops_per_step"] = compiled_flops(compiled)
    print(
        f"fused={fused} precision={precision}: {dt * 1e3:.1f} ms/step, "
        f"{frames:,.0f} replayed frames/s (T={T}, B={B})",
        file=sys.stderr,
    )
    return dt, T, B, extras


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--precision", default="bf16-mixed")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--fused", default="both", choices=["both", "true", "false"])
    ap.add_argument(
        "--async-chain",
        action="store_true",
        help="time chained async dispatches with one trailing sync (the way "
        "the training CLI runs; per-step dispatch latency then overlaps "
        "the device step)",
    )
    args = ap.parse_args()
    sync = not args.async_chain
    if args.fused in ("false", "both"):
        base, _, _, _ = time_variant(False, args.precision, args.steps, sync_every_step=sync)
    if args.fused in ("true", "both"):
        fused, _, _, _ = time_variant(True, args.precision, args.steps, sync_every_step=sync)
    if args.fused == "both":
        print(f"speedup fused/unfused: {base / fused:.3f}x")
