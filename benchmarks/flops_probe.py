"""Per-device compiled-FLOPs probe for the six Dreamer-family train fns.

Correctness tests CANNOT catch silent replication: a sharded program that
GSPMD decides to all-gather-and-replicate still computes the right answer,
just N times over (round 3 shipped exactly that bug in PPO's epoch shuffle
and the Dreamers' imagination flatten).  What does catch it is XLA's own
cost analysis of the compiled per-device program: with the global batch
fixed, an honestly sharded step's per-device FLOPs must drop ~1/N with
mesh size N, while a silently replicated one stays ~1.0.

This probe lowers + compiles each Dreamer-family train fn (DV1, DV2, DV3,
P2E-DV1/DV2/DV3 exploration) at mesh sizes 1 and 8 on the virtual CPU
platform and records flops(8)/flops(1) per device.  Nothing is executed —
only compiled — so it runs anywhere in ~minutes.  A trimmed version gates
CI in tests/test_parallel/test_flops_probe.py.

Usage:  python benchmarks/flops_probe.py [--out benchmarks/results/scaling_r4_flops.json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax

jax.config.update("jax_platforms", "cpu")

import gymnasium as gym
import jax.numpy as jnp
import numpy as np

# tiny-but-structurally-faithful sizes: scans, heads, ensembles and both
# optimizers all present; compile time stays CI-friendly
_COMMON = [
    "env=dummy",
    "env.num_envs=1",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.mlp_keys.encoder=[]",
    "algo.per_rank_batch_size=64",
    "algo.per_rank_sequence_length=8",
    "algo.horizon=4",
    "algo.dense_units=64",
    "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=4",
]
_RSSM_SMALL = [
    "algo.world_model.recurrent_model.recurrent_state_size=64",
    "algo.world_model.representation_model.hidden_size=64",
    "algo.world_model.transition_model.hidden_size=64",
]
T, B = 8, 64
ACTIONS_DIM = (6,)


def _data(is_first: bool):
    rng = np.random.default_rng(0)
    d = {
        "rgb": jnp.asarray(rng.integers(0, 255, size=(T, B, 64, 64, 3)).astype(np.float32)),
        "actions": jnp.asarray(rng.normal(size=(T, B, 6)).astype(np.float32)),
        "rewards": jnp.asarray(rng.normal(size=(T, B, 1)).astype(np.float32)),
        "terminated": jnp.zeros((T, B, 1), jnp.float32),
        "truncated": jnp.zeros((T, B, 1), jnp.float32),
    }
    if is_first:
        d["is_first"] = jnp.zeros((T, B, 1), jnp.float32)
    return d


def _runtime(devices: int):
    from sheeprl_tpu.parallel.mesh import MeshRuntime

    rt = MeshRuntime(devices=devices, accelerator="cpu").launch()
    rt.seed_everything(0)
    return rt


def _obs_space():
    return gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})


def _compiled_flops(runtime, train_fn, args):
    from sheeprl_tpu.obs import compiled_flops

    with jax.set_mesh(runtime.mesh):
        compiled = train_fn._jitted.lower(*args).compile()
    return compiled_flops(compiled) or 0.0


def probe_dv(version: int, devices: int) -> float:
    """DV1/DV2/DV3 (version in {1,2,3}) per-device compiled flops."""
    mod = __import__(f"sheeprl_tpu.algos.dreamer_v{version}.dreamer_v{version}", fromlist=["x"])
    agent_mod = __import__(f"sheeprl_tpu.algos.dreamer_v{version}.agent", fromlist=["x"])
    from sheeprl_tpu.config import compose

    cfg = compose(overrides=[f"exp=dreamer_v{version}"] + _COMMON + _RSSM_SMALL)
    runtime = _runtime(devices)
    world_model, actor, critic, params = agent_mod.build_agent(
        runtime, ACTIONS_DIM, True, cfg, _obs_space()
    )
    params = runtime.replicate(params)
    txs = tuple(
        mod._make_optimizer(getattr(cfg.algo, k).optimizer, getattr(cfg.algo, k).clip_gradients)
        for k in ("world_model", "actor", "critic")
    )
    opt_states = runtime.replicate(
        {k: tx.init(params[k]) for k, tx in zip(("world_model", "actor", "critic"), txs)}
    )
    train_fn = mod.make_train_fn(
        runtime, world_model, actor, critic, txs, cfg, True, ACTIONS_DIM
    )
    data = runtime.shard_batch(_data(is_first=version >= 2), axis=1)
    if version == 3:
        from sheeprl_tpu.algos.dreamer_v3.utils import init_moments

        moments = runtime.replicate(init_moments())
        args = (params, opt_states, moments, data, runtime.next_key())
    else:
        args = (params, opt_states, data, runtime.next_key())
    return _compiled_flops(runtime, train_fn, args)


def probe_p2e(version: int, devices: int) -> float:
    """P2E-DV1/DV2/DV3 exploration per-device compiled flops."""
    mod = __import__(
        f"sheeprl_tpu.algos.p2e_dv{version}.p2e_dv{version}_exploration", fromlist=["x"]
    )
    agent_mod = __import__(f"sheeprl_tpu.algos.p2e_dv{version}.agent", fromlist=["x"])
    from sheeprl_tpu.config import compose

    cfg = compose(overrides=[f"exp=p2e_dv{version}_exploration"] + _COMMON + _RSSM_SMALL)
    runtime = _runtime(devices)
    if version == 3:
        world_model, actor, critic, ensemble, critics_cfg, params = agent_mod.build_agent(
            runtime, ACTIONS_DIM, True, cfg, _obs_space()
        )
        params = runtime.replicate(params)
        mk = mod._make_optimizer
        wm_tx = mk(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients)
        ens_tx = mk(cfg.algo.ensembles.optimizer, cfg.algo.ensembles.clip_gradients)
        a_t = mk(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
        c_t = mk(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
        a_e = mk(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
        c_es = {
            name: mk(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
            for name in critics_cfg
        }
        opt_states = runtime.replicate(
            {
                "world_model": wm_tx.init(params["world_model"]),
                "ensembles": ens_tx.init(params["ensembles"]),
                "actor_task": a_t.init(params["actor_task"]),
                "critic_task": c_t.init(params["critic_task"]),
                "actor_exploration": a_e.init(params["actor_exploration"]),
                "critics_exploration": {
                    name: c_es[name].init(params["critics_exploration"][name]["module"])
                    for name in critics_cfg
                },
            }
        )
        train_fn = mod.make_train_fn(
            runtime, world_model, actor, critic, ensemble, critics_cfg,
            (wm_tx, ens_tx, a_t, c_t, a_e, c_es), cfg, True, ACTIONS_DIM,
        )
        from sheeprl_tpu.algos.dreamer_v3.utils import init_moments

        moments_task = runtime.replicate(init_moments())
        moments_expl = runtime.replicate({name: init_moments() for name in critics_cfg})
        data = runtime.shard_batch(_data(is_first=True), axis=1)
        args = (params, opt_states, moments_task, moments_expl, data, runtime.next_key())
    else:
        world_model, actor, critic, ensemble, params = agent_mod.build_agent(
            runtime, ACTIONS_DIM, True, cfg, _obs_space()
        )
        params = runtime.replicate(params)
        mk = mod._make_optimizer
        wm_tx = mk(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients)
        ens_tx = mk(cfg.algo.ensembles.optimizer, cfg.algo.ensembles.clip_gradients)
        a_t = mk(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
        c_t = mk(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
        a_e = mk(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
        c_e = mk(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
        opt_states = runtime.replicate(
            {
                "world_model": wm_tx.init(params["world_model"]),
                "ensembles": ens_tx.init(params["ensembles"]),
                "actor_task": a_t.init(params["actor_task"]),
                "critic_task": c_t.init(params["critic_task"]),
                "actor_exploration": a_e.init(params["actor_exploration"]),
                "critic_exploration": c_e.init(params["critic_exploration"]),
            }
        )
        train_fn = mod.make_train_fn(
            runtime, world_model, actor, critic, ensemble,
            (wm_tx, ens_tx, a_t, c_t, a_e, c_e), cfg, True, ACTIONS_DIM,
        )
        data = runtime.shard_batch(_data(is_first=version >= 2), axis=1)
        args = (params, opt_states, data, runtime.next_key())
    return _compiled_flops(runtime, train_fn, args)


PROBES = {
    "dreamer_v1": lambda d: probe_dv(1, d),
    "dreamer_v2": lambda d: probe_dv(2, d),
    "dreamer_v3": lambda d: probe_dv(3, d),
    "p2e_dv1": lambda d: probe_p2e(1, d),
    "p2e_dv2": lambda d: probe_p2e(2, d),
    "p2e_dv3": lambda d: probe_p2e(3, d),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/results/scaling_r4_flops.json")
    ap.add_argument("--algos", default=",".join(PROBES))
    args = ap.parse_args()
    rows = {}
    for name in args.algos.split(","):
        f1 = PROBES[name](1)
        f8 = PROBES[name](8)
        ratio = f8 / f1 if f1 else float("nan")
        rows[name] = {
            "flops_per_device_1dev": f1,
            "flops_per_device_8dev": f8,
            "ratio_8dev_over_1dev": round(ratio, 4),
            # 1/8 = 0.125 is ideal; collectives and unshardable tails push it
            # up a little; ~1.0 means silent replication
            "verdict": "sharded" if ratio < 0.3 else ("PARTIAL" if ratio < 0.7 else "REPLICATED"),
        }
        print(json.dumps({"algo": name, **rows[name]}))
    out = {
        "protocol": (
            "XLA cost-analysis flops of the compiled per-device train program at mesh "
            "sizes 1 vs 8 (virtual CPU devices), global batch fixed at "
            f"B={B} x T={T}; nothing executed. Ideal ratio 0.125."
        ),
        "algos": rows,
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
