"""Time the v1 leaf-manifest checkpoint format on a DreamerV3-XL state.

VERDICT r3 item 7 asked for the stable checkpoint format to be "timed at
XL": the S-scale numbers (1.45 GB: save 11.8 s / load 10.0 s vs 26.8 s
pickle) say nothing about how the format behaves at the 13 GB-HBM XL
scale (round-3 chip step timing), where a whole-state pickle is the difference
between a tolerable and an unusable checkpoint cadence.

Builds the REAL XL agent (algo=dreamer_v3_XL shapes, reference
configs/algo/dreamer_v3_XL.yaml parity: 4096 GRU, 1024 dense, 96-channel
CNN) plus its three optimizer states on the host CPU, assembles the exact
``ckpt_state`` dict the training loop saves (dreamer_v3.py:929-941, minus
the replay buffer — buffer persistence is covered by the S-scale
measurements and scales with ``buffer.size`` not model size), and times:

* v1 ``save_state`` / full ``load_checkpoint``
* v1 partial read  (``select=("iter_num", "batch_size")``)
* cloudpickle save / load of the same state (the format it replaced)

Usage: JAX_PLATFORMS=cpu python benchmarks/bench_ckpt_xl.py \
           [--out benchmarks/results/ckpt_xl_timing_r4.json]
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_xl_state():
    import gymnasium as gym
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _make_optimizer
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel.mesh import MeshRuntime

    cfg = compose(
        overrides=[
            "exp=dreamer_v3",
            "env=dummy",
            "algo=dreamer_v3_XL",
            "env.num_envs=1",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
        ]
    )
    runtime = MeshRuntime(devices=1, accelerator="cpu", precision="32-true").launch()
    runtime.seed_everything(0)
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    world_model, actor, critic, params = build_agent(runtime, (6,), False, cfg, obs_space)
    wm_tx = _make_optimizer(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients)
    actor_tx = _make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
    critic_tx = _make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
    opt_states = {
        "world_model": wm_tx.init(params["world_model"]),
        "actor": actor_tx.init(params["actor"]),
        "critic": critic_tx.init(params["critic"]),
    }
    # the exact training-loop state dict (dreamer_v3.py ckpt_state), sans rb
    state = {
        "world_model": params["world_model"],
        "actor": params["actor"],
        "critic": params["critic"],
        "target_critic": params["target_critic"],
        "opt_states": opt_states,
        "moments": init_moments(),
        "ratio": {"_ratio": 0.3, "_prev": 123456, "_pretrain_steps": 0},
        "iter_num": 123456,
        "batch_size": 16,
        "last_log": 120000,
        "last_checkpoint": 120000,
    }
    state = jax.device_get(state)
    n_bytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(state) if hasattr(x, "nbytes")
    )
    n_leaves = len(jax.tree_util.tree_leaves(state))
    return state, n_bytes, n_leaves


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/results/ckpt_xl_timing_r4.json")
    args = ap.parse_args()

    t0 = time.perf_counter()
    state, n_bytes, n_leaves = build_xl_state()
    build_s = time.perf_counter() - t0
    print(f"built XL state: {n_bytes / 1e9:.2f} GB, {n_leaves} leaves, {build_s:.1f} s")

    from sheeprl_tpu.utils.callback import load_checkpoint
    from sheeprl_tpu.utils.ckpt_format import save_state

    import cloudpickle

    results = {
        "protocol": (
            "DreamerV3-XL ckpt_state (params + 3 adam opt states + counters, no "
            "replay buffer) built on host CPU; save/load on local disk, "
            "best of 2 runs each"
        ),
        "state_gb": round(n_bytes / 1e9, 3),
        "n_leaves": n_leaves,
    }

    with tempfile.TemporaryDirectory() as td:
        v1 = os.path.join(td, "xl_v1.ckpt")
        pk = os.path.join(td, "xl_pickle.ckpt")

        for _ in range(2):
            t0 = time.perf_counter()
            save_state(v1, state)
            results["v1_save_s"] = min(
                results.get("v1_save_s", 1e9), round(time.perf_counter() - t0, 2)
            )
        results["v1_file_gb"] = round(os.path.getsize(v1) / 1e9, 3)

        for _ in range(2):
            t0 = time.perf_counter()
            loaded = load_checkpoint(v1)
            results["v1_load_full_s"] = min(
                results.get("v1_load_full_s", 1e9), round(time.perf_counter() - t0, 2)
            )
        assert loaded["iter_num"] == state["iter_num"]
        del loaded

        for _ in range(2):
            t0 = time.perf_counter()
            partial = load_checkpoint(v1, select=("iter_num", "batch_size"))
            results["v1_load_select_ms"] = min(
                results.get("v1_load_select_ms", 1e9),
                round((time.perf_counter() - t0) * 1e3, 1),
            )
        assert partial["iter_num"] == state["iter_num"]

        for _ in range(2):
            t0 = time.perf_counter()
            with open(pk, "wb") as f:
                cloudpickle.dump(state, f)
            results["pickle_save_s"] = min(
                results.get("pickle_save_s", 1e9), round(time.perf_counter() - t0, 2)
            )
        results["pickle_file_gb"] = round(os.path.getsize(pk) / 1e9, 3)

        for _ in range(2):
            t0 = time.perf_counter()
            with open(pk, "rb") as f:
                loaded = cloudpickle.load(f)
            results["pickle_load_s"] = min(
                results.get("pickle_load_s", 1e9), round(time.perf_counter() - t0, 2)
            )
        del loaded

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
