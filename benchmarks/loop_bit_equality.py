"""Do two checkouts train the DreamerV3 family to the same bits?  (PR 28)

Seeded tiny CPU runs of ``exp=dreamer_v3`` (host feed and device ring),
``exp=p2e_dv3_exploration`` and ``exp=p2e_dv3_finetuning`` (from that
exploration run's checkpoint, with and without its ring), several updates each,
then the final checkpoints compared leaf for leaf.  Two things the program
leaves unseeded are seeded here, the same way for every checkout, or no two
runs could be compared: the vector env's action space (the random prefill) and
``np.random.default_rng()`` of the host replay buffers.

    cd <checkout A> && JAX_PLATFORMS=cpu python <this file> run /tmp/a
    cd <checkout B> && JAX_PLATFORMS=cpu python <this file> run /tmp/b
    JAX_PLATFORMS=cpu python <this file> cmp /tmp/a /tmp/b      # exit code 1 if any leaf differs

Run the two checkouts one after the other: with both running at once on this
box the device-ring variant (``dv3_cache``) differed once between two
checkouts whose every other run agreed, and agreed in every sequential repeat.
"""
import glob
import os
import sys

sys.path.insert(0, os.getcwd())

TINY = [
    "env=dummy", "env.num_envs=2", "env.sync_env=True", "env.capture_video=False",
    "fabric.accelerator=cpu", "fabric.devices=1", "fabric.precision=32-true", "buffer.memmap=False", "seed=0",
    "buffer.prioritized=False", "buffer.checkpoint=True", "buffer.size=512",
    "algo.per_rank_batch_size=2", "algo.per_rank_sequence_length=4", "algo.horizon=3", "algo.dense_units=8",
    "algo.mlp_layers=1", "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8", "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8", "algo.world_model.stochastic_size=4",
    "algo.world_model.discrete_size=4", "algo.world_model.reward_model.bins=15", "algo.critic.bins=15",
    "env.screen_size=16", "algo.mlp_keys.encoder=[state]", "algo.cnn_keys.encoder=[rgb]",
    "metric.log_level=1", "metric.log_every=8", "checkpoint.save_last=True", "checkpoint.every=100000",
    "algo.learning_starts=8", "algo.total_steps=48", "algo.replay_ratio=0.5", "algo.run_test=True",
]
P2E = ["algo.ensembles.n=2", "algo.ensembles.dense_units=8", "algo.ensembles.mlp_layers=1"]
RUNS = ("dv3", "dv3_cache", "expl", "fine", "fine_rb")


def last_checkpoint(out, name):
    found = sorted(glob.glob(f"{out}/{name}/r/**/ckpt_*.ckpt", recursive=True))
    assert found, f"{name}: no checkpoint under {out}"
    return found[-1]


def run_all(out):
    import gymnasium.vector as gv
    import numpy as np

    from sheeprl_tpu.cli import run

    init, default_rng = gv.SyncVectorEnv.__init__, np.random.default_rng

    def seeded_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        self.action_space.seed(0)

    gv.SyncVectorEnv.__init__ = seeded_init
    np.random.default_rng = lambda seed=None: default_rng(0 if seed is None else seed)

    def go(name, extra):
        run(TINY + extra + [f"root_dir={out}/{name}", "run_name=r", f"metric.logger.root_dir={out}/logs_{name}"])
        return last_checkpoint(out, name)

    go("dv3", ["exp=dreamer_v3"])
    go("dv3_cache", ["exp=dreamer_v3", "buffer.device_cache=True"])
    explored = go("expl", ["exp=p2e_dv3_exploration"] + P2E)
    finetune = ["exp=p2e_dv3_finetuning", f"checkpoint.exploration_ckpt_path={explored}"] + P2E
    go("fine", finetune)
    go("fine_rb", finetune + ["buffer.load_from_exploration=True"])


def compare(a, b):
    import jax
    import numpy as np

    from sheeprl_tpu.utils.callback import load_checkpoint

    differing = 0
    for name in RUNS:
        sa, sb = load_checkpoint(last_checkpoint(a, name)), load_checkpoint(last_checkpoint(b, name))
        sa.pop("rb", None), sb.pop("rb", None)  # the ring holds the same frames if every weight agrees
        assert set(sa) == set(sb), (name, sorted(sa), sorted(sb))
        la, lb = (dict(jax.tree_util.tree_flatten_with_path(s)[0]) for s in (sa, sb))
        assert la.keys() == lb.keys(), name
        bad = [
            jax.tree_util.keystr(path) for path in la
            if np.asarray(la[path]).dtype != np.asarray(lb[path]).dtype
            or np.asarray(la[path]).tobytes() != np.asarray(lb[path]).tobytes()
        ]
        differing += len(bad)
        print(f"{name}: keys {sorted(sa)} leaves {len(la)} differing {len(bad)}" + "".join(f"\n  {p}" for p in bad[:8]))
    return differing


if __name__ == "__main__":
    if sys.argv[1] == "run":
        run_all(sys.argv[2])
    else:
        sys.exit(1 if compare(sys.argv[2], sys.argv[3]) else 0)
