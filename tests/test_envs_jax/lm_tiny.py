"""The two language-model policies at tiny widths on the CPU, for the tests of
their collection's spans, scopes and counters (ISSUE 32), and the golden
readings that hold a seeded rollout and one whole ``ppo.main`` iteration to
the bits they had BEFORE those were added.

``python -m tests.test_envs_jax.lm_tiny --write`` makes ``lm_golden.json``
anew (run on the commit whose bits are to be kept: PR 31's ``1883031`` made
the committed file).  Floats go in as the hex of their bytes, so the file
holds bits, not roundings; parameters go in as one sha256 a leaf with the
leaf's float64 sum beside it.  ``canary`` is a small seeded f32 program whose
bits say whether this machine's XLA:CPU rounds as the golden's did: where it
does not, the tests compare to a tolerance.
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import sys
import tempfile

import numpy as np

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "lm_golden.json")
P, RESP, ENVS = 8, 16, 3
KINDS = ("sdar_moe", "mla_moe")
# the rollout's arrays that are compared (the prompt rides `data` too)
ROLLOUT_KEYS = ("prompt", "actions", "logprobs", "values", "rewards", "dones")


def overrides(kind: str, root: str, precision: str = "32-true", iterations: int = 2, envs: int = ENVS,
              response: int = RESP):
    """``cli.run`` overrides of ``kind`` at the widths of ``tests/test_models``."""
    common = [
        "fabric.accelerator=cpu", "fabric.devices=1", f"fabric.precision={precision}", f"env.num_envs={envs}",
        "env.wrapper.vocab_size=64", "env.wrapper.mask_id=63", f"env.wrapper.prompt_len={P}",
        f"env.wrapper.response_len={response}", f"algo.total_steps={iterations * envs * response}",
        f"metric.log_every={envs * response}", f"root_dir={root}", f"run_name={kind}", "checkpoint.every=0",
    ]
    if kind == "sdar_moe":
        return ["exp=ppo_sdar_moe"] + common + [
            "algo.sdar.hidden_size=64", "algo.sdar.num_attention_heads=4", "algo.sdar.num_key_value_heads=2",
            "algo.sdar.head_dim=16", "algo.sdar.num_experts=8", "algo.sdar.num_experts_per_tok=2",
            "algo.sdar.moe_intermediate_size=32", "algo.sdar.num_hidden_layers=2", "algo.sdar.experts_held=4",
            "algo.sdar.attention_block=128", "algo.sdar.attention_interpret=True",
        ]
    return ["exp=ppo_joyai_flash"] + common + [
        "algo.per_rank_batch_size=1", "algo.mla.hidden_size=64", "algo.mla.num_attention_heads=4",
        "algo.mla.q_lora_rank=32", "algo.mla.kv_lora_rank=16", "algo.mla.qk_nope_head_dim=16",
        "algo.mla.qk_rope_head_dim=8", "algo.mla.v_head_dim=16", "algo.mla.intermediate_size=96",
        "algo.mla.moe_intermediate_size=32", "algo.mla.n_routed_experts=8", "algo.mla.num_experts_per_tok=2",
        "algo.mla.num_hidden_layers=3", "algo.mla.experts_held=4", "algo.mla.attention_block=128",
        "algo.mla.attention_interpret=True",
    ]


def build_collector(kind: str, root: str, extra=(), aggregator=None, seed: int = 3):
    """(collector, policy, params, runtime, cfg) as ``ppo.main`` builds them."""
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.lm_policy import language_model_policy
    from sheeprl_tpu.config import compose, instantiate
    from sheeprl_tpu.utils.env import make_train_envs

    cfg = compose(overrides=overrides(kind, root) + list(extra))
    runtime = instantiate(dict(cfg.fabric))
    runtime.launch()
    runtime.seed_everything(seed)
    envs = make_train_envs(cfg, runtime, None)
    policy, params = build_agent(runtime, (), False, cfg, envs.single_observation_space)
    collector = language_model_policy(cfg).collector_class(
        envs=envs, module=policy, params=params, cfg=cfg, runtime=runtime, obs_keys=["tokens"], total_envs=ENVS,
        world_size=1, aggregator=aggregator,
    )
    return collector, policy, params, runtime, cfg


def rollout_arrays(kind: str, root: str, rollouts: int = 2):
    """The data of ``rollouts`` seeded rollouts, as numpy arrays (the second
    starts from the env's own auto-reset)."""
    collector, _, _, runtime, _ = build_collector(kind, root)
    out = []
    for i in range(rollouts):
        data = collector.collect(i + 1, True, runtime.next_key).data
        out.append({k: np.asarray(data[k]) for k in ROLLOUT_KEYS})
    return out


def params_after_one_iteration(kind: str, root: str):
    """{leaf path: array} of the agent in the checkpoint that ``cli.run``
    leaves after ONE iteration (a rollout, an update) from ``seed=5``."""
    import jax

    from sheeprl_tpu.cli import run
    from sheeprl_tpu.utils.callback import load_checkpoint

    run(overrides(kind, root, iterations=1) + ["seed=5", "checkpoint.save_last=True"])
    ckpts = sorted(glob.glob(f"{root}/{kind}/**/ckpt_*.ckpt", recursive=True))
    assert ckpts, "the run left no checkpoint"
    agent = load_checkpoint(ckpts[-1])["agent"]
    return {jax.tree_util.keystr(path): np.asarray(x) for path, x in jax.tree_util.tree_leaves_with_path(agent)}


def canary() -> str:
    """Bits of a seeded f32 program (products, softmax, log): the machine's rounding."""
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(11), (48, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(12), (64, 64), jnp.float32) * 0.02
    y = jax.jit(lambda x, w: jax.nn.log_softmax(jnp.tanh(x @ w) @ w.T, axis=-1))(x, w)
    return hashlib.sha256(np.asarray(y).tobytes()).hexdigest()


def encode(a: np.ndarray) -> dict:
    a = np.ascontiguousarray(a)
    return {"dtype": str(a.dtype), "shape": list(a.shape), "hex": a.tobytes().hex()}


def decode(d: dict) -> np.ndarray:
    return np.frombuffer(bytes.fromhex(d["hex"]), dtype=d["dtype"]).reshape(d["shape"]).copy()


def digest(leaves: dict) -> dict:
    return {k: {"sha256": hashlib.sha256(np.ascontiguousarray(v).tobytes()).hexdigest(),
                "sum": float(np.asarray(v, np.float64).sum()), "abs_sum": float(np.abs(np.asarray(v, np.float64)).sum())}
            for k, v in leaves.items()}


def make_golden() -> dict:
    out = {"canary": canary(), "kinds": {}}
    for kind in KINDS:
        with tempfile.TemporaryDirectory() as tmp:
            rollouts = rollout_arrays(kind, tmp)
            leaves = params_after_one_iteration(kind, tmp)
        out["kinds"][kind] = {
            "rollouts": [{k: encode(v) for k, v in r.items()} for r in rollouts],
            "params_after_one_iteration": digest(leaves),
        }
    return out


def load_golden() -> dict:
    with open(GOLDEN) as f:
        return json.load(f)


if __name__ == "__main__":
    golden = make_golden()
    if "--write" in sys.argv:
        with open(GOLDEN, "w") as f:
            json.dump(golden, f, indent=1, sort_keys=True)
            f.write("\n")
    else:  # a second making must give the first's bits
        assert golden == load_golden(), "this tree's bits differ from lm_golden.json"
        print("equal to lm_golden.json")
