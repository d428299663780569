"""The fused causal collector with the Qwen3-Next policy (``models/qwen3_next.py``)
at tiny widths on the CPU: its cached decoding carries two kinds of state side
by side (each linear layer's recurrent state and convolution tail, of fixed
size, and the full layer's keys and values, a position each) and records the
log-probabilities and values the update's one causal pass recomputes (the
chunked delta rule over whole episodes), at every response position."""

import jax
import jax.numpy as jnp
import numpy as np

from tests.test_models.test_qwen3_next import P, VALUE_ATOL, lm_overrides

RESP = 16


def test_collector_record_equals_causal_recomputation(tmp_path):
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.lm_policy import language_model_policy
    from sheeprl_tpu.config import compose, instantiate
    from sheeprl_tpu.envs.jax.collect import FusedCausalCollector
    from sheeprl_tpu.models.qwen3_next import RecurrentCache
    from sheeprl_tpu.utils.env import make_train_envs

    cfg = compose(overrides=lm_overrides(tmp_path, envs=3, response=RESP))
    assert language_model_policy(cfg).collector_class is FusedCausalCollector
    runtime = instantiate(dict(cfg.fabric))
    runtime.launch()
    runtime.seed_everything(5)
    envs = make_train_envs(cfg, runtime, None)
    policy, params = build_agent(runtime, (1, 64), False, cfg, envs.single_observation_space)
    # the zero-centred gains moved off 0, so that the record depends on every one of them
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.2 * jnp.cos(jnp.arange(x.size, dtype=x.dtype)).reshape(x.shape)
        if "norm" in jax.tree_util.keystr(path) else x, params)
    collector = FusedCausalCollector(
        envs=envs, module=policy, params=params, cfg=cfg, runtime=runtime, obs_keys=["tokens"], total_envs=3, world_size=1,
    )
    for _ in range(2):  # the second rollout starts from the env's own auto-reset
        data = collector.collect(1, True, runtime.next_key).data
        actions = jnp.swapaxes(data["actions"], 0, 1)
        assert actions.shape == (3, RESP, 2) and not np.asarray(actions[..., 0]).any()
        logp, _, values, aux = policy.evaluate_episodes(params, data["prompt"][0], actions)
        ratio = np.exp(np.asarray(logp) - np.asarray(data["logprobs"][..., 0]).T)
        np.testing.assert_allclose(ratio, 1.0, atol=2e-5)
        np.testing.assert_allclose(np.asarray(values), np.asarray(data["values"][..., 0]).T, atol=VALUE_ATOL)
        assert "aux_loss" not in aux  # no auxiliary module: the update's loss keeps its three terms
    stats = collector.stats()
    assert stats["passes"] == 2 * (1 + RESP) and stats["positions"] == 2 * 3 * (P + RESP)
    # the cache the rollout carries: fixed-size entries for the linear layers, the episode's length for the full one
    model = policy.model
    _, _, entries = model.apply(params, data["prompt"][0], True, method=type(model).hidden)
    cache = type(model).prefill_cache(entries, P + RESP)
    assert [isinstance(e, RecurrentCache) for e in cache] == [True, True, True, False]
    assert cache[3][0].shape[1] == P + RESP and cache[0].state.dtype == jnp.float32
