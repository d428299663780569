"""The latent-attention sparse-expert policy against its plain reference
(``mla_moe_reference.py``), at tiny widths on the CPU in float32: hidden 64,
4 heads with q / k 16 + 8 wide and v 16, latents of 32 / 16, one dense and two
routed blocks and the MTP module, 8 experts top-2 beside one shared, vocabulary
64, prompt 8, response 16.

Tolerances: both sides compute in float32, in different orders (blocked
online softmax vs one softmax, absorbed vs up-projected attention, sorted
grouped products vs a loop over experts), so values of order 1 agree to a few
1e-6; gradients sum thousands of such terms, hence 2e-4 relative to the
leaf's largest entry.
"""

import collections
import dataclasses
import filecmp
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import mla_moe as M
from sheeprl_tpu.models import mla_moe_reference as R
from sheeprl_tpu.models import sdar_moe as S
from sheeprl_tpu.ops.block_sparse_attention import SegmentMask, _head_width, block_sparse_flash_attention

from . import test_sdar_moe as sdar_tests

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
P, RESP = 8, 16
TINY = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_theta=32e6, rms_norm_eps=1e-6, intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=2.5,
    scoring_func="sigmoid", first_k_dense_replace=1, num_hidden_layers=3, num_nextn_predict_layers=1, vocab_size=64,
    experts_held=4, expert_offset=2,
    attention_block=128, attention_interpret=True,  # the kernel through Pallas' interpreter: no TPU here
)
HYPER = dict(clip_coef=0.2, clip_vloss=False, vf_coef=0.5, ent_coef=0.01, mtp_coef=0.1)
VALUE_ATOL = 5e-6
GRAD_RTOL = 2e-4


def _episodes(seed, n):
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, 64, (n, P))
    response = rng.integers(0, 64, (n, RESP))
    actions = np.stack([np.zeros_like(response), response], -1).astype(np.int32)
    extras = {
        "old_logp": rng.normal(-4.0, 0.3, (n, RESP)).astype(np.float32),
        "old_values": rng.normal(0, 0.1, (n, RESP)).astype(np.float32),
        "advantages": rng.normal(0, 1, (n, RESP)).astype(np.float32),
        "returns": rng.normal(0, 1, (n, RESP)).astype(np.float32),
    }
    return prompt, response, actions, extras


def _policy(overrides=None, remat=True):
    from sheeprl_tpu.algos.ppo.causal_lm_policy import CausalLmPolicy

    cfg = M.MlaMoeConfig.from_mapping({**TINY, **(overrides or {})})
    return CausalLmPolicy(M.MlaMoE(cfg, jnp.float32, remat=remat), P, RESP, aux_coef=HYPER["mtp_coef"]), dataclasses.asdict(cfg)


def _program_loss(policy, params, prompt, actions, extras):
    from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss

    logp, entropy, values, aux = policy.evaluate_episodes(params, jnp.asarray(prompt), jnp.asarray(actions))
    pg = policy_loss(logp, extras["old_logp"], extras["advantages"], HYPER["clip_coef"])
    vl = value_loss(values, extras["old_values"], extras["returns"], HYPER["clip_coef"], HYPER["clip_vloss"])
    ent = entropy_loss(entropy)
    total = pg + HYPER["vf_coef"] * vl + HYPER["ent_coef"] * ent + policy.aux_coef * aux["aux_loss"]
    return total, (jnp.stack([pg, vl, ent, aux["aux_loss"]]), logp, values, aux)


def _reference_loss(rparams, cfg, prompt, response, extras):
    total, outs = 0.0, []
    for b in range(len(prompt)):
        ep = {"prompt": prompt[b], "response": response[b], **{k: v[b] for k, v in extras.items()}}
        loss, out = R.loss_episode(rparams, ep, cfg, HYPER)
        total, outs = total + loss / len(prompt), outs + [out]
    return total, outs


def _assert_trees_close(got, want, rtol):
    flat = jax.tree_util.tree_leaves_with_path(got)
    assert len(flat) == len(jax.tree_util.tree_leaves(want))
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(b).max()) + 1e-6
        assert float(jnp.abs(a - b).max()) <= rtol * scale, jax.tree_util.keystr(path)


# ------------------------------------------------ (a) program vs reference
@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("seed", [0, 1])
def test_program_matches_reference(seed, remat):
    policy, cfg = _policy(remat=remat)
    prompt, response, actions, extras = _episodes(seed, 2)
    params = policy.init(jax.random.PRNGKey(seed))
    rparams = M.reference_params(params)

    (loss, (losses, logp, values, aux)), grads = jax.value_and_grad(
        lambda p: _program_loss(policy, p, prompt, actions, extras), has_aux=True)(params)
    (rloss, routs), rgrads = jax.value_and_grad(
        lambda p: _reference_loss(p, cfg, prompt, response, extras), has_aux=True)(rparams)

    tokens = jnp.concatenate([jnp.asarray(prompt), jnp.asarray(response)], 1)
    u, _ = policy.model.apply(params, tokens, method=M.MlaMoE.hidden)
    logp_all, values_all = policy.model.apply(params, u, method=M.MlaMoE.logits)
    for b in range(2):
        # whole rows of the policy's distribution at every position, not only the taken token's
        ref_logits, ref_values = R.logits_of(rparams, tokens[b], cfg)
        np.testing.assert_allclose(np.asarray(logp_all[b]), np.asarray(jax.nn.log_softmax(ref_logits, -1)), atol=VALUE_ATOL)
        np.testing.assert_allclose(np.asarray(values_all[b]), np.asarray(ref_values), atol=VALUE_ATOL)
        np.testing.assert_allclose(np.asarray(logp[b]), np.asarray(routs[b]["logp"]), atol=VALUE_ATOL)
        np.testing.assert_allclose(np.asarray(values[b]), np.asarray(routs[b]["values"]), atol=VALUE_ATOL)
    for i, name in enumerate(("pg", "vl", "ent", "mtp_loss")):  # the four losses
        assert abs(float(losses[i]) - float(np.mean([o[name] for o in routs]))) <= VALUE_ATOL, name
    assert abs(float(loss) - float(rloss)) <= VALUE_ATOL
    assert float(aux["aux_counters"]["MTP/top1_match"]) == pytest.approx(np.mean([o["mtp_top1_match"] for o in routs]))
    # the counters: a routed block's load is the reference's count, block by block (the MTP module's last)
    counts = sum(np.stack([np.asarray(a["counts"]) for a in o["aux"]]) for o in routs)
    np.testing.assert_array_equal(np.asarray(aux["load"]), counts)
    assert aux["load"].shape == (3, cfg["experts_held"]) and int(aux["dropped"].sum()) == 0
    _assert_trees_close(M.reference_params(grads), rgrads, GRAD_RTOL)
    # the selection bias enters the top-k only: its gradient is exactly zero, in both
    for tree in (M.reference_params(grads), rgrads):
        for block in tree["layers"][1:] + [tree["mtp"]["block"]]:
            assert not np.asarray(block["bias"]).any()
    assert float(jnp.abs(M.reference_params(grads)["mtp"]["eh_proj"]).max()) > 0  # the MTP loss reaches its module
    assert float(jnp.abs(grads["params"]["layer_0"]["mlp"]["w_down"]).max()) > 0


def _kernel_calls(jaxpr):
    """How often each Pallas kernel is called in ``jaxpr``, by kernel name, every inner jaxpr walked."""
    calls = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)  # a ClosedJaxpr holds its jaxpr
                if hasattr(inner, "eqns"):
                    calls += _kernel_calls(inner)
    return calls


@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("model", ["mla_moe", "sdar_moe"])
def test_backward_pass_runs_the_forward_kernel_once_a_block(model, remat):
    """A rematerialised block keeps the attention kernel's output and log-sum-exp (``remat_block``), so
    the gradient of the update's loss calls the forward kernel once a block, as without ``remat``, and
    not a second time to hand the kernel's backward rule what the first call wrote."""
    if model == "mla_moe":
        policy, cfg = _policy(remat=remat)
        prompt, _, actions, extras = _episodes(0, 2)
        loss, blocks = _program_loss, cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    else:
        policy, cfg = sdar_tests._policy(remat=remat)
        prompt, _, _, actions, extras = sdar_tests._episodes(0, 2)
        loss, blocks = sdar_tests._program_loss, cfg["num_hidden_layers"]
    params = policy.init(jax.random.PRNGKey(0))
    grad = jax.make_jaxpr(jax.grad(lambda p: loss(policy, p, prompt, actions, extras)[0]))(params)
    assert _kernel_calls(grad.jaxpr) == {
        "splash_mqa_fwd_residuals": blocks, "splash_mqa_dkv_no_residuals": blocks, "splash_mqa_dq_no_residuals": blocks}


def test_mtp_gradient_reaches_the_trunk():
    """With every PPO term's weight at zero the trunk still gets a gradient: the MTP module's."""
    policy, _ = _policy()
    prompt, _, actions, _ = _episodes(2, 1)
    params = policy.init(jax.random.PRNGKey(2))
    grads = jax.grad(lambda p: policy.evaluate_episodes(p, jnp.asarray(prompt), jnp.asarray(actions))[3]["aux_loss"])(params)
    for name in ("embed", "head", "layer_0", "layer_2"):
        assert max(float(jnp.abs(x).max()) for x in jax.tree_util.tree_leaves(grads["params"][name])) > 0, name
    assert not np.asarray(grads["params"]["value"]).any()


# --------------------------------------------------- (b) the shares add up
@pytest.mark.parametrize("held", [1, 2, 4])
def test_shares_add_up_to_the_uncut_layer(held):
    """The routed parts of the 8 / held shares of one block (``expert_offset``
    0, held, 2 held, ...), with the shared expert and the residual counted
    once, give the uncut reference block."""
    n_experts = TINY["n_routed_experts"]
    full_cfg = {**TINY, "experts_held": n_experts, "expert_offset": 0, "num_hidden_layers": 1, "first_k_dense_replace": 0,
                "num_nextn_predict_layers": 0}
    lp = R.init_params(jax.random.PRNGKey(3), full_cfg)["layers"][0]
    n = P + RESP
    h = jax.random.normal(jax.random.PRNGKey(4), (n, TINY["hidden_size"]))
    pos = jnp.arange(n)
    with jax.default_matmul_precision("highest"):
        want, _ = R.routed_layer(lp, h, pos, full_cfg)
        # what every share computes alike: the residual, attention and the shared expert (no expert held)
        alike, _ = R.routed_layer(lp, h, pos, {**full_cfg, "experts_held": 0})

    parts = []
    for offset in range(0, n_experts, held):
        cfg = M.MlaMoeConfig.from_mapping({**full_cfg, "experts_held": held, "expert_offset": offset})
        block = M.MlaBlock(cfg, True, jnp.float32)
        share = {"params": {
            "norm1": lp["norm1"], "norm2": lp["norm2"],
            "attn": {k: lp[k] for k in ("wdq", "q_norm", "wuq", "wdkv", "kv_norm", "wukv", "wo")},
            "shared": {"w_gate": lp["s_gate"], "w_up": lp["s_up"], "w_down": lp["s_down"]},
            "moe": {"router": lp["router"], "bias": lp["bias"],
                    **{k: lp[k][offset:offset + held] for k in ("w_gate", "w_up", "w_down")}},
        }}
        out, aux, _ = block.apply(share, h[None], pos)
        parts.append(out[0])
        assert int(aux["dropped"]) == 0
    got = sum(parts) - (len(parts) - 1) * alike
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------ (c) the kernel with q / k of one width and v of another
def _dense_attention(q, k, v, mask):
    scores = jnp.einsum("...qhd,...khd->...hqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.swapaxes(jnp.einsum("...hqk,...khd->...hqd", probs, v), -3, -2)


@pytest.mark.parametrize("d_qk,d_v,length", [(24, 16, 200), (192, 128, 130), (16, 24, 128)],
                         ids=["tiny_padded_tile", "published_widths", "v_wider"])
def test_two_head_widths_match_the_dense_mask(d_qk, d_v, length):
    """Heads that share nothing, keys wider (or narrower) than values, a length
    that is no multiple of the tile: forward and gradient equal the dense-mask
    attention."""
    heads = 2
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(d_qk + length), 4)
    q = jax.random.normal(kq, (2, length, heads, d_qk))
    k = jax.random.normal(kk, (2, length, heads, d_qk))
    v = jax.random.normal(kv, (2, length, heads, d_v))
    weight = jax.random.normal(kg, (2, length, heads, d_v))
    mask = SegmentMask.causal(length, length)

    def blocked(q, k, v):
        return block_sparse_flash_attention(q, k, v, mask, 128, interpret=True)

    out = blocked(q, k, v)
    assert out.shape == (2, length, heads, d_v)
    with jax.default_matmul_precision("highest"):
        want = _dense_attention(q, k, v, jnp.asarray(mask.dense()))
        want_grads = jax.grad(lambda *a: (_dense_attention(*a, jnp.asarray(mask.dense())) * weight).sum(), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    grads = jax.grad(lambda *a: (blocked(*a) * weight).sum(), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4)


def test_head_padding_is_in_one_place():
    assert [_head_width(d) for d in (16, 24, 64, 128, 192, 200, 256)] == [128, 128, 128, 128, 192, 256, 256]
    with pytest.raises(ValueError, match="192 wide and keys 128"):
        block_sparse_flash_attention(jnp.zeros((8, 2, 192)), jnp.zeros((8, 2, 128)), jnp.zeros((8, 2, 128)),
                                     SegmentMask.causal(8, 8), interpret=True)


# ----------------- (d) cached, absorbed decoding equals the full causal pass
@pytest.mark.parametrize("seed", [0, 1])
def test_cached_absorbed_decoding_equals_the_full_pass(seed):
    """Prefill the prompt (full-sequence form), then one cached pass a token
    (absorbed form, the cache holds latents only): the logits at every response
    position equal the one causal pass's, and the reference's."""
    policy, cfg = _policy(remat=False)
    model = policy.model
    prompt, response, _, _ = _episodes(seed, 2)
    params = policy.init(jax.random.PRNGKey(seed))
    tokens = jnp.concatenate([jnp.asarray(prompt), jnp.asarray(response)], 1)
    u_full, _ = model.apply(params, tokens, method=M.MlaMoE.hidden)
    full, _ = model.apply(params, u_full, method=M.MlaMoE.logits)

    u, _, latents = model.apply(params, tokens[:, :P], True, method=M.MlaMoE.hidden)
    cache = [tuple(jnp.zeros((2, P + RESP) + x.shape[2:], x.dtype).at[:, :P].set(x) for x in lat) for lat in latents]
    assert [tuple(c.shape[-1] for c in lat) for lat in cache] == [(cfg["kv_lora_rank"], cfg["qk_rope_head_dim"])] * 3
    step = jax.jit(lambda tok, cache, length: model.apply(params, tok, cache, length, method=M.MlaMoE.step))
    rows = [model.apply(params, u[:, -1], method=M.MlaMoE.logits)[0]]
    for i in range(P, P + RESP):
        ui, _, cache = step(tokens[:, i:i + 1], cache, jnp.int32(i))
        rows.append(model.apply(params, ui[:, 0], method=M.MlaMoE.logits)[0])
    got = jnp.stack(rows, 1)  # positions P - 1 .. P + RESP - 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, P - 1:]), atol=VALUE_ATOL)
    ref_logits, _ = R.logits_of(M.reference_params(params), tokens[0], cfg)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(jax.nn.log_softmax(ref_logits[P - 1:], -1)), atol=VALUE_ATOL)


def _lm_overrides(tmp_path, precision="32-true", envs=3):
    return [
        "exp=ppo_joyai_flash", "fabric.accelerator=cpu", f"fabric.precision={precision}", f"env.num_envs={envs}",
        "env.wrapper.vocab_size=64", "env.wrapper.mask_id=63", f"env.wrapper.prompt_len={P}", f"env.wrapper.response_len={RESP}",
        "algo.per_rank_batch_size=1", "algo.mla.hidden_size=64", "algo.mla.num_attention_heads=4", "algo.mla.q_lora_rank=32",
        "algo.mla.kv_lora_rank=16", "algo.mla.qk_nope_head_dim=16", "algo.mla.qk_rope_head_dim=8", "algo.mla.v_head_dim=16",
        "algo.mla.intermediate_size=96", "algo.mla.moe_intermediate_size=32", "algo.mla.n_routed_experts=8",
        "algo.mla.num_experts_per_tok=2", "algo.mla.num_hidden_layers=3", "algo.mla.experts_held=4",
        "algo.mla.attention_block=128", "algo.mla.attention_interpret=True", f"algo.total_steps={2 * envs * RESP}",
        f"metric.log_every={envs * RESP}", f"root_dir={tmp_path}", "run_name=joyai", "checkpoint.every=0",
    ]


def test_collector_record_equals_causal_recomputation(tmp_path):
    """Log-probabilities and values recorded by the fused collector (latent
    cache, one absorbed pass a token) equal the update's recomputation (one
    causal pass, up-projected attention) under the same weights: the first
    epoch's ratio is 1."""
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.lm_policy import language_model_policy
    from sheeprl_tpu.config import compose, instantiate
    from sheeprl_tpu.envs.jax.collect import FusedCausalCollector
    from sheeprl_tpu.utils.env import make_train_envs

    cfg = compose(overrides=_lm_overrides(tmp_path))
    assert language_model_policy(cfg).collector_class is FusedCausalCollector
    runtime = instantiate(dict(cfg.fabric))
    runtime.launch()
    runtime.seed_everything(3)
    envs = make_train_envs(cfg, runtime, None)
    policy, params = build_agent(runtime, (1, 64), False, cfg, envs.single_observation_space)
    collector = FusedCausalCollector(
        envs=envs, module=policy, params=params, cfg=cfg, runtime=runtime, obs_keys=["tokens"], total_envs=3, world_size=1,
    )
    for _ in range(2):  # the second rollout starts from the env's own auto-reset
        data = collector.collect(1, True, runtime.next_key).data
        actions = jnp.swapaxes(data["actions"], 0, 1)
        assert actions.shape == (3, RESP, 2) and not np.asarray(actions[..., 0]).any()
        logp, _, values, _ = policy.evaluate_episodes(params, data["prompt"][0], actions)
        ratio = np.exp(np.asarray(logp) - np.asarray(data["logprobs"][..., 0]).T)
        np.testing.assert_allclose(ratio, 1.0, atol=2e-5)
        np.testing.assert_allclose(np.asarray(values), np.asarray(data["values"][..., 0]).T, atol=VALUE_ATOL)
        dones, rewards = np.asarray(data["dones"][..., 0]), np.asarray(data["rewards"][..., 0])
        assert dones[-1].all() and not dones[:-1].any() and not rewards[:-1].any()
        # the reward rule, recomputed on the host
        response, prompt = np.asarray(actions[..., 1]), np.asarray(data["prompt"][0])
        np.testing.assert_allclose(rewards[-1], (response == prompt[:, np.arange(RESP) % P]).mean(-1), atol=1e-6)


# -------------------- (e) selection uses score + bias, the weights do not
def _routed(spec_overrides=None, seed=0, n=40):
    spec = S.RoutedSpec(**{**dict(hidden_size=64, moe_intermediate_size=32, num_experts=8, top_k=2, experts_held=8,
                                  scoring="sigmoid", scale=2.5), **(spec_overrides or {})})
    layer = S.RoutedExperts(spec, jnp.float32)
    m = jax.random.normal(jax.random.PRNGKey(seed), (n, 64))
    params = layer.init(jax.random.PRNGKey(seed + 1), m)
    return spec, layer, params, m


@pytest.mark.parametrize("case", ["changes_the_choice", "keeps_the_choice"])
def test_bias_selects_and_does_not_weigh(case):
    spec, layer, params, m = _routed()
    scores = jax.nn.sigmoid(jnp.dot(m, params["params"]["router"], precision="highest"))
    biased = jnp.sort(scores + params["params"]["bias"], -1)
    # the narrowest gap among the two kept and the first left out: under half of it no choice and no order changes
    gap = float(jnp.minimum(biased[:, -1] - biased[:, -2], biased[:, -2] - biased[:, -3]).min())
    assert gap > 0
    if case == "keeps_the_choice":  # every expert's bias moves, none by as much as half the narrowest gap
        bump = 0.45 * gap * jnp.sign(jax.random.normal(jax.random.PRNGKey(9), (8,)))
    else:  # one expert's bias rises over every score: it is now chosen everywhere
        bump = jnp.zeros(8).at[3].set(2.0)
    moved = {"params": {**params["params"], "bias": params["params"]["bias"] + bump}}
    (y0, aux0), (y1, aux1) = layer.apply(params, m), layer.apply(moved, m)
    if case == "keeps_the_choice":
        assert float(jnp.abs(bump).min()) > 0
        np.testing.assert_array_equal(np.asarray(aux0["top_i"]), np.asarray(aux1["top_i"]))
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))  # bit-equal: the weights never saw the bias
    else:
        assert (np.asarray(aux1["top_i"]) == 3).any(-1).all() and not (np.asarray(aux0["top_i"]) == 3).any(-1).all()
        assert float(jnp.abs(y1 - y0).max()) > 1e-3
        # the weights at the new choice are the unbiased scores', renormalised and scaled
        picked = jnp.take_along_axis(scores, aux1["top_i"], -1)
        weights = 2.5 * picked / picked.sum(-1, keepdims=True)
        with jax.default_matmul_precision("highest"):
            want = sum(jnp.where(aux1["top_i"] == e, weights, 0.0).sum(-1)[:, None]
                       * R.swiglu(m, *(params["params"][k][e] for k in ("w_gate", "w_up", "w_down"))) for e in range(8))
        np.testing.assert_allclose(np.asarray(y1), np.asarray(want), atol=VALUE_ATOL)
    assert not np.asarray(jax.grad(lambda p: layer.apply(p, m)[0].sum())(params)["params"]["bias"]).any()


# ------ (f) the shared class under SDAR's rule is the rule written out here
@pytest.mark.parametrize("held,offset", [(8, 0), (2, 2)], ids=["all_held_one_length", "a_share_two_lengths"])
def test_softmax_rule_is_bit_equal_to_the_rule_written_out(held, offset):
    """``RoutedExperts`` under ``scoring="softmax"`` (SDAR-MoE's rule) against
    softmax -> top-k -> renormalise written out here and put through the same
    dispatch: outputs, counters and gradients are bit-equal, and the layer has
    no bias parameter."""
    n, k = 600, 2
    cfg = S.SdarConfig.from_mapping(dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=k,
        moe_intermediate_size=32, num_hidden_layers=1, vocab_size=64, mask_id=63, experts_held=held, expert_offset=offset))
    layer = S.RoutedExperts(cfg, jnp.float32)
    m = jax.random.normal(jax.random.PRNGKey(0), (n, 64))
    params = layer.init(jax.random.PRNGKey(1), m)
    assert set(params["params"]) == {"router", "w_gate", "w_up", "w_down"}

    def by_hand(p, m):
        p = p["params"]
        logits = jnp.dot(m, p["router"], precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        weights = top_p / top_p.sum(-1, keepdims=True)
        local = top_i - offset
        is_held = (local >= 0) & (local < held)
        key = jnp.where(is_held, local, held).reshape(-1)
        rows, rows_fit = n * min(k, held), S.short_buffer_rows(n, k, held, 8)
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order).reshape(n, k)
        group_sizes = (key[:, None] == jnp.arange(held)).sum(0).astype(jnp.int32)
        data = (m, jnp.where(is_held, weights, 0.0), p["w_gate"], p["w_up"], p["w_down"], order, inv, is_held, group_sizes)
        if rows_fit < rows:  # (a short buffer this small keeps the pair-wide token side in both branches)
            assert S.compact_slots(n, k, held, 8, 64 * 4) is None
            y = S._experts_tiered(((rows_fit, None), (rows, None)), jnp.float32, is_held.sum() <= rows_fit, *data)
        else:
            y = S._experts_at(rows, None, jnp.float32, *data)
        entropy = -(probs * jnp.log(jnp.maximum(probs, 1e-30))).sum(-1).mean()
        return y, {"load": group_sizes, "top_i": top_i, "entropy": entropy}

    assert (S.short_buffer_rows(n, k, held, 8) < n * min(k, held)) == (held == 2)
    (y, aux), (want, want_aux) = jax.jit(layer.apply)(params, m), jax.jit(by_hand)(params, m)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    for name in ("load", "top_i", "entropy"):
        np.testing.assert_array_equal(np.asarray(aux[name]), np.asarray(want_aux[name]))
    weight = jax.random.normal(jax.random.PRNGKey(2), y.shape)
    grads = jax.jit(jax.grad(lambda p, m: (layer.apply(p, m)[0] * weight).sum(), argnums=(0, 1)))(params, m)
    want_grads = jax.jit(jax.grad(lambda p, m: (by_hand(p, m)[0] * weight).sum(), argnums=(0, 1)))(params, m)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_routed_spec_of_both_models():
    sdar = S.SdarConfig(experts_held=16).routed_spec
    assert (sdar.scoring, sdar.scale, sdar.num_experts, sdar.top_k, sdar.experts_held) == ("softmax", 1.0, 128, 8, 16)
    mla = M.MlaMoeConfig(experts_held=16).routed_spec
    assert (mla.scoring, mla.scale, mla.num_experts, mla.top_k, mla.experts_held) == ("sigmoid", 2.5, 256, 8, 16)
    assert S.RoutedSpec.of(mla) is mla
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        _routed({"scoring": "tanh"})


# ---------------------------- (g) dropless under skew with the sigmoid rule
@pytest.mark.parametrize("favoured", [2, 5])
def test_dropless_under_skew(favoured):
    """A selection bias that sends every token to one held expert still matches
    the reference, and nothing is dropped."""
    policy, cfg = _policy()
    prompt, response, actions, extras = _episodes(5, 2)
    params = policy.init(jax.random.PRNGKey(5))
    blocks = [params["params"]["layer_1"], params["params"]["layer_2"], params["params"]["mtp"]["block"]]
    for block in blocks:
        block["moe"]["bias"] = block["moe"]["bias"].at[favoured].add(2.0)
    rparams = M.reference_params(params)
    (loss, (_, logp, values, aux)), grads = jax.value_and_grad(
        lambda p: _program_loss(policy, p, prompt, actions, extras), has_aux=True)(params)
    (rloss, routs), rgrads = jax.value_and_grad(
        lambda p: _reference_loss(p, cfg, prompt, response, extras), has_aux=True)(rparams)
    load = np.asarray(aux["load"])
    assert (load[:, favoured - cfg["expert_offset"]] == 2 * (P + RESP)).all(), load  # every token, in every routed block
    assert int(aux["dropped"].sum()) == 0
    np.testing.assert_array_equal(load, sum(np.stack([np.asarray(a["counts"]) for a in o["aux"]]) for o in routs))
    assert abs(float(loss) - float(rloss)) <= VALUE_ATOL
    _assert_trees_close(M.reference_params(grads), rgrads, GRAD_RTOL)


# --------------------------------------------- (h) the reference's two copies
def test_reference_copies_are_byte_identical():
    assert filecmp.cmp(
        os.path.join(ROOT, "sheeprl_tpu", "models", "mla_moe_reference.py"),
        os.path.join(ROOT, "chipbench", "reference", "mla_moe.py"), shallow=False,
    )


def test_reference_imports_nothing_of_the_repository():
    with open(os.path.join(ROOT, "sheeprl_tpu", "models", "mla_moe_reference.py")) as f:
        imports = [line for line in f if line.startswith(("import ", "from "))]
    assert not any("sheeprl" in line or "chipbench" in line for line in imports), imports


# ----------------------------------------------------- (i) through the CLI
@pytest.mark.parametrize("precision", ["32-true", "bf16-mixed"])
def test_cli_runs_two_iterations(tmp_path, precision):
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.ops import block_sparse_attention as op

    op._splash_kernel.cache_clear()  # an earlier test's kernels would be this run's: a hit of the cache says nothing
    op.take_engaged()
    run(_lm_overrides(tmp_path, precision))
    records = [json.loads(line) for path in glob.glob(f"{tmp_path}/joyai/*/telemetry.jsonl") for line in open(path)]
    moe, mtp = [r["moe"] for r in records if "moe" in r], [r["mtp"] for r in records if "mtp" in r]
    assert len(moe) == len(mtp) == 2, records
    # how the blocked attention's kernels engaged rides the first record, once: the prefill's and the update's
    (attention,) = [r["attention"] for r in records if "attention" in r]
    assert "attention" in records[0] and sorted(k["s_q"] for k in attention) == [P, P + RESP]
    assert all(k["backward"] == "split" and k["block_q"] == k["block_kv_dkv"] == k["block_kv_dq"] == 128 for k in attention)
    assert all(m["dropped"] == 0 and np.isfinite(m["router_entropy"]) and m["load_max_over_mean"] >= 1 for m in moe)
    assert all(np.isfinite(m["loss"]) and 3.0 < m["loss"] < 5.5 and 0.0 <= m["top1_match"] <= 1.0 for m in mtp)  # ln 64 = 4.16
    assert "load_l2_e3" in moe[0] and "load_l3_e0" not in moe[0]  # two routed blocks and the MTP module's
    assert records[-1]["jaxenv"]["env"] == "TokenEnvJax" and records[-1]["jaxenv"]["env_steps"] == 2 * 3 * RESP


@pytest.mark.parametrize("override,message", [
    ("env.wrapper.block_length=4", "block_length=1"),
    ("algo.env_backend=host", "registered jax env family|env_backend=jax"),
    ("algo.rollout_steps=8", "must equal env.wrapper.response_len"),
])
def test_cli_refuses_what_the_policy_cannot_run(tmp_path, override, message):
    from sheeprl_tpu.cli import run

    with pytest.raises(ValueError, match=message):
        run(_lm_overrides(tmp_path) + [override])


def test_policy_kinds_are_chosen_in_one_place():
    from sheeprl_tpu.algos.ppo.lm_policy import KINDS, language_model_policy
    from sheeprl_tpu.config import compose

    assert set(KINDS) == {"sdar_moe", "mla_moe", "afmoe", "qwen3_next"}
    assert language_model_policy(compose(overrides=["exp=ppo"])) is None
    assert language_model_policy(compose(overrides=["exp=ppo_sdar_moe"])).collector == "FusedDiffusionCollector"
    for exp, model in (("ppo_joyai_flash", M.MlaMoE), ("ppo_trinity_mini", None), ("ppo_qwen3_next", None)):
        kind = language_model_policy(compose(overrides=[f"exp={exp}"]))
        assert kind.collector == "FusedCausalCollector" and kind.model_class.config_class.__name__.endswith("Config")
        assert model is None or kind.model_class is model


# --------------- the episode update: the auxiliary loss, its counters, the frozen bias
@pytest.mark.parametrize("precision", ["32-true", "bf16-mixed"])
def test_episode_update_adds_the_auxiliary_loss(tmp_path, precision):
    """One call of ``make_episode_update_fn`` on the causal policy: four losses
    a step, the fourth the MTP cross-entropy at ``algo.mtp_coef``; the first
    step's gradient is the hand-written loss's; the selection bias takes no
    step while every other leaf moves; ``MTP/*`` ride the metrics."""
    import optax

    import sheeprl_tpu.algos.ppo.ppo as ppo
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.config import compose, instantiate
    from sheeprl_tpu.utils.utils import gae, normalize_tensor

    cfg = compose(overrides=_lm_overrides(tmp_path, precision, envs=4) + ["algo.per_rank_batch_size=2",
                                                                          "algo.optimizer.learning_rate=1e-3"])
    runtime = instantiate(dict(cfg.fabric))
    runtime.launch()
    runtime.seed_everything(7)
    policy, params = build_agent(runtime, (), False, cfg, None)
    assert policy.aux_coef == float(cfg.algo.mtp_coef) == 0.1
    params = runtime.replicate(runtime.to_param_dtype(params))
    tx = ppo.build_ppo_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm, runtime.precision)
    update = ppo.make_update_fn(runtime, policy, tx, cfg, list(cfg.algo.mlp_keys.encoder))

    prompt, _, actions, extras = _episodes(9, 4)
    rng = np.random.default_rng(9)
    data = {"prompt": jnp.asarray(prompt)[None], "actions": jnp.swapaxes(jnp.asarray(actions), 0, 1),
            "logprobs": jnp.asarray(extras["old_logp"]).T[..., None], "values": jnp.asarray(extras["old_values"]).T[..., None],
            "rewards": jnp.asarray(rng.normal(0, 1, (RESP, 4, 1)).astype(np.float32)),
            "dones": jnp.zeros((RESP, 4, 1), jnp.float32).at[-1].set(1.0)}
    clip, ent, lr = jnp.float32(0.2), jnp.float32(0.01), jnp.float32(1e-3)
    start = jax.tree_util.tree_map(jnp.copy, params)  # the call is donated its own
    got_params, _, metrics, probe = update(params, tx.init(params), data, {}, jax.random.PRNGKey(1), clip, ent, lr)

    returns, advantages = gae(data["rewards"], data["values"], data["dones"], jnp.zeros_like(data["values"][0]),
                              float(cfg.algo.gamma), float(cfg.algo.gae_lambda))
    rows = {k: jnp.swapaxes(v[..., 0], 0, 1) for k, v in
            {"logprobs": data["logprobs"], "values": data["values"], "returns": returns, "advantages": advantages}.items()}

    def loss(p, ids, mtp_coef):
        logp, entropy, values, aux = policy.evaluate_episodes(p, jnp.asarray(prompt)[ids], jnp.asarray(actions)[ids])
        adv = normalize_tensor(rows["advantages"][ids])
        return (ppo.policy_loss(logp, rows["logprobs"][ids], adv, clip, cfg.algo.loss_reduction)
                + cfg.algo.vf_coef * ppo.value_loss(values, rows["values"][ids], rows["returns"][ids], clip,
                                                     cfg.algo.clip_vloss, cfg.algo.loss_reduction)
                + ent * ppo.entropy_loss(entropy, cfg.algo.loss_reduction) + mtp_coef * aux["aux_loss"])

    rtol = 1e-4 if precision == "32-true" else 2e-2
    assert probe["losses"].shape == (2, 4) and len(probe["episodes"]) == 2
    ids = np.asarray(probe["episodes"])[0]
    by_hand = jax.grad(loss)(start, ids, 0.1)
    assert float(probe["grad_norm"][0]) == pytest.approx(float(optax.global_norm(by_hand)), rel=rtol)
    # a leaf only the MTP term reaches: its gradient is the term's, at the coefficient (0 without the term)
    eh_proj = float(jnp.sqrt(jnp.sum(jnp.square(by_hand["params"]["mtp"]["eh_proj"]))))
    assert eh_proj > 0 and not np.asarray(jax.grad(loss)(start, ids, 0.0)["params"]["mtp"]["eh_proj"]).any()
    assert float(probe["grad_leaf_norms"]["params"]["mtp"]["eh_proj"][0]) == pytest.approx(eh_proj, rel=rtol)
    assert float(metrics["MTP/loss"]) == pytest.approx(float(probe["losses"][:, 3].mean()))  # the fourth loss
    assert 3.0 < float(metrics["MTP/loss"]) < 5.5 and 0.0 <= float(metrics["MTP/top1_match"]) <= 1.0
    assert {"MoE/dropped", "MoE/short_buffer_share", "MoE/overflow_tokens", "MoE/load_l2_e3"} <= set(metrics)
    flat_new, flat_old = (dict(jax.tree_util.tree_leaves_with_path(t)) for t in (got_params, start))
    for path, new in flat_new.items():
        moved = float(jnp.abs(new.astype(jnp.float32) - flat_old[path].astype(jnp.float32)).max())
        if "bias" in jax.tree_util.keystr(path):
            assert moved == 0.0, jax.tree_util.keystr(path)  # no gradient, and Adam's step of a zero gradient is zero
        else:
            assert moved > 0.0, jax.tree_util.keystr(path)
    assert sum("bias" in jax.tree_util.keystr(path) for path in flat_new) == 3
