"""The Qwen3-Next policy (Gated DeltaNet linear attention and gated full
attention mixed 3:1, a gated shared expert beside softmax-routed experts)
against its plain reference (``qwen3_next_reference.py``: the delta rule token
by token), at tiny widths on the CPU in float32: hidden 64, one whole period of
four layers (linear x 3, full), linear layers of 2 key heads and 4 value heads
of 16, a full layer of 4 query heads sharing 2 key-value heads of 32 (8 of them
rotated), 8 experts top-2 beside the gated shared one, 4 held from 2,
vocabulary 64, prompt 8, response 72: the 80 positions cross a chunk of the
delta rule (64).

Tolerances: both sides compute in float32, in different orders (the chunked
WY form vs the recurrence token by token, blocked online softmax vs one
softmax, sorted grouped products vs a loop over experts), so values of order 1
agree to a few 1e-6; gradients sum thousands of such terms, hence 2e-4
relative to the leaf's largest entry, and 1e-8 at least: a strongly decaying
head's ``A_log`` and ``dt_bias`` take gradients of 1e-6 that are what is left of
terms of 1e-2 cancelling, and keep float32's noise on those terms (5.6e-9 read
under ``remat``, where the block is computed twice in another order).  The
zero-centred gains start at 0 and
are moved off it here, so that a gain read as ``w`` instead of ``1 + w`` shows.
"""

import dataclasses
import filecmp
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import qwen3_next as Q
from sheeprl_tpu.models import qwen3_next_reference as R

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
P, RESP = 8, 72
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=32, partial_rotary_factor=0.25,
    rope_theta=10000000.0, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, full_attention_interval=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=8, num_experts_per_tok=2, norm_topk_prob=True, num_hidden_layers=4,
    rms_norm_eps=1e-6, vocab_size=64, experts_held=4, expert_offset=2,
    attention_block=128, attention_interpret=True,  # the kernel through Pallas' interpreter: no TPU here
)
HYPER = dict(clip_coef=0.2, clip_vloss=False, vf_coef=0.5, ent_coef=0.01)
VALUE_ATOL = 5e-6
GRAD_RTOL, GRAD_ATOL = 2e-4, 1e-8


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

    cfg = Q.Qwen3NextConfig.from_mapping({**TINY, **(overrides or {})})
    return CausalLmPolicy(Q.Qwen3Next(cfg, jnp.float32, remat=remat), P, RESP), dataclasses.asdict(cfg)


def _params(policy, seed):
    """Seeded weights with every gain moved off its initial value (0 for the zero-centred ones)."""
    params = policy.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 100)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.2 * jax.random.normal(jax.random.fold_in(key, len(jax.tree_util.keystr(path))), x.shape)
        if "norm" in jax.tree_util.keystr(path) else x, params)


def _program_loss(policy, params, prompt, actions, extras):
    from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss

    logp, entropy, values, aux = policy.evaluate_episodes(params, jnp.asarray(prompt), jnp.asarray(actions))
    pg = policy_loss(logp, extras["old_logp"], extras["advantages"], HYPER["clip_coef"])
    vl = value_loss(values, extras["old_values"], extras["returns"], HYPER["clip_coef"], HYPER["clip_vloss"])
    ent = entropy_loss(entropy)
    total = pg + HYPER["vf_coef"] * vl + HYPER["ent_coef"] * ent
    return total, (jnp.stack([pg, vl, ent]), logp, entropy, values, aux)


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
        assert float(jnp.abs(a - b).max()) <= rtol * float(jnp.abs(b).max()) + GRAD_ATOL, jax.tree_util.keystr(path)


# ------------------------------------------------ (a) program vs reference
@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("seed", [0, 1])
def test_program_matches_reference(seed, remat):
    """Log-probabilities of every id at every position, values, entropies, the three losses, the
    routed layers' counts and every gradient."""
    policy, cfg = _policy(remat=remat)
    prompt, response, actions, extras = _episodes(seed, 2)
    params = _params(policy, seed)
    rparams = Q.reference_params(params)

    (loss, (losses, logp, entropy, values, aux)), grads = jax.value_and_grad(
        lambda p: _program_loss(policy, p, prompt, actions, extras), has_aux=True)(params)
    (rloss, routs), rgrads = jax.value_and_grad(
        lambda p: _reference_loss(p, cfg, prompt, response, extras), has_aux=True)(rparams)

    tokens = jnp.concatenate([jnp.asarray(prompt), jnp.asarray(response)], 1)
    u, _ = policy.model.apply(params, tokens, method=Q.Qwen3Next.hidden)
    logp_all, values_all = policy.model.apply(params, u, method=Q.Qwen3Next.logits)
    for b in range(2):
        ref_logits, ref_values = R.logits_of(rparams, tokens[b], cfg)
        np.testing.assert_allclose(np.asarray(logp_all[b]), np.asarray(jax.nn.log_softmax(ref_logits, -1)), atol=VALUE_ATOL)
        np.testing.assert_allclose(np.asarray(values_all[b]), np.asarray(ref_values), atol=VALUE_ATOL)
        for name, got in (("logp", logp), ("entropy", entropy), ("values", values)):
            np.testing.assert_allclose(np.asarray(got[b]), np.asarray(routs[b][name]), atol=VALUE_ATOL, err_msg=name)
    for i, name in enumerate(("pg", "vl", "ent")):
        assert abs(float(losses[i]) - float(np.mean([o[name] for o in routs]))) <= VALUE_ATOL, name
    assert abs(float(loss) - float(rloss)) <= VALUE_ATOL
    counts = sum(np.stack([np.asarray(a["counts"]) for a in o["aux"]]) for o in routs)
    np.testing.assert_array_equal(np.asarray(aux["load"]), counts)
    assert aux["load"].shape == (4, cfg["experts_held"]) and int(aux["dropped"].sum()) == 0
    _assert_trees_close(Q.reference_params(grads), rgrads, GRAD_RTOL)
    layer0 = grads["params"]["layer_0"]
    for name in ("A_log", "dt_bias", "conv", "norm"):  # the decay, the convolution and the gated norm all take part
        assert float(jnp.abs(layer0["mixer"][name]).max()) > 0, name
    assert float(jnp.abs(layer0["shared_gate"]).max()) > 0


def test_layers_are_the_kinds_the_interval_names():
    """Layers 3, 7, ... are full attention, the others linear; the default is the published 48 layers with
    12 full ones; heads that cannot share are refused."""
    cfg = Q.Qwen3NextConfig.from_mapping(TINY)
    assert [cfg.is_linear(i) for i in range(4)] == [True, True, True, False]
    assert (cfg.rotary_dim, cfg.conv_dim) == (8, 2 * 2 * 16 + 4 * 16)
    published = Q.Qwen3NextConfig.from_mapping({})
    assert sum(not published.is_linear(i) for i in range(published.num_hidden_layers)) == 12
    assert (published.rotary_dim, published.conv_dim, published.routed_spec.scoring) == (64, 8192, "softmax")
    with pytest.raises(ValueError, match="cannot share"):
        Q.Qwen3NextConfig.from_mapping({**TINY, "linear_num_value_heads": 3})
    policy, _ = _policy()
    params = policy.init(jax.random.PRNGKey(0))["params"]
    assert {k for k in params["layer_0"]["mixer"]} == {"w_qkvz", "w_ba", "conv", "A_log", "dt_bias", "norm", "w_out"}
    assert {k for k in params["layer_3"]["mixer"]} == {"wq", "wk", "wv", "wo", "q_norm", "k_norm"}
    assert not params["final_norm"].any() and not params["layer_3"]["mixer"]["q_norm"].any()  # zero-centred gains start at 0
    a = jnp.exp(params["layer_0"]["mixer"]["A_log"])
    assert float(a.min()) > 0 and float(a.max()) <= 16 and bool((params["layer_0"]["mixer"]["dt_bias"] == 1).all())


# --------------------------------------------------- (b) the shares add up
@pytest.mark.parametrize("held", [1, 2, 4])
def test_shares_add_up_to_the_uncut_layer(held):
    """The routed parts the 16 / held shares of one layer of 16 experts compute (``expert_offset`` 0, held,
    2 held, ...: 16 shares of one expert, as the 16-way deployment holds 32 of 512), with the gated shared
    expert counted once, add up to the uncut reference layer's MLP output ``f``: what the shares of an
    expert-parallel layer sum."""
    n_experts = 16
    full_cfg = {**TINY, "num_experts": n_experts, "experts_held": n_experts, "expert_offset": 0, "num_hidden_layers": 1}
    lp = R.init_params(jax.random.PRNGKey(3), full_cfg)["layers"][0]
    m = jax.random.normal(jax.random.PRNGKey(4), (P + RESP, TINY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        whole, _ = R.routed_mlp(lp, m, full_cfg)
        alike, _ = R.routed_mlp(lp, m, {**full_cfg, "experts_held": 0})
    parts = []
    for offset in range(0, n_experts, held):
        cfg = Q.Qwen3NextConfig.from_mapping({**full_cfg, "experts_held": held, "expert_offset": offset})
        share = {"params": {"router": lp["router"], **{k: lp[k][offset:offset + held] for k in ("w_gate", "w_up", "w_down")}}}
        y, aux = Q.RoutedExperts(cfg, jnp.float32).apply(share, m)
        assert int(aux["dropped"]) == 0
        parts.append(y)
    got = sum(parts) + alike  # the gated shared expert's part (no expert held), once
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole), atol=2e-5)


# ------------------------ (c) prefill + cached decoding equals the full pass
@pytest.mark.parametrize("seed", [0, 1])
def test_cached_decoding_equals_the_full_pass(seed):
    """Prefill the prompt (the chunked rule, the kernel), then one cached pass a token (the recurrence over
    each linear layer's state and convolution tail, the full layer against its keys and values).  The
    log-probabilities at every response position equal the one causal pass's and the reference's."""
    from sheeprl_tpu.ops import gated_delta_rule

    policy, cfg = _policy(remat=False)
    model = policy.model
    prompt, response, _, _ = _episodes(seed, 2)
    params = _params(policy, seed)
    tokens = jnp.concatenate([jnp.asarray(prompt), jnp.asarray(response)], 1)
    u_full, _ = model.apply(params, tokens, method=Q.Qwen3Next.hidden)
    full, _ = model.apply(params, u_full, method=Q.Qwen3Next.logits)

    gated_delta_rule.take_engaged()
    gated_delta_rule._SEEN.clear()
    u, _, entries = model.apply(params, tokens[:, :P], True, method=Q.Qwen3Next.hidden)
    cache = Q.Qwen3Next.prefill_cache(entries, P + RESP)
    # two kinds of state side by side: fixed-size recurrent entries, and keys and values a position each
    assert [type(e).__name__ for e in cache] == ["RecurrentCache"] * 3 + ["tuple"]
    assert cache[0].state.shape == (2, 4, 16, 16) and cache[0].conv.shape == (2, 3, 2 * 2 * 16 + 4 * 16)
    assert [x.shape for x in cache[3]] == [(2, P + RESP, 2, 32)] * 2
    step = jax.jit(lambda tok, cache, length: model.apply(params, tok, cache, length, method=Q.Qwen3Next.step))
    rows = [model.apply(params, u[:, -1], method=Q.Qwen3Next.logits)[0]]
    for i in range(P, P + RESP):
        ui, _, cache = step(tokens[:, i:i + 1], cache, jnp.int32(i))
        rows.append(model.apply(params, ui[:, 0], method=Q.Qwen3Next.logits)[0])
    got = jnp.stack(rows, 1)  # positions P - 1 .. P + RESP - 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, P - 1:]), atol=VALUE_ATOL)
    ref_logits, _ = R.logits_of(Q.reference_params(params), tokens[0], cfg)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(jax.nn.log_softmax(ref_logits[P - 1:], -1)), atol=VALUE_ATOL)
    assert {r["form"] for r in gated_delta_rule.take_engaged()} == {"chunked", "recurrent"}


def test_the_reference_layer_hands_over_the_prefill_state():
    """The reference's own final state and convolution tail of a linear layer are what the program's prefill
    hands the cache."""
    policy, cfg = _policy(remat=False)
    params = _params(policy, 2)
    a = jax.random.normal(jax.random.PRNGKey(5), (1, P, TINY["hidden_size"]))
    mixer = Q.GatedDeltaNet(policy.cfg, jnp.float32)
    _, entry = mixer.apply({"params": params["params"]["layer_0"]["mixer"]}, a)
    with jax.default_matmul_precision("highest"):
        _, state, tail = R.linear_attention(params["params"]["layer_0"]["mixer"], a[0], cfg)
    np.testing.assert_allclose(np.asarray(entry.state[0]), np.asarray(state), atol=VALUE_ATOL)
    np.testing.assert_allclose(np.asarray(entry.conv[0]), np.asarray(tail), atol=VALUE_ATOL)


# --------------------------------------------- (d) the reference's two copies
def test_reference_copies_are_byte_identical():
    assert filecmp.cmp(
        os.path.join(ROOT, "sheeprl_tpu", "models", "qwen3_next_reference.py"),
        os.path.join(ROOT, "chipbench", "reference", "qwen3_next.py"), shallow=False,
    )


def test_reference_imports_nothing_of_the_repository():
    with open(os.path.join(ROOT, "sheeprl_tpu", "models", "qwen3_next_reference.py")) as f:
        imports = [line for line in f if line.startswith(("import ", "from "))]
    assert not any("sheeprl" in line or "chipbench" in line for line in imports), imports


# ----------------------------------------------------- (e) through the CLI
def lm_overrides(tmp_path, precision="32-true", envs=2, response=RESP):
    """``exp=ppo_qwen3_next`` at tiny widths: one whole period of four layers."""
    return [
        "exp=ppo_qwen3_next", "fabric.accelerator=cpu", f"fabric.precision={precision}", f"env.num_envs={envs}",
        "env.wrapper.vocab_size=64", "env.wrapper.mask_id=63", f"env.wrapper.prompt_len={P}",
        f"env.wrapper.response_len={response}", "algo.per_rank_batch_size=1", "algo.qwen3_next.hidden_size=64",
        "algo.qwen3_next.num_attention_heads=4", "algo.qwen3_next.num_key_value_heads=2", "algo.qwen3_next.head_dim=32",
        "algo.qwen3_next.linear_num_key_heads=2", "algo.qwen3_next.linear_num_value_heads=4",
        "algo.qwen3_next.linear_key_head_dim=16", "algo.qwen3_next.linear_value_head_dim=16",
        "algo.qwen3_next.moe_intermediate_size=32", "algo.qwen3_next.shared_expert_intermediate_size=32",
        "algo.qwen3_next.num_experts=8", "algo.qwen3_next.num_experts_per_tok=2", "algo.qwen3_next.experts_held=4",
        "algo.qwen3_next.attention_block=128", "algo.qwen3_next.attention_interpret=True",
        f"algo.total_steps={envs * response}", f"metric.log_every={envs * response}", f"root_dir={tmp_path}",
        "run_name=qwen3next", "checkpoint.every=0",
    ]


def test_cli_runs_a_ppo_iteration(tmp_path):
    """``sheeprl.py exp=ppo_qwen3_next`` at tiny widths: one iteration through ``ppo.main``, the fused causal
    collector and ``make_episode_update_fn``; the telemetry carries the routed layers' counters and how the
    delta rule was built: chunked for the prefill and the update, recurrent for the cached passes."""
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.ops import gated_delta_rule

    gated_delta_rule.take_engaged()
    gated_delta_rule._SEEN.clear()
    run(lm_overrides(tmp_path, response=16))
    records = [json.loads(line) for path in glob.glob(f"{tmp_path}/qwen3next/*/telemetry.jsonl") for line in open(path)]
    moe = [r["moe"] for r in records if "moe" in r]
    assert len(moe) == 1 and moe[0]["dropped"] == 0 and "load_l3_e3" in moe[0] and "load_l4_e0" not in moe[0], records
    (gdn,) = [r["gdn"] for r in records if "gdn" in r]
    n = P + 16
    assert sorted((k["form"], k["tokens"]) for k in gdn) == [("chunked", P), ("chunked", n), ("recurrent", 1)], gdn
    assert {(k["key_heads"], k["value_heads"], k["d_k"], k["d_v"]) for k in gdn} == {(2, 4, 16, 16)}
    assert records[-1]["jaxenv"]["env_steps"] == 2 * 16


def test_policy_kind_builds_the_model_its_section_names(tmp_path):
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.lm_policy import language_model_policy
    from sheeprl_tpu.config import compose, instantiate

    cfg = compose(overrides=lm_overrides(tmp_path))
    kind = language_model_policy(cfg)
    assert (kind.name, kind.model_class, kind.section, kind.collector) == (
        "qwen3_next", Q.Qwen3Next, "qwen3_next", "FusedCausalCollector")
    runtime = instantiate(dict(cfg.fabric))
    runtime.launch()
    policy, params = build_agent(runtime, (), False, cfg, None)
    assert isinstance(policy.model, Q.Qwen3Next) and policy.aux_coef == 0.0
    assert policy.cfg.routed_spec == Q.RoutedSpec(64, 32, 8, 2, 4, 0, True, "softmax")
    published = compose(overrides=["exp=ppo_qwen3_next"])
    assert (published.algo.qwen3_next.num_hidden_layers, published.algo.qwen3_next.experts_held,
            published.env.wrapper.vocab_size) == (4, 32, 18992)
