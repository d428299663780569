"""The AFMoE policy (sliding-window and global attention mixed, gated outputs,
sandwich norms, a shared expert beside sigmoid-routed experts) against its
plain reference (``afmoe_reference.py``), at tiny widths on the CPU in
float32: hidden 64, 4 query heads sharing 2 key-value heads of 16, window 6,
three layers (sliding and dense; sliding and routed; full, NoPE and routed),
8 experts top-2 beside one shared, 4 held from 2, vocabulary 64, prompt 8,
response 16, so that 18 of the 24 positions lie past the window.

Tolerances: both sides compute in float32, in different orders (blocked
online softmax vs one softmax, sorted grouped products vs a loop over
experts), so values of order 1 agree to a few 1e-6; gradients sum thousands
of such terms, hence 2e-4 relative to the leaf's largest entry.  What each
part of the mathematics moves is far above that: the gate, a window, a rotary
embedding or a norm changes log-probabilities by 1e-3 to 1e-1 at these weights
(the PR's notes show each left out once).
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

from sheeprl_tpu.models import afmoe as A
from sheeprl_tpu.models import afmoe_reference as R

from .test_mla_moe import _kernel_calls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
P, RESP, WINDOW = 8, 16, 6
LAYERS = ("sliding_attention", "sliding_attention", "full_attention")
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, num_shared_experts=1, num_dense_layers=1,
    num_hidden_layers=3, layer_types=LAYERS, sliding_window=WINDOW, rope_theta=10000.0, rms_norm_eps=1e-5,
    route_norm=True, route_scale=2.826, score_func="sigmoid", mup_enabled=True, vocab_size=64, experts_held=4,
    expert_offset=2,
    attention_block=128, attention_interpret=True,  # the kernel through Pallas' interpreter: no TPU here
)
HYPER = dict(clip_coef=0.2, clip_vloss=False, vf_coef=0.5, ent_coef=0.01)
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

    cfg = A.AfmoeConfig.from_mapping({**TINY, **(overrides or {})})
    return CausalLmPolicy(A.Afmoe(cfg, jnp.float32, remat=remat), P, RESP), dataclasses.asdict(cfg)


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
        scale = float(jnp.abs(b).max()) + 1e-6
        assert float(jnp.abs(a - b).max()) <= rtol * scale, jax.tree_util.keystr(path)


# ------------------------------------------------ (a) program vs reference
@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("seed", [0, 1])
def test_program_matches_reference(seed, remat):
    """Log-probabilities of every id at every position, values, entropies, the three losses, the
    routed layers' counts and every gradient, with a window shorter than the sequence."""
    policy, cfg = _policy(remat=remat)
    prompt, response, actions, extras = _episodes(seed, 2)
    params = policy.init(jax.random.PRNGKey(seed))
    rparams = A.reference_params(params)

    (loss, (losses, logp, entropy, values, aux)), grads = jax.value_and_grad(
        lambda p: _program_loss(policy, p, prompt, actions, extras), has_aux=True)(params)
    (rloss, routs), rgrads = jax.value_and_grad(
        lambda p: _reference_loss(p, cfg, prompt, response, extras), has_aux=True)(rparams)

    tokens = jnp.concatenate([jnp.asarray(prompt), jnp.asarray(response)], 1)
    u, _ = policy.model.apply(params, tokens, method=A.Afmoe.hidden)
    logp_all, values_all = policy.model.apply(params, u, method=A.Afmoe.logits)
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
    assert aux["load"].shape == (2, cfg["experts_held"]) and int(aux["dropped"].sum()) == 0
    _assert_trees_close(A.reference_params(grads), rgrads, GRAD_RTOL)
    for tree in (A.reference_params(grads), rgrads):  # the selection bias enters the top-k only
        assert all(not np.asarray(block["bias"]).any() for block in tree["layers"][1:])
    assert float(jnp.abs(grads["params"]["layer_2"]["attn"]["w_out_gate"]).max()) > 0


def test_layers_are_the_kinds_the_config_names():
    """``layer_types`` decides window and rotation layer by layer; the default is the published list,
    and a list that does not name every layer is refused."""
    cfg = A.AfmoeConfig.from_mapping(TINY)
    assert [cfg.window(i) for i in range(3)] == [WINDOW, WINDOW, 0]
    published = A.AfmoeConfig.from_mapping({})
    assert published.layer_types == (("sliding_attention",) * 3 + ("full_attention",)) * 8
    for wrong in ({**TINY, "layer_types": LAYERS[:2]}, {"num_hidden_layers": 8}):
        with pytest.raises(ValueError, match="layer_types"):
            A.AfmoeConfig.from_mapping(wrong)


@pytest.mark.parametrize("remat", [True, False])
def test_backward_pass_runs_the_forward_kernel_once_a_block(remat):
    """Both kinds of layer keep the kernel's output under ``remat``: one forward kernel a block."""
    policy, cfg = _policy(remat=remat)
    prompt, _, actions, extras = _episodes(0, 2)
    params = policy.init(jax.random.PRNGKey(0))
    grad = jax.make_jaxpr(jax.grad(lambda p: _program_loss(policy, p, prompt, actions, extras)[0]))(params)
    blocks = cfg["num_hidden_layers"]
    assert _kernel_calls(grad.jaxpr) == {
        "splash_mqa_fwd_residuals": blocks, "splash_mqa_dkv_no_residuals": blocks, "splash_mqa_dq_no_residuals": blocks}


# --------------------------------------------------- (b) the shares add up
@pytest.mark.parametrize("held", [1, 2, 4])
def test_shares_add_up_to_the_uncut_layer(held):
    """The routed parts the 8 / held shares of one routed sliding block compute (``expert_offset`` 0,
    held, 2 held, ...), with the shared expert counted once, add up to the uncut reference block's MLP
    output ``f``: what the shares of an expert-parallel layer sum before the post-MLP norm."""
    n_experts = TINY["num_experts"]
    full_cfg = {**TINY, "experts_held": n_experts, "expert_offset": 0, "num_hidden_layers": 1, "num_dense_layers": 0,
                "layer_types": LAYERS[:1]}
    lp = R.init_params(jax.random.PRNGKey(3), full_cfg)["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(4), (P + RESP, TINY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        _, whole = R.routed_layer(lp, h, jnp.arange(P + RESP), WINDOW, True, full_cfg)
        _, alike = R.routed_layer(lp, h, jnp.arange(P + RESP), WINDOW, True, {**full_cfg, "experts_held": 0})
    parts = []
    for offset in range(0, n_experts, held):
        cfg = A.AfmoeConfig.from_mapping({**full_cfg, "experts_held": held, "expert_offset": offset})
        share = {"params": {"router": lp["router"], "bias": lp["bias"],
                            **{k: lp[k][offset:offset + held] for k in ("w_gate", "w_up", "w_down")}}}
        y, aux = A.RoutedExperts(cfg, jnp.float32).apply(share, whole["mlp_in"])
        assert int(aux["dropped"]) == 0
        parts.append(y)
    got = sum(parts) + alike["mlp_out"]  # the shared expert's part (no expert held), once
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole["mlp_out"]), atol=2e-5)


# ----------------- (c) cached decoding equals the full pass past the window
@pytest.mark.parametrize("seed", [0, 1])
def test_cached_decoding_equals_the_full_pass(seed):
    """Prefill the prompt (full-sequence form), then one cached pass a token: a sliding layer reads the
    window's slice of the cache, the full layer all of it.  The log-probabilities at every response
    position, 18 of them past the window, equal the one causal pass's and the reference's."""
    policy, cfg = _policy(remat=False)
    model = policy.model
    prompt, response, _, _ = _episodes(seed, 2)
    params = policy.init(jax.random.PRNGKey(seed))
    tokens = jnp.concatenate([jnp.asarray(prompt), jnp.asarray(response)], 1)
    u_full, _ = model.apply(params, tokens, method=A.Afmoe.hidden)
    full, _ = model.apply(params, u_full, method=A.Afmoe.logits)

    u, _, kvs = model.apply(params, tokens[:, :P], True, method=A.Afmoe.hidden)
    cache = [tuple(jnp.zeros((2, P + RESP) + x.shape[2:], x.dtype).at[:, :P].set(x) for x in kv) for kv in kvs]
    assert [tuple(c.shape[2:] for c in kv) for kv in cache] == [((2, 16), (2, 16))] * 3
    step = jax.jit(lambda tok, cache, length: model.apply(params, tok, cache, length, method=A.Afmoe.step))
    rows = [model.apply(params, u[:, -1], method=A.Afmoe.logits)[0]]
    for i in range(P, P + RESP):
        ui, _, cache = step(tokens[:, i:i + 1], cache, jnp.int32(i))
        rows.append(model.apply(params, ui[:, 0], method=A.Afmoe.logits)[0])
    got = jnp.stack(rows, 1)  # positions P - 1 .. P + RESP - 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, P - 1:]), atol=VALUE_ATOL)
    ref_logits, _ = R.logits_of(A.reference_params(params), tokens[0], cfg)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(jax.nn.log_softmax(ref_logits[P - 1:], -1)), atol=VALUE_ATOL)


# --------------------------------------------- (d) the reference's two copies
def test_reference_copies_are_byte_identical():
    assert filecmp.cmp(
        os.path.join(ROOT, "sheeprl_tpu", "models", "afmoe_reference.py"),
        os.path.join(ROOT, "chipbench", "reference", "afmoe.py"), shallow=False,
    )


def test_reference_imports_nothing_of_the_repository():
    with open(os.path.join(ROOT, "sheeprl_tpu", "models", "afmoe_reference.py")) as f:
        imports = [line for line in f if line.startswith(("import ", "from "))]
    assert not any("sheeprl" in line or "chipbench" in line for line in imports), imports


# ----------------------------------------------------- (e) through the CLI
def lm_overrides(tmp_path, precision="32-true", envs=2):
    """``exp=ppo_trinity_mini`` at tiny widths: two routed layers after the dense one, window 6."""
    return [
        "exp=ppo_trinity_mini", "fabric.accelerator=cpu", f"fabric.precision={precision}", f"env.num_envs={envs}",
        "env.wrapper.vocab_size=64", "env.wrapper.mask_id=63", f"env.wrapper.prompt_len={P}", f"env.wrapper.response_len={RESP}",
        "algo.per_rank_batch_size=1", "algo.afmoe.hidden_size=64", "algo.afmoe.num_attention_heads=4",
        "algo.afmoe.num_key_value_heads=2", "algo.afmoe.head_dim=16", "algo.afmoe.intermediate_size=96",
        "algo.afmoe.moe_intermediate_size=32", "algo.afmoe.num_experts=8", "algo.afmoe.num_experts_per_tok=2",
        "algo.afmoe.num_hidden_layers=3", f"algo.afmoe.layer_types=[{','.join(LAYERS)}]",
        f"algo.afmoe.sliding_window={WINDOW}", "algo.afmoe.experts_held=4",
        "algo.afmoe.attention_block=128", "algo.afmoe.attention_interpret=True", f"algo.total_steps={envs * RESP}",
        f"metric.log_every={envs * RESP}", f"root_dir={tmp_path}", "run_name=trinity", "checkpoint.every=0",
    ]


def test_cli_runs_a_ppo_iteration(tmp_path):
    """``sheeprl.py exp=ppo_trinity_mini`` at tiny widths: one iteration through ``ppo.main``, the fused
    causal collector and ``make_episode_update_fn``; the telemetry carries the routed layers' counters and
    how both kinds of kernel engaged (the prefill's and the update's, banded and causal) and which form the q/k
    norms took (heads of 16 numbers fill no lane tile: the composed form everywhere)."""
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.ops import block_sparse_attention as op
    from sheeprl_tpu.ops import qk_norm_rope

    op._splash_kernel.cache_clear()
    op.take_engaged()
    qk_norm_rope.take_engaged()
    qk_norm_rope._SEEN.clear()
    run(lm_overrides(tmp_path))
    records = [json.loads(line) for path in glob.glob(f"{tmp_path}/trinity/*/telemetry.jsonl") for line in open(path)]
    moe = [r["moe"] for r in records if "moe" in r]
    assert len(moe) == 1 and moe[0]["dropped"] == 0 and "load_l1_e3" in moe[0] and "load_l2_e0" not in moe[0], records
    assert not any("mtp" in r for r in records)
    (attention,) = [r["attention"] for r in records if "attention" in r]
    got = sorted((k["s_q"], k["window"], k["pairs"]) for k in attention)
    n = P + RESP
    assert got == [(P, 0, P * (P + 1) // 2), (P, WINDOW, 33), (n, 0, n * (n + 1) // 2), (n, WINDOW, 129)], got
    (qk,) = [r["qk_norm"] for r in records if "qk_norm" in r]
    # the prefill's, the cached passes' and the update's q (4 heads) and k (2), rotated in the sliding layers only
    assert {(k["batch"], k["tokens"], k["heads"], k["rotate"]) for k in qk} == {
        (b, t, h, r) for b, t in ((2, P), (2, 1), (1, n)) for h in (4, 2) for r in (True, False)}, qk
    assert {k["form"] for k in qk} == {"composed"}
    assert records[-1]["jaxenv"]["env_steps"] == 2 * RESP


def test_policy_kind_builds_the_model_its_section_names(tmp_path):
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.lm_policy import language_model_policy
    from sheeprl_tpu.config import compose, instantiate

    cfg = compose(overrides=lm_overrides(tmp_path))
    kind = language_model_policy(cfg)
    assert (kind.name, kind.model_class, kind.section, kind.collector) == ("afmoe", A.Afmoe, "afmoe", "FusedCausalCollector")
    runtime = instantiate(dict(cfg.fabric))
    runtime.launch()
    policy, params = build_agent(runtime, (), False, cfg, None)
    assert isinstance(policy.model, A.Afmoe) and policy.cfg.layer_types == LAYERS and policy.aux_coef == 0.0
    assert set(params["params"]["layer_2"]["attn"]) == {"wq", "wk", "wv", "w_out_gate", "wo", "q_norm", "k_norm"}
