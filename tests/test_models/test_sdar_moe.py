"""The SDAR-MoE policy against its plain reference (``sdar_moe_reference.py``),
at tiny widths on the CPU in float32: hidden 64, 2 layers, 8 experts top-2,
vocabulary 64, block 4, prompt 8, response 16.

Tolerances: both sides compute in float32, in different orders (blocked
online softmax vs one softmax, sorted grouped products vs a loop over
experts), so values of order 1 agree to a few 1e-6; gradients sum thousands
of such terms, hence 2e-4 relative to the leaf's largest entry.
"""

import filecmp
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import sdar_moe as M
from sheeprl_tpu.models import sdar_moe_reference as R
from sheeprl_tpu.ops.block_sparse_attention import SegmentMask, block_sparse_flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
P, RESP, BLOCK = 8, 16, 4
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, rope_theta=1e6, rms_norm_eps=1e-6,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True, moe_intermediate_size=32, num_hidden_layers=2,
    vocab_size=64, experts_held=4, expert_offset=2, block_length=BLOCK, denoise_steps=BLOCK, mask_id=63,
    attention_block=128, attention_interpret=True,  # the kernel through Pallas' interpreter: no TPU here
)
HYPER = dict(clip_coef=0.2, clip_vloss=False, vf_coef=0.5, ent_coef=0.01)
VALUE_ATOL = 5e-6
GRAD_RTOL = 2e-4


def _episodes(seed, n):
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, 63, (n, P))
    response = rng.integers(0, 63, (n, RESP))
    order = np.stack([np.stack([rng.permutation(BLOCK) for _ in range(RESP // BLOCK)]) for _ in range(n)])
    taken = np.take_along_axis(response.reshape(n, -1, BLOCK), order, -1)
    actions = np.stack([order.reshape(n, -1), taken.reshape(n, -1)], -1).astype(np.int32)
    extras = {
        "old_logp": rng.normal(-4.0, 0.3, (n, RESP)).astype(np.float32),
        "old_values": rng.normal(0, 0.1, (n, RESP)).astype(np.float32),
        "advantages": rng.normal(0, 1, (n, RESP)).astype(np.float32),
        "returns": rng.normal(0, 1, (n, RESP)).astype(np.float32),
    }
    return prompt, response, order, actions, extras


def _policy(overrides=None, remat=True):
    from sheeprl_tpu.algos.ppo.sdar_policy import SdarPolicy

    cfg = {**TINY, **(overrides or {})}
    return SdarPolicy(M.SdarConfig.from_mapping(cfg), P, RESP, jnp.float32, remat=remat), cfg


def _program_loss(policy, params, prompt, actions, extras):
    from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss

    logp, entropy, values, aux = policy.evaluate_episodes(params, jnp.asarray(prompt), jnp.asarray(actions))
    pg = policy_loss(logp, extras["old_logp"], extras["advantages"], HYPER["clip_coef"])
    vl = value_loss(values, extras["old_values"], extras["returns"], HYPER["clip_coef"], HYPER["clip_vloss"])
    ent = entropy_loss(entropy)
    return pg + HYPER["vf_coef"] * vl + HYPER["ent_coef"] * ent, (logp, values, aux)


def _reference_loss(rparams, cfg, prompt, response, order, extras):
    total, outs = 0.0, []
    for b in range(len(prompt)):
        ep = {"prompt": prompt[b], "response": response[b], "order": order[b], **{k: v[b] for k, v in extras.items()}}
        loss, out = R.ppo_loss_episode(rparams, ep, cfg, HYPER)
        total, outs = total + loss / len(prompt), outs + [out]
    return total, outs


def _assert_trees_close(got, want, rtol):
    flat = jax.tree_util.tree_leaves_with_path(got)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(b).max()) + 1e-6
        assert float(jnp.abs(a - b).max()) <= rtol * scale, jax.tree_util.keystr(path)


# ------------------------------------------------ (a) program vs reference
@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("seed", [0, 1])
def test_program_matches_reference(seed, remat):
    policy, cfg = _policy(remat=remat)
    prompt, response, order, actions, extras = _episodes(seed, 2)
    params = policy.init(jax.random.PRNGKey(seed))
    rparams = M.reference_params(params)

    (loss, (logp, values, aux)), grads = jax.value_and_grad(
        lambda p: _program_loss(policy, p, prompt, actions, extras), has_aux=True
    )(params)
    (rloss, routs), rgrads = jax.value_and_grad(
        lambda p: _reference_loss(p, cfg, prompt, response, order, extras), has_aux=True
    )(rparams)

    tokens, at = policy.layout.pack(jnp.asarray(prompt), jnp.asarray(actions), cfg["mask_id"])
    for b in range(2):
        rtokens, rat = R.pack_episode(prompt[b], response[b], order[b], BLOCK, BLOCK, cfg["mask_id"])
        np.testing.assert_array_equal(np.asarray(tokens[b]), rtokens)
        np.testing.assert_array_equal(np.asarray(at[b]), rat)
        np.testing.assert_allclose(np.asarray(logp[b]), np.asarray(routs[b]["logp"]), atol=VALUE_ATOL)
        np.testing.assert_allclose(np.asarray(values[b]), np.asarray(routs[b]["values"]), atol=VALUE_ATOL)
    # whole rows of the policy's distribution at the action positions, not only the taken token's
    hidden, _ = policy.model.apply(params, tokens, policy.layout, method=M.SdarMoE.hidden)
    logp_all, _ = policy.model.apply(params, hidden[0][at[0]], method=M.SdarMoE.score)
    ref_layout = R.packed_layout(P, RESP, BLOCK, BLOCK)
    rtokens, rat = R.pack_episode(prompt[0], response[0], order[0], BLOCK, BLOCK, cfg["mask_id"])
    ref_hidden, _ = R.forward(rparams, jnp.asarray(rtokens), jnp.asarray(ref_layout["pos"]),
                              jnp.asarray(R.dense_mask(ref_layout)), cfg)
    with jax.default_matmul_precision("highest"):
        ref_logits = ref_hidden[rat] @ rparams["head"]
    ref_logp_all = jax.nn.log_softmax(jnp.where(jnp.arange(64) == 63, -jnp.inf, ref_logits), -1)
    np.testing.assert_allclose(np.asarray(logp_all[:, :63]), np.asarray(ref_logp_all[:, :63]), atol=VALUE_ATOL)
    assert float(jnp.exp(logp_all[:, 63]).max()) == 0.0  # [MASK] is never drawn

    assert abs(float(loss) - float(rloss)) <= VALUE_ATOL
    _assert_trees_close(M.reference_params(grads), rgrads, GRAD_RTOL)
    # the counters: assignments per held expert equal the reference's own count
    ref_counts = sum(np.stack([np.asarray(a["counts"]) for a in out["aux"]]) for out in routs)
    np.testing.assert_array_equal(np.asarray(aux["load"]), ref_counts)
    assert int(aux["dropped"].sum()) == 0


# --------------------------------------------------- (b) the shares add up
@pytest.mark.parametrize("held", [1, 2, 4])
def test_shares_add_up_to_the_uncut_layer(held):
    """The 8 / held shares of one layer (``expert_offset`` 0, held, 2 held,
    ...), with the residual counted once, give the uncut reference layer."""
    n_experts = TINY["num_experts"]
    full_cfg = {**TINY, "experts_held": n_experts, "expert_offset": 0, "num_hidden_layers": 1}
    rfull = R.init_params(jax.random.PRNGKey(3), full_cfg)
    lp = rfull["layers"][0]
    layout = R.packed_layout(P, RESP, BLOCK, BLOCK)
    n = len(layout["pos"])
    h = jax.random.normal(jax.random.PRNGKey(4), (n, TINY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        want, _ = R.layer(lp, h, jnp.asarray(layout["pos"]), jnp.asarray(R.dense_mask(layout)), full_cfg)

    episode = M.EpisodeLayout(P, RESP, BLOCK, BLOCK)
    parts = []
    for offset in range(0, n_experts, held):
        cfg = M.SdarConfig.from_mapping({**full_cfg, "experts_held": held, "expert_offset": offset})
        layer = M.SdarLayer(cfg, jnp.float32)
        share = {"params": {
            "norm1": lp["norm1"], "norm2": lp["norm2"],
            "attn": {k: lp[k] for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")},
            "moe": {"router": lp["router"], **{k: lp[k][offset:offset + held] for k in ("w_gate", "w_up", "w_down")}},
        }}
        out, aux, _ = layer.apply(share, h[None], jnp.asarray(episode.positions), episode)
        parts.append(out[0])
        assert int(aux["dropped"]) == 0
    # out_s = h1 + y_s, and every share computes the residual h1 alike: it is counted once
    with jax.default_matmul_precision("highest"):
        h1, _ = R.layer(lp, h, jnp.asarray(layout["pos"]), jnp.asarray(R.dense_mask(layout)),
                        {**full_cfg, "experts_held": 0})
    got = sum(parts) - (len(parts) - 1) * h1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------- (c) blocked masked attention vs dense mask
def _dense_attention(q, k, v, mask):
    rep = q.shape[-2] // k.shape[-2]
    kk, vv = jnp.repeat(k, rep, axis=-2), jnp.repeat(v, rep, axis=-2)
    scores = jnp.einsum("...qhd,...khd->...hqk", q, kk) / jnp.sqrt(jnp.float32(q.shape[-1]))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.swapaxes(jnp.einsum("...hqk,...khd->...hqd", probs, vv), -3, -2)


@pytest.mark.parametrize("sizes", [(P, RESP, 16), (P + RESP, 0, 16), (128, 128, 128), (256, 0, 128)],
                         ids=["packed", "clean_only", "packed_6_tiles", "clean_only_2_tiles"])
def test_blocked_masked_attention_matches_dense(sizes):
    """The block-sparse flash kernel (interpreted here) under the episode's
    mask: forward and gradient against the dense-mask reference, where the
    sizes are padded (88 positions, heads of 16) and where they are whole
    tiles."""
    prompt_len, response_len, head = sizes
    episode = M.EpisodeLayout(prompt_len, response_len, BLOCK, BLOCK)
    ref_layout = R.packed_layout(prompt_len, response_len, BLOCK, BLOCK)
    dense = jnp.asarray(R.dense_mask(ref_layout))
    np.testing.assert_array_equal(episode.mask.dense(), np.asarray(dense))
    n = episode.length
    keys = jax.random.split(jax.random.PRNGKey(n + head), 3)
    q = jax.random.normal(keys[0], (2, n, 4, head))
    k = jax.random.normal(keys[1], (2, n, 2, head))
    v = jax.random.normal(keys[2], (2, n, 2, head))

    def blocked(q, k, v):
        return block_sparse_flash_attention(q, k, v, episode.mask, block_size=128, interpret=True)

    # (two traces, one kernel object: its block tables must not be values of the first trace)
    np.testing.assert_allclose(np.asarray(jax.jit(blocked)(q, k, v)), np.asarray(_dense_attention(q, k, v, dense)), atol=1e-5)
    got = jax.jit(jax.grad(lambda *a: (blocked(*a) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(lambda *a: (_dense_attention(*a, dense) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_blocked_attention_skips_unseen_tiles():
    """The kernel's block tables hold only tiles in which some query sees some
    key: under a third of all at an episode's shape."""
    from sheeprl_tpu.ops.block_sparse_attention import _as_bytes, _one_tile, _splash_kernel

    episode = M.EpisodeLayout(256, 512, BLOCK, BLOCK)  # 768 clean + 2,048 noised positions, tiles of 128
    dense = episode.mask.dense()
    n = dense.shape[0]
    n_tiles = n // 128
    needed = sum(dense[a * 128:(a + 1) * 128, b * 128:(b + 1) * 128].any() for a in range(n_tiles) for b in range(n_tiles))
    kernel = _splash_kernel(_as_bytes(episode.mask), 1, _one_tile(128), False, True)
    visited = np.asarray(kernel.fwd_mask_info.block_mask) != 0  # (heads, query tiles, steps): the grid a query tile walks
    assert int(visited.sum()) == needed < n_tiles * n_tiles // 3
    assert visited.shape[-1] < n_tiles // 2  # no query tile walks more than its own keys' tiles


def test_blocked_attention_refuses_what_it_cannot_run():
    q = jnp.zeros((1, 128, 3, 128))
    kv = jnp.zeros((1, 128, 2, 128))
    with pytest.raises(ValueError, match="cannot share"):
        block_sparse_flash_attention(q, kv, kv, SegmentMask.causal(128, 128), interpret=True)
    with pytest.raises(ValueError, match="multiples of 128"):
        block_sparse_flash_attention(q[:, :, :2], kv, kv, SegmentMask.causal(128, 128), block_size=64, interpret=True)


def test_model_refuses_a_cpu_without_the_interpreter():
    """The model has one masked attention and no fallback: off a TPU, without
    ``attention_interpret``, the call fails instead of running something else."""
    cfg = M.SdarConfig.from_mapping({**TINY, "attention_interpret": False, "num_hidden_layers": 1})
    layout = M.EpisodeLayout(P, RESP, BLOCK, BLOCK)
    tokens = jnp.zeros((1, layout.length), jnp.int32)
    with pytest.raises(Exception, match="(?i)interpret"):
        jax.block_until_ready(M.SdarMoE(cfg, jnp.float32).init(jax.random.PRNGKey(0), tokens, layout))


# ------------------------------------------------- (d) dropless under skew
@pytest.mark.parametrize("favoured", [2, 5])
def test_dropless_under_skew(favoured):
    """A router biased so that one held expert takes most assignments still
    matches the reference, and nothing is dropped."""
    policy, cfg = _policy()
    prompt, response, order, actions, extras = _episodes(5, 2)
    params = policy.init(jax.random.PRNGKey(5))
    tokens, _ = policy.layout.pack(jnp.asarray(prompt), jnp.asarray(actions), cfg["mask_id"])
    h = policy.model.apply(params, tokens, policy.layout, method=M.SdarMoE.hidden)[0]
    direction = h.reshape(-1, h.shape[-1]).mean(0)
    params = jax.tree_util.tree_map(lambda x: x, params)
    for i in range(cfg["num_hidden_layers"]):
        router = params["params"][f"layer_{i}"]["moe"]["router"]
        params["params"][f"layer_{i}"]["moe"]["router"] = router.at[:, favoured].add(16.0 * direction / jnp.linalg.norm(direction))
    rparams = M.reference_params(params)
    (loss, (logp, values, aux)), grads = jax.value_and_grad(
        lambda p: _program_loss(policy, p, prompt, actions, extras), has_aux=True)(params)
    (rloss, routs), rgrads = jax.value_and_grad(
        lambda p: _reference_loss(p, cfg, prompt, response, order, extras), has_aux=True)(rparams)
    load = np.asarray(aux["load"])
    # (a token gives an expert one assignment at most, so "most" is bounded by the token count)
    assert load[0, favoured - cfg["expert_offset"]] > 0.5 * load[0].sum(), load
    assert int(aux["dropped"].sum()) == 0
    np.testing.assert_array_equal(load, sum(np.stack([np.asarray(a["counts"]) for a in o["aux"]]) for o in routs))
    assert abs(float(loss) - float(rloss)) <= VALUE_ATOL
    _assert_trees_close(M.reference_params(grads), rgrads, GRAD_RTOL)


# --------------------- (d') the sorted buffer's length follows the counted load
# 6 episodes = 528 packed positions, 16 experts top-2, 2 held: the even share is 132 assignments, the
# short buffer 512 rows (3 shares, rounded up to whole tiles of rows), the worst case 1,056
TIERS = dict(num_experts=16, experts_held=2, expert_offset=2)


def _load_biased_to(policy, params, tokens, target):
    """The parameters with layer 0's router pushed toward its held experts just as far as gives them
    ``target`` assignments (every token flips at its own push: the load moves by single steps)."""
    cfg = policy.cfg
    direction = policy.model.apply(params, tokens, policy.layout, method=M.SdarMoE.hidden)[0]
    direction = direction.reshape(-1, direction.shape[-1]).mean(0)
    held = slice(cfg.expert_offset, cfg.expert_offset + cfg.experts_held)
    router = params["params"]["layer_0"]["moe"]["router"]

    def pushed(push):
        moved = router.at[:, held].add(push * (direction / jnp.linalg.norm(direction))[:, None])
        return {"params": {**params["params"], "layer_0": {**params["params"]["layer_0"], "moe": {
            **params["params"]["layer_0"]["moe"], "router": moved}}}}

    load = jax.jit(lambda push: policy.model.apply(pushed(push), tokens, policy.layout, method=M.SdarMoE.hidden)[1]["load"][0].sum())
    lo, hi = 0.0, 64.0
    assert int(load(lo)) < target < int(load(hi))
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        got = int(load(mid))
        if got == target:
            return pushed(mid)
        lo, hi = (mid, hi) if got < target else (lo, mid)
    raise AssertionError(f"no push gives layer 0's held experts {target} assignments")


@pytest.mark.parametrize("token_side", ["pairs", "compact"])
@pytest.mark.parametrize("over", [None, 0, 1], ids=["under", "exactly_at", "one_over"])
def test_buffer_length_follows_the_counted_load(over, token_side, monkeypatch):
    """Layer 0's held load under, exactly at and one over the short buffer's rows (layer 1's stays
    under): the short buffer is taken, taken, not taken, and on either the program is the reference's
    layer, with nothing dropped; with the short branch's token side reading every pair (a buffer this
    small) and reading held choices only (as beside a large one)."""
    if token_side == "compact":
        monkeypatch.setattr(M, "COMPACT_OVER_BYTES", 0)
    policy, cfg = _policy(TIERS)
    n_eps = 6
    prompt, response, order, actions, extras = _episodes(7, n_eps)
    params = policy.init(jax.random.PRNGKey(7))
    tokens, _ = policy.layout.pack(jnp.asarray(prompt), jnp.asarray(actions), cfg["mask_id"])
    n = tokens.size
    rows_fit = M.short_buffer_rows(n, cfg["num_experts_per_tok"], cfg["experts_held"], cfg["num_experts"])
    assert rows_fit == 512 < n * cfg["experts_held"]  # both lengths exist at this shape
    if over is not None:
        params = _load_biased_to(policy, params, tokens, rows_fit + over)
    rparams = M.reference_params(params)
    (loss, (logp, values, aux)), grads = jax.value_and_grad(
        lambda p: _program_loss(policy, p, prompt, actions, extras), has_aux=True)(params)
    (rloss, routs), rgrads = jax.value_and_grad(
        lambda p: _reference_loss(p, cfg, prompt, response, order, extras), has_aux=True)(rparams)
    load = np.asarray(aux["load"])
    if over is not None:
        assert load[0].sum() == rows_fit + over
    assert load[0].sum() <= rows_fit or over == 1
    assert load[1].sum() < rows_fit
    assert np.asarray(aux["short"]).tolist() == [over != 1, True]
    assert int(aux["dropped"].sum()) == 0
    np.testing.assert_array_equal(load, sum(np.stack([np.asarray(a["counts"]) for a in o["aux"]]) for o in routs))
    for b in range(n_eps):
        np.testing.assert_allclose(np.asarray(logp[b]), np.asarray(routs[b]["logp"]), atol=VALUE_ATOL)
        np.testing.assert_allclose(np.asarray(values[b]), np.asarray(routs[b]["values"]), atol=VALUE_ATOL)
    assert abs(float(loss) - float(rloss)) <= VALUE_ATOL
    _assert_trees_close(M.reference_params(grads), rgrads, GRAD_RTOL)


# ------------- (d'') the token side reads held choices only where the short buffer is taken
# 512 tokens, 64 experts top-8, 8 held: the short buffer 1,536 of 4,096 rows, 4 rows a token, a list of 32 tokens
COMPACT = dict(hidden_size=32, moe_intermediate_size=16, num_experts=64, top_k=8, experts_held=8, expert_offset=8)
COMPACT_CASES = {  # (tokens sent to all eight held experts, the routing rule)
    "even": (0, "softmax"), "all_held": (20, "softmax"), "over_the_list": (40, "softmax"), "sigmoid": (20, "sigmoid")}


def _plain_routed_layer(p, m, spec):
    """The held experts' part of the layer, one expert at a time (``sdar_moe_reference.layer``'s loop),
    under either routing rule as the two references write them."""
    from sheeprl_tpu.models import mla_moe_reference as RM

    with jax.default_matmul_precision("highest"):
        if spec.scoring == "softmax":
            _, top_i, weights = R.route(m, p["router"], spec.top_k, spec.norm_topk_prob)
        else:
            _, top_i, weights = RM.route(m, p["router"], p["bias"], {
                "num_experts_per_tok": spec.top_k, "norm_topk_prob": spec.norm_topk_prob, "routed_scaling_factor": spec.scale})
        y = jnp.zeros_like(m)
        for e in range(spec.experts_held):
            w_e = jnp.where(top_i == spec.expert_offset + e, weights, 0.0).sum(-1)
            y = y + w_e[:, None] * R.expert(m, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return y


@pytest.mark.parametrize("case", list(COMPACT_CASES))
def test_compact_token_side_is_the_k_wide_sum(case, monkeypatch):
    """Where both lengths exist the short branch sums ``c`` rows a token and lists the tokens that hold
    more: output and the gradients of the input, the router (through the routing weights) and the three
    expert matrices equal the k-wide form's (the same layer beside a small buffer) and the plain reference's,
    under even routing, with tokens whose eight choices are all held (the list in use), with more such
    tokens than the list holds (the fallback is taken and nothing is dropped) and under sigmoid scoring."""
    sent, scoring = COMPACT_CASES[case]
    n, spec = 512, M.RoutedSpec(**COMPACT, scoring=scoring, scale=2.5 if scoring == "sigmoid" else 1.0)
    rows_fit = M.short_buffer_rows(n, spec.top_k, spec.experts_held, spec.num_experts)
    assert M.compact_slots(n, spec.top_k, spec.experts_held, spec.num_experts, 4 * spec.hidden_size) is None  # a small buffer
    monkeypatch.setattr(M, "COMPACT_OVER_BYTES", 0)
    slot_c, slot_r = M.compact_slots(n, spec.top_k, spec.experts_held, spec.num_experts, 4 * spec.hidden_size)
    assert (rows_fit, slot_c, slot_r) == (1536, 4, 32) and rows_fit < n * spec.experts_held
    layer = M.RoutedExperts(spec, jnp.float32)
    m = jax.random.normal(jax.random.PRNGKey(0), (n, spec.hidden_size)).at[:, -1].set(0.0).at[:sent, -1].set(8.0)
    params = layer.init(jax.random.PRNGKey(1), m)
    held_ids = spec.expert_offset + jnp.arange(spec.experts_held)
    params = {"params": {**params["params"], "router": params["params"]["router"].at[-1, held_ids].set(4.0)}}
    weight = jax.random.normal(jax.random.PRNGKey(2), m.shape)

    def run(apply):
        fn = lambda p, m: (apply(p, m)[0] * weight).sum()  # noqa: E731
        (y, aux), grads = jax.jit(apply)(params, m), jax.jit(jax.grad(fn, argnums=(0, 1)))(params, m)
        return y, aux, grads

    y, aux, grads = run(layer.apply)
    held_choices = np.asarray(((aux["top_i"] >= spec.expert_offset) & (aux["top_i"] < spec.expert_offset + spec.experts_held)).sum(-1))
    assert (held_choices[:sent] == 8).all() and int(aux["load"].sum()) == held_choices.sum() <= rows_fit
    assert int(aux["overflow"]) == (held_choices > slot_c).sum() >= sent
    assert bool(aux["short"]) == (int(aux["overflow"]) <= slot_r) == (case != "over_the_list")
    assert int(aux["dropped"]) == 0
    monkeypatch.undo()  # a buffer this small reads every pair in both branches: the k-wide form
    wide_y, wide_aux, wide_grads = run(layer.apply)
    assert bool(wide_aux["short"]) and int(wide_aux["overflow"]) == 0
    plain_y, _, plain_grads = run(lambda p, m: (_plain_routed_layer(p["params"], m, spec), None))
    np.testing.assert_allclose(np.asarray(y), np.asarray(wide_y), atol=VALUE_ATOL)
    np.testing.assert_allclose(np.asarray(y), np.asarray(plain_y), atol=VALUE_ATOL)
    assert set(grads[0]["params"]) >= {"router", "w_gate", "w_up", "w_down"}
    _assert_trees_close(grads, wide_grads, 1e-5)
    _assert_trees_close(grads, plain_grads, GRAD_RTOL)


def test_compact_sums_read_no_row_beyond_the_held_ones():
    """Rows of the sorted buffer past the counted load are whatever the grouped product left there: a
    token with fewer held choices than the compact side reads, or with none, must not touch them (a
    layer that held NO assignment of an episode once turned the next layer into NaN on the chip)."""
    n, k, c, r, d = 64, 8, 3, 4, 16
    rng = np.random.default_rng(0)
    held = rng.random((n, k)) < 0.2
    held[:3] = [[True] * 8, [True] * 5 + [False] * 3, [False] * 8]  # two tokens for the list, one that holds nothing
    assigned = int(held.sum())
    inv = np.full((n, k), 10_000)  # a pair that is not held points past the buffer
    inv[held] = rng.permutation(assigned)
    rows = np.full((assigned + 40, d), np.nan, np.float32)
    rows[:assigned] = rng.normal(size=(assigned, d))
    w = np.where(held, rng.random((n, k)), 0).astype(np.float32)
    slots, overflow = M._slots(jnp.asarray(held), c, r)
    got = M._held_sums(jnp.asarray(rows), jnp.asarray(w), jnp.asarray(inv), slots, c)
    want = np.stack([sum(w[t, j] * rows[inv[t, j]] for j in range(k) if held[t, j]) + np.zeros(d, np.float32) for t in range(n)])
    assert int(overflow) == (held.sum(1) > c).sum() <= r
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    nothing_held = M._slots(jnp.zeros((n, k), bool), c, r)[0]  # then every row of the buffer is such a leftover
    assert not np.asarray(M._held_sums(jnp.full((8, d), jnp.nan), jnp.zeros((n, k)), jnp.asarray(inv), nothing_held, c)).any()


def _conditionals(lowered_text):
    return lowered_text.count("stablehlo.case") + lowered_text.count("stablehlo.if")


def test_one_length_where_the_short_buffer_saves_nothing(monkeypatch):
    """With all experts held, and at the collector's cached pass over a block of tokens an env, the
    short length reaches the worst case: one length, and the lowered program holds no conditional
    (where both lengths exist it holds one a pass)."""
    assert M.short_buffer_rows(16896, 8, 16, 128) == 50688  # the published widths: one chip's share of 8
    assert M.short_buffer_rows(16896, 8, 128, 128) == 16896 * 8  # all held
    assert M.short_buffer_rows(12 * 4, 8, 16, 128) == 12 * 4 * 8  # the collector's pass over 12 envs
    # beside the short buffer the token side reads 4 (3) rows a token and lists a sixteenth of the tokens
    # where it is large (SDAR's update: 50,688 rows of 4 KB), and beside a small one every pair (the causal update's
    # 12,288 rows, the collector's prefill over 12 x 512 positions: 18,432)
    assert M.compact_slots(16896, 8, 16, 128, 4096) == (4, 1056)
    assert M.compact_slots(8192, 8, 16, 256, 4096) is None and M.compact_slots(12 * 512, 8, 16, 128, 4096) is None

    def layer_text(overrides, n):  # the routed layer alone: the attention kernel's interpreter has conditionals of its own
        layer = M.RoutedExperts(M.SdarConfig.from_mapping({**TINY, **overrides}), jnp.float32)
        m = jax.ShapeDtypeStruct((n, TINY["hidden_size"]), jnp.float32)
        params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), m)
        fwd = lambda p, m: layer.apply(p, m)[0].sum()  # noqa: E731
        return jax.jit(fwd).lower(params, m).as_text() + jax.jit(jax.grad(fwd, argnums=(0, 1))).lower(params, m).as_text()

    assert _conditionals(layer_text(TIERS, 528)) == 2  # one in the forward pass, one in the backward rule
    # (one length reads every pair and builds no slots: from here on nothing may ask for them)
    monkeypatch.setattr(M, "_slots", lambda *a: pytest.fail("the one-length path built the compact side's slots"))
    assert _conditionals(layer_text({"num_experts": 16, "experts_held": 16, "expert_offset": 0}, 528)) == 0

    policy, cfg = _policy(TIERS)
    envs, seen = 12, P + RESP
    kv = jax.ShapeDtypeStruct((envs, seen, cfg["num_key_value_heads"], cfg["head_dim"]), jnp.float32)
    block = jax.ShapeDtypeStruct((envs, BLOCK), jnp.int32)
    text = jax.jit(lambda p, t, pos, cache, length: policy.model.apply(p, t, pos, cache, length, method=M.SdarMoE.block)).lower(
        jax.eval_shape(policy.init, jax.random.PRNGKey(0)), block, block, [(kv, kv)] * cfg["num_hidden_layers"],
        jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    assert _conditionals(text) == 0


# ---------------- (e) the collector's record equals the update's recomputation
def _lm_overrides(tmp_path, precision="32-true"):
    return [
        "exp=ppo_sdar_moe", "fabric.accelerator=cpu", "fabric.devices=1", f"fabric.precision={precision}",
        "env.num_envs=3", "env.wrapper.vocab_size=64", "env.wrapper.mask_id=63", f"env.wrapper.prompt_len={P}",
        f"env.wrapper.response_len={RESP}", "algo.sdar.hidden_size=64", "algo.sdar.num_attention_heads=4",
        "algo.sdar.num_key_value_heads=2", "algo.sdar.head_dim=16", "algo.sdar.num_experts=8",
        "algo.sdar.num_experts_per_tok=2", "algo.sdar.moe_intermediate_size=32", "algo.sdar.num_hidden_layers=2",
        "algo.sdar.experts_held=4", "algo.sdar.attention_block=128", "algo.sdar.attention_interpret=True", f"algo.total_steps={2 * 3 * RESP}",
        f"metric.log_every={3 * RESP}", f"root_dir={tmp_path}", "run_name=sdar", "checkpoint.every=0",
    ]


def test_collector_record_equals_packed_recomputation(tmp_path):
    """Log-probabilities and values recorded by the fused collector (cache,
    five passes a block) equal the packed update's recomputation under the
    same weights: the first epoch's ratio is 1."""
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.config import compose, instantiate
    from sheeprl_tpu.envs.jax.collect import FusedDiffusionCollector
    from sheeprl_tpu.utils.env import make_train_envs

    cfg = compose(overrides=_lm_overrides(tmp_path))
    runtime = instantiate(dict(cfg.fabric))
    runtime.launch()
    runtime.seed_everything(3)
    envs = make_train_envs(cfg, runtime, None)
    policy, params = build_agent(runtime, (BLOCK, 64), False, cfg, envs.single_observation_space)
    collector = FusedDiffusionCollector(
        envs=envs, module=policy, params=params, cfg=cfg, runtime=runtime, obs_keys=["tokens"], total_envs=3,
        world_size=1,
    )
    for _ in range(2):  # the second rollout starts from the env's own auto-reset
        data = collector.collect(1, True, runtime.next_key).data
        actions = jnp.swapaxes(data["actions"], 0, 1)
        order = np.asarray(actions[..., 0]).reshape(3, -1, BLOCK)
        assert (np.sort(order, -1) == np.arange(BLOCK)).all()  # every position of a block revealed once
        assert (np.asarray(actions[..., 1]) != 63).all()  # [MASK] never drawn
        logp, _, values, _ = policy.evaluate_episodes(params, data["prompt"][0], actions)
        ratio = np.exp(np.asarray(logp) - np.asarray(data["logprobs"][..., 0]).T)
        np.testing.assert_allclose(ratio, 1.0, atol=2e-5)
        np.testing.assert_allclose(np.asarray(values), np.asarray(data["values"][..., 0]).T, atol=VALUE_ATOL)
        dones, rewards = np.asarray(data["dones"][..., 0]), np.asarray(data["rewards"][..., 0])
        assert dones[-1].all() and not dones[:-1].any() and not rewards[:-1].any()
        # the reward rule, recomputed on the host
        response = np.zeros((3, RESP), np.int64)
        at = (np.arange(RESP) // BLOCK) * BLOCK + np.asarray(actions[..., 0])
        np.put_along_axis(response, at, np.asarray(actions[..., 1]), axis=1)
        prompt = np.asarray(data["prompt"][0])
        np.testing.assert_allclose(rewards[-1], (response == prompt[:, np.arange(RESP) % P]).mean(-1), atol=1e-6)


# --------------------------------------------- (f) the reference's two copies
def test_reference_copies_are_byte_identical():
    assert filecmp.cmp(
        os.path.join(ROOT, "sheeprl_tpu", "models", "sdar_moe_reference.py"),
        os.path.join(ROOT, "chipbench", "reference", "sdar_moe.py"), shallow=False,
    )


# ----------------------------------------------------- (g) through the CLI
@pytest.mark.parametrize("precision", ["32-true", "bf16-mixed"])
def test_cli_runs_two_iterations(tmp_path, precision):
    from sheeprl_tpu.cli import run

    overrides = _lm_overrides(tmp_path, precision)
    run(overrides)
    records = [json.loads(line) for path in glob.glob(f"{tmp_path}/sdar/*/telemetry.jsonl") for line in open(path)]
    moe = [r["moe"] for r in records if "moe" in r]
    assert len(moe) == 2, records
    assert all(m["dropped"] == 0 and np.isfinite(m["router_entropy"]) and m["load_max_over_mean"] >= 1 for m in moe)
    assert all(0.0 <= m["short_buffer_share"] <= 1.0 and m["overflow_tokens"] == 0 for m in moe)  # one length at these sizes
    assert records[-1]["jaxenv"]["env"] == "TokenEnvJax" and records[-1]["jaxenv"]["env_steps"] == 2 * 3 * RESP


def test_cli_refuses_the_host_backend(tmp_path):
    from sheeprl_tpu.cli import run

    with pytest.raises(ValueError, match="registered jax env family|env_backend=jax"):
        run(_lm_overrides(tmp_path) + ["algo.env_backend=host"])


# ------------------------ the episode update: its steps, its probe and the state it returns
@pytest.mark.parametrize("precision", ["32-true", "bf16-mixed"])
def test_episode_update_is_the_hand_loop_of_its_steps(tmp_path, precision):
    """One call of ``make_episode_update_fn`` (two minibatch steps in a scan)
    against the same steps taken one by one outside it: the parameters the
    call returns are the last step's, every step started from the one
    before, and the probe's norms are those of the gradients and of
    ``new - old``, leaf by leaf."""
    import optax

    import sheeprl_tpu.algos.ppo.ppo as ppo
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.config import compose, instantiate
    from sheeprl_tpu.utils.utils import gae, normalize_tensor

    cfg = compose(overrides=_lm_overrides(tmp_path, precision) + ["env.num_envs=4", "algo.per_rank_batch_size=2",
                                                                   "algo.optimizer.learning_rate=1e-3"])
    runtime = instantiate(dict(cfg.fabric))
    runtime.launch()
    runtime.seed_everything(7)
    policy, params = build_agent(runtime, (), False, cfg, None)
    params = runtime.replicate(runtime.to_param_dtype(params))
    tx = ppo.build_ppo_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm, runtime.precision)
    update = ppo.make_update_fn(runtime, policy, tx, cfg, list(cfg.algo.mlp_keys.encoder))

    prompt, _, _, actions, extras = _episodes(9, 4)
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

    def loss(p, ids):
        logp, entropy, values, _ = policy.evaluate_episodes(p, jnp.asarray(prompt)[ids], jnp.asarray(actions)[ids])
        adv = normalize_tensor(rows["advantages"][ids]) if cfg.algo.normalize_advantages else rows["advantages"][ids]
        return (ppo.policy_loss(logp, rows["logprobs"][ids], adv, clip, cfg.algo.loss_reduction)
                + cfg.algo.vf_coef * ppo.value_loss(values, rows["values"][ids], rows["returns"][ids], clip,
                                                     cfg.algo.clip_vloss, cfg.algo.loss_reduction)
                + ent * ppo.entropy_loss(entropy, cfg.algo.loss_reduction))

    norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))  # noqa: E731
    rtol = 1e-4 if precision == "32-true" else 2e-2
    p, state = start, ppo._set_lr(tx.init(start), lr)
    assert len(probe["episodes"]) == 2
    for t, ids in enumerate(np.asarray(probe["episodes"])):
        grads = jax.grad(loss)(p, ids)
        updates, state = tx.update(grads, state, p)
        new = optax.apply_updates(p, updates)
        for (path, g), old_leaf, new_leaf, got_g, got_moved in zip(
                jax.tree_util.tree_leaves_with_path(grads), *(jax.tree_util.tree_leaves(x) for x in (
                    p, new, probe["grad_leaf_norms"], probe["moved_leaf_norms"]))):
            where = f"step {t} {jax.tree_util.keystr(path)}"
            assert float(got_g[t]) == pytest.approx(norm(g), rel=rtol, abs=1e-9), where
            assert float(got_moved[t]) == pytest.approx(norm(new_leaf - old_leaf), rel=rtol, abs=1e-9), where
            assert float(got_moved[t]) > 0, where  # every leaf moved, in every step
        assert float(probe["grad_norm"][t]) == pytest.approx(float(optax.global_norm(grads)), rel=rtol)
        p = new
    assert float(metrics["Grads/agent"]) == pytest.approx(float(probe["grad_norm"].mean()))
    assert float(metrics["MoE/short_buffer_share"]) == float(np.asarray(probe["short"]).mean())
    assert float(metrics["MoE/overflow_tokens"]) == float(np.asarray(probe["overflow"]).max())
    # the returned state is the last step's, not the one the call was given
    moved = float(optax.global_norm(jax.tree_util.tree_map(lambda a, b: a - b, got_params, start)))
    assert moved > 0.5 * float(jnp.sqrt(sum(jnp.square(v[-1]) for v in jax.tree_util.tree_leaves(probe["moved_leaf_norms"]))))
    # (under bf16 products an element's gradient can change sign, and Adam's step of 1e-3 with it: two steps
    # of 1e-3 on weights of 0.07 at most)
    _assert_trees_close(got_params, p, 1e-5 if precision == "32-true" else 3e-2)
