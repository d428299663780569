"""Plain reference of the latent-attention sparse-expert policy: forward pass,
PPO + MTP loss, gradients.

Written from the layer equations of ISSUE 30 and the published ``config.json``
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json,
``model_type: joyai_llm_flash``: the DeepSeek-V3 block), not from the program:
straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, a dense causal mask, attention in
its unabsorbed form only (keys and values up-projected per head), experts as a
Python loop over the held ones, no sorting, no kernel, no cache; one episode
at a time.

This file imports nothing of the repository.  ``chipbench/reference/mla_moe.py``
is a byte-identical copy (a test holds them together): the benchmark may not
depend on the program for what it checks.

The equations (``S`` positions of one sequence, tokens ``t``)::

    a      = RMSNorm(h; g1)
    cq     = RMSNorm(a Wdq; gq);   q = cq Wuq -> heads x [q_nope | q_rope]
    [ckv | kr] = a Wdkv;   ckv = RMSNorm(ckv; gkv);   kr = RoPE(kr)   (ONE rotary key for all heads)
    [k_nope | v] = ckv Wukv -> heads x [nope | v]
    q_rope = RoPE(q_rope)          RoPE: adjacent pairs, theta, no scaling
    s_ij   = (q_nope_i . k_nope_j + q_rope_i . kr_j) / sqrt(nope + rope),  j <= i
    h1     = h + softmax(s) v Wo;   m = RMSNorm(h1; g2)
    dense block:   h2 = h1 + Wd(silu(Wg m) * Wu m)
    routed block:  sc = sigmoid(m Wr);  S8 = top-k(sc + b);  w_e = scale * sc_e / sum_{S8} sc
                   h2 = h1 + Shared(m) + sum_{e in S8, e held here} w_e Expert_e(m)
    logits = RMSNorm(h_L; g) W_head
    MTP:   x_j = [RMSNorm(Emb(t_{j+1}); ge) ; RMSNorm(u_j; gh)] Weh;  x' = RoutedBlock(x)
           z_j = RMSNorm(x'_j; gs) W_head;   L_mtp = mean_j CE(z_j, t_{j+2})

Departures from the published model (each also under ``assumed`` / ``reduced``
in ``chipbench/configs/joyai_flash_ep.json``):

- the chip's share: ``experts_held`` experts starting at ``expert_offset`` are
  computed, the router still scores all ``n_routed_experts`` and keeps the top
  ``num_experts_per_tok``; what absent experts would add is left out.  The
  shared expert is computed whole (every chip computes it alike);
- the vocabulary is a slice: embedding and head have ``vocab_size`` rows;
- ``u_j`` is the trunk's hidden state after the final norm, and the order in
  ``Weh``'s input is ``[embedding ; hidden]`` (the released serving code's; the
  paper writes the other order);
- the selection bias ``b`` is a constant of the loss (bias update speed 0): it
  enters the top-k only, so its gradient is zero;
- a scalar value head on the final-norm hidden state (this system's addition:
  PPO needs a critic); PPO reads response token ``i``'s log-probability at
  position ``P + i - 1`` and the MTP loss is taken over the response tokens
  (read at ``P + i - 2``);
- attention is computed ``q_block`` queries at a time (8,192 x 8,192 x 32
  scores are 8.6 GB in float32), against all keys under the dense mask;
- ``wrap(name, f)``: a caller may transform (``jax.checkpoint``, ``jax.jit``)
  the functions named ``"dense_layer"``, ``"routed_layer"``, ``"attention"`` (one
  block of queries), ``"expert"`` and ``"head"`` so that gradients at the
  published widths fit a chip and each compiles once; the default returns
  ``f`` and the arithmetic is the same either way.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
Params = Dict[str, Any]
Q_BLOCK = 1024


def _same(name, f):
    return f


# ------------------------------------------------------------------- layers
def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope_pairs(x, pos, theta):
    """Rotate adjacent pairs ``(x[2i], x[2i + 1])`` by ``pos * theta^(-2i / D)``.
    ``x``: (N, H, D); ``pos``: (N,)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None, None] * inv_freq[None, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang), odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def attention_block(q, k, v, first):
    """Queries ``first .. first + len(q) - 1`` of every head against all keys
    under the dense causal mask.  ``q``: (Q, H, Dk); ``k``: (N, H, Dk); ``v``: (N, H, Dv)."""
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    mask = jnp.arange(k.shape[0])[None, :] <= (first + jnp.arange(q.shape[0]))[:, None]
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def swiglu(m, w_gate, w_up, w_down):
    """One SwiGLU MLP (the dense MLP, the shared expert, one routed expert) on every row of ``m``."""
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def route(m, router_w, bias, cfg: Dict[str, Any]):
    """Sigmoid scores over all experts, the ids chosen by ``score + bias`` and
    their weights from the unbiased scores."""
    scores = jax.nn.sigmoid(m @ router_w)
    _, top_i = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    return scores, top_i, route_weights(scores, top_i, cfg)


def route_weights(scores, top_i, cfg: Dict[str, Any]):
    picked = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    return picked * cfg["routed_scaling_factor"]


def latent_attention(p: Params, a, pos, cfg: Dict[str, Any], wrap: Callable = _same):
    n, heads = a.shape[0], cfg["num_attention_heads"]
    nope, rot, eps = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["rms_norm_eps"]
    cq = rms_norm(a @ p["wdq"], p["q_norm"], eps)
    q = (cq @ p["wuq"]).reshape(n, heads, nope + rot)
    down = a @ p["wdkv"]
    ckv = rms_norm(down[:, : cfg["kv_lora_rank"]], p["kv_norm"], eps)
    kr = rope_pairs(down[:, None, cfg["kv_lora_rank"]:], pos, cfg["rope_theta"])  # (N, 1, rot): one key for all heads
    kv = (ckv @ p["wukv"]).reshape(n, heads, nope + cfg["v_head_dim"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(kr, (n, heads, rot))], axis=-1)
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], pos, cfg["rope_theta"])], axis=-1)
    v = kv[..., nope:]
    attend = wrap("attention", attention_block)
    o = jnp.concatenate([attend(q[i:i + Q_BLOCK], k, v, i) for i in range(0, n, Q_BLOCK)], axis=0)
    return o.reshape(n, heads * cfg["v_head_dim"]) @ p["wo"]


def dense_layer(p: Params, h, pos, cfg: Dict[str, Any], wrap: Callable = _same):
    h1 = h + latent_attention(p, rms_norm(h, p["norm1"], cfg["rms_norm_eps"]), pos, cfg, wrap)
    m = rms_norm(h1, p["norm2"], cfg["rms_norm_eps"])
    return h1 + swiglu(m, p["w_gate"], p["w_up"], p["w_down"])


def routed_layer(p: Params, h, pos, cfg: Dict[str, Any], forced=None, wrap: Callable = _same):
    """One block with a shared expert beside the routed ones.  ``forced = (ids
    (N, k), margin)`` hands over another implementation's top-k choice at the
    positions where it differs from this router's own AND this router's choice
    could flip on rounding: where the last biased score kept and the first one
    left out differ by less than ``margin`` times the former (the weights are
    still this router's unbiased scores, at those ids).  A choice that differs
    at a wider gap is not taken over: it shows in the counts (``counts``: after
    the hand-over; ``own_counts``: by this router's own choice everywhere)."""
    top_k = cfg["num_experts_per_tok"]
    h1 = h + latent_attention(p, rms_norm(h, p["norm1"], cfg["rms_norm_eps"]), pos, cfg, wrap)
    m = rms_norm(h1, p["norm2"], cfg["rms_norm_eps"])
    scores, top_i, weights = route(m, p["router"], p["bias"], cfg)
    biased, _ = jax.lax.top_k(scores + p["bias"], top_k + 1)
    rel_gap = (biased[:, top_k - 1] - biased[:, top_k]) / biased[:, top_k - 1]
    differs = handed = jnp.zeros(rel_gap.shape, bool)
    held_ids = cfg["expert_offset"] + jnp.arange(cfg["experts_held"])
    own_counts = (top_i[:, :, None] == held_ids).sum((0, 1))
    if forced is not None:
        ids, margin = forced
        differs = (jnp.sort(ids, axis=-1) != jnp.sort(top_i, axis=-1)).any(-1)
        handed = differs & (rel_gap < margin)
        top_i = jnp.where(handed[:, None], ids, top_i)
        weights = route_weights(scores, top_i, cfg)
    y = swiglu(m, p["s_gate"], p["s_up"], p["s_down"])  # the shared expert: every token, once, no weight
    counts = []
    run_expert = wrap("expert", swiglu)
    for e in range(cfg["experts_held"]):  # the experts held here; the others' part is left out
        w_e = jnp.where(top_i == cfg["expert_offset"] + e, weights, 0.0).sum(-1)
        y = y + w_e[:, None] * run_expert(m, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        counts.append((top_i == cfg["expert_offset"] + e).sum())
    counts = jnp.stack(counts) if counts else jnp.zeros((0,), jnp.int32)  # a share may hold no expert
    aux = {"top_i": top_i, "rel_gap": rel_gap, "differs": differs, "handed": handed, "counts": counts,
           "own_counts": own_counts}
    return h1 + y, aux


def forward(params: Params, tokens, cfg: Dict[str, Any], forced: Optional[Sequence] = None, wrap: Callable = _same):
    """The trunk: final-norm hidden states ``u`` (N, hidden) of one sequence
    and the routing record of every routed block.  ``forced``: one entry a
    routed block, the trunk's first (the MTP module's is the last)."""
    with jax.default_matmul_precision(HIGHEST):
        h = params["embed"][tokens]
        pos = jnp.arange(tokens.shape[0])
        auxes = []
        run_dense = wrap("dense_layer", lambda p, h, pos: dense_layer(p, h, pos, cfg, wrap))
        run_routed = wrap("routed_layer", lambda p, h, f, pos: routed_layer(p, h, pos, cfg, f, wrap))
        for p in params["layers"]:
            if "router" in p:
                h, aux = run_routed(p, h, forced[len(auxes)] if forced is not None else None, pos)
                auxes.append(aux)
            else:
                h = run_dense(p, h, pos)
        return rms_norm(h, params["final_norm"], cfg["rms_norm_eps"]), auxes


def mtp_forward(params: Params, tokens, u, cfg: Dict[str, Any], forced=None, wrap: Callable = _same):
    """The MTP module's normed hidden states ``x'`` (N, hidden): position ``j``
    holds what predicts token ``j + 2``.  Position ``N - 1`` has no next token
    (its own stands in) and predicts nothing."""
    with jax.default_matmul_precision(HIGHEST):
        p, eps = params["mtp"], cfg["rms_norm_eps"]
        nxt = jnp.concatenate([tokens[1:], tokens[-1:]])
        both = jnp.concatenate([rms_norm(params["embed"][nxt], p["enorm"], eps), rms_norm(u, p["hnorm"], eps)], axis=-1)
        run_routed = wrap("routed_layer", lambda p, h, f, pos: routed_layer(p, h, pos, cfg, f, wrap))
        x, aux = run_routed(p["block"], both @ p["eh_proj"], forced, jnp.arange(tokens.shape[0]))
        return rms_norm(x, p["norm"], eps), aux


def head_terms(at, head, taken):
    """Log-probability of ``taken``, entropy and top-1 id of every row, over the slice."""
    logits = at @ head
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    logp = jnp.take_along_axis(logp_all, taken[:, None], axis=-1)[:, 0]
    return logp, -(jnp.exp(logp_all) * logp_all).sum(-1), jnp.argmax(logits, axis=-1)


def evaluate_episode(params: Params, prompt, response, cfg: Dict[str, Any], forced: Optional[Sequence] = None,
                     wrap: Callable = _same) -> Dict[str, Any]:
    """One episode: log-probability, entropy and value of every response token
    (read one position before it), the MTP module's loss over the response
    tokens (read two positions before) and its top-1 hit share, the logits'
    positions' hidden states aside."""
    prompt, response = jnp.asarray(prompt), jnp.asarray(response)
    tokens = jnp.concatenate([prompt, response])
    p_len = prompt.shape[0]
    n_trunk = sum(1 for p in params["layers"] if "router" in p)
    u, auxes = forward(params, tokens, cfg, None if forced is None else forced[:n_trunk], wrap)
    run_head = wrap("head", head_terms)
    with jax.default_matmul_precision(HIGHEST):
        at = u[p_len - 1: -1]
        logp, entropy, _ = run_head(at, params["head"], response)
        values = (at @ params["value"])[:, 0]
    out = {"logp": logp, "entropy": entropy, "values": values}
    if "mtp" in params:
        x, aux = mtp_forward(params, tokens, u, cfg, None if forced is None else forced[n_trunk], wrap)
        auxes = auxes + [aux]
        with jax.default_matmul_precision(HIGHEST):
            mtp_logp, _, top1 = run_head(x[p_len - 2: -2], params["head"], response)
        out.update(mtp_loss=-mtp_logp.mean(), mtp_top1_match=(top1 == response).mean())
    out["aux"] = auxes
    return out


def logits_of(params: Params, tokens, cfg: Dict[str, Any]):
    """Next-token logits (N, vocab) and values (N,) at every position of one sequence."""
    u, _ = forward(params, jnp.asarray(tokens), cfg)
    with jax.default_matmul_precision(HIGHEST):
        return u @ params["head"], (u @ params["value"])[:, 0]


def gae(rewards, values, dones, next_value, gamma: float, lam: float):
    """Generalised advantage estimation over one episode's steps (numpy)."""
    rewards, values, dones = (np.asarray(x, np.float64) for x in (rewards, values, dones))
    adv = np.zeros_like(rewards)
    last = 0.0
    for t in reversed(range(len(rewards))):
        not_done = 1.0 - dones[t]
        nxt = next_value if t == len(rewards) - 1 else values[t + 1]
        delta = rewards[t] + gamma * nxt * not_done - values[t]
        last = delta + gamma * lam * not_done * last
        adv[t] = last
    return (adv + values).astype(np.float32), adv.astype(np.float32)


def ppo_terms(logp, entropy, values, old_logp, old_values, advantages, returns, clip_coef, clip_vloss: bool):
    """The three PPO losses (mean over the cells given): clipped surrogate,
    value loss (optionally clipped, then halved, as the program's ``loss.py``)
    and the negated entropy."""
    ratio = jnp.exp(logp - old_logp)
    pg = -jnp.minimum(advantages * ratio, advantages * jnp.clip(ratio, 1.0 - clip_coef, 1.0 + clip_coef)).mean()
    if clip_vloss:
        clipped = old_values + jnp.clip(values - old_values, -clip_coef, clip_coef)
        vl = 0.5 * jnp.maximum((values - returns) ** 2, (clipped - returns) ** 2).mean()
    else:
        vl = ((values - returns) ** 2).mean()
    return pg, vl, -entropy.mean()


TARGETS = ("old_logp", "old_values", "advantages", "returns")


def loss_episode(params: Params, episode: Dict[str, Any], cfg: Dict[str, Any], hyper: Dict[str, Any],
                 forced: Optional[Sequence] = None, wrap: Callable = _same):
    """PPO + MTP loss of one episode's cells: ``pg + vf_coef vl + ent_coef ent
    + mtp_coef L_mtp``.  ``episode``: ``prompt`` (P,), ``response`` (R,) and the
    ``TARGETS`` (``advantages`` already normalised over the minibatch where the
    run normalises), each (R,).  With equal-length episodes the minibatch loss
    is the mean of these over its episodes."""
    out = evaluate_episode(params, episode["prompt"], episode["response"], cfg, forced, wrap)
    pg, vl, ent = ppo_terms(out["logp"], out["entropy"], out["values"], *(jnp.asarray(episode[k]) for k in TARGETS),
                            hyper["clip_coef"], hyper["clip_vloss"])
    total = pg + hyper["vf_coef"] * vl + hyper["ent_coef"] * ent
    if "mtp_loss" in out:
        total = total + hyper["mtp_coef"] * out["mtp_loss"]
    out.update(pg=pg, vl=vl, ent=ent)
    return total, out


def init_params(key, cfg: Dict[str, Any], std: float = 0.02) -> Params:
    """Random weights: normal(0, ``std``), norms 1, the selection bias normal too."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rot, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    held, f, vocab = cfg["experts_held"], cfg["moe_intermediate_size"], cfg["vocab_size"]
    shared = cfg["n_shared_experts"] * f
    n_layers = cfg["num_hidden_layers"]
    keys = iter(jax.random.split(key, 4 + 16 * (n_layers + 1)))

    def normal(shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def block(routed: bool) -> Params:
        p = {"norm1": ones(d), "norm2": ones(d), "wdq": normal((d, cfg["q_lora_rank"])), "q_norm": ones(cfg["q_lora_rank"]),
             "wuq": normal((cfg["q_lora_rank"], heads * (nope + rot))), "wdkv": normal((d, cfg["kv_lora_rank"] + rot)),
             "kv_norm": ones(cfg["kv_lora_rank"]), "wukv": normal((cfg["kv_lora_rank"], heads * (nope + dv))),
             "wo": normal((heads * dv, d))}
        if not routed:
            i = cfg["intermediate_size"]
            return {**p, "w_gate": normal((d, i)), "w_up": normal((d, i)), "w_down": normal((i, d))}
        return {**p, "router": normal((d, cfg["n_routed_experts"])), "bias": normal((cfg["n_routed_experts"],)),
                "w_gate": normal((held, d, f)), "w_up": normal((held, d, f)), "w_down": normal((held, f, d)),
                "s_gate": normal((d, shared)), "s_up": normal((d, shared)), "s_down": normal((shared, d))}

    params = {"embed": normal((vocab, d)), "head": normal((d, vocab)), "value": normal((d, 1)), "final_norm": ones(d),
              "layers": [block(i >= cfg["first_k_dense_replace"]) for i in range(n_layers)]}
    if cfg["num_nextn_predict_layers"]:
        params["mtp"] = {"enorm": ones(d), "hnorm": ones(d), "eh_proj": normal((2 * d, d)), "norm": ones(d),
                         "block": block(True)}
    return params
