"""Plain reference of the Qwen3-Next policy (Gated DeltaNet linear attention
and gated full attention mixed 3:1, a sigmoid-gated shared expert beside
softmax-routed experts): forward pass, PPO loss, gradients.

Written from the layer equations of Gated DeltaNet (Yang, Kautz and Hatamizadeh 2024,
arXiv:2412.06464) and the published ``config.json``
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json,
``model_type: qwen3_next``; the parts the file has no key for follow the
``qwen3_next`` modelling code of Hugging Face transformers,
``models/qwen3_next/modeling_qwen3_next.py``), not from the program:
straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, the delta rule token by token (its
definition), causality as a dense mask, experts as a Python loop over the held
ones, no sorting, no kernel, no cache; one episode at a time.

This file imports nothing of the repository.  ``chipbench/reference/qwen3_next.py``
is a byte-identical copy (a test holds them together): the benchmark may not
depend on the program for what it checks.

The equations (position ``t`` of one sequence, ``d`` hidden; every norm but the
gated one is zero-centred, ``ZNorm(x; w) = x / rms(x) * (1 + w)``)::

    h0_t  = E[x_t]
    a     = ZNorm(h; w_in)
    linear-attention layer (Gated DeltaNet; Hk key heads of dk, Hv value heads of dv, R = Hv / Hk):
      [q~, k~, v~, z] = a W_qkvz   laid key head by key head: (q~ dk, k~ dk, v~ R dv, z R dv)
      [b, a_] = a W_ba             laid key head by key head: (b R, a_ R)
      [q, k, v] = SiLU(causal depthwise conv over positions of [q~; k~; v~]; kernel K, no bias)
      q <- l2norm(q) / sqrt(dk),  k <- l2norm(k)        l2norm(x) = x / sqrt(|x|^2 + 1e-6)
      value head j reads key head j // R
      beta = sigmoid(b),  g = -exp(A_log) softplus(a_ + dt_bias)
      S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T,  S_0 = 0;  o_t = S_t^T q_t
      y     = concat_j RMSNorm(o_j; w_norm) * SiLU(z_j),   out = y W_o
    full-attention layer (H query heads sharing G key-value heads of D):
      [q_j; gate_j] = (a Wq)_j,  q_j = ZNorm(q_j; wq_n),  k_g = ZNorm((a Wk)_g; wk_n),  v_g = (a Wv)_g
      q, k <- RoPE on their first D * partial_rotary_factor numbers (rotate-half, theta)
      o_j(t) = sum_{s <= t} softmax_s(q_j(t) . k_g(s) / sqrt(D)) v_g(s),   g = j // (H / G)
      out   = (concat_j o_j * sigmoid(gate)) Wo
    h     = h + out
    m     = ZNorm(h; w_post)
    p = softmax(m Wr) over all experts;  E = top-k(p);  w_e = p_e / sum_E p       (norm_topk_prob)
    f = sigmoid(m w_sg) Shared(m) + sum_{e in E, e held here} w_e Expert_e(m)
    h     = h + f
    logits = ZNorm(h; w_final) W_head;   value = ZNorm(h; w_final) w_v

Departures from the published model (each also under ``assumed`` / ``reduced``
in ``chipbench/configs/qwen3_next_80b_a3b_ep16.json``):

- the chip's share: ``experts_held`` experts starting at ``expert_offset`` are
  computed, the router still scores all ``num_experts`` and keeps the top
  ``num_experts_per_tok``; what absent experts would add is left out.  The
  shared expert and its gate are computed whole (every chip computes them alike);
- the vocabulary is a slice: embedding and head have ``vocab_size`` rows;
- the multi-token-prediction layer is left out (the catalog gives no shape for
  it and the modelling code drops its weights);
- a scalar value head on the final-norm hidden state (this system's addition:
  PPO needs a critic); PPO reads response token ``i``'s log-probability at
  position ``P + i - 1``;
- attention is computed ``Q_BLOCK`` queries at a time against all keys under
  the dense mask; the delta rule's scan is checkpointed every ``STATE_BLOCK``
  positions for its gradient (the arithmetic is the same);
- ``wrap(name, f)``: a caller may transform (``jax.checkpoint``, ``jax.jit``)
  the functions named ``"linear_layer"``, ``"full_layer"``, ``"attention"``
  (one block of queries), ``"expert"`` and ``"head"`` so that gradients at the
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
STATE_BLOCK = 256
L2_EPS = 1e-6


def _same(name, f):
    return f


# ------------------------------------------------------------------- layers
def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def zc_norm(x, w, eps):
    """The zero-centred RMSNorm: its gain is ``1 + w``."""
    return rms_norm(x, 1.0 + w, eps)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def partial_rope(x, pos, rotary, theta):
    """Rotate-half over the first ``rotary`` numbers of every head; the rest pass.  ``x``: (N, heads, D)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary))
    ang = pos.astype(jnp.float32)[:, None, None] * inv_freq[None, None, :]
    x1, x2, rest = x[..., : rotary // 2], x[..., rotary // 2: rotary], x[..., rotary:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang), rest], axis=-1)


def causal_conv(x, w):
    """Depthwise causal convolution over positions: ``out[t, c] = sum_j w[j, c] x[t - K + 1 + j, c]``
    (zeros before the first position).  ``x``: (N, C); ``w``: (K, C)."""
    k = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(w[j] * xp[j: j + x.shape[0]] for j in range(k))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token.  ``q``, ``k``: (N, Hv, dk), one row a value head; ``v``:
    (N, Hv, dv); ``g``, ``beta``: (N, Hv).  Returns the outputs (N, Hv, dv) and the final state."""
    n, hv, dk = k.shape
    dv = v.shape[-1]
    pad = -n % STATE_BLOCK  # no decay, no write: the state passes them unchanged, their outputs are dropped
    xs = [jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) for x in (q, k, v, g, beta)]
    xs = [x.reshape(-1, STATE_BLOCK, *x.shape[1:]) for x in xs]

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + b_t[:, None, None] * k_t[:, :, None] * (v_t - read)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    @jax.checkpoint
    def block(state, x):
        return jax.lax.scan(token, state, x)

    state, o = jax.lax.scan(block, jnp.zeros((hv, dk, dv), jnp.float32), tuple(xs))
    return o.reshape(-1, hv, dv)[:n], state


def linear_attention(p: Params, a, cfg: Dict[str, Any]):
    """The Gated DeltaNet mixer over one sequence ``a`` (N, d).  Returns its output (N, d), the final
    recurrent state (Hv, dk, dv) and the convolution's input at the last ``K - 1`` positions."""
    n = a.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv, rep = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], hv // hk
    qkvz = (a @ p["w_qkvz"]).reshape(n, hk, 2 * dk + 2 * rep * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk: 2 * dk]
    v, z = qkvz[..., 2 * dk: 2 * dk + rep * dv], qkvz[..., 2 * dk + rep * dv:]
    ba = (a @ p["w_ba"]).reshape(n, hk, 2 * rep)
    b, a_ = ba[..., :rep].reshape(n, hv), ba[..., rep:].reshape(n, hv)
    x = jnp.concatenate([q.reshape(n, -1), k.reshape(n, -1), v.reshape(n, -1)], axis=-1)
    mixed = jax.nn.silu(causal_conv(x, p["conv"]))
    q = l2norm(mixed[:, : hk * dk].reshape(n, hk, dk)) / jnp.sqrt(jnp.float32(dk))
    k = l2norm(mixed[:, hk * dk: 2 * hk * dk].reshape(n, hk, dk))
    v = mixed[:, 2 * hk * dk:].reshape(n, hv, dv)
    q, k = jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1)  # value head j reads key head j // rep
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a_ + p["dt_bias"])
    o, state = delta_rule(q, k, v, g, beta)
    y = rms_norm(o, p["norm"], cfg["rms_norm_eps"]) * jax.nn.silu(z.reshape(n, hv, dv))
    tail = jnp.concatenate([jnp.zeros((cfg["linear_conv_kernel_dim"] - 1, x.shape[1])), x])[n:]
    return y.reshape(n, hv * dv) @ p["w_out"], state, tail


def attention_block(q, k, v, first):
    """Queries ``first .. first + len(q) - 1`` of every head against all keys under the dense causal
    mask.  ``q``: (Q, H, D); ``k``, ``v``: (N, H, D)."""
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    t, i = jnp.arange(k.shape[0])[None, :], (first + jnp.arange(q.shape[0]))[:, None]
    scores = jnp.where((t <= i)[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def gated_attention(p: Params, a, pos, cfg: Dict[str, Any], wrap: Callable = _same):
    n, heads, groups, hd = a.shape[0], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, rotary = cfg["rms_norm_eps"], int(hd * cfg["partial_rotary_factor"])
    qg = (a @ p["wq"]).reshape(n, heads, 2 * hd)  # each head: its query, then its gate
    q = partial_rope(zc_norm(qg[..., :hd], p["q_norm"], eps), pos, rotary, cfg["rope_theta"])
    k = partial_rope(zc_norm((a @ p["wk"]).reshape(n, groups, hd), p["k_norm"], eps), pos, rotary, cfg["rope_theta"])
    v = (a @ p["wv"]).reshape(n, groups, hd)
    k, v = jnp.repeat(k, heads // groups, axis=1), jnp.repeat(v, heads // groups, axis=1)  # head j reads group j // rep
    attend = wrap("attention", attention_block)
    o = jnp.concatenate([attend(q[i:i + Q_BLOCK], k, v, i) for i in range(0, n, Q_BLOCK)], axis=0)
    u = o.reshape(n, heads * hd) * jax.nn.sigmoid(qg[..., hd:].reshape(n, heads * hd))
    return u @ p["wo"]


def swiglu(m, w_gate, w_up, w_down):
    """One SwiGLU MLP (the shared expert, one routed expert) on every row of ``m``."""
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def route_weights(probs, top_i, cfg: Dict[str, Any]):
    picked = jnp.take_along_axis(probs, top_i, axis=-1)
    return picked / picked.sum(-1, keepdims=True) if cfg["norm_topk_prob"] else picked


def routed_mlp(p: Params, m, cfg: Dict[str, Any], forced=None, wrap: Callable = _same):
    """The shared expert under its sigmoid gate beside the routed experts held here, on ``m`` (N, d).
    ``forced = (ids (N, k), margin)`` hands over another implementation's top-k choice at the positions
    where it differs from this router's own AND this router's choice could flip on rounding: where the
    last probability kept and the first one left out differ by less than ``margin`` times the former
    (the weights are still this router's probabilities, at those ids).  A choice that differs at a
    wider gap is not taken over: it shows in the counts (``counts``: after the hand-over;
    ``own_counts``: by this router's own choice everywhere).  ``mlp_in`` and ``mlp_out`` are ``m`` and
    ``f``: what the shares of an expert-parallel layer exchange (``f`` is summed over the shares)."""
    top_k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(m @ p["router"], axis=-1)
    ranked, top_i = jax.lax.top_k(probs, top_k + 1)
    top_i = top_i[:, :top_k]
    weights = route_weights(probs, top_i, cfg)
    rel_gap = (ranked[:, top_k - 1] - ranked[:, top_k]) / ranked[:, top_k - 1]
    differs = handed = jnp.zeros(rel_gap.shape, bool)
    held_ids = cfg["expert_offset"] + jnp.arange(cfg["experts_held"])
    own_counts = (top_i[:, :, None] == held_ids).sum((0, 1))
    if forced is not None:
        ids, margin = forced
        differs = (jnp.sort(ids, axis=-1) != jnp.sort(top_i, axis=-1)).any(-1)
        handed = differs & (rel_gap < margin)
        top_i = jnp.where(handed[:, None], ids, top_i)
        weights = route_weights(probs, top_i, cfg)
    # the shared expert: every token, once, weighed by its own sigmoid gate
    f = jax.nn.sigmoid(m @ p["shared_gate"]) * swiglu(m, p["s_gate"], p["s_up"], p["s_down"])
    counts = []
    run_expert = wrap("expert", swiglu)
    for e in range(cfg["experts_held"]):  # the experts held here; the others' part is left out
        w_e = jnp.where(top_i == cfg["expert_offset"] + e, weights, 0.0).sum(-1)
        f = f + w_e[:, None] * run_expert(m, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        counts.append((top_i == cfg["expert_offset"] + e).sum())
    counts = jnp.stack(counts) if counts else jnp.zeros((0,), jnp.int32)  # a share may hold no expert
    return f, {"top_i": top_i, "rel_gap": rel_gap, "differs": differs, "handed": handed, "counts": counts,
               "own_counts": own_counts, "mlp_in": m, "mlp_out": f}


def linear_layer(p: Params, h, cfg: Dict[str, Any], forced=None, wrap: Callable = _same):
    eps = cfg["rms_norm_eps"]
    out, _, _ = linear_attention(p, zc_norm(h, p["input_norm"], eps), cfg)
    h = h + out
    f, aux = routed_mlp(p, zc_norm(h, p["post_attn_norm"], eps), cfg, forced, wrap)
    return h + f, aux


def full_layer(p: Params, h, pos, cfg: Dict[str, Any], forced=None, wrap: Callable = _same):
    eps = cfg["rms_norm_eps"]
    h = h + gated_attention(p, zc_norm(h, p["input_norm"], eps), pos, cfg, wrap)
    f, aux = routed_mlp(p, zc_norm(h, p["post_attn_norm"], eps), cfg, forced, wrap)
    return h + f, aux


def is_linear(cfg: Dict[str, Any], layer: int) -> bool:
    """Layers ``interval - 1, 2 interval - 1, ...`` are full attention, the others linear."""
    return (layer + 1) % cfg["full_attention_interval"] != 0


def forward(params: Params, tokens, cfg: Dict[str, Any], forced: Optional[Sequence] = None, wrap: Callable = _same):
    """Final-norm hidden states (N, hidden) of one sequence and the routing record of every layer.
    ``forced``: one entry a layer."""
    with jax.default_matmul_precision(HIGHEST):
        h = params["embed"][tokens]
        pos = jnp.arange(tokens.shape[0])
        auxes = []
        run_linear = wrap("linear_layer", lambda p, h, f: linear_layer(p, h, cfg, f, wrap))
        run_full = wrap("full_layer", lambda p, h, f, pos: full_layer(p, h, pos, cfg, f, wrap))
        for i, p in enumerate(params["layers"]):
            f = forced[i] if forced is not None else None
            h, aux = run_linear(p, h, f) if is_linear(cfg, i) else run_full(p, h, f, pos)
            auxes.append(aux)
        return zc_norm(h, params["final_norm"], cfg["rms_norm_eps"]), auxes


def head_terms(at, head, taken):
    """Log-probability of ``taken``, entropy and top-1 id of every row, over the slice."""
    logits = at @ head
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    logp = jnp.take_along_axis(logp_all, taken[:, None], axis=-1)[:, 0]
    return logp, -(jnp.exp(logp_all) * logp_all).sum(-1), jnp.argmax(logits, axis=-1)


def evaluate_episode(params: Params, prompt, response, cfg: Dict[str, Any], forced: Optional[Sequence] = None,
                     wrap: Callable = _same) -> Dict[str, Any]:
    """One episode: log-probability, entropy and value of every response token (read one position
    before it) and the routing record."""
    prompt, response = jnp.asarray(prompt), jnp.asarray(response)
    tokens = jnp.concatenate([prompt, response])
    p_len = prompt.shape[0]
    u, auxes = forward(params, tokens, cfg, forced, wrap)
    with jax.default_matmul_precision(HIGHEST):
        at = u[p_len - 1: -1]
        logp, entropy, _ = wrap("head", head_terms)(at, params["head"], response)
        values = (at @ params["value"])[:, 0]
    return {"logp": logp, "entropy": entropy, "values": values, "aux": auxes}


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
    """The three PPO losses (mean over the cells given): clipped surrogate, value loss (optionally
    clipped, then halved, as the program's ``loss.py``) and the negated entropy."""
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
    """PPO loss of one episode's cells: ``pg + vf_coef vl + ent_coef ent``.  ``episode``: ``prompt``
    (P,), ``response`` (R,) and the ``TARGETS`` (``advantages`` already normalised over the minibatch
    where the run normalises), each (R,).  With equal-length episodes the minibatch loss is the mean
    of these over its episodes."""
    out = evaluate_episode(params, episode["prompt"], episode["response"], cfg, forced, wrap)
    pg, vl, ent = ppo_terms(out["logp"], out["entropy"], out["values"], *(jnp.asarray(episode[k]) for k in TARGETS),
                            hyper["clip_coef"], hyper["clip_vloss"])
    out.update(pg=pg, vl=vl, ent=ent)
    return pg + hyper["vf_coef"] * vl + hyper["ent_coef"] * ent, out


def init_params(key, cfg: Dict[str, Any], std: float = 0.02) -> Params:
    """Random weights as the modelling code initialises them: normal(0, ``std``) for every product and the
    convolution, zero-centred gains 0, the gated norm's gain 1, ``dt_bias`` 1 and ``A_log`` the log of a
    uniform draw over (0, 16]."""
    d, heads, groups, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv, kernel = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    held, f, shared, vocab = cfg["experts_held"], cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"], cfg["vocab_size"]
    keys = iter(jax.random.split(key, 4 + 16 * cfg["num_hidden_layers"]))

    def normal(shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def layer_params(linear: bool) -> Params:
        p = {"input_norm": jnp.zeros((d,)), "post_attn_norm": jnp.zeros((d,)),
             "router": normal((d, cfg["num_experts"])), "w_gate": normal((held, d, f)), "w_up": normal((held, d, f)),
             "w_down": normal((held, f, d)), "s_gate": normal((d, shared)), "s_up": normal((d, shared)),
             "s_down": normal((shared, d)), "shared_gate": normal((d, 1))}
        if linear:
            a = 16.0 * (1.0 - jax.random.uniform(next(keys), (hv,)))  # over (0, 16]
            return {**p, "w_qkvz": normal((d, 2 * hk * dk + 2 * hv * dv)), "w_ba": normal((d, 2 * hv)),
                    "conv": normal((kernel, 2 * hk * dk + hv * dv)), "A_log": jnp.log(a), "dt_bias": jnp.ones((hv,)),
                    "norm": jnp.ones((dv,)), "w_out": normal((hv * dv, d))}
        return {**p, "wq": normal((d, heads * 2 * hd)), "wk": normal((d, groups * hd)), "wv": normal((d, groups * hd)),
                "wo": normal((heads * hd, d)), "q_norm": jnp.zeros((hd,)), "k_norm": jnp.zeros((hd,))}

    return {"embed": normal((vocab, d)), "head": normal((d, vocab)), "value": normal((d, 1)),
            "final_norm": jnp.zeros((d,)), "layers": [layer_params(is_linear(cfg, i)) for i in range(cfg["num_hidden_layers"])]}
