"""Plain reference of the SDAR-MoE policy: forward pass, PPO loss, gradients.

Written from the layer equations of ISSUE 26 and the published ``config.json``
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json,
``model_type: sdar_moe``), not from the program: straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``, a
dense ``N x N`` mask built from the rule, experts as a Python loop over the
held ones, no sorting, no blocking, no cache; one episode at a time.

This file imports nothing of the repository.  ``chipbench/reference/sdar_moe.py``
is a byte-identical copy (a test holds them together): the benchmark may not
depend on the program for what it checks.

Departures from the published model (each also under ``assumed`` / ``reduced``
in ``chipbench/configs/sdar_30b_a3b_ep8.json``):

- the chip's share: ``experts_held`` experts starting at ``expert_offset`` are
  computed, the router still scores all ``num_experts`` and keeps the top
  ``num_experts_per_tok``; what absent experts would add is left out;
- the vocabulary is a slice: embedding and head have ``vocab_size`` rows of the
  slice, ``mask_id`` is the slice's last id (the published id lies outside);
- per-head RMSNorm on q and k (the Qwen3-MoE block this family builds on);
- ``block_length`` 4 and 4 denoising steps a block, one token a step;
- a scalar value head on the final-norm hidden state at the action's position
  (this system's addition: PPO needs a critic);
- ``wrap(name, f)``: a caller may transform (``jax.checkpoint``, ``jax.jit``) the
  functions named ``"layer"``, ``"attention"`` (one key-value head's) and
  ``"expert"``, so that gradients at the published widths fit a chip and each
  compiles once; the default returns ``f`` and the arithmetic is the same
  either way.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
Params = Dict[str, Any]


# ------------------------------------------------------------------ layout
def packed_layout(prompt_len: int, response_len: int, block: int, steps: int) -> Dict[str, np.ndarray]:
    """Per-position description of one packed episode of ``P + R + steps * R``
    positions: the clean sequence (prompt then response) followed, for every
    response block ``b`` in order, by its ``steps`` noised copies.

    ``pos``: rotary position (a copy sits at its clean block's positions);
    ``blk``: block index counted from position 0 of the sequence;
    ``copy``: 0 for a clean position, ``1 + b_resp * steps + j`` for copy ``j``
    of response block ``b_resp``."""
    if prompt_len % block or response_len % block:
        raise ValueError(f"prompt ({prompt_len}) and response ({response_len}) must be multiples of block {block}")
    n_clean = prompt_len + response_len
    clean_pos = np.arange(n_clean)
    n_blocks = response_len // block
    b = np.repeat(np.arange(n_blocks), steps * block)
    j = np.tile(np.repeat(np.arange(steps), block), n_blocks)
    u = np.tile(np.arange(block), n_blocks * steps)
    noised_pos = prompt_len + b * block + u
    return {
        "pos": np.concatenate([clean_pos, noised_pos]).astype(np.int32),
        "blk": np.concatenate([clean_pos // block, noised_pos // block]).astype(np.int32),
        "copy": np.concatenate([np.zeros(n_clean, np.int64), 1 + b * steps + j]).astype(np.int32),
    }


def dense_mask(layout: Dict[str, np.ndarray]) -> np.ndarray:
    """``M[i, t]``: may query ``i`` see key ``t``.  A clean position of block
    ``b`` sees clean positions of blocks ``<= b``; a position of a noised copy
    sees clean positions of blocks ``< b`` and the positions of its own copy;
    nothing else sees a noised position."""
    blk, copy = layout["blk"], layout["copy"]
    q_clean = (copy == 0)[:, None]
    k_clean = (copy == 0)[None, :]
    clean_sees = q_clean & k_clean & (blk[None, :] <= blk[:, None])
    noised_sees_clean = ~q_clean & k_clean & (blk[None, :] < blk[:, None])
    own_copy = ~q_clean & ~k_clean & (copy[None, :] == copy[:, None])
    return clean_sees | noised_sees_clean | own_copy


def pack_episode(prompt, response, order, block: int, steps: int, mask_id: int) -> Tuple[np.ndarray, np.ndarray]:
    """Token ids of the packed episode and the packed index of every step's
    action position.  ``order[b, j]`` is the position (0..block-1) that step
    ``j`` of block ``b`` reveals; copy ``(b, j)`` shows the tokens revealed in
    steps ``< j`` and ``mask_id`` elsewhere."""
    prompt, response, order = np.asarray(prompt), np.asarray(response), np.asarray(order)
    n_blocks = response.shape[0] // block
    resp = response.reshape(n_blocks, block)
    # step_of[b, u]: the step that reveals position u of block b
    step_of = np.empty((n_blocks, block), np.int64)
    np.put_along_axis(step_of, order, np.broadcast_to(np.arange(steps), order.shape), axis=1)
    copies = np.where(step_of[:, None, :] < np.arange(steps)[None, :, None], resp[:, None, :], mask_id)
    tokens = np.concatenate([prompt, response, copies.reshape(-1)]).astype(np.int32)
    base = prompt.shape[0] + response.shape[0]
    act = base + (np.arange(n_blocks)[:, None] * steps + np.arange(steps)[None, :]) * block + order
    return tokens, act.reshape(-1).astype(np.int32)


# ------------------------------------------------------------------- layers
def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """Rotate-half over the whole head.  ``x``: (N, H, D); ``pos``: (N,)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention_kv_head(q, k, v, mask):
    """One key-value head and the query heads it serves.  ``q``: (N, G, D);
    ``k``, ``v``: (N, D); ``mask``: (N, N) bool."""
    scores = jnp.einsum("qgd,kd->gqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1), v)


def expert(m, w_gate, w_up, w_down):
    """One SwiGLU expert on every row of ``m``."""
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def route(m, router_w, top_k: int, norm_topk_prob: bool):
    """Router probabilities over all experts, the chosen ids and their weights."""
    probs = jax.nn.softmax(m @ router_w, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    weights = top_p / top_p.sum(-1, keepdims=True) if norm_topk_prob else top_p
    return probs, top_i, weights


def top_gap(probs, top_k: int):
    """Distance between the last probability kept and the first one left out."""
    top_p, _ = jax.lax.top_k(probs, top_k + 1)
    return top_p[..., top_k - 1] - top_p[..., top_k]


def layer(p: Params, h, pos, mask, cfg: Dict[str, Any], forced=None, wrap: Callable = lambda name, f: f):
    """One block.  ``forced = (ids (N, k), margin)`` hands over another
    implementation's top-k choice at the positions where it differs from this
    router's own AND this router's choice could flip on rounding: where the
    last probability kept and the first one left out differ by less than
    ``margin`` times the former (the weights are still this router's
    probabilities, at those ids).  A choice that differs at a wider gap is not
    taken over: it shows in the counts (``counts``: after the hand-over;
    ``own_counts``: by this router's own choice at every position)."""
    n_q, n_kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, top_k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    n = h.shape[0]
    a = rms_norm(h, p["norm1"], eps)
    q = (a @ p["wq"]).reshape(n, n_q, d)
    k = (a @ p["wk"]).reshape(n, n_kv, d)
    v = (a @ p["wv"]).reshape(n, n_kv, d)
    q = rope(rms_norm(q, p["q_norm"], eps), pos, cfg["rope_theta"])
    k = rope(rms_norm(k, p["k_norm"], eps), pos, cfg["rope_theta"])
    group = n_q // n_kv
    attend = wrap("attention", attention_kv_head)
    o = jnp.concatenate(
        [attend(q[:, g * group:(g + 1) * group], k[:, g], v[:, g], mask) for g in range(n_kv)], axis=1
    )
    h1 = h + o.reshape(n, n_q * d) @ p["wo"]

    m = rms_norm(h1, p["norm2"], eps)
    probs, top_i, weights = route(m, p["router"], top_k, cfg["norm_topk_prob"])
    rel_gap = top_gap(probs, top_k) / jnp.take_along_axis(probs, top_i[:, -1:], axis=-1)[:, 0]
    differs = handed = jnp.zeros(rel_gap.shape, bool)
    held_ids = cfg["expert_offset"] + jnp.arange(cfg["experts_held"])
    own_counts = (top_i[:, :, None] == held_ids).sum((0, 1))  # by this router's own choice, before any hand-over
    if forced is not None:
        ids, margin = forced
        differs = (jnp.sort(ids, axis=-1) != jnp.sort(top_i, axis=-1)).any(-1)
        handed = differs & (rel_gap < margin)
        top_i = jnp.where(handed[:, None], ids, top_i)
        picked = jnp.take_along_axis(probs, top_i, axis=-1)
        weights = picked / picked.sum(-1, keepdims=True) if cfg["norm_topk_prob"] else picked
    y = jnp.zeros_like(h1)
    counts = []
    run_expert = wrap("expert", expert)
    for e in range(cfg["experts_held"]):  # the experts held here; the others' part is left out
        w_e = jnp.where(top_i == cfg["expert_offset"] + e, weights, 0.0).sum(-1)
        y = y + w_e[:, None] * run_expert(m, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        counts.append((top_i == cfg["expert_offset"] + e).sum())
    counts = jnp.stack(counts) if counts else jnp.zeros((0,), jnp.int32)  # a share may hold no expert
    aux = {"top_i": top_i, "rel_gap": rel_gap, "differs": differs, "handed": handed, "counts": counts,
           "own_counts": own_counts}
    return h1 + y, aux


def forward(params: Params, tokens, pos, mask, cfg: Dict[str, Any], forced: Optional[Sequence] = None,
            wrap: Callable = lambda name, f: f):
    """Final-norm hidden states (N, hidden) of one packed episode and the
    per-layer routing record."""
    with jax.default_matmul_precision(HIGHEST):
        h = params["embed"][tokens]
        auxes = []
        run_layer = wrap("layer", lambda p, h, f, pos, mask: layer(p, h, pos, mask, cfg, f, wrap))
        for i, p in enumerate(params["layers"]):
            h, aux = run_layer(p, h, forced[i] if forced is not None else None, pos, mask)
            auxes.append(aux)
        return rms_norm(h, params["final_norm"], cfg["rms_norm_eps"]), auxes


def pack(episode: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """One episode (``prompt`` (P,), ``response`` (R,), ``order`` (R / block,
    steps)) as the arrays the forward pass takes: packed ``tokens``, rotary
    ``pos``, the dense ``mask``, every step's action position ``act`` and the
    token it reveals, ``taken``.  Step ``(b, j)`` reveals position ``u =
    order[b, j]`` with token ``x = response[b * block + u]`` and is scored at
    copy ``(b, j)``'s position ``u``."""
    block, steps = cfg["block_length"], cfg["denoise_steps"]
    layout = packed_layout(len(episode["prompt"]), len(episode["response"]), block, steps)
    tokens, act = pack_episode(episode["prompt"], episode["response"], episode["order"], block, steps, cfg["mask_id"])
    order = np.asarray(episode["order"])
    n_blocks = order.shape[0]
    taken = np.asarray(episode["response"]).reshape(n_blocks, block)[np.arange(n_blocks)[:, None], order].reshape(-1)
    return {"tokens": tokens, "pos": layout["pos"], "mask": dense_mask(layout), "act": act,
            "taken": taken.astype(np.int32)}


def evaluate_packed(params: Params, packed: Dict[str, Any], cfg: Dict[str, Any], forced=None,
                    wrap: Callable = lambda name, f: f):
    """Log-probability, entropy and value of every step of one packed episode."""
    hidden, auxes = forward(params, packed["tokens"], packed["pos"], packed["mask"], cfg, forced, wrap)
    with jax.default_matmul_precision(HIGHEST):
        at = hidden[packed["act"]]
        logits = at @ params["head"]
        drawable = jnp.arange(logits.shape[-1]) != cfg["mask_id"]  # [MASK] is never drawn as a token
        logp_all = jax.nn.log_softmax(jnp.where(drawable, logits, -jnp.inf), axis=-1)
        logp = jnp.take_along_axis(logp_all, packed["taken"][:, None], axis=-1)[:, 0]
        entropy = -(jnp.exp(logp_all) * jnp.where(drawable, logp_all, 0.0)).sum(-1)
        values = (at @ params["value"])[:, 0]
    return logp, entropy, values, auxes


def evaluate_episode(params: Params, episode: Dict[str, Any], cfg: Dict[str, Any], forced=None,
                     wrap: Callable = lambda name, f: f):
    packed = {k: jnp.asarray(v) for k, v in pack(episode, cfg).items()}
    return evaluate_packed(params, packed, cfg, forced, wrap)


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


def ppo_loss_packed(params: Params, packed: Dict[str, Any], targets: Dict[str, Any], cfg: Dict[str, Any],
                    hyper: Dict[str, Any], forced=None, wrap: Callable = lambda name, f: f):
    """PPO loss of one packed episode's cells.  ``targets``: ``old_logp``,
    ``old_values``, ``advantages`` (already normalised over the minibatch where
    the run normalises) and ``returns``, each (T,).  With equal-length episodes
    the minibatch loss is the mean of these over its episodes."""
    logp, entropy, values, auxes = evaluate_packed(params, packed, cfg, forced, wrap)
    pg, vl, ent = ppo_terms(logp, entropy, values, targets["old_logp"], targets["old_values"], targets["advantages"],
                            targets["returns"], hyper["clip_coef"], hyper["clip_vloss"])
    total = pg + hyper["vf_coef"] * vl + hyper["ent_coef"] * ent
    return total, {"pg": pg, "vl": vl, "ent": ent, "logp": logp, "values": values, "aux": auxes}


TARGETS = ("old_logp", "old_values", "advantages", "returns")


def ppo_loss_episode(params: Params, episode: Dict[str, Any], cfg: Dict[str, Any], hyper: Dict[str, Any],
                     forced=None, wrap: Callable = lambda name, f: f):
    """``ppo_loss_packed`` of an episode that also holds its ``TARGETS``."""
    packed = {k: jnp.asarray(v) for k, v in pack(episode, cfg).items()}
    targets = {k: jnp.asarray(episode[k]) for k in TARGETS}
    return ppo_loss_packed(params, packed, targets, cfg, hyper, forced, wrap)


def init_params(key, cfg: Dict[str, Any], std: float = 0.02) -> Params:
    """Random weights: normal(0, ``std``), norms 1."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n_q, n_kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    held, vocab = cfg["experts_held"], cfg["vocab_size"]

    def normal(k, shape):
        return std * jax.random.normal(k, shape, jnp.float32)

    keys = iter(jax.random.split(key, 3 + 8 * cfg["num_hidden_layers"]))
    params = {
        "embed": normal(next(keys), (vocab, d)),
        "head": normal(next(keys), (d, vocab)),
        "value": normal(next(keys), (d, 1)),
        "final_norm": jnp.ones((d,), jnp.float32),
        "layers": [],
    }
    for _ in range(cfg["num_hidden_layers"]):
        params["layers"].append({
            "norm1": jnp.ones((d,), jnp.float32), "norm2": jnp.ones((d,), jnp.float32),
            "q_norm": jnp.ones((hd,), jnp.float32), "k_norm": jnp.ones((hd,), jnp.float32),
            "wq": normal(next(keys), (d, n_q * hd)), "wk": normal(next(keys), (d, n_kv * hd)),
            "wv": normal(next(keys), (d, n_kv * hd)), "wo": normal(next(keys), (n_q * hd, d)),
            "router": normal(next(keys), (d, cfg["num_experts"])),
            "w_gate": normal(next(keys), (held, d, f)), "w_up": normal(next(keys), (held, d, f)),
            "w_down": normal(next(keys), (held, f, d)),
        })
    return params
