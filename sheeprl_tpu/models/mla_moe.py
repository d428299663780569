"""A causal sparse-expert transformer with latent attention as flax modules:
the DeepSeek-V3 block (``model_type: joyai_llm_flash`` and its family; layer
equations in ``mla_moe_reference.py``, the plain reference every test compares
this with).

What is particular here:

- ``LatentAttention`` (MLA): queries and keys/values go through low-rank
  latents with an RMSNorm inside each; a head's query and key are
  ``qk_nope_head_dim`` numbers of its own and ``qk_rope_head_dim`` rotary
  numbers, the rotary *key* being one vector shared by all heads; values are
  ``v_head_dim`` wide.  Two forms over one set of weights:
  the **full-sequence** form (``__call__``: keys and values up-projected per
  head, the blocked kernel of ``ops/block_sparse_attention.py`` under
  ``SegmentMask.causal``), which the update's one causal pass takes, and the
  **cached, absorbed** form (``cached``: one token against a cache that holds
  the latent ``ckv`` and the rotary key ``kr`` of every position, 576 numbers a
  position and layer, never per-head keys or values; ``W_uk`` is folded into
  the query and ``W_uv`` applied after the weighted sum of latents), which
  collection takes.  Up-projecting 8,192 cached positions a step would cost 69
  GFLOP a step and layer; absorbed, 0.6.
- the first ``first_k_dense_replace`` blocks have a dense SwiGLU MLP; the others
  a shared SwiGLU expert, computed once for every token, beside the routed
  layer (``sdar_moe.RoutedExperts`` under the sigmoid rule: selected by
  ``score + bias``, weighed by the score, renormalised, scaled), which is told
  which experts it holds (``experts_held`` from ``expert_offset``).
- ``MtpModule`` predicts the token after next (``num_nextn_predict_layers`` 1):
  the next token's embedding and the trunk's final-norm hidden state, each
  normed, concatenated and projected, through one more block of the routed
  kind, the model's own head.  It is trained (an auxiliary loss the policy
  hands to the update), not used to draft.

Norm statistics, router scores, sigmoid, top-k and every softmax run in
float32 (the router's product at ``highest`` precision); products elsewhere in
``dtype``.  The blocks are unrolled (a scan would hide them from the
profiler's scopes) and each is rematerialised in the backward pass, keeping
the attention kernel's output (``sdar_moe.remat_block``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from sheeprl_tpu.models.sdar_moe import RoutedExperts, RoutedSpec, remat_block, rms_norm
from sheeprl_tpu.ops.block_sparse_attention import SegmentMask, block_sparse_flash_attention

Dtype = Any
_INIT = nn.initializers.normal(0.02)


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    """The published ``config.json`` keys this model reads, and the cut."""

    hidden_size: int = 2048
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    rms_norm_eps: float = 1e-6
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    first_k_dense_replace: int = 1
    num_hidden_layers: int = 40
    num_nextn_predict_layers: int = 1
    vocab_size: int = 129280
    experts_held: int = 256
    expert_offset: int = 0
    # tiles of the causal attention (the TPU's block-sparse flash kernel): None, by the call's shapes
    # (``ops.block_sparse_attention._tiles``); an integer makes every tile of every kernel that wide
    attention_block: Optional[int] = None
    attention_interpret: bool = False  # run that kernel through Pallas' interpreter: off a TPU, for the tests

    @classmethod
    def from_mapping(cls, cfg: Mapping[str, Any]) -> "MlaMoeConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        self = cls(**{k: v for k, v in dict(cfg).items() if k in names})
        if not 0 <= self.expert_offset <= self.n_routed_experts - self.experts_held:
            raise ValueError(f"experts {self.expert_offset}..+{self.experts_held} are not among {self.n_routed_experts}")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(f"{self.first_k_dense_replace} leading dense blocks of {self.num_hidden_layers}")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one multi-token-prediction module at most (num_nextn_predict_layers 0 or 1)")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary part of a head rotates adjacent pairs: qk_rope_head_dim must be even")
        return self

    @property
    def routed_spec(self) -> RoutedSpec:
        return RoutedSpec(self.hidden_size, self.moe_intermediate_size, self.n_routed_experts, self.num_experts_per_tok,
                          self.experts_held, self.expert_offset, self.norm_topk_prob, self.scoring_func,
                          self.routed_scaling_factor)


def rope_interleaved(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over adjacent pairs ``(x[2i], x[2i + 1])`` (``rope_interleave``), no scaling.
    ``x``: (..., N, H, D) float32; ``pos``: (..., N) or (N,)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None, None] * inv_freq  # (..., N, 1, D / 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


class SwiGLU(nn.Module):
    """``Wd(silu(Wg m) * Wu m)``, no bias: the dense MLP and the shared expert."""

    width: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, m: jax.Array) -> jax.Array:
        d = m.shape[-1]
        w_gate = self.param("w_gate", _INIT, (d, self.width), jnp.float32)
        w_up = self.param("w_up", _INIT, (d, self.width), jnp.float32)
        w_down = self.param("w_down", _INIT, (self.width, d), jnp.float32)
        m = m.astype(self.dtype)
        act = jax.nn.silu(m @ w_gate.astype(self.dtype)) * (m @ w_up.astype(self.dtype))
        return (act @ w_down.astype(self.dtype)).astype(jnp.float32)


class LatentAttention(nn.Module):
    """Multi-head latent attention; the mask is causal."""

    cfg: MlaMoeConfig
    dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        d, h = c.hidden_size, c.num_attention_heads
        self.wdq = self.param("wdq", _INIT, (d, c.q_lora_rank), jnp.float32)
        self.q_norm = self.param("q_norm", nn.initializers.ones, (c.q_lora_rank,), jnp.float32)
        self.wuq = self.param("wuq", _INIT, (c.q_lora_rank, h * (c.qk_nope_head_dim + c.qk_rope_head_dim)), jnp.float32)
        self.wdkv = self.param("wdkv", _INIT, (d, c.kv_lora_rank + c.qk_rope_head_dim), jnp.float32)
        self.kv_norm = self.param("kv_norm", nn.initializers.ones, (c.kv_lora_rank,), jnp.float32)
        self.wukv = self.param("wukv", _INIT, (c.kv_lora_rank, h * (c.qk_nope_head_dim + c.v_head_dim)), jnp.float32)
        self.wo = self.param("wo", _INIT, (h * c.v_head_dim, d), jnp.float32)

    def latents(self, a: jax.Array, pos: jax.Array):
        """What both forms share: per-head queries ``q_nope`` (..., N, H, nope) and rotated
        ``q_rope`` (..., N, H, rope), the normed latent ``ckv`` (..., N, kv_lora_rank) and the
        rotated shared key ``kr`` (..., N, rope), in ``dtype``."""
        c, dt = self.cfg, self.dtype
        a = a.astype(dt)
        cq = rms_norm(a @ self.wdq.astype(dt), self.q_norm, c.rms_norm_eps).astype(dt)
        q = (cq @ self.wuq.astype(dt)).reshape(*a.shape[:-1], c.num_attention_heads, -1)
        q_rope = rope_interleaved(q[..., c.qk_nope_head_dim:].astype(jnp.float32), pos, c.rope_theta).astype(dt)
        down = a @ self.wdkv.astype(dt)
        ckv = rms_norm(down[..., : c.kv_lora_rank], self.kv_norm, c.rms_norm_eps).astype(dt)
        kr = rope_interleaved(down[..., None, c.kv_lora_rank:].astype(jnp.float32), pos, c.rope_theta)[..., 0, :]
        return q[..., : c.qk_nope_head_dim], q_rope, ckv, kr.astype(dt)

    def out(self, o: jax.Array) -> jax.Array:
        return (o.reshape(*o.shape[:-2], -1) @ self.wo.astype(self.dtype)).astype(jnp.float32)

    def __call__(self, a: jax.Array, pos: jax.Array):
        """``a``: (B, N, hidden), whole sequences.  Keys and values up-projected per head, then the
        blocked kernel under the causal mask; also returns the sequence's ``(ckv, kr)``."""
        c, dt = self.cfg, self.dtype
        q_nope, q_rope, ckv, kr = self.latents(a, pos)
        kv = (ckv @ self.wukv.astype(dt)).reshape(*a.shape[:-1], c.num_attention_heads, -1)
        k = jnp.concatenate([kv[..., : c.qk_nope_head_dim], jnp.broadcast_to(kr[..., None, :], q_rope.shape)], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        n = a.shape[-2]
        with jax.named_scope("mla_kernel"):
            o = block_sparse_flash_attention(q, k, kv[..., c.qk_nope_head_dim:], SegmentMask.causal(n, n),
                                             c.attention_block, interpret=c.attention_interpret)
        return self.out(o), (ckv, kr)

    def cached(self, a: jax.Array, pos: jax.Array, ckv_cache: jax.Array, kr_cache: jax.Array, length: jax.Array):
        """One token an env (B, 1, hidden) at position ``length`` against the cached latents
        (B, S, kv_lora_rank) and rotary keys (B, S, rope) of the positions before it, absorbed:
        ``q_nope W_uk`` against the latents, the weighted sum of latents through ``W_uv``.  Returns the
        output and the caches with this token's entry written at ``length``."""
        c, dt = self.cfg, self.dtype
        q_nope, q_rope, ckv, kr = self.latents(a, pos)
        ckv_cache = jax.lax.dynamic_update_slice_in_dim(ckv_cache, ckv, length, axis=1)
        kr_cache = jax.lax.dynamic_update_slice_in_dim(kr_cache, kr, length, axis=1)
        w = self.wukv.astype(dt).reshape(c.kv_lora_rank, c.num_attention_heads, -1)
        w_uk, w_uv = w[..., : c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]
        q_lat = jnp.einsum("bqhd,chd->bqhc", q_nope, w_uk, preferred_element_type=jnp.float32).astype(dt)
        scores = jnp.einsum("bqhc,bkc->bhqk", q_lat, ckv_cache, preferred_element_type=jnp.float32)
        scores = scores + jnp.einsum("bqhr,bkr->bhqk", q_rope, kr_cache, preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(c.qk_nope_head_dim + c.qk_rope_head_dim))
        p = jax.nn.softmax(jnp.where(jnp.arange(ckv_cache.shape[1]) <= length, scores, -jnp.inf), axis=-1)
        # (b, h, q, c) and then swapped: XLA:CPU has no bf16 product that writes (b, q, h, c) directly
        o_lat = jnp.einsum("bhqk,bkc->bhqc", p.astype(dt), ckv_cache, preferred_element_type=jnp.float32)
        o_lat = jnp.swapaxes(o_lat, 1, 2).astype(dt)
        o = jnp.einsum("bqhc,chd->bqhd", o_lat, w_uv, preferred_element_type=jnp.float32).astype(dt)
        return self.out(o), (ckv_cache, kr_cache)


class MlaBlock(nn.Module):
    """Latent attention, then a dense SwiGLU MLP (``routed=False``) or the shared expert beside the
    routed layer."""

    cfg: MlaMoeConfig
    routed: bool
    dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        self.norm1 = self.param("norm1", nn.initializers.ones, (c.hidden_size,), jnp.float32)
        self.norm2 = self.param("norm2", nn.initializers.ones, (c.hidden_size,), jnp.float32)
        self.attn = LatentAttention(c, self.dtype)
        if self.routed:
            self.shared = SwiGLU(c.n_shared_experts * c.moe_intermediate_size, self.dtype)
            self.moe = RoutedExperts(c, self.dtype)
        else:
            self.mlp = SwiGLU(c.intermediate_size, self.dtype)

    def _mlp(self, h1: jax.Array):
        eps = self.cfg.rms_norm_eps
        if not self.routed:
            with jax.named_scope("dense_mlp"):
                return h1 + self.mlp(rms_norm(h1, self.norm2, eps)), None
        with jax.named_scope("moe_shared"):
            m = rms_norm(h1, self.norm2, eps)
            h2 = h1 + self.shared(m)  # every token, once: not a routed expert, no weight
        y, aux = self.moe(m.reshape(-1, m.shape[-1]))
        with jax.named_scope("moe_shared"):
            return h2 + y.reshape(h1.shape), aux

    def __call__(self, h: jax.Array, pos: jax.Array):
        """``h``: (B, N, hidden) float32, whole sequences."""
        with jax.named_scope("mla_proj"):
            o, latents = self.attn(rms_norm(h, self.norm1, self.cfg.rms_norm_eps), pos)
            h1 = h + o
        h2, aux = self._mlp(h1)
        return h2, aux, latents

    def cached(self, h: jax.Array, pos: jax.Array, ckv_cache, kr_cache, length):
        with jax.named_scope("mla_proj"):
            o, caches = self.attn.cached(rms_norm(h, self.norm1, self.cfg.rms_norm_eps), pos, ckv_cache, kr_cache, length)
            h1 = h + o
        h2, aux = self._mlp(h1)
        return h2, aux, caches


class MtpModule(nn.Module):
    """``x_j = [RMSNorm(Emb(t_{j+1})) ; RMSNorm(u_j)] W_eh`` through one block of the routed kind and a
    norm of its own; the embedding and the head are the model's."""

    cfg: MlaMoeConfig
    dtype: Dtype = jnp.float32
    remat: bool = True

    def setup(self) -> None:
        d = self.cfg.hidden_size
        self.enorm = self.param("enorm", nn.initializers.ones, (d,), jnp.float32)
        self.hnorm = self.param("hnorm", nn.initializers.ones, (d,), jnp.float32)
        self.eh_proj = self.param("eh_proj", _INIT, (2 * d, d), jnp.float32)
        self.norm = self.param("norm", nn.initializers.ones, (d,), jnp.float32)
        self.block = (remat_block(MlaBlock) if self.remat else MlaBlock)(self.cfg, True, self.dtype)

    def __call__(self, emb_next: jax.Array, u: jax.Array, pos: jax.Array):
        eps = self.cfg.rms_norm_eps
        both = jnp.concatenate([rms_norm(emb_next, self.enorm, eps), rms_norm(u, self.hnorm, eps)], axis=-1)
        x = (both.astype(self.dtype) @ self.eh_proj.astype(self.dtype)).astype(jnp.float32)
        x, aux, _ = self.block(x, pos)
        return rms_norm(x, self.norm, eps), aux


def _stack(auxes: List[Dict[str, jax.Array]]) -> Dict[str, jax.Array]:
    return {k: jnp.stack([a[k] for a in auxes]) for k in auxes[0]}


class MlaMoE(nn.Module):
    """Embedding, the blocks, final norm, an untied head over the vocabulary slice, a scalar value
    head (this system's addition) and the multi-token-prediction module."""

    cfg: MlaMoeConfig
    dtype: Dtype = jnp.float32
    remat: bool = True

    def setup(self) -> None:
        c = self.cfg
        self.embed = self.param("embed", _INIT, (c.vocab_size, c.hidden_size), jnp.float32)
        self.head = self.param("head", _INIT, (c.hidden_size, c.vocab_size), jnp.float32)
        self.value = self.param("value", _INIT, (c.hidden_size, 1), jnp.float32)
        self.final_norm = self.param("final_norm", nn.initializers.ones, (c.hidden_size,), jnp.float32)
        block = remat_block(MlaBlock) if self.remat else MlaBlock
        self.layers = [block(c, i >= c.first_k_dense_replace, self.dtype, name=f"layer_{i}")
                       for i in range(c.num_hidden_layers)]
        if c.num_nextn_predict_layers:
            self.mtp = MtpModule(c, self.dtype, self.remat)

    def hidden(self, tokens: jax.Array, with_latents: bool = False):
        """Final-norm hidden states ``u`` (B, N, hidden) of whole sequences ``tokens`` (B, N), the
        routed layers' counters (a list, one entry a routed block) and, on request, every block's
        ``(ckv, kr)`` (a prefill)."""
        with jax.named_scope("lm_embed"):
            h = self.embed[tokens]
        pos = jnp.arange(tokens.shape[1])
        auxes, latents = [], []
        for layer in self.layers:
            h, aux, lat = layer(h, pos)
            latents.append(lat)
            if aux is not None:
                auxes.append(aux)
        with jax.named_scope("lm_head"):
            u = rms_norm(h, self.final_norm, self.cfg.rms_norm_eps)
        return (u, auxes, latents) if with_latents else (u, auxes)

    def step(self, tokens: jax.Array, cache, length: jax.Array):
        """One token an env (B, 1) at position ``length`` against the latent cache (per block
        ``(ckv, kr)``, (B, S, kv_lora_rank) and (B, S, rope)): the cached pass collection makes.
        Returns the final-norm hidden state (B, 1, hidden), the routed blocks' counters stacked over
        them (the trunk's: the MTP module does not run here) and the caches with the token written."""
        h = self.embed[tokens]
        pos = length + jnp.arange(1)
        auxes, new_cache = [], []
        for layer, (ckv_cache, kr_cache) in zip(self.layers, cache):
            h, aux, caches = layer.cached(h, pos, ckv_cache, kr_cache, length)
            new_cache.append(caches)
            if aux is not None:
                auxes.append(aux)
        return rms_norm(h, self.final_norm, self.cfg.rms_norm_eps), _stack(auxes), new_cache

    def _head_terms(self, at: jax.Array, taken: jax.Array):
        """(log-probability of ``taken``, entropy, the top-1 id) at hidden states ``at`` (..., hidden),
        over the vocabulary slice, float32.  Rematerialised: the (..., vocab) logits of a head pass are
        computed again in the backward pass, so that the two head passes' logits are not live together."""
        def terms(at, head, taken):
            logits = jnp.dot(at.astype(self.dtype), head.astype(self.dtype), preferred_element_type=jnp.float32)
            logp_all = jax.nn.log_softmax(logits, axis=-1)
            logp = jnp.take_along_axis(logp_all, taken[..., None], axis=-1)[..., 0]
            return logp, -(jnp.exp(logp_all) * logp_all).sum(-1), jnp.argmax(logits, axis=-1)

        # rows flattened: a leading axis of one episode made XLA relayout the (rows, vocab) logits
        out = (jax.checkpoint(terms) if self.remat else terms)(at.reshape(-1, at.shape[-1]), self.head, taken.reshape(-1))
        return tuple(x.reshape(taken.shape) for x in out)

    def logits(self, at: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Hidden states (..., hidden) -> the policy's log-probabilities over the vocabulary slice
        (float32) and the value: what the collector samples from."""
        with jax.named_scope("lm_head"):
            logits = jnp.dot(at.astype(self.dtype), self.head.astype(self.dtype), preferred_element_type=jnp.float32)
            values = jnp.dot(at, self.value, precision=jax.lax.Precision.HIGHEST)[..., 0]
            return jax.nn.log_softmax(logits, axis=-1), values

    def evaluate(self, tokens: jax.Array, prompt_len: int):
        """One causal pass over whole episodes ``tokens`` (B, P + R).  Response token ``i`` sits at
        position ``P + i`` and was drawn from the distribution at position ``P + i - 1``, so the ``R``
        steps' log-probabilities, entropies and values are read at positions ``P - 1 .. P + R - 2``.
        The MTP module's prediction of response token ``i`` is read at position ``P + i - 2``.
        Returns ``(logp, entropy, values)``, each (B, R), the routed layers' counters stacked over the
        routed blocks (the trunk's, then the MTP module's) and ``{"loss", "top1_match"}`` of the MTP
        module (None without one)."""
        p = int(prompt_len)
        if p < 2:
            raise ValueError("the MTP module reads two positions before a response token: prompt_len >= 2")
        response = tokens[:, p:]
        u, auxes = self.hidden(tokens)
        with jax.named_scope("lm_head"):
            at = u[:, p - 1: -1]
            logp, entropy, _ = self._head_terms(at, response)
            values = jnp.dot(at, self.value, precision=jax.lax.Precision.HIGHEST)[..., 0]
        mtp = None
        if self.cfg.num_nextn_predict_layers:
            with jax.named_scope("mtp_module"):
                nxt = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)  # the last position predicts nothing
                with jax.named_scope("lm_embed"):
                    emb_next = self.embed[nxt]
                x, aux = self.mtp(emb_next, u, jnp.arange(tokens.shape[1]))
                auxes = auxes + [aux]
                with jax.named_scope("lm_head"):
                    mtp_logp, _, top1 = self._head_terms(x[:, p - 2: -2], response)
                    mtp = {"loss": -mtp_logp.mean(), "top1_match": (top1 == response).mean()}
        return (logp, entropy, values), _stack(auxes), mtp

    def __call__(self, tokens: jax.Array, prompt_len: int):
        return self.evaluate(tokens, prompt_len)


def reference_params(params: Mapping[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree in the layout of the plain reference
    (``mla_moe_reference.init_params``)."""
    p = params["params"] if "params" in params else params

    def block(bp) -> Dict[str, Any]:
        out = {"norm1": bp["norm1"], "norm2": bp["norm2"], **bp["attn"]}
        if "moe" in bp:
            return {**out, **bp["moe"], **{"s_" + k[2:]: v for k, v in bp["shared"].items()}}
        return {**out, **bp["mlp"]}

    out = {"embed": p["embed"], "head": p["head"], "value": p["value"], "final_norm": p["final_norm"],
           "layers": [block(p[f"layer_{i}"]) for i in range(sum(1 for k in p if k.startswith("layer_")))]}
    if "mtp" in p:
        m = p["mtp"]
        out["mtp"] = {"enorm": m["enorm"], "hnorm": m["hnorm"], "eh_proj": m["eh_proj"], "norm": m["norm"],
                      "block": block(m["block"])}
    return out
