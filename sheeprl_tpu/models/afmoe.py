"""A causal sparse-expert transformer with windowed and global attention mixed
in one model, as flax modules: the AFMoE block (``model_type: afmoe``, Arcee's
Trinity family; layer equations in ``afmoe_reference.py``, the plain reference
every test compares this with).

What is particular here:

- ``layer_types`` gives every layer ``sliding_attention`` (the keys of the last
  ``sliding_window`` positions, the query's own included) or
  ``full_attention`` (every earlier key).  Both are grouped-query attention
  with per-head RMSNorm on q and k, and both run as the one blocked kernel of
  ``ops/block_sparse_attention.py``: a sliding layer under
  ``SegmentMask.causal(n, n, window=W)`` (the library's computed ``LocalMask``),
  a full layer under the causal mask (its ``CausalMask``).  Sliding layers
  rotate q and k (rotate-half, ``sdar_moe.rope``); full layers have no
  positional embedding at all (NoPE).  The per-head norm and the rotation are
  one op (``ops/qk_norm_rope.py``), as in SDAR.
- the attention's output is gated, head by head and number by number, by
  ``sigmoid(a W_out_gate)`` from a projection of its own, before ``W_o``.
- sandwich norms: an RMSNorm before and after attention and before and after
  the MLP, four a layer; the embedding is scaled by ``sqrt(hidden)`` (muP).
- the first ``num_dense_layers`` layers have a dense SwiGLU MLP; the others a
  shared SwiGLU expert, computed once for every token, beside the routed layer
  (``sdar_moe.RoutedExperts`` under the sigmoid rule: selected by ``score +
  bias``, weighed by the score, renormalised, scaled by ``route_scale``), which
  is told which experts it holds (``experts_held`` from ``expert_offset``).
- the cache of the cached pass (``step``, what collection makes) holds every
  layer's grouped keys and values (``num_key_value_heads`` x ``head_dim`` each,
  a position); a sliding layer reads a fixed-width slice of the last
  ``sliding_window`` positions ending at the current one, a full layer every
  position up to it.

Norm statistics, router scores, sigmoid, top-k and every softmax run in
float32 (the router's product at ``highest`` precision); products elsewhere in
``dtype``.  The blocks are unrolled (a scan would hide them from the
profiler's scopes) and each is rematerialised in the backward pass, keeping
the attention kernel's output (``sdar_moe.remat_block``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from sheeprl_tpu.models.mla_moe import SwiGLU, _stack, head_logits, head_terms, padded_cache
from sheeprl_tpu.models.sdar_moe import RoutedExperts, RoutedSpec, remat_block, rms_norm
from sheeprl_tpu.ops.block_sparse_attention import SegmentMask, block_sparse_flash_attention
from sheeprl_tpu.ops.qk_norm_rope import qk_norm_rope

Dtype = Any
_INIT = nn.initializers.normal(0.02)
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The published ``config.json`` keys this model reads, and the cut."""

    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    num_dense_layers: int = 2
    num_hidden_layers: int = 32
    # every layer's kind, the published list: three sliding layers, then a full one, eight times
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 8
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    route_norm: bool = True
    route_scale: float = 2.826
    score_func: str = "sigmoid"
    mup_enabled: bool = True
    vocab_size: int = 200192
    experts_held: int = 128
    expert_offset: int = 0
    # tiles of the attention (the TPU's block-sparse flash kernel): None, by the call's shapes and mask
    # (``ops.block_sparse_attention._tiles``); an integer makes every tile of every kernel that wide
    attention_block: Optional[int] = None
    attention_interpret: bool = False  # run that kernel and the q/k norm's through Pallas' interpreter: off a TPU, for the tests

    @classmethod
    def from_mapping(cls, cfg: Mapping[str, Any]) -> "AfmoeConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in dict(cfg).items() if k in names}
        kw["layer_types"] = tuple(str(t) for t in kw.get("layer_types", cls.layer_types))
        self = cls(**kw)
        if len(self.layer_types) != self.num_hidden_layers or not set(self.layer_types) <= {SLIDING, FULL}:
            raise ValueError(f"layer_types must name {SLIDING} or {FULL} for each of {self.num_hidden_layers} layers, "
                             f"got {self.layer_types}")
        if not 0 <= self.expert_offset <= self.num_experts - self.experts_held:
            raise ValueError(f"experts {self.expert_offset}..+{self.experts_held} are not among {self.num_experts}")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError(f"{self.num_dense_layers} leading dense layers of {self.num_hidden_layers}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads cannot share {self.num_key_value_heads} key-value heads")
        if self.head_dim % 2 or self.sliding_window < 1:
            raise ValueError("rotate-half needs an even head_dim, and a window holds at least the query's own key")
        return self

    @property
    def routed_spec(self) -> RoutedSpec:
        return RoutedSpec(self.hidden_size, self.moe_intermediate_size, self.num_experts, self.num_experts_per_tok,
                          self.experts_held, self.expert_offset, self.route_norm, self.score_func, self.route_scale)

    def window(self, layer: int) -> int:
        """The keys a query of ``layer`` sees back to and including its own; 0 for a full layer."""
        return self.sliding_window if self.layer_types[layer] == SLIDING else 0


class GatedAttention(nn.Module):
    """Grouped-query attention with per-head RMSNorm on q and k, rotary positions on a windowed layer
    only (``window`` > 0; 0 is a full layer: causal, no positional embedding) and a sigmoid gate on
    the output."""

    cfg: AfmoeConfig
    window: int
    dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        d, hd = c.hidden_size, c.head_dim
        self.wq = self.param("wq", _INIT, (d, c.num_attention_heads * hd), jnp.float32)
        self.wk = self.param("wk", _INIT, (d, c.num_key_value_heads * hd), jnp.float32)
        self.wv = self.param("wv", _INIT, (d, c.num_key_value_heads * hd), jnp.float32)
        self.w_out_gate = self.param("w_out_gate", _INIT, (d, c.num_attention_heads * hd), jnp.float32)
        self.wo = self.param("wo", _INIT, (c.num_attention_heads * hd, d), jnp.float32)
        self.q_norm = self.param("q_norm", nn.initializers.ones, (hd,), jnp.float32)
        self.k_norm = self.param("k_norm", nn.initializers.ones, (hd,), jnp.float32)

    def qkv(self, a: jax.Array, pos: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
        c, dt = self.cfg, self.dtype
        a = a.astype(dt)
        lead = a.shape[:-1]
        rotary = pos if self.window else None  # a full layer has no positional embedding
        q, k = ((a @ w.astype(dt)).reshape(*lead, heads, c.head_dim)
                for w, heads in ((self.wq, c.num_attention_heads), (self.wk, c.num_key_value_heads)))
        q, k = (qk_norm_rope(x, g, c.rms_norm_eps, rotary, c.rope_theta, interpret=c.attention_interpret)
                for x, g in ((q, self.q_norm), (k, self.k_norm)))
        v = (a @ self.wv.astype(dt)).reshape(*lead, c.num_key_value_heads, c.head_dim)
        return q, k, v

    def out(self, o: jax.Array, a: jax.Array) -> jax.Array:
        """The heads' outputs (..., H, head) gated by ``sigmoid(a W_out_gate)``, then ``W_o``."""
        dt = self.dtype
        gate = jax.nn.sigmoid((a.astype(dt) @ self.w_out_gate.astype(dt)).astype(jnp.float32))
        u = (o.reshape(*o.shape[:-2], -1).astype(jnp.float32) * gate).astype(dt)
        return (u @ self.wo.astype(dt)).astype(jnp.float32)

    def __call__(self, a: jax.Array, pos: jax.Array):
        """``a``: (B, N, hidden), whole sequences, under the window or the causal mask; also returns the
        sequence's keys and values."""
        c = self.cfg
        q, k, v = self.qkv(a, pos)
        n = a.shape[-2]
        with jax.named_scope("swa_kernel" if self.window else "global_kernel"):
            o = block_sparse_flash_attention(q, k, v, SegmentMask.causal(n, n, self.window), c.attention_block,
                                             interpret=c.attention_interpret)
        return self.out(o, a), (k, v)

    def cached(self, a: jax.Array, pos: jax.Array, k_cache: jax.Array, v_cache: jax.Array, length: jax.Array):
        """One token an env (B, 1, hidden) at position ``length`` against the cached keys and values
        (B, S, kv heads, head) of the positions before it; a windowed layer reads the fixed-width slice
        of the last ``window`` positions ending at this one.  Returns the output and the caches with this
        token's entry written at ``length``."""
        c, dt = self.cfg, self.dtype
        q, k, v = self.qkv(a, pos)
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, length, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, length, axis=1)
        keys, values, first = k_cache, v_cache, 0
        if 0 < self.window < k_cache.shape[1]:
            first = jnp.clip(length + 1 - self.window, 0, k_cache.shape[1] - self.window)
            keys, values = (jax.lax.dynamic_slice_in_dim(x, first, self.window, axis=1) for x in (k_cache, v_cache))
        at = first + jnp.arange(keys.shape[1])
        seen = (at <= length) & ((at > length - self.window) if self.window else True)
        bsz, rep = q.shape[0], c.num_attention_heads // c.num_key_value_heads
        qg = q.reshape(bsz, 1, c.num_key_value_heads, rep, c.head_dim)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, keys, preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(seen, scores / jnp.sqrt(jnp.float32(c.head_dim)), -jnp.inf), axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(dt), values, preferred_element_type=jnp.float32)
        o = o.astype(dt).reshape(bsz, 1, c.num_attention_heads, c.head_dim)
        return self.out(o, a), (k_cache, v_cache)


class AfmoeBlock(nn.Module):
    """Sandwich-normed gated attention, then a sandwich-normed dense SwiGLU MLP (``routed=False``) or
    the shared expert beside the routed layer."""

    cfg: AfmoeConfig
    routed: bool
    window: int
    dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        ones = lambda name: self.param(name, nn.initializers.ones, (c.hidden_size,), jnp.float32)  # noqa: E731
        self.input_norm, self.post_attn_norm = ones("input_norm"), ones("post_attn_norm")
        self.pre_mlp_norm, self.post_mlp_norm = ones("pre_mlp_norm"), ones("post_mlp_norm")
        self.attn = GatedAttention(c, self.window, self.dtype)
        if self.routed:
            self.shared = SwiGLU(c.num_shared_experts * c.moe_intermediate_size, self.dtype)
            self.moe = RoutedExperts(c, self.dtype)
        else:
            self.mlp = SwiGLU(c.intermediate_size, self.dtype)

    def _attend(self, h: jax.Array, run):
        eps = self.cfg.rms_norm_eps
        with jax.named_scope("gqa_proj"):
            o, kv = run(rms_norm(h, self.input_norm, eps))
            return h + rms_norm(o, self.post_attn_norm, eps), kv

    def _mlp(self, h1: jax.Array):
        eps = self.cfg.rms_norm_eps
        if not self.routed:
            with jax.named_scope("dense_mlp"):
                return h1 + rms_norm(self.mlp(rms_norm(h1, self.pre_mlp_norm, eps)), self.post_mlp_norm, eps), None
        with jax.named_scope("moe_shared"):
            m = rms_norm(h1, self.pre_mlp_norm, eps)
            shared = self.shared(m)  # every token, once: not a routed expert, no weight
        y, aux = self.moe(m.reshape(-1, m.shape[-1]))
        with jax.named_scope("moe_shared"):
            return h1 + rms_norm(shared + y.reshape(h1.shape), self.post_mlp_norm, eps), aux

    def __call__(self, h: jax.Array, pos: jax.Array):
        """``h``: (B, N, hidden) float32, whole sequences."""
        h1, kv = self._attend(h, lambda a: self.attn(a, pos))
        h2, aux = self._mlp(h1)
        return h2, aux, kv

    def cached(self, h: jax.Array, pos: jax.Array, k_cache, v_cache, length):
        h1, caches = self._attend(h, lambda a: self.attn.cached(a, pos, k_cache, v_cache, length))
        h2, aux = self._mlp(h1)
        return h2, aux, caches


class Afmoe(nn.Module):
    """Embedding (scaled by ``sqrt(hidden)`` where ``mup_enabled``), the blocks, final norm, an untied
    head over the vocabulary slice and a scalar value head (this system's addition)."""

    cfg: AfmoeConfig
    dtype: Dtype = jnp.float32
    remat: bool = True
    config_class = AfmoeConfig  # what ``from_mapping`` builds the ``cfg`` from (``algos/ppo/lm_policy.py``)
    prefill_cache = staticmethod(padded_cache)  # the collector's cache from the prefill's keys and values

    def setup(self) -> None:
        c = self.cfg
        self.embed = self.param("embed", _INIT, (c.vocab_size, c.hidden_size), jnp.float32)
        self.head = self.param("head", _INIT, (c.hidden_size, c.vocab_size), jnp.float32)
        self.value = self.param("value", _INIT, (c.hidden_size, 1), jnp.float32)
        self.final_norm = self.param("final_norm", nn.initializers.ones, (c.hidden_size,), jnp.float32)
        block = remat_block(AfmoeBlock) if self.remat else AfmoeBlock
        self.layers = [block(c, i >= c.num_dense_layers, c.window(i), self.dtype, name=f"layer_{i}")
                       for i in range(c.num_hidden_layers)]

    def _embed(self, tokens: jax.Array) -> jax.Array:
        h = self.embed[tokens]
        return h * jnp.sqrt(jnp.float32(self.cfg.hidden_size)) if self.cfg.mup_enabled else h

    def hidden(self, tokens: jax.Array, with_kv: bool = False):
        """Final-norm hidden states ``u`` (B, N, hidden) of whole sequences ``tokens`` (B, N), the
        routed layers' counters (a list, one entry a routed block) and, on request, every layer's
        keys and values (a prefill)."""
        with jax.named_scope("lm_embed"):
            h = self._embed(tokens)
        pos = jnp.arange(tokens.shape[1])
        auxes, kvs = [], []
        for layer in self.layers:
            h, aux, kv = layer(h, pos)
            kvs.append(kv)
            if aux is not None:
                auxes.append(aux)
        with jax.named_scope("lm_head"):
            u = rms_norm(h, self.final_norm, self.cfg.rms_norm_eps)
        return (u, auxes, kvs) if with_kv else (u, auxes)

    def step(self, tokens: jax.Array, cache, length: jax.Array):
        """One token an env (B, 1) at position ``length`` against the cache (per layer ``(k, v)``, each
        (B, S, kv heads, head)): the cached pass collection makes.  Returns the final-norm hidden state
        (B, 1, hidden), the routed blocks' counters stacked over them and the caches with the token
        written."""
        h = self._embed(tokens)
        pos = length + jnp.arange(1)
        auxes, new_cache = [], []
        for layer, (k_cache, v_cache) in zip(self.layers, cache):
            h, aux, caches = layer.cached(h, pos, k_cache, v_cache, length)
            new_cache.append(caches)
            if aux is not None:
                auxes.append(aux)
        return rms_norm(h, self.final_norm, self.cfg.rms_norm_eps), _stack(auxes), new_cache

    def logits(self, at: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Hidden states (..., hidden) -> the policy's log-probabilities over the vocabulary slice
        (float32) and the value: what the collector samples from."""
        return head_logits(at, self.head, self.value, self.dtype)

    def evaluate(self, tokens: jax.Array, prompt_len: int):
        """One causal pass over whole episodes ``tokens`` (B, P + R).  Response token ``i`` sits at
        position ``P + i`` and was drawn from the distribution at position ``P + i - 1``, so the ``R``
        steps' log-probabilities, entropies and values are read at positions ``P - 1 .. P + R - 2``.
        Returns ``(logp, entropy, values)``, each (B, R), the routed layers' counters stacked over the
        routed blocks, and None (no auxiliary module)."""
        p = int(prompt_len)
        if p < 1:
            raise ValueError("a response token is read one position before it: prompt_len >= 1")
        u, auxes = self.hidden(tokens)
        with jax.named_scope("lm_head"):
            at = u[:, p - 1: -1]
            logp, entropy, _ = head_terms(at, self.head, tokens[:, p:], self.dtype, self.remat)
            values = jnp.dot(at, self.value, precision=jax.lax.Precision.HIGHEST)[..., 0]
        return (logp, entropy, values), _stack(auxes), None

    def __call__(self, tokens: jax.Array, prompt_len: int):
        return self.evaluate(tokens, prompt_len)


def reference_params(params: Mapping[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree in the layout of the plain reference
    (``afmoe_reference.init_params``)."""
    p = params["params"] if "params" in params else params
    norms = ("input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")

    def block(bp) -> Dict[str, Any]:
        out = {**{k: bp[k] for k in norms}, **bp["attn"]}
        if "moe" in bp:
            return {**out, **bp["moe"], **{"s_" + k[2:]: v for k, v in bp["shared"].items()}}
        return {**out, **bp["mlp"]}

    return {"embed": p["embed"], "head": p["head"], "value": p["value"], "final_norm": p["final_norm"],
            "layers": [block(p[f"layer_{i}"]) for i in range(sum(1 for k in p if k.startswith("layer_")))]}
