"""A causal sparse-expert transformer whose layers mix linear attention with a
recurrent state and full softmax attention, as flax modules: the Qwen3-Next
block (``model_type: qwen3_next``; layer equations in ``qwen3_next_reference.py``,
the plain reference every test compares this with).

What is particular here:

- in every period of ``full_attention_interval`` layers the first ones are
  Gated DeltaNet linear attention (``GatedDeltaNet``) and the last is gated full
  attention (``GatedAttention``).  A linear layer projects q, k, v, a gate ``z``
  and the decay's and write's inputs; q, k and v pass a causal depthwise
  convolution over positions and SiLU; q and k are L2-normed (q scaled), and the
  gated delta rule (``ops/gated_delta_rule.py``) carries a fixed-size state of
  ``d_k x d_v`` a value head along the sequence: the chunked form over whole
  sequences, the one-step recurrence in a cached pass.  Its output is normed
  head by head and gated by ``SiLU(z)`` before ``W_o``.
- a full layer is grouped-query attention with heads of 256: per-head
  zero-centred RMSNorm on q and k, rotate-half rotary on the first
  ``partial_rotary_factor`` of each head only, the TPU's blocked kernel
  (``ops/block_sparse_attention.py``) under the causal mask, and every head's
  output gated by ``sigmoid`` of a gate the query's projection makes beside it.
  The q/k norm and the partial rotation are the plain ``jnp`` chain (the fused
  ``qk_norm_rope`` op rotates whole heads; it is not used here).
- every layer is routed: softmax over all ``num_experts``, the top
  ``num_experts_per_tok`` renormalised (``sdar_moe.RoutedExperts`` under
  ``RoutedSpec(scoring="softmax")``, told which experts it holds), beside a
  shared SwiGLU expert weighed by ``sigmoid(m w_sg)``, a gate of its own.
- every RMSNorm but the linear layer's gated one is zero-centred: its gain is
  ``1 + w``, and ``w`` starts at 0.
- the cache of the cached pass (``step``, what collection makes) holds two kinds
  of state side by side: a linear layer's recurrent state (f32, ``Hv x d_k x
  d_v``) and its convolution's input at the last ``K - 1`` positions
  (``RecurrentCache``), both of fixed size whatever the length; a full layer's
  grouped keys and values, a position each.  ``prefill_cache`` lays out what the
  prefill hands over.

Norm statistics, the L2 norms, the decay, the router's scores, sigmoid, top-k
and every softmax run in float32 (the router's and the shared gate's products at
``highest`` precision), the delta rule in float32 at ``highest``; products
elsewhere in ``dtype``.  The blocks are unrolled (a scan would hide them from
the profiler's scopes) and each is rematerialised in the backward pass, keeping
the attention kernel's output (``sdar_moe.remat_block``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from sheeprl_tpu.models.mla_moe import SwiGLU, _stack, head_logits, head_terms, padded_cache
from sheeprl_tpu.models.sdar_moe import RoutedExperts, RoutedSpec, remat_block, rms_norm, rope
from sheeprl_tpu.ops.block_sparse_attention import SegmentMask, block_sparse_flash_attention
from sheeprl_tpu.ops.gated_delta_rule import gated_delta_rule

Dtype = Any
_INIT = nn.initializers.normal(0.02)
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The published ``config.json`` keys this model reads, and the cut."""

    hidden_size: int = 2048
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    full_attention_interval: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    num_hidden_layers: int = 48
    rms_norm_eps: float = 1e-6
    vocab_size: int = 151936
    experts_held: int = 512
    expert_offset: int = 0
    # tiles of the full layers' attention (the TPU's block-sparse flash kernel): None, by the call's shapes and mask
    # (``ops.block_sparse_attention._tiles``); an integer makes every tile of every kernel that wide
    attention_block: Optional[int] = None
    attention_interpret: bool = False  # run that kernel through Pallas' interpreter: off a TPU, for the tests

    @classmethod
    def from_mapping(cls, cfg: Mapping[str, Any]) -> "Qwen3NextConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        self = cls(**{k: v for k, v in dict(cfg).items() if k in names})
        if not 0 <= self.expert_offset <= self.num_experts - self.experts_held:
            raise ValueError(f"experts {self.expert_offset}..+{self.experts_held} are not among {self.num_experts}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads cannot share {self.num_key_value_heads} key-value heads")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(f"{self.linear_num_value_heads} value heads cannot share {self.linear_num_key_heads} key heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"rotate-half needs an even number of rotated dims within the head, got {self.rotary_dim}")
        return self

    @property
    def routed_spec(self) -> RoutedSpec:
        return RoutedSpec(self.hidden_size, self.moe_intermediate_size, self.num_experts, self.num_experts_per_tok,
                          self.experts_held, self.expert_offset, self.norm_topk_prob, "softmax")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_dim(self) -> int:
        """Channels of the linear layer's convolution: q, k and v side by side."""
        return 2 * self.linear_num_key_heads * self.linear_key_head_dim + self.linear_num_value_heads * self.linear_value_head_dim

    def is_linear(self, layer: int) -> bool:
        """Layers ``interval - 1, 2 interval - 1, ...`` are full attention, the others linear."""
        return (layer + 1) % self.full_attention_interval != 0


class RecurrentCache(NamedTuple):
    """A linear layer's entry of the cache: the delta rule's state (B, Hv, d_k, d_v) float32 and the
    convolution's input at the last ``K - 1`` positions (B, K - 1, channels)."""

    state: jax.Array
    conv: jax.Array


def zc_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """The zero-centred RMSNorm: its gain is ``1 + w``."""
    return rms_norm(x, 1.0 + w, eps)


def l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A_log``: the log of a uniform draw over (0, 16], as the modelling code initialises it."""
    return jnp.log(16.0 * (1.0 - jax.random.uniform(key, shape, dtype)))


class GatedDeltaNet(nn.Module):
    """Gated DeltaNet linear attention: projections, a causal depthwise convolution, the gated delta
    rule over a recurrent state, a gated RMSNorm and ``W_o``."""

    cfg: Qwen3NextConfig
    dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        d, hv, dv = c.hidden_size, c.linear_num_value_heads, c.linear_value_head_dim
        self.w_qkvz = self.param("w_qkvz", _INIT, (d, c.conv_dim + hv * dv), jnp.float32)
        self.w_ba = self.param("w_ba", _INIT, (d, 2 * hv), jnp.float32)
        self.conv = self.param("conv", _INIT, (c.linear_conv_kernel_dim, c.conv_dim), jnp.float32)
        self.A_log = self.param("A_log", _a_log_init, (hv,), jnp.float32)
        self.dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,), jnp.float32)
        self.norm = self.param("norm", nn.initializers.ones, (dv,), jnp.float32)
        self.w_out = self.param("w_out", _INIT, (hv * dv, d), jnp.float32)

    def _project(self, a: jax.Array):
        """``a`` (B, N, hidden) -> the convolution's input (B, N, channels) in ``dtype``, the gate ``z``
        (B, N, Hv, d_v) and the write's and the decay's inputs (B, N, Hv) float32.  The projections are
        laid key head by key head, as the published weights are: (q, k, the value heads' v, their z)
        and (their b, their a)."""
        c, dt = self.cfg, self.dtype
        hk, dk, dv = c.linear_num_key_heads, c.linear_key_head_dim, c.linear_value_head_dim
        rep = c.linear_num_value_heads // hk
        lead = a.shape[:-1]
        a = a.astype(dt)
        qkvz = (a @ self.w_qkvz.astype(dt)).reshape(*lead, hk, 2 * dk + 2 * rep * dv)
        q, k = qkvz[..., :dk], qkvz[..., dk: 2 * dk]
        v, z = qkvz[..., 2 * dk: 2 * dk + rep * dv], qkvz[..., 2 * dk + rep * dv:]
        x = jnp.concatenate([q.reshape(*lead, -1), k.reshape(*lead, -1), v.reshape(*lead, -1)], axis=-1)
        ba = jnp.matmul(a, self.w_ba.astype(dt), preferred_element_type=jnp.float32).reshape(*lead, hk, 2 * rep)
        b, a_ = (x_.reshape(*lead, hk * rep) for x_ in (ba[..., :rep], ba[..., rep:]))
        return x, z.reshape(*lead, hk * rep, dv), b, a_

    def _mix(self, conv_out: jax.Array, b: jax.Array, a_: jax.Array):
        """The convolution's output -> q, k (B, N, Hk, d_k; L2-normed, q scaled), v (B, N, Hv, d_v), the
        decay's log ``g`` and the write ``beta`` (B, N, Hv), float32."""
        c = self.cfg
        hk, dk, dv = c.linear_num_key_heads, c.linear_key_head_dim, c.linear_value_head_dim
        lead = conv_out.shape[:-1]
        mixed = jax.nn.silu(conv_out)
        q = l2norm(mixed[..., : hk * dk].reshape(*lead, hk, dk)) * (dk ** -0.5)
        k = l2norm(mixed[..., hk * dk: 2 * hk * dk].reshape(*lead, hk, dk))
        v = mixed[..., 2 * hk * dk:].reshape(*lead, -1, dv)
        g = -jnp.exp(self.A_log) * jax.nn.softplus(a_ + self.dt_bias)
        return q, k, v, g, jax.nn.sigmoid(b)

    def _out(self, o: jax.Array, z: jax.Array) -> jax.Array:
        dt = self.dtype
        y = rms_norm(o, self.norm, self.cfg.rms_norm_eps) * jax.nn.silu(z.astype(jnp.float32))
        return (y.reshape(*y.shape[:-2], -1).astype(dt) @ self.w_out.astype(dt)).astype(jnp.float32)

    def _conv(self, window: jax.Array, n: int) -> jax.Array:
        """The depthwise convolution's ``n`` outputs over ``window`` (B, n + K - 1, channels), float32."""
        w = self.conv
        return sum(w[j] * window[:, j: j + n].astype(jnp.float32) for j in range(w.shape[0]))

    def __call__(self, a: jax.Array):
        """``a``: (B, N, hidden), whole sequences.  Returns the output and the sequence's cache entry."""
        n, kernel = a.shape[1], self.cfg.linear_conv_kernel_dim
        with jax.named_scope("gdn_proj"):
            x, z, b, a_ = self._project(a)
            window = jnp.pad(x, ((0, 0), (kernel - 1, 0), (0, 0)))  # zeros before the first position
            q, k, v, g, beta = self._mix(self._conv(window, n), b, a_)
        with jax.named_scope("gdn_scan"):
            o, state = gated_delta_rule(q, k, v, g, beta)
        with jax.named_scope("gdn_out"):
            return self._out(o, z), RecurrentCache(state, window[:, n:])

    def cached(self, a: jax.Array, cache: RecurrentCache):
        """One token an env (B, 1, hidden) against the layer's cache entry; returns the output and the
        entry with this token taken in."""
        with jax.named_scope("gdn_proj"):
            x, z, b, a_ = self._project(a)
            window = jnp.concatenate([cache.conv, x.astype(cache.conv.dtype)], axis=1)
            q, k, v, g, beta = self._mix(self._conv(window, 1), b, a_)
        with jax.named_scope("gdn_scan"):
            o, state = gated_delta_rule(q, k, v, g, beta, cache.state)
        with jax.named_scope("gdn_out"):
            return self._out(o, z), RecurrentCache(state, window[:, 1:])


class GatedAttention(nn.Module):
    """Grouped-query attention with per-head zero-centred RMSNorm on q and k, rotary positions on the
    first ``rotary_dim`` numbers of each head, the causal mask, and a sigmoid gate on the output that
    the query's projection makes beside it."""

    cfg: Qwen3NextConfig
    dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        d, hd = c.hidden_size, c.head_dim
        self.wq = self.param("wq", _INIT, (d, c.num_attention_heads * 2 * hd), jnp.float32)
        self.wk = self.param("wk", _INIT, (d, c.num_key_value_heads * hd), jnp.float32)
        self.wv = self.param("wv", _INIT, (d, c.num_key_value_heads * hd), jnp.float32)
        self.wo = self.param("wo", _INIT, (c.num_attention_heads * hd, d), jnp.float32)
        self.q_norm = self.param("q_norm", nn.initializers.zeros, (hd,), jnp.float32)
        self.k_norm = self.param("k_norm", nn.initializers.zeros, (hd,), jnp.float32)

    def qkv(self, a: jax.Array, pos: jax.Array):
        """q (…, H, D), k and v (…, G, D) in ``dtype`` and the output's gate (…, H x D) float32."""
        c, dt = self.cfg, self.dtype
        a = a.astype(dt)
        lead, hd, r = a.shape[:-1], c.head_dim, c.rotary_dim
        # q and k leave the product in float32, the norm's input precision
        qg = jnp.matmul(a, self.wq.astype(dt), preferred_element_type=jnp.float32).reshape(*lead, -1, 2 * hd)
        k = jnp.matmul(a, self.wk.astype(dt), preferred_element_type=jnp.float32).reshape(*lead, -1, hd)

        def norm_rope(x, w):
            x = zc_norm(x, w, c.rms_norm_eps)
            return jnp.concatenate([rope(x[..., :r], pos, c.rope_theta), x[..., r:]], axis=-1).astype(dt)

        q, k = norm_rope(qg[..., :hd], self.q_norm), norm_rope(k, self.k_norm)
        v = (a @ self.wv.astype(dt)).reshape(*lead, -1, hd)
        return q, k, v, qg[..., hd:].reshape(*lead, -1)

    def out(self, o: jax.Array, gate: jax.Array) -> jax.Array:
        dt = self.dtype
        u = (o.reshape(*o.shape[:-2], -1).astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(dt)
        return (u @ self.wo.astype(dt)).astype(jnp.float32)

    def __call__(self, a: jax.Array, pos: jax.Array):
        """``a``: (B, N, hidden), whole sequences under the causal mask; also returns the sequence's keys
        and values."""
        c = self.cfg
        q, k, v, gate = self.qkv(a, pos)
        n = a.shape[-2]
        with jax.named_scope("gated_attn_kernel"):
            o = block_sparse_flash_attention(q, k, v, SegmentMask.causal(n, n), c.attention_block,
                                             interpret=c.attention_interpret)
        return self.out(o, gate), (k, v)

    def cached(self, a: jax.Array, pos: jax.Array, k_cache: jax.Array, v_cache: jax.Array, length: jax.Array):
        """One token an env (B, 1, hidden) at position ``length`` against the cached keys and values
        (B, S, kv heads, head) of the positions before it.  Returns the output and the caches with this
        token's entry written at ``length``."""
        c, dt = self.cfg, self.dtype
        q, k, v, gate = self.qkv(a, pos)
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, length, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, length, axis=1)
        bsz, rep = q.shape[0], c.num_attention_heads // c.num_key_value_heads
        qg = q.reshape(bsz, 1, c.num_key_value_heads, rep, c.head_dim)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_cache, preferred_element_type=jnp.float32)
        seen = jnp.arange(k_cache.shape[1]) <= length
        p = jax.nn.softmax(jnp.where(seen, scores / jnp.sqrt(jnp.float32(c.head_dim)), -jnp.inf), axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(dt), v_cache, preferred_element_type=jnp.float32)
        o = o.astype(dt).reshape(bsz, 1, c.num_attention_heads, c.head_dim)
        return self.out(o, gate), (k_cache, v_cache)


class Qwen3NextBlock(nn.Module):
    """A zero-centred norm, the layer's mixer (linear or full attention), a zero-centred norm, then the
    gated shared expert beside the routed layer."""

    cfg: Qwen3NextConfig
    linear: bool
    dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        zeros = lambda name: self.param(name, nn.initializers.zeros, (c.hidden_size,), jnp.float32)  # noqa: E731
        self.input_norm, self.post_attn_norm = zeros("input_norm"), zeros("post_attn_norm")
        self.mixer = GatedDeltaNet(c, self.dtype) if self.linear else GatedAttention(c, self.dtype)
        self.shared = SwiGLU(c.shared_expert_intermediate_size, self.dtype)
        self.shared_gate = self.param("shared_gate", _INIT, (c.hidden_size, 1), jnp.float32)
        self.moe = RoutedExperts(c, self.dtype)

    def _mix(self, h: jax.Array, run):
        with jax.named_scope("gdn_proj" if self.linear else "gated_attn_proj"):
            out, cache = run(zc_norm(h, self.input_norm, self.cfg.rms_norm_eps))
            return h + out, cache

    def _mlp(self, h1: jax.Array):
        with jax.named_scope("moe_shared"):
            m = zc_norm(h1, self.post_attn_norm, self.cfg.rms_norm_eps)
            # every token, once, weighed by its own gate: not a routed expert
            shared = jax.nn.sigmoid(jnp.dot(m, self.shared_gate, precision=jax.lax.Precision.HIGHEST)) * self.shared(m)
        y, aux = self.moe(m.reshape(-1, m.shape[-1]))
        with jax.named_scope("moe_shared"):
            return h1 + shared + y.reshape(h1.shape), aux

    def __call__(self, h: jax.Array, pos: jax.Array):
        """``h``: (B, N, hidden) float32, whole sequences."""
        h1, cache = self._mix(h, self.mixer if self.linear else (lambda a: self.mixer(a, pos)))
        h2, aux = self._mlp(h1)
        return h2, aux, cache

    def cached(self, h: jax.Array, pos: jax.Array, cache, length):
        if self.linear:
            h1, cache = self._mix(h, lambda a: self.mixer.cached(a, cache))
        else:
            h1, cache = self._mix(h, lambda a: self.mixer.cached(a, pos, *cache, length))
        h2, aux = self._mlp(h1)
        return h2, aux, cache


class Qwen3Next(nn.Module):
    """Embedding, the blocks, a zero-centred final norm, an untied head over the vocabulary slice and a
    scalar value head (this system's addition)."""

    cfg: Qwen3NextConfig
    dtype: Dtype = jnp.float32
    remat: bool = True
    config_class = Qwen3NextConfig  # what ``from_mapping`` builds the ``cfg`` from (``algos/ppo/lm_policy.py``)

    def setup(self) -> None:
        c = self.cfg
        self.embed = self.param("embed", _INIT, (c.vocab_size, c.hidden_size), jnp.float32)
        self.head = self.param("head", _INIT, (c.hidden_size, c.vocab_size), jnp.float32)
        self.value = self.param("value", _INIT, (c.hidden_size, 1), jnp.float32)
        self.final_norm = self.param("final_norm", nn.initializers.zeros, (c.hidden_size,), jnp.float32)
        block = remat_block(Qwen3NextBlock) if self.remat else Qwen3NextBlock
        self.layers = [block(c, c.is_linear(i), self.dtype, name=f"layer_{i}") for i in range(c.num_hidden_layers)]

    @staticmethod
    def prefill_cache(entries: List[Any], total_len: int) -> List[Any]:
        """The cache the collector carries, from what ``hidden(..., with_kv=True)`` handed over: a linear
        layer's recurrent entry as it is (fixed size), a full layer's keys and values padded to
        ``total_len`` positions (``mla_moe.padded_cache``)."""
        return [e if isinstance(e, RecurrentCache) else padded_cache([e], total_len)[0] for e in entries]

    def hidden(self, tokens: jax.Array, with_kv: bool = False):
        """Final-norm hidden states ``u`` (B, N, hidden) of whole sequences ``tokens`` (B, N), the
        routed layers' counters (a list, one entry a block) and, on request, every layer's cache entry
        (a prefill): a linear layer's final state and convolution input, a full layer's keys and values."""
        with jax.named_scope("lm_embed"):
            h = self.embed[tokens]
        pos = jnp.arange(tokens.shape[1])
        auxes, caches = [], []
        for layer in self.layers:
            h, aux, cache = layer(h, pos)
            auxes.append(aux)
            caches.append(cache)
        with jax.named_scope("lm_head"):
            u = zc_norm(h, self.final_norm, self.cfg.rms_norm_eps)
        return (u, auxes, caches) if with_kv else (u, auxes)

    def step(self, tokens: jax.Array, cache, length: jax.Array):
        """One token an env (B, 1) at position ``length`` against the cache (``prefill_cache``'s
        layout): the cached pass collection makes.  Returns the final-norm hidden state (B, 1, hidden),
        the blocks' counters stacked over them and the cache with the token taken in."""
        h = self.embed[tokens]
        pos = length + jnp.arange(1)
        auxes, new_cache = [], []
        for layer, entry in zip(self.layers, cache):
            h, aux, entry = layer.cached(h, pos, entry, length)
            new_cache.append(entry)
            auxes.append(aux)
        return zc_norm(h, self.final_norm, self.cfg.rms_norm_eps), _stack(auxes), new_cache

    def logits(self, at: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Hidden states (..., hidden) -> the policy's log-probabilities over the vocabulary slice
        (float32) and the value: what the collector samples from."""
        return head_logits(at, self.head, self.value, self.dtype)

    def evaluate(self, tokens: jax.Array, prompt_len: int):
        """One causal pass over whole episodes ``tokens`` (B, P + R).  Response token ``i`` sits at
        position ``P + i`` and was drawn from the distribution at position ``P + i - 1``, so the ``R``
        steps' log-probabilities, entropies and values are read at positions ``P - 1 .. P + R - 2``.
        Returns ``(logp, entropy, values)``, each (B, R), the routed layers' counters stacked over the
        blocks, and None (no auxiliary module)."""
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
    (``qwen3_next_reference.init_params``)."""
    p = params["params"] if "params" in params else params

    def block(bp) -> Dict[str, Any]:
        return {"input_norm": bp["input_norm"], "post_attn_norm": bp["post_attn_norm"], "shared_gate": bp["shared_gate"],
                **bp["mixer"], **bp["moe"], **{"s_" + k[2:]: v for k, v in bp["shared"].items()}}

    return {"embed": p["embed"], "head": p["head"], "value": p["value"], "final_norm": p["final_norm"],
            "layers": [block(p[f"layer_{i}"]) for i in range(sum(1 for k in p if k.startswith("layer_")))]}
