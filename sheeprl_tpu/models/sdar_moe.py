"""SDAR-MoE as flax modules: a sparse-expert transformer that generates by
diffusion over blocks (``model_type: sdar_moe``; layer equations in
``sdar_moe_reference.py``, the plain reference every test compares this with).

What is particular here:

- ``RoutedExperts`` (also ``models/mla_moe.py``'s routed layer, under another
  routing rule: ``RoutedSpec``) is told which experts it holds (``experts_held`` from
  ``expert_offset``): it routes over all ``num_experts``, keeps the published
  top-k, and computes its own experts' part of the result.  That is the layer
  expert parallelism needs; on one chip it runs without its exchange, and what
  absent experts would add is left out (``howto/language_model_policy.md``).
  The held experts run as one grouped product (``jax.lax.ragged_dot``, tokens
  sorted by expert) over a sorted buffer whose length follows the counted
  load: ``short_buffer_rows`` rows (``SHORT_BUFFER_SHARES`` even shares) when
  the assignments to held experts fit, else, by ``jax.lax.cond``, a row for
  every assignment a token could make to a held expert (``tokens x min(top_k,
  experts_held)``).  Where the short buffer is large (``COMPACT_OVER_BYTES``:
  SDAR's update, not the causal model's nor a collector's prefill) the token
  side follows the counted load with it: beside the short buffer the combine,
  the routing weights' gradient and the dispatch's backward read a token's
  held choices only (``compact_slots``: a few rows a token, and a short list
  of the tokens that hold more), beside the worst-case buffer a row for every
  (token, choice) pair; the one conditional then takes the short form only if
  the load fits the buffer AND that list.  Both branches hold every held
  assignment: it is dropless, nothing is ever cut, whatever the imbalance.
  Where the short length would save nothing (all experts held, or few tokens)
  there is one length, the pair-wide token side and no conditional.
- attention takes its mask as data (``ops.block_sparse_attention.SegmentMask``): one
  packed episode holds the clean sequence and every denoising step's noised
  copy of its block (``EpisodeLayout``), so that one forward pass scores a whole
  denoising trajectory.
- ``SdarMoE.block`` is the cached pass collection makes: a block of
  ``block_length`` tokens against the clean keys and values of the finished
  blocks (a denoising pass, or the pass that writes a finished block).

Router logits, softmax, top-k and every norm run in float32 (the router's
product at ``highest`` precision); products elsewhere in ``dtype``.  With
``remat`` each layer is rematerialised in the backward pass, keeping the
attention kernel's output (``remat_block``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.ops.block_sparse_attention import KERNEL_RESIDUALS, SegmentMask, block_sparse_flash_attention

Dtype = Any
_INIT = nn.initializers.normal(0.02)
_NEG = -1e30  # the logit of an id the policy may never draw ([MASK])


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The published ``config.json`` keys this model reads, and the cut."""

    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    vocab_size: int = 151936
    experts_held: int = 128
    expert_offset: int = 0
    block_length: int = 4
    denoise_steps: int = 4
    mask_id: int = 151935
    # tiles of the masked attention (the TPU's block-sparse flash kernel): None, by the call's shapes and mask
    # (``ops.block_sparse_attention._tiles``); an integer makes every tile of every kernel that wide
    attention_block: Optional[int] = None
    attention_interpret: bool = False  # run that kernel through Pallas' interpreter: off a TPU, for the tests

    @classmethod
    def from_mapping(cls, cfg: Mapping[str, Any]) -> "SdarConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        self = cls(**{k: v for k, v in dict(cfg).items() if k in names})
        if self.denoise_steps != self.block_length:
            raise ValueError("one token a step: denoise_steps must equal block_length")
        if not 0 <= self.expert_offset <= self.num_experts - self.experts_held:
            raise ValueError(f"experts {self.expert_offset}..+{self.experts_held} are not among {self.num_experts}")
        if not 0 <= self.mask_id < self.vocab_size:
            raise ValueError(f"mask_id {self.mask_id} lies outside the vocabulary slice of {self.vocab_size}")
        return self

    @property
    def routed_spec(self) -> "RoutedSpec":
        return RoutedSpec(self.hidden_size, self.moe_intermediate_size, self.num_experts, self.num_experts_per_tok,
                          self.experts_held, self.expert_offset, self.norm_topk_prob)


@dataclasses.dataclass(frozen=True)
class EpisodeLayout:
    """One packed episode: ``prompt_len + response_len`` clean positions, then
    for every response block its ``steps`` noised copies (copy ``j`` is the
    block as it stood before denoising step ``j``), at the clean block's rotary
    positions.  ``response_len = 0`` is a clean-only sequence (a prefill)."""

    prompt_len: int
    response_len: int
    block: int
    steps: int

    @property
    def n_clean(self) -> int:
        return self.prompt_len + self.response_len

    @property
    def n_blocks(self) -> int:
        return self.response_len // self.block

    @property
    def length(self) -> int:
        return self.n_clean + self.steps * self.response_len

    @functools.cached_property
    def _arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        if self.prompt_len % self.block or self.response_len % self.block:
            raise ValueError(f"prompt and response must be multiples of the block ({self.block})")
        clean = np.arange(self.n_clean)
        b = np.repeat(np.arange(self.n_blocks), self.steps * self.block)
        j = np.tile(np.repeat(np.arange(self.steps), self.block), self.n_blocks)
        u = np.tile(np.arange(self.block), self.n_blocks * self.steps)
        noised = self.prompt_len + b * self.block + u
        pos = np.concatenate([clean, noised]).astype(np.int32)
        copy = np.concatenate([np.zeros(self.n_clean, np.int64), 1 + b * self.steps + j]).astype(np.int32)
        return pos, pos // self.block, copy

    @property
    def positions(self) -> np.ndarray:
        return self._arrays[0]

    @functools.cached_property
    def mask(self) -> SegmentMask:
        """A clean position of block ``b`` sees clean positions of blocks
        ``<= b``; a noised one sees clean positions of blocks ``< b`` and its
        own copy; nothing else sees a noised position."""
        _, blk, copy = self._arrays
        return SegmentMask(q_limit=blk - (copy > 0), q_segment=copy, k_index=blk, k_segment=copy)

    def pack(self, prompt: jax.Array, actions: jax.Array, mask_id: int) -> Tuple[jax.Array, jax.Array]:
        """``prompt`` (B, P) and the episode's actions (B, T, 2) = (position in
        the block, token), one a denoising step -> the packed token ids (B, N)
        and the packed index of every step's action position (B, T)."""
        bsz = prompt.shape[0]
        order = actions[..., 0].reshape(bsz, self.n_blocks, self.steps)
        token = actions[..., 1].reshape(bsz, self.n_blocks, self.steps)
        hit = order[..., None] == jnp.arange(self.block)  # (B, nb, step, u): step j reveals position u
        response = (hit * token[..., None]).sum(-2)
        step_of = (hit * jnp.arange(self.steps)[:, None]).sum(-2)
        copies = jnp.where(step_of[:, :, None, :] < jnp.arange(self.steps)[:, None], response[:, :, None, :], mask_id)
        tokens = jnp.concatenate([prompt, response.reshape(bsz, -1), copies.reshape(bsz, -1)], axis=1)
        base = self.n_clean + (jnp.arange(self.n_blocks)[:, None] * self.steps + jnp.arange(self.steps)) * self.block
        return tokens.astype(jnp.int32), (base + order).reshape(bsz, -1).astype(jnp.int32)


def remat_block(block, **kwargs):
    """``block`` (a module class) rematerialised in the backward pass, keeping beside its input the
    attention kernel's output and log-sum-exp (``KERNEL_RESIDUALS``): all that the kernel's backward
    rule wants of its forward, so the rematerialised block does not run the forward kernel again.
    Everything else in the block is computed a second time."""
    return nn.remat(block, policy=jax.checkpoint_policies.save_only_these_names(KERNEL_RESIDUALS), **kwargs)


def rms_norm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate-half over the whole head, positions given per token.
    ``x``: (..., N, H, D) float32; ``pos``: (..., N) or (N,)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# ------------------------------------------------------- dropless dispatch
# Assignments are (token, choice) pairs, N x k of them.  ``perm`` (rows,) lists the assignments in
# sorted order (by held expert; the head of a permutation, long enough for every assignment to a held
# expert this call) and ``inv`` (N, k) is each assignment's row.  Both directions of both moves are
# gathers: XLA's own transpose of a gather is a scatter-add, which a TPU serialises.
#
# The token side sums, for every token, the rows of its held assignments (the combine's forward, the
# dispatch's backward).  Its k-wide form reads a row for EVERY pair and zeroes those not held
# (``_rows_of``: an (N, k, d) array, 553 MB at the published sizes, 81-93 % of it zeros where a chip
# holds an eighth of the experts); its compact form (``slots`` given) reads ``c`` rows a token, the
# token's first ``c`` held choices, and the few tokens that hold more get the k-wide form over a
# fixed list of ``r`` of them, merged back by a gather (``compact_slots``, ``_slots``).  The routing
# weights' gradient needs no rows of its own in row space (``_combine_bwd``).
def _rows_of(sorted_rows, inv, keep):
    """Every assignment's row (N, k, d); zeros where ``keep`` (N, k) is unset."""
    rows = sorted_rows[jnp.minimum(inv, sorted_rows.shape[0] - 1)]
    return jnp.where(keep[..., None], rows, 0)


def _slots(held, c, r):
    """What the compact token-side sums need of ``held`` (N, k): ``slot_of`` (N, k), the place of a
    held choice among its token's held choices (k where not held); ``over`` (r,), the tokens with more
    than ``c`` held choices (N from the list's end on) and ``over_at`` (N,), such a token's place in
    that list (-1 for every other token); and how many such tokens there are: the list holds them all
    only if that is at most ``r``."""
    k = held.shape[1]
    slot_of = jnp.where(held, jnp.cumsum(held, axis=1) - 1, k).astype(jnp.int32)
    more = held.sum(1) > c
    listed = jnp.cumsum(more)
    over = jnp.searchsorted(listed, jnp.arange(1, r + 1), method="compare_all").astype(jnp.int32)
    return (slot_of, over, jnp.where(more, listed - 1, -1).astype(jnp.int32)), listed[-1]


def _held_sums(sorted_rows, w, inv, slots, c):
    """``out[t] = sum_j w[t, j] * sorted_rows[inv[t, j]]`` over token ``t``'s held choices, float32:
    ``c`` rows a token, and the listed tokens' further rows on top."""
    slot_of, over, over_at = slots
    n, k = inv.shape
    # a gather of (N, d) a slot: as one (N, c, d) array c is padded to a whole tile of rows in every token, and as
    # (c, N, d) the rows are first converted to float32 by an op of their own (415 MB at the published sizes)
    hit = slot_of[None] == jnp.arange(c)[:, None, None]  # (c, N, k): the choice that is its token's held choice number s
    at_slot, w_slot = (jnp.where(hit, x[None], 0).sum(-1) for x in (inv, w.astype(jnp.float32)))
    out = sum(_rows_of(sorted_rows, at_slot[s], hit[s].any(-1)).astype(jnp.float32) * w_slot[s][:, None] for s in range(c))
    at = jnp.minimum(over, n - 1)
    rest = (slot_of[at] >= c) & (slot_of[at] < k) & (over < n)[:, None]  # (r, k): the listed tokens' further held choices
    extra = jnp.einsum("rkd,rk->rd", _rows_of(sorted_rows, inv[at], rest), jnp.where(rest, w[at], 0),
                       preferred_element_type=jnp.float32)
    return out + jnp.where((over_at >= 0)[:, None], extra[jnp.clip(over_at, 0, over.shape[0] - 1)], 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(c, m, perm, inv, held, slots):
    """Rows of ``m`` (N, d) in sorted order: row ``r`` is the token of assignment ``perm[r]``."""
    return m[perm // inv.shape[1]]


def _dispatch_fwd(c, m, perm, inv, held, slots):
    return _dispatch(c, m, perm, inv, held, slots), (inv, held, slots)


def _dispatch_bwd(c, res, g):
    inv, held, slots = res
    if slots is None:
        gm = _rows_of(g, inv, held).sum(1).astype(g.dtype)
    else:
        gm = _held_sums(g, held.astype(g.dtype), inv, slots, c).astype(g.dtype)
    return gm, None, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(c, y_sorted, w, perm, inv, slots):
    """``out[t] = sum_j w[t, j] * y_sorted[inv[t, j]]``, float32; ``w`` (N, k) is 0 where the
    assignment is not held."""
    if slots is not None:
        return _held_sums(y_sorted, w, inv, slots, c)
    return jnp.einsum("nkd,nk->nd", _rows_of(y_sorted, inv, w != 0), w, preferred_element_type=jnp.float32)


def _combine_fwd(c, y_sorted, w, perm, inv, slots):
    return _combine(c, y_sorted, w, perm, inv, slots), (y_sorted, w, perm, inv, slots)


def _combine_bwd(c, res, g):
    y_sorted, w, perm, inv, slots = res
    k = w.shape[1]
    if slots is None:
        gw = jnp.einsum("nkd,nd->nk", _rows_of(y_sorted, inv, w != 0), g, preferred_element_type=jnp.float32)
        # gathered in the rows' dtype: in f32 this gather alone wrote 1.1 GB a layer at the published sizes
        gy = g.astype(y_sorted.dtype)[perm // k] * w.reshape(-1)[perm][:, None].astype(y_sorted.dtype)
    else:
        # in row space: row r belongs to token perm[r] // k, so <y_sorted[inv[t, j]], g[t]> is row inv[t, j] of the
        # rows' own products with their tokens' g (float32, as the k-wide form's), and gw a gather of scalars
        g_rows = g[perm // k]
        gw_rows = (y_sorted.astype(jnp.float32) * g_rows).sum(-1)
        gw = jnp.where(w != 0, gw_rows[jnp.minimum(inv, gw_rows.shape[0] - 1)], 0)
        gy = g_rows.astype(y_sorted.dtype) * w.reshape(-1)[perm][:, None].astype(y_sorted.dtype)
    return gy, gw, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


# ------------------------------------------- the length of the sorted buffer
# The worst case, a row for every assignment a token could make to a held expert, is N x min(k, held);
# the even share of the held experts is N x k x held / experts, an eighth of it at one chip's share of
# an 8-way expert-parallel layer.  Gathers, grouped products and SwiGLU's passes all walk the buffer,
# padding included.  The short length is SHORT_BUFFER_SHARES even shares, set from chip readings
# (PERF.md section 6, PR 29; ``benchmarks/sdar_load_readings.py``): under a router drawn at 0.02 a
# layer's heaviest possible minibatch read up to 3.15 even shares over 40 seeds (over 2 on 60 of 160
# layer readings, over 3 on 2), its mean minibatch at most 1.83; at 2 shares one layer pass in 16
# fell back to the worst case and the steps' cost followed the seed.
SHORT_BUFFER_SHARES = 3
ROW_TILE = 512  # the short length is whole tiles of rows
# Beside the short buffer the token side reads ``c`` rows a token, one more than the buffer's own rows a
# token, and lists the tokens that hold more: a sixteenth of the tokens at most, else the call falls
# back with the buffer.  Set from the same readings taken per token (PERF.md section 6, PR 33): a
# random router sends its tokens to held experts far less evenly than an even draw would (0.9 % of
# the tokens over 3 of 16 held among 128): in the heaviest minibatch of a layer (160 readings: 40
# seeds x 4 layers, 16,896 tokens) the tokens that hold more than 3 choices read 218 at the median,
# 2,061 at the ninth decile and 5,897 at most, those that hold more than 4 read 3, 79 and 1,305 (over
# 1,056 on that one reading); at the causal cell's sizes (8,192 tokens, 16 of 256 held, 200 readings)
# 432 tokens at most hold more than 2 choices and 51 more than 3, of a list of 512.
OVERFLOW_LIST_SHARE = 16
# Where the short buffer is small the pair-wide form stays: what a row-wide gather costs follows the size of
# the array it reads from.  From the causal cell's short buffer (12,288 rows of 2,048 in bf16, 50 MB) a
# row for every pair (65,536) is gathered in 0.415 ms, 6 ns a row, and the compact form won 1.6 ms a step
# of dispatch there while the program around it lost 4.5 (469.2 against 466.3 ms a step); from SDAR's
# (50,688 rows, 208 MB) the 135,168 rows take 4.75 ms, 35 ns a row, and the compact form wins 35 ms a
# step (my chip runs, PR 33; the compiled programs keep a pool of 112 MiB on the chip, which the first
# source can live in and the second cannot).  So the compact form is taken where the short buffer's bytes
# pass this bound, and below it both lengths read every pair: those programs lower as they did.
COMPACT_OVER_BYTES = 128 * 2**20


def short_buffer_rows(n: int, k: int, held_n: int, num_experts: int) -> int:
    """Rows of the short sorted buffer for ``n`` tokens, never above the worst case."""
    fit = -(-SHORT_BUFFER_SHARES * n * k * held_n // num_experts)
    return min(-(-fit // ROW_TILE) * ROW_TILE, n * min(k, held_n))


def compact_slots(n: int, k: int, held_n: int, num_experts: int, row_bytes: int) -> Optional[Tuple[int, int]]:
    """``(c, r)`` of the compact token-side sums beside the short buffer for ``n`` tokens, rows of
    ``row_bytes``: the rows a token reads, one more than the short buffer's own rows a token rounded
    up, and the length of the list of tokens that hold more than ``c`` choices.  None where the short
    buffer is too small for the compact form to pay (``COMPACT_OVER_BYTES``)."""
    rows_fit = short_buffer_rows(n, k, held_n, num_experts)
    if rows_fit * row_bytes <= COMPACT_OVER_BYTES:
        return None
    return -(-rows_fit // n) + 1, -(-n // OVERFLOW_LIST_SHARE)


def _experts_at(rows, c, dtype, m, w, w_gate, w_up, w_down, order, inv, held, group_sizes, *slots):
    """Dispatch -> SwiGLU experts -> combine over a sorted buffer of ``rows`` rows, which must hold
    every held assignment (``group_sizes.sum() <= rows``); products in ``dtype``.  ``m`` (N, d) and
    ``w`` (N, k) float32, ``w`` 0 where the assignment is not held.  The token side reads every
    (token, choice) pair where ``c`` is None, else ``c`` rows a token through ``slots``, whose list
    must hold every token with more than ``c`` held choices."""
    slots = None if c is None else slots
    with jax.named_scope("moe_dispatch"):
        perm = order[:rows]
        x = _dispatch(c, m.astype(dtype), perm, inv, held, slots)
    with jax.named_scope("moe_experts"):
        # gate and up as one grouped product: the sorted rows are read once, their gradient summed once
        w_in = jnp.concatenate([w_gate, w_up], axis=-1).astype(dtype)
        gate, up = jnp.split(jax.lax.ragged_dot(x, w_in, group_sizes), 2, axis=-1)
        y_sorted = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_down.astype(dtype), group_sizes)
    with jax.named_scope("moe_dispatch"):
        return _combine(c, y_sorted, w, perm, inv, slots)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _experts_tiered(tiers, dtype, fits, *data):
    """``_experts_at`` at ``tiers[0]`` (rows, c) where ``fits``, else at ``tiers[1]``.  The conditional
    stands in the forward pass and again in the backward rule, which computes the taken length's
    forward anew: differentiating through one ``cond`` would give both lengths one set of residuals,
    the long one's, and have the short branch write them as zeros.  What goes into either conditional
    (the layer's input and parameters) and comes out (the output, their gradients) has one size."""
    return jax.lax.cond(fits, *(functools.partial(_experts_at, *tier, dtype) for tier in tiers), *data)


def _experts_tiered_fwd(tiers, dtype, fits, *data):
    return _experts_tiered(tiers, dtype, fits, *data), (fits, *data)


def _experts_tiered_bwd(tiers, dtype, res, g):
    fits, m, w, w_gate, w_up, w_down, *indices = res

    def grads_at(tier, g, *diff):
        return jax.vjp(lambda *d: _experts_at(*tier, dtype, *d, *indices), *diff)[1](g)

    grads = jax.lax.cond(fits, *(functools.partial(grads_at, tier) for tier in tiers), g, m, w, w_gate, w_up, w_down)
    return (None, *grads, *(None for _ in indices))


_experts_tiered.defvjp(_experts_tiered_fwd, _experts_tiered_bwd)


@dataclasses.dataclass(frozen=True)
class RoutedSpec:
    """What the routed layer needs to know: its sizes, the experts it holds, and the routing rule.

    ``scoring="softmax"``: softmax over all experts, the top ``top_k`` probabilities, renormalised
    where ``norm_topk_prob`` (SDAR-MoE).  ``scoring="sigmoid"``: a sigmoid score per expert; the
    top ``top_k`` of ``score + bias`` are selected (``bias``: a parameter of the layer that enters
    the selection only, takes no gradient and so no optimizer step), weighed by the unbiased score,
    renormalised where ``norm_topk_prob`` and scaled by ``scale`` (the DeepSeek-V3 family's
    ``noaux_tc`` without group limits; ``models/mla_moe.py``)."""

    hidden_size: int
    moe_intermediate_size: int
    num_experts: int
    top_k: int
    experts_held: int
    expert_offset: int = 0
    norm_topk_prob: bool = True
    scoring: str = "softmax"
    scale: float = 1.0

    @classmethod
    def of(cls, cfg: Any) -> "RoutedSpec":
        """``cfg`` itself if it is a spec, else the spec a model's configuration offers
        (``routed_spec``: ``SdarConfig``, ``mla_moe.MlaMoeConfig``)."""
        return cfg if isinstance(cfg, cls) else cfg.routed_spec


class RoutedExperts(nn.Module):
    """Router over all experts, SwiGLU experts held here, dropless: the one routed layer of both
    language-model policies.  ``cfg``: a ``RoutedSpec``, or a model configuration that offers one."""

    cfg: Any
    dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = RoutedSpec.of(self.cfg)
        d, f, held = c.hidden_size, c.moe_intermediate_size, c.experts_held
        if c.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring is softmax or sigmoid, got {c.scoring!r}")
        self.router = self.param("router", _INIT, (d, c.num_experts), jnp.float32)
        if c.scoring == "sigmoid":
            self.bias = self.param("bias", _INIT, (c.num_experts,), jnp.float32)
        self.w_gate = self.param("w_gate", _INIT, (held, d, f), jnp.float32)
        self.w_up = self.param("w_up", _INIT, (held, d, f), jnp.float32)
        self.w_down = self.param("w_down", _INIT, (held, f, d), jnp.float32)

    def __call__(self, m: jax.Array) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """``m``: (N, hidden) float32, already normed.  Returns the held
        experts' part of the layer's output (N, hidden) float32 and the
        counters."""
        c = RoutedSpec.of(self.cfg)
        n, k, held_n = m.shape[0], c.top_k, c.experts_held
        with jax.named_scope("moe_router"):
            logits = jnp.dot(m, self.router, precision=jax.lax.Precision.HIGHEST)
            if c.scoring == "softmax":
                probs = dist = jax.nn.softmax(logits, axis=-1)
                top_p, top_i = jax.lax.top_k(probs, k)
            else:  # selected with the bias, weighed without it
                probs = jax.nn.sigmoid(logits)
                _, top_i = jax.lax.top_k(probs + jax.lax.stop_gradient(self.bias), k)
                top_p = jnp.take_along_axis(probs, top_i, axis=-1)
                dist = probs / probs.sum(-1, keepdims=True)
            weights = top_p / top_p.sum(-1, keepdims=True) if c.norm_topk_prob else top_p
            if c.scale != 1.0:
                weights = weights * c.scale
            entropy = -(dist * jnp.log(jnp.maximum(dist, 1e-30))).sum(-1).mean()
        with jax.named_scope("moe_dispatch"):
            local = top_i - c.expert_offset
            held = (local >= 0) & (local < held_n)
            key = jnp.where(held, local, held_n).reshape(-1)
            # the worst case, a row for every assignment a token could make to a held expert, and the short
            # length; the call takes the short one when its assignments fit, so nothing is ever cut
            rows = n * min(k, held_n)
            rows_fit = short_buffer_rows(n, k, held_n, c.num_experts)
            order = jnp.argsort(key, stable=True)
            inv = jnp.argsort(order).reshape(n, k)
            group_sizes = (key[:, None] == jnp.arange(held_n)).sum(0).astype(jnp.int32)
            assigned = held.sum()
            dropped = jnp.maximum(assigned - rows, 0)
            data = (m, jnp.where(held, weights, 0.0), self.w_gate, self.w_up, self.w_down, order, inv, held, group_sizes)
        short, overflow, slot_c, slots = jnp.zeros((), bool), jnp.zeros((), jnp.int32), None, ()
        if rows_fit < rows:  # both lengths, one conditional: the short one where the counted load fits it
            short = assigned <= rows_fit
            compact = compact_slots(n, k, held_n, c.num_experts, c.hidden_size * jnp.dtype(self.dtype).itemsize)
            if compact is not None:  # its token side reads held choices only, and the load must fit its list too
                slot_c, slot_r = compact
                with jax.named_scope("moe_dispatch"):
                    slots, overflow = _slots(held, slot_c, slot_r)
                short &= overflow <= slot_r
            y = _experts_tiered(((rows_fit, slot_c), (rows, None)), self.dtype, short, *data, *slots)
        else:  # the short length reaches the worst case: one length, and no conditional in the program
            y = _experts_at(rows, None, self.dtype, *data)
        aux = {"load": group_sizes, "dropped": dropped, "short": short, "overflow": overflow, "entropy": entropy,
               "top_i": top_i}
        return y, aux


class GroupedQueryAttention(nn.Module):
    """Grouped-query attention with per-head RMSNorm on q and k and rotary
    positions given per token; the mask is the caller's."""

    cfg: SdarConfig
    dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        d, hd = c.hidden_size, c.head_dim
        self.wq = self.param("wq", _INIT, (d, c.num_attention_heads * hd), jnp.float32)
        self.wk = self.param("wk", _INIT, (d, c.num_key_value_heads * hd), jnp.float32)
        self.wv = self.param("wv", _INIT, (d, c.num_key_value_heads * hd), jnp.float32)
        self.wo = self.param("wo", _INIT, (c.num_attention_heads * hd, d), jnp.float32)
        self.q_norm = self.param("q_norm", nn.initializers.ones, (hd,), jnp.float32)
        self.k_norm = self.param("k_norm", nn.initializers.ones, (hd,), jnp.float32)

    def qkv(self, a: jax.Array, pos: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
        c = self.cfg
        a = a.astype(self.dtype)
        lead = a.shape[:-1]
        q = (a @ self.wq.astype(self.dtype)).reshape(*lead, c.num_attention_heads, c.head_dim)
        k = (a @ self.wk.astype(self.dtype)).reshape(*lead, c.num_key_value_heads, c.head_dim)
        v = (a @ self.wv.astype(self.dtype)).reshape(*lead, c.num_key_value_heads, c.head_dim)
        q = rope(rms_norm(q, self.q_norm, c.rms_norm_eps), pos, c.rope_theta).astype(self.dtype)
        k = rope(rms_norm(k, self.k_norm, c.rms_norm_eps), pos, c.rope_theta).astype(self.dtype)
        return q, k, v

    def out(self, o: jax.Array) -> jax.Array:
        return (o.reshape(*o.shape[:-2], -1) @ self.wo.astype(self.dtype)).astype(jnp.float32)

    def __call__(self, a: jax.Array, pos: jax.Array, mask: SegmentMask):
        """``a``: (B, N, hidden).  Blocked online-softmax attention under
        ``mask``; also returns this sequence's keys and values."""
        c = self.cfg
        q, k, v = self.qkv(a, pos)
        with jax.named_scope("blockdiff_attn"):
            o = block_sparse_flash_attention(q, k, v, mask, c.attention_block, interpret=c.attention_interpret)
        return self.out(o), (k, v)

    def cached(self, a: jax.Array, pos: jax.Array, k_cache: jax.Array, v_cache: jax.Array, length: jax.Array):
        """One block (B, block, hidden) against the first ``length`` cached
        clean positions and itself."""
        c = self.cfg
        q, k, v = self.qkv(a, pos)
        bsz, blk = q.shape[:2]
        rep = c.num_attention_heads // c.num_key_value_heads
        qg = q.reshape(bsz, blk, c.num_key_value_heads, rep, c.head_dim)
        scale = 1.0 / jnp.sqrt(jnp.float32(c.head_dim))
        past = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_cache, preferred_element_type=jnp.float32) * scale
        past = jnp.where(jnp.arange(k_cache.shape[1]) < length, past, -jnp.inf)
        own = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.concatenate([past, own], axis=-1), axis=-1)
        p_past, p_own = p[..., : k_cache.shape[1]], p[..., k_cache.shape[1]:]
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p_past.astype(self.dtype), v_cache, preferred_element_type=jnp.float32)
        o = o + jnp.einsum("bgrqk,bkgd->bqgrd", p_own.astype(self.dtype), v, preferred_element_type=jnp.float32)
        o = o.astype(self.dtype).reshape(bsz, blk, c.num_attention_heads, c.head_dim)
        return self.out(o), (k, v)


class SdarLayer(nn.Module):
    cfg: SdarConfig
    dtype: Dtype = jnp.float32

    def setup(self) -> None:
        d = self.cfg.hidden_size
        self.norm1 = self.param("norm1", nn.initializers.ones, (d,), jnp.float32)
        self.norm2 = self.param("norm2", nn.initializers.ones, (d,), jnp.float32)
        self.attn = GroupedQueryAttention(self.cfg, self.dtype)
        self.moe = RoutedExperts(self.cfg, self.dtype)

    def _moe(self, h1: jax.Array):
        m = rms_norm(h1, self.norm2, self.cfg.rms_norm_eps)
        y, aux = self.moe(m.reshape(-1, m.shape[-1]))
        return h1 + y.reshape(h1.shape), aux

    def __call__(self, h: jax.Array, pos: jax.Array, layout: EpisodeLayout):
        """``h``: (B, N, hidden) float32, one packed episode a row."""
        with jax.named_scope("sdar_attn"):
            o, kv = self.attn(rms_norm(h, self.norm1, self.cfg.rms_norm_eps), pos, layout.mask)
        h2, aux = self._moe(h + o)
        return h2, aux, kv

    def cached(self, h: jax.Array, pos: jax.Array, k_cache, v_cache, length):
        with jax.named_scope("sdar_attn"):
            o, kv = self.attn.cached(rms_norm(h, self.norm1, self.cfg.rms_norm_eps), pos, k_cache, v_cache, length)
        h2, aux = self._moe(h + o)
        return h2, aux, kv


class SdarMoE(nn.Module):
    """Embedding, the layers (unrolled: a scan would hide them from the
    profiler's scopes), final norm, an untied head over the vocabulary slice
    and a scalar value head (this system's addition)."""

    cfg: SdarConfig
    dtype: Dtype = jnp.float32
    remat: bool = True

    def setup(self) -> None:
        c = self.cfg
        self.embed = self.param("embed", _INIT, (c.vocab_size, c.hidden_size), jnp.float32)
        self.head = self.param("head", _INIT, (c.hidden_size, c.vocab_size), jnp.float32)
        self.value = self.param("value", _INIT, (c.hidden_size, 1), jnp.float32)
        self.final_norm = self.param("final_norm", nn.initializers.ones, (c.hidden_size,), jnp.float32)
        layer = remat_block(SdarLayer, static_argnums=(3,)) if self.remat else SdarLayer
        self.layers = [layer(c, self.dtype, name=f"layer_{i}") for i in range(c.num_hidden_layers)]

    def _finish(self, h, auxes):
        aux = {k: jnp.stack([a[k] for a in auxes]) for k in auxes[0]}
        with jax.named_scope("sdar_head"):
            return rms_norm(h, self.final_norm, self.cfg.rms_norm_eps), aux

    def hidden(self, tokens: jax.Array, layout: EpisodeLayout, with_kv: bool = False):
        """Final-norm hidden states (B, N, hidden) of packed episodes
        ``tokens`` (B, N), the counters stacked over layers and, on request,
        every layer's keys and values (a prefill)."""
        with jax.named_scope("sdar_embed"):
            h = self.embed[tokens]
        pos = jnp.asarray(layout.positions)
        auxes, kvs = [], []
        for layer in self.layers:
            h, aux, kv = layer(h, pos, layout)
            auxes.append(aux)
            kvs.append(kv)
        out = self._finish(h, auxes)
        return (*out, kvs) if with_kv else out

    def block(self, tokens: jax.Array, pos: jax.Array, cache, length):
        """One block of tokens (B, block) against the cache of clean keys and
        values (per layer ``(k, v)``, each (B, S, kv heads, head)): a denoising
        pass, or the pass that writes a finished block.  Returns the hidden
        states, the counters and the block's keys and values per layer."""
        h = self.embed[tokens]
        auxes, kvs = [], []
        for layer, (k_cache, v_cache) in zip(self.layers, cache):
            h, aux, kv = layer.cached(h, pos, k_cache, v_cache, length)
            auxes.append(aux)
            kvs.append(kv)
        return (*self._finish(h, auxes), kvs)

    def commit(self, tokens: jax.Array, pos: jax.Array, cache, length):
        """The pass that writes a finished block, for what it is run for: the
        block's keys and values per layer, and the counters of the routed
        layers they depend on.  That is every layer but the last, whose routed
        part feeds the hidden states alone: a caller that takes no hidden
        states does not run it, and what never ran is not counted."""
        _, aux, kvs = self.block(tokens, pos, cache, length)
        return {k: v[:-1] for k, v in aux.items()}, kvs

    def score(self, at: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Hidden states at action positions (..., hidden) -> the policy's
        log-probabilities over the vocabulary slice (float32; ``[MASK]`` is
        never drawn) and the value."""
        with jax.named_scope("sdar_head"):
            logits = jnp.dot(at.astype(self.dtype), self.head.astype(self.dtype), preferred_element_type=jnp.float32)
            logits = jnp.where(jnp.arange(logits.shape[-1]) == self.cfg.mask_id, _NEG, logits)
            values = jnp.dot(at, self.value, precision=jax.lax.Precision.HIGHEST)[..., 0]
            return jax.nn.log_softmax(logits, axis=-1), values

    def __call__(self, tokens: jax.Array, layout: EpisodeLayout):
        hidden, aux = self.hidden(tokens, layout)
        return (*self.score(hidden), aux)


def reference_params(params: Mapping[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree in the layout of the plain reference
    (``sdar_moe_reference.init_params``)."""
    p = params["params"] if "params" in params else params
    layers = []
    for i in range(sum(1 for k in p if k.startswith("layer_"))):
        lp = p[f"layer_{i}"]
        layers.append({"norm1": lp["norm1"], "norm2": lp["norm2"], **lp["attn"], **lp["moe"]})
    return {"embed": p["embed"], "head": p["head"], "value": p["value"], "final_norm": p["final_norm"], "layers": layers}
