"""Efficient-BPTT custom VJP for the Dreamer dynamic scans (DV3 + DV2).

The discrete-latent dynamic recurrence (this repo's
``RSSM.dynamic_posterior``; reference sheeprl dreamer_v3.py:113-146 +
RSSM.dynamic agent.py:396, dreamer_v2 agent.py RSSM.dynamic:336) interleaves
posterior sampling with the GRU:

    feat   = act(LN_p?([z_{t-1}, a_t] @ Wp + bp))     # input projection
    h_t    = LayerNormGRU(h_{t-1}, feat)              # Hafner GRU (+bias in V2)
    logits = head(act(LN_r?(h_t @ k_h + emb_proj_t))) # representation model
    z_t    = ST-sample(unimix?(logits) + gumbel)      # posterior

Autodiff-through-``lax.scan`` puts every weight-gradient accumulator
(Wp, Wg, k_h, head — ~4.5 MB f32 at DV3-S) into the backward while-loop's
carry: every reverse iteration reads and writes them all (~9 MB of HBM
round-trip per step) on top of the serial matmuls.  A Pallas
whole-sequence forward kernel does NOT help here — measured on the v5e,
one-kernel grid=(T,) recurrences are launch-overhead-bound and lose to
XLA's while loop (benchmarks/results/seq_gru_tpu_r4.json: 4.10 ms vs
3.85 ms fwd at T=64/B=16/H=512) — but the backward is fixable in pure JAX:

* the forward stays an XLA ``lax.scan`` (already latency-optimal), saving
  only the carried states (hs, zs) — no per-step residual stacking;
* the backward recomputes every activation, LayerNorm statistic and gate
  from the saved states in batched (T*B) matmuls, then runs a reverse
  ``lax.scan`` whose carry is ONLY (dh, dz): four small matmuls per step
  (head/rep/GRU/projection transposes) and elementwise chain rules;
* every weight gradient is a single batched contraction over stacked
  reverse-scan outputs, OUTSIDE the sequential loop.

Chip A/B at DV3-S: 16.2-16.3 → 15.7 ms per train step.

Generality knobs (static): activation (``silu`` for V3 / ``elu`` for V2),
optional LayerNorms on the projection and representation trunks (with
their epsilons: V3 configures 1e-3, V2 uses flax's 1e-6 default), Dense
biases on the projection and GRU (always-present zero arrays when the
module variant has none — the adds are free next to the matmuls), and
``unimix`` (V3's 1% log-mix; 0 means the logits pass through raw, V2).
The is_first reset state is an input pair (init_rec/init_post): V3 passes
its learned initial state, V2 passes zeros.

Numerics: matmuls run in the caller's compute dtype with f32 LayerNorms,
mirroring ``linear_ln_act_apply``/``gru_cell_apply``/``DenseActLn``; all
backward cotangent arithmetic is f32 (autodiff would carry bf16
cotangents through bf16 segments — the f32 choice is strictly more
precise; grads match autodiff exactly in f32 and to bf16 tolerance under
bf16-mixed, pinned by ``tests/test_parallel/test_dyn_bptt.py``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "DynParams",
    "V1DynParams",
    "dyn_bptt_setting",
    "dyn_rssm_sequence",
    "dyn_rssm_sequence_v1",
    "extract_dyn_params",
    "extract_dyn_params_v1",
    "extract_dyn_params_v2",
    "rssm_dyn_bptt_eligible",
]


def dyn_bptt_setting(cfg) -> bool:
    """The ``algo.world_model.dyn_bptt`` config knob (shared by every
    Dreamer-family train fn; callers AND their own structural eligibility
    check, e.g. :func:`rssm_dyn_bptt_eligible` or a supported-activation test)."""
    return bool(cfg.algo.world_model.get("dyn_bptt", False))


class DynParams(NamedTuple):
    """Raw weight leaves of the fused dynamic step (flax param layout).

    w_proj (S+A, P) / b_proj (P,)   recurrent model input projection
    lnp_*  (P,)        its LayerNorm (when proj_ln)
    w_gru  (H+P, 3H) / b_gru (3H,)  LayerNormGRUCell dense
    lng_*  (3H,)       its LayerNorm (eps 1e-6, always on)
    k_h    (H, R)      representation trunk, h-side rows of the first Dense
                       (the embed-side rows and the Dense bias live in the
                       precomputed ``emb_proj``)
    lnr_*  (R,)        representation trunk LayerNorm (when rep_ln)
    head_k (R, S) / head_b (S,)     logits head (f32 matmul)

    Bias/LN arrays are always present; pass zeros/ones when the module
    variant has none (their gradients are then simply discarded).
    """

    w_proj: jax.Array
    b_proj: jax.Array
    lnp_scale: jax.Array
    lnp_bias: jax.Array
    w_gru: jax.Array
    b_gru: jax.Array
    lng_scale: jax.Array
    lng_bias: jax.Array
    k_h: jax.Array
    lnr_scale: jax.Array
    lnr_bias: jax.Array
    head_k: jax.Array
    head_b: jax.Array


def _ln_fwd(x32, scale, bias, eps):
    """flax fast-variance LayerNorm in f32; returns (out, xhat, inv)."""
    mu = x32.mean(-1, keepdims=True)
    var = jnp.maximum((x32 * x32).mean(-1, keepdims=True) - mu * mu, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (x32 - mu) * inv
    return xhat * scale + bias, xhat, inv


def _ln_bwd(dy, scale, xhat, inv):
    """Cotangent of the LN input given d(out); scale/bias grads batch outside."""
    dxhat = dy * scale
    return inv * (
        dxhat
        - dxhat.mean(-1, keepdims=True)
        - xhat * (dxhat * xhat).mean(-1, keepdims=True)
    )


def _act_fwd(v, act: str):
    if act == "silu":
        return jax.nn.silu(v)
    if act == "elu":
        return jax.nn.elu(v)
    raise ValueError(f"unsupported activation for dyn_bptt: {act}")


def _act_grad(v, act: str):
    """d act(v) / dv evaluated at the saved pre-activation value."""
    if act == "silu":
        s = jax.nn.sigmoid(v)
        return s * (1.0 + v * (1.0 - s))
    if act == "elu":
        return jnp.where(v > 0, 1.0, jnp.exp(jnp.minimum(v, 0.0)))
    raise ValueError(f"unsupported activation for dyn_bptt: {act}")


def _group_softmax(x, groups, classes):
    return jax.nn.softmax(x.reshape(*x.shape[:-1], groups, classes), -1)


@functools.lru_cache(maxsize=16)
def _get_op(
    eps_p: float,
    eps_r: float,
    unimix: float,
    discrete: int,
    dt_name: str,
    unroll: int,
    act: str,
    proj_ln: bool,
    rep_ln: bool,
):
    dt = jnp.dtype(dt_name)
    f32 = jnp.float32

    def _step_fwd(params: DynParams, init_rec, init_post, carry, inp):
        """One dynamic step, numerics-identical to RSSM.dynamic_posterior
        (V3) / RSSM.dynamic_posterior_from_proj (V2)."""
        z, h = carry
        a, emb, f, n = inp
        keep = 1.0 - f
        a_eff = keep * a
        hg = keep * h + f * init_rec
        zg = keep * z + f * init_post

        fpre = (
            jnp.concatenate([zg, a_eff], -1).astype(dt) @ params.w_proj.astype(dt)
            + params.b_proj.astype(dt)
        )
        if proj_ln:
            lnp, _, _ = _ln_fwd(fpre.astype(f32), params.lnp_scale, params.lnp_bias, eps_p)
            feat = _act_fwd(lnp.astype(dt), act)
        else:
            feat = _act_fwd(fpre, act)

        gpre = (
            jnp.concatenate([hg.astype(dt), feat], -1) @ params.w_gru.astype(dt)
            + params.b_gru.astype(dt)
        )
        parts, _, _ = _ln_fwd(gpre.astype(f32), params.lng_scale, params.lng_bias, 1e-6)
        hidden = h.shape[-1]
        reset = jax.nn.sigmoid(parts[..., :hidden])
        cand = jnp.tanh(reset * parts[..., hidden : 2 * hidden])
        update = jax.nn.sigmoid(parts[..., 2 * hidden :] - 1.0)
        h_new = update * cand + (1.0 - update) * hg

        xpre = h_new.astype(dt) @ params.k_h.astype(dt) + emb
        if rep_ln:
            lnr, _, _ = _ln_fwd(xpre.astype(f32), params.lnr_scale, params.lnr_bias, eps_r)
            x = _act_fwd(lnr.astype(dt), act)
        else:
            x = _act_fwd(xpre, act)
        logits = x.astype(f32) @ params.head_k + params.head_b

        groups = logits.shape[-1] // discrete
        if unimix > 0.0:
            pr = _group_softmax(logits, groups, discrete)
            pm = (1.0 - unimix) * pr + unimix / discrete
            mixed = jnp.log(pm)
        else:
            mixed = logits.reshape(*logits.shape[:-1], groups, discrete)
        hard = jax.nn.one_hot(
            jnp.argmax(mixed + n.reshape(mixed.shape), -1), discrete, dtype=f32
        )
        z_new = hard.reshape(z.shape)
        return (z_new, h_new), (h_new, z_new, mixed.reshape(z.shape))

    def _fwd_scan(z0, h0, actions, emb_proj, is_first, noise, init_rec, init_post, params):
        step = functools.partial(_step_fwd, params, init_rec, init_post)
        _, (hs, zs, mixed) = jax.lax.scan(
            step, (z0, h0), (actions, emb_proj, is_first, noise), unroll=unroll
        )
        return hs, zs, mixed

    @jax.custom_vjp
    def op(z0, h0, actions, emb_proj, is_first, noise, init_rec, init_post, params):
        return _fwd_scan(z0, h0, actions, emb_proj, is_first, noise, init_rec, init_post, params)

    def op_fwd(z0, h0, actions, emb_proj, is_first, noise, init_rec, init_post, params):
        hs, zs, mixed = _fwd_scan(
            z0, h0, actions, emb_proj, is_first, noise, init_rec, init_post, params
        )
        return (hs, zs, mixed), (
            z0,
            h0,
            actions,
            emb_proj,
            is_first,
            noise,
            init_rec,
            init_post,
            params,
            hs,
            zs,
        )

    def op_bwd(res, cots):
        z0, h0, actions, emb_proj, is_first, noise, init_rec, init_post, params, hs, zs = res
        d_hs, d_zs, d_mixed = cots
        T, b = hs.shape[:2]
        hidden = h0.shape[-1]
        stoch = z0.shape[-1]
        groups = stoch // discrete

        # ---- batched recompute of every step's activations from the saved
        # states (one (T*B) matmul per layer, nothing sequential)
        f = is_first.astype(f32)
        keep = 1.0 - f
        z_prev = jnp.concatenate([z0[None], zs[:-1]], 0)
        h_prev = jnp.concatenate([h0[None], hs[:-1]], 0)
        a_eff = keep * actions
        hg = keep * h_prev + f * init_rec
        zg = keep * z_prev + f * init_post

        inp_p32 = jnp.concatenate([zg, a_eff], -1)
        fpre_dt = (
            inp_p32.astype(dt) @ params.w_proj.astype(dt) + params.b_proj.astype(dt)
        )
        fpre = fpre_dt.astype(f32)
        if proj_ln:
            lnp, xhat_p, inv_p = _ln_fwd(fpre, params.lnp_scale, params.lnp_bias, eps_p)
            actin_p = lnp.astype(dt)  # activation input (saved pre-act value)
        else:
            xhat_p = inv_p = jnp.zeros_like(fpre[..., :1])
            actin_p = fpre_dt
        feat = _act_fwd(actin_p, act)

        g_in32 = jnp.concatenate([hg, feat.astype(f32)], -1)
        gpre = (
            g_in32.astype(dt) @ params.w_gru.astype(dt) + params.b_gru.astype(dt)
        ).astype(f32)
        parts, xhat_g, inv_g = _ln_fwd(gpre, params.lng_scale, params.lng_bias, 1e-6)
        reset = jax.nn.sigmoid(parts[..., :hidden])
        p2 = parts[..., hidden : 2 * hidden]
        cand = jnp.tanh(reset * p2)
        update = jax.nn.sigmoid(parts[..., 2 * hidden :] - 1.0)

        xpre_dt = hs.astype(dt) @ params.k_h.astype(dt) + emb_proj
        xpre = xpre_dt.astype(f32)
        if rep_ln:
            lnr, xhat_r, inv_r = _ln_fwd(xpre, params.lnr_scale, params.lnr_bias, eps_r)
            actin_r = lnr.astype(dt)
        else:
            xhat_r = inv_r = jnp.zeros_like(xpre[..., :1])
            actin_r = xpre_dt
        x32 = _act_fwd(actin_r, act).astype(f32)
        logits = x32 @ params.head_k + params.head_b
        l3 = logits.reshape(T, b, groups, discrete)
        if unimix > 0.0:
            pr = jax.nn.softmax(l3, -1)
            pm = (1.0 - unimix) * pr + unimix / discrete
            p_st = jax.nn.softmax(jnp.log(pm), -1)  # fp-faithful to the fwd
        else:
            pr = pm = jnp.zeros_like(l3[..., :1])  # unused
            p_st = jax.nn.softmax(l3, -1)

        w_gru_h = params.w_gru[:hidden].astype(f32)
        w_gru_x = params.w_gru[hidden:].astype(f32)
        w_proj_z = params.w_proj[:stoch].astype(f32)
        k_h32 = params.k_h.astype(f32)
        head_k32 = params.head_k.astype(f32)

        def back_step(carry, inp_t):
            dh_c, dz_c = carry
            (
                d_hs_t,
                d_zs_t,
                d_mixed_t,
                f_t,
                p_st_t,
                pm_t,
                pr_t,
                actin_r_t,
                xhat_r_t,
                inv_r_t,
                hg_t,
                cand_t,
                update_t,
                reset_t,
                p2_t,
                xhat_g_t,
                inv_g_t,
                actin_p_t,
                xhat_p_t,
                inv_p_t,
            ) = inp_t
            keep_t = 1.0 - f_t

            # straight-through (+ unimix) backward into the logits
            dz3 = (d_zs_t + dz_c).reshape(-1, groups, discrete)
            dmx = p_st_t * (dz3 - (dz3 * p_st_t).sum(-1, keepdims=True))
            dmx = dmx + d_mixed_t.reshape(dmx.shape)
            if unimix > 0.0:
                dpm = dmx / pm_t
                dpr = (1.0 - unimix) * dpm
                dlogits = (pr_t * (dpr - (dpr * pr_t).sum(-1, keepdims=True))).reshape(
                    -1, groups * discrete
                )
            else:
                dlogits = dmx.reshape(-1, groups * discrete)

            # representation head + trunk backward
            dx32 = dlogits @ head_k32.T
            dl = dx32 * _act_grad(actin_r_t.astype(f32), act)
            if rep_ln:
                dxpre = _ln_bwd(dl, params.lnr_scale, xhat_r_t, inv_r_t)
            else:
                dxpre = dl
            dh_rep = dxpre @ k_h32.T

            # GRU backward (gated carry hg)
            dh_tot = d_hs_t + dh_c + dh_rep
            du = (cand_t - hg_t) * dh_tot
            dcand = update_t * dh_tot
            dhg = (1.0 - update_t) * dh_tot
            dp3 = du * update_t * (1.0 - update_t)
            dtanh = dcand * (1.0 - cand_t * cand_t)
            dp2 = dtanh * reset_t
            dreset = dtanh * p2_t
            dp1 = dreset * reset_t * (1.0 - reset_t)
            dparts = jnp.concatenate([dp1, dp2, dp3], -1)
            dgpre = _ln_bwd(dparts, params.lng_scale, xhat_g_t, inv_g_t)
            dhg = dhg + dgpre @ w_gru_h.T
            dfeat = dgpre @ w_gru_x.T

            # input projection backward
            dl_p = dfeat * _act_grad(actin_p_t.astype(f32), act)
            if proj_ln:
                dfpre = _ln_bwd(dl_p, params.lnp_scale, xhat_p_t, inv_p_t)
            else:
                dfpre = dl_p
            dzg = dfpre @ w_proj_z.T

            dh_prev = keep_t * dhg
            dz_prev = keep_t * dzg
            return (dh_prev, dz_prev), (dlogits, dxpre, dparts, dgpre, dfpre, dhg, dzg)

        seq = (
            d_hs.astype(f32),
            d_zs.astype(f32).reshape(T, b, stoch),
            d_mixed.astype(f32),
            f,
            p_st,
            pm,
            pr,
            actin_r,
            xhat_r,
            inv_r,
            hg,
            cand,
            update,
            reset,
            p2,
            xhat_g,
            inv_g,
            actin_p,
            xhat_p,
            inv_p,
        )
        (dh0, dz0), (dlogits, dxpre, dparts, dgpre, dfpre, dhgs, dzgs) = jax.lax.scan(
            back_step,
            (jnp.zeros_like(h0, f32), jnp.zeros_like(z0, f32)),
            seq,
            reverse=True,
            unroll=unroll,
        )

        # ---- weight gradients: one batched contraction each
        n_r = params.k_h.shape[-1]
        x32f = x32.reshape(T * b, n_r)
        dlogf = dlogits.reshape(T * b, stoch)
        dxpref = dxpre.reshape(T * b, n_r)
        # LN scale/bias grads need the pre-LN-input cotangents dlnr/dlnp
        dlnr_full = (dlogits @ head_k32.T) * _act_grad(actin_r.astype(f32), act)
        dlnp_full = (dgpre @ w_gru_x.T) * _act_grad(actin_p.astype(f32), act)

        grads = DynParams(
            w_proj=(inp_p32.reshape(T * b, -1).T @ dfpre.reshape(T * b, -1)).astype(
                params.w_proj.dtype
            ),
            b_proj=dfpre.sum((0, 1)).astype(params.b_proj.dtype),
            lnp_scale=(dlnp_full * xhat_p).sum((0, 1)) if proj_ln else jnp.zeros_like(params.lnp_scale),
            lnp_bias=dlnp_full.sum((0, 1)) if proj_ln else jnp.zeros_like(params.lnp_bias),
            w_gru=(g_in32.reshape(T * b, -1).T @ dgpre.reshape(T * b, -1)).astype(
                params.w_gru.dtype
            ),
            b_gru=dgpre.sum((0, 1)).astype(params.b_gru.dtype),
            lng_scale=(dparts * xhat_g).sum((0, 1)),
            lng_bias=dparts.sum((0, 1)),
            k_h=(hs.reshape(T * b, hidden).T @ dxpref).astype(params.k_h.dtype),
            lnr_scale=(dlnr_full * xhat_r).sum((0, 1)) if rep_ln else jnp.zeros_like(params.lnr_scale),
            lnr_bias=dlnr_full.sum((0, 1)) if rep_ln else jnp.zeros_like(params.lnr_bias),
            head_k=(x32f.T @ dlogf).astype(params.head_k.dtype),
            head_b=dlogf.sum(0).astype(params.head_b.dtype),
        )
        d_actions = (keep * (dfpre @ params.w_proj[stoch:].astype(f32).T)).astype(actions.dtype)
        d_emb = dxpre.astype(emb_proj.dtype)
        d_init_rec = (f * dhgs).sum(0).astype(init_rec.dtype)
        d_init_post = (f * dzgs).sum(0).astype(init_post.dtype)
        return (
            dz0.astype(z0.dtype),
            dh0.astype(h0.dtype),
            d_actions,
            d_emb,
            jnp.zeros_like(is_first),
            jnp.zeros_like(noise),
            d_init_rec,
            d_init_post,
            grads,
        )

    op.defvjp(op_fwd, op_bwd)
    return op


class V1DynParams(NamedTuple):
    """Raw weight leaves of the DV1 (Gaussian-latent) dynamic step.

    w_proj (S+A, P) / b_proj (P,)  recurrent model input projection
                                   (``RecurrentModel.Dense_0`` — bias present)
    w_i    (P, 3H) / b_i (3H,)     flax GRUCell input kernels [ir|iz|in]
    w_h    (H, 3H) / b_hn (H,)     flax GRUCell hidden kernels [hr|hz|hn]
                                   (only ``hn`` has a bias)
    k_h    (H, R)                  representation trunk, h-side rows of the
                                   first Dense (embed-side rows + bias live
                                   in the precomputed ``emb_proj``)
    head_k (R, 2S) / head_b (2S,)  (mean, std) head (f32 matmul)
    """

    w_proj: jax.Array
    b_proj: jax.Array
    w_i: jax.Array
    b_i: jax.Array
    w_h: jax.Array
    b_hn: jax.Array
    k_h: jax.Array
    head_k: jax.Array
    head_b: jax.Array


@functools.lru_cache(maxsize=16)
def _get_op_v1(min_std: float, dt_name: str, unroll: int, act: str):
    """Efficient-BPTT op for the DV1 continuous-latent dynamic recurrence.

    The DV1 chain (``dreamer_v1.agent.RSSM.dynamic_posterior_from_proj``;
    reference sheeprl dreamer_v1/agent.py RSSM.dynamic:97 +
    dreamer_v1/utils.py:80) is simpler than V3's: reparameterized Gaussian
    sampling instead of straight-through/unimix, a plain flax GRUCell
    instead of the Hafner LayerNorm GRU, no LayerNorms anywhere, and no
    is_first resets.  The efficient-BPTT design is identical: forward is
    the plain XLA ``lax.scan`` saving only (hs, zs); backward recomputes
    all activations in batched (T*B) matmuls and runs a reverse scan whose
    carry is only (dh, dz), with every weight gradient one batched
    contraction outside the loop.
    """
    dt = jnp.dtype(dt_name)
    f32 = jnp.float32

    def _gru_fwd(params: V1DynParams, h, feat32):
        """flax nn.GRUCell numerics: r/z gates, reset applied to the
        hidden-side candidate product, new_h = (1-z)*n + z*h."""
        hidden = h.shape[-1]
        gi = feat32 @ params.w_i.astype(f32) + params.b_i.astype(f32)
        gh = h @ params.w_h.astype(f32)
        r = jax.nn.sigmoid(gi[..., :hidden] + gh[..., :hidden])
        u = jax.nn.sigmoid(gi[..., hidden : 2 * hidden] + gh[..., hidden : 2 * hidden])
        ghn = gh[..., 2 * hidden :] + params.b_hn.astype(f32)
        n = jnp.tanh(gi[..., 2 * hidden :] + r * ghn)
        return (1.0 - u) * n + u * h, (r, u, n, ghn)

    def _step_fwd(params: V1DynParams, carry, inp):
        z, h = carry
        a, emb, n_t = inp
        fpre = (
            jnp.concatenate([z, a], -1).astype(dt) @ params.w_proj.astype(dt)
            + params.b_proj.astype(dt)
        )
        feat32 = _act_fwd(fpre, act).astype(f32)
        h_new, _ = _gru_fwd(params, h, feat32)
        xpre = h_new.astype(dt) @ params.k_h.astype(dt) + emb
        x = _act_fwd(xpre, act)
        ms = x.astype(f32) @ params.head_k + params.head_b
        mean, stdraw = jnp.split(ms, 2, -1)
        std = jax.nn.softplus(stdraw) + min_std
        z_new = mean + std * n_t
        return (z_new, h_new), (h_new, z_new, mean, std)

    def _fwd_scan(z0, h0, actions, emb_proj, noise, params):
        step = functools.partial(_step_fwd, params)
        _, (hs, zs, means, stds) = jax.lax.scan(
            step, (z0, h0), (actions, emb_proj, noise), unroll=unroll
        )
        return hs, zs, means, stds

    @jax.custom_vjp
    def op(z0, h0, actions, emb_proj, noise, params):
        return _fwd_scan(z0, h0, actions, emb_proj, noise, params)

    def op_fwd(z0, h0, actions, emb_proj, noise, params):
        hs, zs, means, stds = _fwd_scan(z0, h0, actions, emb_proj, noise, params)
        return (hs, zs, means, stds), (z0, h0, actions, emb_proj, noise, params, hs, zs)

    def op_bwd(res, cots):
        z0, h0, actions, emb_proj, noise, params, hs, zs = res
        d_hs, d_zs, d_means, d_stds = cots
        T, b = hs.shape[:2]
        hidden = h0.shape[-1]
        stoch = z0.shape[-1]

        # ---- batched recompute of every step's activations from the saved
        # states (one (T*B) matmul per layer, nothing sequential)
        z_prev = jnp.concatenate([z0[None], zs[:-1]], 0)
        h_prev = jnp.concatenate([h0[None], hs[:-1]], 0)
        inp_p32 = jnp.concatenate([z_prev, actions.astype(f32)], -1)
        fpre_dt = (
            inp_p32.astype(dt) @ params.w_proj.astype(dt) + params.b_proj.astype(dt)
        )
        feat32 = _act_fwd(fpre_dt, act).astype(f32)
        gi = feat32 @ params.w_i.astype(f32) + params.b_i.astype(f32)
        gh = h_prev @ params.w_h.astype(f32)
        r = jax.nn.sigmoid(gi[..., :hidden] + gh[..., :hidden])
        u = jax.nn.sigmoid(gi[..., hidden : 2 * hidden] + gh[..., hidden : 2 * hidden])
        ghn = gh[..., 2 * hidden :] + params.b_hn.astype(f32)
        n_cand = jnp.tanh(gi[..., 2 * hidden :] + r * ghn)
        xpre_dt = hs.astype(dt) @ params.k_h.astype(dt) + emb_proj
        x32 = _act_fwd(xpre_dt, act).astype(f32)
        ms = x32 @ params.head_k + params.head_b
        stdraw = ms[..., stoch:]
        sig_std = jax.nn.sigmoid(stdraw)  # d softplus

        w_i32 = params.w_i.astype(f32)
        w_h32 = params.w_h.astype(f32)
        w_proj_z32 = params.w_proj[:stoch].astype(f32)
        k_h32 = params.k_h.astype(f32)
        head_k32 = params.head_k.astype(f32)

        def back_step(carry, inp_t):
            dh_c, dz_c = carry
            (
                d_hs_t,
                d_zs_t,
                d_mean_t,
                d_std_t,
                noise_t,
                sig_t,
                actin_r_t,
                h_prev_t,
                r_t,
                u_t,
                n_t,
                ghn_t,
                actin_p_t,
            ) = inp_t

            # reparameterized-sample backward into the (mean, std) head
            dz_tot = d_zs_t + dz_c
            dmean = dz_tot + d_mean_t
            dstd = dz_tot * noise_t + d_std_t
            dms = jnp.concatenate([dmean, dstd * sig_t], -1)

            # representation trunk backward
            dx32 = dms @ head_k32.T
            dxpre = dx32 * _act_grad(actin_r_t.astype(f32), act)
            dh_rep = dxpre @ k_h32.T

            # flax-GRUCell backward
            dh_tot = d_hs_t + dh_c + dh_rep
            du = (h_prev_t - n_t) * dh_tot
            dn = (1.0 - u_t) * dh_tot
            dh_direct = u_t * dh_tot
            dtanh = dn * (1.0 - n_t * n_t)
            dr = dtanh * ghn_t
            dghn = dtanh * r_t
            du_pre = du * u_t * (1.0 - u_t)
            dr_pre = dr * r_t * (1.0 - r_t)
            dgi = jnp.concatenate([dr_pre, du_pre, dtanh], -1)
            dgh = jnp.concatenate([dr_pre, du_pre, dghn], -1)
            dh_prev = dh_direct + dgh @ w_h32.T
            dfeat = dgi @ w_i32.T

            # input projection backward
            dfpre = dfeat * _act_grad(actin_p_t.astype(f32), act)
            dz_prev = dfpre @ w_proj_z32.T
            return (dh_prev, dz_prev), (dms, dxpre, dgi, dgh, dfpre)

        seq = (
            d_hs.astype(f32),
            d_zs.astype(f32),
            d_means.astype(f32),
            d_stds.astype(f32),
            noise,
            sig_std,
            xpre_dt,
            h_prev,
            r,
            u,
            n_cand,
            ghn,
            fpre_dt,
        )
        (dh0, dz0), (dms_s, dxpre_s, dgi_s, dgh_s, dfpre_s) = jax.lax.scan(
            back_step,
            (jnp.zeros_like(h0, f32), jnp.zeros_like(z0, f32)),
            seq,
            reverse=True,
            unroll=unroll,
        )

        # ---- weight gradients: one batched contraction each
        tb = T * b
        grads = V1DynParams(
            w_proj=(inp_p32.reshape(tb, -1).T @ dfpre_s.reshape(tb, -1)).astype(
                params.w_proj.dtype
            ),
            b_proj=dfpre_s.sum((0, 1)).astype(params.b_proj.dtype),
            w_i=(feat32.reshape(tb, -1).T @ dgi_s.reshape(tb, -1)).astype(params.w_i.dtype),
            b_i=dgi_s.sum((0, 1)).astype(params.b_i.dtype),
            w_h=(h_prev.reshape(tb, -1).T @ dgh_s.reshape(tb, -1)).astype(params.w_h.dtype),
            b_hn=dgh_s[..., 2 * hidden :].sum((0, 1)).astype(params.b_hn.dtype),
            k_h=(hs.reshape(tb, hidden).T @ dxpre_s.reshape(tb, -1)).astype(
                params.k_h.dtype
            ),
            head_k=(x32.reshape(tb, -1).T @ dms_s.reshape(tb, -1)).astype(
                params.head_k.dtype
            ),
            head_b=dms_s.sum((0, 1)).astype(params.head_b.dtype),
        )
        d_actions = (dfpre_s @ params.w_proj[stoch:].astype(f32).T).astype(actions.dtype)
        d_emb = dxpre_s.astype(emb_proj.dtype)
        return (
            dz0.astype(z0.dtype),
            dh0.astype(h0.dtype),
            d_actions,
            d_emb,
            jnp.zeros_like(noise),
            grads,
        )

    op.defvjp(op_fwd, op_bwd)
    return op


def extract_dyn_params_v1(rssm_variables, hidden: int) -> V1DynParams:
    """Pull the DV1 op's raw weight leaves out of a bound DV1 RSSM param
    tree (``wm_params["rssm"]``).  Plain dict indexing/slicing so autodiff
    routes the op's weight cotangents back into the original tree; the
    embed-side rows of the representation Dense get their gradient through
    the ``representation_embed_proj`` path."""
    p = rssm_variables["params"]
    lin = p["recurrent_model"]["Dense_0"]
    gru = p["recurrent_model"]["GRUCell_0"]
    rep_lin = p["representation_model"]["DenseActLn_0"]["Dense_0"]
    head = p["representation_model"]["Dense_0"]
    return V1DynParams(
        w_proj=lin["kernel"],
        b_proj=lin["bias"],
        w_i=jnp.concatenate(
            [gru["ir"]["kernel"], gru["iz"]["kernel"], gru["in"]["kernel"]], -1
        ),
        b_i=jnp.concatenate([gru["ir"]["bias"], gru["iz"]["bias"], gru["in"]["bias"]], -1),
        w_h=jnp.concatenate(
            [gru["hr"]["kernel"], gru["hz"]["kernel"], gru["hn"]["kernel"]], -1
        ),
        b_hn=gru["hn"]["bias"],
        k_h=rep_lin["kernel"][:hidden],
        head_k=head["kernel"],
        head_b=head["bias"],
    )


def dyn_rssm_sequence_v1(
    z0,
    h0,
    actions,
    emb_proj,
    noise,
    params: V1DynParams,
    *,
    min_std: float = 0.1,
    matmul_dtype=jnp.float32,
    unroll: int = 1,
    act: str = "elu",
):
    """Run the DV1 T-step dynamic recurrence with the efficient-BPTT VJP.

    z0 (B, S) f32 Gaussian posterior sample; h0 (B, H); actions (T, B, A);
    emb_proj (T, B, R) in the compute dtype (embed-side projection incl.
    the Dense bias, ``RSSM.representation_embed_proj``); noise (T, B, S)
    pre-drawn standard normal.  No is_first gating — DV1 sequences cross
    episode boundaries (reference dreamer_v1/agent.py dynamic:97).

    Returns (hs (T,B,H) f32, zs (T,B,S) f32, means (T,B,S) f32,
    stds (T,B,S) f32); ``zs`` is the reparameterized sample
    ``mean + std * noise`` so gradients flow through both moments,
    exactly like scanning ``dynamic_posterior_from_proj``.
    """
    op = _get_op_v1(float(min_std), jnp.dtype(matmul_dtype).name, int(unroll), str(act))
    return op(z0, h0, actions, emb_proj, noise, params)


def rssm_dyn_bptt_eligible(rssm) -> bool:
    """Does this DV3 RSSM's configuration match the op's closed-form
    backward?  Requires the non-decoupled posterior, LayerNorm blocks,
    a supported activation, unimix > 0, and the plain (non-Pallas) GRU
    cell so the fwd numerics are the reference scan's."""
    return (
        not rssm.decoupled
        and rssm.layer_norm
        and rssm.unimix > 0.0
        and rssm.act in ("silu", "elu")
        and not rssm.fused_gru
    )


def extract_dyn_params(rssm_variables, hidden: int) -> DynParams:
    """Pull the op's raw weight leaves out of a bound DV3 RSSM param tree
    (``wm_params["rssm"]``). Plain dict indexing/slicing, so autodiff
    routes the op's weight cotangents back into the original tree
    (including the h-side rows of the representation model's first Dense —
    the embed-side rows get their gradient through the
    ``representation_embed_proj`` path)."""
    p = rssm_variables["params"]
    lin = p["recurrent_model"]["LinearLnAct_0"]
    gru = p["recurrent_model"]["LayerNormGRUCell_0"]
    rep_lin = p["representation_model"]["LinearLnAct_0"]
    head = p["representation_model"]["Dense_0"]
    w_proj = lin["Dense_0"]["kernel"]
    w_gru = gru["Dense_0"]["kernel"]
    return DynParams(
        w_proj=w_proj,
        b_proj=jnp.zeros((w_proj.shape[-1],), w_proj.dtype),
        lnp_scale=lin["LayerNorm_0"]["scale"],
        lnp_bias=lin["LayerNorm_0"]["bias"],
        w_gru=w_gru,
        b_gru=jnp.zeros((w_gru.shape[-1],), w_gru.dtype),
        lng_scale=gru["LayerNorm_0"]["scale"],
        lng_bias=gru["LayerNorm_0"]["bias"],
        k_h=rep_lin["Dense_0"]["kernel"][:hidden],
        lnr_scale=rep_lin["LayerNorm_0"]["scale"],
        lnr_bias=rep_lin["LayerNorm_0"]["bias"],
        head_k=head["kernel"],
        head_b=head["bias"],
    )


def extract_dyn_params_v2(rssm_variables, hidden: int) -> DynParams:
    """Same extraction for the DV2 RSSM (DenseActLn blocks: Dense WITH
    bias; GRU with bias; rep-trunk LayerNorm optional — absent leaves are
    filled with identity LN params, gated off by the ``rep_ln``/
    ``proj_ln`` statics)."""
    p = rssm_variables["params"]
    lin = p["recurrent_model"]["DenseActLn_0"]
    gru = p["recurrent_model"]["LayerNormGRUCell_0"]
    rep_lin = p["representation_model"]["DenseActLn_0"]
    head = p["representation_model"]["Dense_0"]
    w_proj = lin["Dense_0"]["kernel"]
    w_gru = gru["Dense_0"]["kernel"]
    proj_units = w_proj.shape[-1]
    rep_units = rep_lin["Dense_0"]["kernel"].shape[-1]

    def _ln_or_identity(block, n):
        if "LayerNorm_0" in block:
            return block["LayerNorm_0"]["scale"], block["LayerNorm_0"]["bias"]
        return jnp.ones((n,), w_proj.dtype), jnp.zeros((n,), w_proj.dtype)

    lnp_scale, lnp_bias = _ln_or_identity(lin, proj_units)
    lnr_scale, lnr_bias = _ln_or_identity(rep_lin, rep_units)
    return DynParams(
        w_proj=w_proj,
        b_proj=lin["Dense_0"]["bias"],
        lnp_scale=lnp_scale,
        lnp_bias=lnp_bias,
        w_gru=w_gru,
        b_gru=gru["Dense_0"]["bias"],
        lng_scale=gru["LayerNorm_0"]["scale"],
        lng_bias=gru["LayerNorm_0"]["bias"],
        k_h=rep_lin["Dense_0"]["kernel"][:hidden],
        lnr_scale=lnr_scale,
        lnr_bias=lnr_bias,
        head_k=head["kernel"],
        head_b=head["bias"],
    )


def dyn_rssm_sequence(
    z0,
    h0,
    actions,
    emb_proj,
    is_first,
    noise,
    init_rec,
    init_post,
    params: DynParams,
    *,
    eps_proj: float = 1e-3,
    eps_rep: float = 1e-3,
    unimix: float = 0.01,
    discrete: int = 32,
    matmul_dtype=jnp.float32,
    unroll: int = 1,
    act: str = "silu",
    proj_ln: bool = True,
    rep_ln: bool = True,
):
    """Run the full T-step dynamic recurrence with the efficient-BPTT VJP.

    z0 (B, S) f32 flat posterior; h0 (B, H); actions (T, B, A) f32
    (UNgated — the is_first gating happens inside); emb_proj (T, B, R) in
    the compute dtype (embed-side projection incl. any Dense bias,
    ``RSSM.representation_embed_proj``); is_first (T, B, 1); noise
    (T, B, groups, discrete) pre-drawn gumbel; init_rec (B, H) /
    init_post (B, S) reset states (DV3: the learned initial state; DV2:
    zeros).

    Returns (hs (T,B,H) f32, z_st (T,B,S) f32, logits (T,B,S) f32 — the
    unimix-mixed logits for V3, the raw logits for V2); ``z_st``'s forward
    value is the hard one-hot sample and its gradient is the
    straight-through estimator, exactly like scanning the corresponding
    ``dynamic_posterior`` method.
    """
    op = _get_op(
        float(eps_proj),
        float(eps_rep),
        float(unimix),
        int(discrete),
        jnp.dtype(matmul_dtype).name,
        int(unroll),
        str(act),
        bool(proj_ln),
        bool(rep_ln),
    )
    noise = noise.reshape(*noise.shape[:2], -1)
    return op(z0, h0, actions, emb_proj, is_first, noise, init_rec, init_post, params)
