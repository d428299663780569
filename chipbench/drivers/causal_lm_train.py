"""Driver ``causal_lm_train``: PPO + MTP minibatch steps of the causal
language-model policy over a seeded rollout of whole episodes, back to back; no
env, no collector.

Set-up: build the update as ``ppo.main`` builds it, weights from ``--seed``;
make the rollout on the device from the seed and hold it to its numpy
reference; record old log-probabilities and values with a no-gradient pass at
the initial weights; run ONE update call (the program the window times, at the
timed sizes) and compare what its first minibatch step produced, and what the
call did to the state it returned, with the plain reference at the published
widths, one episode at a time (``compare``, ``judge``); start again from the
initial state and warm up.  Window: update calls back to back (each: GAE, then
one epoch of minibatch steps of ``minibatch_episodes`` whole episodes), the
host at most ``run_ahead`` calls ahead, closed by a fetch of the last losses.
``setup_s`` leaves out the reference's own seconds.

The parameters with their optimizer state are half of the chip's memory, so the
initial parameters the driver subtracts from the returned state are kept on
the HOST, and the returned state leaves the device before the reference runs.
"""

from __future__ import annotations

import collections
import itertools
import time
from typing import Any, Dict, List

from chipbench import causal_rollout_fill, flops_joyai, program_joyai
from chipbench.drivers.sdar_train import _worst_leaf, adam_first_step, leaf_norms
from chipbench.harness import Context, fetch_losses, note, require, span
from chipbench.program import compose_cfg, recompile_monitor
from chipbench.reference import mla_moe as reference

# ---- the comparison with the reference (float32, "highest", published widths, unabsorbed attention).
# The program computes in bf16-mixed: f32 parameters, gradients and Adam, bf16 products summed in
# f32, norms / sigmoid / softmax / router in f32.  Two readings stand behind every limit (PERF.md
# section 4, my chip runs, PR 30): the largest the program read over its seeds, and what the SAME
# measurement reads on the program's own lower precision, ``fabric.precision=bf16-true``
# (parameters stored in bf16; ``benchmarks/sdar_bf16_reading.py --workload joyai_ep_train``).
#
# As in ``sdar_train``: forward quantities hardly tell the two precisions apart; what parameters
# kept in bf16 lose is the step (1e-5 x g / (|g| + 1e-4) on weights of 0.02 is under bf16's
# resolution), so precision is decided on the STATE THE CALL RETURNED, which the driver reads
# itself, on the host: the norm of ``returned - initial`` over the sum of the steps' own changes
# (1 if every step moved the same way, 1 / sqrt(steps) if the steps are independent, 0 for a state
# returned unchanged).  The other limits hold the mathematics: a missing term (the MTP loss left
# out), a wrong mask, a wrong reduction or half a batch shows as tens of per cent.
#
# A top-8 choice flips where the last biased score kept and the first one left out differ by less
# than rounding.  The reference takes the program's choice where the two differ AND its own gap is
# under ROUTE_MARGIN of the last kept biased score; a choice that differs at a wider gap is not
# taken over and shows as a count mismatch.  ``handed_share`` says how much of the reference's
# routing came from the program.  The counts per held expert are compared twice: after the
# hand-over exactly (``count_mismatch``), and against the reference's OWN choice at every position
# (``own_count_mismatch_share``).
#
# The readings (my chip runs, PR 30; one v5e): the largest over ten runs of the program on ten seeds |
# the control on bf16-true (one seed, not correct by ``returned_shortfall`` alone).  The control's
# forward and gradient numbers read LOWER than the program's (both sides then start from the same
# rounded weights), so those limits cannot stand between the two: they stand three to five times over
# the program's largest, where a missing term or half a batch reads tens of per cent or 1.
# choices differ at 5.9-7.6 % of positions | 5.9 %, widest differing gap 0.73 % | 0.38 % of the last kept biased score
ROUTE_MARGIN = 0.02
HANDED_MAX = 0.15  # 0.059-0.076 | 0.059: all of the differing choices were handed over
OWN_COUNT_MISMATCH_MAX = 0.03  # 0.006-0.009 | 0.006
# log-probabilities of the taken tokens (about -9.69 each) and values: mean and worst absolute
# difference over the minibatch's 7,168 cells (6.6e-3 / 4.1e-2, 6.9e-3 / 3.6e-2; control 5.0e-3 / 2.6e-2, 4.7e-3 / 2.4e-2)
LOGP_MEAN_ATOL, LOGP_MAX_ATOL = 2e-2, 1e-1
VALUE_MEAN_ATOL, VALUE_MAX_ATOL = 2e-2, 1e-1
# the four losses (policy, value, entropy, MTP): |got - ref| <= RTOL * (|ref| + FLOOR), the accepted cells'
# form (0.036 of it at most; control 0.014).  A loss missing from the update reads infinite.
LOSS_RTOL, LOSS_FLOOR = 1e-1, 5e-2
# the gradient's norm, whole (8.8e-5 to 0.050 with the seed; control 2.0e-4) and leaf by leaf, the worst of 72
# leaves (0.006 to 0.095, an output projection or a router; control 0.013)
GRAD_NORM_RTOL, GRAD_LEAF_RTOL = 0.25, 0.4
# the norm of every leaf's ``new - old`` in the first step, the worst leaf (0.004-0.057; control 0.007):
# between that and the 1 of a leaf that did not move
MOVED_LEAF_RTOL = 0.3
# the state the call returned against the state it was given, over the sum of the steps' changes (above):
# shortfall 0.140-0.160 | 0.794; a state returned unchanged reads 1, one step of four kept 0.75
RETURNED_MIN, RETURNED_MAX = 0.5, 1.001
LIMITS = {
    "handed_share": HANDED_MAX, "own_count_mismatch_share": OWN_COUNT_MISMATCH_MAX,
    "logp_mean_abs": LOGP_MEAN_ATOL, "logp_max_abs": LOGP_MAX_ATOL,
    "value_mean_abs": VALUE_MEAN_ATOL, "value_max_abs": VALUE_MAX_ATOL, "loss_worst": 1.0,
    "grad_norm_rel": GRAD_NORM_RTOL, "grad_leaf_worst_rel": GRAD_LEAF_RTOL, "moved_leaf_worst_rel": MOVED_LEAF_RTOL,
    "returned_shortfall": 1.0 - RETURNED_MIN, "returned_excess": RETURNED_MAX - 1.0, "count_mismatch": 0,
}
LOSSES = ("pg", "vl", "ent", "mtp_loss")  # the order of the update's probe: policy, value, entropy, the auxiliary loss


def _ref_episodes(data: Dict[str, Any], ids, hyper: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The reference's view of the minibatch's episodes: GAE per episode, the
    advantages normalised over the minibatch as the run does."""
    import numpy as np

    eps = []
    for e in ids:
        returns, adv = reference.gae(data["rewards"][:, e, 0], data["values"][:, e, 0], data["dones"][:, e, 0], 0.0,
                                     hyper["gamma"], hyper["gae_lambda"])
        eps.append({"prompt": data["prompt"][0, e], "response": data["actions"][:, e, 1], "returns": returns,
                    "advantages": adv, "old_logp": data["logprobs"][:, e, 0], "old_values": data["values"][:, e, 0]})
    if hyper["normalize_advantages"]:
        adv = np.stack([ep["advantages"] for ep in eps])
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        for ep, a in zip(eps, adv):
            ep["advantages"] = a.astype(np.float32)
    return eps


def reference_step(rparams, episodes: List[Dict[str, Any]], model_cfg: Dict[str, Any], hyper: Dict[str, Any],
                   top_i) -> Dict[str, Any]:
    """What one minibatch step must produce, by the plain reference: one
    episode at a time, gradients averaged over the episodes.  ``top_i``
    (routed blocks, episodes, N, k): the program's routing choice, handed over
    where the reference's own gap is under ``ROUTE_MARGIN``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # The reference's two kinds of layer are compiled once each and serve every block (the MTP module's
    # too) and episode; a block of queries' attention, an expert and a head pass are rematerialised
    # inside, the layer itself in the backward pass: whole, the f32 reference does not fit a chip
    # beside its parameters and gradients.
    transformed = {}

    def wrap(name, f):
        # the functions of one name differ only in what they close over (the configuration and this
        # very `wrap`), which is the same for every call of this step
        if name not in transformed:
            transformed[name] = jax.jit(jax.checkpoint(f)) if name.endswith("_layer") else jax.checkpoint(f)
        return transformed[name]

    def step(rparams, episode, ids):
        forced = [(ids[i], ROUTE_MARGIN) for i in range(ids.shape[0])]
        (_, out), grads = jax.value_and_grad(
            lambda p: reference.loss_episode(p, episode, model_cfg, hyper, forced, wrap=wrap), has_aux=True)(rparams)
        aux = out.pop("aux")
        out.update({k: jnp.stack([a[k] for a in aux])
                    for k in ("counts", "own_counts", "handed", "differs", "rel_gap", "top_i")})
        return out, grads

    total, outs = None, []
    for b, ep in enumerate(episodes):
        episode = {k: jnp.asarray(v) for k, v in ep.items()}
        out, grads = step(rparams, episode, jnp.asarray(top_i[:, b]))
        total = grads if total is None else jax.tree_util.tree_map(jnp.add, total, grads)
        outs.append(jax.device_get(out))
        del grads
    grads = jax.tree_util.tree_map(lambda g: g / len(episodes), total)
    del total
    # the step as the stored parameters take it: a norm gain of 1.0 in f32 does not register a step under
    # 3e-8 (lr 1e-5 on an element whose gradient is under 3e-7), in the program or here
    moved = jax.tree_util.tree_map(lambda p, step: (p + step) - p, rparams, adam_first_step(grads, hyper))
    return {
        "logp": np.stack([o["logp"] for o in outs]), "values": np.stack([o["values"] for o in outs]),
        "losses": np.asarray([np.mean([o[k] for o in outs]) for k in LOSSES]),
        "mtp_top1_match": float(np.mean([o["mtp_top1_match"] for o in outs])),
        "grad_norm": float(np.sqrt(sum(v * v for v in leaf_norms(grads).values()))),
        "grad_leaf_norms": leaf_norms(grads), "moved_leaf_norms": leaf_norms(moved),
        "load": sum(o["counts"] for o in outs), "own_load": sum(o["own_counts"] for o in outs),
        **{k: np.stack([o[k] for o in outs], 1) for k in ("handed", "differs", "rel_gap", "top_i")},
    }


def compare(got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """Readings of one call of the update (its first minibatch step, and the
    state it returned) against the reference's, each beside its limit in
    ``LIMITS``; the keys outside ``LIMITS`` are not judged."""
    import numpy as np

    dl, dv = np.abs(got["logp"] - ref["logp"]), np.abs(got["values"] - ref["values"])
    if len(got["losses"]) == len(ref["losses"]):
        loss_err = np.abs(got["losses"] - ref["losses"]) / (LOSS_RTOL * (np.abs(ref["losses"]) + LOSS_FLOOR))
    else:  # a term is missing from the update's loss (the MTP cross-entropy): no reading can stand in for it
        loss_err = np.asarray([np.inf])
    grad_leaf, grad_at = _worst_leaf(got["grad_leaf_norms"], ref["grad_leaf_norms"])
    moved_leaf, moved_at = _worst_leaf(got["moved_leaf_norms"], ref["moved_leaf_norms"])
    returned = got["returned_change"] / max(sum(got["steps_change"]), 1e-30)
    handed, differs = ref["handed"], ref["differs"]  # (routed blocks, episodes, N)
    # after the hand-over the two have chosen alike wherever the gap allowed it, so the counts per held
    # expert agree unless a choice differed at a wider gap
    same = (np.sort(got["top_i"], -1) == np.sort(ref["top_i"], -1)).all(-1)
    return {
        "handed_share": float(handed.mean()),
        "own_count_mismatch_share": float(np.abs(got["load"] - ref["own_load"]).sum() / max(ref["own_load"].sum(), 1)),
        "logp_mean_abs": float(dl.mean()), "logp_max_abs": float(dl.max()),
        "value_mean_abs": float(dv.mean()), "value_max_abs": float(dv.max()), "loss_worst": float(loss_err.max()),
        "grad_norm_rel": abs(got["grad_norm"] - ref["grad_norm"]) / ref["grad_norm"],
        "grad_leaf_worst_rel": grad_leaf, "moved_leaf_worst_rel": moved_leaf,
        "returned_shortfall": 1.0 - returned, "returned_excess": returned - 1.0,
        "count_mismatch": int(np.abs(got["load"] - ref["load"]).sum()) + int((~same).sum()),
        # not judged: where the worst leaves were, and how wide the widest gap was at which a choice differed
        "grad_leaf_worst_at": grad_at, "moved_leaf_worst_at": moved_at,
        "differs_share": float(differs.mean()),
        "differs_rel_gap_max": float(ref["rel_gap"][differs].max()) if differs.any() else 0.0,
    }


def judge(readings: Dict[str, Any]) -> Dict[str, Any]:
    """The readings over their limits: empty for a correct update."""
    return {k: v for k, v in readings.items() if k in LIMITS and not v <= LIMITS[k]}


def build(ctx: Context):
    """(the update built as ``ppo.main`` builds it, the shapes), the program
    held to the configuration's file number for number."""
    cfg = compose_cfg(ctx.overrides())  # its seed, and so the weights' draw, is --seed
    shapes = flops_joyai.MlaShapes.from_config(ctx.config, ctx.traffic, ctx.tiny)
    with span("setup:build"):
        prog = program_joyai.CausalLmUpdate(cfg)
    mc = prog.model_cfg
    want = {"hidden_size": shapes.hidden, "num_attention_heads": shapes.heads, "q_lora_rank": shapes.q_rank,
            "kv_lora_rank": shapes.kv_rank, "qk_nope_head_dim": shapes.nope, "qk_rope_head_dim": shapes.rope,
            "v_head_dim": shapes.v_dim, "intermediate_size": shapes.dense_width, "moe_intermediate_size": shapes.expert_width,
            "n_shared_experts": shapes.shared_experts, "n_routed_experts": shapes.router_width,
            "num_experts_per_tok": shapes.top_k, "experts_held": shapes.experts_held, "num_hidden_layers": shapes.layers,
            "first_k_dense_replace": shapes.dense_layers, "num_nextn_predict_layers": shapes.mtp_modules,
            "vocab_size": shapes.vocab}
    differs = {k: (mc[k], v) for k, v in want.items() if mc[k] != v}
    require(not differs, f"the program's model differs from the configuration file: {differs}")
    c = ctx.config
    require(ctx.tiny or (mc["rope_theta"], mc["rms_norm_eps"], mc["norm_topk_prob"], mc["routed_scaling_factor"],
                         mc["scoring_func"], mc["expert_offset"], prog.hyper["mtp_coef"], str(cfg.fabric.precision)) ==
            (c["rope_theta"], c["rms_norm_eps"], c["norm_topk_prob"], c["routed_scaling_factor"], c["scoring_func"],
             c["expert_offset"], c["mtp_coef"], c["precision"]),
            "rope_theta, rms_norm_eps, norm_topk_prob, routed_scaling_factor, scoring_func, expert_offset, mtp_coef or "
            "precision differ from the file")
    require(c["rope_interleave"] and c["rope_scaling"] is None and c["n_group"] == c["topk_group"] == 1,
            "the program rotates adjacent pairs without scaling and routes without group limits: the file says otherwise")
    n_eps, mb_eps = int(ctx.param("episodes")), int(ctx.param("minibatch_episodes"))
    require((int(cfg.env.num_envs), int(cfg.algo.per_rank_batch_size), int(cfg.algo.update_epochs)) == (n_eps, mb_eps, 1),
            "episodes, minibatch or epochs of the program differ from the traffic mix")
    require((int(cfg.env.wrapper.prompt_len), int(cfg.env.wrapper.response_len)) == (shapes.prompt, shapes.response),
            "prompt or response length of the program differ from the traffic mix")
    return prog, shapes


def make_rollout(ctx: Context, prog, shapes):
    """The seeded rollout on the device, held to its numpy reference, with old
    log-probabilities and values from a no-gradient pass at the initial
    weights; also its copy on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_eps, mb_eps = int(ctx.param("episodes")), int(ctx.param("minibatch_episodes"))
    rollout = (ctx.seed, n_eps, shapes.prompt, shapes.response, shapes.vocab)
    with span("setup:rollout"):
        data = causal_rollout_fill.fill(*rollout)
        problem = causal_rollout_fill.check(jax.device_get(data), *rollout)
    require(not problem, f"the rollout vs its seeded reference: {problem}")
    with span("setup:old_policy"):
        params = prog.initial_params()
        prompt, actions = data["prompt"][0], jnp.swapaxes(data["actions"], 0, 1)
        old = [prog.old_policy(params, prompt[i:i + mb_eps], actions[i:i + mb_eps]) for i in range(0, n_eps, mb_eps)]
        data["logprobs"] = jnp.concatenate([o[0] for o in old]).T[..., None]
        data["values"] = jnp.concatenate([o[1] for o in old]).T[..., None]
        del old, params
    data = jax.device_put(data, prog.runtime.replicated)
    host = {k: np.asarray(v) for k, v in jax.device_get(data).items()}
    require(all(np.isfinite(host[k]).all() for k in ("logprobs", "values")), "non-finite old log-probabilities or values")
    return data, host


def compared_call(prog, shapes, data, key, learning_rate=None):
    """One call of the timed update from the initial state: what its first
    minibatch step produced (the update's own probe) and what the call did to
    the state it returned (the driver's own subtraction, on the host, from the
    initial parameters fetched before the call).  Returns ``(got, the initial
    parameters on the host, the call's metrics)``."""
    import jax
    import numpy as np

    params, opt_state = prog.initial_state()
    initial = jax.device_get(params)
    params, opt_state, metrics, probe = prog.update(params, opt_state, data, key, learning_rate)
    returned = jax.device_get(params)
    del params, opt_state
    returned_change = float(np.sqrt(sum(
        float(np.sum(np.square(np.asarray(n, np.float32) - np.asarray(o, np.float32)), dtype=np.float64))
        for n, o in zip(jax.tree_util.tree_leaves(returned), jax.tree_util.tree_leaves(initial)))))
    del returned
    probe, metrics = jax.device_get((probe, metrics))
    first = jax.tree_util.tree_map(lambda x: x[0], probe)
    in_layout = lambda tree: {jax.tree_util.keystr(path): float(v) for path, v in  # noqa: E731
                              jax.tree_util.tree_leaves_with_path(program_joyai.reference_params(tree))}
    mb_eps = first["logprobs"].shape[0]
    got = {"episodes": [int(i) for i in first["episodes"]], "logp": first["logprobs"], "values": first["values"],
           "losses": first["losses"], "grad_norm": float(first["grad_norm"]),
           "grad_leaf_norms": in_layout(first["grad_leaf_norms"]), "moved_leaf_norms": in_layout(first["moved_leaf_norms"]),
           "returned_change": returned_change,
           "steps_change": [float(np.sqrt(sum(np.square(v[i]) for v in jax.tree_util.tree_leaves(probe["moved_leaf_norms"]))))
                            for i in range(len(probe["episodes"]))],
           "mtp_top1_match": float(first["aux_counters"].get("MTP/top1_match", np.nan)),
           "load": first["load"], "top_i": first["top_i"].reshape(shapes.routed_blocks, mb_eps, shapes.positions, shapes.top_k)}
    return got, initial, metrics


def reference_for(prog, shapes, host, got, initial) -> Dict[str, Any]:
    """The plain reference's reading of the compared minibatch step, from the
    same initial parameters in f32."""
    import jax
    import jax.numpy as jnp

    rparams = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), program_joyai.reference_params(initial))
    return reference_step(rparams, _ref_episodes(host, got["episodes"], prog.hyper), prog.model_cfg, prog.hyper,
                          got["top_i"])


def run(ctx: Context) -> dict:
    import jax
    import numpy as np

    monitor = recompile_monitor("chipbench")
    prog, shapes = build(ctx)
    ctx.lap("build")
    rt = prog.runtime
    devices = list(rt.mesh.devices.ravel())
    require(rt.device_count == ctx.chips == 1, f"the update runs on {rt.device_count} device(s), the cell asks for {ctx.chips}")
    require(ctx.tiny or devices[0].platform == "tpu", "the mesh is not a TPU")
    n_eps, mb_eps = int(ctx.param("episodes")), int(ctx.param("minibatch_episodes"))
    steps_per_call = n_eps // mb_eps
    note(n_params=prog.n_params, positions_per_step=mb_eps * shapes.positions, frames_per_step=shapes.frames,
         steps_per_call=steps_per_call, needed_tflop_per_step_even_routing=flops_joyai.step_flops(shapes)["total"] / 1e12)

    data, host = make_rollout(ctx, prog, shapes)
    ctx.lap("rollout")

    # ---- one call of the timed update, against the reference
    key0 = jax.random.PRNGKey(ctx.seed)
    keys = (jax.random.fold_in(key0, i) for i in itertools.count(1))  # the later calls' keys
    with span("setup:first_call"):
        got, initial, first_metrics = compared_call(prog, shapes, data, key0)
    ctx.lap("first_call")
    t_reference = time.perf_counter()
    with span("setup:reference"):
        ref = reference_for(prog, shapes, host, got, initial)
        del initial
    readings = compare(got, ref)
    reference_s = time.perf_counter() - t_reference  # the reference's own seconds: not the system's set-up
    even = flops_joyai.expected_assignments(shapes)
    note(compare_with_reference={
        "episodes": got["episodes"], "readings": readings, "limits": LIMITS, "route_margin": ROUTE_MARGIN,
        "program": {"losses": got["losses"].tolist(), "grad_norm": got["grad_norm"], "steps_change": got["steps_change"],
                    "returned_change": got["returned_change"], "mtp_top1_match": got["mtp_top1_match"]},
        "reference": {"losses": ref["losses"].tolist(), "grad_norm": ref["grad_norm"], "mtp_top1_match": ref["mtp_top1_match"]},
        "load_per_held_expert": got["load"].tolist(), "even_load": even,
        "held_share": float(got["load"].sum() / (shapes.routed_blocks * mb_eps * shapes.positions * shapes.top_k)),
        "even_held_share": shapes.experts_held / shapes.router_width, "reference_s": reference_s,
    })
    ctx.lap("reference")
    over = judge(readings)  # judged after the window: an incorrect run still says how fast it was

    # ---- warm-up from the initial state again (the compared call was donated its own)
    params, opt_state = prog.initial_state()
    measured = []
    with span("setup:warmup"):
        for _ in range(int(ctx.param("warmup_calls", 2))):
            params, opt_state, m, _ = prog.update(params, opt_state, data, next(keys))
            measured.append(m)
        warm = fetch_losses(measured)
    require(all(np.all(np.isfinite(v)) for v in warm.values()), "non-finite losses in the warm-up")
    ctx.lap("warmed_up")

    # ---- the window
    depth = int(ctx.param("run_ahead", 4))
    seconds = ctx.window_seconds
    pending = collections.deque()
    measured = []
    before = monitor.snapshot()
    with ctx.profile():
        t0 = time.perf_counter()
        calls = 0
        while True:
            with span("update"):
                params, opt_state, m, _ = prog.update(params, opt_state, data, next(keys))
            measured.append(m)
            pending.append(m["Loss/policy_loss"])
            calls += 1
            if len(pending) > depth:
                with span("pace"):
                    pending.popleft().block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with span("close"):
            last = float(jax.device_get(measured[-1]["Loss/policy_loss"]))
        t1 = time.perf_counter()
    after = monitor.snapshot()
    window_s = t1 - t0
    steps = calls * steps_per_call

    losses = fetch_losses(measured)
    bad = sorted(k for k, v in losses.items() if not np.all(np.isfinite(v)))
    window_compiles = after["total"] - before["total"]
    load = np.asarray([[losses[f"MoE/load_l{i}_e{e}"].mean() for e in range(shapes.experts_held)]
                       for i in range(shapes.routed_blocks)]) / steps_per_call  # per routed block, expert and step
    assignments = float(load.sum(-1).mean())  # to held experts, per routed block and step
    dropped = float(losses["MoE/dropped"].sum())
    needed = flops_joyai.step_flops(shapes, assignments)  # the experts' term from the counted assignments
    ctx.evidence.update(
        steps=steps, window_s=window_s, steps_per_s=steps / window_s, frames_per_step=shapes.frames, chips=1,
        device_kind=devices[0].device_kind, steps_per_call=steps_per_call,
        flops_per_step=needed["total"], mla_kernel_flops_per_step=needed["attention"], window_compiles=window_compiles,
        programs=ctx.param("programs", {}),
        moe={"load_max_over_mean": float(losses["MoE/load_max_over_mean"].mean()),
             "held_share": float(losses["MoE/held_share"].mean()), "assignments_per_layer_and_step": assignments,
             "expert_flops_per_step": needed["experts"],
             "dropped": dropped},
    )
    note(window={"calls": calls, "steps": steps, "seconds": window_s, "last_policy_loss": last},
         moe={"load_per_block_expert_step": load.round(1).tolist(), "even_load": even, **ctx.evidence["moe"],
              "even_held_share": shapes.experts_held / shapes.router_width,
              "short_buffer_share": float(losses["MoE/short_buffer_share"].mean()),
              "router_entropy": float(losses["MoE/router_entropy"].mean())},
         mtp={"loss": float(losses["MTP/loss"].mean()), "top1_match": float(losses["MTP/top1_match"].mean())},
         first_call_metrics={k: float(v) for k, v in first_metrics.items() if not k.startswith("MoE/load_l")},
         compiles={"before": before, "after": after}, setup_laps_s=ctx.evidence["setup_laps_s"])
    require(not over, f"the update's compared call vs the reference: {over} over {({k: LIMITS[k] for k in over})}")
    require(not bad, f"non-finite losses in the window: {bad}")
    require(window_compiles == 0, f"{window_compiles} compiles inside the window")
    require(dropped == 0, f"{dropped} assignments dropped")
    return {
        "attempted": steps,
        "failed": 0,
        "setup_s": t0 - ctx.t_process_start - reference_s,
        "end_to_end": {"train_frames_per_s": (steps * shapes.frames / window_s, "frames/s")},
    }
