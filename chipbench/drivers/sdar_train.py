"""Driver ``sdar_train``: PPO minibatch steps of the language-model policy over
a seeded rollout of packed denoising trajectories, back to back; no env, no
collector.

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
"""

from __future__ import annotations

import collections
import itertools
import time
from typing import Any, Dict, List

from chipbench import flops_sdar, program_sdar, rollout_fill
from chipbench.harness import Context, fetch_losses, note, require, span
from chipbench.program import compose_cfg, recompile_monitor
from chipbench.reference import sdar_moe as reference

# ---- the comparison with the reference (float32, "highest", published widths).
# The program computes in bf16-mixed: f32 parameters, gradients and Adam, bf16 products summed in
# f32, norms / softmax / router in f32.  Two readings stand behind every limit (PERF.md section 4,
# my chip runs, PR 26): the largest the program read over its seeds (9 runs of this tree), and what
# the SAME measurement reads on the program's own lower precision, ``fabric.precision=bf16-true``
# (parameters stored in bf16; ``benchmarks/sdar_bf16_reading.py`` runs this driver so).
#
# Forward quantities do not tell the two apart (the control's read LOWER: both sides then start
# from the same rounded weights).  What parameters kept in bf16 lose is the step: 1e-5 x g / (|g| +
# 1e-4) on weights of 0.02 is under bf16's resolution.  So precision is decided on the STATE THE
# CALL RETURNED, which the driver reads itself: the norm of ``returned - initial`` over the sum of
# the steps' own changes (1 if every step moved the same way, 1 / sqrt(steps) if the steps are
# independent, 1 / steps if one step was kept, 0 for a state returned unchanged).  The program read
# 0.833-0.859 (shortfall 0.141-0.167), bf16-true 0.214 (0.786: most bf16 elements stay, the rest
# jump a whole ulp at random); RETURNED_MIN stands between.  The update's own probe (the norm of
# every leaf's ``new - old`` in the first step, against the reference's gradient put through the
# same clip and Adam step, the worst leaf) holds the step's mathematics and reads 1 for a leaf that
# did not move, but it cannot see bf16 storage: XLA keeps excess precision inside a program, so the
# probe of bf16-true read like f32 (0.012).  The other limits hold the mathematics as well: a
# missing term, a wrong mask, a wrong reduction or half a batch shows as tens of per cent.
# A top-k choice flips where the last probability kept and the first one left out differ by less
# than rounding.  With weights drawn at 0.02 the router's logits spread 0.9 over 128 experts: the
# 8th and 9th probabilities differ by 5.7 % on average, and bf16 products before the router move a
# probability by about 0.3 %.  So 4-5 % of the positions choose otherwise than the f32 reference
# (ISSUE 26 hoped for under 1 %, which this router's statistics do not allow).  The reference takes
# the program's choice where the two differ AND its own gap is under ROUTE_MARGIN of the last kept
# probability (widest differing gap read: 2.3 %); a choice that differs at a wider gap is not taken
# over and shows as a count mismatch.  ``handed_share`` says how much of the reference's routing
# came from the program (0.041-0.049; control 0.028): its limit stands just over the sound runs'
# largest and tells nothing about precision.  The counts per held expert are compared twice: after
# the hand-over exactly (``count_mismatch``, 0 on every run), and against the reference's OWN
# choice at every position (``own_count_mismatch_share``, the share of the reference's assignments
# to held experts that the program counted otherwise: 0.004-0.011; control 0.003).
ROUTE_MARGIN = 0.05
HANDED_MAX = 0.07
OWN_COUNT_MISMATCH_MAX = 0.03
# log-probabilities of the taken tokens (about -9.85 each) and values: mean and worst absolute
# difference over the minibatch's 3,072 cells (4.4e-3 / 2.3e-2, 8.7e-3 / 2.1e-2; control 2.5e-3 / 1.2e-2, 1.7e-3 / 8e-3)
LOGP_MEAN_ATOL, LOGP_MAX_ATOL = 1.5e-2, 8e-2
VALUE_MEAN_ATOL, VALUE_MAX_ATOL = 3e-2, 6e-2
# the three losses: |got - ref| <= RTOL * (|ref| + FLOOR), the accepted cells' form (0.19 of it; control 0.017)
LOSS_RTOL, LOSS_FLOOR = 1e-1, 5e-2
# the gradient's norm, whole (6.2e-2; control 1.2e-2) and leaf by leaf, the worst leaf (0.133, a router; 0.018)
GRAD_NORM_RTOL, GRAD_LEAF_RTOL = 0.25, 0.4
# the norm of every leaf's ``new - old`` in the first step, the worst leaf (0.198, a norm gain; 0.012): between that and 1
MOVED_LEAF_RTOL = 0.6
# the state the call returned against the state it was given, over the sum of the steps' changes (above)
RETURNED_MIN, RETURNED_MAX = 0.5, 1.001
LIMITS = {
    "handed_share": HANDED_MAX, "own_count_mismatch_share": OWN_COUNT_MISMATCH_MAX,
    "logp_mean_abs": LOGP_MEAN_ATOL, "logp_max_abs": LOGP_MAX_ATOL,
    "value_mean_abs": VALUE_MEAN_ATOL, "value_max_abs": VALUE_MAX_ATOL, "loss_worst": 1.0,
    "grad_norm_rel": GRAD_NORM_RTOL, "grad_leaf_worst_rel": GRAD_LEAF_RTOL, "moved_leaf_worst_rel": MOVED_LEAF_RTOL,
    "returned_shortfall": 1.0 - RETURNED_MIN, "returned_excess": RETURNED_MAX - 1.0, "count_mismatch": 0,
}


def leaf_norms(tree) -> Dict[str, float]:
    """{path: norm} of a tree in the reference's layout, on the host."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_leaves_with_path(tree)
    norms = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for _, x in flat])
    return {jax.tree_util.keystr(path): float(v) for (path, _), v in zip(flat, norms)}


def adam_first_step(grads, hyper: Dict[str, Any]):
    """The parameters' change under the run's optimizer at its first step:
    clip to global norm ``max_grad_norm``, then Adam with zero moments
    (``m_hat = g``, ``v_hat = g^2``)."""
    import jax
    import jax.numpy as jnp

    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, hyper["max_grad_norm"] / (norm + 1e-6)) if hyper["max_grad_norm"] > 0 else 1.0
    return jax.tree_util.tree_map(
        lambda g: -hyper["learning_rate"] * (g * scale) / (jnp.abs(g * scale) + hyper["adam_eps"]), grads)


def _ref_episodes(data: Dict[str, Any], ids, hyper: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The reference's view of the minibatch's episodes: GAE per episode, the
    advantages normalised over the minibatch as the run does."""
    import numpy as np

    eps = []
    for e in ids:
        actions = data["actions"][:, e]
        n_blocks = actions.shape[0] // hyper["block"]
        order = actions[:, 0].reshape(n_blocks, hyper["block"])
        response = np.zeros(actions.shape[0], np.int64)
        at = (np.arange(actions.shape[0]) // hyper["block"]) * hyper["block"] + actions[:, 0]
        response[at] = actions[:, 1]
        returns, adv = reference.gae(data["rewards"][:, e, 0], data["values"][:, e, 0], data["dones"][:, e, 0], 0.0,
                                     hyper["gamma"], hyper["gae_lambda"])
        eps.append({"prompt": data["prompt"][0, e], "response": response, "order": order, "returns": returns,
                    "advantages": adv, "old_logp": data["logprobs"][:, e, 0], "old_values": data["values"][:, e, 0]})
    if hyper["normalize_advantages"]:
        adv = np.stack([ep["advantages"] for ep in eps])
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        for ep, a in zip(eps, adv):
            ep["advantages"] = a.astype(np.float32)
    return eps


def reference_step(rparams, episodes: List[Dict[str, Any]], model_cfg: Dict[str, Any], hyper: Dict[str, Any],
                   top_i=None) -> Dict[str, Any]:
    """What one minibatch step must produce, by the plain reference: one
    episode at a time, gradients averaged over the episodes.  ``top_i``
    (layers, episodes, N, k): the program's routing choice, handed over where
    the reference's own gap is under ``ROUTE_MARGIN``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # The reference's layer is compiled once and serves every layer and episode (one key-value head's
    # attention and one expert are rematerialised inside it, the layer itself in the backward pass):
    # compiled whole, the f32 reference is a 1 GB program and 240 s of compile; run op by op it took
    # 100 s an episode on the chip's host and, without the layer's remat, ran out of memory beside
    # three trees of parameters and gradients (my chip runs, PR 26).
    transformed = {}

    def wrap(name, f):
        # the functions of one name differ only in what they close over (the configuration and this
        # very `wrap`), which is the same for every call of this step
        if name not in transformed:
            transformed[name] = jax.jit(jax.checkpoint(f)) if name == "layer" else jax.checkpoint(f)
        return transformed[name]

    def step(rparams, packed, targets, ids):
        forced = None if ids is None else [(ids[i], ROUTE_MARGIN) for i in range(ids.shape[0])]
        (_, out), grads = jax.value_and_grad(
            lambda p: reference.ppo_loss_packed(p, packed, targets, model_cfg, hyper, forced, wrap=wrap), has_aux=True,
        )(rparams)
        aux = out.pop("aux")
        out.update({k: jnp.stack([a[k] for a in aux])
                    for k in ("counts", "own_counts", "handed", "differs", "rel_gap", "top_i")})
        return out, grads

    total, outs = None, []
    for b, ep in enumerate(episodes):
        packed = {k: jnp.asarray(v) for k, v in reference.pack(ep, model_cfg).items()}  # packed on the host
        targets = {k: jnp.asarray(ep[k]) for k in reference.TARGETS}
        out, grads = step(rparams, packed, targets, None if top_i is None else jnp.asarray(top_i[:, b]))
        total = grads if total is None else jax.tree_util.tree_map(jnp.add, total, grads)
        outs.append(jax.device_get(out))
        del grads
    grads = jax.tree_util.tree_map(lambda g: g / len(episodes), total)
    del total
    return {
        "logp": np.stack([o["logp"] for o in outs]), "values": np.stack([o["values"] for o in outs]),
        "losses": np.asarray([np.mean([o[k] for o in outs]) for k in ("pg", "vl", "ent")]),
        "grad_norm": float(np.sqrt(sum(v * v for v in leaf_norms(grads).values()))),
        "grad_leaf_norms": leaf_norms(grads), "moved_leaf_norms": leaf_norms(adam_first_step(grads, hyper)),
        "load": sum(o["counts"] for o in outs), "own_load": sum(o["own_counts"] for o in outs),
        **{k: np.stack([o[k] for o in outs], 1) for k in ("handed", "differs", "rel_gap", "top_i")},
    }


def _worst_leaf(got: Dict[str, float], ref: Dict[str, float]):
    """(largest relative difference of a leaf's norm, that leaf); a leaf that
    must not move and did not reads 0."""
    rel = {k: abs(got[k] - ref[k]) / ref[k] if ref[k] > 0 else float(got[k] != 0) for k in ref}
    worst = max(rel, key=rel.get)
    return float(rel[worst]), worst


def compare(got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """Readings of one call of the update (its first minibatch step, and the
    state it returned) against the reference's, each beside its limit in
    ``LIMITS``; the keys outside ``LIMITS`` are not judged."""
    import numpy as np

    dl, dv = np.abs(got["logp"] - ref["logp"]), np.abs(got["values"] - ref["values"])
    loss_err = np.abs(got["losses"] - ref["losses"]) / (LOSS_RTOL * (np.abs(ref["losses"]) + LOSS_FLOOR))
    grad_leaf, grad_at = _worst_leaf(got["grad_leaf_norms"], ref["grad_leaf_norms"])
    moved_leaf, moved_at = _worst_leaf(got["moved_leaf_norms"], ref["moved_leaf_norms"])
    returned = got["returned_change"] / max(sum(got["steps_change"]), 1e-30)
    handed, differs = ref["handed"], ref["differs"]  # (layers, episodes, N)
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
    shapes = flops_sdar.SdarShapes.from_config(ctx.config, ctx.traffic, ctx.tiny)
    with span("setup:build"):
        prog = program_sdar.LmUpdate(cfg)
    mc = prog.model_cfg
    want = {"hidden_size": shapes.hidden, "num_attention_heads": shapes.q_heads, "num_key_value_heads": shapes.kv_heads,
            "head_dim": shapes.head_dim, "num_experts": shapes.router_width, "num_experts_per_tok": shapes.top_k,
            "experts_held": shapes.experts_held, "moe_intermediate_size": shapes.expert_width,
            "num_hidden_layers": shapes.layers, "vocab_size": shapes.vocab, "block_length": shapes.block,
            "denoise_steps": shapes.steps}
    differs = {k: (mc[k], v) for k, v in want.items() if mc[k] != v}
    require(not differs, f"the program's model differs from the configuration file: {differs}")
    require(ctx.tiny or (mc["rope_theta"], mc["rms_norm_eps"], mc["norm_topk_prob"], mc["mask_id"],
                         mc["expert_offset"], str(cfg.fabric.precision)) ==
            (ctx.config["rope_theta"], ctx.config["rms_norm_eps"], ctx.config["norm_topk_prob"],
             ctx.config["mask_id"], ctx.config["expert_offset"], ctx.config["precision"]),
            "rope_theta, rms_norm_eps, norm_topk_prob, mask_id, expert_offset or precision differ from the file")
    n_eps, mb_eps = int(ctx.param("episodes")), int(ctx.param("minibatch_episodes"))
    require((int(cfg.env.num_envs), int(cfg.algo.per_rank_batch_size), int(cfg.algo.update_epochs)) == (n_eps, mb_eps, 1),
            "episodes, minibatch or epochs of the program differ from the traffic mix")
    return prog, shapes


def make_rollout(ctx: Context, prog, shapes):
    """The seeded rollout on the device, held to its numpy reference, with old
    log-probabilities and values from a no-gradient pass at the initial
    weights; also its copy on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_eps, mb_eps = int(ctx.param("episodes")), int(ctx.param("minibatch_episodes"))
    rollout = (ctx.seed, n_eps, shapes.prompt, shapes.response, shapes.block, shapes.vocab - 1)  # ids without [MASK]
    with span("setup:rollout"):
        data = rollout_fill.fill(*rollout)
        problem = rollout_fill.check(jax.device_get(data), *rollout)
    require(not problem, f"the rollout vs its seeded reference: {problem}")
    with span("setup:old_policy"):
        params = prog.fresh_params()[1]
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
    the state it returned (the driver's own subtraction from the initial
    parameters).  Returns ``(got, the initial parameters, the call's metrics)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    params, opt_state = prog.initial_state()
    params, opt_state, metrics, probe = prog.update(params, opt_state, data, key, learning_rate)
    initial = prog.fresh_params()[1]
    returned_change = float(jax.jit(lambda new, old: jnp.sqrt(sum(
        jnp.sum(jnp.square(n.astype(jnp.float32) - o.astype(jnp.float32)))
        for n, o in zip(jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(old)))))(params, initial))
    del params, opt_state
    probe = jax.device_get(probe)
    first = jax.tree_util.tree_map(lambda x: x[0], probe)
    in_layout = lambda tree: {jax.tree_util.keystr(path): float(v) for path, v in  # noqa: E731
                              jax.tree_util.tree_leaves_with_path(program_sdar.reference_params(tree))}
    mb_eps = first["logprobs"].shape[0]
    got = {"episodes": [int(i) for i in first["episodes"]], "logp": first["logprobs"], "values": first["values"],
           "losses": first["losses"], "grad_norm": float(first["grad_norm"]),
           "grad_leaf_norms": in_layout(first["grad_leaf_norms"]), "moved_leaf_norms": in_layout(first["moved_leaf_norms"]),
           "returned_change": returned_change,
           "steps_change": [float(np.sqrt(sum(np.square(v[i]) for v in jax.tree_util.tree_leaves(probe["moved_leaf_norms"]))))
                            for i in range(len(probe["episodes"]))],
           "load": first["load"], "top_i": first["top_i"].reshape(shapes.layers, mb_eps, shapes.packed_positions, shapes.top_k)}
    return got, initial, metrics


def reference_for(prog, shapes, host, got, initial) -> Dict[str, Any]:
    """The plain reference's reading of the compared minibatch step, from the
    same initial parameters in f32."""
    import jax
    import jax.numpy as jnp

    hyper = {**prog.hyper, "block": shapes.block}
    rparams = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), program_sdar.reference_params(initial))
    return reference_step(rparams, _ref_episodes(host, got["episodes"], hyper), prog.model_cfg, hyper, got["top_i"])


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
    note(n_params=prog.n_params, packed_positions_per_step=mb_eps * shapes.packed_positions, frames_per_step=shapes.frames,
         steps_per_call=steps_per_call, needed_tflop_per_step_even_routing=flops_sdar.step_flops(shapes)["total"] / 1e12)

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
    even = flops_sdar.expected_assignments(shapes)
    note(compare_with_reference={
        "episodes": got["episodes"], "readings": readings, "limits": LIMITS, "route_margin": ROUTE_MARGIN,
        "program": {"losses": got["losses"].tolist(), "grad_norm": got["grad_norm"], "steps_change": got["steps_change"],
                    "returned_change": got["returned_change"]},
        "reference": {"losses": ref["losses"].tolist(), "grad_norm": ref["grad_norm"]},
        "load_per_held_expert": got["load"].tolist(), "even_load": even,
        "held_share": float(got["load"].sum() / (shapes.layers * mb_eps * shapes.packed_positions * shapes.top_k)),
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
                       for i in range(shapes.layers)]) / steps_per_call  # per layer, expert and minibatch step
    assignments = float(load.sum(-1).mean())  # to held experts, per layer and step
    dropped = float(losses["MoE/dropped"].sum())
    needed = flops_sdar.step_flops(shapes, assignments)  # the experts' term from the counted assignments
    ctx.evidence.update(
        steps=steps, window_s=window_s, steps_per_s=steps / window_s, frames_per_step=shapes.frames, chips=1,
        device_kind=devices[0].device_kind, steps_per_call=steps_per_call,
        flops_per_step=needed["total"], attention_flops_per_step=needed["attention"], window_compiles=window_compiles,
        programs=ctx.param("programs", {}),
        moe={"load_max_over_mean": float(losses["MoE/load_max_over_mean"].mean()),
             "held_share": float(losses["MoE/held_share"].mean()), "assignments_per_layer_and_step": assignments,
             "expert_flops_per_step": needed["experts"],
             "dropped": dropped},
    )
    note(window={"calls": calls, "steps": steps, "seconds": window_s, "last_policy_loss": last},
         moe={"load_per_layer_expert_step": load.round(1).tolist(), "even_load": even, **ctx.evidence["moe"],
              "router_entropy": float(losses["MoE/router_entropy"].mean())},
         first_call_metrics={k: float(v) for k, v in jax.device_get(first_metrics).items() if not k.startswith("MoE/load_l")},
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
