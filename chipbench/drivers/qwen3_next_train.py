"""Driver ``qwen3_next_train``: PPO minibatch steps of the Qwen3-Next policy
(Gated DeltaNet linear attention and gated full attention mixed 3:1) over a
seeded rollout of whole causal episodes, back to back; no env, no collector.

What ``causal_lm_train`` does for latent attention, for this model: build the
update as ``ppo.main`` builds it, weights from ``--seed``; make the rollout on
the device from the seed and hold it to its numpy reference, with old
log-probabilities and values from a no-gradient pass at the initial weights
(``causal_lm_train.make_rollout``); run ONE update call (the program the window
times, at the timed sizes) and compare what its first minibatch step produced,
and what the call did to the state it returned, with the plain reference
(``reference/qwen3_next.py``: the delta rule token by token) at the published
widths, one episode and one layer at a time (``causal_lm_train.compare``: the
same readings, under this model's limits, with ``afmoe_train.coverage``); start
again from the initial state and warm up.  Window: update calls back to back,
the host at most ``run_ahead`` calls ahead, closed by a fetch of the last
losses.  ``setup_s`` leaves out the reference's own seconds.

The parameters with their optimizer state are most of the chip's memory, so the
initial parameters the driver subtracts from the returned state are kept on
the HOST, and the returned state leaves the device before the reference runs.
"""

from __future__ import annotations

import collections
import itertools
import time
from typing import Any, Dict, List

from chipbench import flops_qwen3_next, program_qwen3_next
from chipbench.drivers.afmoe_train import coverage
from chipbench.drivers.causal_lm_train import _ref_episodes, compare, make_rollout
from chipbench.drivers.sdar_train import adam_first_step, leaf_norms
from chipbench.harness import Context, fetch_losses, note, require, span
from chipbench.program import compose_cfg, recompile_monitor
from chipbench.reference import qwen3_next as reference

# ---- the comparison with the reference (float32, "highest", published widths, the delta rule token by token).
# The program computes in bf16-mixed: f32 parameters, gradients and Adam, bf16 products summed in f32, norms /
# sigmoid / softmax / router / the delta rule in f32.  As for the other causal cells: forward quantities hardly tell
# the program's precision from the next one below it (``fabric.precision=bf16-true``: parameters stored in bf16),
# what parameters kept in bf16 lose is the step, so precision is decided on the STATE THE CALL RETURNED
# (``returned_shortfall``); the other limits hold the mathematics, and stand between the program's readings and
# those of three faults planted at the cell's size (``benchmarks/gdn_fault_readings.py``): no decay (``g = 0``),
# the delta rule's read of the state left out (``additive``: ``S += beta k v^T``) and the linear layers' output
# gate left out (``no_gate``).  A top-10 choice flips where the last probability kept and the first one left
# out differ by less than rounding: the reference takes the program's choice there (``ROUTE_MARGIN``).  Here
# that margin is wider than the other causal cells' 0.02: the 10th and 11th of 512 softmax probabilities lie
# about 4 % apart (order statistics of router logits of std 0.9), a layer's bf16 products move the hidden
# state by about 1 % (a linear layer's mixer 0.7 % mean, 1.0 % at worst, read on the CPU at the published
# widths), and a choice that flips without being handed over replaces an expert at a tenth of the
# weight, moving that position's hidden state by about 14 % and every later layer's choice there at gaps
# up to 11 % (on a v5e, one seed: at 0.02, 17.3 % of choices differed and 14.8 % were handed over;
# the rest cascaded to `count_mismatch` 878, `logp_max_abs` 0.33).  The reference's OWN choice is still held
# to the program's counts everywhere (``own_count_mismatch_share``: 0.0127 there).
# Each limit below stands beside its readings, on a v5e: the largest the program read over three seeds (2500000003,
# 3100000037, 3200000041) | the control, the program at bf16-true (seed 3400000057: not correct by
# ``returned_shortfall`` alone) | additive (seed 3600000071) | no_gate (the same seed).  ``no_decay`` has no reading
# at the cell's size (its run was cut by its own time limit in set-up); under g = 0 the program's ``A_log`` and
# ``dt_bias`` take no gradient at all, so ``grad_leaf_worst_rel`` and ``moved_leaf_worst_rel`` read 1.0 on them
# whatever the size (3.97 and 1.0 at the tiny size).  As in the other causal cells the control's forward and
# gradient numbers read LOWER than the program's (both sides then start from the same rounded weights), so those
# limits stand over the program's largest, with room for seeds not read, and under the faults' readings.
# PERF.md section 4 and chipbench/testdata/qwen3next_ep16_train_readings.json keep them all.
ROUTE_MARGIN = 0.1
HANDED_MAX = 0.4  # 0.172 | 0.143 | 0.732 | 0.944
# by the reference's own choice everywhere, whatever was handed over: 0.0118 (0.0127 on a fourth seed) | 0.0106 |
# 0.0265 | 0.109
OWN_COUNT_MISMATCH_MAX = 0.02
# log-probabilities of the taken tokens (about -9.44 each) and values, mean and worst absolute difference over
# 7,168 cells: 1.62e-2 / 0.123 and 1.61e-2 / 0.105 | 1.34e-2 / 0.095 and 1.34e-2 / 0.081 | 0.123 / 0.977 and
# 0.118 / 0.760 | 0.98 / 5.19 and 0.95 / 4.91
LOGP_MEAN_ATOL, LOGP_MAX_ATOL = 3e-2, 3e-1
VALUE_MEAN_ATOL, VALUE_MAX_ATOL = 3e-2, 3e-1
# the three losses, ``causal_lm_train.compare``'s form (the accepted cells'): 0.088 | 0.027 | 1.13 | 9.3.  The gradient's
# norm, whole: 0.0014 | 0.0086 | 0.025 | 0.78 (it varies tenfold with the seed in the other causal cells: their limit);
# leaf by leaf, the worst of 71 leaves: 0.102 (a linear layer's ``A_log``) | 0.036 | 1.35 | 0.85
GRAD_NORM_RTOL, GRAD_LEAF_RTOL = 0.25, 0.4
MOVED_LEAF_RTOL = 0.1  # every leaf's ``new - old`` in the first step, the worst leaf: 0.041 (0.051 on a fourth seed) | 0.017 | 0.124 | 0.98
# choices that differ after the hand-over, and counts per held expert that differ, over the 4 x 8,192 layer-positions:
# 0 (the widest differing gap 0.098, under the margin) | 0 | 882 | 2,260; 64 (0.2 %) leaves room for a choice that flips
# just past the margin and the later layers' choices it moves at that position
COUNT_MISMATCH_MAX = 64
# the state the call returned against the state it was given, over the sum of the steps' changes: shortfall
# 0.148-0.149 | 0.792 (what parameters kept in bf16 lose is the step) | 0.149 | 0.149; a state returned unchanged
# reads 1, one step of four kept 0.75: between the program's reading and the control's
RETURNED_MIN, RETURNED_MAX = 0.5, 1.001
LIMITS = {
    "handed_share": HANDED_MAX, "own_count_mismatch_share": OWN_COUNT_MISMATCH_MAX,
    "logp_mean_abs": LOGP_MEAN_ATOL, "logp_max_abs": LOGP_MAX_ATOL,
    "value_mean_abs": VALUE_MEAN_ATOL, "value_max_abs": VALUE_MAX_ATOL, "loss_worst": 1.0,
    "grad_norm_rel": GRAD_NORM_RTOL, "grad_leaf_worst_rel": GRAD_LEAF_RTOL, "moved_leaf_worst_rel": MOVED_LEAF_RTOL,
    "returned_shortfall": 1.0 - RETURNED_MIN, "returned_excess": RETURNED_MAX - 1.0, "count_mismatch": COUNT_MISMATCH_MAX,
    # the call's steps (``afmoe_train.coverage``): a step that ran moves the state, and one epoch takes each episode once
    "steps_missing": 0, "episodes_uncovered": 0,
}
LOSSES = ("pg", "vl", "ent")  # the order of the update's probe: policy, value, entropy


def judge(readings: Dict[str, Any]) -> Dict[str, Any]:
    """The readings over their limits: empty for a correct update."""
    return {k: v for k, v in readings.items() if k in LIMITS and not v <= LIMITS[k]}


def reference_step(rparams, episodes: List[Dict[str, Any]], model_cfg: Dict[str, Any], hyper: Dict[str, Any],
                   top_i) -> Dict[str, Any]:
    """What one minibatch step must produce, by the plain reference: one episode at a time, gradients
    averaged over the episodes.  ``top_i`` (layers, episodes, N, k): the program's routing choice, handed
    over where the reference's own gap is under ``ROUTE_MARGIN``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # the reference's two kinds of layer are compiled once each and serve every layer and episode; a block of
    # queries' attention, an expert and the head pass are rematerialised inside, the layer itself in the backward
    transformed = {}

    def wrap(name, f):
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
    moved = jax.tree_util.tree_map(lambda p, step: (p + step) - p, rparams, adam_first_step(grads, hyper))
    return {
        "logp": np.stack([o["logp"] for o in outs]), "values": np.stack([o["values"] for o in outs]),
        "losses": np.asarray([np.mean([o[k] for o in outs]) for k in LOSSES]),
        "grad_norm": float(np.sqrt(sum(v * v for v in leaf_norms(grads).values()))),
        "grad_leaf_norms": leaf_norms(grads), "moved_leaf_norms": leaf_norms(moved),
        "load": sum(o["counts"] for o in outs), "own_load": sum(o["own_counts"] for o in outs),
        **{k: np.stack([o[k] for o in outs], 1) for k in ("handed", "differs", "rel_gap", "top_i")},
    }


def build(ctx: Context):
    """(the update built as ``ppo.main`` builds it, the shapes), the program held to the configuration's
    file number for number."""
    cfg = compose_cfg(ctx.overrides())  # its seed, and so the weights' draw, is --seed
    shapes = flops_qwen3_next.Qwen3NextShapes.from_config(ctx.config, ctx.traffic, ctx.tiny)
    with span("setup:build"):
        prog = program_qwen3_next.CausalLmUpdate(cfg)
    mc = prog.model_cfg
    want = {"hidden_size": shapes.hidden, "num_attention_heads": shapes.heads, "num_key_value_heads": shapes.kv_heads,
            "head_dim": shapes.head_dim, "linear_num_key_heads": shapes.key_heads,
            "linear_num_value_heads": shapes.value_heads, "linear_key_head_dim": shapes.d_k,
            "linear_value_head_dim": shapes.d_v, "linear_conv_kernel_dim": shapes.conv_kernel,
            "full_attention_interval": shapes.interval, "num_hidden_layers": shapes.layers,
            "moe_intermediate_size": shapes.expert_width, "shared_expert_intermediate_size": shapes.shared_width,
            "num_experts": shapes.router_width, "num_experts_per_tok": shapes.top_k, "experts_held": shapes.experts_held,
            "vocab_size": shapes.vocab}
    differs = {k: (mc[k], v) for k, v in want.items() if mc[k] != v}
    require(not differs, f"the program's model differs from the configuration file: {differs}")
    c = ctx.config
    require(ctx.tiny or (mc["rope_theta"], mc["partial_rotary_factor"], mc["rms_norm_eps"], mc["norm_topk_prob"],
                         mc["expert_offset"], str(cfg.fabric.precision)) ==
            (c["rope_theta"], c["partial_rotary_factor"], c["rms_norm_eps"], c["norm_topk_prob"], c["expert_offset"],
             c["precision"]),
            "rope_theta, partial_rotary_factor, rms_norm_eps, norm_topk_prob, expert_offset or precision differ from the file")
    require(c["rope_scaling"] is None and c["decoder_sparse_step"] == 1 and c["mlp_only_layers"] == []
            and not c["tie_word_embeddings"],
            "the program rotates without scaling, routes every layer and unties the head: the file says otherwise")
    n_eps, mb_eps = int(ctx.param("episodes")), int(ctx.param("minibatch_episodes"))
    require((int(cfg.env.num_envs), int(cfg.algo.per_rank_batch_size), int(cfg.algo.update_epochs)) == (n_eps, mb_eps, 1),
            "episodes, minibatch or epochs of the program differ from the traffic mix")
    require((int(cfg.env.wrapper.prompt_len), int(cfg.env.wrapper.response_len)) == (shapes.prompt, shapes.response),
            "prompt or response length of the program differ from the traffic mix")
    return prog, shapes


def compared_call(prog, shapes, data, key):
    """One call of the timed update from the initial state: what its first minibatch step produced (the
    update's own probe) and what the call did to the state it returned (the driver's own subtraction, on
    the host, from the initial parameters fetched before the call).  Returns ``(got, the initial
    parameters on the host, the call's metrics)``."""
    import jax
    import numpy as np

    params, opt_state = prog.initial_state()
    initial = jax.device_get(params)
    params, opt_state, metrics, probe = prog.update(params, opt_state, data, key)
    returned = jax.device_get(params)
    del params, opt_state
    returned_change = float(np.sqrt(sum(
        float(np.sum(np.square(np.asarray(n, np.float32) - np.asarray(o, np.float32)), dtype=np.float64))
        for n, o in zip(jax.tree_util.tree_leaves(returned), jax.tree_util.tree_leaves(initial)))))
    del returned
    probe, metrics = jax.device_get((probe, metrics))
    first = jax.tree_util.tree_map(lambda x: x[0], probe)
    in_layout = lambda tree: {jax.tree_util.keystr(path): float(v) for path, v in  # noqa: E731
                              jax.tree_util.tree_leaves_with_path(program_qwen3_next.reference_params(tree))}
    mb_eps = first["logprobs"].shape[0]
    got = {"episodes": [int(i) for i in first["episodes"]], "logp": first["logprobs"], "values": first["values"],
           "losses": first["losses"], "grad_norm": float(first["grad_norm"]),
           "grad_leaf_norms": in_layout(first["grad_leaf_norms"]), "moved_leaf_norms": in_layout(first["moved_leaf_norms"]),
           "returned_change": returned_change, "steps_episodes": probe["episodes"].tolist(),
           "steps_change": [float(np.sqrt(sum(np.square(v[i]) for v in jax.tree_util.tree_leaves(probe["moved_leaf_norms"]))))
                            for i in range(len(probe["episodes"]))],
           "load": first["load"], "top_i": first["top_i"].reshape(shapes.routed_blocks, mb_eps, shapes.positions, shapes.top_k)}
    return got, initial, metrics


def reference_for(prog, host, got, initial) -> Dict[str, Any]:
    """The plain reference's reading of the compared minibatch step, from the same initial parameters in f32."""
    import jax
    import jax.numpy as jnp

    rparams = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), program_qwen3_next.reference_params(initial))
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
    even_flops = flops_qwen3_next.step_flops(shapes)
    gdn = flops_qwen3_next.gdn_step(shapes)
    note(n_params=prog.n_params, positions_per_step=mb_eps * shapes.positions, frames_per_step=shapes.frames,
         steps_per_call=steps_per_call, needed_tflop_per_step_even_routing=even_flops["total"] / 1e12,
         tflop_per_step_by_part_even_routing={k: v / 1e12 for k, v in even_flops.items()},
         gdn_scan_per_step={"tflop": gdn["flops"] / 1e12, "gb": gdn["bytes"] / 1e9})

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
        ref = reference_for(prog, host, got, initial)
        del initial
    readings = {**compare(got, ref), **coverage(got, steps_per_call, n_eps)}
    reference_s = time.perf_counter() - t_reference  # the reference's own seconds: not the system's set-up
    even = flops_qwen3_next.expected_assignments(shapes)
    note(compare_with_reference={
        "episodes": got["episodes"], "readings": readings, "limits": LIMITS, "route_margin": ROUTE_MARGIN,
        "program": {"losses": got["losses"].tolist(), "grad_norm": got["grad_norm"], "steps_change": got["steps_change"],
                    "steps_episodes": got["steps_episodes"], "returned_change": got["returned_change"]},
        "reference": {"losses": ref["losses"].tolist(), "grad_norm": ref["grad_norm"]},
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
    needed = flops_qwen3_next.step_flops(shapes, assignments)  # the experts' term from the counted assignments
    ctx.evidence.update(
        steps=steps, window_s=window_s, steps_per_s=steps / window_s, frames_per_step=shapes.frames, chips=1,
        device_kind=devices[0].device_kind, steps_per_call=steps_per_call,
        flops_per_step=needed["total"], gdn_step=gdn, window_compiles=window_compiles,
        programs=ctx.param("programs", {}),
        moe={"load_max_over_mean": float(losses["MoE/load_max_over_mean"].mean()),
             "held_share": float(losses["MoE/held_share"].mean()), "assignments_per_layer_and_step": assignments,
             "expert_flops_per_step": needed["experts"], "dropped": dropped},
    )
    note(window={"calls": calls, "steps": steps, "seconds": window_s, "last_policy_loss": last},
         moe={"load_per_block_expert_step": load.round(1).tolist(), "even_load": even, **ctx.evidence["moe"],
              "even_held_share": shapes.experts_held / shapes.router_width,
              "short_buffer_share": float(losses["MoE/short_buffer_share"].mean()),
              "router_entropy": float(losses["MoE/router_entropy"].mean())},
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
