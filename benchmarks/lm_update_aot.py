#!/usr/bin/env python3
"""The update of a language-model policy, lowered (and on request compiled) for
a described, unattached TPU v5e at a benchmark cell's sizes, without a chip and
without a single full-size array: what the program would hold on the chip
(``compiled.memory_analysis()``), a hash of its lowered text, to hold two
checkouts' programs against each other, and how often it calls each of the
attention's kernels (``attention_forward_kernels``: once a block where the
rematerialised blocks keep the kernel's output, twice where they do not) and
the q/k norm's (``qk_norm_fwd_kernels``: q and k of every block, twice, the
forward and the rematerialised one; ``qk_norm_bwd_kernels`` once).
Compiled, it also counts the routed layers' arrays with a hidden-wide row for
every (token, choice) pair, by the conditionals' branches
(``pair_arrays_short_branches``: none since PR 33, whose short branch reads a
token's held choices only).  ``--rollout`` lowers the kind's fused collector's
rollout in place of the update, at the cell's envs and lengths, for its hash
and its conditionals (the prefill's routed layers have both buffer lengths,
the cached passes one; that one builds the parameters: 2-3 GB on the host).

    JAX_PLATFORMS=cpu python benchmarks/lm_update_aot.py --workload sdar_ep8_train            # hash only
    JAX_PLATFORMS=cpu python benchmarks/lm_update_aot.py --workload joyai_ep_train --compile  # + bytes, ~2-4 min
    JAX_PLATFORMS=cpu python benchmarks/lm_update_aot.py --workload sdar_ep8_loop --rollout   # the collector's hash

Everything is built as ``ppo.main`` builds it (``build_agent``,
``build_ppo_optimizer``, ``make_update_fn``), under ``jax.eval_shape``.  Takes
libtpu's lock: run one at a time, and not beside ``tests/test_ops/test_tpu_compile.py``.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def location_free(text: str) -> str:
    """The lowered text with every Pallas kernel's serialized body (MLIR bytecode, which holds the
    source locations of the kernel's Python) replaced by the hash of its location-free assembly."""
    import base64
    import re

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body_hash(m):
        ctx = ir.Context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True  # the serialized form names a versioned dialect of its own
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))).operation.get_asm(enable_debug_info=False)
        return "kernel:" + hashlib.sha256(asm.encode()).hexdigest()

    return re.sub(r'(?<=\\22body\\22: \\22)([A-Za-z0-9+/=]+)(?=\\22)', body_hash, text)


def kernel_calls(text: str, kernel: str) -> int:
    """Calls of the Pallas kernels whose name holds ``kernel`` in a program's text, every function
    inlined.  Compiled text names a custom call after its kernel; lowered text holds a kernel once, in
    a private function, and the count is that of the call sites that reach it from ``main``."""
    import re

    if text.startswith("HloModule"):
        return len(re.findall(rf"^\s*(?:ROOT )?%?[\w.\-]*{kernel}[\w.\-]* = .*custom-call\(", text, re.M))
    bodies = dict(re.findall(r"func\.func \w+ @(\w+)\((.*?)(?=\n  func\.func |\Z)", text, re.S))
    counts: dict = {}

    def reach(fn: str) -> int:
        if fn not in counts:
            counts[fn] = len(re.findall(rf'kernel_name = "[^"]*{kernel}', bodies[fn])) + sum(
                reach(callee) for callee in re.findall(r"\bcall @(\w+)\(", bodies[fn]))
        return counts[fn]

    return reach("main")


def computation(text: str, name: str) -> str:
    """The body of a compiled program's computation ``name``: its own ops, one a line."""
    body = text[text.index(f"\n%{name} ("):]
    return body[:body.index("\n}\n")]


def pair_arrays(text: str, rows: int, width: int) -> dict:
    """Arrays of ``rows`` x ``width`` that the branches of a compiled program's two-way conditionals
    define, by branch: 0 is taken where the predicate is false (the routed layer's worst-case buffer),
    1 where it is true (its short one)."""
    import re

    found = {"long": 0, "short": 0}
    for branches in re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}", text):
        names = re.findall(r"%([\w.\-]+)", branches)
        if len(names) != 2:
            continue
        for key, name in zip(found, names):
            found[key] += len(re.findall(rf"^\s*(?:ROOT )?%[\w.\-]+ = \w+\[{rows},{width}\]", computation(text, name), re.M))
    return found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True, help="a cell of chipbench/workloads whose driver trains a language-model policy")
    ap.add_argument("--compile", action="store_true", help="compile for the described chip and print its memory analysis")
    ap.add_argument("--rollout", action="store_true", help="lower the fused collector's rollout, not the update (hash only)")
    ap.add_argument("--override", action="append", default=[], help="a further override of the program's configuration")
    ap.add_argument("--text-out", help="write the lowered text here")
    ap.add_argument("--hlo-out", help="with --compile: write the compiled program's text here (scopes in op_name metadata)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import sheeprl_tpu.algos.ppo.ppo as ppo
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.config import compose, instantiate

    def load(*parts):
        with open(os.path.join(ROOT, "chipbench", *parts)) as f:
            return json.load(f)

    workload = load("workloads", args.workload + ".json")
    config, traffic = load("configs", workload["config"] + ".json"), load("traffic", workload["traffic"] + ".json")
    overrides = list(config["overrides"]) + list(traffic.get("overrides", [])) + ["seed=0", "fabric.accelerator=cpu"]
    cfg = compose(config_name="config", overrides=overrides + args.override)
    runtime = instantiate(dict(cfg.fabric))
    runtime.launch()

    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

    if args.rollout:
        from sheeprl_tpu.algos.ppo.lm_policy import language_model_policy
        from sheeprl_tpu.utils.env import make_train_envs

        runtime.seed_everything(0)
        envs = make_train_envs(cfg, runtime, None)
        policy, params = build_agent(runtime, (), False, cfg, envs.single_observation_space)
        collector = language_model_policy(cfg).collector_class(
            envs=envs, module=policy, params=params, cfg=cfg, runtime=runtime, obs_keys=["tokens"],
            total_envs=int(cfg.env.num_envs), world_size=1, aggregator=None)
        text = location_free(collector._rollout.lower(
            *on_chip((collector.params, collector._carry, runtime.next_key(), collector._env_base))).as_text())
        print(json.dumps({"workload": args.workload, "program": type(collector).__name__ + " rollout",
                          "lowered_lines": text.count("\n"), "lowered_sha256": hashlib.sha256(text.encode()).hexdigest(),
                          "conditionals": text.count("stablehlo.case") + text.count("stablehlo.if")}))
        return 0

    built = {}

    def initial_params():
        built["policy"], params = build_agent(runtime, (), False, cfg, None)
        return runtime.to_param_dtype(params)

    params = jax.eval_shape(initial_params)
    policy = built["policy"]
    tx = ppo.build_ppo_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm, runtime.precision)
    opt_state = jax.eval_shape(tx.init, params)
    update = ppo.make_update_fn(runtime, policy, tx, cfg, list(cfg.algo.mlp_keys.encoder))
    n_eps, steps = int(cfg.env.num_envs), int(cfg.algo.rollout_steps)
    per_step = jax.ShapeDtypeStruct((steps, n_eps, 1), jnp.float32)
    data = {"prompt": jax.ShapeDtypeStruct((1, n_eps, int(cfg.env.wrapper.prompt_len)), jnp.int32),
            "actions": jax.ShapeDtypeStruct((steps, n_eps, 2), jnp.int32),
            "logprobs": per_step, "values": per_step, "rewards": per_step, "dones": per_step}
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    t0 = time.perf_counter()
    lowered = update._jitted.lower(*on_chip((params, opt_state, data, {}, key, scalar, scalar, scalar)))
    text = location_free(lowered.as_text())
    out = {"workload": args.workload, "parameters": sum(int(x.size) for x in jax.tree_util.tree_leaves(params)),
           "lowered_lines": text.count("\n"), "lowered_sha256": hashlib.sha256(text.encode()).hexdigest(),
           "lower_s": round(time.perf_counter() - t0, 1)}
    program = text
    if args.text_out:
        with open(args.text_out, "w") as f:
            f.write(text)
    if args.compile:
        t0 = time.perf_counter()
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        program = compiled.as_text()
        if args.hlo_out:
            with open(args.hlo_out, "w") as f:
                f.write(program)
        out.update(compile_s=round(time.perf_counter() - t0, 1), argument_bytes=m.argument_size_in_bytes,
                   output_bytes=m.output_size_in_bytes, alias_bytes=m.alias_size_in_bytes, temp_bytes=m.temp_size_in_bytes,
                   generated_code_bytes=m.generated_code_size_in_bytes,
                   arguments_plus_temporaries_gb=(m.argument_size_in_bytes + m.temp_size_in_bytes) / 1e9,
                   # the compiler's own peak of what is live at once; the temporaries above are summed whole
                   peak_gb=m.peak_memory_in_bytes / 1e9,
                   conditionals=program.count(" conditional("))
        spec = policy.cfg.routed_spec
        positions = policy.layout.length if hasattr(policy, "layout") else int(cfg.env.wrapper.prompt_len) + int(cfg.env.wrapper.response_len)
        pairs = pair_arrays(program, int(cfg.algo.per_rank_batch_size) * positions * spec.top_k, spec.hidden_size)
        out.update(pair_arrays_long_branches=pairs["long"], pair_arrays_short_branches=pairs["short"])
    out.update({f"attention_{name}_kernels": kernel_calls(program, "splash_mqa_" + kernel)
                for name, kernel in (("forward", "fwd"), ("dkv", "dkv"), ("dq", "dq"))})
    out.update({f"qk_norm_{name}_kernels": kernel_calls(program, "qk_norm_rope_" + name) for name in ("fwd", "bwd")})
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
