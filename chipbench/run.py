#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--workload X`` reads ``chipbench/workloads/X.json``, which names a
configuration (``configs/<config>.json``), a traffic mix
(``traffic/<traffic>.json``, which names its driver,
``drivers/<driver>.py``) and the per-layer metrics it reports
(``layer_metrics/<name>.py``).  Nothing here branches on a name.

The last line of standard output is the result and nothing else.  Without a
TPU, or with fewer chips than the cell asks for, the exit code is 2 and no
result is printed.  ``--tiny`` rehearses the same code on the CPU at the
configuration's ``tiny_overrides``: it prints ``"correct": false`` and is
never a result.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse
import importlib
import json
import os
import shutil
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal at tiny widths; never a result")
    args = ap.parse_args(argv)
    # numpy's legacy seeding, which the program calls, takes 32 bits; any --seed maps into 31
    args.seed %= 2**31

    if not os.path.isdir(os.path.join(ROOT, "sheeprl_tpu")):
        print(f"chipbench: the program (sheeprl_tpu/) is not beside {HERE}", file=sys.stderr)
        return 3
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import harness

    workload = harness.load_json("workloads", args.workload + ".json")
    config = harness.load_json("configs", workload["config"] + ".json")
    traffic = harness.load_json("traffic", workload["traffic"] + ".json")
    chips = int(workload["chips"])

    # the compile cache: where JAX_COMPILATION_CACHE_DIR says, else a fixed
    # path in the checkout; every program is kept, however small or quick, so
    # that only a cell's first run compiles.  Set in the environment, before
    # jax is imported, so that the program's own setter and any child follow.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(harness.OUT, "jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={chips}"
        ).strip()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.tiny:
        print(f"chipbench: no TPU (JAX found {devices[0].platform}); nothing measured", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"chipbench: the cell needs {chips} chips, JAX found {len(devices)}", file=sys.stderr)
        return 2

    ctx = harness.Context(
        name=args.workload, workload=workload, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), tiny=args.tiny, t_process_start=T_PROCESS_START,
        trace_dir=os.path.join(harness.OUT, "trace", args.workload),
        run_dir=os.path.join(harness.OUT, "runs"),
    )
    harness.note(
        workload=args.workload, config=workload["config"], traffic=workload["traffic"], driver=traffic["driver"],
        seed=args.seed, seconds=args.seconds, trace=args.trace, tiny=args.tiny,
        device={"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)},
        compilation_cache_dir=os.environ["JAX_COMPILATION_CACHE_DIR"],
    )

    if args.trace:  # a run that never opens its window must not find an earlier run's trace
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    driver = importlib.import_module("chipbench.drivers." + traffic["driver"])
    correct, why = True, ""
    result = {"attempted": 0, "failed": 0, "end_to_end": {}, "setup_s": None}
    try:
        result.update(driver.run(ctx))
    except harness.Incorrect as e:
        correct, why = False, str(e)
    except Exception as e:  # the boundary: a run that raised is an incorrect run, with its traceback
        traceback.print_exc()
        correct, why = False, f"{type(e).__name__}: {e}"

    metrics = {}
    device = harness.device_report(chips)
    out = {"correct": correct, "attempted": result["attempted"], "failed": result["failed"]}
    if args.trace:
        from chipbench import trace_reduce

        try:
            summary, table = trace_reduce.reduce_dir(ctx.trace_dir)
        except Exception as e:  # no trace, no device plane, or one the reduction cannot read
            if not isinstance(e, (FileNotFoundError, RuntimeError)):
                traceback.print_exc()
            correct, why = False, why or f"trace: {type(e).__name__}: {e}"
            summary = None
        if summary is not None:
            ctx.evidence["trace"] = summary
            device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
            out["breakdown"] = {"device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"]}
            keep = os.environ.get("CHIPBENCH_KEEP_EVENTS")
            if keep:
                trace_reduce.save_events(table, keep)
            harness.note(
                trace={k: summary[k] for k in ("window_s", "busy_s", "idle_share", "idle_share_worst")},
                programs=summary["programs"], program_top_ops=summary["program_top_ops"],
            )
        for name in workload["layer_metrics"]:
            reader = importlib.import_module("chipbench.layer_metrics." + name)
            try:
                value = reader.read(ctx.evidence)
            except KeyError as e:  # e.g. a device kind the table of peaks does not hold
                correct, why, value = False, why or f"{name}: {e}", None
            if value is not None:
                metrics[name] = {"value": value, "unit": reader.UNIT}
    else:
        for name, (value, unit) in result["end_to_end"].items():
            metrics[name] = {"value": value, "unit": unit}
        if result["setup_s"] is not None:
            metrics["setup_s"] = {"value": result["setup_s"], "unit": "s"}
    if why:
        harness.note(incorrect=why)
    if args.tiny:
        correct = False
        harness.note(tiny="a CPU rehearsal at tiny widths: never a result")
    out.update(correct=correct, metrics=metrics, device=device)
    print(json.dumps(out), flush=True)
    return 0 if (correct or args.tiny) else 1


if __name__ == "__main__":
    sys.exit(main())
