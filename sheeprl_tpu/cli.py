"""CLI: run / evaluation / registration entrypoints.

Counterpart of reference sheeprl/cli.py (run:358, run_algorithm:60,
eval_algorithm:202, check_configs:271, resume_from_checkpoint:23,
evaluation:369, registration:408), driven by the in-house hydra-style
composer (no hydra dependency). Overrides are passed exactly like the
reference: ``python sheeprl.py exp=ppo env.num_envs=8 fabric.devices=4``.

There is no ``fabric.launch`` process boundary: under single-controller
SPMD one process per host drives all local devices through the mesh.
"""

from __future__ import annotations

import importlib
import os
import sys
import warnings
from typing import Any, Dict, List, Optional, Sequence

from sheeprl_tpu.config import compose, dotdict
from sheeprl_tpu.config.compose import deep_merge, yaml_load
from sheeprl_tpu.utils.registry import algorithm_registry, evaluation_registry, find_algorithm, find_evaluation


def _app_config(name: str) -> dict:
    """Defaults of an app-level entry config (eval_config.yaml /
    model_manager_config.yaml, reference sheeprl/configs/*.yaml) — the
    reference mounts these via @hydra.main; here they are plain yaml files
    in the package config dir."""
    path = os.path.join(os.path.dirname(__file__), "configs", f"{name}.yaml")
    try:
        with open(path) as f:
            return yaml_load(f.read()) or {}
    except OSError:
        return {}


def _resolve_interp(value, ctx: dict):
    """Resolve the tiny interpolation set the app-level entry configs use
    (``${now:FMT}``, ``${oc.env:VAR}``, ``${key}`` from ``ctx``) — the
    stand-in for the omegaconf resolvers the reference's @hydra.main
    mounting provides.  Unresolvable values (missing env var / ``???``)
    become None so callers fall back to their defaults."""
    if not isinstance(value, str) or value == "???":
        return None if value == "???" else value

    import re
    from datetime import datetime

    unresolved = False

    def sub(m) -> str:
        nonlocal unresolved
        expr = m.group(1)
        if expr.startswith("now:"):
            return datetime.now().strftime(expr[4:])
        if expr.startswith("oc.env:"):
            env = os.getenv(expr[7:])
            if env is None:
                unresolved = True
                return ""
            return env
        if expr in ctx and ctx[expr] is not None:
            return str(ctx[expr])
        unresolved = True
        return ""

    out = re.sub(r"\$\{([^}]+)\}", sub, value)
    return None if unresolved else out


def resume_from_checkpoint(cfg: dotdict) -> dotdict:
    """Merge the checkpoint's config with the current one, keeping the new
    total_steps / learning_starts-style knobs (reference cli.py:23-57)."""
    import yaml

    ckpt_path = cfg.checkpoint.resume_from
    ckpt_dir = os.path.dirname(os.path.dirname(ckpt_path))
    old_cfg_path = os.path.join(ckpt_dir, "config.yaml")
    if not os.path.exists(old_cfg_path):
        old_cfg_path = os.path.join(os.path.dirname(ckpt_path), "config.yaml")
    if not os.path.exists(old_cfg_path):
        raise RuntimeError(f"Cannot find the config file of the checkpoint: {old_cfg_path}")
    with open(old_cfg_path) as f:
        old_cfg = yaml_load(f.read())
    if old_cfg["env"]["id"] != cfg.env.id:
        raise RuntimeError(
            f"This experiment is run with a different environment from the checkpoint: "
            f"{old_cfg['env']['id']} vs {cfg.env.id}"
        )
    if old_cfg["algo"]["name"] != cfg.algo.name:
        raise RuntimeError(
            f"This experiment is run with a different algorithm from the checkpoint: "
            f"{old_cfg['algo']['name']} vs {cfg.algo.name}"
        )
    kept = {
        "total_steps": cfg.algo.total_steps,
        "resume_from": ckpt_path,
        "run_name": cfg.run_name,
        "exp_name": cfg.exp_name,
        "seed": cfg.seed,
    }
    learning_starts = cfg.algo.get("learning_starts")
    merged = dict(old_cfg)
    # checkpoint cadence and metric knobs are OPERATIONAL, not training
    # semantics: they follow the resuming invocation, so a resume chain can
    # e.g. checkpoint more often or fetch metrics less often (amortizing
    # the per-dispatch device sync on high-latency links) than the original
    # run did (deviation from the reference, whose resume pins the old
    # cadence — cli.py:49-57)
    deep_merge(
        merged,
        {
            "checkpoint": {
                "resume_from": ckpt_path,
                "every": cfg.checkpoint.every,
                "keep_last": cfg.checkpoint.keep_last,
                "save_last": cfg.checkpoint.save_last,
                "async_save": cfg.checkpoint.get("async_save", True),
                "sharded": cfg.checkpoint.get("sharded", False),
                "device_digests": cfg.checkpoint.get("device_digests", False),
            },
            # the mesh is a RESTART-TIME choice: sharded checkpoints restore
            # with resharding (resilience/sharded_ckpt.py), so the resuming
            # invocation's fabric section (devices/strategy/mesh_shape) wins
            # over the saved one — a 4x2 run resumes onto 2x4, 8x1 or a
            # single device without the old mesh pinning it
            "fabric": {k: v for k, v in cfg.fabric.items()},
            "metric": {
                "log_every": cfg.metric.log_every,
                "log_level": cfg.metric.log_level,
                "fetch_every": cfg.metric.get("fetch_every", 1),
                "disable_timer": cfg.metric.get("disable_timer", False),
            },
        },
    )
    merged["algo"]["total_steps"] = kept["total_steps"]
    if learning_starts is not None:
        merged["algo"]["learning_starts"] = learning_starts
    merged["run_name"] = kept["run_name"]
    merged["exp_name"] = kept["exp_name"]
    merged["seed"] = kept["seed"]
    return dotdict(merged)


def check_configs(cfg: dotdict) -> None:
    """Config validation (reference cli.py:271-345): strategy whitelist and
    per-algo constraints."""
    from sheeprl_tpu.parallel.mesh import _STRATEGIES

    strategy = str(cfg.fabric.get("strategy", "auto"))
    if strategy not in _STRATEGIES:
        raise ValueError(
            f"Unknown fabric strategy '{strategy}'. The TPU runtime supports: "
            + ", ".join(_STRATEGIES)
        )
    decoupled = False
    try:
        _, _, decoupled = find_algorithm(cfg.algo.name)
    except RuntimeError:
        pass
    if decoupled:
        # reference cli.py:289-332: decoupled algos only run under DDP; here
        # the learner runs on the mesh, so only dp-style layouts qualify
        if strategy == "fsdp":
            raise ValueError(
                f"The '{strategy}' strategy is currently not supported for decoupled "
                "algorithms. Please launch the script with a data-parallel strategy "
                "('python sheeprl.py fabric.strategy=ddp')"
            )
        if cfg.fabric.get("accelerator") == "cpu" and int(cfg.env.num_envs) < 1:
            raise ValueError("Decoupled algorithms need at least one environment")


def _build_runtime(cfg: dotdict):
    from sheeprl_tpu.config import instantiate

    fabric_cfg = dict(cfg.fabric)
    if fabric_cfg.get("accelerator") == "cpu":
        # keep the accelerator backends uninitialized in a CPU run (takes
        # effect while no backend is initialized yet; afterwards the mesh
        # still asks for jax.devices("cpu") explicitly)
        import jax

        jax.config.update("jax_platforms", "cpu")
    runtime = instantiate(fabric_cfg)
    runtime.launch()
    return runtime


def run_algorithm(cfg: dotdict) -> None:
    """Registry lookup + algorithm dispatch (reference cli.py:60-199)."""
    module, entrypoint, decoupled = find_algorithm(cfg.algo.name)
    algo_module = importlib.import_module(f"{module}.{cfg.algo.name}")
    utils_module = importlib.import_module(f"{module}.utils")

    # filter metric aggregator by the algo's known keys (reference cli.py:151-165)
    keys = getattr(utils_module, "AGGREGATOR_KEYS", set())
    if "aggregator" in cfg.metric and "metrics" in cfg.metric.aggregator:
        cfg.metric.aggregator.metrics = dotdict(
            {k: v for k, v in cfg.metric.aggregator.metrics.items() if k in keys}
        )

    from sheeprl_tpu.utils.metric import MetricAggregator
    from sheeprl_tpu.utils.timer import timer

    # set both ways: these are class-level flags, and a previous in-process
    # run (tests, notebooks) may have disabled them
    MetricAggregator.disabled = cfg.metric.log_level == 0
    timer.disabled = cfg.metric.log_level == 0 or bool(cfg.metric.get("disable_timer", False))

    runtime = _build_runtime(cfg)
    entry_fn = getattr(algo_module, entrypoint)

    if cfg.metric.get("profile", False) and runtime.is_global_zero:
        # jax.profiler trace of the whole run (rank 0): the TPU analogue of
        # the reference's missing torch-profiler hook (SURVEY §5.1). Meant
        # for short profiling runs — traces grow with wall-clock. View with
        # tensorboard --logdir <root_dir>/profile.
        import jax

        trace_dir = os.path.join(
            str(cfg.get("root_dir", ".")), str(cfg.get("run_name", "run")), "profile"
        )
        os.makedirs(trace_dir, exist_ok=True)
        with jax.profiler.trace(trace_dir):
            entry_fn(runtime, cfg)
    else:
        entry_fn(runtime, cfg)


def install_stack_dumper(suffix: str = "") -> None:
    """Observability for long headless runs: dump every thread's stack to
    ``SHEEPRL_STACK_DUMP_FILE``(+suffix) every ``SHEEPRL_STACK_DUMP_S``
    seconds, so a slow/stuck loop shows WHERE it sits without gdb/py-spy.
    Decoupled player subprocesses call this too (with a suffix), since the
    parent's dumper cannot see their threads."""
    try:
        stack_dump_s = float(os.environ.get("SHEEPRL_STACK_DUMP_S", 0))
    except ValueError:
        stack_dump_s = 0.0
    if stack_dump_s <= 0:
        return
    # idempotent per-process: repeated run() calls in one interpreter (the
    # bench harness) must neither truncate earlier legs' stack history nor
    # leak the previously registered dump file
    if getattr(install_stack_dumper, "_installed", None) == suffix:
        return
    import faulthandler

    path = os.environ.get("SHEEPRL_STACK_DUMP_FILE", "/tmp/sheeprl_stacks.log") + suffix
    try:
        dump_file = open(path, "a", buffering=1)
    except OSError as e:  # diagnostics must never kill the run
        warnings.warn(f"stack dump disabled, cannot open {path}: {e}")
    else:
        install_stack_dumper._installed = suffix
        faulthandler.dump_traceback_later(
            stack_dump_s, repeat=True, file=dump_file, exit=False
        )


def run(args: Optional[Sequence[str]] = None) -> None:
    """Main training app: ``sheeprl exp=... [overrides...]``.

    ``--profile`` is a convenience flag equivalent to ``metric.profile=True``
    (whole-run jax.profiler trace on rank 0); windowed capture on long runs
    goes through ``metric.profile_every_n`` instead (howto/observability.md).
    """
    install_stack_dumper()
    overrides = list(args if args is not None else sys.argv[1:])
    if "--profile" in overrides:
        overrides = [o for o in overrides if o != "--profile"] + ["metric.profile=True"]
    cfg = compose(config_name="config", overrides=overrides)
    if cfg.get("num_threads"):
        os.environ.setdefault("XLA_FLAGS", "")
    from sheeprl_tpu.utils.utils import print_config

    # fault-injection harness (howto/resilience.md): cfg.faults rides the
    # env var so spawned decoupled children inherit the armed sites
    if cfg.get("faults"):
        os.environ["SHEEPRL_FAULTS"] = str(cfg.faults)
    if cfg.checkpoint.resume_from:
        from sheeprl_tpu.resilience import resolve_auto_resume

        resolve_auto_resume(cfg)
    if cfg.checkpoint.resume_from:
        cfg = resume_from_checkpoint(cfg)
    check_configs(cfg)
    print_config(cfg)
    run_algorithm(cfg)


def eval_algorithm(cfg: dotdict) -> None:
    """Load checkpoint + dispatch registered evaluation (reference cli.py:202)."""
    from sheeprl_tpu.utils.callback import load_checkpoint

    state = load_checkpoint(cfg.checkpoint_path)
    module, entrypoint = find_evaluation(cfg.algo.name)
    eval_module = importlib.import_module(f"{module}.evaluate")
    eval_fn = getattr(eval_module, entrypoint)
    runtime = _build_runtime(cfg)
    eval_fn(runtime, cfg, state)


def evaluation(args: Optional[Sequence[str]] = None) -> None:
    """Evaluation app: ``sheeprl-eval checkpoint_path=... [overrides...]``.

    Loads the run config saved next to the checkpoint, then overrides
    env/fabric for single-device evaluation (reference cli.py:369-405).
    """
    overrides = list(args if args is not None else sys.argv[1:])
    kv = dict(o.split("=", 1) for o in overrides if "=" in o)
    ckpt_path = kv.get("checkpoint_path")
    if not ckpt_path:
        raise ValueError("You must specify `checkpoint_path=...`")
    ckpt_dir = os.path.dirname(os.path.dirname(os.path.abspath(ckpt_path)))
    cfg_path = os.path.join(ckpt_dir, "config.yaml")
    if not os.path.exists(cfg_path):
        raise RuntimeError(f"Cannot find the config file of the checkpoint: {cfg_path}")
    with open(cfg_path) as f:
        run_cfg = dotdict(yaml_load(f.read()))
    app_defaults = _app_config("eval_config")
    capture_video = yaml_load(
        kv.get("env.capture_video", str(app_defaults.get("env", {}).get("capture_video", True)))
    )
    default_seed = app_defaults.get("seed")
    seed = int(kv.get("seed", run_cfg.get("seed", 42 if default_seed is None else default_seed)))
    run_cfg["env"]["capture_video"] = bool(capture_video)
    run_cfg["env"]["num_envs"] = 1
    run_cfg["fabric"] = dotdict(
        {
            "_target_": "sheeprl_tpu.parallel.MeshRuntime",
            "devices": 1,
            "num_nodes": 1,
            "strategy": "auto",
            "accelerator": kv.get(
                "fabric.accelerator",
                app_defaults.get("fabric", {}).get("accelerator")
                or run_cfg["fabric"].get("accelerator", "auto"),
            ),
            "precision": run_cfg["fabric"].get("precision", "32-true"),
        }
    )
    run_cfg["seed"] = seed
    run_cfg["checkpoint_path"] = os.path.abspath(ckpt_path)
    run_cfg["run_name"] = os.path.join(str(run_cfg.get("run_name", "run")), "evaluation")
    cfg = dotdict(run_cfg)
    eval_algorithm(cfg)


def registration(args: Optional[Sequence[str]] = None) -> None:
    """Model-manager registration app:
    ``sheeprl-registration checkpoint_path=... [model_manager overrides...]``
    (reference cli.py:408-448). Requires the optional mlflow backend.

    Loads the run config saved next to the checkpoint, merges any
    ``model_manager.*`` overrides, then logs + registers the configured
    MODELS_TO_REGISTER param trees from the checkpoint state."""
    from sheeprl_tpu.utils.imports import _IS_MLFLOW_AVAILABLE

    if not _IS_MLFLOW_AVAILABLE:
        raise ModuleNotFoundError(
            "mlflow is not installed in this environment; the model-manager registration app "
            "requires it (`pip install mlflow`)"
        )
    overrides = list(args if args is not None else sys.argv[1:])
    kv = dict(o.split("=", 1) for o in overrides if "=" in o)
    ckpt_path = kv.pop("checkpoint_path", None)
    if not ckpt_path:
        raise ValueError("You must specify `checkpoint_path=...`")
    ckpt_dir = os.path.dirname(os.path.dirname(os.path.abspath(ckpt_path)))
    cfg_path = os.path.join(ckpt_dir, "config.yaml")
    if not os.path.exists(cfg_path):
        raise RuntimeError(f"Cannot find the config file of the checkpoint: {cfg_path}")
    with open(cfg_path) as f:
        run_cfg = dotdict(yaml_load(f.read()))
    # apply model_manager / tracking overrides on the saved config
    for key, value in kv.items():
        node = run_cfg
        parts = key.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, dotdict({}))
        node[parts[-1]] = yaml_load(value)
    run_cfg["fabric"] = dotdict(
        {
            "_target_": "sheeprl_tpu.parallel.MeshRuntime",
            "devices": 1,
            "num_nodes": 1,
            "strategy": "auto",
            "accelerator": "cpu",
            "precision": run_cfg["fabric"].get("precision", "32-true"),
        }
    )
    cfg = dotdict(run_cfg)

    from sheeprl_tpu.utils.callback import load_checkpoint
    from sheeprl_tpu.utils.mlflow import register_model_from_checkpoint

    # run/experiment naming + tracking uri defaults from the registration
    # app's entry config (reference sheeprl/configs/model_manager_config.yaml);
    # explicit run.name= / experiment.name= / tracking_uri= overrides win
    app_defaults = _app_config("model_manager_config")
    ctx = {"exp_name": run_cfg.get("exp_name")}
    run_name = run_cfg.get("run", {}).get("name") or _resolve_interp(
        (app_defaults.get("run") or {}).get("name"), ctx
    )
    experiment_name = run_cfg.get("experiment", {}).get("name") or _resolve_interp(
        (app_defaults.get("experiment") or {}).get("name"), ctx
    )
    tracking_uri = run_cfg.get("tracking_uri") or _resolve_interp(
        app_defaults.get("tracking_uri"), ctx
    )

    state = load_checkpoint(os.path.abspath(ckpt_path))
    runtime = _build_runtime(cfg)
    register_model_from_checkpoint(
        runtime,
        cfg,
        state,
        run_name=run_name,
        experiment_name=experiment_name,
        tracking_uri=tracking_uri,
    )
