"""The two controls behind the limits of ``chipbench/drivers/ppo_loop.py``'s
comparison (PERF.md section 4): the cell run as it is, but with one fault
planted from outside the program, held to the plain reference by the driver's
own ``compare`` and ``judge``.  Each has to come out as not correct: the run
ends with ``"correct": false`` and exit code 1, and the earlier line
``compare_with_reference`` holds the readings.

- ``--control bf16_true``: the program in the nearest precision below the
  configuration's, ``fabric.precision=bf16-true`` (parameters stored in bf16):
  ``benchmarks/sdar_bf16_reading.py``, which this calls.
- ``--control cache_shift``: the collector's cache write lands one update's
  length late (the write's index, shifted while the rollout is traced; nothing
  else changes): the block-diffusion collector writes every finished block's
  keys and values one block late, the causal one every token's latent and
  rotary key one token late (a pass then misses its own token and sees an
  empty place at the first response position).

Run as the cell itself, on the chip:
``python benchmarks/ppo_loop_controls.py --control cache_shift --workload sdar_ep8_loop --seed <n> --seconds 4``
(``--tiny`` rehearses either on the CPU)."""
import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "chipbench"))

# the modules whose one use of ``dynamic_update_slice_in_dim`` is collection's cache write: the block-diffusion
# collector's own, and the causal model's cached attention (``LatentAttention.cached``, which collection alone runs)
CACHE_WRITERS = ("sheeprl_tpu.envs.jax.collect", "sheeprl_tpu.models.mla_moe")


@contextlib.contextmanager
def cache_written_one_block_late():
    """``jax.lax.dynamic_update_slice_in_dim`` as ``CACHE_WRITERS`` call it
    lands one update's length later."""
    import jax

    inner = jax.lax.dynamic_update_slice_in_dim

    def shifted(operand, update, start_index, axis):
        if sys._getframe(1).f_globals.get("__name__") in CACHE_WRITERS:
            start_index = start_index + update.shape[axis]
        return inner(operand, update, start_index, axis)

    jax.lax.dynamic_update_slice_in_dim = shifted
    try:
        yield
    finally:
        jax.lax.dynamic_update_slice_in_dim = inner


def main(argv):
    argv = list(argv)
    control = argv.pop(argv.index("--control") + 1)
    argv.remove("--control")
    if control == "bf16_true":  # the script that gives sdar_train its second reading names no cell
        import sdar_bf16_reading

        sys.argv = sys.argv[:1] + argv
        return sdar_bf16_reading.main()
    if control != "cache_shift":
        raise SystemExit(f"--control is bf16_true or cache_shift, not {control}")
    import run as bench_run  # chipbench/run.py

    with cache_written_one_block_late():
        return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
