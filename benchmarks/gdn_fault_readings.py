"""The upper readings behind the limits of ``chipbench/drivers/qwen3_next_train.py``'s
comparison (PERF.md section 4): the cell ``qwen3next_ep16_train`` run as it is,
at its own sizes, but with one fault planted in the program's Gated DeltaNet
layers from outside, held to the plain reference by the driver's own
``compare`` and ``judge``.  Each has to come out as not correct: the run ends
before its window with ``"correct": false`` and exit code 1, and the earlier
line ``compare_with_reference`` holds the readings.

- ``--fault no_decay``: the delta rule runs with ``g = 0``: the state never
  decays (``A_log`` and ``dt_bias`` then take no gradient).
- ``--fault additive``: the delta term dropped: ``S_t = e^{g_t} S_{t-1} +
  beta_t k_t v_t^T`` (gated linear attention, no read of the state before the
  write), token by token, checkpointed every 256 positions.
- ``--fault no_gate``: the linear layers' output gate left out: ``y =
  RMSNorm(o)`` without ``* SiLU(z)``.

Run as the cell itself, on the chip:
``python benchmarks/gdn_fault_readings.py --fault additive --workload qwen3next_ep16_train --seed <n> --seconds 4``
(``--tiny`` rehearses any of them on the CPU)."""
import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "chipbench"))

BLOCK = 256


@contextlib.contextmanager
def _rule(replacement):
    from sheeprl_tpu.models import qwen3_next

    sound = qwen3_next.gated_delta_rule
    qwen3_next.gated_delta_rule = replacement
    try:
        yield
    finally:
        qwen3_next.gated_delta_rule = sound


def no_decay():
    import jax.numpy as jnp

    from sheeprl_tpu.ops.gated_delta_rule import gated_delta_rule

    return _rule(lambda q, k, v, g, beta, state=None: gated_delta_rule(q, k, v, jnp.zeros_like(g), beta, state))


def additive_rule(q, k, v, g, beta, state=None):
    """``S_t = e^{g_t} S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``: the op's contract without its delta term."""
    import jax
    import jax.numpy as jnp

    b, n, hk, dk = q.shape
    hv, dv = v.shape[2:]
    q, k = (jnp.repeat(x.astype(jnp.float32), hv // hk, axis=2) for x in (q, k))
    state = jnp.zeros((b, hv, dk, dv), jnp.float32) if state is None else state.astype(jnp.float32)
    pad = -n % BLOCK
    xs = [jnp.pad(x.astype(jnp.float32), [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2)) for x in (q, k, v, g, beta)]
    xs = [jnp.moveaxis(x, 1, 0).reshape(-1, BLOCK, *x.shape[:1], *x.shape[2:]) for x in xs]

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None] + b_t[..., None, None] * k_t[..., :, None] * v_t[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s)

    with jax.default_matmul_precision("highest"):
        state, o = jax.lax.scan(jax.checkpoint(lambda s, x: jax.lax.scan(token, s, x)), state, tuple(xs))
    return jnp.moveaxis(o.reshape(-1, b, hv, dv)[:n], 0, 1), state


def additive():
    return _rule(additive_rule)


@contextlib.contextmanager
def no_gate():
    import jax.numpy as jnp

    from sheeprl_tpu.models import qwen3_next
    from sheeprl_tpu.models.sdar_moe import rms_norm

    gated = qwen3_next.GatedDeltaNet._out

    def out(self, o, z):
        dt = self.dtype
        y = rms_norm(o, self.norm, self.cfg.rms_norm_eps)
        return (y.reshape(*y.shape[:-2], -1).astype(dt) @ self.w_out.astype(dt)).astype(jnp.float32)

    qwen3_next.GatedDeltaNet._out = out
    try:
        yield
    finally:
        qwen3_next.GatedDeltaNet._out = gated


FAULTS = {"no_decay": no_decay, "additive": additive, "no_gate": no_gate}


def main(argv):
    argv = list(argv)
    fault = argv.pop(argv.index("--fault") + 1)
    argv.remove("--fault")
    if fault not in FAULTS:
        raise SystemExit(f"--fault is one of {sorted(FAULTS)}, not {fault}")
    import run as bench_run  # chipbench/run.py

    with FAULTS[fault]():
        return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
