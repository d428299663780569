"""Every ``pallas_call`` left in ``sheeprl_tpu/ops`` compiles for the chip.

The TPU compiler is installed beside the CPU backend and compiles for a
device that is described and not attached (on-chip-measurement guide §2.3):
what Mosaic would refuse on the chip it refuses here, at no chip time.
Interpret-mode tests cannot see that — two data-plane kernels passed every
one of theirs and compiled for no TPU at all.  Forward and backward, the
DV3-S and DV3-XL widths, f32 and bf16 matmuls.  Nothing runs, so these say
nothing about results or speed.
"""

import glob
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sheeprl_tpu.ops.pallas_gru import gru_cell
from sheeprl_tpu.ops.seq_gru import fits_vmem, gru_sequence

# (hidden, input) of the RSSM's GRU: recurrent_state_size x dense_units
S = (512, 512)
XL = (4096, 1024)
# the widest f32 weight fits_vmem admits (9.8 of its 10 MB): what it lets
# through must be what Mosaic takes
SEQ_BOUND = (640, 640)


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved)
    cc.reset_cache()


def _compiles_with_kernel(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(dims, jnp.float32, sharding=chip) for dims in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("width", [S, XL], ids=["S", "XL"])
def test_gru_cell_compiles_for_v5e(chip, width, dtype, direction):
    hidden, xdim = width
    b = 16

    def fwd(h, x, w, gamma, beta):
        return gru_cell(h, x, w, gamma, beta, 1e-6, True, 8, 512, False, dtype)

    fn = fwd if direction == "fwd" else jax.value_and_grad(lambda *a: fwd(*a).sum(), argnums=(0, 1, 2, 3, 4))
    _compiles_with_kernel(
        chip, fn, (b, hidden), (b, xdim), (hidden + xdim, 3 * hidden), (3 * hidden,), (3 * hidden,)
    )


@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize(
    "width,dtype",
    [(S, jnp.float32), (S, jnp.bfloat16), (SEQ_BOUND, jnp.float32)],
    ids=["S-f32", "S-bf16", "bound-f32"],
)
def test_gru_sequence_compiles_for_v5e(chip, width, dtype, direction):
    hidden, xdim = width
    t, b = 64, 16
    assert fits_vmem(hidden, xdim, dtype)

    def fwd(h0, xs, w, gamma, beta, is_first, init_rec):
        return gru_sequence(h0, xs, w, gamma, beta, is_first, init_rec, 1e-6, False, dtype)

    fn = fwd if direction == "fwd" else jax.value_and_grad(lambda *a: fwd(*a).sum(), argnums=(0, 1, 2, 3, 4, 6))
    _compiles_with_kernel(
        chip,
        fn,
        (b, hidden),
        (t, b, xdim),
        (hidden + xdim, 3 * hidden),
        (3 * hidden,),
        (3 * hidden,),
        (t, b, 1),
        (b, hidden),
    )


def test_gru_sequence_is_not_offered_at_xl_widths():
    """The one-kernel recurrence keeps the weights in VMEM; XL's 126 MB of
    bf16 weights exceed the chip's 128 MiB with the working set (Mosaic:
    "Used 135.48M of 128.00M vmem"), and ``fits_vmem`` keeps callers on the
    per-step path there."""
    assert not fits_vmem(*XL, jnp.bfloat16)
    assert not fits_vmem(*XL, jnp.float32)


def test_no_interpret_default_asks_the_default_backend():
    """Interpret mode is chosen from the lowering platform
    (``jax.lax.platform_dependent``) or an explicit argument, never from
    ``jax.default_backend()`` — which is not where the arrays live (a
    CPU-pinned player, a ``jax.default_device`` scope)."""
    ops_dir = os.path.join(os.path.dirname(__file__), "..", "..", "sheeprl_tpu", "ops")
    sources = glob.glob(os.path.join(ops_dir, "*.py"))
    assert sources
    for path in sources:
        with open(path) as f:
            assert "default_backend" not in f.read(), path
