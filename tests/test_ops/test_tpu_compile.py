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


PAIR_ROWS = "[{},2048]"  # an array of the routed layer with a 2,048-wide row a (token, choice) pair


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


# ---- the language-model policy's two device paths at the published widths (one v5e's share:
# 3 packed episodes of 5,632 positions, 32 / 4 heads of 128; 16 experts of 2048 x 768 held of 128)
@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize("prompt_len,response_len", [(512, 1024), (508, 1000)], ids=["whole_tiles", "padded"])
def test_block_diffusion_attention_compiles_for_v5e(chip, prompt_len, response_len, direction):
    from sheeprl_tpu.models.sdar_moe import EpisodeLayout
    from sheeprl_tpu.ops.block_sparse_attention import block_sparse_flash_attention

    layout = EpisodeLayout(prompt_len, response_len, 4, 4)  # 5,632 positions = 11 tiles of 512; 5,508 are padded to them

    def fwd(q, k, v):
        return block_sparse_flash_attention(q, k, v, layout.mask, block_size=512).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((3, layout.length, 32, 128), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((3, layout.length, 4, 128), jnp.bfloat16, sharding=chip)
    fn = fwd if direction == "fwd" else jax.grad(fwd, argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    # blocked: far from the 3 x 32 x 5632^2 x 4 bytes = 12 GB a materialised score matrix would take
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9
    assert "tpu_custom_call" in compiled.as_text()


# temporaries of the layer with ONE buffer length, the worst case (16 of 128 held; AOT here, PR 29): what two lengths may cost
ONE_LENGTH_TEMP_BYTES = {"fwd": 1_264_930_304, "grad": 2_220_866_560}


@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize("held", [16, 128], ids=["one_chip_of_8", "all_held"])
def test_routed_experts_compile_for_v5e(chip, held, direction):
    import re

    from benchmarks.lm_update_aot import computation
    from sheeprl_tpu.models.sdar_moe import RoutedExperts, SdarConfig

    cfg = SdarConfig(num_hidden_layers=1, experts_held=held, vocab_size=18992, mask_id=18991)
    layer = RoutedExperts(cfg, jnp.bfloat16)
    m = jax.ShapeDtypeStruct((16896, 2048), jnp.float32, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), jnp.zeros((8, 2048), jnp.float32)),
    )

    def fwd(p, m):
        y, aux = layer.apply(p, m)
        return y.sum(), aux["load"]

    fn = fwd if direction == "fwd" else jax.grad(lambda p, m: fwd(p, m)[0], argnums=(0, 1))
    compiled = jax.jit(fn).lower(params, m).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the grouped products run as the TPU's ragged-dot kernel
    conditionals = re.findall(r" conditional\(.*", text)
    if held == cfg.num_experts:  # the short buffer would be the worst case: one length, no conditional
        assert not conditionals
        return
    # two buffer lengths (50,688 rows when the counted load fits, else 135,168), the kernel in both
    assert len(conditionals) == 1
    branches = re.findall(r"%([\w.]+)", re.search(r"branch_computations=\{([^}]*)\}", conditionals[0]).group(1))
    assert len(branches) == 2
    bodies = [computation(text, name) for name in branches]
    assert all("tpu_custom_call" in body for body in bodies), branches
    # a row for every (token, choice) pair only where the worst-case buffer is taken (branch 0): the short
    # branch's token side reads 4 rows a token (PR 33; until then 1 such array forward, 3 in the gradient)
    assert PAIR_ROWS.format(16896 * 8) in bodies[0] and PAIR_ROWS.format(16896 * 8) not in bodies[1]
    # the short branch writes no zeros the size of the long one's residuals: no more temporaries than one length took
    assert compiled.memory_analysis().temp_size_in_bytes < 1.05 * ONE_LENGTH_TEMP_BYTES[direction]


# ---- the causal language-model policy's device paths at the published widths (one v5e's share of 16: one
# episode of 8,192 positions, 32 heads that share nothing, q / k 192 wide and v 128; 16 experts held of 256)
@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize("length", [8192, 8000], ids=["whole_tiles", "padded"])
def test_latent_attention_kernel_compiles_for_v5e(chip, length, direction):
    """Queries and keys go to the kernel 192 wide, unpadded in memory (``_head_width``), values 128."""
    from sheeprl_tpu.ops.block_sparse_attention import SegmentMask, block_sparse_flash_attention

    mask = SegmentMask.causal(length, length)

    def fwd(q, k, v):
        return block_sparse_flash_attention(q, k, v, mask, block_size=512).astype(jnp.float32).sum()

    qk = jax.ShapeDtypeStruct((1, length, 32, 192), jnp.bfloat16, sharding=chip)
    v = jax.ShapeDtypeStruct((1, length, 32, 128), jnp.bfloat16, sharding=chip)
    fn = fwd if direction == "fwd" else jax.grad(fwd, argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(qk, qk, v).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "bf16[32,8192,192]" in text and "bf16[32,8192,256]" not in text
    # blocked: far from the 32 x 8192^2 x 4 bytes = 8.6 GB a materialised score matrix would take
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("shape", ["joyai_update", "joyai_loop_update", "joyai_prefill", "sdar_update", "sdar_prefill"])
def test_tiles_chosen_by_shape_compile_for_v5e(chip, shape):
    """What ``_tiles`` returns for the benchmark cells' shapes (``block_size=None``: the models' default) is
    within the compiler's 16 MiB of scoped VMEM, forward and backward (what it returns:
    ``test_block_sparse_attention.py::test_chooser_gives_the_table``)."""
    from benchmarks.attention_tile_readings import SHAPES, chooser_arguments
    from sheeprl_tpu.ops import block_sparse_attention as op

    batch, length, h_q, h_kv, d, d_v, _, backward, _ = SHAPES[shape]
    mask = chooser_arguments(shape)[-1]
    sizes, _ = op._tiles(*chooser_arguments(shape))

    def fwd(q, k, v):
        return op.block_sparse_flash_attention(q, k, v, mask).astype(jnp.float32).sum()

    q, k, v = (jax.ShapeDtypeStruct((batch, length, heads, width), jnp.bfloat16, sharding=chip)
               for heads, width in ((h_q, d), (h_kv, d), (h_kv, d_v)))
    text = jax.jit(jax.grad(fwd, argnums=(0, 1, 2)) if backward else fwd).lower(q, k, v).compile().as_text()
    assert "tpu_custom_call" in text
    if backward:  # the fused backward has no dQ kernel
        assert ("splash_mqa_dq" in text) == (not sizes.use_fused_bwd_kernel) and "splash_mqa_dkv" in text


@pytest.mark.parametrize("direction", ["fwd", "grad"])
def test_routed_experts_under_the_sigmoid_rule_compile_for_v5e(chip, direction):
    import re

    from benchmarks.lm_update_aot import computation
    from sheeprl_tpu.models.mla_moe import MlaMoeConfig
    from sheeprl_tpu.models.sdar_moe import RoutedExperts, short_buffer_rows

    cfg = MlaMoeConfig(experts_held=16)
    layer = RoutedExperts(cfg, jnp.bfloat16)
    m = jax.ShapeDtypeStruct((8192, 2048), jnp.float32, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), jnp.zeros((8, 2048), jnp.float32)),
    )
    assert params["params"]["bias"].shape == (256,) and short_buffer_rows(8192, 8, 16, 256) == 12288

    def fwd(p, m):
        y, aux = layer.apply(p, m)
        return y.sum(), aux["load"]

    fn = fwd if direction == "fwd" else jax.grad(lambda p, m: fwd(p, m)[0], argnums=(0, 1))
    text = jax.jit(fn).lower(params, m).compile().as_text()
    assert "tpu_custom_call" in text and text.count(" conditional(") == 1  # 12,288 rows when the load fits, else 65,536
    # a short buffer of 50 MB: gathers from it are cheap, and both branches read every (token, choice) pair (PR 33)
    branches = re.findall(r"%([\w.]+)", re.search(r"branch_computations=\{([^}]*)\}", text).group(1))
    assert all(PAIR_ROWS.format(8192 * 8) in computation(text, name) for name in branches)


# ---- one rematerialised block of each language-model policy, forward + backward, at the published widths and
# the cells' sizes.  Temporaries of the block that keeps the kernel's output and log-sum-exp (AOT here, PR 31;
# rematerialised whole, with the forward kernel called twice: 1,784,745,984 and 3,365,251,072; since PR 35 the
# causal block's backward is fused and its four bf16 parts of ``dq`` live no longer than the scratch the dQ
# kernel's call took: 1,932,135,936 -> 1,930,781,184; the block-diffusion layer compiles to 3,367,830,016 since
# PR 33's compact token side, on the parent too, where 3,628,106,240 stood)
REMAT_BLOCK_TEMP_BYTES = {"mla_moe": 1_930_781_184, "sdar_moe": 3_367_830_016}


@pytest.mark.parametrize("model", ["mla_moe", "sdar_moe"])
def test_rematerialised_block_calls_the_forward_kernel_once_on_v5e(chip, model):
    """``remat_block`` keeps the attention kernel's output and log-sum-exp: the compiled forward + backward of a
    block holds one forward kernel, not a second one for the backward pass, and what it keeps (68 MB a causal
    block, 141 MB a block-diffusion one) stays what it cost the temporaries when this was written."""
    from benchmarks.lm_update_aot import kernel_calls
    from sheeprl_tpu.models import mla_moe, sdar_moe

    if model == "mla_moe":
        block = sdar_moe.remat_block(mla_moe.MlaBlock)(mla_moe.MlaMoeConfig(experts_held=16), True, jnp.bfloat16)
        rows, length, extra = 1, 8192, ()
    else:
        layout = sdar_moe.EpisodeLayout(512, 1024, 4, 4)
        block = sdar_moe.remat_block(sdar_moe.SdarLayer, static_argnums=(3,))(
            sdar_moe.SdarConfig(experts_held=16), jnp.bfloat16)
        rows, length, extra = 3, layout.length, (layout,)
    pos = jnp.arange(length)
    h = jax.ShapeDtypeStruct((rows, length, 2048), jnp.float32, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), jnp.zeros((1, length, 2048), jnp.float32), pos, *extra)),
    )

    def loss(p, h):
        return block.apply(p, h, pos, *extra)[0].sum()

    # the value too: a gradient alone needs no forward pass but the rematerialised one
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(params, h).compile()
    text = compiled.as_text()
    # the causal block's backward is fused at 8,192 positions (``_tiles``: dK/dV writes ``dq`` in four parts, no dQ
    # kernel); the block-diffusion layer keeps the split form at tiles of 512
    for kernel, calls in (("splash_mqa_fwd", 1), ("splash_mqa_dkv", 1), ("splash_mqa_dq", 0 if model == "mla_moe" else 1)):
        assert kernel_calls(text, kernel) == calls, kernel
    assert compiled.memory_analysis().temp_size_in_bytes <= REMAT_BLOCK_TEMP_BYTES[model] * 1.02


@pytest.mark.parametrize("heads", [32, 4], ids=["q", "k"])
@pytest.mark.parametrize("model", ["sdar_update", "trinity_update", "trinity_update_nope"])
def test_qk_norm_rope_compiles_for_v5e(chip, model, heads):
    """The fused q/k norm and rotary, forward and backward, at the update's shapes: SDAR's 3 packed episodes of
    5,632 positions (the product's output in float32, as the model hands it over) and Trinity's one of 8,192 (bf16;
    a full layer without rotation).  One kernel each way, each writing the projection's size in bf16; Trinity's
    read nothing of it in float32."""
    from benchmarks.lm_update_aot import kernel_calls
    from sheeprl_tpu.ops.qk_norm_rope import qk_norm_rope

    rows, length, dtype = (3, 5632, jnp.float32) if model == "sdar_update" else (1, 8192, jnp.bfloat16)
    pos = None if model.endswith("nope") else jnp.arange(length)

    def loss(x, g):
        return qk_norm_rope(x, g, 1e-6, pos, 1e6, dtype=jnp.bfloat16).astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((rows, length, heads, 128), dtype, sharding=chip)
    g = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=chip)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, g).compile().as_text()
    assert kernel_calls(text, "qk_norm_rope_fwd") == 1 and kernel_calls(text, "qk_norm_rope_bwd") == 1
    calls = [line for line in text.splitlines() if "qk_norm_rope_" in line and "custom-call(" in line]
    sized = f"[{rows},{length},{heads * 128}]"
    assert all(line.split(" custom-call(")[0].split(" = ")[1].lstrip("(").startswith(f"bf16{sized}") for line in calls)
    assert dtype == jnp.float32 or not any(f"f32{sized}" in line for line in calls)


@pytest.mark.parametrize("direction", ["fwd", "grad"])
def test_gated_delta_rule_compiles_for_v5e(chip, direction):
    """The gated delta rule at the Qwen3-Next cell's shapes (one episode of 8,192 positions, 16 key heads
    serving 32 value heads of 128): the chunked form and its gradient through the scan over 128 chunks
    compile, and their temporaries stay far below the 8.6 GB a materialised (N, N) decay a head would
    take (``ops/gated_delta_rule.py``: plain JAX, no Pallas kernel)."""
    from sheeprl_tpu.ops.gated_delta_rule import gated_delta_rule

    n, hk, hv, d = 8192, 16, 32, 128

    def fwd(q, k, v, g, beta):
        o, state = gated_delta_rule(q, k, v, g, beta)
        return o.sum() + state.sum()

    shapes = ((1, n, hk, d), (1, n, hk, d), (1, n, hv, d), (1, n, hv), (1, n, hv))
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip) for s in shapes]
    compiled = jax.jit(jax.grad(fwd, argnums=(0, 1, 2, 3, 4)) if direction == "grad" else fwd).lower(*args).compile()
    assert "while" in compiled.as_text()  # the chunks' state passes through one scan
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


@pytest.mark.parametrize("direction", ["fwd", "grad"])
def test_wide_head_causal_attention_compiles_for_v5e(chip, direction):
    """Qwen3-Next's full layer: 16 query heads sharing 2 key-value heads of 256 under the causal mask at
    8,192 positions, at the tiles ``_tiles`` keeps for values over 128 lanes (one tile of 512 for every
    kernel), forward and backward within the compiler's scoped VMEM."""
    from sheeprl_tpu.ops import block_sparse_attention as op

    n = 8192
    mask = op.SegmentMask.causal(n, n)
    sizes, computed = op._tiles(n, n, 256, 256, 8, mask)
    assert (sizes.block_q, sizes.block_kv, sizes.block_q_dkv, sizes.block_q_dq, computed) == (512, 512, 512, 512, True)

    def fwd(q, k, v):
        return op.block_sparse_flash_attention(q, k, v, mask).astype(jnp.float32).sum()

    q, k, v = (jax.ShapeDtypeStruct((1, n, heads, 256), jnp.bfloat16, sharding=chip) for heads in (16, 2, 2))
    text = jax.jit(jax.grad(fwd, argnums=(0, 1, 2)) if direction == "grad" else fwd).lower(q, k, v).compile().as_text()
    assert "tpu_custom_call" in text
