#!/usr/bin/env python3
"""The blocked attention op alone (``ops/block_sparse_attention.py``) at the
language-model cells' shapes, over candidate tiles for each of its three
kernels: what ``_tiles`` was set from, and what says whether it still holds
after a change of chip, library or shape.

A shape is read one kernel at a time, the others held at tiles of 512 (the
three kernels share nothing but their operands, so their times add): the
forward alone, then forward + backward with the dK/dV kernel's tiles varied,
with the dQ kernel's varied, with the fused backward (``use_fused_bwd_kernel``:
no dQ kernel, dK/dV writes a part of ``dq`` per key tile, summed outside; the
sum is inside what is timed), and last whole candidates beside what ``_tiles``
returns.  A causal shape's candidates run with the mask computed in the kernel
(``CausalMask``); the whole candidates also with it stored (``NumpyMask``).  A
row: the tiles, the padded lengths, the tiles the mask leaves non-empty and
partial at the varied kernel's size, milliseconds a call (the median of
``--calls`` calls on the host's clock, each closed by ``block_until_ready``),
or "does not compile" with the compiler's reason.

    python benchmarks/attention_tile_readings.py --out chiprun_out/tiles.jsonl   # on the chip: ~9 min
    JAX_PLATFORMS=cpu python benchmarks/attention_tile_readings.py --aot            # here, ~15 min: which candidates compile

``--aot`` lowers and compiles every candidate for a described, unattached v5e
(as ``lm_update_aot.py`` does) and prints the temporaries in place of a time:
candidates Mosaic refuses for VMEM are known before chip time is spent.  It
takes libtpu's lock: not beside ``tests/test_ops/test_tpu_compile.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BASE = (512, 512, 512)

# name -> (batch, length, query heads, key-value heads, q/k width, v width, mask, backward?, who runs it)
SHAPES = {
    "joyai_update": (1, 8192, 32, 32, 192, 128, "causal", True, "joyai_ep_train's minibatch step"),
    "sdar_update": (3, 5632, 32, 4, 128, 128, "blockdiff", True, "sdar_ep8_train's and sdar_ep8_loop's minibatch step"),
    "joyai_loop_update": (1, 4608, 32, 32, 192, 128, "causal", True, "joyai_ep_loop's minibatch step"),
    "joyai_prefill": (4, 1024, 32, 32, 192, 128, "causal", False, "joyai_ep_loop's prefill"),
    "sdar_prefill": (12, 512, 32, 4, 128, 128, "blockdiff_prompt", False, "sdar_ep8_loop's prefill"),
}

# (query tile, key tile, key compute tile) of the forward and of the dK/dV kernel, (query tile, key tile) of dQ
CANDIDATES = {
    "joyai_update": {
        "fwd": [(1024, 512, 512), (512, 1024, 512), (1024, 1024, 512), (1024, 1024, 1024), (2048, 512, 512), (2048, 1024, 512),
                (512, 2048, 512), (1024, 2048, 512), (2048, 2048, 512), (1024, 2048, 1024), (2048, 2048, 1024), (1024, 1024, 256),
                (4096, 1024, 512), (256, 256, 256), (2048, 1024, 1024), (1024, 4096, 512)],
        "dkv": [(1024, 512, 512), (512, 1024, 512), (1024, 1024, 512), (1024, 1024, 1024), (2048, 512, 512), (2048, 1024, 512),
                (512, 2048, 512), (1024, 2048, 512), (2048, 2048, 512), (1024, 2048, 1024), (1024, 1024, 256), (4096, 512, 512),
                (512, 4096, 512), (1024, 4096, 512)],
        "dq": [(1024, 512), (512, 1024), (1024, 1024), (2048, 512), (2048, 1024), (512, 2048), (1024, 2048), (4096, 512),
               (2048, 2048)],
        "fused": [(512, 1024, 512), (1024, 1024, 512), (1024, 1024, 1024), (2048, 1024, 512), (512, 2048, 512), (1024, 2048, 512),
                  (2048, 2048, 512), (1024, 2048, 1024), (1024, 4096, 512), (2048, 4096, 512)],
    },
    "sdar_update": {
        "fwd": [(256, 256, 256), (256, 512, 512), (512, 256, 256), (512, 512, 256), (512, 512, 128), (1408, 512, 512),
                (512, 1408, 1408), (512, 1408, 128), (1408, 1408, 1408), (1408, 1408, 128), (2816, 512, 512), (256, 1408, 128),
                (1408, 256, 256), (128, 128, 128)],
        "dkv": [(256, 256, 256), (256, 512, 512), (512, 256, 256), (512, 512, 256), (512, 512, 128), (1408, 512, 512),
                (512, 1408, 1408), (512, 1408, 128), (1408, 1408, 128), (2816, 512, 512), (1408, 256, 256), (128, 128, 128)],
        "dq": [(256, 256), (256, 512), (512, 256), (1408, 512), (512, 1408), (1408, 256), (2816, 512), (2816, 256), (128, 128)],
        "fused": [(512, 512, 512), (512, 1408, 128), (512, 1408, 1408), (1408, 1408, 128), (256, 1408, 128), (512, 2816, 128),
                  (1408, 2816, 128), (512, 2816, 256), (1408, 512, 512)],
    },
    "joyai_loop_update": {
        "fwd": [(768, 768, 768), (768, 768, 384), (1536, 512, 512), (512, 1536, 512), (1536, 768, 768), (768, 1536, 768),
                (1536, 1536, 512), (1536, 1536, 768), (1152, 1152, 384), (2304, 768, 768), (1536, 768, 384)],
        "dkv": [(768, 768, 768), (768, 768, 384), (1536, 512, 512), (512, 1536, 512), (1536, 768, 768), (768, 1536, 768),
                (1536, 1536, 512), (1536, 1536, 768), (1152, 1152, 384), (2304, 512, 512)],
        "dq": [(768, 768), (1536, 512), (512, 1536), (1536, 768), (768, 1536), (1152, 1152), (2304, 512)],
        "fused": [(768, 768, 768), (1536, 768, 768), (768, 1536, 768), (1536, 1536, 512), (1536, 1536, 768), (768, 2304, 768),
                  (1536, 2304, 768)],
    },
    "joyai_prefill": {"fwd": [(1024, 512, 512), (512, 1024, 512), (1024, 1024, 512), (1024, 1024, 1024), (256, 256, 256),
                              (1024, 1024, 256)]},
    "sdar_prefill": {"fwd": [(256, 256, 256), (512, 512, 256), (256, 512, 512), (512, 256, 256), (512, 512, 128), (128, 128, 128)]},
}


def the_mask(kind: str, length: int):
    from sheeprl_tpu.models.sdar_moe import EpisodeLayout
    from sheeprl_tpu.ops.block_sparse_attention import SegmentMask

    if kind == "causal":
        return SegmentMask.causal(length, length)
    if kind == "blockdiff":  # prompt 512 + response 1,024 + 4 noised copies of it: rollout_p512_r1024_mb3's episode
        layout = EpisodeLayout(length * 512 // 5632, length * 1024 // 5632, 4, 4)
    else:
        layout = EpisodeLayout(length, 0, 4, 4)
    assert layout.length == length, (layout.length, length)
    return layout.mask


def chooser_arguments(name: str):
    """What the op hands ``_tiles`` at the shape ``name``: lengths, widths as ``_head_width`` gives them, ``rep``, mask."""
    from sheeprl_tpu.ops.block_sparse_attention import _head_width

    _, length, h_q, h_kv, d, d_v, kind, _, _ = SHAPES[name]
    return length, length, _head_width(d), _head_width(d_v), h_q // h_kv, the_mask(kind, length)


def block_sizes(fwd=BASE, dkv=BASE, dq=(512, 512), fused=False):
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk

    return sk.BlockSizes(block_q=fwd[0], block_kv=fwd[1], block_kv_compute=fwd[2], block_q_dkv=dkv[0], block_kv_dkv=dkv[1],
                         block_kv_dkv_compute=dkv[2], block_q_dq=None if fused else dq[0], block_kv_dq=None if fused else dq[1],
                         use_fused_bwd_kernel=fused)


def describe(sizes) -> str:
    back = f"dkv {sizes.block_q_dkv}/{sizes.block_kv_dkv}/{sizes.block_kv_dkv_compute} "
    back += "fused" if sizes.use_fused_bwd_kernel else f"dq {sizes.block_q_dq}/{sizes.block_kv_dq}"
    return f"fwd {sizes.block_q}/{sizes.block_kv}/{sizes.block_kv_compute} {back}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--shapes", default=",".join(SHAPES), help="comma-separated names of SHAPES")
    ap.add_argument("--stages", default="fwd,dkv,dq,fused,whole", help="which kernels' candidates to read")
    ap.add_argument("--aot", action="store_true", help="compile for a described v5e, no chip and no times")
    ap.add_argument("--calls", type=int, default=7, help="timed calls a candidate")
    ap.add_argument("--out", help="append one JSON line a row here")
    ap.add_argument("--stored", action="store_true",
                    help="read the causal shapes' candidates under the stored mask too (they are read under the computed one: a "
                         "stored partial tile is an int32 array of the tile's size in VMEM, twice, and rules the larger tiles out)")
    ap.add_argument("--skip-from", help="rows of an --aot run: what did not compile there is not tried again")
    ap.add_argument("--whole", action="append", default=[],
                    help="a further whole candidate 'shape:fq,fk,fc:kq,kk,kc:dq,dk|fused[:computed]'; the run's best tiles of "
                         "each kernel are read together anyway, split and fused, under the stored and the computed causal mask")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.ops import block_sparse_attention as op

    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.devices()[0].platform != "tpu":
        print("no TPU here: times come from a chip run only (--aot compiles for a described one)", file=sys.stderr)
        return 2
    stages = args.stages.split(",")
    rows = []
    refused = {}
    if args.skip_from:
        with open(args.skip_from) as f:
            for r in map(json.loads, f):
                if "does not compile" in r.values():
                    refused[(r["shape"], r["stage"], r["tiles"], r["mask"])] = r

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")

    for name in args.shapes.split(","):
        batch, length, h_q, h_kv, d, d_v, kind, backward, who = SHAPES[name]
        mask = the_mask(kind, length)
        census = {}

        def counts(bq, bkv, p_q, p_k):
            if (p_q, p_k) not in census:
                census[(p_q, p_k)] = op.tile_census(mask, p_q, p_k)
            table = op.coarser(census[(p_q, p_k)], bq // 128, bkv // 128)
            return {"all_tiles": int(table.size), "nonempty": int((table > 0).sum()), "partial": int((table == 1).sum())}

        shapes = [(batch, length, h_q, d), (batch, length, h_kv, d), (batch, length, h_kv, d_v)]
        if args.aot:
            operands = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=chip) for s in shapes]
        else:
            keys = jax.random.split(jax.random.PRNGKey(0), 3)
            operands = [jax.random.normal(key, s, jnp.bfloat16) for key, s in zip(keys, shapes)]

        def read(stage, sizes, varied, computed=False, with_backward=backward):
            """One row: the forward alone where ``stage`` is the forward's, else forward + backward."""
            p_q, p_k = op._padded(length, length, sizes)
            row = {"shape": name, "stage": stage, "tiles": describe(sizes), "mask": "computed" if computed else "stored",
                   "padded": [p_q, p_k], **counts(varied[0], varied[1], p_q, p_k)}
            known = refused.get((name, stage, row["tiles"], row["mask"]))
            if known:
                emit({**known, "from": "aot"})
                return

            def forward(q, k, v):
                return op.attention_under(q, k, v, mask, sizes, computed_causal=computed)

            fns = {"fwd_ms": forward}
            if with_backward:
                fns["fwd_bwd_ms"] = jax.grad(lambda q, k, v: forward(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))
            if stage == "fwd":
                fns.pop("fwd_bwd_ms", None)
            elif stage != "whole":
                fns.pop("fwd_ms")
            for key, fn in fns.items():
                try:
                    if args.aot:
                        compiled = jax.jit(fn).lower(*operands).compile()
                        row[key.replace("_ms", "_temp_bytes")] = compiled.memory_analysis().temp_size_in_bytes
                        continue
                    jitted = jax.jit(fn)
                    jax.block_until_ready(jitted(*operands))
                    laps = []
                    for _ in range(args.calls):
                        t0 = time.perf_counter()
                        jax.block_until_ready(jitted(*operands))
                        laps.append((time.perf_counter() - t0) * 1e3)
                    row[key] = round(statistics.median(laps), 3)
                    row[key.replace("_ms", "_min_ms")] = round(min(laps), 3)
                except Exception as e:  # Mosaic's refusal for VMEM comes as an XlaRuntimeError
                    text = " ".join(str(e).split())
                    scoped = re.search(r"Scoped allocation with size ([\d.]+\w) and limit ([\d.]+\w)", text)
                    row[key] = "does not compile"
                    row["why"] = f"scoped VMEM {scoped.group(1)} of {scoped.group(2)}" if scoped else text[:300]
            if computed and stage == "whole":  # the computed mask's block tables against the stored mask's
                a, b = (op._splash_kernel(op._as_bytes(mask), h_q // h_kv, sizes, c, False) for c in (True, False))
                row["tables_equal"] = all(
                    np.array_equal(np.asarray(getattr(getattr(a, info), t)), np.asarray(getattr(getattr(b, info), t)))
                    for info in ("fwd_mask_info", "dkv_mask_info", "dq_mask_info") if getattr(a, info) is not None
                    for t in ("block_mask", "data_next"))
            op._splash_kernel.cache_clear()  # a kernel object holds its partial tiles on the device
            emit(row)

        cands = CANDIDATES[name]
        for computed in ((True, False) if args.stored else (True,)) if kind == "causal" else (False,):
            if "fwd" in stages:
                for fwd in [BASE] + cands["fwd"]:
                    read("fwd", block_sizes(fwd=fwd), fwd, computed)
            if backward:
                if "dkv" in stages:
                    for dkv in [BASE] + cands["dkv"]:
                        read("dkv", block_sizes(dkv=dkv), dkv, computed)
                if "dq" in stages:
                    for dq in cands["dq"]:
                        read("dq", block_sizes(dq=dq), dq, computed)
                if "fused" in stages:
                    for dkv in cands["fused"]:
                        read("fused", block_sizes(dkv=dkv, fused=True), dkv, computed)
        if "whole" in stages:
            chosen, computed = op._tiles(*chooser_arguments(name))
            read("whole", block_sizes(), BASE)
            read("whole", chosen, (chosen.block_q, chosen.block_kv), computed=computed)
            wholes = [w.split(":") for w in args.whole if w.split(":")[0] == name]
            if not args.aot:  # this run's best tiles of each kernel together, split and fused
                def best(stage, key):
                    timed = [r for r in rows if r["shape"] == name and r["stage"] == stage and isinstance(r.get(key), float)
                             and (r["mask"] == "computed") == (kind == "causal")]
                    return min(timed, key=lambda r: r[key])["tiles"].split() if timed else None

                fwd, dkv, dq, fused = best("fwd", "fwd_ms"), best("dkv", "fwd_bwd_ms"), best("dq", "fwd_bwd_ms"), best("fused", "fwd_bwd_ms")
                if fwd and dkv and dq:
                    wholes.append([name, fwd[1], dkv[3], dq[5]])
                if fwd and fused:
                    wholes.append([name, fwd[1], fused[3], "fused"])
            trio = lambda text: tuple(int(x) for x in text.replace("/", ",").split(","))  # noqa: E731
            for _, fwd, dkv, dq, *more in wholes:
                sizes = block_sizes(trio(fwd), trio(dkv), (512, 512) if dq == "fused" else trio(dq), fused=dq == "fused")
                for computed in ((False, True) if kind == "causal" and not more else (bool(more),)):
                    read("whole", sizes, trio(fwd)[:2], computed=computed)

    # the table as PERF.md holds it
    print("\n| shape | stage | tiles | mask | padded | tiles non-empty / partial / all | fwd ms | fwd+bwd ms |")
    print("|---|---|---|---|---|---|---|---|")
    for r in rows:
        cell = lambda k: r.get(k, r.get(k.replace("_ms", "_temp_bytes"), ""))  # noqa: E731
        print(f"| {r['shape']} | {r['stage']} | {r['tiles']} | {r['mask']} | {r['padded'][0]} | "
              f"{r['nonempty']} / {r['partial']} / {r['all_tiles']} | {cell('fwd_ms')} | {cell('fwd_bwd_ms')} |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
