"""Driver ``ppo_loop_causal``: ``drivers/ppo_loop.py`` whole (its ``run``,
window, comparison and ``judge``, imported, not copied) for the CAUSAL
language-model policy's loop, the cell ``joyai_ep_loop``.  It differs in three
things that ``ppo_loop.py`` and ``causal_lm_train.py``, which a PR that is no
``benchmark`` PR may not edit, cannot give the cell.

1. **The limits on what collection recorded**, for this kind alone.
   ``ppo_loop.RECORDED_LIMITS`` are the block-diffusion cell's readings (mean
   recorded log-probability error 4.42-4.46e-3, limit 6.3e-3).  The causal
   collector's 3,584 cached, absorbed passes read otherwise against the plain
   reference's full pass on a sound run (my chip runs, PR 34; every reading in
   ``chipbench/testdata/joyai_ep_loop_readings.json``, PERF.md section 4): sound
   runs | the causal cache write one token late (``benchmarks/ppo_loop_controls.py
   --control cache_shift``: every pass misses its own token's latent and sees an
   empty place at the first response position):

   - ``recorded_logp_mean_abs`` 7.59-8.67e-3 over fifteen seeds | 1.70e-2 and
     1.87e-2 on two: the one reading that tells the two apart here too.  The
     limit is the geometric mean of the sound runs' largest when it was set
     (8.51e-3, twelve seeds) and the late cache's smallest, as PR 32 set
     SDAR's: 1.2e-2; three later seeds read up to 8.67e-3, so it stands 1.38
     times the sound runs' largest and 1 / 1.42 of the control's smallest.
   - ``recorded_value_mean_abs`` 7.2-9.4e-3 | 1.68e-2, 1.76e-2 keeps
     ``ppo_loop``'s 3e-2.
   - **The two worst-cell readings are noted and NOT judged here.**
     ``recorded_logp_max_abs`` 0.147-0.477 | 0.213, 0.417 and
     ``recorded_value_max_abs`` 0.184-0.472 | 0.264, 0.252: the worst of 3,584
     cells is a routing choice that the cached pass alone made otherwise (4
     rows choose 8 of 256 sigmoid scores scaled by 2.5;
     ``recorded_vs_update_logp_max_abs`` reads the same 0.148-0.482 against
     the UPDATE's own pass), so the control reads inside the sound runs'
     range.  ``ppo_loop`` holds them to 0.16 / 0.15 so that a single wrong
     cell shows; here the fault such a limit would be for (a wrong token, a
     wrong position) was never read at the cell's size, and by the head's
     own spread (logit std about 0.9) it would often read under any limit
     that clears 0.477.  A limit with no reading above it is no limit: both
     stay in the ``compare_with_reference`` note, and a PR that plants that
     fault through this driver may set them.

   The update's own limits stay ``causal_lm_train``'s, through ``ppo_loop``:
   the program at ``fabric.precision=bf16-true`` (``--control bf16_true``) fails
   in this cell by ``returned_shortfall`` alone, as in the train cell: 0.799
   against 0.143-0.162 on the sound runs, limit 0.5.

2. **A leaf whose whole step is at float32's resolution** (``compare`` below).
   ``causal_lm_train.compare`` reads every leaf's ``new - old`` norm against the
   reference's clip + Adam step, and a leaf that moved on one side and not at
   all on the other reads exactly 1.  A norm gain stored at 1.0 registers a
   step only over 3e-8 (downwards) or 6e-8 (upwards), i.e. where its gradient
   passes 3-6e-7; on an episode of 4,608 positions (``joyai_ep_train``'s has
   8,192) the MTP block's ``q_norm``, 1,536 gains, lies AT that threshold, and
   on one sound seed of twelve its elements registered on one side and none on
   the other (seed 3420000011, call 2: ``moved_leaf_worst_rel`` 1.0 at
   ``['mtp']['block']['q_norm']``, every other reading as on the other seeds;
   with the floor, call 4, the same seed reads 0.0596 there: ONE element, one
   unit of 5.96e-8).
   The reading here is taken against a floor of ``MOVED_FLOOR`` = 1e-6 on the
   reference's norm: what about a hundred elements moving by one such unit
   come to.  Every matrix leaf moves by 1e-5 an element and reads as before,
   and so does a state left unchanged (each such leaf reads 1); the limit
   stays ``causal_lm_train``'s 0.3.

3. **What the counter readers need of the cell** (``reach_collect.py``): the
   configuration's and the traffic mix's files and the size, under the
   evidence's ``cell``; ``ppo_loop.run`` keeps of them only the bytes at the
   update's routing.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict

from chipbench.drivers import causal_lm_train, ppo_loop
from chipbench.drivers.causal_lm_train import LIMITS, compared_call, reference_for  # noqa: F401  (the kind's train driver, as ``ppo_loop`` takes one)
from chipbench.harness import Context, require

POLICY = "mla_moe"  # the one kind this driver runs
# what stands for ``ppo_loop.RECORDED_LIMITS`` while this cell runs: the two means (the head has the readings)
RECORDED_LIMITS = {"recorded_logp_mean_abs": 1.2e-2, "recorded_value_mean_abs": 3e-2}


# the norm of a leaf's whole step under which the reading is taken against this floor (the head has the reason)
MOVED_FLOOR = 1e-6


def compare(got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """``causal_lm_train.compare``, the worst leaf's ``new - old`` norm read against ``MOVED_FLOOR``."""
    readings = causal_lm_train.compare(got, ref)
    rel = {k: abs(got["moved_leaf_norms"][k] - norm) / max(norm, MOVED_FLOOR) for k, norm in ref["moved_leaf_norms"].items()}
    worst = max(rel, key=rel.get)
    readings.update(moved_leaf_worst_rel=float(rel[worst]), moved_leaf_worst_at=worst)
    return readings


@contextlib.contextmanager
def in_ppo_loop():
    """``ppo_loop.run`` judges by its module's ``RECORDED_LIMITS`` and compares through the train driver its
    ``KINDS`` names: this driver's limits and this module (``causal_lm_train``'s functions, and ``compare``
    above) stand there while it runs.  (Once a ``benchmark`` PR keys ``ppo_loop.RECORDED_LIMITS`` by policy,
    this goes.)"""
    limits, kinds = ppo_loop.RECORDED_LIMITS, ppo_loop.KINDS
    ppo_loop.RECORDED_LIMITS = RECORDED_LIMITS
    ppo_loop.KINDS = {**kinds, POLICY: (__name__, *kinds[POLICY][1:])}
    try:
        yield
    finally:
        ppo_loop.RECORDED_LIMITS, ppo_loop.KINDS = limits, kinds


def run(ctx: Context) -> dict:
    require(ctx.traffic["policy"] == POLICY, f"driver ppo_loop_causal runs {POLICY}, the traffic names {ctx.traffic['policy']}")
    ctx.evidence["cell"] = {"config": ctx.config, "traffic": ctx.traffic, "tiny": ctx.tiny}
    with in_ppo_loop():
        return ppo_loop.run(ctx)
