"""The language-model policies' fused collectors (ISSUE 32, 34), both kinds at
tiny widths on the CPU: the counters are exact (those from shapes, and the one
the rollout counts on the device against a count by hand over a full pass's
routing), the episode events' fetch is a span of its own and one fetch a
rollout carries the device's count, the rollout program names its phases and
the names are metadata only, and a seeded rollout is the bits the parent
commit gave (``lm_golden.json``)."""

import contextlib
import re
import types

import jax
import numpy as np
import pytest

from sheeprl_tpu.envs.jax import collect as collect_module
from sheeprl_tpu.utils.metric import MeanMetric, MetricAggregator
from sheeprl_tpu.utils.timer import timer
from tests.test_envs_jax import lm_tiny
from tests.test_envs_jax.lm_tiny import ENVS, KINDS, P, RESP, ROLLOUT_KEYS

BLOCK = 4
SCOPES = {
    "sdar_moe": ("collect_prefill", "collect_denoise", "collect_commit", "collect_score", "collect_sample", "collect_env"),
    "mla_moe": ("collect_prefill", "collect_decode", "collect_score", "collect_sample", "collect_env"),
}
TOKEN_RE = re.compile(r"\bcollect_(prefill|denoise|decode|commit|score|sample|env)\b")
# one rollout, by hand: SDAR makes a prefill and five passes over 4 positions a block of 4 tokens,
# the causal policy a prefill and one pass a token
WORK = {
    "sdar_moe": {"passes": 1 + (RESP // BLOCK) * 5, "positions": ENVS * (P + (RESP // BLOCK) * 5 * BLOCK)},
    "mla_moe": {"passes": 1 + RESP, "positions": ENVS * (P + RESP)},
}


def _aggregator():
    return MetricAggregator({k: MeanMetric() for k in ("Rewards/rew_avg", "Game/ep_len_avg")})


@pytest.fixture(params=KINDS)
def kind(request):
    return request.param


def test_counters_are_exact_after_two_rollouts(kind, tmp_path):
    collector, _, _, runtime, _ = lm_tiny.build_collector(kind, str(tmp_path), aggregator=_aggregator())
    timer.reset()
    for i in range(2):
        collector.collect(i + 1, True, runtime.next_key)
        if i == 0:
            collector.adopt(collector.params)  # the loop hands over an update's result before the next rollout
    stats = collector.stats()
    for k in ("passes", "positions"):
        assert stats[k] == 2 * WORK[kind][k], k
    assert stats["env_steps"] == 2 * ENVS * RESP and stats["rollouts"] == 2
    assert stats["params_age"] == 0 and stats["event_fetches"] == 2 and stats["episodes_reported"] == 2 * ENVS
    sums = timer.compute()
    assert sums["Time/collect_wait"] > 0.0 and sums["Time/collect_events"] > 0.0


def test_a_rollout_behind_the_newest_weights_says_so(kind, tmp_path):
    collector, _, _, runtime, _ = lm_tiny.build_collector(kind, str(tmp_path))
    for i in range(3):  # no hand-over: every later rollout acts with the first weights
        collector.collect(i + 1, True, runtime.next_key)
        assert collector.stats()["params_age"] == i


def test_one_fetch_was_added(kind, tmp_path, monkeypatch):
    """A rollout whose events are due costs the three fetches it always did and
    ONE more since ISSUE 34, the count the rollout made on the device
    (``experts_reached``: one scalar, fetched after the wait for the rollout
    has ended); one that is skipped (``metric.fetch_every=2``) none: the loop
    then waits later, in its own spans, and that rollout's count is dropped
    with its events."""
    fetched = []
    counting = types.SimpleNamespace(**{k: getattr(np, k) for k in ("nonzero",)},
                                     asarray=lambda x: (fetched.append(type(x).__name__), np.asarray(x))[1])
    monkeypatch.setattr(collect_module, "np", counting)
    for name in ("device_get", "block_until_ready"):
        monkeypatch.setattr(jax, name, lambda *a, _name=name, **k: pytest.fail(f"jax.{_name} in collect"))
    collector, _, _, runtime, _ = lm_tiny.build_collector(kind, str(tmp_path), extra=["metric.fetch_every=2"],
                                                          aggregator=_aggregator())
    per_rollout = []
    for i in range(4):
        before = len(fetched)
        collector.collect(i + 1, True, runtime.next_key)
        per_rollout.append(len(fetched) - before)
    assert per_rollout == [4, 0, 4, 0]
    assert collector.stats()["event_fetches"] == 2


def _cached_passes(kind, policy):
    """[(the packed positions of an episode that one cached pass ran, how many of the routed layers' routed
    parts ran in it)] of one rollout, by hand.  Block diffusion: copy ``j`` of block ``b`` is its denoising pass
    ``j``, the block's clean positions are the pass that commits it (``SdarMoE.commit``: keys and values alone,
    which the last layer's routed part does not feed).  Causal: response token ``t`` is cached pass ``t``, every
    layer runs."""
    if kind == "mla_moe":
        return [(np.array([P + t]), None) for t in range(RESP)]
    steps, out = policy.cfg.denoise_steps, []
    for b in range(RESP // BLOCK):
        out += [(P + RESP + (b * steps + j) * BLOCK + np.arange(BLOCK), None) for j in range(steps)]
        out.append((P + b * BLOCK + np.arange(BLOCK), -1))
    return out


def test_the_device_counts_equal_a_count_by_hand(kind, tmp_path):
    """``experts_reached`` of a rollout against numpy over the routing that a FULL pass gives the recorded
    rollout (float32 at the tiny widths: the cached passes choose as the full pass does)."""
    from sheeprl_tpu.models.sdar_moe import RoutedSpec

    collector, policy, params, runtime, _ = lm_tiny.build_collector(kind, str(tmp_path), aggregator=_aggregator())
    data = collector.collect(1, True, runtime.next_key).data
    *_, aux = policy.evaluate_episodes(params, data["prompt"][0], np.asarray(data["actions"]).swapaxes(0, 1))
    spec = RoutedSpec.of(policy.cfg)
    trunk = policy.cfg.num_hidden_layers - getattr(policy.cfg, "first_k_dense_replace", 0)  # (the MTP block's routing is last)
    top_i = np.asarray(aux["top_i"])[:trunk].reshape(trunk, ENVS, -1, spec.top_k) - spec.expert_offset
    if kind == "sdar_moe":
        # the pass that commits the LAST block runs after the env's auto-reset, over the new episode's tokens in
        # that place (what it writes is never read): its routing is a clean pass's over the episode so changed
        from sheeprl_tpu.models.sdar_moe import EpisodeLayout, SdarMoE

        clean = np.array(policy.layout.pack(data["prompt"][0], np.asarray(data["actions"]).swapaxes(0, 1), policy.cfg.mask_id)[0])
        clean = clean[:, : P + RESP]
        clean[:, -BLOCK:] = np.asarray(collector._carry["obs"]["tokens"])[:, P + RESP - BLOCK: P + RESP]
        layout = EpisodeLayout(P + RESP, 0, BLOCK, policy.cfg.denoise_steps)
        _, last = policy.model.apply(params, clean, layout, method=SdarMoE.hidden)
        last = np.asarray(last["top_i"]).reshape(trunk, ENVS, -1, spec.top_k) - spec.expert_offset
        top_i[:, :, P + RESP - BLOCK: P + RESP] = last[:, :, -BLOCK:]
    reached = 0
    for positions, layers in _cached_passes(kind, policy):
        local = top_i[:layers, :, positions]
        ok = (local >= 0) & (local < spec.experts_held)
        reached += sum(np.unique(layer[keep]).size for layer, keep in zip(local, ok))
    assert collector.stats()["experts_reached"] == reached > 0
    collector.collect(2, True, runtime.next_key)  # cumulative, like ``passes``
    assert collector.stats()["experts_reached"] > reached


def test_a_seeded_rollout_is_bit_equal_to_the_parents(kind, tmp_path):
    golden = lm_tiny.load_golden()
    exact = lm_tiny.canary() == golden["canary"]
    for got, want in zip(lm_tiny.rollout_arrays(kind, str(tmp_path)), golden["kinds"][kind]["rollouts"]):
        for k in ROLLOUT_KEYS:
            ref = lm_tiny.decode(want[k])
            assert got[k].dtype == ref.dtype and got[k].shape == ref.shape, k
            if exact or ref.dtype.kind in "iu":
                assert got[k].tobytes() == ref.tobytes(), k
            else:  # another machine's rounding
                np.testing.assert_allclose(got[k], ref, rtol=1e-5, atol=1e-6, err_msg=k)


# ------------------------------------------------------------------ the scopes
def _traced(kind, root):
    """(lowered text with locations, without them, the traced program)."""
    collector, _, _, runtime, _ = lm_tiny.build_collector(kind, root)
    args = (collector.params, collector._carry, runtime.next_key(), collector._env_base)
    lowered = collector._rollout.lower(*args)
    return lowered.as_text(debug_info=True), lowered.as_text(), jax.make_jaxpr(collector._rollout_fn)(*args), lowered


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for x in value if isinstance(value, (list, tuple)) else [value]:
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def _ops_by_scope(jaxpr, prefix="", out=None):
    """{scope or None: the program's operations under it}: an equation that
    holds others (the scan, a rematerialised layer, a jitted helper) counts
    through what it holds, a Pallas kernel as the one call it is on the chip."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        path = f"{prefix}/{eqn.source_info.name_stack}"
        inner = [] if eqn.primitive.name == "pallas_call" else list(_sub_jaxprs(eqn))
        for sub in inner:
            _ops_by_scope(sub, path, out)
        if not inner:
            hit = TOKEN_RE.search(path)
            key = hit.group(0) if hit else None
            out[key] = out.get(key, 0) + 1
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    done = {}
    return lambda kind: done.setdefault(kind, _traced(kind, str(tmp_path_factory.mktemp(f"traced_{kind}"))))


def test_lowered_rollout_names_its_phases(kind, traced):
    text = traced(kind)[0]
    assert "jit(collect_rollout)" in text
    for token in SCOPES[kind]:
        assert f"{token}/" in text, token
    # the model's own scopes lie beneath the collector's
    assert re.search(r"collect_prefill/[^\"]*moe_experts", text)


def test_under_three_per_cent_of_the_ops_lie_under_no_scope(kind, traced):
    ops = _ops_by_scope(traced(kind)[2].jaxpr)
    assert set(ops) - {None} == set(SCOPES[kind])
    assert ops.get(None, 0) / sum(ops.values()) < 0.03, ops


@contextlib.contextmanager
def _no_scope(name):  # a context manager and a decorator, as jax.named_scope is
    yield


def _ops_and_cost(lowered):
    compiled = lowered.compile()
    text = compiled.as_text()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return len(re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = ", text, flags=re.M)), {k: v for k, v in cost.items() if "{" not in k}


def test_scopes_are_metadata_only(kind, traced, tmp_path, monkeypatch):
    """The rollout traced with ``jax.named_scope`` a no-op, the program as it
    read before the scopes, lowers to the same text once locations are left
    out, and compiles to as many operations at the same cost."""
    scoped_debug, scoped_plain, _, scoped_lowered = traced(kind)
    monkeypatch.setattr(jax, "named_scope", _no_scope)
    bare_debug, bare_plain, _, bare_lowered = _traced(kind, str(tmp_path))
    assert TOKEN_RE.search(scoped_debug) and not TOKEN_RE.search(bare_debug)  # the switch reached the program
    assert scoped_plain == bare_plain
    assert _ops_and_cost(scoped_lowered) == _ops_and_cost(bare_lowered)
