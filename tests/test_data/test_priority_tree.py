"""Unit tests for the device sum-tree (replay/priority_tree.py)."""

import jax
import numpy as np
import pytest

from sheeprl_tpu.replay.priority_tree import PriorityTree, per_beta_schedule, priority_from_td


def test_set_and_total_invariant():
    t = PriorityTree(10)
    t.set_priorities(np.arange(10), np.arange(10, dtype=np.float32))
    assert t.total == pytest.approx(45.0)
    # root equals the sum of every internal level
    tree = np.asarray(t.tree)
    p = 1 << t.depth
    for node in range(1, p):
        assert tree[node] == pytest.approx(tree[2 * node] + tree[2 * node + 1])


def test_proportional_sampling_distribution():
    t = PriorityTree(8)
    pri = np.array([0, 1, 2, 3, 4, 0, 0, 6], np.float32)
    t.set_priorities(np.arange(8), pri)
    leaf, _ = t.sample(jax.random.PRNGKey(0), 40000, beta=1.0, count=5)
    counts = np.bincount(np.asarray(leaf), minlength=8)
    emp = counts / counts.sum()
    expected = pri / pri.sum()
    assert np.allclose(emp, expected, atol=0.02)
    # zero-priority leaves are never drawn
    assert counts[0] == 0 and counts[5] == 0 and counts[6] == 0


def test_is_weights_formula_and_normalization():
    t = PriorityTree(4)
    pri = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    t.set_priorities(np.arange(4), pri)
    leaf, w = t.sample(jax.random.PRNGKey(1), 2000, beta=0.5, count=4)
    leaf, w = np.asarray(leaf), np.asarray(w)
    probs = pri[leaf] / pri.sum()
    raw = (4 * probs) ** -0.5
    np.testing.assert_allclose(w, raw / raw.max(), rtol=1e-5)
    assert w.max() == pytest.approx(1.0)  # batch-max normalized: only scales down


def test_exclusion_is_functional():
    t = PriorityTree(6)
    t.set_priorities(np.arange(6), np.ones(6, np.float32))
    leaf, _ = t.sample(jax.random.PRNGKey(2), 3000, beta=1.0, count=5, exclude_idx=np.array([3]))
    assert not (np.asarray(leaf) == 3).any()
    # the stored tree is untouched
    assert t.total == pytest.approx(6.0)
    assert float(t.priorities(3)) == pytest.approx(1.0)


def test_seed_max_and_update_track_running_max():
    t = PriorityTree(8, alpha=1.0, eps=0.0)
    t.seed_max(np.arange(4), np.ones(4, bool))
    assert t.total == pytest.approx(4.0)  # initial max priority 1.0
    t.update(np.array([0]), np.array([5.0]))
    assert float(t.max_priority) == pytest.approx(5.0)
    # subsequent seeds enter at the new max
    t.seed_max(np.array([6]), np.ones(1, bool))
    assert float(t.priorities(6)) == pytest.approx(5.0)


def test_masked_writes_leave_inactive_cells():
    t = PriorityTree(8)
    t.set_priorities(np.arange(8), np.full(8, 2.0, np.float32))
    t.set_priorities(np.arange(8), np.zeros(8, np.float32), active=np.arange(8) % 2 == 0)
    pri = np.asarray(t.priorities(np.arange(8)))
    np.testing.assert_allclose(pri, [0, 2, 0, 2, 0, 2, 0, 2])
    assert t.total == pytest.approx(8.0)


def test_duplicate_updates_stay_consistent():
    t = PriorityTree(8, alpha=1.0, eps=0.0)
    t.update(np.array([3, 3, 3]), np.array([2.0, 2.0, 2.0]))
    assert float(t.priorities(3)) == pytest.approx(2.0)
    assert t.total == pytest.approx(2.0)


def test_scale_decays_once_per_duplicate():
    t = PriorityTree(4)
    t.set_priorities(np.arange(4), np.full(4, 8.0, np.float32))
    t.scale(np.array([1, 1]), 0.5)
    assert float(t.priorities(1)) == pytest.approx(4.0)  # scaled once, not twice


def test_state_roundtrip_rebuilds_internal_nodes():
    t = PriorityTree(10)
    t.set_priorities(np.arange(10), np.arange(10, dtype=np.float32))
    t.update(np.array([2]), np.array([1.5]))
    s = t.state_dict()
    t2 = PriorityTree(10)
    t2.load_state_dict(s)
    assert t2.total == pytest.approx(t.total)
    np.testing.assert_allclose(
        np.asarray(t2.priorities(np.arange(10))), np.asarray(t.priorities(np.arange(10)))
    )
    assert float(t2.max_priority) == pytest.approx(float(t.max_priority))


def test_state_shape_mismatch_raises():
    t = PriorityTree(4)
    with pytest.raises(ValueError, match="leaves"):
        t.load_state_dict({"leaves": np.zeros(7, np.float32), "max_priority": 1.0})


def test_beta_schedule_and_priority_exponent():
    beta = per_beta_schedule(0.4, 1.0, 100)
    assert beta(0) == pytest.approx(0.4)
    assert beta(50) == pytest.approx(0.7)
    assert beta(100) == pytest.approx(1.0)
    assert beta(1000) == pytest.approx(1.0)  # clamped past the horizon
    assert priority_from_td(np.float32(-2.0), alpha=1.0, eps=0.5) == pytest.approx(2.5)


# -------------------------------------------- duplicate-index semantics unit
def test_scale_duplicate_indices_scale_once():
    """`scale` documents gather-then-write: duplicates decay ONCE per
    call, not once per occurrence."""
    t = PriorityTree(8)
    t.set_priorities(np.arange(8), np.full(8, 2.0, np.float32))
    t.scale(np.array([3, 3, 3, 5]), 0.5)
    pri = np.asarray(t.priorities(np.arange(8)))
    np.testing.assert_allclose(pri, [2, 2, 2, 1, 2, 1, 2, 2])
    assert t.total == pytest.approx(float(pri.sum()))


def test_set_priorities_masked_duplicate_cannot_drop_active_write():
    """The PR-12 `_write_impl` regression, at the public API: an INACTIVE
    duplicate of an active leaf must not win the one-writer-per-duplicate
    scatter and drop the active write."""
    t = PriorityTree(8)
    t.set_priorities(np.arange(8), np.ones(8, np.float32))
    idx = np.array([4, 4], np.int32)
    vals = np.array([9.0, 123.0], np.float32)
    act = np.array([True, False])
    t.set_priorities(idx, vals, act)
    assert float(t.priorities(4)) == pytest.approx(9.0)
    assert t.total == pytest.approx(16.0)
    # ancestors rebuilt consistently
    tree = np.asarray(t.tree)
    p = 1 << t.depth
    for node in range(1, p):
        assert tree[node] == pytest.approx(tree[2 * node] + tree[2 * node + 1])


def test_set_priorities_equal_duplicates_write_once():
    t = PriorityTree(8)
    t.set_priorities(np.array([2, 2, 2]), np.array([3.0, 3.0, 3.0], np.float32))
    assert float(t.priorities(2)) == pytest.approx(3.0)
    assert t.total == pytest.approx(3.0)
