"""The plain references agree with the program where the program is right,
and each count moves when an answer is wrong."""

import numpy as np

from bench import reference


def test_membership_checks_count_each_kind_of_wrong_answer():
    aged = np.array([10, 20, 30], dtype=np.uint64)
    fps = np.array([10, 40, 40, 50, 20], dtype=np.uint64)
    dup = reference.truly_duplicate(aged, fps)
    assert dup.tolist() == [True, False, True, False, True]
    flags = np.array([True, False, True, False, False])
    ok = reference.membership_checks(aged, fps, flags, 5, 3, 2)
    assert all(c.ok for c in ok)
    bad = {c.name: c.value for c in reference.membership_checks(
        aged, fps, np.array([True, True, False, False, False]), 4, 2, 3)}
    assert bad == {"applied_gap": 1, "dup_count_gap": 1, "flag_report_gap": 1, "false_inline": 1}


def test_truncated_membership_is_the_control():
    rng = np.random.default_rng(4)
    aged = np.unique(rng.integers(0, 2**63, size=3000, dtype=np.uint64))
    fps = rng.integers(0, 2**63, size=3000, dtype=np.uint64)
    exact = int(reference.truly_duplicate(aged, fps).sum())
    assert exact == 0
    assert reference.truncated_dup_count(aged, fps, 12) > 0
    assert reference.truncated_dup_count(aged, fps, 64 - 1) == exact
