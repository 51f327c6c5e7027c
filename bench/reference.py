"""Plain references that decide ``correct``: independent of the program.

Nothing here imports the program: membership is a sorted array and
``np.unique``.

Each check is a ``Check(name, value, limit)``; a run is correct iff every
value is at most its limit.  Every limit here is 0: each number counts
answers that differ from the reference.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass(frozen=True)
class Check:
    name: str
    value: int
    limit: int

    @property
    def ok(self) -> bool:
        return self.value <= self.limit



def truly_duplicate(aged_sorted: np.ndarray, fps: np.ndarray) -> np.ndarray:
    """Per write, in order: was its fingerprint written before it (in the
    aged state or by an earlier write of ``fps``)?"""
    fps = np.asarray(fps, dtype=np.uint64)
    in_aged = _isin_sorted(fps, aged_sorted)
    _, first, inv = np.unique(fps, return_index=True, return_inverse=True)
    return in_aged | (first[inv] < np.arange(fps.size))


def _isin_sorted(x: np.ndarray, sorted_keys: np.ndarray) -> np.ndarray:
    if sorted_keys.size == 0:
        return np.zeros(x.size, dtype=bool)
    pos = np.minimum(np.searchsorted(sorted_keys, x), sorted_keys.size - 1)
    return sorted_keys[pos] == x


def membership_checks(aged_sorted: np.ndarray, fps: np.ndarray, flags: np.ndarray,
                      engine_writes: int, engine_dups: int, engine_hits: int) -> List[Check]:
    """The writes served after the aged state, in submission order, against
    what the engines counted over the same writes.

    * ``applied_gap``: writes the engines applied vs writes acknowledged;
    * ``dup_count_gap``: duplicate writes the engines' membership index
      counted vs the reference's (the index's answers, in aggregate);
    * ``flag_report_gap``: inline flags acknowledged vs the engines'
      reported cache hits;
    * ``false_inline``: writes acknowledged as deduplicated inline whose
      fingerprint was never written before them.
    """
    dup = truly_duplicate(aged_sorted, fps)
    flags = np.asarray(flags, dtype=bool)
    return [
        Check("applied_gap", abs(int(engine_writes) - int(fps.size)), 0),
        Check("dup_count_gap", abs(int(engine_dups) - int(dup.sum())), 0),
        Check("flag_report_gap", abs(int(engine_hits) - int(flags.sum())), 0),
        Check("false_inline", int((flags & ~dup).sum()), 0),
    ]


def truncated_dup_count(aged_sorted: np.ndarray, fps: np.ndarray, bits: int) -> int:
    """Duplicate writes as a membership keyed by the low ``bits`` of each
    fingerprint would count them: the control's broken guarantee."""
    mask = np.uint64((1 << bits) - 1)
    return int(truly_duplicate(np.unique(aged_sorted & mask), np.asarray(fps) & mask).sum())
