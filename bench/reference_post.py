"""Plain references for a deployment whose exact phase runs: what every
written block key must read back, and how many blocks an exactly
deduplicated store holds.  Independent of the program: nothing here imports
it; keys are packed ints and the answers come from ``np.unique``.

A block key is a (disk, LBA) pair; a write's expected content is the
fingerprint of the last write to its key (later writes replace earlier
ones).  Each check is a ``reference.Check`` with limit 0, as each number counts
answers that differ from the reference.
"""

from __future__ import annotations

import numpy as np

from bench.reference import Check

LBA_BITS = 48


def block_keys(disks: np.ndarray, lbas: np.ndarray) -> np.ndarray:
    """One uint64 per (disk, LBA): ``disk << 48 | lba``."""
    disks = np.asarray(disks, dtype=np.uint64)
    lbas = np.asarray(lbas, dtype=np.int64)
    if lbas.size and (lbas.min() < 0 or lbas.max() >= 1 << LBA_BITS or disks.max() >= 1 << 16):
        raise ValueError("a disk or LBA does not fit the packed key")
    return (disks << np.uint64(LBA_BITS)) | lbas.astype(np.uint64)


def last_writes(keys: np.ndarray, fps: np.ndarray):
    """The key set the writes leave and each key's content: (sorted unique
    keys, the fingerprint of the last write to each)."""
    keys = np.asarray(keys, dtype=np.uint64)
    fps = np.asarray(fps, dtype=np.uint64)
    uniq, first_rev = np.unique(keys[::-1], return_index=True)
    return uniq, fps[::-1][first_rev]


def readback_gap(keys: np.ndarray, fps: np.ndarray, read: np.ndarray) -> Check:
    """Writes whose key did not read back the fingerprint it should hold
    (``read``: what the store gave for each key of ``last_writes(keys,
    fps)``, in its order, 0 where it gave no live block)."""
    uniq, content = last_writes(keys, fps)
    bad = uniq[np.asarray(read, dtype=np.uint64) != content]
    return Check("readback_gap", int(np.isin(np.asarray(keys, dtype=np.uint64), bad).sum()), 0)


def post_pass_gap(passes, writes, period: int) -> Check:
    """Per shard, |passes run − passes due|: a pass is due each time a
    shard has taken ``period`` writes since its last one, and nothing else
    runs one before the cluster-wide exact pass, so ``writes // period``
    are due (``passes``, ``writes``: per shard, since the engine started)."""
    passes, writes = np.asarray(passes, dtype=np.int64), np.asarray(writes, dtype=np.int64)
    return Check("post_pass_gap", int(np.abs(passes - writes // period).sum()), 0)


def post_backlog_gap(rows, writes, passes, period: int) -> Check:
    """Per shard, duplicate rows (fingerprints at several blocks) beyond the
    writes since its last pass: a whole pass leaves none, and each write
    adds at most one, so a pass that ran short shows here."""
    rows = np.asarray(rows, dtype=np.int64)
    since = np.asarray(writes, dtype=np.int64) - np.asarray(passes, dtype=np.int64) * period
    return Check("post_backlog_gap", int(np.maximum(rows - since, 0).sum()), 0)


def distinct_live(keys: np.ndarray, fps: np.ndarray) -> np.ndarray:
    """Sorted distinct fingerprints the keys hold after every write: what
    an exactly deduplicated store keeps one block of each."""
    return np.unique(last_writes(keys, fps)[1])


def exact_gap(live_blocks: int, keys: np.ndarray, fps: np.ndarray) -> Check:
    """|blocks the store holds after a full exact pass − distinct live
    fingerprints|."""
    return Check("exact_gap", abs(int(live_blocks) - int(distinct_live(keys, fps).size)), 0)


def truncated_distinct(live_fps: np.ndarray, bits: int) -> int:
    """Blocks an exact phase keyed by the low ``bits`` of each fingerprint
    would keep: contents that agree there collapse onto one block (the
    control's broken guarantee)."""
    mask = np.uint64((1 << bits) - 1)
    return int(np.unique(np.asarray(live_fps, dtype=np.uint64) & mask).size)
