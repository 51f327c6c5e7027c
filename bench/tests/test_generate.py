"""The block-write generator: seeded, shaped like the program's generator."""

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

blocks = harness.load_family("block_writes")


def _tenants():
    _, cfg, _ = tiny.cell_files("vmA.deep")
    return cfg["tenants"]


def test_block_trace_is_seeded_and_large_seeds_work():
    a, _ = blocks.block_trace(_tenants(), 20000, 2**31 + 12345)
    b, _ = blocks.block_trace(_tenants(), 20000, 2**31 + 12345)
    c, _ = blocks.block_trace(_tenants(), 20000, 2**31 + 12346)
    assert np.array_equal(a, b) and not np.array_equal(a["fp"], c["fp"])
    with pytest.raises(ValueError):
        blocks.block_trace(_tenants(), 1000, -1)


def test_block_trace_matches_the_program_generator_statistics():
    from repro.core.traces import generate_workload, trace_stats

    ours = trace_stats(blocks.block_trace(_tenants(), 200000, 3)[0])
    theirs = trace_stats(generate_workload("A", total_requests=200000, seed=3)[0])
    assert ours["requests"] == theirs["requests"]
    assert abs(ours["write_ratio"] - theirs["write_ratio"]) < 0.01
    assert abs(ours["dup_ratio"] - theirs["dup_ratio"]) < 0.03
    assert abs(ours["unique_blocks"] / theirs["unique_blocks"] - 1) < 0.1


def test_aged_trace_cuts_at_the_distinct_target():
    aged = blocks.aged_trace(_tenants(), 5000, 20000, 9, 4.75)
    assert aged.aged_fps.size == 5000
    assert sum(s.size for s in aged.supply_fp) >= 20000
    w = aged.aged[aged.aged["op"] == blocks.OP_WRITE]
    assert np.unique(w["fp"][:-1]).size == 4999  # the last aged write is the 5000th
    for s, lbas in enumerate(aged.supply_lba):
        # each disk's window writes continue its own logical block sequence
        own = w["lba"][w["stream"] == s]
        assert lbas.size == 0 or own.size == 0 or lbas[0] == own.max() + 1
