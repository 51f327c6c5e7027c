import os
import sys

import pytest

# Tests must see exactly ONE device (the dry-run alone fakes 512); keep jax
# imports lazy to the first test so no global XLA_FLAGS leak here.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def require_hypothesis():
    """Guard for property-test files: skip locally, hard-fail in CI.

    ``pytest.importorskip("hypothesis")`` alone lets a broken CI install
    silently drop every property suite — the run stays green while the
    differential property coverage quietly vanishes.  CI sets
    ``REQUIRE_HYPOTHESIS=1`` (hypothesis is pinned in requirements-dev.txt),
    turning a missing import into a loud failure; local runs without the
    dev extras still skip.
    """
    try:
        import hypothesis  # noqa: F401
    except ImportError:
        if os.environ.get("REQUIRE_HYPOTHESIS"):
            raise RuntimeError(
                "hypothesis is required (REQUIRE_HYPOTHESIS=1) but not "
                "installed — the property suites would silently skip"
            )
        pytest.skip("hypothesis not installed", allow_module_level=True)
    return hypothesis

# Centralized hypothesis profiles (test hygiene, ISSUE 4): property tests use
# bare @given and inherit the profile instead of scattering per-file
# @settings.  ``dev`` favors fresh examples locally; ``ci`` derandomizes so
# CI runs are reproducible and prints the failure blob for replays.  Both
# disable the deadline — differential replays legitimately take long on
# shared runners.  Hypothesis stays optional (pytest.importorskip guards the
# property files), so this block must not require it.
try:
    from hypothesis import settings
except ImportError:
    pass
else:
    settings.register_profile("dev", max_examples=50, deadline=None)
    settings.register_profile(
        "ci", max_examples=50, deadline=None, derandomize=True, print_blob=True
    )
    settings.load_profile("ci" if os.environ.get("CI") else "dev")


def feed_cluster(cluster, trace, entry, batch_size):
    """Drive ``trace`` through one of a ``ShardedCluster``'s two entry paths,
    in chunks of ``batch_size`` per shard: ``ingest_batched`` takes the whole
    trace, reads included; ``write_batch`` takes its writes, chunk by chunk."""
    from repro.core.fingerprint import OP_WRITE

    if entry == "ingest_batched":
        cluster.ingest_batched(trace, batch_size=batch_size)
        return
    writes = trace[trace["op"] == OP_WRITE]
    step = batch_size * cluster.num_shards
    for lo in range(0, len(writes), step):
        w = writes[lo : lo + step]
        cluster.write_batch(w["stream"], w["lba"], w["fp"])
