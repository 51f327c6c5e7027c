"""HPDedup core: the paper's contribution as a composable library.

Public surface:

* ``Engine`` — the protocol every dedup engine implements; ``run_replay``
  drives any engine, batched or scalar, over a merged trace.
* ``HPDedup`` / ``HybridReport`` — the hybrid prioritized dedup mechanism.
* ``ShardedCluster`` — consistent-hash fingerprint partitioning across N
  per-shard engines, same ``Engine`` protocol (``core.cluster``); grows and
  shrinks live via ``resize`` (minimal-remap migration).
* ``snapshot_engine`` / ``restore_engine`` / ``load_engine_state`` —
  versioned, JSON-serializable state trees for every engine; a restored
  engine is bit-exact on all future writes (``core.snapshot``).
* ``ReplayBatch`` — columnar batched ingestion (``core.batch_replay``).
* ``FingerprintIndex`` — the exact membership layer every probe in the
  stack routes through: a device-layout hash table (Pallas kernel pair /
  vectorized numpy) over an authoritative host key set (``core.fp_index``).
* ``StreamLocalityEstimator`` — reservoir + unseen-estimator LDSS tracking.
* ``PrioritizedCache`` / ``GlobalCache`` — fingerprint caches.
* ``SpatialThreshold`` — per-stream adaptive duplicate-sequence threshold.
* ``BlockStore`` / ``PostProcessEngine`` — storage substrate + exact phase.
* baselines: ``make_idedup``, ``PurePostProcessing``, ``DIODE``.
* ``generate_workload`` — FIU-like synthetic multi-tenant traces.
* ``ContentDefinedChunker`` — content-defined chunking of raw byte streams
  (Gear rolling hash on-device, ``kernels.cdc``) into ``ReplayBatch``
  columns; ``chunk_boundaries_scalar`` is its reference oracle
  (``core.cdc``).
"""

from typing import Protocol, runtime_checkable

import numpy as np

from .baselines import DIODE, PurePostProcessing, make_idedup
from .batch_replay import (
    DEFAULT_BATCH_SIZE,
    ReplayBatch,
    engine_finish_replay,
    engine_ingest,
    run_replay,
)
from .cache import ARCCache, GlobalCache, LFUCache, LRUCache, PrioritizedCache
from .cdc import (
    CDCConfig,
    ContentDefinedChunker,
    chunk_boundaries_scalar,
    select_boundaries,
)
from .cluster import (
    ConsistentHashRing,
    ParallelShardExecutor,
    ShardedCluster,
    ShardWorkerError,
    aggregate_reports,
)
from .ffh import ffh_from_counts, ffh_from_sample, occurrence_counts
from .fingerprint import OP_READ, OP_WRITE, TRACE_DTYPE, host_fingerprint
from .fp_index import FingerprintIndex
from .hybrid import HPDedup, HybridReport
from .inline_engine import InlineDedupEngine
from .ldss import HoltPredictor, StreamLocalityEstimator
from .postprocess import PostProcessEngine
from .reservoir import Reservoir
from .segment_tree import FenwickSegments
from .snapshot import (
    SNAPSHOT_FORMAT,
    SNAPSHOT_VERSION,
    load_engine_state,
    report_from_tree,
    report_to_tree,
    restore_engine,
    snapshot_engine,
)
from .store import BlockStore
from .threshold import SpatialThreshold
from .traces import TEMPLATES, WORKLOADS, generate_workload, trace_stats
from .unseen import (
    ldss_batch,
    ldss_from_counts,
    unseen_estimate_from_counts,
    unseen_estimate_jax,
    unseen_estimate_jax_from_counts,
    unseen_estimate_ref,
)


@runtime_checkable
class Engine(Protocol):
    """One driver interface from trace ingest to reporting.

    ``HPDedup`` (and its ``make_idedup`` configuration), ``DIODE`` and
    ``PurePostProcessing`` all implement it, so the cluster, the data
    pipeline and the serving layer drive every engine the same way:
    columnar batches in, a ``HybridReport`` out.  Engines additionally
    expose ``replay_batched`` (the fast columnar path); ``replay`` stays
    the per-record reference oracle.
    """

    def write_batch(self, streams, lbas, fps) -> np.ndarray:
        """Ingest aligned (stream, lba, fingerprint) columns; returns the
        per-record inline-dedup flags."""
        ...

    def replay(self, trace: np.ndarray) -> "Engine":
        """Replay a merged TRACE_DTYPE trace in timestamp order."""
        ...

    def finish(self) -> HybridReport:
        """Flush, run the exact post-processing phase, and report."""
        ...


__all__ = [
    "Engine",
    "ShardedCluster",
    "ConsistentHashRing",
    "aggregate_reports",
    "ReplayBatch",
    "run_replay",
    "engine_ingest",
    "engine_finish_replay",
    "DEFAULT_BATCH_SIZE",
    "SNAPSHOT_FORMAT",
    "SNAPSHOT_VERSION",
    "snapshot_engine",
    "restore_engine",
    "load_engine_state",
    "report_to_tree",
    "report_from_tree",
    "DIODE",
    "PurePostProcessing",
    "make_idedup",
    "CDCConfig",
    "ContentDefinedChunker",
    "chunk_boundaries_scalar",
    "select_boundaries",
    "ARCCache",
    "GlobalCache",
    "LFUCache",
    "LRUCache",
    "PrioritizedCache",
    "ffh_from_counts",
    "ffh_from_sample",
    "occurrence_counts",
    "OP_READ",
    "OP_WRITE",
    "TRACE_DTYPE",
    "host_fingerprint",
    "HPDedup",
    "HybridReport",
    "InlineDedupEngine",
    "HoltPredictor",
    "StreamLocalityEstimator",
    "PostProcessEngine",
    "Reservoir",
    "FenwickSegments",
    "BlockStore",
    "SpatialThreshold",
    "TEMPLATES",
    "WORKLOADS",
    "generate_workload",
    "trace_stats",
    "ldss_batch",
    "ldss_from_counts",
    "unseen_estimate_from_counts",
    "unseen_estimate_jax",
    "unseen_estimate_jax_from_counts",
    "unseen_estimate_ref",
]
