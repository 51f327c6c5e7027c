"""Content-defined chunking front end: byte buffers -> chunk fingerprints.

``ContentDefinedChunker`` turns raw byte streams into variable-size chunks
cut at content-defined boundaries (Gear rolling hash, ``kernels.cdc``) and
hands each chunk a 64-bit fingerprint, feeding the same ``ReplayBatch``
columns every engine already ingests.  Three backends, bit-identical by the
same exactness contract as ``core.fp_index``:

* ``pallas``  — the fused device pipeline: one upload of the packed haloed
  rows, a candidate-flag kernel launch, then a gather+fingerprint launch
  over the *same device-resident* rows.  Only the candidate flags round-trip
  to the host (greedy min/max selection is inherently sequential but
  O(#chunks)); the bytes never do.  Default on TPU.
* ``numpy``   — vectorized windowed-sum candidates + one batched fingerprint
  call over the packed chunk matrix.  Default on the CPU (interpret-mode
  Pallas is a correctness artifact, not a fast path).
* ``scalar``  — the per-byte reference oracle (``chunk_boundaries_scalar``):
  the literal rolling-hash recurrence + per-chunk fingerprints.  The other
  two backends are property-tested bit-exact against it.

Boundary semantics (all backends): cut candidates are byte positions ``i``
with ``(H_i & (avg_size-1)) == 0`` where ``H_i`` hashes the trailing
32-byte window (zero-prefixed at stream start); ``select_boundaries`` then
greedily takes the first candidate at least ``min_size`` into the current
chunk, forcing a cut at ``max_size``, with a final sub-``min_size`` tail
allowed.  Chunks are fingerprinted zero-padded to ``max_size`` with the true
length mixed in (``kernels.ops.chunk_fp64``), so boundary math and hashing
are decoupled and every backend hashes identical images.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..kernels.cdc import SEG_BYTES, WINDOW, gear_table, pack_haloed, unpack_candidates
from ..kernels.ops import cdc_candidate_flags, cdc_chunk_fingerprints, chunk_fp64
from .batch_replay import ReplayBatch

_GEAR = gear_table()
# seed making the scalar recurrence equal the zero-prefixed windowed sum:
# h_init * 2^(i+1) must cancel the GEAR[0] terms of the implicit zero prefix,
# i.e. h_init = -GEAR[0] mod 2^32
_H_INIT = (int(_GEAR[0]) * 0xFFFFFFFF) & 0xFFFFFFFF


@dataclass(frozen=True)
class CDCConfig:
    """Chunking parameters; validated against the kernel layout limits."""

    min_size: int = 2048
    avg_size: int = 4096
    max_size: int = 16384

    def __post_init__(self):
        if self.min_size < 2 * WINDOW:
            raise ValueError(f"min_size must be >= {2 * WINDOW}, got {self.min_size}")
        if self.avg_size & (self.avg_size - 1):
            raise ValueError(f"avg_size must be a power of two, got {self.avg_size}")
        if not self.min_size < self.avg_size <= self.max_size:
            raise ValueError(
                f"need min_size < avg_size <= max_size, got "
                f"{self.min_size}/{self.avg_size}/{self.max_size}")
        if self.max_size % 512:
            # max_size/4 words must be a LANES multiple for the fingerprint tile
            raise ValueError(f"max_size must be a multiple of 512, got {self.max_size}")
        if self.max_size > 16384:
            # (TILE_B, max_size/4) uint32 must fit VMEM next to scratch
            raise ValueError(f"max_size must be <= 16384, got {self.max_size}")


def select_boundaries(candidates: np.ndarray, n: int, min_size: int, max_size: int) -> np.ndarray:
    """Greedy boundary selection over sorted candidate positions.

    Shared verbatim by every backend — the scalar oracle's cut rule
    ("first position with length >= min_size that is a candidate or reaches
    max_size") expressed over the sparse candidate array.  Returns chunk end
    offsets (exclusive); the final tail may be shorter than ``min_size``.
    """
    ends: List[int] = []
    cand_ends = np.asarray(candidates, dtype=np.int64) + 1
    start = 0
    while start < n:
        lo = int(np.searchsorted(cand_ends, start + min_size))
        if lo < cand_ends.size and cand_ends[lo] <= min(start + max_size, n):
            end = int(cand_ends[lo])
        elif start + max_size <= n:
            end = start + max_size
        else:
            end = n
        ends.append(end)
        start = end
    return np.asarray(ends, dtype=np.int64)


def chunk_boundaries_scalar(data, min_size: int, avg_size: int, max_size: int) -> np.ndarray:
    """Per-byte reference oracle: the literal Gear recurrence + cut rule."""
    data = np.ascontiguousarray(data, dtype=np.uint8).reshape(-1)
    gear = _GEAR
    mask = avg_size - 1
    h = _H_INIT
    n = data.size
    ends: List[int] = []
    start = 0
    for i, b in enumerate(data.tolist()):
        h = ((h << 1) + int(gear[b])) & 0xFFFFFFFF
        length = i + 1 - start
        if length >= min_size and ((h & mask) == 0 or length >= max_size):
            ends.append(i + 1)
            start = i + 1
    if start < n:
        ends.append(n)
    return np.asarray(ends, dtype=np.int64)


def _candidates_numpy(data: np.ndarray, avg_size: int) -> np.ndarray:
    """Vectorized windowed-sum candidates: H_i = sum_j GEAR[b_{i-j}] << j."""
    g = _GEAR[data]
    gz = np.concatenate([np.full(WINDOW - 1, _GEAR[0], dtype=np.uint32), g])
    n = data.size
    h = np.zeros(n, dtype=np.uint32)
    for j in range(WINDOW):
        h += gz[WINDOW - 1 - j: WINDOW - 1 - j + n] << np.uint32(j)
    return np.nonzero((h & np.uint32(avg_size - 1)) == 0)[0]


def _chunk_matrix(buffers: Sequence[np.ndarray], ends_per: Sequence[np.ndarray],
                  max_size: int) -> Tuple[np.ndarray, np.ndarray]:
    """Pack every chunk zero-padded into a (C, max_size) uint8 matrix."""
    lens: List[int] = []
    rows: List[np.ndarray] = []
    for data, ends in zip(buffers, ends_per):
        start = 0
        for end in ends.tolist():
            rows.append(data[start:end])
            lens.append(end - start)
            start = end
    mat = np.zeros((len(rows), max_size), dtype=np.uint8)
    for i, row in enumerate(rows):
        mat[i, : row.size] = row
    return mat, np.asarray(lens, dtype=np.int64)


class ContentDefinedChunker:
    """Byte buffers -> (chunk ends, chunk fingerprints) -> ReplayBatch.

    ``backend`` is ``"pallas"`` / ``"numpy"`` / ``"scalar"`` or ``None`` for
    the platform default (pallas on TPU, numpy on the CPU, an error on any
    other platform) — all bit-exact.
    """

    def __init__(self, min_size: int = 2048, avg_size: int = 4096,
                 max_size: int = 16384, backend: Optional[str] = None):
        self.config = CDCConfig(min_size, avg_size, max_size)
        if backend not in (None, "pallas", "numpy", "scalar"):
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend

    def _resolve(self) -> str:
        if self.backend is not None:
            return self.backend
        from ..kernels.ops import device_platform
        return "pallas" if device_platform() == "tpu" else "numpy"

    # -- boundaries ---------------------------------------------------------

    def chunk(self, data) -> np.ndarray:
        """Chunk end offsets (exclusive) for one buffer."""
        return self.chunk_many([data])[0]

    def chunk_many(self, buffers) -> List[np.ndarray]:
        cfg = self.config
        backend = self._resolve()
        bufs = [np.ascontiguousarray(b, dtype=np.uint8).reshape(-1) for b in buffers]
        if backend == "scalar":
            return [chunk_boundaries_scalar(b, cfg.min_size, cfg.avg_size, cfg.max_size)
                    for b in bufs]
        if backend == "numpy":
            return [select_boundaries(_candidates_numpy(b, cfg.avg_size), b.size,
                                      cfg.min_size, cfg.max_size) for b in bufs]
        haloed, spans = pack_haloed(bufs)
        flags = np.asarray(cdc_candidate_flags(haloed, cfg.avg_size))
        return [select_boundaries(unpack_candidates(flags, span), span[2],
                                  cfg.min_size, cfg.max_size) for span in spans]

    # -- boundaries + fingerprints ------------------------------------------

    def chunk_fingerprints(self, data) -> Tuple[np.ndarray, np.ndarray]:
        """(ends, fp64) for one buffer."""
        return self.chunk_fingerprints_many([data])[0]

    def chunk_fingerprints_many(self, buffers) -> List[Tuple[np.ndarray, np.ndarray]]:
        cfg = self.config
        backend = self._resolve()
        bufs = [np.ascontiguousarray(b, dtype=np.uint8).reshape(-1) for b in buffers]

        if backend == "pallas":
            # fused device path: rows upload once; flags (small) come back for
            # selection; the gather+fingerprint launches (CHUNKS_PER_LAUNCH
            # chunks each, so device temp is bounded for any input size)
            # reuse the resident rows
            import jax.numpy as jnp
            haloed, spans = pack_haloed(bufs)
            dev_rows = jnp.asarray(haloed)
            flags = np.asarray(cdc_candidate_flags(dev_rows, cfg.avg_size))
            ends_per = [select_boundaries(unpack_candidates(flags, span), span[2],
                                          cfg.min_size, cfg.max_size) for span in spans]
            starts_g: List[int] = []
            lens: List[int] = []
            for (row0, _, _), ends in zip(spans, ends_per):
                base = row0 * SEG_BYTES
                start = 0
                for end in ends.tolist():
                    starts_g.append(base + start)
                    lens.append(end - start)
                    start = end
            fps = cdc_chunk_fingerprints(dev_rows, starts_g, lens, cfg.max_size)
        else:
            if backend == "scalar":
                ends_per = [chunk_boundaries_scalar(b, cfg.min_size, cfg.avg_size,
                                                    cfg.max_size) for b in bufs]
            else:
                ends_per = [select_boundaries(_candidates_numpy(b, cfg.avg_size), b.size,
                                              cfg.min_size, cfg.max_size) for b in bufs]
            mat, lens_arr = _chunk_matrix(bufs, ends_per, cfg.max_size)
            if backend == "scalar":
                # per-chunk hashing (no batching) — the throughput baseline
                from ..kernels.ops import fingerprint_blocks
                fp128 = np.concatenate(
                    [np.asarray(fingerprint_blocks(mat[i:i + 1].view("<u4")))
                     for i in range(mat.shape[0])]
                ) if mat.shape[0] else np.empty((0, 4), dtype=np.uint32)
            else:
                from ..kernels.ops import fingerprint_blocks
                fp128 = np.asarray(fingerprint_blocks(mat.view("<u4"))) \
                    if mat.shape[0] else np.empty((0, 4), dtype=np.uint32)
            fps = chunk_fp64(fp128, lens_arr)

        out: List[Tuple[np.ndarray, np.ndarray]] = []
        pos = 0
        for ends in ends_per:
            c = ends.size
            out.append((ends, fps[pos:pos + c]))
            pos += c
        return out

    # -- engine ingest ------------------------------------------------------

    def batch_from_buffers(self, stream_ids: Sequence[int], buffers,
                           lba_next: Optional[Dict[int, int]] = None,
                           ) -> Tuple[ReplayBatch, np.ndarray]:
        """Chunk buffers into aligned ``ReplayBatch`` columns.

        Each chunk occupies one logical slot: LBAs are per-stream running
        counters (``lba_next`` carries them across calls), so byte streams
        append and never overwrite.  Returns the batch plus the aligned
        chunk-length column for byte-weighted accounting.
        """
        if len(stream_ids) != len(buffers):
            raise ValueError("stream_ids and buffers must align")
        lba_next = lba_next if lba_next is not None else {}
        results = self.chunk_fingerprints_many(buffers)
        streams: List[np.ndarray] = []
        lbas: List[np.ndarray] = []
        fps: List[np.ndarray] = []
        lens: List[np.ndarray] = []
        for sid, (ends, fp) in zip(stream_ids, results):
            c = ends.size
            nxt = lba_next.get(sid, 0)
            streams.append(np.full(c, sid, dtype=np.int32))
            lbas.append(np.arange(nxt, nxt + c, dtype=np.int64))
            lba_next[sid] = nxt + c
            fps.append(fp)
            lens.append(np.diff(ends, prepend=0))
        cat = lambda parts, dt: (np.concatenate(parts) if parts
                                 else np.empty(0, dtype=dt))
        batch = ReplayBatch(cat(streams, np.int32), cat(lbas, np.int64),
                            cat(fps, np.uint64))
        return batch, cat(lens, np.int64)
