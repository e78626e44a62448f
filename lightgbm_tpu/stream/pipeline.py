"""Double-buffered host->device row-block pipeline.

The consumer iterates blocks; the pipeline keeps up to ``prefetch`` blocks
in flight beyond the one being consumed, issuing each ``jax.device_put``
BEFORE the previous block's compute is drained — on TPU the H2D copy of
block k+1 runs behind the histogram/partition pass on block k (async
dispatch), on CPU the same structure degrades to eager copies so tier-1
tests exercise identical ordering/eviction behavior.

Every block is padded to the uniform ``block_rows`` shape (pad rows ride
row-weight 0, so they vanish from every histogram and sum) — one compiled
program shape serves all blocks.  Device-byte accounting
(``PipelineStats``) is the measurement surface for the synthetic-HBM-cap
tests.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterator, NamedTuple, Optional, Sequence

import numpy as np

from ..obs import costs as obs_costs
from ..obs import metrics as obs_metrics
from .host_matrix import HostBinMatrix


@dataclass
class PipelineStats:
    """Cumulative transfer accounting across passes (shared per trainer)."""
    puts: int = 0                  # device_put calls (blocks)
    bytes_h2d: int = 0             # bytes moved host -> device
    peak_block_bytes: int = 0      # max bytes of blocks live at once
    passes: int = 0                # full sweeps over the matrix
    blocks_skipped: int = 0        # blocks never transferred (empty leaves)

    def as_dict(self) -> dict:
        return dict(puts=self.puts, bytes_h2d=self.bytes_h2d,
                    peak_block_bytes=self.peak_block_bytes,
                    passes=self.passes, blocks_skipped=self.blocks_skipped)


class Block(NamedTuple):
    """One in-flight row block."""
    index: int
    rows: int                # actual rows (<= block_rows; rest is padding)
    start: int               # global row offset of the block
    bins: object             # [block_rows, C] device array
    extras: Dict[str, object]   # name -> [block_rows] device array (padded)


class RowBlockPipeline:
    """Bounded-prefetch iterator over a ``HostBinMatrix``'s row blocks.

    ``extras`` are per-row host arrays (float32/int32) sliced, padded and
    device-put alongside each bins block — gradients/hessians/row-weights
    and per-block leaf-index vectors ride here, so ONE put per block moves
    everything the pass consumes.
    """

    def __init__(self, matrix: HostBinMatrix, prefetch: int = 2,
                 stats: Optional[PipelineStats] = None) -> None:
        self.matrix = matrix
        self.prefetch = max(1, int(prefetch))
        self.stats = stats if stats is not None else PipelineStats()
        # process-wide mirrors of the per-trainer PipelineStats, so
        # obs-report sees H2D volume without a handle on the trainer
        self._m_puts = obs_metrics.counter("stream.h2d_puts")
        self._m_bytes = obs_metrics.counter("stream.h2d_bytes")
        self._m_passes = obs_metrics.counter("stream.passes")
        self._m_skipped = obs_metrics.counter("stream.blocks_skipped")
        self._m_peak = obs_metrics.gauge("stream.peak_block_bytes")

    # ------------------------------------------------------------------
    def _put(self, i: int, extras: Dict[str, np.ndarray]) -> Block:
        import jax

        m = self.matrix
        sl = m.block_slice(i)
        rows = sl.stop - sl.start
        pad = m.block_rows - rows
        blk = m.bins[sl]
        if pad:
            blk = np.pad(blk, ((0, pad), (0, 0)))
        dev_extras = {}
        nbytes = blk.nbytes
        for name, arr in extras.items():
            a = arr[sl.start:sl.stop]
            if pad:
                a = np.pad(a, (0, pad))
            d = jax.device_put(a)
            nbytes += a.nbytes
            dev_extras[name] = d
        bins_dev = jax.device_put(blk)
        self.stats.puts += 1
        self.stats.bytes_h2d += nbytes
        self._m_puts.inc()
        self._m_bytes.inc(nbytes)
        # HBM watermark per transfer (local stats read, no sync; {} on CPU)
        obs_costs.record_watermarks("stream")
        return Block(index=i, rows=rows, start=sl.start, bins=bins_dev,
                     extras=dev_extras)

    def blocks(self, extras: Optional[Dict[str, np.ndarray]] = None,
               only: Optional[Sequence[int]] = None) -> Iterator[Block]:
        """Yield blocks in index order with bounded prefetch.

        ``only``: optional block-index subset (sorted) — blocks whose
        target leaf is empty are never transferred at all (the skip is
        recorded, so bench/tests can assert the eviction math).
        """
        extras = extras or {}
        m = self.matrix
        order = list(range(m.num_blocks)) if only is None else sorted(only)
        if only is not None:
            self.stats.blocks_skipped += m.num_blocks - len(order)
            self._m_skipped.inc(m.num_blocks - len(order))
        self.stats.passes += 1
        self._m_passes.inc()
        q: deque = deque()
        nxt = 0
        first = True
        while nxt < len(order) or q:
            # issue the H2D of upcoming blocks BEFORE consuming the oldest:
            # on an async backend these copies overlap the caller's compute.
            # Refill only to `prefetch`: during this refill the CONSUMER
            # still references the previously yielded block (its loop
            # variable is rebound only after next() returns), so total
            # device residency is len(q) + 1 — refilling to prefetch+1 here
            # would transiently pin prefetch+2 blocks, silently overshooting
            # the (prefetch+1)-block budget model of plan_streaming
            while nxt < len(order) and len(q) < self.prefetch:
                q.append(self._put(order[nxt], extras))
                nxt += 1
            per_block = (m.block_nbytes
                         + sum(4 * m.block_rows for _ in extras))
            held = 0 if first else 1          # the consumer-held block
            self.stats.peak_block_bytes = max(
                self.stats.peak_block_bytes, (len(q) + held) * per_block)
            self._m_peak.set_max(self.stats.peak_block_bytes)
            blk = q.popleft()
            first = False
            yield blk
            # the yielded block's device buffers die with the last reference
            # (the consumer drops them when it moves on) — eviction is
            # reference-counted, nothing pins more than prefetch + 1 blocks
            del blk
